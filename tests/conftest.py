"""Test harness config: force a virtual 8-device CPU platform so mesh /
collective tests run anywhere (SURVEY.md §4: the reference has no fake
device backend and skips multi-GPU tests without hardware — we do better
via XLA host-platform device simulation).

The platform is pinned through the config API as well as by the
``JAX_PLATFORMS=cpu`` the tier-1 command exports, so a bare ``pytest``
on a chip host still runs the suite on the virtual CPU mesh and never
takes the chip. This explicit pin is also what lets ``TPUPlace``
resolve to a CPU device here (core/place.py).
"""
import os

# ISSUE 12: the static IR verifier (paddle_tpu/analysis) is default-OFF
# in prod but forced ON for every test run — each rewrite pass, engine
# first-run, lazy flush, and model load re-verifies under the suite.
# Explicitly exporting PADDLE_TPU_VERIFY_IR=0 still wins (overhead
# gates measure the default-off path).
os.environ.setdefault("PADDLE_TPU_VERIFY_IR", "1")

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")


def pytest_configure(config):
    # tier-1 (ROADMAP) runs `-m 'not slow'` under a hard wall-clock
    # budget; the heavyweight end-to-end tests opt out of it and run
    # in the full CI suite (ci/check.sh gate 8) instead
    config.addinivalue_line(
        "markers", "slow: excluded from the tier-1 budgeted run")


# Files whose cases compile unrolled interpret-mode kernels keep two or
# three cores busy for a minute. The parameter-server failover tests
# (test_fault_tolerance.py, test_survivable_ps.py) hold wall-clock
# deadlines and lose them beside such load under `-n 6`, so these files
# are handed out last, when most workers have run dry. Every worker
# sorts alike: the order stays deterministic.
_COLLECTED_LAST = ("test_flash_tokens.py",)


def pytest_collection_modifyitems(items):
    items.sort(key=lambda item: item.path.name in _COLLECTED_LAST)
