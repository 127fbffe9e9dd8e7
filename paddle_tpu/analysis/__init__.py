"""Static analysis over the Program/Block/Operator/Variable IR.

Three analyses (ISSUE 12), the roles graph validators and
torch.distributed's debug-level checks play in production stacks:

- **Well-formedness verification** (``verifier.verify_program``):
  def-before-use per block, no dangling var references, op slot-arity /
  attr-type / dtype consistency against the op registry, duplicate-write
  aliasing hazards, unreachable-op and dead-var detection. Violations
  surface as structured ``IRVerificationError``s naming the op, the
  block, and the violated invariant.

- **Collective-consistency checking** (``collective.
  check_collective_schedule``): the static sequence of collective ops a
  rank would issue (kind, ring/axis, payload numel + dtype, bucket id)
  is extracted per program and cross-checked across ranks — a
  mismatched order/kind is a would-DEADLOCK finding, a mismatched
  payload/dtype a would-CORRUPT finding, and a collective under a
  conditional sub-block is divergence waiting to happen. The engine's
  first-run path and ``bench.py --multichip`` run the single-program
  form; the cross-rank form takes one schedule (or program) per rank.

- **Rewrite-invariant contracts** (``contracts``): each program-rewrite
  pass declares pre/post contracts (bucket pass: same multiset of
  reduced grads + consumer-barrier ordering preserved; sharded update:
  every spared param still sees its reduced grad). The
  ``checked_rewrite`` decorator snapshots the contract state before the
  pass, checks it after, and re-verifies the whole program — a future
  pass author gets invariant checking for free by decorating their
  pass.

Gate: ``PADDLE_TPU_VERIFY_IR`` (default OFF in prod — the disabled hook
is one env read + a branch, budgeted <1us by the CI overhead gate;
forced ON for the test suite via tests/conftest.py and for CI gates via
ci/check.sh).
"""
from __future__ import annotations

import os
from typing import Optional, Sequence

from .verifier import (Finding, IRVerificationError,  # noqa: F401
                       verify_lazy_graph, verify_program)
from .collective import (CollectiveMismatchError,  # noqa: F401
                         CollectiveSig, check_collective_schedule,
                         check_cross_rank, extract_collective_schedule,
                         schedule_record)
from .contracts import (ContractViolation, RewriteContract,  # noqa: F401
                        check_pipeline_split, checked_rewrite,
                        register_contract)

__all__ = [
    "verify_enabled", "maybe_verify_program", "verify_program",
    "verify_lazy_graph", "Finding", "IRVerificationError",
    "CollectiveMismatchError", "CollectiveSig",
    "check_collective_schedule", "check_cross_rank",
    "extract_collective_schedule", "schedule_record",
    "ContractViolation", "RewriteContract", "checked_rewrite",
    "register_contract", "check_pipeline_split",
]

_TRUTHY = ("1", "true", "yes", "on")

# Fast path: probe os.environ's backing dict directly. The full
# os.environ.get goes through the _Environ mapping (encodekey + method
# dispatch, ~0.5-1.5us under load) — too close to the <1us/program-run
# budget ci gate 4 enforces. The backing dict probe is ~50ns and stays
# correct under monkeypatch.setenv/putenv (both write through
# __setitem__ into _data). Falls back to the mapping on interpreters
# without the CPython _Environ internals.
try:
    _ENV_DATA = os.environ._data
    _ENV_KEY = os.environ.encodekey("PADDLE_TPU_VERIFY_IR")
except Exception:  # non-CPython / exotic platform
    _ENV_DATA = None
    _ENV_KEY = None


def verify_enabled() -> bool:
    """One dict probe + a membership test — the whole disabled-path
    cost of every verify hook (ci gate 4 budgets it under 1us)."""
    if _ENV_DATA is not None:
        raw = _ENV_DATA.get(_ENV_KEY)
    else:
        raw = os.environ.get("PADDLE_TPU_VERIFY_IR")
    if raw is None:
        return False
    if isinstance(raw, bytes):
        raw = raw.decode("utf-8", "ignore")
    return raw.strip().lower() in _TRUTHY


def maybe_verify_program(program, where: str,
                         fetch_names: Optional[Sequence[str]] = None,
                         nranks: Optional[int] = None,
                         scope=None, recheck_shapes: bool = False):
    """The hook rewrite passes / engines / loaders call: no-op unless
    ``PADDLE_TPU_VERIFY_IR`` is set, else full well-formedness
    verification (raising ``IRVerificationError`` on error-severity
    findings) plus the single-program collective-schedule check.
    Returns the finding list (errors raise before returning)."""
    # enabled-check first: the disabled path costs exactly one env read
    # + a branch whatever the arguments (ci gate 4 benches this)
    if not verify_enabled() or program is None:
        return None
    findings = verify_program(program, fetch_names=fetch_names,
                              pass_name=where,
                              recheck_shapes=recheck_shapes)
    check_collective_schedule(program, nranks=nranks, where=where,
                              scope=scope)
    from .. import observability as _obs

    _obs.inc("analysis.verify_runs", where=where)
    return findings
