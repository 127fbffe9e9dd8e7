"""The options PR 30 retired stay retired: a name that is set in the
environment is simply not read, so no source file may mention it."""
import os

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
RETIRED = ["PADDLE_TPU_FUSED_OPTIMIZER", "PADDLE_TPU_FUSED_EPILOGUE",
           "PADDLE_TPU_ASYNC_FEED", "FLAGS_use_pallas_conv"]


def _sources():
    for top in ("paddle_tpu", "tools", "ci"):
        for folder, _dirs, files in os.walk(os.path.join(ROOT, top)):
            for name in files:
                if name.endswith((".py", ".sh")):
                    yield os.path.join(folder, name)
    yield os.path.join(ROOT, "chip_smoke.py")
    yield os.path.join(ROOT, "bench.py")


@pytest.mark.parametrize("name", RETIRED)
def test_name_is_gone(name):
    # flags are read both as FLAGS_<x> and through flag("<x>")
    needles = {name, name.replace("FLAGS_", "")}
    hits = []
    for path in _sources():
        with open(path, encoding="utf-8") as f:
            text = f.read()
        hits += [os.path.relpath(path, ROOT)
                 for needle in needles if needle in text]
    assert not hits, hits


def test_no_environment_name_selects_the_scans_form():
    """Which form of ``ssd_chunk_scan`` runs is read from the platform and
    the operands (``ssm_ops.scan_path``): neither the op's file nor the
    kernels' reads the environment or a flag, and the op and its layer have
    no attr or argument beyond the ones they had."""
    import inspect

    import paddle_tpu as fluid
    from paddle_tpu.core.registry import OpInfoMap

    for path in ("paddle_tpu/ops/ssm_ops.py",
                 "paddle_tpu/ops/pallas/ssd_scan.py"):
        with open(os.path.join(ROOT, path), encoding="utf-8") as f:
            text = f.read()
        for needle in ("environ", "getenv", "PADDLE_", "FLAGS_", "flag("):
            assert needle not in text, (path, needle)
    for name in ("ssd_chunk_scan", "ssd_chunk_scan_grad"):
        assert set(OpInfoMap.instance().get(name).attrs) == {"chunk"}, name
    assert list(inspect.signature(
        fluid.layers.ssd_chunk_scan).parameters) == [
            "x", "dt", "A", "B", "C", "D", "dt_bias", "chunk"]
