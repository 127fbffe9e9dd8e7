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
