"""Runtime flag system.

Parity: /root/reference/paddle/fluid/platform/flags.cc (~40 gflags) +
pybind global_value_getter_setter (fluid.get_flags/set_flags) + the
FLAGS_* env-var init tier (pybind.cc:1484 init_gflags). Flags that
steered CUDA/allocator machinery XLA now owns are accepted for script
compatibility and marked no-op below.
"""
from __future__ import annotations

import os
from typing import Dict, List, Union

# name -> (default, doc). "(no-op)" = subsumed by XLA/JAX.
_DEFS = {
    "FLAGS_check_nan_inf": (False, "scan op outputs for nan/inf "
                            "(reference operator.cc:1032)"),
    "FLAGS_benchmark": (False, "sync + time every op (no-op)"),
    "FLAGS_eager_delete_tensor_gb": (-1.0, "eager var deletion in the "
                                     "interpreter when >= 0; compiled "
                                     "programs rely on XLA buffer "
                                     "liveness instead"),
    "FLAGS_fraction_of_gpu_memory_to_use": (0.92, "allocator fraction "
                                            "(no-op)"),
    "FLAGS_allocator_strategy": ("auto_growth", "allocator choice "
                                 "(no-op)"),
    "FLAGS_cudnn_deterministic": (False, "deterministic conv: maps to "
                                  "XLA deterministic ops"),
    "FLAGS_paddle_num_threads": (1, "CPU math threads (no-op)"),
    "FLAGS_use_mkldnn": (False, "MKLDNN kernels (no-op)"),
    "FLAGS_selected_gpus": ("", "visible devices (use JAX platform env)"),
    "FLAGS_enable_parallel_graph": (False, "executor choice (no-op)"),
    "FLAGS_max_inplace_grad_add": (0, "grad-add inplace (no-op)"),
    "FLAGS_dygraph_lazy": (False, "queue eager dygraph ops and flush "
                           "them as one compiled dispatch per step "
                           "(lazy-tensor mode, dygraph/lazy.py)"),
    "FLAGS_tpu_metrics": (False, "arm the runtime observability layer "
                          "(paddle_tpu/observability: metrics registry "
                          "+ span tracing across every execution "
                          "path). Env alias: PADDLE_TPU_METRICS"),
}

# secondary env names honored at init (the primary is FLAGS_<name>)
_ENV_ALIASES = {
    "FLAGS_tpu_metrics": "PADDLE_TPU_METRICS",
}

_values: Dict[str, object] = {}


def _coerce(default, raw: str):
    if isinstance(default, bool):
        return raw.lower() in ("1", "true", "yes", "on")
    if isinstance(default, int):
        return int(raw)
    if isinstance(default, float):
        return float(raw)
    return raw


def _init_from_env():
    for name, (default, _doc) in _DEFS.items():
        raw = os.environ.get(name)
        if raw is None and name in _ENV_ALIASES:
            raw = os.environ.get(_ENV_ALIASES[name])
        _values[name] = _coerce(default, raw) if raw is not None else default


_init_from_env()


def _norm(name: str) -> str:
    return name if name.startswith("FLAGS_") else "FLAGS_" + name


def get_flags(flags: Union[str, List[str]]):
    """fluid.get_flags (reference pybind global_value_getter_setter)."""
    single = isinstance(flags, str)
    names = [flags] if single else list(flags)
    out = {}
    for n in names:
        key = _norm(n)
        if key not in _values:
            raise ValueError("unknown flag %r" % n)
        out[key] = _values[key]
    return out


def set_flags(flags: Dict[str, object]):
    """fluid.set_flags."""
    for n, v in flags.items():
        key = _norm(n)
        if key not in _values:
            raise ValueError("unknown flag %r" % n)
        default = _DEFS[key][0]
        _values[key] = _coerce(default, v) if isinstance(v, str) else \
            type(default)(v) if not isinstance(default, str) else str(v)
        if key == "FLAGS_tpu_metrics":
            # keep the observability layer's fast-path bool in sync
            from .. import observability

            (observability.enable if _values[key]
             else observability.disable)()


def flag(name: str):
    """Internal fast read."""
    return _values[_norm(name)]
