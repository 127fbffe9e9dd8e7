"""Device-memory facade.

Reference counterpart: paddle/fluid/memory/ — ``memory::Alloc`` behind
an ``AllocatorFacade`` with strategies selected by
``FLAGS_allocator_strategy`` and sized by
``FLAGS_fraction_of_gpu_memory_to_use`` (allocation/
allocator_facade.cc, allocator_strategy.cc:27-38). On TPU the physical
allocator belongs to PJRT/XLA (BFC under the hood), so the facade's job
is the same CONTROL SURFACE over that allocator rather than a
reimplementation:

- ``configure_allocator()`` maps the reference flags onto the XLA
  client knobs (XLA_PYTHON_CLIENT_MEM_FRACTION /
  XLA_PYTHON_CLIENT_PREALLOCATE / _ALLOCATOR) — effective when called
  before the first backend touch, exactly like the reference reads its
  gflags at init;
- ``alloc`` / ``Alloc`` hands out device buffers through the facade
  (``memory::Alloc(place, size)`` parity: a raw byte buffer);
- ``memory_stats`` / ``memory_usage`` expose the live allocator
  counters (the stats surface the reference keeps in
  memory/stats.h), with zeros where a backend (the CPU one) publishes
  none; a backend that fails the query raises.
"""
from __future__ import annotations

import os
from typing import Dict, Optional

__all__ = ["configure_allocator", "alloc", "Alloc", "memory_stats",
           "memory_usage", "release_all"]


def configure_allocator(fraction: Optional[float] = None,
                        strategy: Optional[str] = None,
                        preallocate: Optional[bool] = None) -> Dict:
    """Apply allocator knobs (reference FLAGS_fraction_of_gpu_memory_
    to_use / FLAGS_allocator_strategy) to the XLA client.

    Must run before the first jax backend touch to take effect — the
    same contract as the reference's init-time gflag read. Values
    default from the FLAGS_ registry. Returns the applied env map.
    """
    from .flags import get_flags

    if fraction is None:
        fraction = get_flags("FLAGS_fraction_of_gpu_memory_to_use")[
            "FLAGS_fraction_of_gpu_memory_to_use"]
    if strategy is None:
        strategy = get_flags("FLAGS_allocator_strategy")[
            "FLAGS_allocator_strategy"]
    applied = {}
    applied["XLA_PYTHON_CLIENT_MEM_FRACTION"] = str(float(fraction))
    # naive_best_fit ~ grab-the-fraction-up-front (buddy allocator);
    # auto_growth ~ grow on demand
    if preallocate is None:
        preallocate = strategy == "naive_best_fit"
    applied["XLA_PYTHON_CLIENT_PREALLOCATE"] = (
        "true" if preallocate else "false")
    applied["XLA_PYTHON_CLIENT_ALLOCATOR"] = (
        "default" if strategy == "naive_best_fit" else "bfc")
    os.environ.update(applied)
    return applied


def alloc(place, size_bytes: int):
    """``memory::Alloc(place, size)`` parity: a device-resident byte
    buffer (uint8 tensor) of the requested size."""
    import jax
    import jax.numpy as jnp

    dev = place.jax_device() if hasattr(place, "jax_device") else place
    return jax.device_put(jnp.zeros((int(size_bytes),), jnp.uint8), dev)


Alloc = alloc


def _device(place=None):
    import jax

    if place is not None and hasattr(place, "jax_device"):
        return place.jax_device()
    return jax.devices()[0]


def memory_stats(place=None) -> Dict:
    """Raw allocator counters from the backend (empty dict when the
    platform publishes none — the CPU backend returns None). A failing
    query propagates: on a TPU an empty answer would read as "nothing
    allocated"."""
    return dict(_device(place).memory_stats() or {})


def memory_usage(place=None) -> Dict[str, int]:
    """Normalized view: allocated / reserved / peak bytes (the stats.h
    surface). When the observability layer is armed, each read also
    refreshes the ``memory.*_bytes`` gauges (live + high-water marks) —
    ``observability.dump()`` pulls through here, so a dump always
    carries current allocator state."""
    s = memory_stats(place)
    usage = {
        "allocated": int(s.get("bytes_in_use", 0)),
        "reserved": int(s.get("bytes_reserved",
                              s.get("bytes_reservable_limit", 0))),
        "peak": int(s.get("peak_bytes_in_use", 0)),
        "limit": int(s.get("bytes_limit", 0)),
    }
    from .. import observability as _obs

    if _obs.enabled():
        for k, v in usage.items():
            _obs.set_gauge("memory.%s_bytes" % k, v)
    return usage


def release_all(place=None) -> None:
    """Facade Release parity. XLA owns the device arena and exposes no
    targeted free-cached-blocks call, so this is a documented no-op —
    buffers return to the arena when their arrays die. (Deliberately
    NOT jax.clear_caches(): that frees no device memory and would force
    every compiled program to retrace.)"""
