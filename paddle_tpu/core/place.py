"""Device places.

TPU-native analogue of the reference's tagged place variant
(/root/reference/paddle/fluid/platform/place.h). Instead of a C++ boost
variant dispatched per kernel, a Place here simply selects the JAX device
an op's arrays live on; XLA owns streams/layout so no DeviceContext pool
is needed.
"""
from __future__ import annotations

import functools

from .enforce import OutOfRangeError, PreconditionNotMetError


class Place:
    """Base place. Equality is (kind, device_id)."""

    kind = "undefined"

    def __init__(self, device_id: int = 0):
        self._device_id = int(device_id)

    def get_device_id(self) -> int:
        return self._device_id

    # JAX integration -----------------------------------------------------
    @property
    def jax_platform(self) -> str:
        raise NotImplementedError

    def jax_device(self):
        """Resolve to a concrete jax.Device (lazily; import-cheap)."""
        import jax

        devs = _devices_for_platform(self.jax_platform)
        if not devs:
            raise PreconditionNotMetError(
                "No %s device available (jax backends: %s)"
                % (self.jax_platform, [d.platform for d in jax.devices()])
            )
        if self._device_id >= len(devs):
            # no modulo wrap: Place(5) on a four-chip host silently
            # piling onto chip 1 hides a mis-sized job
            raise OutOfRangeError(
                "%r: this process has %d %s device(s)"
                % (self, len(devs), devs[0].platform))
        return devs[self._device_id]

    def __eq__(self, other):
        return (
            isinstance(other, Place)
            and self.kind == other.kind
            and self._device_id == other._device_id
        )

    def __hash__(self):
        return hash((self.kind, self._device_id))

    def __repr__(self):
        return "%s(%d)" % (type(self).__name__, self._device_id)


@functools.lru_cache(maxsize=None)
def _devices_for_platform(platform: str):
    """THIS process's devices only: under multi-process jax the global
    list includes other processes' (non-addressable) devices, and
    placing computation there produces arrays the process cannot read
    (every process's Place(0) must be its own first local chip)."""
    import jax

    if platform == "any_accelerator":
        # Prefer the default backend's devices (TPU if present).
        return tuple(jax.local_devices())
    try:
        # backend= keeps non-default backends reachable (CPUPlace on a
        # TPU host); plain local_devices() lists only the default one
        return tuple(jax.local_devices(backend=platform))
    except RuntimeError:
        return ()


class CPUPlace(Place):
    kind = "cpu"

    def __init__(self):
        super().__init__(0)

    @property
    def jax_platform(self):
        return "cpu"


class TPUPlace(Place):
    """The accelerator place: device ``i`` of the default JAX backend.

    It resolves to a CPU device only in a process explicitly started on
    the CPU platform (``JAX_PLATFORMS=cpu`` — the test suite's virtual
    host mesh). Where JAX merely FOUND no accelerator and fell back to
    the CPU, it raises: a training job must not run on the host while
    its logs say TPUPlace.
    """

    kind = "tpu"

    @property
    def jax_platform(self):
        return "any_accelerator"

    def jax_device(self):
        import jax

        dev = super().jax_device()
        if dev.platform == "cpu" and not str(
                jax.config.jax_platforms or "").startswith("cpu"):
            raise PreconditionNotMetError(
                "%r: JAX found no accelerator (default backend is cpu). "
                "Set JAX_PLATFORMS=cpu to run on the host on purpose, "
                "or use CPUPlace()." % (self,))
        return dev


# The reference exposes CUDAPlace; scripts being migrated may still name it.
# It is an alias of the accelerator place here.
CUDAPlace = TPUPlace


class CUDAPinnedPlace(CPUPlace):
    kind = "cpu_pinned"


def is_cpu_place(p):
    return isinstance(p, CPUPlace)


def is_tpu_place(p):
    return isinstance(p, TPUPlace)


def _current_expected_place_default():
    import jax

    dev = jax.devices()[0]
    return CPUPlace() if dev.platform == "cpu" else TPUPlace(0)


def compute_platform() -> str:
    """Platform of the device the computation being traced or run will
    execute on: the ``jax.default_device`` an executor entered for its
    Place, else the default backend. The Pallas kernels choose between
    Mosaic, interpret mode and the XLA/dense math by THIS, not by
    ``jax.default_backend()`` — an ``Executor(CPUPlace())`` on a TPU
    host must not emit a Mosaic call into a CPU computation."""
    import jax

    dev = jax.config.jax_default_device
    if dev is None:
        return jax.default_backend()
    return dev if isinstance(dev, str) else dev.platform
