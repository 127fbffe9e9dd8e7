"""Dygraph DataParallel.

Parity: /root/reference/python/paddle/fluid/dygraph/parallel.py
(DataParallel :223, scale_loss :290, apply_collective_grads :382) and the
C++ NCCLParallelContext (imperative/nccl_context.cc:117).

TPU-native: rank/world come from jax.distributed (coordination service
over DCN — replacing the TCP ncclUniqueId broadcast); gradient allreduce
is a psum across processes expressed with jax collectives when a
multiprocess mesh is live, or an identity on world=1. Gradients are
coalesced before the allreduce, mirroring the reference's
_coalesce_tensors.
"""
from __future__ import annotations

import os

import numpy as np

from .layers import Layer
from .varbase import VarBase

__all__ = ["prepare_context", "ParallelEnv", "DataParallel", "Env"]


class ParallelEnv:
    def __init__(self):
        self._nranks = int(os.getenv("PADDLE_TRAINERS_NUM", "1"))
        self._local_rank = int(os.getenv("PADDLE_TRAINER_ID", "0"))
        self._dev_id = int(os.getenv("FLAGS_selected_tpus",
                                     os.getenv("FLAGS_selected_gpus", "0")))
        eps = os.getenv("PADDLE_TRAINER_ENDPOINTS", "")
        self._trainer_endpoints = eps.split(",") if eps else []
        self._current_endpoint = os.getenv("PADDLE_CURRENT_ENDPOINT", "")

    @property
    def nranks(self):
        return self._nranks

    @property
    def local_rank(self):
        return self._local_rank

    @property
    def dev_id(self):
        return self._dev_id

    @property
    def current_endpoint(self):
        return self._current_endpoint

    @property
    def trainer_endpoints(self):
        return self._trainer_endpoints


Env = ParallelEnv


def prepare_context(strategy=None):
    """Initialize the multi-process context (reference: NCCL id broadcast
    + ncclCommInitRank). Here: jax.distributed.initialize when launched by
    paddle_tpu.distributed.launch / TPU pod runtime."""
    env = ParallelEnv()
    if env.nranks > 1:
        import jax

        coord = env.trainer_endpoints[0] if env.trainer_endpoints else None
        try:
            jax.distributed.initialize(
                coordinator_address=coord,
                num_processes=env.nranks,
                process_id=env.local_rank,
            )
        except (RuntimeError, ValueError):
            pass  # already initialized (or single-host simulation)
    return env


class DataParallel(Layer):
    def __init__(self, layers, strategy=None):
        super().__init__()
        self._layers = layers
        self._strategy = strategy or ParallelEnv()
        nr = getattr(self._strategy, "nranks", None)
        self._nranks = nr if nr is not None else ParallelEnv().nranks

    def forward(self, *inputs, **kwargs):
        return self._layers(*inputs, **kwargs)

    def scale_loss(self, loss):
        if self._nranks <= 1:
            return loss
        from .tracer import current_tracer

        return current_tracer().trace_op(
            "scale", {"X": loss},
            {}, {"scale": 1.0 / self._nranks, "bias": 0.0})["Out"][0]

    @property
    def _sub(self):
        return self._layers

    def parameters(self, include_sublayers=True):
        return self._layers.parameters(include_sublayers)

    def state_dict(self, *args, **kwargs):
        return self._layers.state_dict(*args, **kwargs)

    def set_dict(self, *args, **kwargs):
        return self._layers.set_dict(*args, **kwargs)

    set_state_dict = set_dict

    def apply_collective_grads(self):
        """Coalesce + allreduce gradients across processes (reference
        dygraph/parallel.py:382 _coalesce_tensors + allreduce): all grads
        flatten into ONE buffer (one collective instead of one per
        param), the buffer all-reduces on device, and the slices scatter
        back."""
        if self._nranks <= 1:
            return
        params = [p for p in self.parameters() if p._grad is not None]
        if not params:
            return
        flat = _coalesce([p._grad for p in params])
        summed = _allreduce_across_processes(flat, self._nranks)
        for p, g in zip(params, _split_like(summed,
                                            [p._grad for p in params])):
            p._grad = g


def _coalesce(grads):
    """One flat f32 buffer (mixed grad dtypes upcast for the collective;
    _split_like restores each grad's own dtype — the reference groups
    by dtype instead, one collective per group)."""
    import jax.numpy as jnp

    return jnp.concatenate([g.astype(jnp.float32).ravel() for g in grads])


def _split_like(flat, refs):
    out = []
    off = 0
    for r in refs:
        n = int(np.prod(r.shape)) if r.ndim else 1
        out.append(flat[off:off + n].reshape(r.shape).astype(r.dtype))
        off += n
    return out


def _allreduce_across_processes(flat, nranks):
    """On-device cross-process sum: the local buffer becomes one shard
    of a global [nranks, n] array (one device per process); a psum under
    shard_map makes XLA insert the all-reduce over ICI/DCN (Gloo on the
    CPU backend). The output keeps the P('dp') sharding — every row
    holds the sum, so each process reads its OWN local shard and no
    cross-process gather of a replicated array is ever needed (a
    replicated out_sharding would be non-fully-addressable under
    multi-process jax and unreadable locally). Host-gather fallback only
    if global-array construction is unsupported by the runtime."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    try:
        devs = np.array(jax.devices()[:nranks])
        mesh = Mesh(devs, ("dp",))
        dist = NamedSharding(mesh, P("dp"))
        local = jnp.asarray(flat)[None, :]
        garr = jax.make_array_from_single_device_arrays(
            (nranks,) + flat.shape, dist,
            [jax.device_put(local, jax.local_devices()[0])])
        psummed = jax.shard_map(
            lambda x: jax.lax.psum(x, "dp"), mesh=mesh,
            in_specs=P("dp"), out_specs=P("dp"), check_vma=False)
        out = jax.jit(psummed)(garr)
        [shard] = [s.data for s in out.addressable_shards]
        return shard[0]
    except Exception as e:
        import warnings

        warnings.warn(
            "on-device cross-process allreduce unavailable (%s); falling "
            "back to host-gather — expect much slower DP steps" % e)
        try:
            from jax.experimental import multihost_utils

            gathered = multihost_utils.process_allgather(flat,
                                                         tiled=True)
            return gathered.reshape(nranks, -1).sum(axis=0)
        except Exception:
            # process_allgather is itself a jitted cross-process
            # computation, so a backend that refused the psum above
            # (jaxlib's CPU backend: "Multiprocess computations
            # aren't implemented") refuses this too
            return _kv_allreduce(np.asarray(flat), nranks)


_kv_allreduce_seq = [0]


def _kv_allreduce(flat: np.ndarray, nranks: int) -> np.ndarray:
    """Last-resort cross-process sum over the jax.distributed
    coordinator's key-value store: every rank publishes its buffer,
    reads every peer's, sums on host. No XLA computation crosses a
    process boundary, so this works where the CPU backend refuses
    multiprocess programs outright. Correctness leans on the DP
    contract that every rank traces the SAME program — collective
    call N on rank 0 is collective call N everywhere, so a per-call
    sequence number keys the exchange."""
    from jax._src import distributed

    client = distributed.global_state.client
    if client is None:
        raise RuntimeError(
            "cross-process allreduce needs jax.distributed to be "
            "initialized (no coordinator client)")
    rank = int(distributed.global_state.process_id or 0)
    seq = _kv_allreduce_seq[0]
    _kv_allreduce_seq[0] += 1
    base = "paddle_tpu/allreduce/%d" % seq
    flat = np.ascontiguousarray(flat)
    client.key_value_set_bytes("%s/%d" % (base, rank), flat.tobytes())
    out = np.zeros_like(flat)
    for r in range(nranks):
        raw = client.blocking_key_value_get_bytes(
            "%s/%d" % (base, r), 120_000)
        out += np.frombuffer(raw, dtype=flat.dtype).reshape(flat.shape)
    # every rank holds the sum before anyone deletes, or a slow
    # reader races a cleaned-up key
    client.wait_at_barrier("%s/read" % base, 120_000)
    if rank == 0:
        for r in range(nranks):
            try:
                client.key_value_delete("%s/%d" % (base, r))
            except RuntimeError:
                # XlaRuntimeError from the coordinator: stale keys
                # only cost coordinator memory, never the allreduce
                pass
    return out
