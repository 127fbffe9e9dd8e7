"""Expert parallelism: switch-routed MoE over a mesh axis.

The reference snapshot has no expert parallelism (SURVEY §2.5 "NOT
present" row); the collective layer here was designed so new mesh axes
drop in, and this module is the EP drop-in, GShard/Switch style:

- top-1 gating with a fixed per-expert capacity (static shapes — XLA
  needs them; overflow tokens are dropped exactly as Switch does);
- dispatch is einsum against a one-hot dispatch mask, then ONE
  ``lax.all_to_all`` over the expert axis moves token slots to the
  devices owning their experts (this is the canonical EP collective —
  not an all_gather: each device keeps only its experts' slots);
- experts run their FFN on local slots; a second all_to_all routes
  results back; the combine weights the outputs by gate probability.

``expert_parallel_moe`` is the collective-level entry (call inside
shard_map with tokens sharded over the axis and one expert group per
device); ``moe_reference`` is the single-device oracle with identical
routing/drop semantics for tests.
"""
from __future__ import annotations

from typing import Optional


def _top1_dispatch(x, gate_w, num_experts, capacity):
    """Returns (dispatch [E, C, T], combine [T, E, C], gate_probs [T])."""
    import jax
    import jax.numpy as jnp

    logits = x @ gate_w                               # [T, E]
    probs = jax.nn.softmax(logits, axis=-1)
    expert = jnp.argmax(probs, axis=-1)               # [T]
    gate = jnp.take_along_axis(probs, expert[:, None], axis=1)[:, 0]
    # position of each token within its expert's queue
    onehot = jax.nn.one_hot(expert, num_experts, dtype=jnp.int32)  # [T,E]
    pos = jnp.cumsum(onehot, axis=0) * onehot - 1      # [T, E], -1 if not
    pos_in_expert = pos.max(axis=1)                    # [T]
    keep = pos_in_expert < capacity
    disp = (jax.nn.one_hot(expert, num_experts, dtype=x.dtype)[:, :, None]
            * jax.nn.one_hot(jnp.clip(pos_in_expert, 0, capacity - 1),
                             capacity, dtype=x.dtype)[:, None, :])
    disp = disp * keep[:, None, None].astype(x.dtype)  # [T, E, C]
    return jnp.swapaxes(disp, 0, 1).swapaxes(1, 2), disp, gate


def expert_parallel_moe(x, gate_w, w_in, w_out, axis_name: str,
                        capacity_factor: float = 1.0,
                        axis_size: Optional[int] = None):
    """Switch-MoE layer inside shard_map.

    Args:
      x: local token shard ``[T_local, D]`` (tokens sharded over
        ``axis_name``).
      gate_w: ``[D, E_total]`` replicated gate weights.
      w_in / w_out: LOCAL expert weights ``[E_local, D, H]`` /
        ``[E_local, H, D]`` (experts sharded over ``axis_name``,
        E_total = E_local * axis_size).
      capacity_factor: per-expert slots per sending device =
        ceil(T_local * cf / E_total).
    """
    import jax
    import jax.numpy as jnp
    from jax import lax

    if axis_size:
        n = int(axis_size)
    else:
        n = jax.lax.axis_size(axis_name)
    T, D = x.shape
    e_local = w_in.shape[0]
    e_total = e_local * n
    capacity = max(1, int(-(-T * capacity_factor // e_total)))  # ceil

    disp_ect, disp_tec, gate = _top1_dispatch(x, gate_w, e_total,
                                              capacity)
    # tokens into per-expert slots: [E_total, C, D]
    slots = jnp.einsum("ect,td->ecd", disp_ect, x)
    # group experts by owning device and all_to_all the device axis:
    # [n, E_local, C, D] local -> receive MY experts' slots from all
    # devices: [n, E_local, C, D] (sender-major)
    slots = slots.reshape(n, e_local, capacity, D)
    slots = lax.all_to_all(slots, axis_name, split_axis=0, concat_axis=0,
                           tiled=False)
    # slots: [n_senders, E_local, C, D] — flatten sender into the slot
    # dim and run the local experts
    h = jnp.einsum("secd,edh->sech", slots, w_in)
    h = jax.nn.relu(h)
    out = jnp.einsum("sech,ehd->secd", h, w_out)
    # route back: inverse all_to_all, then combine
    out = lax.all_to_all(out, axis_name, split_axis=0, concat_axis=0,
                         tiled=False)
    out = out.reshape(e_total, capacity, D)
    y = jnp.einsum("tec,ecd->td", disp_tec, out)
    return y * gate[:, None]


def moe_reference(x, gate_w, w_in_full, w_out_full,
                  capacity_factor: float = 1.0, axis_size: int = 1):
    """Single-device oracle with the same top-1 + capacity semantics.

    w_in_full/w_out_full: ``[E_total, D, H]`` / ``[E_total, H, D]``.
    ``x`` here is the FULL token set processed in per-shard chunks of
    ``T_local = T / axis_size`` so capacity math matches the sharded
    run exactly.
    """
    import jax
    import jax.numpy as jnp

    T, D = x.shape
    e_total = w_in_full.shape[0]
    if T % axis_size:
        raise ValueError(
            "moe_reference: token count %d must divide by axis_size %d "
            "(the sharded run it mirrors requires equal shards)"
            % (T, axis_size))
    t_local = T // axis_size
    outs = []
    for s in range(axis_size):
        xs = x[s * t_local:(s + 1) * t_local]
        capacity = max(1, int(-(-t_local * capacity_factor // e_total)))
        disp_ect, disp_tec, gate = _top1_dispatch(xs, gate_w, e_total,
                                                  capacity)
        slots = jnp.einsum("ect,td->ecd", disp_ect, xs)
        h = jax.nn.relu(jnp.einsum("ecd,edh->ech", slots, w_in_full))
        out = jnp.einsum("ech,ehd->ecd", h, w_out_full)
        y = jnp.einsum("tec,ecd->td", disp_tec, out)
        outs.append(y * gate[:, None])
    return jnp.concatenate(outs, axis=0)
