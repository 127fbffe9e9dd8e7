"""Every Pallas kernel under ops/pallas/ at the shapes its callers use,
as (name, function, argument specs) — for checking WITHOUT a chip that
the TPU compiler still accepts them.

``tests/test_chip_smoke.py`` uses this two ways:

- in-process, cross-lowering each case with
  ``lowering_platforms=("tpu",)`` (catches a Mosaic-lowering break);
- as a script (``python tests/tpu_kernel_cases.py``), compiling each
  case for a compile-only v5e topology — libtpu's real Mosaic and XLA
  TPU back ends run on this CPU host, so a scoped-VMEM overflow or a
  layout Mosaic refuses shows up here too. Run in a process of its own:
  it loads libtpu.

Numerics are the chip's to check (chip_smoke.py's kernels phase).
"""
from __future__ import annotations

import importlib
import sys

import jax
import jax.numpy as jnp

bf16, f32, i32 = jnp.bfloat16, jnp.float32, jnp.int32


def cases():
    fa = importlib.import_module("paddle_tpu.ops.pallas.flash_attention")
    fo = importlib.import_module("paddle_tpu.ops.pallas.fused_optimizer")
    pa = importlib.import_module("paddle_tpu.ops.pallas.paged_attention")
    cv = importlib.import_module("paddle_tpu.ops.pallas.conv")

    # flash fwd + dQ + dK/dV, causal, the gpt_long shape
    def flash(q, k, v):
        def loss(q, k, v):
            return jnp.sum(fa._flash(q, k, v, True, 0.125, 512, 1024,
                                     False).astype(f32))
        return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

    qkv = ((2, 16, 4096, 64), bf16)
    yield "flash_causal_s4096", flash, [qkv, qkv, qkv]

    # masked flash fwd+bwd, the transformer_wmt shape: the whole
    # [B*H, 1] lengths operand rides in SMEM (512 rows here)
    def masked(q, k, v, lengths):
        def loss(q, k, v):
            return jnp.sum(fa._flash_masked(
                q, k, v, lengths, False, 0.125, 256, 256,
                False).astype(f32))
        return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

    qkv = ((64, 8, 256, 64), bf16)
    yield "flash_masked_b64_s256", masked, [qkv, qkv, qkv, ((64,), i32)]

    # fused optimizer over a BERT-base-sized flat buffer: adam streams
    # 4 inputs + 3 outputs of 2048x128 f32, double-buffered ~14 MiB of
    # the 16 MiB scoped VMEM
    n = (110_000_000 // fo.LANE_PAD) * fo.LANE_PAD
    for op_type in ("adam", "momentum"):
        n_state = fo._n_states(op_type)

        def update(p, g, lr, sa, sb, b1, b2, op_type=op_type,
                   n_state=n_state):
            adam = n_state == 2
            scalars = [lr.reshape(1)] + (
                [b1.reshape(1), b2.reshape(1)] if adam else [])
            return fo._pallas_update(
                op_type, {}, p, g, scalars, sa, sb if adam else None,
                n_state, adam, interpret=False)

        flat, scalar = ((n,), f32), ((), f32)
        yield ("fused_%s_110M" % op_type, update,
               [flat, flat, scalar, flat, flat, scalar, scalar])

    # paged attention at the decode engine's geometry
    def paged(q, k_arena, v_arena, tables, lens):
        return pa._paged_pallas(q, k_arena, v_arena, tables, lens,
                                block_tokens=16, scale=8.0 ** -0.5,
                                interpret=False)

    arena = ((128, 16, 2, 8), f32)
    yield "paged_b8_h2_d8", paged, [((8, 2, 8), f32), arena, arena,
                                    ((8, 5), i32), ((8,), i32)]

    # conv: one 1x1 and one 3x3 ResNet stage-2 shape
    for name, w_shape, pad in (("conv_1x1", (1, 1, 128, 512), 0),
                               ("conv_3x3", (3, 3, 128, 128), 1)):
        def conv(x, w, pad=pad):
            return cv.conv2d_bn_act(x, w, stride=1, padding=pad,
                                    relu=True, interpret=False)

        yield name, conv, [((8, 28, 28, 128), bf16), (w_shape, bf16)]


def compile_all_for_v5e() -> int:
    """Compile every case for a compile-only v5e topology; print one
    OK/FAIL line each. Exit codes: 0 all compiled, 1 a kernel was
    refused, 3 no TPU compiler could be set up on this host."""
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(topology_name="v5e:2x2",
                                            platform="tpu")
    except Exception as e:  # noqa: BLE001 — reported, exit code 3
        print("NO_TPU_COMPILER %r" % (e,))
        return 3
    sharding = SingleDeviceSharding(topo.devices[0])
    failed = 0
    for name, fn, specs in cases():
        args = [jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)
                for shape, dtype in specs]
        try:
            compiled = jax.jit(fn).lower(*args).compile()
        except Exception as e:  # noqa: BLE001 — each case is reported
            failed += 1
            print("FAIL %s %s: %s" % (name, type(e).__name__,
                                      str(e)[:800].replace("\n", " | ")))
            continue
        print("OK %s mosaic_calls=%d"
              % (name, compiled.as_text().count("tpu_custom_call")))
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(compile_all_for_v5e())
