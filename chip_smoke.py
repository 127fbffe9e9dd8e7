#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the tree still starts on the chip.

Drives the Program -> Executor training path once on the TPU this
process finds, through the entry points a user calls
(``fluid.Program``/``program_guard``, ``paddle_tpu.models``,
``contrib.mixed_precision.decorate``, ``fluid.Executor(fluid.TPUPlace(0))``,
``fluid.CompiledProgram(...).with_data_parallel``), at the full width
of BERT-base with random weights made from a seed, and checks what
comes out against the repo's own references. ONE process, no children:
a chip belongs to one process at a time.

    python3 chip_smoke.py                 # on a TPU host; exits non-zero
                                          # before any phase without one
    JAX_PLATFORMS=cpu python3 chip_smoke.py --rehearse-cpu
                                          # tiny sizes, interpret-mode
                                          # kernels; prints platform cpu

Phases (each prints one JSON line; every failed assertion or exception
is fatal — nothing is caught and turned into a warning):

  device   the first device is a TPU; kind and count reported
  train    BERT-base pretraining, bf16 AMP, b32 x s128: startup + steps
           on one fixed batch fed from host numpy
  kernels  every kernel under ops/pallas/ compiled (Mosaic, not
           interpret) at the shapes its callers use, against its own
           reference under ``jax.default_matmul_precision("highest")``
  dp4      (>= 4 devices) the BERT-base program data-parallel over four
           chips, global batch 128, against the one-chip loss

The set-up seconds and step milliseconds on the phase lines are SMOKE
figures (a handful of steps, one window): evidence that the step runs
and how long a cold or warm start takes, not benchmark measurements.

The last stdout line is the result the driver reads, with exactly these
keys: ``{"ok": true, "device": {"platform": "tpu", "kind": "...",
"count": 1}}``. The run's wall seconds and XLA build totals are on the
``summary`` phase line before it.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

import numpy as np

SEED = 2024

# one model, two sizes: the repo's own BERT-base config on the chip, a
# toy of the same shape for the CPU rehearsal
FULL = {
    "bert": dict(layers=12, d_model=768, heads=12, d_ff=3072, vocab=30522,
                 batch=32, seq=128, masked=20, steps=6),
    "gpt": dict(layers=2, d_model=1024, heads=16, vocab=8192, batch=2,
                seq=4096, steps=3),
    "flash": dict(b=1, h=4, s=4096, d=64),
    "masked": dict(b=64, h=8, s=256, d=64),
    "short": dict(b=32, h=12, s=512, d=64),
    "short_dp": dict(b=128, h=12, s=128, d=64),
    "scan": dict(b=1, t=2048, h=16, p=64, g=2, n=128),
    "delta": dict(b=1, t=2048, h=8, d=128),
    "selected": dict(b=1, h=32, kv=4, s=2048, d=128, keep=512),
    "latent": dict(b=1, h=32, s=4096, d=192, dv=128),
    "mhc": dict(b=1, n=4, t=4096, c=3584),
    "index": dict(r=512, s=2048, h=16, d=64),
    "decode": dict(streams=5, max_tokens=12),
    "dp_steps": 4,
}
REHEARSAL = {
    "bert": dict(layers=2, d_model=64, heads=4, d_ff=128, vocab=512,
                 batch=4, seq=16, masked=4, steps=6),
    "gpt": dict(layers=1, d_model=64, heads=4, vocab=256, batch=1,
                seq=256, steps=2),
    "flash": dict(b=1, h=2, s=256, d=16),
    "masked": dict(b=2, h=2, s=128, d=16),
    "short": dict(b=2, h=2, s=128, d=64),
    "short_dp": dict(b=4, h=4, s=128, d=32),
    "scan": dict(b=1, t=256, h=4, p=64, g=2, n=128),
    "delta": dict(b=1, t=128, h=4, d=128),
    "selected": dict(b=1, h=4, kv=2, s=256, d=16, keep=64),
    "latent": dict(b=1, h=2, s=256, d=24, dv=16),
    "mhc": dict(b=1, n=4, t=128, c=128),
    "index": dict(r=32, s=64, h=2, d=8),
    "decode": dict(streams=3, max_tokens=6),
    "dp_steps": 2,
}


class _XlaCompiles:
    """Every executable JAX builds in this process, from jax.monitoring:
    ``builds`` counts backend compiles (served from the persistent
    cache or not), ``cache_hits`` those the cache served, ``seconds``
    the time they took. The executor's own counters see fresh jit
    closures and retraces; an XLA recompile for a changed input
    sharding shows only here."""

    def __init__(self):
        import jax

        self.builds = self.cache_hits = 0
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._timed)
        jax.monitoring.register_event_listener(self._event)

    def _timed(self, event, seconds, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.builds += 1
            self.seconds += seconds

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def snapshot(self):
        return {"xla_builds": self.builds, "cache_hits": self.cache_hits,
                "xla_build_s": round(self.seconds, 2)}


def _since(now, then):
    return {k: round(now[k] - then[k], 2) for k in now}


def _emit(phase, device, **fields):
    rec = {"phase": phase, "smoke": True}
    rec.update(device)
    rec.update(fields)
    print(json.dumps(rec), flush=True)


def _mosaic_calls(lowered) -> int:
    return lowered.as_text().count("tpu_custom_call")


def _rel_err(got, ref) -> float:
    """max |got - ref| over max |ref| — one number per tensor, robust
    to the near-zero entries a plain rtol chokes on."""
    got = np.asarray(got, np.float32)
    ref = np.asarray(ref, np.float32)
    assert got.shape == ref.shape, (got.shape, ref.shape)
    assert np.all(np.isfinite(got)), "non-finite kernel output"
    return float(np.max(np.abs(got - ref)) / max(np.max(np.abs(ref)), 1e-30))


# ---------------------------------------------------------------------------
# programs
# ---------------------------------------------------------------------------


def build_bert(cfg, batch):
    """BERT-base masked-LM pretraining step: models.bert_base_pretrain
    + softmax-CE over the masked positions, Adam 1e-4, bf16 AMP."""
    import paddle_tpu as fluid
    from paddle_tpu import models
    from paddle_tpu.contrib import mixed_precision as mp

    T, M, V = cfg["seq"], cfg["masked"], cfg["vocab"]
    main, startup = fluid.Program(), fluid.Program()
    startup.random_seed = SEED
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        src = fluid.data(name="src", shape=[batch, T], dtype="int64")
        pos = fluid.data(name="pos", shape=[batch, T], dtype="int64")
        mpos = fluid.data(name="mpos", shape=[batch, M], dtype="int64")
        labels = fluid.data(name="labels", shape=[batch, M, 1],
                            dtype="int64")
        logits = models.bert_base_pretrain(
            src, pos, mpos, vocab_size=V, max_len=T,
            num_layers=cfg["layers"], num_heads=cfg["heads"],
            d_model=cfg["d_model"], d_ff=cfg["d_ff"])
        loss = fluid.layers.mean(fluid.layers.softmax_with_cross_entropy(
            fluid.layers.reshape(logits, [batch * M, V]),
            fluid.layers.reshape(labels, [batch * M, 1])))
        mp.decorate(fluid.optimizer.AdamOptimizer(1e-4)).minimize(loss)
    return main, startup, loss


def bert_feed(cfg, batch):
    rng = np.random.RandomState(0)
    T, M, V = cfg["seq"], cfg["masked"], cfg["vocab"]
    return {
        "src": rng.randint(0, V, (batch, T)).astype("int64"),
        "pos": np.tile(np.arange(T), (batch, 1)).astype("int64"),
        "mpos": rng.randint(0, T, (batch, M)).astype("int64"),
        "labels": rng.randint(0, V, (batch, M, 1)).astype("int64"),
    }


def build_gpt_long(cfg):
    """The gpt_long cell: a small causal LM at long sequence whose
    attention is the flash_attention op (fwd + dQ + dK/dV kernels)."""
    import paddle_tpu as fluid
    from paddle_tpu import layers
    from paddle_tpu.contrib import mixed_precision as mp

    B, S, D, H, V = (cfg["batch"], cfg["seq"], cfg["d_model"],
                     cfg["heads"], cfg["vocab"])
    main, startup = fluid.Program(), fluid.Program()
    startup.random_seed = SEED
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        ids = fluid.data(name="ids", shape=[B, S], dtype="int64")
        lbl = fluid.data(name="lbl", shape=[B * S, 1], dtype="int64")
        x = layers.embedding(ids, size=(V, D))

        def heads(t):
            t = layers.reshape(t, [B, S, H, D // H])
            return layers.transpose(t, [0, 2, 1, 3])

        for _ in range(cfg["layers"]):
            h = layers.layer_norm(x)
            q, k, v = (layers.fc(h, D, num_flatten_dims=2)
                       for _ in range(3))
            ctx = layers.flash_attention(heads(q), heads(k), heads(v),
                                         causal=True)
            ctx = layers.reshape(layers.transpose(ctx, [0, 2, 1, 3]),
                                 [B, S, D])
            x = x + layers.fc(ctx, D, num_flatten_dims=2)
            m = layers.fc(layers.layer_norm(x), D * 4, num_flatten_dims=2,
                          act="gelu")
            x = x + layers.fc(m, D, num_flatten_dims=2)
        logits = layers.fc(layers.layer_norm(x), V, num_flatten_dims=2)
        loss = layers.mean(layers.softmax_with_cross_entropy(
            layers.reshape(logits, [B * S, V]), lbl))
        mp.decorate(fluid.optimizer.AdamOptimizer(1e-4)).minimize(loss)
    rng = np.random.RandomState(1)
    feed = {"ids": rng.randint(0, V, (B, S)).astype("int64"),
            "lbl": rng.randint(0, V, (B * S, 1)).astype("int64")}
    return main, startup, loss, feed


# ---------------------------------------------------------------------------
# one-device training driver
# ---------------------------------------------------------------------------


def _counters(obs):
    return {
        "compiled": obs.counter_value("executor.steps", path="compiled") or 0,
        "interpreter": obs.counter_value("executor.steps",
                                         path="interpreter") or 0,
        "fallbacks": obs.counter_value("executor.compile_fallbacks") or 0,
        "compiles": obs.counter_value("executor.compiles") or 0,
        "traces": obs.counter_value("executor.jit_traces") or 0,
    }


def train_one_device(main, startup, loss, feed, steps, platform, xla,
                     param_probe=None):
    """startup + ``steps`` steps under Executor(TPUPlace(0)), feeding
    host numpy every step. Returns the loss trajectory, set-up seconds
    (startup + compile + first step), the steady step ms, what XLA
    built, the executor and the scope (kept alive so the caller can
    lower the step), and asserts the path the steps took."""
    import jax

    import paddle_tpu as fluid
    from paddle_tpu import observability as obs

    scope = fluid.Scope()
    exe = fluid.Executor(fluid.TPUPlace(0))
    device = fluid.TPUPlace(0).jax_device()
    losses, step_s = [], []
    x0 = xla.snapshot()
    with fluid.scope_guard(scope):
        t0 = time.perf_counter()
        exe.run(startup)
        c0 = _counters(obs)
        c1 = None
        for _ in range(steps):
            t = time.perf_counter()
            (out,) = exe.run(main, feed=feed, fetch_list=[loss],
                             return_numpy=False)
            jax.block_until_ready(out.array)
            step_s.append(time.perf_counter() - t)
            losses.append(float(np.asarray(out.array).ravel()[0]))
            if c1 is None:
                setup_s = time.perf_counter() - t0
                c1, x1 = _counters(obs), xla.snapshot()
        c_end = _counters(obs)

        # the path every step took
        assert len(losses) == steps
        assert all(np.isfinite(losses)), losses
        assert losses[-1] < losses[0], "loss did not fall: %r" % (losses,)
        assert c_end["compiled"] - c0["compiled"] == steps, (c0, c_end)
        assert c_end["interpreter"] == c0["interpreter"], (c0, c_end)
        assert c_end["fallbacks"] == 0, c_end
        # one compile (and one trace) for the program, none after step 1
        assert c1["compiles"] - c0["compiles"] == 1, (c0, c1)
        assert c1["traces"] - c0["traces"] == 1, (c0, c1)
        assert (c_end["compiles"], c_end["traces"]) == \
            (c1["compiles"], c1["traces"]), (c1, c_end)
        # ... and XLA built no executable after it either
        assert xla.builds == x1["xla_builds"], (x1, xla.snapshot())
        # where the results live
        assert {d.platform for d in out.array.devices()} == {platform}, \
            out.array.devices()
        if param_probe is not None:
            p = scope.find_var(param_probe).raw().array
            assert p.devices() == {device}, (param_probe, p.devices())
    return {"losses": losses, "setup_s": setup_s,
            "step_ms": 1e3 * float(np.mean(step_s[1:])),
            "xla": _since(x1, x0), "exe": exe, "scope": scope}


def _first_param(main):
    return next(n for n, v in main.global_block().vars.items()
                if getattr(v, "persistable", False)
                and type(v).__name__ == "Parameter")


def _peak_bytes(device, platform):
    stats = device.memory_stats() or {}
    peak = int(stats.get("peak_bytes_in_use", 0))
    if platform == "tpu":
        assert peak > 0, "device reports no peak_bytes_in_use: %r" % stats
    return peak


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------


def phase_train(sizes, dev_rec, platform, xla):
    import jax

    import paddle_tpu as fluid
    from paddle_tpu.core.enforce import PreconditionNotMetError

    cfg = sizes["bert"]
    feed = bert_feed(cfg, cfg["batch"])
    main, startup, loss = build_bert(cfg, cfg["batch"])
    probe = _first_param(main)
    base = train_one_device(main, startup, loss, feed, cfg["steps"],
                            platform, xla, param_probe=probe)
    peak = _peak_bytes(jax.devices()[0], platform)
    _emit("train", dev_rec, program="bert_base",
          setup_s=round(base["setup_s"], 2),
          step_ms=round(base["step_ms"], 2),
          losses=[round(x, 5) for x in base["losses"]],
          peak_bytes_in_use=peak,
          ops=len(main.global_block().ops), **base["xla"])
    del base["exe"], base["scope"]
    gc.collect()

    # Executor(CPUPlace()) on the chip host: the kernels follow where
    # the computation runs, so a flash_attention program must run as
    # plain XLA on the host — or the place raises
    # its typed error where this process has no CPU backend
    try:
        fluid.CPUPlace().jax_device()
    except PreconditionNotMetError as e:
        cpu_place = "unavailable: %s" % e
    else:
        cpu_place = _cpu_place_program()
    _emit("train", dev_rec, program="cpu_place_probe", cpu_place=cpu_place)
    return base["losses"]


def _cpu_place_program():
    """A tiny flash_attention + Adam program through
    Executor(CPUPlace())."""
    import paddle_tpu as fluid
    from paddle_tpu import layers

    main, startup = fluid.Program(), fluid.Program()
    startup.random_seed = SEED
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        x = fluid.data(name="x", shape=[2, 2, 128, 16],
                       dtype="float32")
        q = layers.fc(x, 16, num_flatten_dims=3)
        k = layers.fc(x, 16, num_flatten_dims=3)
        o = layers.flash_attention(q, k, x, causal=True)
        loss = layers.mean(layers.square(o))
        fluid.optimizer.AdamOptimizer(1e-2).minimize(loss)
    feed = {"x": np.random.RandomState(3).randn(
        2, 2, 128, 16).astype("float32")}
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        vals = []
        for _ in range(2):
            (out,) = exe.run(main, feed=feed, fetch_list=[loss],
                             return_numpy=False)
            vals.append(float(np.asarray(out.array)))
        assert all(np.isfinite(vals)), vals
        assert {d.platform for d in out.array.devices()} == {"cpu"}
        n = _mosaic_calls(exe.lower(main, feed=feed,
                                    fetch_list=[loss]))
        assert n == 0, "Mosaic call in a CPU-place computation"
    return "ran on cpu, xla path, loss %.5f -> %.5f" % tuple(vals)


def phase_kernels(sizes, dev_rec, platform, xla):
    import importlib

    import jax
    import jax.numpy as jnp

    import paddle_tpu as fluid

    fa = importlib.import_module("paddle_tpu.ops.pallas.flash_attention")
    pa = importlib.import_module("paddle_tpu.ops.pallas.paged_attention")
    on_tpu = platform == "tpu"
    t_phase, x_phase = time.perf_counter(), xla.snapshot()
    report = {}

    def check_mosaic(name, fn, args, want):
        """On the chip the jitted comparison must hold the kernel as a
        Mosaic custom call; interpret mode cannot pass for it."""
        n = _mosaic_calls(jax.jit(fn).lower(*args))
        if on_tpu:
            assert n >= want, "%s: %d Mosaic calls, want >= %d" % (
                name, n, want)
        return n

    # -- flash fwd + dQ + dK/dV through a gpt_long step ---------------------
    cfg = sizes["gpt"]
    main, startup, loss, feed = build_gpt_long(cfg)
    run = train_one_device(main, startup, loss, feed, cfg["steps"],
                           platform, xla)
    with fluid.scope_guard(run["scope"]):
        n_step = _mosaic_calls(run["exe"].lower(main, feed=feed,
                                                fetch_list=[loss]))
    if on_tpu:
        # fwd, dQ, dK/dV per layer: a dense fallback has none
        assert n_step >= 3 * cfg["layers"], n_step
    report["gpt_long_step"] = {"mosaic_calls": n_step,
                               "setup_s": round(run["setup_s"], 2),
                               "step_ms": round(run["step_ms"], 2),
                               "losses": [round(x, 5)
                                          for x in run["losses"]]}
    gpt_step_ms, gpt_setup_s = run["step_ms"], run["setup_s"]
    del run
    gc.collect()

    rng = np.random.RandomState(7)

    def flash_case(name, c, causal, lengths, tol, path, block=None,
                   tokens=False):
        """``path``: the kernels the shapes must select ("stream": fwd,
        dQ, dK+dV, since these cases have one K/V head a query head;
        "short": fwd and one backward kernel). ``block``
        smaller than the sequence keeps a short sequence on the
        streaming kernels. ``tokens``: operands [b, s, h * d] as the
        projections leave them, for the token-major short kernels.
        ``c["dv"]``: a head dim of v's own (and the context's)."""
        shape = (c["b"], c["h"], c["s"], c["d"])
        blocks = {} if block is None else {"block_q": block,
                                           "block_k": block}
        if tokens:
            shape = (c["b"], c["s"], c["h"] * c["d"])
            blocks["num_heads"] = c["h"]
        v_shape = shape[:-1] + (c.get("dv", shape[-1]),)
        q, k, v, w = (jnp.asarray(rng.randn(*sh), jnp.bfloat16)
                      for sh in (shape, shape, v_shape, v_shape))
        scale = float(c["d"]) ** -0.5
        lens = None if lengths is None else jnp.asarray(lengths, jnp.int32)

        def kernel(q, k, v):
            def f(q, k, v):
                o = fa.flash_attention(q, k, v, causal=causal,
                                       force_pallas=True, lengths=lens,
                                       **blocks)
                return jnp.sum(o.astype(jnp.float32)
                               * w.astype(jnp.float32)), o
            (_, o), g = jax.value_and_grad(f, argnums=(0, 1, 2),
                                           has_aux=True)(q, k, v)
            return (o,) + g

        def reference(q, k, v):
            def f(q, k, v):
                if tokens:
                    q, k, v = (fa.split_heads(x, c["h"]) for x in (q, k, v))
                o = fa._dense_attention(q, k, v, causal, scale, lens)
                if tokens:
                    o = fa.merge_heads(o)
                return jnp.sum(o.astype(jnp.float32)
                               * w.astype(jnp.float32)), o
            with jax.default_matmul_precision("highest"):
                (_, o), g = jax.value_and_grad(f, argnums=(0, 1, 2),
                                               has_aux=True)(q, k, v)
            return (o,) + g

        took = fa.attention_path(q, k, force_pallas=True, v=v, **blocks)
        assert took == path, (name, took)
        if tokens:   # the token-major kernels' blocks, not a split and merge
            planned = fa._plan(q, k, 512, 1024, c["h"])[0]
            assert isinstance(planned, tuple), (name, planned)
        n = check_mosaic(name, kernel, (q, k, v),
                         3 if path == "stream" else 2)
        got = jax.jit(kernel)(q, k, v)
        ref = jax.jit(reference)(q, k, v)
        errs = {t: _rel_err(g, r)
                for t, g, r in zip(("out", "dq", "dk", "dv"), got, ref)}
        assert max(errs.values()) < tol, (name, errs)
        report[name] = {"mosaic_calls": n, "path": took, "rel_err": errs,
                        "tol": tol}

    # bf16 in and out: the output and the three gradients are each
    # rounded to bf16 (2^-9 of their magnitude) and P / dS pass through
    # one bf16 MXU pass inside the kernel where the reference keeps
    # f32 — 2e-2 of the tensor's max leaves ~4x over what that predicts
    f = sizes["flash"]   # the rehearsal's toy length would go short
    flash_case("flash_causal", f, True, None, 2e-2, "stream",
               block=None if f["s"] > 1024 else f["s"] // 2)
    m = sizes["masked"]
    lengths = rng.randint(m["s"] // 2, m["s"] + 1, (m["b"],))
    flash_case("flash_masked", m, False, lengths, 2e-2, "stream",
               block=m["s"] // 2)
    # the short path: BERT's attention as the benchmark's cell runs it,
    # and transformer_wmt's (causal + lengths) as the model routes it
    flash_case("flash_short", sizes["short"], False, None, 2e-2, "short")
    flash_case("flash_short_masked", m, True, lengths, 2e-2, "short")
    # the same, token-major, as the models route them since PR 29: both
    # BERT cells' shapes (T = 512; a dp4 replica's T = 128) and the
    # encoder-decoder's with both masks
    flash_case("flash_tokens", sizes["short"], False, None, 2e-2, "short",
               tokens=True)
    flash_case("flash_tokens_dp", sizes["short_dp"], False, None, 2e-2,
               "short", tokens=True)
    if m["h"] * m["d"] % 128 == 0:   # the rehearsal's toy heads are 32 wide
        flash_case("flash_tokens_masked", m, True, lengths, 2e-2, "short",
                   tokens=True)

    # latent attention's shape: 32 heads, q and k at 192, v and the context
    # at 128, T = 4096, causal, on the streaming kernels against the dense
    # form, forward and the three gradients
    la = sizes["latent"]
    flash_case("flash_value_dim", la, True, None, 2e-2, "stream",
               block=None if la["s"] > 1024 else la["s"] // 2)
    # and at equal dims the entry that now reads v's head dim gives, bit for
    # bit, what the kernels give when planned and called as before it did
    eq = dict(la, d=la["dv"])
    q, k, v = (jnp.asarray(rng.randn(eq["b"], eq["h"], eq["s"], eq["d"]),
                           jnp.bfloat16) for _ in range(3))
    blk = ({} if eq["s"] > 1024 else
           {"block_q": eq["s"] // 2, "block_k": eq["s"] // 2})

    def by_entry(q, k, v):
        out, vjp = jax.vjp(lambda *a: fa.flash_attention(
            *a, causal=True, force_pallas=True, **blk), q, k, v)
        return (out,) + vjp(out)

    def as_before(q, k, v):
        short, bq, bk = fa._plan(q, k, blk.get("block_q", 512),
                                 blk.get("block_k", 1024))
        out, vjp = jax.vjp(lambda *a: fa._flash(
            *a, None, True, float(eq["d"]) ** -0.5, bq, bk, short,
            not on_tpu)[0], q, k, v)
        return (out,) + vjp(out)

    same = [bool(jnp.array_equal(a, b)) for a, b in zip(
        jax.jit(by_entry)(q, k, v), jax.jit(as_before)(q, k, v))]
    assert all(same), ("flash_equal_dims", same)
    report["flash_equal_dims"] = {"bit_for_bit": same}

    # -- the streaming kernels with a per-query key selection ---------------
    # shared K/V heads, causal, each query keeping ``keep`` of its causal
    # keys (all of them where it has fewer): forward and backward against
    # the dense masked form
    c = sizes["selected"]
    q, w = (jnp.asarray(rng.randn(c["b"], c["h"], c["s"], c["d"]),
                        jnp.bfloat16) for _ in range(2))
    k, v = (jnp.asarray(rng.randn(c["b"], c["kv"], c["s"], c["d"]),
                        jnp.bfloat16) for _ in range(2))
    draw = rng.rand(c["b"], c["s"], c["s"]) + np.triu(
        np.full((c["s"], c["s"]), 2.0), 1)           # keys after t: never
    select = jnp.asarray((draw < 2.0) & (draw <= np.sort(draw, -1)[
        ..., c["keep"] - 1:c["keep"]]), jnp.int8)
    assert (int(select[0, -1].sum()), int(select[0, 0].sum())) == (
        c["keep"], 1)
    scale = float(c["d"]) ** -0.5
    block = {} if c["s"] > 1024 else {"block_q": c["s"] // 2,
                                      "block_k": c["s"] // 2}

    def selected(attend):
        def f(q, k, v):
            def loss(q, k, v):
                o = attend(q, k, v)
                return jnp.sum(o.astype(jnp.float32)
                               * w.astype(jnp.float32)), o
            (_, o), g = jax.value_and_grad(loss, argnums=(0, 1, 2),
                                           has_aux=True)(q, k, v)
            return (o,) + g
        return f

    kernel = selected(lambda q, k, v: fa.flash_attention(
        q, k, v, causal=True, scale=scale, force_pallas=True, select=select,
        **block))
    # shared K/V heads: the forward and the one backward kernel
    n = check_mosaic("flash_selected", kernel, (q, k, v), 2)
    got = jax.jit(kernel)(q, k, v)
    with jax.default_matmul_precision("highest"):
        ref = jax.jit(selected(lambda q, k, v: fa._dense_attention(
            q, k, v, True, scale, select=select)))(q, k, v)
    errs = {t: _rel_err(g, r)
            for t, g, r in zip(("out", "dq", "dk", "dv"), got, ref)}
    assert max(errs.values()) < 2e-2, ("flash_selected", errs)
    report["flash_selected"] = {"mosaic_calls": n, "rel_err": errs,
                                "tol": 2e-2, "keys_a_query": c["keep"]}

    # -- the selective scan's kernels against its XLA form ------------------
    from paddle_tpu.ops import ssm_ops
    from paddle_tpu.ops.pallas import ssd_scan

    c = sizes["scan"]
    bf16, f32 = jnp.bfloat16, jnp.float32
    x, w = (jnp.asarray(rng.randn(c["b"], c["t"], c["h"], c["p"]), bf16)
            for _ in range(2))
    b_in, c_in = (jnp.asarray(rng.randn(c["b"], c["t"], c["g"], c["n"]), bf16)
                  for _ in range(2))
    dt, cs = ssm_ops._prologue(
        jnp.asarray(rng.randn(c["b"], c["t"], c["h"]) - 2, f32),
        jnp.asarray(-np.exp(rng.randn(c["h"])), f32), None, 128, 0)
    scan_args = (x, dt, cs, b_in, c_in, jnp.asarray(rng.randn(c["h"]), f32))
    assert ssd_scan.fits(x, b_in, 128), c

    def both(scan):
        def f(*a):
            out, vjp = jax.vjp(scan, *a)
            return (out,) + vjp(w)
        return f

    kernel = both(lambda *a: ssd_scan.scan(*a, 128, not on_tpu))
    # forward, state pass, backward kernel
    n = check_mosaic("ssd_scan", kernel, scan_args, 3)
    got = jax.jit(kernel)(*scan_args)
    with jax.default_matmul_precision("highest"):
        ref = jax.jit(both(lambda *a: ssm_ops._xla_chunked(*a, 128)))(
            *scan_args)
    errs = {t: _rel_err(g, r) for t, g, r in zip(
        ("out", "dx", "ddt", "dcs", "db", "dc", "dd"), got, ref)}
    # bf16 operands on both sides, summed in another order: as the flash cases
    assert max(errs.values()) < 2e-2, ("ssd_scan", errs)
    report["ssd_scan"] = {"mosaic_calls": n, "rel_err": errs, "tol": 2e-2}

    # -- the delta rule's kernels against its XLA form -----------------------
    # q, k, v in bf16 on the kernels, the einsums in float32 at full
    # precision: the output and the five gradients the kernels give
    from unittest import mock

    from paddle_tpu.ops import kda_ops
    from paddle_tpu.ops.pallas import kda

    c = sizes["delta"]
    shape = (c["b"], c["t"], c["h"], c["d"])
    q, k, v, w = (jnp.asarray(rng.randn(*shape), bf16) for _ in range(4))
    leaves = (jnp.asarray(np.log(rng.uniform(1, 16, c["h"])), f32),
              jnp.asarray(0.1 * rng.randn(c["h"] * c["d"]), f32))
    g, beta = kda_ops.gates(jnp.asarray(rng.randn(*shape) - 2, f32),
                            jnp.asarray(rng.randn(*shape[:3]), f32), *leaves)
    rule_args = (q, k, v, g, beta)
    assert kda.fits(q, v, 64), c

    def einsums(q, k, v, g, beta):
        # the XLA form takes the raw gates: hand it the made ones through
        # ``gates``' place
        with mock.patch.object(kda_ops, "gates", lambda g, b, *_: (g, b)):
            return kda_ops._xla_chunked(q, k, v, g, beta, *leaves, 64)

    def with_gradients(rule):
        def f(*a):
            out, vjp = jax.vjp(rule, *a)
            return (out,) + vjp(w.astype(out.dtype))
        return f

    kernel = with_gradients(lambda *a: kda.delta_rule(*a, 64, not on_tpu))
    # forward; forward with the entering states, backward kernel
    n = check_mosaic("kda", kernel, rule_args, 3)
    got = jax.jit(kernel)(*rule_args)
    with jax.default_matmul_precision("highest"):
        ref = jax.jit(with_gradients(einsums))(
            *(a.astype(f32) for a in rule_args))
    errs = {t: _rel_err(a, r) for t, a, r in zip(
        ("out", "dq", "dk", "dv", "dg", "dbeta"), got, ref)}
    # bf16 operands and cotangents against float32: dg is a sum back over a
    # chunk's positions of terms that cancel
    assert max(errs.values()) < 5e-2, ("kda", errs)
    report["kda"] = {"mosaic_calls": n, "rel_err": errs, "tol": 5e-2}

    # -- the hyper-connections' passes on their kernels against the XLA form --
    # the four ops over the latent-attention cell's streams, each form traced
    # with the program's question answered for it: every output of the two
    # forward ops and every gradient of the two gradient ops
    import contextlib
    import functools
    from unittest import mock

    from paddle_tpu.core.registry import OpInfoMap
    from paddle_tpu.ops import hyper_connection_ops as hc
    from paddle_tpu.ops.pallas import hyper_connection as hk

    c = sizes["mhc"]
    n_s, k_s = c["n"], 2 * c["n"] + c["n"] ** 2
    streams = (c["b"], n_s, c["t"], c["c"])
    ins = {"X": rng.randn(*streams), "Phi": 0.02 * rng.randn(n_s * c["c"], k_s),
           "Alpha": rng.randn(3), "BPre": rng.randn(n_s),
           "BPost": rng.randn(n_s), "BRes": rng.randn(n_s, n_s),
           "Y": rng.randn(c["b"], c["t"], c["c"]),
           "H@GRAD": rng.randn(c["b"], c["t"], c["c"]),
           "HPost@GRAD": rng.randn(c["b"], n_s, c["t"]),
           "HRes@GRAD": rng.randn(c["b"], n_s, n_s, c["t"]),
           "Out@GRAD": rng.randn(*streams)}
    ins = {k: jnp.asarray(v, f32) for k, v in ins.items()}
    assert hk.fits(ins["X"]), c

    def mhc_passes(ins):
        ops = OpInfoMap.instance()
        pre = ops.get("mhc_pre").fn(ins, {})
        post_in = dict(ins, HRes=pre["HRes"], HPost=pre["HPost"])
        return dict(
            pre, Out=ops.get("mhc_post").fn(post_in, {})["Out"],
            **{"pre." + k: v for k, v in
               ops.get("mhc_pre_grad").fn(ins, {}).items()},
            **{"post." + k: v for k, v in
               ops.get("mhc_post_grad").fn(post_in, {}).items()})

    def in_form(asked):
        """``mhc_passes`` traced with ``compute_platform()`` answering
        ``asked``; off the chip the kernels it then takes are interpreted."""
        def traced(ins):
            with contextlib.ExitStack() as stack:
                stack.enter_context(mock.patch.object(
                    hc._fa, "compute_platform", lambda: asked))
                for entry in () if on_tpu else hk.ENTRIES:
                    stack.enter_context(mock.patch.object(
                        hk, entry, functools.partial(getattr(hk, entry),
                                                     interpret=True)))
                return mhc_passes(ins)
        return traced

    # one kernel a forward op, one for the mix's gradient, two for the maps'
    n = check_mosaic("mhc_passes", in_form("tpu"), (ins,), 5)
    got = jax.jit(in_form("tpu"))(ins)
    ref = jax.jit(in_form("cpu"))(ins)
    errs = {t: _rel_err(got[t], r) for t, r in ref.items()}
    # float32 on both sides, products at full precision, sums in another
    # order: rows of 3,584 and, in dPhi, columns of 4,096
    assert max(errs.values()) < 1e-4, ("mhc_passes", errs)
    report["mhc_passes"] = {"mosaic_calls": n, "rel_err": errs, "tol": 1e-4}

    # -- the indexer's float32 product from bfloat16 pieces ------------------
    # the three pieces of a float32 value as the device makes them under
    # jit (no rounding folded away: mid and lo hold bits, the sum is the
    # value), and the packed product and its VJP against float64 on the host
    from paddle_tpu.ops import sparse_attn_ops as sa

    c = sizes["index"]

    def spanning(*shape):
        """Normal draws scaled over e^-4..e^4."""
        return (rng.randn(*shape) * np.exp(rng.uniform(-4, 4, shape))).astype(
            np.float32)

    qi, ki, wi, gi = (spanning(c["r"], c["h"], c["d"]),
                      spanning(c["s"], c["d"]), spanning(c["r"], c["h"]),
                      spanning(c["r"], c["s"]))
    pieces = [np.asarray(piece.astype(f32), np.float64)
              for piece in jax.jit(sa.split3)(ki)]
    assert np.array_equal(sum(pieces), ki.astype(np.float64)), "pieces' sum"
    held = [float(np.mean(piece != 0)) for piece in pieces]
    assert min(held) > 0.9, ("a piece is empty", held)

    def scores_and_vjp(scores):
        def f(q, k, w, g):
            out, vjp = jax.vjp(scores, q, k, w)
            return (out,) + vjp(g)
        return jax.jit(f)

    def highest_scores(q, k, w):
        s = jnp.einsum("rhd,sd->hrs", q, k, precision=jax.lax.Precision.HIGHEST)
        return jnp.sum(jax.nn.relu(s) * w.T[:, :, None], 0) + 0.0

    q64, k64, w64 = (a.astype(np.float64) for a in (qi, ki, wi))
    s64 = np.einsum("rhd,sd->hrs", q64, k64)
    # no cotangent where a head's product is within rounding of the ReLU's
    # kink: there float32 and float64 may take different sides of it
    gi[(np.abs(s64) < 1e-5 * np.einsum("rhd,sd->hrs", np.abs(q64),
                                       np.abs(k64))).any(0)] = 0.0
    g64 = gi.astype(np.float64)
    ds64 = (s64 > 0) * g64[None] * w64.T[:, :, None]
    want = (np.sum(np.maximum(s64, 0) * w64.T[:, :, None], 0),
            np.einsum("hrs,sd->rhd", ds64, k64),
            np.einsum("hrs,rhd->sd", ds64, q64),
            np.sum(np.maximum(s64, 0) * g64[None], -1).T)

    def norm_errs(got):
        return {t: float(np.linalg.norm(np.asarray(a, np.float64) - b)
                         / np.linalg.norm(b))
                for t, a, b in zip(("scores", "dq", "dk", "dw"), got, want)}

    errs = norm_errs(scores_and_vjp(sa.index_scores)(qi, ki, wi, gi))
    plain = norm_errs(scores_and_vjp(highest_scores)(qi, ki, wi, gi))
    # float32-accurate: under 1e-6 of each result's norm, the scores no
    # worse than twice what one float32 product at HIGHEST leaves here
    assert max(errs.values()) < 1e-6 and errs["scores"] < 2 * plain[
        "scores"], ("index_scores", errs, plain)
    report["index_scores"] = {"pieces_nonzero": held, "norm_err": errs,
                              "norm_err_highest": plain, "tol": 1e-6}

    # -- paged attention at the decode engine's shapes ----------------------
    from paddle_tpu.serving.decode import DecodeConfig, DecodeEngine

    dc = DecodeConfig()
    cc = dc.cache
    B, nb, bt = dc.max_batch_size, cc.num_blocks, cc.block_tokens
    H, D = cc.num_heads, cc.head_dim
    q = rng.randn(B, H, D).astype(np.float32)
    k_ar = rng.randn(nb, bt, H, D).astype(np.float32)
    v_ar = rng.randn(nb, bt, H, D).astype(np.float32)
    lens = rng.randint(0, 5 * bt, (B,)).astype(np.int32)
    lens[0], lens[1] = 0, 5 * bt - 1          # an empty row, a long one
    width = -(-int(lens.max()) // bt)
    table = np.full((B, width), -1, np.int32)
    perm = rng.permutation(nb)
    at = 0
    for b in range(B):
        n_blk = -(-int(lens[b]) // bt)
        table[b, :n_blk] = perm[at:at + n_blk]
        at += n_blk
    backend = "pallas" if on_tpu else "pallas_interpret"
    got = pa.paged_decode_attention(q, k_ar, v_ar, table, lens,
                                    block_tokens=bt, backend=backend)
    ref = pa.paged_attention_reference(q, k_ar, v_ar, table, lens,
                                       block_tokens=bt)
    n = _mosaic_calls(pa._paged_pallas.lower(
        q, k_ar, v_ar, np.maximum(table, 0), lens, block_tokens=bt,
        scale=float(D) ** -0.5, interpret=not on_tpu))
    if on_tpu:
        assert n >= 1, "paged kernel lowered without a Mosaic call"
    # f32 throughout on the VPU (no MXU pass); the running softmax
    # re-associates the sums and exp is the hardware's — 1e-4 of max
    err, tol = _rel_err(got, ref), 1e-4
    assert err < tol, ("paged", err)
    report["paged_attention"] = {"mosaic_calls": n, "rel_err": err,
                                 "tol": tol, "shape": [B, H, D, bt, nb]}

    # -- the decode engine, default backend, token-exact vs dense ----------
    d = sizes["decode"]
    prompts = [[int(t) for t in rng.randint(1, dc.vocab_size,
                                            rng.randint(3, 40))]
               for _ in range(d["streams"])]

    def serve(attn_backend):
        eng = DecodeEngine(DecodeConfig(attn_backend=attn_backend,
                                        eos_token=None)).start()
        try:
            streams = [eng.submit(pr, max_tokens=d["max_tokens"])
                       for pr in prompts]
            return [s.result(timeout_s=600)[0] for s in streams]
        finally:
            eng.stop(drain=False)

    compiled_before = pa._paged_pallas._cache_size()
    t0 = time.perf_counter()
    toks = serve(None if on_tpu else "pallas_interpret")
    serve_s = time.perf_counter() - t0
    kernel_shapes = pa._paged_pallas._cache_size() - compiled_before
    assert kernel_shapes > 0, "the engine never reached the paged kernel"
    want = serve("dense")
    assert all(len(t) == d["max_tokens"] for t in toks), toks
    assert toks == want, "decode streams diverge from the dense backend"
    report["decode_engine"] = {"streams": len(prompts),
                               "tokens": sum(map(len, toks)),
                               "kernel_shapes_compiled": kernel_shapes,
                               "serve_s": round(serve_s, 2),
                               "token_exact_vs_dense": True}

    _emit("kernels", dev_rec,
          setup_s=round(gpt_setup_s, 2), step_ms=round(gpt_step_ms, 2),
          phase_s=round(time.perf_counter() - t_phase, 2),
          interpret=not on_tpu, kernels=report,
          **_since(xla.snapshot(), x_phase))


def phase_dp4(sizes, dev_rec, platform, xla):
    """BERT-base data-parallel over four chips in this one process."""
    import jax

    import paddle_tpu as fluid
    from paddle_tpu import observability as obs
    from paddle_tpu.core.enforce import OutOfRangeError
    from paddle_tpu.parallel.mesh_utils import make_mesh

    cfg = sizes["bert"]
    n = 4
    devices = jax.devices()[:n]
    gbatch = n * cfg["batch"]
    feed = bert_feed(cfg, gbatch)
    x_phase = xla.snapshot()

    # the one-chip loss on the SAME global batch, from the same seed
    main1, startup1, loss1 = build_bert(cfg, gbatch)
    probe = _first_param(main1)
    scope1 = fluid.Scope()
    with fluid.scope_guard(scope1):
        exe1 = fluid.Executor(fluid.TPUPlace(0))
        exe1.run(startup1)
        init_probe = np.asarray(scope1.find_var(probe).raw().array)
        (l1,) = exe1.run(main1, feed=feed, fetch_list=[loss1])
    loss_one_chip = float(np.asarray(l1).ravel()[0])
    del scope1, exe1
    gc.collect()

    # dp4: the program is built at the per-replica batch and fed the
    # global one (shard_map slices the feed over the mesh)
    main, startup, loss = build_bert(cfg, cfg["batch"])
    mesh = make_mesh([n], ["dp"], devices)
    scope = fluid.Scope()
    losses, step_s = [], []
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.TPUPlace(0))
        t0 = time.perf_counter()
        exe.run(startup)
        # same seed -> same weights as the one-chip run
        np.testing.assert_array_equal(
            np.asarray(scope.find_var(probe).raw().array), init_probe)
        cp = fluid.CompiledProgram(main).with_data_parallel(
            loss_name=loss.name, places=mesh)
        coll0 = obs.counter_value("parallel.collective_ops") or 0
        for i in range(sizes["dp_steps"]):
            t = time.perf_counter()
            (out,) = exe.run(cp, feed=feed, fetch_list=[loss],
                             return_numpy=False)
            jax.block_until_ready(out)
            step_s.append(time.perf_counter() - t)
            # the fetch is all-gathered: one per-replica mean each
            losses.append(float(np.mean(np.asarray(out))))
            if i == 0:
                setup_s = time.perf_counter() - t0
                x1 = xla.snapshot()
        assert xla.builds == x1["xla_builds"], (x1, xla.snapshot())
        coll = (obs.counter_value("parallel.collective_ops") or 0) - coll0
        p = scope.find_var(probe).raw().array
        shard_devices = {s.device for s in p.addressable_shards}

    assert all(np.isfinite(losses)), losses
    assert losses[-1] < losses[0], losses
    # mean of four per-replica means of 32 == mean over 128; bf16
    # matmuls tile a b32 and a b128 problem differently, so the sums
    # re-associate: 2e-3 relative on a loss of ~ln(vocab)
    tol = 2e-3
    gap = abs(losses[0] - loss_one_chip) / abs(loss_one_chip)
    assert gap < tol, (losses[0], loss_one_chip)
    assert coll > 0, "no collective ops counted over %d steps" % len(losses)
    assert shard_devices == set(devices), (shard_devices, devices)
    assert {d.platform for d in shard_devices} == {platform}
    peaks = [_peak_bytes(d, platform) for d in devices]

    # TPUPlace(i) is chip i, and past the last chip it raises
    place_probe = _place_probe(devices[1])
    try:
        fluid.TPUPlace(len(jax.devices())).jax_device()
    except OutOfRangeError:
        pass
    else:
        raise AssertionError("TPUPlace past the device count resolved")

    _emit("dp4", dev_rec, program="bert_base", mesh={"dp": n},
          global_batch=gbatch, setup_s=round(setup_s, 2),
          step_ms=round(1e3 * float(np.mean(step_s[1:])), 2),
          losses=[round(x, 5) for x in losses],
          loss_one_chip=round(loss_one_chip, 5),
          first_step_rel_gap=gap, tol=tol, collective_ops=coll,
          param_shard_devices=sorted(str(d) for d in shard_devices),
          peak_bytes_in_use=peaks, tpuplace_1=place_probe,
          **_since(xla.snapshot(), x_phase))


def _place_probe(device):
    """An MLP step under Executor(TPUPlace(1)): feeds, parameters and
    the loss must all live on chip 1."""
    import paddle_tpu as fluid

    main, startup = fluid.Program(), fluid.Program()
    startup.random_seed = SEED
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        x = fluid.data(name="x", shape=[16, 32], dtype="float32")
        y = fluid.data(name="y", shape=[16, 1], dtype="float32")
        pred = fluid.layers.fc(fluid.layers.fc(x, 64, act="relu"), 1)
        loss = fluid.layers.mean(fluid.layers.square_error_cost(pred, y))
        fluid.optimizer.SGD(0.05).minimize(loss)
    rng = np.random.RandomState(5)
    feed = {"x": rng.randn(16, 32).astype("float32"),
            "y": rng.randn(16, 1).astype("float32")}
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.TPUPlace(1))
        exe.run(startup)
        for _ in range(2):
            (out,) = exe.run(main, feed=feed, fetch_list=[loss],
                             return_numpy=False)
        assert out.array.devices() == {device}, out.array.devices()
        p = scope.find_var(_first_param(main)).raw().array
        assert p.devices() == {device}, p.devices()
        assert np.isfinite(float(np.asarray(out.array)))
    return "loss and parameters on %s" % device


# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="tiny sizes on the CPU platform with the Pallas "
                         "kernels in interpret mode; prints platform cpu "
                         "and is not a pass on the chip")
    args = ap.parse_args(argv)

    import jax

    devs = jax.devices()
    platform = devs[0].platform
    dev_rec = {"platform": platform, "device_kind": devs[0].device_kind,
               "device_count": len(devs)}
    if args.rehearse_cpu:
        if platform != "cpu":
            print("chip_smoke: --rehearse-cpu is for JAX_PLATFORMS=cpu; "
                  "this process found %r" % platform, file=sys.stderr)
            return 2
        sizes = REHEARSAL
    elif platform != "tpu":
        print("chip_smoke: JAX found no TPU (first device: %r). Run it on "
              "the chip; --rehearse-cpu is the explicit CPU rehearsal."
              % (devs[0],), file=sys.stderr)
        return 2
    else:
        sizes = FULL

    from paddle_tpu import observability as obs
    from paddle_tpu.core.compile_cache import enable_compile_cache

    # the rehearsal's toy programs gain nothing from a persistent cache
    cache_dir = None if args.rehearse_cpu else enable_compile_cache()
    obs.enable()
    xla = _XlaCompiles()
    t_all = time.perf_counter()
    _emit("device", dev_rec, devices=[str(d) for d in devs],
          compile_cache=cache_dir,
          compile_cache_entries=(len(os.listdir(cache_dir))
                                 if cache_dir and os.path.isdir(cache_dir)
                                 else 0),
          rehearsal=args.rehearse_cpu)

    phase_train(sizes, dev_rec, platform, xla)
    phase_kernels(sizes, dev_rec, platform, xla)
    if len(devs) >= 4:
        phase_dp4(sizes, dev_rec, platform, xla)

    # what the whole run cost, as one more smoke line; the result line
    # after it carries exactly the keys the driver reads and no others
    _emit("summary", dev_rec, rehearsal=args.rehearse_cpu,
          wall_s=round(time.perf_counter() - t_all, 1), **xla.snapshot())
    print(json.dumps({"ok": True,
                      "device": {"platform": platform,
                                 "kind": devs[0].device_kind,
                                 "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
