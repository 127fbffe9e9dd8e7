"""Fast collective path (ISSUE 6): bucketed / quantized allreduce and
cross-replica sharded weight update.

Numerics contract under test:
- bucketed allreduce is BIT-FOR-BIT vs the per-grad path (psum is
  elementwise over replicas, so concat-then-psum == psum-then-concat);
- the sharded weight update matches the replicated update bit-for-bit,
  including uneven shard sizes (total params not divisible by nranks)
  and the flat sharded optimizer state matching the per-param state;
- quantized allreduce (opt-in) stays within its stated error bound and
  still converges on the mlp workload.
"""
import os

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import observability as obs
from paddle_tpu.parallel import collectives
from paddle_tpu.parallel.mesh_utils import make_mesh

KNOBS = ("PADDLE_TPU_BUCKET_MB", "PADDLE_TPU_QUANT_ALLREDUCE",
         "PADDLE_TPU_SHARDED_UPDATE", "PADDLE_TPU_BUCKET_PLAN",
         "PADDLE_TPU_BUCKET_PROFILE")


@pytest.fixture(autouse=True)
def _clean_knobs(monkeypatch):
    for k in KNOBS:
        monkeypatch.delenv(k, raising=False)
    yield


# -- knob parsing -----------------------------------------------------------


def test_knob_parsing(monkeypatch):
    assert collectives.bucket_mb() == collectives.DEFAULT_BUCKET_MB
    monkeypatch.setenv("PADDLE_TPU_BUCKET_MB", "2.5")
    assert collectives.bucket_mb() == 2.5
    monkeypatch.setenv("PADDLE_TPU_BUCKET_MB", "0")
    assert collectives.bucket_mb() == 0.0
    monkeypatch.setenv("PADDLE_TPU_BUCKET_MB", "junk")
    assert collectives.bucket_mb() == collectives.DEFAULT_BUCKET_MB

    class BS:
        fuse_all_reduce_ops = False
        fuse_all_optimizer_ops = True

    assert collectives.bucket_mb(BS()) == 0.0

    assert collectives.quant_mode() == "none"
    for raw, want in (("bf16", "bf16"), ("INT8", "int8"), ("off", "none"),
                      ("0", "none")):
        monkeypatch.setenv("PADDLE_TPU_QUANT_ALLREDUCE", raw)
        assert collectives.quant_mode() == want
    monkeypatch.setenv("PADDLE_TPU_QUANT_ALLREDUCE", "fp4")
    with pytest.raises(ValueError):
        collectives.quant_mode()
    monkeypatch.delenv("PADDLE_TPU_QUANT_ALLREDUCE")

    assert not collectives.sharded_update_enabled()
    assert collectives.sharded_update_enabled(BS())  # BuildStrategy knob
    monkeypatch.setenv("PADDLE_TPU_SHARDED_UPDATE", "0")
    assert not collectives.sharded_update_enabled(BS())  # env overrides
    monkeypatch.setenv("PADDLE_TPU_SHARDED_UPDATE", "1")
    assert collectives.sharded_update_enabled()


def test_plan_buckets_caps_and_order():
    # items: (anchor, first_use, key, nbytes, idx)
    K = (0, "float32")
    # size cap: three 3-byte grads under a 6-byte cap -> 2 buckets
    b = collectives.plan_buckets(
        [(0, 10, K, 3, 0), (1, 10, K, 3, 1), (2, 10, K, 3, 2)], 6)
    assert [x["members"] for x in b] == [[0, 1], [2]]
    # dtype change closes the bucket
    K2 = (0, "float16")
    b = collectives.plan_buckets(
        [(0, 10, K, 3, 0), (1, 10, K2, 1, 1)], 1 << 20)
    assert [x["members"] for x in b] == [[0], [1]]
    # ordering: a grad consumed before a later grad's anchor cannot
    # share its bucket (the bucket op would land after the consumer)
    b = collectives.plan_buckets(
        [(0, 3, K, 1, 0), (5, 10, K, 1, 1)], 1 << 20)
    assert [x["members"] for x in b] == [[0], [1]]
    # bucket_bytes <= 0 means one bucket per grad
    b = collectives.plan_buckets(
        [(0, 10, K, 1, 0), (1, 10, K, 1, 1)], 0)
    assert [x["members"] for x in b] == [[0], [1]]


# -- program-path parity harness -------------------------------------------


def _build(optimizer, sizes=(32, 10), feat=8, batch=16):
    main, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        x = fluid.data(name="x", shape=[batch, feat], dtype="float32")
        lbl = fluid.data(name="lbl", shape=[batch, 1], dtype="int64")
        h = x
        for s in sizes[:-1]:
            h = fluid.layers.fc(h, size=s, act="relu")
        pred = fluid.layers.fc(h, size=sizes[-1], act="softmax")
        loss = fluid.layers.mean(fluid.layers.cross_entropy(pred, lbl))
        optimizer().minimize(loss)
    return main, startup, loss


def _run_mesh(env, optimizer, snap, steps=3, n=2, sizes=(32, 10), feat=8,
              batch=16, monkeypatch=None):
    """One fresh program trained `steps` steps on an n-way dp mesh with
    the given knob env; params seeded from (or recorded into) `snap`."""
    import jax.numpy as jnp

    for k in KNOBS:
        os.environ.pop(k, None)
    os.environ.update(env)
    try:
        main, startup, loss = _build(optimizer, sizes, feat, batch)
        rng = np.random.RandomState(0)
        feed = {"x": rng.rand(batch, feat).astype("float32"),
                "lbl": rng.randint(0, sizes[-1],
                                   (batch, 1)).astype("int64")}
        scope = fluid.Scope()
        with fluid.scope_guard(scope):
            exe = fluid.Executor(fluid.CPUPlace())
            exe.run(startup)
            blk = main.global_block()
            if not snap:
                for name in blk.vars:
                    v = scope.find_var(name)
                    bv = blk._find_var_recursive(name)
                    if (v is not None and v.is_initialized()
                            and bv is not None and bv.persistable):
                        snap[name] = np.asarray(v.raw().array)
            else:
                for name, arr in snap.items():
                    scope.var(name).get_tensor()._array = jnp.asarray(arr)
            cp = fluid.CompiledProgram(main).with_data_parallel(
                loss_name=loss.name, places=make_mesh([n], ["dp"]))
            for _ in range(steps):
                out = exe.run(cp, feed=feed, fetch_list=[loss])
            state = {}
            for name in blk.vars:
                v = scope.find_var(name)
                bv = blk._find_var_recursive(name)
                if (v is not None and v.is_initialized() and bv is not None
                        and getattr(bv, "persistable", False)):
                    state[name] = np.asarray(v.raw().array)
            # flat sharded-state vars exist only in the scope
            for nm in scope.local_var_names():
                if not nm.startswith("sharded_update_"):
                    continue
                var = scope.find_var(nm)
                if var is not None and var.is_initialized():
                    state[nm] = np.asarray(var.raw().array)
        ctypes = [op.type for op in main.global_block().ops
                  if op.type.startswith("c_")]
        return float(np.asarray(out[0]).ravel()[0]), state, ctypes
    finally:
        for k in env:
            os.environ.pop(k, None)


def _momentum():
    return fluid.optimizer.MomentumOptimizer(0.1, 0.9)


def _adam():
    return fluid.optimizer.AdamOptimizer(1e-2)


def _assert_params_equal(a, b, skip_substr=()):
    for k, va in a.items():
        if any(s in k.lower() for s in skip_substr):
            continue
        assert k in b, "var %r missing" % k
        assert np.array_equal(va, b[k]), (
            "var %r diverged, max abs err %g"
            % (k, np.abs(va.astype(np.float64)
                         - b[k].astype(np.float64)).max()))


def test_bucketed_allreduce_bit_for_bit():
    snap = {}
    base_loss, base, t0 = _run_mesh({"PADDLE_TPU_BUCKET_MB": "0"},
                                    _momentum, snap)
    buck_loss, buck, t1 = _run_mesh({}, _momentum, snap)
    assert t0.count("c_allreduce_sum") == 4  # 2 fc layers x (w, b)
    assert t1.count("c_bucket_allreduce") == 1
    assert "c_allreduce_sum" not in t1
    assert buck_loss == base_loss
    _assert_params_equal(base, buck)


def test_bucket_size_cap_splits_buckets():
    # a tiny cap forces one bucket per grad — still bit-for-bit
    snap = {}
    base_loss, base, _ = _run_mesh({"PADDLE_TPU_BUCKET_MB": "0"},
                                   _momentum, snap)
    tiny_loss, tiny, t = _run_mesh(
        {"PADDLE_TPU_BUCKET_MB": "0.00001"}, _momentum, snap)
    assert t.count("c_bucket_allreduce") == 4
    assert tiny_loss == base_loss
    _assert_params_equal(base, tiny)


@pytest.mark.parametrize("opt,state_slots", [
    (_momentum, ("velocity",)),
    (_adam, ("moment1", "moment2")),
])
def test_sharded_update_bit_for_bit(opt, state_slots):
    snap = {}
    base_loss, base, _ = _run_mesh({"PADDLE_TPU_BUCKET_MB": "0"},
                                   opt, snap)
    sh_loss, sh, t = _run_mesh({"PADDLE_TPU_SHARDED_UPDATE": "1"},
                               opt, snap)
    assert t.count("c_sharded_update") == 1
    assert "c_allreduce_sum" not in t and "c_bucket_allreduce" not in t
    assert sh_loss == base_loss
    _assert_params_equal(base, sh, skip_substr=("velocity", "moment"))
    # the flat sharded state holds exactly the per-param accumulators,
    # concatenated in group order then zero-padded
    for slot in state_slots:
        flats = [v for k, v in sh.items()
                 if k.startswith("sharded_update_")
                 and k.endswith("." + slot)]
        assert len(flats) == 1, "expected one flat %s var" % slot
        flat = flats[0]
        parts = [v.ravel() for k, v in sorted(base.items())
                 if slot in k.lower()]
        want = np.concatenate(parts)
        # flat layout follows optimizer-op order, not sorted-name
        # order; compare as multisets (pad tail must be all zeros)
        assert flat.size >= want.size
        pad = flat.size - want.size
        assert np.array_equal(
            np.sort(flat), np.sort(np.concatenate(
                [want, np.zeros(pad, want.dtype)])))


def test_sharded_update_uneven_shards_dp8():
    """Total param count 58 is not divisible by nranks=8: the flat
    buffers pad to 64 and the padded tail must stay inert."""
    snap = {}
    kw = dict(sizes=(5, 3), feat=7, n=8, steps=4)
    base_loss, base, _ = _run_mesh({"PADDLE_TPU_BUCKET_MB": "0"},
                                   _adam, snap, **kw)
    sh_loss, sh, t = _run_mesh({"PADDLE_TPU_SHARDED_UPDATE": "1"},
                               _adam, snap, **kw)
    assert t.count("c_sharded_update") == 1
    assert sh_loss == base_loss
    _assert_params_equal(base, sh, skip_substr=("moment",))


def test_sharded_update_flat_names_unique_across_programs():
    """Two different programs sharing one Scope (a GAN's two
    optimizers) must get DISTINCT flat-state var names — a per-program
    group counter would have both claim sharded_update_0.velocity and
    clobber each other's optimizer state."""
    from paddle_tpu.parallel.transpiler import insert_allreduce_ops

    scope = fluid.Scope()
    flat_names = []
    for sizes in ((32, 10), (16, 4)):
        main, startup, _loss = _build(_momentum, sizes=sizes)
        with fluid.scope_guard(scope):
            exe = fluid.Executor(fluid.CPUPlace())
            exe.run(startup)
            insert_allreduce_ops(main, 2)
            n = collectives.apply_sharded_weight_update(main, scope, 2)
        assert n == 1
        flat_names.append({
            nm for nm in scope.local_var_names()
            if nm.startswith("sharded_update_")})
    assert flat_names[0] and flat_names[0] < flat_names[1], flat_names


def _cycle_with_restart(env, snap):
    """Train 2 mesh steps, re-run the startup program (pinning params
    back to `snap` so the restart is deterministic), train 2 more;
    return the final loss."""
    import jax.numpy as jnp

    for k in KNOBS:
        os.environ.pop(k, None)
    os.environ.update(env)
    try:
        main, startup, loss = _build(_adam)
        rng = np.random.RandomState(0)
        feed = {"x": rng.rand(16, 8).astype("float32"),
                "lbl": rng.randint(0, 10, (16, 1)).astype("int64")}
        scope = fluid.Scope()
        with fluid.scope_guard(scope):
            exe = fluid.Executor(fluid.CPUPlace())
            exe.run(startup)

            def pin():
                blk = main.global_block()
                if not snap:
                    for name in blk.vars:
                        v = scope.find_var(name)
                        bv = blk._find_var_recursive(name)
                        if (v is not None and v.is_initialized()
                                and bv is not None and bv.persistable):
                            snap[name] = np.asarray(v.raw().array)
                else:
                    for name, arr in snap.items():
                        scope.var(name).get_tensor()._array = \
                            jnp.asarray(arr)

            pin()
            cp = fluid.CompiledProgram(main).with_data_parallel(
                loss_name=loss.name, places=make_mesh([2], ["dp"]))
            for _ in range(2):
                exe.run(cp, feed=feed, fetch_list=[loss])
            exe.run(startup)  # restart from scratch mid-job
            pin()
            for _ in range(2):
                out = exe.run(cp, feed=feed, fetch_list=[loss])
        return float(np.asarray(out[0]).ravel()[0])
    finally:
        for k in env:
            os.environ.pop(k, None)


def test_sharded_update_state_resets_on_startup_rerun():
    """exe.run(startup) mid-job must reset the flat sharded optimizer
    state exactly like it resets the per-param accumulators — a
    restarted sharded run matches a restarted per-grad run
    bit-for-bit instead of keeping its trained moments."""
    snap = {}
    base = _cycle_with_restart({"PADDLE_TPU_BUCKET_MB": "0"}, snap)
    sh = _cycle_with_restart({"PADDLE_TPU_SHARDED_UPDATE": "1"}, snap)
    assert sh == base


def test_sharded_update_spares_grads_with_other_readers():
    """A grad some other op reads AFTER its allreduce (grad-norm
    logging, clipping, a fetch op) must keep its per-param
    (allreduce, update) pair: the sharded rewrite deletes the in-place
    reduction, so collapsing that pair would hand the reader the raw
    local gradient."""
    from paddle_tpu.parallel.transpiler import insert_allreduce_ops

    main, startup, _loss = _build(_momentum)
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        insert_allreduce_ops(main, 2)
        blk = main.global_block()
        watched = next(op.input("Grad")[0] for op in blk.ops
                       if op.type == "momentum")
        blk.append_op("scale", {"X": [watched]},
                      {"Out": ["grad_watch"]}, {"scale": 1.0})
        n = collectives.apply_sharded_weight_update(main, scope, 2)
    assert n == 1
    types = [op.type for op in blk.ops]
    assert types.count("c_sharded_update") == 1
    kept = [op for op in blk.ops if op.type == "momentum"]
    assert [op.input("Grad")[0] for op in kept] == [watched]
    kept_ar = [op for op in blk.ops if op.type == "c_allreduce_sum"]
    assert [op.input("X")[0] for op in kept_ar] == [watched]


def test_sharded_update_dense_fallback_matches():
    """The rewritten program still runs on a single device (no mesh),
    where c_sharded_update's dense path must match the per-param
    optimizer ops exactly. Both programs are transpiled the same way
    (1/n loss scale, identity collectives), so dense-vs-dense isolates
    the flat-update math."""
    import jax.numpy as jnp

    snap = {}

    def _dense_after_transpile(env):
        for k in KNOBS:
            os.environ.pop(k, None)
        os.environ.update(env)
        try:
            main, startup, loss = _build(_momentum)
            rng = np.random.RandomState(0)
            feed = {"x": rng.rand(16, 8).astype("float32"),
                    "lbl": rng.randint(0, 10, (16, 1)).astype("int64")}
            scope = fluid.Scope()
            with fluid.scope_guard(scope):
                exe = fluid.Executor(fluid.CPUPlace())
                exe.run(startup)
                blk = main.global_block()
                if not snap:
                    for name in blk.vars:
                        v = scope.find_var(name)
                        bv = blk._find_var_recursive(name)
                        if (v is not None and v.is_initialized()
                                and bv is not None and bv.persistable):
                            snap[name] = np.asarray(v.raw().array)
                # mesh run applies the rewrite (and one update step)
                cp = fluid.CompiledProgram(main).with_data_parallel(
                    loss_name=loss.name, places=make_mesh([2], ["dp"]))
                exe.run(cp, feed=feed, fetch_list=[loss])
                # rewind params + optimizer state, then run DENSE
                for name, arr in snap.items():
                    scope.var(name).get_tensor()._array = jnp.asarray(arr)
                for nm in scope.local_var_names():
                    if not (nm.startswith("sharded_update_")
                            and nm.endswith(".velocity")):
                        continue
                    var = scope.find_var(nm)
                    if var is not None and var.is_initialized():
                        z = np.zeros_like(np.asarray(var.raw().array))
                        scope.var(nm).get_tensor()._array = jnp.asarray(z)
                for _ in range(3):
                    out = exe.run(main, feed=feed, fetch_list=[loss])
                return float(np.asarray(out[0]).ravel()[0])
        finally:
            for k in env:
                os.environ.pop(k, None)

    dense_pergrad = _dense_after_transpile({"PADDLE_TPU_BUCKET_MB": "0"})
    dense_sharded = _dense_after_transpile(
        {"PADDLE_TPU_SHARDED_UPDATE": "1"})
    assert dense_sharded == dense_pergrad


# -- quantized allreduce ----------------------------------------------------


def test_quantized_psum_error_bounds():
    """Direct shard_map check of the wire formats: int8 error per
    element is bounded by n * scale / 2 with the shared per-bucket
    scale; bf16 error by n * one bf16 ulp of the largest element."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from paddle_tpu.ops.collective_ops import quantized_psum
    from paddle_tpu.parallel.mesh_utils import make_mesh

    n = 8
    mesh = make_mesh([n], ["dp"])
    rng = np.random.RandomState(3)
    x = (rng.randn(n, 4096) * np.exp(rng.uniform(-3, 3, (n, 1)))
         ).astype("float32")

    def body(mode):
        def f(xs):
            return quantized_psum(xs.reshape(-1), "dp", mode)[None, :]
        return jax.shard_map(f, mesh=mesh, in_specs=P("dp"),
                             out_specs=P("dp"), check_vma=False)

    exact = np.asarray(jax.jit(body("none"))(jnp.asarray(x)))[0]
    assert np.array_equal(exact, x.sum(0).astype("float32")) or \
        np.allclose(exact, x.sum(0), rtol=1e-6)

    q8 = np.asarray(jax.jit(body("int8"))(jnp.asarray(x)))[0]
    scale = np.abs(x).max() / 127.0
    bound8 = n * scale / 2.0 + 1e-12
    err8 = np.abs(q8 - exact).max()
    assert err8 <= bound8, (err8, bound8)

    qb = np.asarray(jax.jit(body("bf16"))(jnp.asarray(x)))[0]
    # bf16 has 8 mantissa bits -> relative step 2^-8 per addend
    boundb = n * np.abs(x).max() * 2.0 ** -8
    errb = np.abs(qb - exact).max()
    assert errb <= boundb, (errb, boundb)
    # and the compressed payloads really differ from exact (they are
    # lossy — identical output would mean the mode didn't engage)
    assert not np.array_equal(q8, exact)


def test_quantized_allreduce_mlp_converges():
    """ISSUE 6 gate: with int8 quantized allreduce ON, the mlp
    workload still trains — loss strictly drops and lands within
    QUANT_LOSS_TOL of the exact-path loss; the measured deviation is
    reported in the assertion message."""
    QUANT_LOSS_TOL = 0.05  # abs loss deviation after 8 steps

    snap = {}
    kw = dict(sizes=(64, 10), feat=32, batch=32, steps=8)
    l_first, _, _ = _run_mesh({"PADDLE_TPU_BUCKET_MB": "0"}, _adam, snap,
                              **dict(kw, steps=1))
    l_exact, _, _ = _run_mesh({"PADDLE_TPU_BUCKET_MB": "0"}, _adam, snap,
                              **kw)
    l_q, _, t = _run_mesh({"PADDLE_TPU_QUANT_ALLREDUCE": "int8"}, _adam,
                          snap, **kw)
    assert any(x == "c_bucket_allreduce" for x in t)
    assert np.isfinite(l_q)
    assert l_q < l_first, "quantized run did not reduce the loss"
    err = abs(l_q - l_exact)
    assert err <= QUANT_LOSS_TOL, (
        "quantized mlp loss %.6f vs exact %.6f: measured error %.6f "
        "exceeds tolerance %.3f" % (l_q, l_exact, err, QUANT_LOSS_TOL))


def test_quant_off_by_default():
    snap = {}
    _, _, t = _run_mesh({}, _momentum, snap)
    assert collectives.quant_mode() == "none"
    # default path: bucketed, exact
    assert t.count("c_bucket_allreduce") == 1


# -- observability: kind labels + bucketing win -----------------------------


def test_collective_counters_by_kind_and_bucketing_win():
    obs.enable()
    obs.metrics().reset()
    snap = {}
    _run_mesh({"PADDLE_TPU_BUCKET_MB": "0"}, _momentum, snap, steps=1)
    base = obs.counter_value("parallel.collective_ops")
    base_ar = obs.counter_value("parallel.collective_ops",
                                kind="allreduce")
    assert base == base_ar == 4
    assert obs.counter_value("parallel.collective_bytes",
                             kind="allreduce") > 0

    obs.metrics().reset()
    _run_mesh({}, _momentum, snap, steps=1)
    bucketed = obs.counter_value("parallel.collective_ops")
    assert bucketed < base  # bucketing strictly reduces op count
    assert bucketed == 1

    # bf16 genuinely halves the executed payload and reports the saving
    obs.metrics().reset()
    _run_mesh({"PADDLE_TPU_QUANT_ALLREDUCE": "bf16"}, _momentum, snap,
              steps=1)
    wire = obs.counter_value("parallel.collective_bytes")
    saved = obs.counter_value("parallel.collective_bytes_saved")
    assert saved == wire  # bf16 wire = exact/2

    # int8 codes psum in int32: the EXECUTED traffic does not shrink,
    # so the honest counter reports zero saving — the native-wire
    # figure is only ever a projection (bench quant_int8_bytes_saved)
    obs.metrics().reset()
    _run_mesh({"PADDLE_TPU_QUANT_ALLREDUCE": "int8"}, _momentum, snap,
              steps=1)
    assert (obs.counter_value("parallel.collective_bytes")
            == 2 * wire)  # int32 codes: full f32-width payload
    assert obs.counter_value("parallel.collective_bytes_saved") == 0

    # sharded update traffic splits into allreduce + allgather kinds
    obs.metrics().reset()
    _run_mesh({"PADDLE_TPU_SHARDED_UPDATE": "1"}, _momentum, snap,
              steps=1)
    assert obs.counter_value("parallel.collective_ops",
                             kind="allreduce") == 1
    assert obs.counter_value("parallel.collective_ops",
                             kind="allgather") == 1


# -- profile-guided bucket planning (ISSUE 10) ------------------------------


def test_bucket_plan_knob_parsing(monkeypatch):
    monkeypatch.delenv("PADDLE_TPU_BUCKET_PLAN", raising=False)
    assert collectives.bucket_plan_mode() == "size"
    for raw, want in (("profile", "profile"), ("SIZE", "size"),
                      ("static", "size"), ("", "size")):
        monkeypatch.setenv("PADDLE_TPU_BUCKET_PLAN", raw)
        assert collectives.bucket_plan_mode() == want
    monkeypatch.setenv("PADDLE_TPU_BUCKET_PLAN", "vibes")
    with pytest.raises(ValueError):
        collectives.bucket_plan_mode()


def test_load_profile_report(tmp_path, monkeypatch):
    monkeypatch.delenv("PADDLE_TPU_BUCKET_PROFILE", raising=False)
    assert collectives.load_profile_report() is None
    assert collectives.load_profile_report(
        str(tmp_path / "missing.json")) is None
    bad = tmp_path / "bad.json"
    bad.write_text("{not json")
    assert collectives.load_profile_report(str(bad)) is None
    # a report missing its measured fields is refused, not guessed at
    import json as _json

    inc = tmp_path / "inc.json"
    inc.write_text(_json.dumps({"per_bucket": []}))
    assert collectives.load_profile_report(str(inc)) is None
    good = {"per_bucket": [{"bytes": 8, "collective_ms": 1.0}],
            "backward_segments": [[0, 4, 2.0]], "n_compute": 9}
    ok = tmp_path / "ok.json"
    ok.write_text(_json.dumps(good))
    assert collectives.load_profile_report(str(ok)) == good
    # a bench record wrapping the report under "profile" unwraps
    wrapped = tmp_path / "wrapped.json"
    wrapped.write_text(_json.dumps({"loss": 1.0, "profile": good}))
    assert collectives.load_profile_report(str(wrapped)) == good
    # env-named path works too
    monkeypatch.setenv("PADDLE_TPU_BUCKET_PROFILE", str(ok))
    assert collectives.load_profile_report() == good


def test_plan_buckets_profile_splits_early_merges_tail():
    K = (0, "float32")
    # measured story: backward spans positions [0, 10) and takes 10ms;
    # the (single) measured bucket cost 10ms for 100 bytes => slope
    # 0.1 ms/B, intercept 0.1*10ms = 1ms
    report = {"backward_segments": [[0, 10, 10.0]],
              "per_bucket": [{"bytes": 100, "collective_ms": 10.0}],
              "n_compute": 11}
    # grads early in backward: each alone costs 1+3=4ms <= 0.5*10ms,
    # together 1+6=7ms > 5ms budget -> the planner must split where
    # the size plan (huge cap) would have merged them
    items = [(0, 100, K, 30, 0), (2, 100, K, 30, 1),
             # grads at the very end of backward (hide budget 0):
             # merged into ONE tail bucket, not per-grad
             (9, 100, K, 30, 2), (9, 100, K, 40, 3)]
    buckets = collectives.plan_buckets_profile(
        items, report, bucket_bytes=1 << 20,
        compute_pos=lambda a: a + 1)
    assert [b["members"] for b in buckets] == [[0], [1], [2, 3]]
    # the same items under the size plan: one late bucket — the
    # measurement is what changed the schedule
    assert [b["members"] for b in collectives.plan_buckets(
        items, 1 << 20)] == [[0, 1, 2, 3]]
    # byte cap still binds in profile mode
    capped = collectives.plan_buckets_profile(
        items, report, bucket_bytes=35, compute_pos=lambda a: a + 1)
    assert all(b["bytes"] <= 35 or len(b["members"]) == 1
               for b in capped)
    # an unusable report (no measured cost) refuses to plan
    assert collectives.plan_buckets_profile(
        items, {"backward_segments": [[0, 10, 10.0]], "per_bucket": []},
        1 << 20, compute_pos=lambda a: a + 1) is None


def test_profile_plan_bit_for_bit(tmp_path):
    """The replanned program must stay bit-for-bit with the per-grad
    path (the same psum algebra as any bucketing) while demonstrably
    using a DIFFERENT, measurement-driven bucket layout."""
    import json as _json

    # a report shaped for the test model: positions from the plain
    # program (compute ops are identical under any bucket plan)
    with fluid.unique_name.guard():
        main, _startup, _loss = _build(_momentum)
    from paddle_tpu.observability.profiler import classify_ops

    phases = classify_ops(main.global_block())
    n_compute = len(phases)
    fwd_end = sum(1 for p in phases if p == "forward")
    bwd_end = sum(1 for p in phases if p in ("forward", "backward"))
    report = {"n_compute": n_compute,
              "backward_segments": [[fwd_end, bwd_end, 10.0]],
              # slope steep enough that coalescing ALL grads blows the
              # hide budget -> the profile plan must split
              "per_bucket": [{"bytes": 256, "collective_ms": 1.0}]}
    rpt = tmp_path / "report.json"
    rpt.write_text(_json.dumps(report))

    snap = {}
    base_loss, base, t0 = _run_mesh({"PADDLE_TPU_BUCKET_MB": "0"},
                                    _momentum, snap)
    prof_loss, prof_state, t1 = _run_mesh(
        {"PADDLE_TPU_BUCKET_PLAN": "profile",
         "PADDLE_TPU_BUCKET_PROFILE": str(rpt)}, _momentum, snap)
    assert t0.count("c_allreduce_sum") == 4
    assert "c_allreduce_sum" not in t1
    # the measurement split the plan (the size plan coalesces these 4
    # grads into ONE bucket — test_bucketed_allreduce_bit_for_bit)
    assert t1.count("c_bucket_allreduce") >= 2
    assert prof_loss == base_loss
    _assert_params_equal(base, prof_state)


def test_profile_plan_falls_back_without_report(tmp_path):
    """plan=profile with a missing/stale report must quietly use the
    size plan — a deleted report file can never break training."""
    snap = {}
    _, base, t_default = _run_mesh({}, _momentum, snap)
    # missing file
    _, got, t1 = _run_mesh(
        {"PADDLE_TPU_BUCKET_PLAN": "profile",
         "PADDLE_TPU_BUCKET_PROFILE": str(tmp_path / "nope.json")},
        _momentum, snap)
    assert t1 == t_default
    _assert_params_equal(base, got)
    # stale report (n_compute mismatch): detected, ignored
    import json as _json

    stale = tmp_path / "stale.json"
    stale.write_text(_json.dumps(
        {"n_compute": 99999, "backward_segments": [[0, 5, 1.0]],
         "per_bucket": [{"bytes": 8, "collective_ms": 1.0}]}))
    _, got2, t2 = _run_mesh(
        {"PADDLE_TPU_BUCKET_PLAN": "profile",
         "PADDLE_TPU_BUCKET_PROFILE": str(stale)}, _momentum, snap)
    assert t2 == t_default
    _assert_params_equal(base, got2)
