"""Placement synthesis (ISSUE 15): cost-model fitting, the plan
artifact, the verifier-gated search, the new scheduling passes, and
EQuARX error feedback.

Numerics contract under test:
- the async start/await split is BIT-FOR-BIT with the fused bucket
  path (same flat psum, sliced one op later);
- the tree / two_stage reduction spellings re-associate the same sum
  (exact for integer int8 codes, tight-tolerance for floats);
- int8 + error feedback tracks the bf16 loss trajectory within the
  existing int8 tolerance, and the residual provably cancels the
  quantization bias a feedback-less int8 reduction accumulates.
"""
import json
import os

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu.observability import steering
from paddle_tpu.parallel import scheduling
from paddle_tpu.parallel.mesh_utils import make_mesh
from paddle_tpu.placement import (PlacementPlan, analytic_cost_model,
                                  enumerate_meshes, fit_cost_model,
                                  load_plan, save_plan,
                                  search_placement)
from paddle_tpu.placement.cost_model import strategy_factors

KNOBS = ("PADDLE_TPU_BUCKET_MB", "PADDLE_TPU_QUANT_ALLREDUCE",
         "PADDLE_TPU_SHARDED_UPDATE", "PADDLE_TPU_BUCKET_PLAN",
         "PADDLE_TPU_BUCKET_PROFILE", "PADDLE_TPU_REDUCE_STRATEGY",
         "PADDLE_TPU_ASYNC_COLLECTIVES",
         "PADDLE_TPU_QUANT_ERROR_FEEDBACK",
         "PADDLE_TPU_PLACEMENT_PLAN")


@pytest.fixture(autouse=True)
def _clean_knobs(monkeypatch):
    for k in KNOBS:
        monkeypatch.delenv(k, raising=False)
    yield


# -- model + mesh helpers ----------------------------------------------------


def _build():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        x = fluid.data(name="x", shape=[16, 8], dtype="float32")
        lbl = fluid.data(name="lbl", shape=[16, 1], dtype="int64")
        h = fluid.layers.fc(x, size=32, act="relu")
        pred = fluid.layers.fc(h, size=10, act="softmax")
        loss = fluid.layers.mean(fluid.layers.cross_entropy(pred, lbl))
        fluid.optimizer.MomentumOptimizer(0.1, 0.9).minimize(loss)
    return main, startup, loss


def _builder():
    main, _startup, loss = _build()
    return main, loss.name


def _run_mesh(env, snap, steps=3, n=8):
    """Fresh program trained ``steps`` steps on an n-way dp mesh under
    the given knob env; params seeded from (or recorded into) snap."""
    import jax.numpy as jnp

    for k in KNOBS:
        os.environ.pop(k, None)
    os.environ.update(env)
    try:
        main, startup, loss = _build()
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
                if (v is not None and v.is_initialized()
                        and bv is not None
                        and getattr(bv, "persistable", False)):
                    state[name] = np.asarray(v.raw().array)
        ctypes = [op.type for op in main.global_block().ops
                  if op.type.startswith("c_")]
        return float(np.asarray(out[0]).ravel()[0]), state, ctypes, main
    finally:
        for k in env:
            os.environ.pop(k, None)


def _assert_equal(a, b, skip=()):
    for k, va in a.items():
        if any(s in k.lower() for s in skip):
            continue
        assert np.array_equal(va, b[k]), k


# -- cost model --------------------------------------------------------------


def _canned_report(a=0.5, b=2e-3, n_pts=4):
    """per_bucket points generated from a KNOWN a + b*bytes line."""
    pts = [{"bytes": x, "collective_ms": a + b * x,
            "kind": "allreduce", "strategy": "ring", "quant": "none"}
           for x in (1024.0 * (i + 1) for i in range(n_pts))]
    return {"per_bucket": pts,
            "backward_segments": [[4, 12, 10.0]],
            "phase_ms": {"forward": 5.0, "backward": 10.0,
                         "optimizer": 2.0},
            "overlap_frac": 0.5, "n_compute": 15, "nranks": 8,
            "step_ms": 20.0, "exposed_collective_ms": 1.0}


def test_fit_recovers_coefficients():
    a, b = 0.5, 2e-3
    m = fit_cost_model(_canned_report(a, b), nranks=8)
    fa, fb = m.terms["allreduce"]
    assert abs(fa - a) < 1e-6 and abs(fb - b) < 1e-9
    assert m.term_provenance("allreduce") == "fitted"
    # a kind the report never measured stays analytic — and taints
    # every score that consumes it
    assert m.term_provenance("allgather") == "analytic"
    assert m.provenance == "fitted"
    assert m.compute_ms == pytest.approx(17.0)
    # fixed overhead = step_ms - compute - exposed = 20 - 17 - 1
    assert m.overhead_ms == pytest.approx(2.0)
    # prediction through the fitted terms at the measured point
    pred = m.predict([{"kind": "allreduce", "bytes": 2048.0,
                       "avail_pos": None, "strategy": "ring"}])
    assert pred["provenance"] == "fitted"
    assert pred["step_ms"] == pytest.approx(
        17.0 + 2.0 + a + b * 2048.0)


def test_fit_accepts_bench_profile_block_keys():
    """A bench record's profile block — the documented report source —
    spells the whole-step time 'profiled_step_ms'; the overhead anchor
    must fire on it exactly like on the raw profiler's 'step_ms'."""
    rep = _canned_report()
    rep["profiled_step_ms"] = rep.pop("step_ms")
    m = fit_cost_model(rep, nranks=8)
    assert m.overhead_ms == pytest.approx(2.0)


def test_fit_single_point_floor():
    # one measured point: intercept = 10% of the cost (PR-10 rule)
    m = fit_cost_model(_canned_report(n_pts=1), nranks=8)
    fa, fb = m.terms["allreduce"]
    y = 0.5 + 2e-3 * 1024.0
    assert fa == pytest.approx(0.1 * y)
    assert fa + fb * 1024.0 == pytest.approx(y)


def test_analytic_fallback():
    for bad in (None, {}, {"per_bucket": []},
                {"per_bucket": [], "backward_segments": "nope"}):
        m = fit_cost_model(bad, nranks=8)
        assert m.provenance == "analytic"
        assert not m.fitted_kinds
    m = analytic_cost_model(8, compute_ms=1.0)
    pred = m.predict([{"kind": "allreduce", "bytes": 1 << 20,
                       "avail_pos": None}])
    assert pred["provenance"] == "analytic"
    assert pred["step_ms"] > 1.0


def test_strategy_factors_and_transfer():
    # factors price what strategy_psum EXECUTES: tree = ring's bytes
    # plus one extra collective launch; two_stage = one full-payload
    # psum per axis (more busiest-link bytes than the fused psum)
    r_ln, r_bw = strategy_factors("ring", 8)
    t_ln, t_bw = strategy_factors("tree", 8)
    assert t_ln > r_ln and t_bw == r_bw
    ts_ln, ts_bw = strategy_factors("two_stage", 8, (4, 2))
    assert ts_ln == 2.0 and ts_bw > r_bw
    m = fit_cost_model(_canned_report(a=1.0, b=1e-5), nranks=8)
    assert m.collective_ms("allreduce", 64, "ring") < \
        m.collective_ms("allreduce", 64, "tree")
    # tree's surcharge is exactly the extra launch — byte-independent
    d_small = m.collective_ms("allreduce", 64, "tree") \
        - m.collective_ms("allreduce", 64, "ring")
    d_big = m.collective_ms("allreduce", 1 << 26, "tree") \
        - m.collective_ms("allreduce", 1 << 26, "ring")
    assert d_small == pytest.approx(d_big)


def test_unmeasured_quant_pays_compute_penalty():
    """The emulated quantized wire is not free: a quant mode the
    report never measured must carry the analytic cast/scale penalty
    (and taint provenance) — otherwise the search calls bf16 a win on
    byte count alone and measures 40% slower."""
    m = fit_cost_model(_canned_report(), nranks=8)  # measured exact
    nbytes = 1 << 20
    exact = m.collective_ms("allreduce", nbytes)
    bf16 = m.collective_ms("allreduce", nbytes / 2, quant="bf16")
    assert bf16 > m.collective_ms("allreduce", nbytes / 2)
    assert m.quant_penalty_ms("bf16", nbytes) > 0
    assert m.quant_penalty_ms("none", nbytes) == 0.0
    pred = m.predict([{"kind": "allreduce", "bytes": nbytes,
                       "avail_pos": None, "quant": "int8"}])
    assert pred["provenance"] == "analytic"  # penalty is a hand number
    # a report MEASURED under bf16 carries the cost in its fitted line
    rep = _canned_report()
    for b in rep["per_bucket"]:
        b["quant"] = "bf16"
    m2 = fit_cost_model(rep, nranks=8)
    assert m2.quant_penalty_ms("bf16", nbytes) == 0.0
    assert exact > 0  # silence unused warnings


def test_derive_quant_buckets_flips_only_wire_bound():
    from paddle_tpu.placement.cost_model import CostModel
    from paddle_tpu.placement.search import derive_quant_buckets

    sched = [{"op": "c_bucket_allreduce", "kind": "allreduce",
              "bytes": 4 << 20, "avail_pos": 2, "strategy": "ring"},
             {"op": "c_bucket_allreduce", "kind": "allreduce",
              "bytes": 64, "avail_pos": 8, "strategy": "ring"}]
    # emulated-wire magnitudes (the smoke measures b ~ 5e-6 ms/B on
    # this host class, below the cast penalty): nothing flips
    m = fit_cost_model(_canned_report(b=5e-6), nranks=8)
    assert derive_quant_buckets(sched, m) is None
    # a wire where bytes utterly dominate (fitted b huge) and whose
    # report measured bf16 (penalty inside the fitted line): the big
    # bucket flips, the tiny latency-bound one stays exact
    wire = CostModel(nranks=8, terms={"allreduce": (0.01, 1e-4)},
                     compute_ms=1.0, backward_segments=[],
                     fitted_kinds=frozenset({"allreduce"}),
                     base_quant="bf16", compute_fitted=True)
    modes = derive_quant_buckets(sched, wire)
    assert modes is not None and modes[0] == "bf16"


def test_predict_overlap_and_async_bonus():
    m = fit_cost_model(_canned_report(), nranks=8)
    sched = [{"kind": "allreduce", "bytes": 1024.0, "avail_pos": 5,
              "strategy": "ring"}]
    sync = m.predict(sched, async_scheduled=False)
    asy = m.predict(sched, async_scheduled=True)
    # measured overlap_frac 0.5 + async bonus hides strictly more
    assert asy["exposed_ms"] < sync["exposed_ms"]
    assert asy["overlap_eff"] > sync["overlap_eff"]
    # a tail collective (no budget after its anchor) is fully exposed
    tail = m.predict([{"kind": "allreduce", "bytes": 1024.0,
                       "avail_pos": 14, "strategy": "ring"}])
    assert tail["exposed_ms"] == pytest.approx(
        tail["collective_ms"])


# -- plan artifact -----------------------------------------------------------


def test_plan_round_trip(tmp_path):
    plan = PlacementPlan(mesh=[("dp", 8)], strategy="tree",
                         bucket_mb=2.0, quant_mode="int8",
                         error_feedback=True, async_collectives=True,
                         model="mlp")
    p = str(tmp_path / "plan.json")
    d = save_plan(plan, p)
    got = load_plan(p)
    assert got.digest == d == plan.digest
    assert got.strategy == "tree" and got.error_feedback
    # canonical: re-save is byte-identical
    p2 = str(tmp_path / "plan2.json")
    save_plan(got, p2)
    assert open(p).read() == open(p2).read()


def test_plan_rejects_corruption(tmp_path):
    plan = PlacementPlan(mesh=[("dp", 8)])
    p = str(tmp_path / "plan.json")
    save_plan(plan, p)
    doc = json.load(open(p))
    doc["strategy"] = "tree"  # edit without re-digesting
    with open(p, "w") as f:
        json.dump(doc, f)
    with pytest.raises(ValueError, match="digest mismatch"):
        load_plan(p)
    with pytest.raises(ValueError):
        PlacementPlan(mesh=[("dp", 8)], strategy="vibes")
    with pytest.raises(ValueError):
        PlacementPlan(mesh=[("dp", 8)], bucket_plan_mode="profile",
                      report=None)


def test_plan_matches():
    plan = PlacementPlan(mesh=[("dp", 8)])
    assert plan.matches(8, ("dp",))
    assert not plan.matches(4, ("dp",))
    hybrid = PlacementPlan(mesh=[("dp", 4), ("sp", 2)])
    assert hybrid.matches(8, ("dp", "sp"))
    assert not hybrid.matches(8, ("dp",))


# -- mesh enumeration + search ----------------------------------------------


def test_enumerate_meshes_capability_gated():
    sup, unsup = enumerate_meshes(8, frozenset({"dp"}))
    assert (("dp", 8),) in sup
    assert len(sup) == 1  # a dp-only model supports exactly one mesh
    assert unsup and all("unsupported" == u["status"] for u in unsup)
    sup2, _ = enumerate_meshes(8, frozenset({"dp", "mp"}))
    assert (("dp", 4), ("mp", 2)) in sup2
    # every enumerated factorization multiplies to the device count
    for mesh in sup2:
        n = 1
        for _a, s in mesh:
            n *= s
        assert n == 8


def test_search_deterministic_and_verifier_gated():
    report = _canned_report()
    # shape the report for the real model (n_compute must match)
    from paddle_tpu.observability.profiler import classify_ops
    from paddle_tpu.parallel.transpiler import insert_allreduce_ops

    probe, _, _ = _build()
    insert_allreduce_ops(probe, 8)
    phases = classify_ops(probe.global_block())
    report["n_compute"] = sum(1 for p in phases if p != "collective")

    plan1, audit1 = search_placement(_builder, 8, report=report,
                                     beam_width=4, model="mlp")
    plan2, audit2 = search_placement(_builder, 8, report=report,
                                     beam_width=4, model="mlp")
    assert plan1 is not None
    assert plan1.digest == plan2.digest  # same report+seed, same plan
    rows = audit1["candidates"]
    assert rows and all(r["verified"] for r in rows)
    assert not any(r["traced"] for r in rows)
    assert audit1["traced_before_verify"] == 0
    assert audit1["rejected"] == 0
    assert audit1["cost_provenance"] == "fitted"
    # hybrid factorizations are recorded as unsupported, not dropped
    assert audit1["unsupported"]
    assert plan1.predicted_step_ms > 0
    assert plan1.schedule_digest


def test_search_dedups_equivalent_candidates():
    # without a report the profile bucket dim is absent and several
    # spellings collapse to identical schedules — dedup must fire
    _plan, audit = search_placement(_builder, 8, report=None,
                                    beam_width=4, model="mlp")
    assert audit["deduped"] > 0
    assert audit["cost_provenance"] == "analytic"


# -- steering registry -------------------------------------------------------


def test_steering_registry():
    names = steering.steerers()
    assert "bucket_layout" in names    # the PR-10 planner
    assert "placement" in names        # this PR's search
    with pytest.raises(KeyError):
        steering.steer("no_such_steerer", None)
    # dispatch reaches the search (builder-less call must complain
    # about context, not about dispatch)
    with pytest.raises(ValueError, match="builder"):
        steering.steer("placement", None)


def test_steering_load_report(tmp_path, monkeypatch):
    monkeypatch.delenv("PADDLE_TPU_BUCKET_PROFILE", raising=False)
    assert steering.load_report() is None
    good = {"per_bucket": [], "backward_segments": []}
    p = tmp_path / "r.json"
    p.write_text(json.dumps({"profile": good, "loss": 1.0}))
    assert steering.load_report(str(p)) == good
    assert steering.coerce_report({"per_bucket": []}) is None


# -- scheduling passes on the mesh (execution parity) ------------------------


def test_async_split_bit_for_bit():
    snap = {}
    base_loss, base, t0, _ = _run_mesh({"PADDLE_TPU_BUCKET_MB": "0"},
                                       snap)
    a_loss, a_state, t1, main = _run_mesh(
        {"PADDLE_TPU_ASYNC_COLLECTIVES": "1",
         "PADDLE_TPU_BUCKET_MB": "0.00001"}, snap)
    assert t1.count("c_bucket_allreduce_start") >= 2
    assert (t1.count("c_bucket_allreduce_await")
            == t1.count("c_bucket_allreduce_start"))
    assert a_loss == base_loss
    _assert_equal(base, a_state)
    rec = getattr(main, "_async_schedule", None)
    assert rec and rec["split"] >= 2


def test_async_keeps_no_slack_buckets():
    # ONE whole-step bucket sits right before its first consumer — the
    # pass must refuse to split it (no room = no win, one extra op)
    main, _startup, _loss = _build()
    from paddle_tpu.parallel.collectives import bucket_allreduce_ops
    from paddle_tpu.parallel.transpiler import insert_allreduce_ops

    insert_allreduce_ops(main, 8)
    bucket_allreduce_ops(main, bucket_bytes=4 << 20)
    n = scheduling.schedule_async_collectives(main)
    assert n == 0
    assert main._async_schedule["kept"] == 1


def test_reduction_strategy_parity():
    snap = {}
    base_loss, base, _t0, _ = _run_mesh({}, snap)
    tree_loss, tree, t1, _ = _run_mesh(
        {"PADDLE_TPU_REDUCE_STRATEGY": "tree"}, snap)
    assert t1.count("c_bucket_allreduce") >= 1
    # re-associated float sum: tight tolerance, not bitwise
    assert tree_loss == pytest.approx(base_loss, abs=1e-5)
    for k, v in base.items():
        assert np.allclose(v, tree[k], atol=1e-5), k


def test_strategy_psum_spellings_two_stage():
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from paddle_tpu.ops.collective_ops import strategy_psum
    mesh = make_mesh([4, 2], ["dp", "sp"])
    x = jnp.arange(8 * 6, dtype=jnp.float32).reshape(8, 6)

    def run(strategy):
        def body(v):
            return strategy_psum(v, ("dp", "sp"), strategy)

        return np.asarray(jax.jit(jax.shard_map(
            body, mesh=mesh, in_specs=P(("dp", "sp")), out_specs=P(),
            check_vma=False))(x))

    want = run("ring")
    np.testing.assert_allclose(run("two_stage"), want, rtol=1e-6)
    np.testing.assert_allclose(run("tree"), want, rtol=1e-6)
    with pytest.raises(ValueError, match="unknown reduction strategy"):
        run("vibes")


def test_swap_strategy_knob_parsing(monkeypatch):
    assert scheduling.reduce_strategy_mode() == "ring"
    for raw, want in (("tree", "tree"), ("TWO_STAGE", "two_stage"),
                      ("ring", "ring"), ("auto", "ring")):
        monkeypatch.setenv("PADDLE_TPU_REDUCE_STRATEGY", raw)
        assert scheduling.reduce_strategy_mode() == want
    monkeypatch.setenv("PADDLE_TPU_REDUCE_STRATEGY", "vibes")
    with pytest.raises(ValueError):
        scheduling.reduce_strategy_mode()


# -- EQuARX error feedback ---------------------------------------------------


def test_error_feedback_cancels_bias():
    """A constant gradient reduced with int8 rounding: WITHOUT
    feedback the same rounding error recurs every step (bias
    accumulates linearly in the sum over steps); WITH the residual the
    error feeds back and the accumulated sum tracks the true one."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from paddle_tpu.ops.collective_ops import quantized_psum
    n = 8
    mesh = make_mesh([n], ["dp"])
    rng = np.random.RandomState(7)
    base = rng.randn(n, 64).astype(np.float32)
    true_sum = base.sum(axis=0)

    def step_ef(x, r):
        out, new_r = quantized_psum(x, "dp", "int8", "ring", r)
        return out, new_r

    def step_plain(x):
        return quantized_psum(x, "dp", "int8")

    f_ef = jax.jit(jax.shard_map(
        step_ef, mesh=mesh, in_specs=(P("dp"), P("dp")),
        out_specs=(P(), P("dp")), check_vma=False))
    f_plain = jax.jit(jax.shard_map(
        step_plain, mesh=mesh, in_specs=P("dp"), out_specs=P(),
        check_vma=False))

    steps = 16
    r = jnp.zeros_like(jnp.asarray(base))
    acc_ef = np.zeros(64, np.float64)
    acc_plain = np.zeros(64, np.float64)
    for _ in range(steps):
        out, r = f_ef(jnp.asarray(base), r)
        acc_ef += np.asarray(out, np.float64).reshape(-1)
        acc_plain += np.asarray(f_plain(jnp.asarray(base)),
                                np.float64).reshape(-1)
    err_ef = np.abs(acc_ef - steps * true_sum).mean()
    err_plain = np.abs(acc_plain - steps * true_sum).mean()
    # feedback keeps the accumulated error near ONE step's rounding;
    # the plain path repeats it every step
    assert err_ef < err_plain / 4, (err_ef, err_plain)


def test_int8_error_feedback_tracks_bf16_trajectory():
    snap = {}
    losses = {}
    for tag, env in (
            ("bf16", {"PADDLE_TPU_QUANT_ALLREDUCE": "bf16"}),
            ("int8ef", {"PADDLE_TPU_QUANT_ALLREDUCE": "int8",
                        "PADDLE_TPU_QUANT_ERROR_FEEDBACK": "1"})):
        loss, _state, ctypes, main = _run_mesh(env, snap, steps=8)
        losses[tag] = loss
        assert ctypes.count("c_bucket_allreduce") >= 1
        if tag == "int8ef":
            ops = [op for op in main.global_block().ops
                   if op.type == "c_bucket_allreduce"]
            assert all(op.input("Residual") for op in ops), \
                "error feedback did not wire residuals"
    # the existing int8 tolerance (test_collectives pins 0.05 abs on
    # the mlp convergence path)
    assert abs(losses["int8ef"] - losses["bf16"]) < 0.05, losses


# -- plan application through the engine ------------------------------------


def test_plan_applies_through_engine(tmp_path):
    plan = PlacementPlan(mesh=[("dp", 8)], strategy="ring",
                         sharded_update=False, bucket_mb=0.00001,
                         async_collectives=True, model="mlp",
                         predicted_step_ms=12.5)
    path = str(tmp_path / "plan.json")
    save_plan(plan, path)
    snap = {}
    base_loss, base, _t, _ = _run_mesh({"PADDLE_TPU_BUCKET_MB": "0"},
                                       snap)
    loss, state, ctypes, main = _run_mesh(
        {"PADDLE_TPU_PLACEMENT_PLAN": path}, snap)
    # the plan (not the env defaults) drove the rewrite: tiny cap =>
    # per-grad buckets, async on => start/await pairs
    assert ctypes.count("c_bucket_allreduce_start") >= 2
    rec = getattr(main, "_placement_plan", None)
    assert rec and rec["plan_digest"] == plan.digest
    assert rec["predicted_step_ms"] == 12.5
    assert loss == base_loss
    _assert_equal(base, state)


def test_plan_mesh_mismatch_skipped(tmp_path):
    plan = PlacementPlan(mesh=[("dp", 4)], strategy="tree",
                         async_collectives=True)
    path = str(tmp_path / "plan.json")
    save_plan(plan, path)
    snap = {}
    _base_loss, base, t0, _ = _run_mesh({}, snap)
    loss, state, t1, main = _run_mesh(
        {"PADDLE_TPU_PLACEMENT_PLAN": path}, snap)
    # wrong fan-in: the plan is ignored wholesale, env defaults apply
    assert t1 == t0
    assert getattr(main, "_placement_plan", None) is None
    _assert_equal(base, state)


def test_sharded_plan_skipped_wholesale_on_unsupported_topology(
        tmp_path, monkeypatch):
    """A sharded-update plan on a topology where the fused update
    cannot run (multi-data-axis mesh) must be skipped WHOLESALE — the
    bucket/strategy half must not apply while the update it was priced
    with silently drops."""
    from paddle_tpu.parallel.collectives import maybe_rewrite_collectives
    from paddle_tpu.parallel.transpiler import (_merge_data_axes,
                                                insert_allreduce_ops)
    from paddle_tpu.placement import plan as plan_mod

    plan = PlacementPlan(mesh=[("dp", 4), ("sp", 2)],
                         sharded_update=True, strategy="tree",
                         bucket_mb=0.00001)
    path = str(tmp_path / "plan.json")
    save_plan(plan, path)
    monkeypatch.setenv("PADDLE_TPU_PLACEMENT_PLAN", path)
    plan_mod._plan_cache.clear()
    main, _startup, _loss = _build()
    _merge_data_axes(main, ("dp", "sp"))
    insert_allreduce_ops(main, 8)
    scope = fluid.Scope()
    maybe_rewrite_collectives(main, scope, 8, ("dp", "sp"))
    types = [op.type for op in main.global_block().ops]
    assert "c_sharded_update" not in types
    # the plan's tiny-cap/tree half did NOT leak in: default 4MB size
    # plan coalesces everything into one ring bucket
    buckets = [op for op in main.global_block().ops
               if op.type == "c_bucket_allreduce"]
    assert len(buckets) == 1
    assert buckets[0].attrs.get("strategy", "ring") == "ring"
    assert getattr(main, "_placement_plan", None) is None


def test_unreadable_plan_degrades(tmp_path, monkeypatch):
    from paddle_tpu.placement import plan as plan_mod

    p = tmp_path / "garbage.json"
    p.write_text("{not json")
    monkeypatch.setenv("PADDLE_TPU_PLACEMENT_PLAN", str(p))
    plan_mod._plan_cache.clear()
    assert plan_mod.active_plan() is None
    # memoized: a second call doesn't re-read the file
    assert plan_mod.active_plan() is None
