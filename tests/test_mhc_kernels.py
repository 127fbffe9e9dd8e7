"""The hyper-connection ops on their Pallas kernels (interpret mode, on the
CPU) against their XLA form, ``maps`` / ``mix_in`` / ``mix_out`` and their
``jax.vjp``, at a shape that fills the kernels' blocks: every output of the
two forward ops and every gradient of the two gradient ops; which form
``hyper_path`` picks from the platform and the shapes; the counter that says
which one a traced ``mhc_pre`` took."""
import functools

import jax
import jax.numpy as jnp
import pytest

from paddle_tpu.core.registry import OpInfoMap
from paddle_tpu.ops import hyper_connection_ops as hc
from paddle_tpu.ops.pallas import hyper_connection as kernels

N, T, C = 4, 256, 256
PRE = ("X", "Phi", "Alpha", "BPre", "BPost", "BRes")
ATTRS = {"sinkhorn_iters": 20, "epsilon": 1e-6, "clamp_min": -30.0,
         "clamp_max": 30.0}


def op(name):
    return OpInfoMap.instance().get(name).fn


def rel(a, b):
    b = jnp.asarray(b, jnp.float32)
    return float(jnp.max(jnp.abs(jnp.asarray(a, jnp.float32) - b))
                 / (jnp.max(jnp.abs(b)) + 1e-30))


@pytest.fixture
def kernels_here(monkeypatch):
    """The program's question answered as a TPU would, and the kernels it
    then takes run in interpret mode, as the scan's tests do."""
    monkeypatch.setattr(hc._fa, "compute_platform", lambda: "tpu")
    for name in kernels.ENTRIES:
        monkeypatch.setattr(kernels, name, functools.partial(
            getattr(kernels, name), interpret=True))


def point(seed=5, batch=2, t=T, c=C):
    """A stirred point: unequal streams, every leaf random (the rounds have
    work to do), a sublayer's output and a cotangent for every output."""
    k = jax.random.split(jax.random.key(seed), 12)
    normal = jax.random.normal
    pre = {"X": normal(k[0], (batch, N, t, c)),
           "Phi": 0.1 * normal(k[1], (N * c, 2 * N + N * N)),
           "Alpha": normal(k[2], (3,)), "BPre": normal(k[3], (N,)),
           "BPost": normal(k[4], (N,)), "BRes": normal(k[5], (N, N))}
    cts = {"H@GRAD": normal(k[6], (batch, t, c)),
           "HPost@GRAD": normal(k[7], (batch, N, t)),
           "HRes@GRAD": normal(k[8], (batch, N, N, t)),
           "Out@GRAD": normal(k[9], (batch, N, t, c))}
    return pre, normal(k[10], (batch, t, c)), cts


def both_forms(monkeypatch, run):
    """``run()`` on the kernels (the fixture's answer), then in the XLA
    form."""
    assert hc.hyper_path(point()[0]["X"]) == "pallas"
    with jax.default_matmul_precision("highest"):
        got = run()
        monkeypatch.setattr(hc._fa, "compute_platform", lambda: "cpu")
        assert hc.hyper_path(point()[0]["X"]) == "xla"
        return got, run()


def forward():
    pre, y, _ = point()
    out = op("mhc_pre")(pre, ATTRS)
    out["Out"] = op("mhc_post")({"X": pre["X"], "HRes": out["HRes"],
                                 "HPost": out["HPost"], "Y": y}, {})["Out"]
    return out


def gradients():
    pre, y, cts = point()
    maps = op("mhc_pre")(pre, ATTRS)
    got = op("mhc_pre_grad")(dict(pre, **{
        k: cts[k] for k in ("H@GRAD", "HPost@GRAD", "HRes@GRAD")}), ATTRS)
    post = op("mhc_post_grad")(
        {"X": pre["X"], "HRes": maps["HRes"], "HPost": maps["HPost"],
         "Y": y, "Out@GRAD": cts["Out@GRAD"]}, {})
    return dict({"pre." + k: v for k, v in got.items()},
                **{"post." + k: v for k, v in post.items()})


@pytest.fixture(scope="module")
def computed():
    """Both tests' results in both forms, made once for all their cases."""
    return {}


def in_both_forms(computed, monkeypatch, run):
    if run.__name__ not in computed:
        computed[run.__name__] = both_forms(monkeypatch, run)
    return computed[run.__name__]


@pytest.mark.parametrize("name", ["H", "HPost", "HRes", "Out"])
def test_the_forward_ops_on_the_kernels_are_the_xla_form(
        kernels_here, monkeypatch, computed, name):
    got, want = in_both_forms(computed, monkeypatch, forward)
    assert got[name].shape == want[name].shape
    assert got[name].dtype == want[name].dtype == jnp.float32
    assert rel(got[name], want[name]) < 1e-5


@pytest.mark.parametrize("name", ["pre." + n + "@GRAD" for n in PRE] + [
    "post." + n + "@GRAD" for n in ("X", "HRes", "HPost", "Y")])
def test_the_gradient_ops_on_the_kernels_are_the_xla_forms_vjp(
        kernels_here, monkeypatch, computed, name):
    got, want = in_both_forms(computed, monkeypatch, gradients)
    assert set(got) == set(want)
    assert got[name].shape == want[name].shape
    assert got[name].dtype == want[name].dtype
    assert rel(got[name], want[name]) < 1e-5


def test_a_missing_cotangent_is_zero_in_either_form(kernels_here,
                                                    monkeypatch):
    """``mhc_pre_grad`` with only ``H@GRAD`` bound, and ``Y`` in bfloat16:
    ``dY`` comes back in Y's type."""
    pre, y, cts = point(7, batch=1, t=64, c=128)

    def run():
        return (op("mhc_pre_grad")(dict(pre, **{"H@GRAD": cts["H@GRAD"]}),
                                   ATTRS),
                op("mhc_post_grad")(
                    {"X": pre["X"], "HRes": jnp.abs(cts["HRes@GRAD"]),
                     "HPost": cts["HPost@GRAD"], "Y": y.astype(jnp.bfloat16),
                     "Out@GRAD": cts["Out@GRAD"]}, {}))

    (pre_got, post_got), (pre_want, post_want) = both_forms(monkeypatch, run)
    for name, ref in pre_want.items():
        assert rel(pre_got[name], ref) < 1e-5, name
    assert post_got["Y@GRAD"].dtype == jnp.bfloat16
    for name, ref in post_want.items():
        assert rel(post_got[name], ref) < (
            1e-2 if name == "Y@GRAD" else 1e-5), name


def like(shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype)


@pytest.mark.parametrize("platform,x,dtype,want", [
    # the latent-attention cell's streams, where the computation runs on a
    # TPU and where it does not
    ("tpu", (1, 4, 4096, 3584), jnp.float32, "pallas"),
    ("cpu", (1, 4, 4096, 3584), jnp.float32, "xla"),
    # any number of streams, a batch, the smallest tile
    ("tpu", (2, 2, 64, 128), jnp.float32, "pallas"),
    # what the blocks cannot take: tokens that fill no tile, rows of no
    # whole lane tiles, streams that are not float32
    ("tpu", (2, 4, 96, 256), jnp.float32, "xla"),
    ("tpu", (2, 4, 5, 24), jnp.float32, "xla"),
    ("tpu", (2, 4, 256, 192), jnp.float32, "xla"),
    ("tpu", (1, 4, 4096, 3584), jnp.bfloat16, "xla"),
])
def test_hyper_path_reads_the_platform_and_the_shapes(monkeypatch, platform,
                                                      x, dtype, want):
    monkeypatch.setattr(hc._fa, "compute_platform", lambda: platform)
    assert hc.hyper_path(like(x, dtype)) == want


def test_hyper_path_is_xla_here():
    """No fixture: this process computes on the CPU."""
    assert hc.hyper_path(like((1, 4, 4096, 3584))) == "xla"


def test_each_traced_mhc_pre_counts_the_form_it_took(kernels_here,
                                                     monkeypatch):
    """``kernels.mhc{path=...}`` beside ``kernels.mhc_sublayers``: one each
    a traced ``mhc_pre``; the gradient op counts neither."""
    from paddle_tpu import observability as obs

    pre, _, cts = point(9, batch=1, t=64, c=128)

    def counted():
        got = obs.dump()["counters"]
        return (got.get("kernels.mhc{path=pallas}", 0),
                got.get("kernels.mhc{path=xla}", 0),
                got.get("kernels.mhc_sublayers", 0))

    was_on = obs.enabled()
    obs.enable()
    try:
        start = counted()
        jax.eval_shape(lambda: op("mhc_pre")(pre, ATTRS))
        jax.eval_shape(lambda: op("mhc_pre")(pre, ATTRS))
        jax.eval_shape(lambda: op("mhc_pre_grad")(
            dict(pre, **{"H@GRAD": cts["H@GRAD"]}), ATTRS))
        on_kernels = counted()
        monkeypatch.setattr(hc._fa, "compute_platform", lambda: "cpu")
        jax.eval_shape(lambda: op("mhc_pre")(pre, ATTRS))
        after = counted()
    finally:
        if not was_on:
            obs.disable()
    assert tuple(b - a for a, b in zip(start, on_kernels)) == (2, 0, 2)
    assert tuple(b - a for a, b in zip(on_kernels, after)) == (0, 1, 1)


@pytest.mark.parametrize("n,c,tokens", [(4, 3584, 32), (4, 256, 64),
                                        (2, 128, 64), (4, 16384, 8)])
def test_a_block_of_whole_rows_stays_under_its_bytes(n, c, tokens):
    assert kernels._row_tokens(n, c) == tokens


@pytest.mark.parametrize("c,lanes", [(3584, 1792), (256, 256), (2048, 1024),
                                     (128, 128)])
def test_the_c_tile_divides_the_row(c, lanes):
    assert kernels._lanes(c) == lanes
