"""Layer "kernels", latent attention: per traced step (median over the steps
of the window) the union of chip 0's operations that the latent mixer's ops
make outside its ``flash_attention`` op (``project_ms``: the down- and
up-projections, the latent norms, rotary positions, the shared key repeated
beside the heads' own, transposes, the output projection, and their
gradients). The mixer builds its ops inside ``name_scope("latent")``, which
a compiled step carries as the third component of an operation's scope,
``<role>/<op_type>/latent/...``: that tells its ``mul`` and ``rms_norm`` from
the other layers'. And the attention core's share of its roofline
(``attend_roofline_pct``): the least time of scores at the query/key dim and
context at the value dim over the causal pairs
(``configs/<family>/flops.py:attend_ops_and_bytes``, forward once and
backward twice that for each ``L`` layer) over what ``attention.kernels_ms``
reads.

A program without such a scope (an older commit, another model) has no such
operation: the reader returns nothing.
"""
from benchmarks.layer_metrics import _scoped as S
from benchmarks.layer_metrics import attention
from benchmarks.lib import program_spans as P

NAME_SCOPE = "latent"
CORE = frozenset(("flash_attention", "flash_attention_grad"))


def is_projection(event_name, op_name):
    """An operation of the latent mixer other than its attention core."""
    scope = P.scope_of(op_name)
    if not scope or scope.partition("/")[2] in CORE:
        return False
    inner = op_name.partition(scope + "/")[2]
    return inner == NAME_SCOPE or inner.startswith(NAME_SCOPE + "/")


def read(ctx):
    loaded = S.load()
    if loaded is None:
        return {}
    path, steps, events, op_names = loaded
    ns = S.per_step_ns(events, op_names, steps, is_projection)
    if not any(ns):
        return {}
    s = ctx["suffix"]
    project_ms = P.median_ms(ns)
    out = {"mla.project_ms." + s: project_ms}
    attend_ms = P.median_ms(attention.per_step_ns(events, op_names, steps))
    roof = attend_ms and S.step_roofline(ctx, path, "L",
                                         "attend_ops_and_bytes", attend_ms)
    if roof:
        out["mla.attend_roofline_pct." + s] = roof[0]
        print("# mla: latent attention's core of a step, forward and "
              "backward: %.3f GFLOP, %.3f GB, bound by %s, over %.4f ms of "
              "attention's kernels" % (roof[2], roof[3], roof[1], attend_ms),
              flush=True)
    print("# mla: read %s: projections, norms and rotary %.4f ms a step "
          "(median of %d steps)" % (path, project_ms, len(ns)), flush=True)
    return out
