"""Fused ops (reference operators/fused/) + Pallas fast paths.

The reference ships hand-fused CUDA kernels (fused_elemwise_activation,
multihead_matmul, fused_embedding_eltwise_layernorm...). On TPU, XLA does
most elementwise fusion automatically; these ops exist for program parity
and as the hook points where Pallas kernels (paddle_tpu/ops/pallas/) plug
in for the truly hot paths (flash attention).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..core.registry import In, Out, register_op


@register_op(
    "fused_elemwise_activation",
    inputs=[In("X"), In("Y")],
    outputs=[Out("Out"), Out("IntermediateOut", no_grad=True)],
    attrs={"functor_list": [], "axis": -1, "scale": 0.0,
           "save_intermediate_out": False},
)
def _fused_elemwise_activation(ins, attrs):
    from .elementwise_ops import _align

    funcs = list(attrs.get("functor_list", []))
    x, y = ins["X"], ins["Y"]

    def apply_unary(name, v):
        return {
            "relu": jax.nn.relu,
            "scale": lambda a: a * attrs.get("scale", 1.0),
            "tanh": jnp.tanh,
            "sigmoid": jax.nn.sigmoid,
        }[name](v)

    inter = None
    if funcs and funcs[0].startswith("elementwise_"):
        bin_name, un_name = funcs[0], funcs[1] if len(funcs) > 1 else None
        xa, ya = _align(x, y, attrs.get("axis", -1))
        binf = {"elementwise_add": jnp.add, "elementwise_mul": jnp.multiply}[bin_name]
        inter = binf(xa, ya)
        out = apply_unary(un_name.replace("_grad", ""), inter) if un_name else inter
    else:
        un_name, bin_name = funcs[0], funcs[1]
        inter = apply_unary(un_name, y)
        xa, ia = _align(x, inter, attrs.get("axis", -1))
        binf = {"elementwise_add": jnp.add, "elementwise_mul": jnp.multiply}[bin_name]
        out = binf(xa, ia)
    return {"Out": out, "IntermediateOut": inter}


@register_op(
    "multihead_matmul",
    inputs=[In("Input"), In("W"), In("Bias"), In("BiasQK", dispensable=True)],
    outputs=[Out("Out")],
    attrs={"transpose_Q": False, "transpose_K": True, "transpose_V": False,
           "alpha": 1.0, "head_number": 1},
)
def _multihead_matmul(ins, attrs):
    # Fused QKV attention (reference fused/multihead_matmul_op.cu): Input
    # [B, S, 3H], W [3H? ...] — inference-era fused layout. Simplified:
    # Input already projected [B, S, 3, N, H/N] via W/Bias application.
    x, w, b = ins["Input"], ins["W"], ins["Bias"]
    nheads = attrs.get("head_number", 1)
    B, S, D = x.shape
    qkv = jnp.matmul(x, w.reshape(D, -1)) + b.reshape(1, 1, -1)
    q, k, v = jnp.split(qkv, 3, axis=-1)
    hd = q.shape[-1] // nheads

    def split_heads(t):
        return t.reshape(B, S, nheads, hd).transpose(0, 2, 1, 3)

    q, k, v = split_heads(q), split_heads(k), split_heads(v)
    scores = jnp.matmul(q, k.transpose(0, 1, 3, 2)) * attrs.get("alpha", 1.0)
    if ins.get("BiasQK") is not None:
        scores = scores + ins["BiasQK"]
    probs = jax.nn.softmax(scores, axis=-1)
    ctx = jnp.matmul(probs, v)
    return {"Out": ctx.transpose(0, 2, 1, 3).reshape(B, S, -1)}


@register_op(
    "fc",
    inputs=[In("Input"), In("W"), In("Bias", dispensable=True)],
    outputs=[Out("Out")],
    attrs={"in_num_col_dims": 1, "activation_type": ""},
)
def _fc(ins, attrs):
    """Fused fully-connected (reference operators/fc_op.cc — the target
    of ir/fc_fuse_pass.cc). XLA fuses dot+add+act on its own; this op
    exists so fused inference graphs execute 1:1."""
    x = ins["Input"]
    w = ins["W"]
    k = int(attrs.get("in_num_col_dims", 1))
    lead = 1
    for s in x.shape[:k]:
        lead *= s
    out = x.reshape(lead, -1) @ w
    if ins.get("Bias") is not None:
        out = out + ins["Bias"].reshape(1, -1)
    act = attrs.get("activation_type", "")
    if act == "relu":
        out = jnp.maximum(out, 0)
    elif act:
        raise NotImplementedError("fc activation %r" % act)
    return {"Out": out.reshape(tuple(x.shape[:k]) + (w.shape[1],))}


def _flash_attention_grad(ins, attrs):
    """dQ, dK, dV from the forward op's own ``Out`` and ``LSE``: the
    backward kernels alone. XLA does not merge the kernel a ``jax.vjp``
    re-run of the forward makes with the forward op's (two custom calls
    of different names), so the generic auto-VJP grad would run the
    forward twice a layer. A program whose forward op bound no ``LSE``
    (or ran the dense math, which has none) re-runs the forward under
    ``jax.vjp`` as every auto-VJP grad op does. The backward that runs
    counts itself where its branch is taken,
    ``kernels.flash_attention_grad{path=fused|split|short|dense}``
    (``flash_attention.count_backward``); the dense math's automatic VJP
    is counted here."""
    from .pallas.flash_attention import (compute_platform, count_backward,
                                         flash_attention,
                                         flash_attention_bwd)

    q, k, v = ins["Q"], ins["K"], ins["V"]
    lengths, select = ins.get("Lengths"), ins.get("Select")
    causal = bool(attrs.get("causal"))
    num_heads = int(attrs.get("num_heads", 0))
    scale = attrs.get("scale", 0.0) or None
    g = ins["Out@GRAD"].astype(q.dtype)
    # a selected call binds its LSE off the TPU too, where the dense math ran
    kernels = ins.get("LSE") is not None and (
        select is None or compute_platform() == "tpu")
    if kernels:
        dq, dk, dv = flash_attention_bwd(
            q, k, v, lengths, ins["Out"], ins["LSE"], g, causal, scale,
            num_heads=num_heads, select=select)
    else:
        if compute_platform() != "tpu":
            count_backward("dense")   # on the TPU the kernels' VJP counts
        _, vjp = jax.vjp(
            lambda q, k, v: flash_attention(
                q, k, v, causal=causal, scale=scale, lengths=lengths,
                num_heads=num_heads, select=select),
            q, k, v)
        dq, dk, dv = vjp(g)
    return {"Q@GRAD": dq, "K@GRAD": dk, "V@GRAD": dv}


# registered before its forward op, so that no auto-VJP grad op is made
register_op(
    "flash_attention_grad",
    inputs=[In("Q"), In("K"), In("V"),
            In("Lengths", dispensable=True, no_grad=True),
            In("Select", dispensable=True, no_grad=True),
            In("Out", dispensable=True), In("LSE", dispensable=True),
            In("Out@GRAD")],
    outputs=[Out("Q@GRAD", dispensable=True),
             Out("K@GRAD", dispensable=True),
             Out("V@GRAD", dispensable=True)],
    attrs={"causal": False, "scale": 0.0, "num_heads": 0},
    grad=None,
)(_flash_attention_grad)


@register_op(
    "flash_attention",
    inputs=[In("Q"), In("K"), In("V"),
            In("Lengths", dispensable=True, no_grad=True),
            In("Select", dispensable=True, no_grad=True)],
    outputs=[Out("Out"), Out("LSE", dispensable=True, no_grad=True)],
    attrs={"causal": False, "scale": 0.0, "num_heads": 0},
)
def _flash_attention(ins, attrs):
    """Attention with no S x S matrix in HBM. The layout is the
    operands': rank 4 is Q [B, H, S, D] and K, V [B, H_kv, S, D] (H_kv
    dividing H: shared K/V heads; V's head dim may be its own, and is
    then ``Out``'s); rank 3 is token-major Q, K, V
    [B, T, H*hd] with the attribute ``num_heads``, as the projections
    leave them, and ``Out`` comes back in the operands' layout. Pallas
    kernels where the computation runs on a TPU (which ones is decided
    from the shapes, see ops/pallas/flash_attention.py: token-major
    operands the short kernels do not take are split into heads, and
    the context merged, inside the op), the same dense math elsewhere.
    ``Lengths`` [B] int: per-row valid-KV count — the kernel-side
    padding mask (reference's additive src_slf_attn_bias). ``Select``
    [B, S, S] int8: a per-query key selection (non-zero where row r sees
    key c), one for all the heads of a batch row, applied inside the
    streaming kernels beside the causal mask (head-major operands).
    ``LSE`` is the log-sum-exp residual ``flash_attention_grad`` reads
    (None where the dense math ran without a selection; [B*H, S, 1] with
    one, wherever it ran). Each trace of the op counts the path it took,
    the layout it was given and the form of its selection:
    ``kernels.flash_attention{path=short|stream|dense}``,
    ``kernels.flash_attention_layout{layout=tokens|heads}``,
    ``kernels.flash_attention_select{form=none|mask}``, and, where V's head
    dim is not Q's, ``kernels.flash_attention_value_dim{path=...}``."""
    from .. import observability as _obs
    from .pallas.flash_attention import (attention_path,
                                         flash_attention_with_lse)

    q, k, v = ins["Q"], ins["K"], ins["V"]
    num_heads = int(attrs.get("num_heads", 0))
    select = ins.get("Select")
    if _obs.enabled():
        path = attention_path(q, k, num_heads=num_heads, select=select, v=v)
        _obs.inc("kernels.flash_attention", path=path)
        _obs.inc("kernels.flash_attention_layout",
                 layout="tokens" if q.ndim == 3 else "heads")
        _obs.inc("kernels.flash_attention_select",
                 form="none" if select is None else "mask")
        if v.shape[-1] != q.shape[-1]:   # a value dim of its own
            _obs.inc("kernels.flash_attention_value_dim", path=path)
    scale = attrs.get("scale", 0.0) or None
    out, lse = flash_attention_with_lse(
        q, k, v, causal=bool(attrs.get("causal")), scale=scale,
        lengths=ins.get("Lengths"), num_heads=num_heads, select=select)
    return {"Out": out, "LSE": lse}
