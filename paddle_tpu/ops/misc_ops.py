"""Wave-3 ops: tensor rearrangement, vision utilities, losses, CTC.

Parity targets (reference /root/reference/paddle/fluid/operators/):
pixel_shuffle_op.cc, shuffle_channel_op.cc, space_to_depth_op.cc,
temporal_shift_op.cc, shard_index_op.cc, multiplex_op.cc, crop_op.cc,
affine_channel_op.cc, unfold_op.cc, grid_sampler_op.cc,
affine_grid_op.cc, selu_op.cc, mean_iou_op.cc,
bilinear_tensor_product_op.cc, cos_sim_op.cc, bpr_loss_op.cc,
teacher_student_sigmoid_loss_op.cc, sigmoid_focal_loss (detection/),
row_conv_op.cc, warpctc_op.cc, edit_distance_op.cc,
ctc_align_op.cc (ctc_greedy_decoder), hash_op.cc, unique_op.cc,
reverse_op.cc, scatter_nd_op (via scatter_nd_add), fsp_op.cc.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ..core.registry import In, Out, register_host_op, register_op


@register_op("reverse", inputs=[In("X")], outputs=[Out("Out")],
             attrs={"axis": []})
def _reverse(ins, attrs):
    x = ins["X"]
    axes = attrs.get("axis", [])
    for a in (axes if isinstance(axes, (list, tuple)) else [axes]):
        x = jnp.flip(x, axis=int(a))
    return {"Out": x}


@register_op("pixel_shuffle", inputs=[In("X")], outputs=[Out("Out")],
             attrs={"upscale_factor": 1})
def _pixel_shuffle(ins, attrs):
    x = ins["X"]  # [N, C*r*r, H, W]
    r = int(attrs.get("upscale_factor", 1))
    n, c, h, w = x.shape
    oc = c // (r * r)
    x = x.reshape(n, oc, r, r, h, w)
    x = jnp.transpose(x, (0, 1, 4, 2, 5, 3))
    return {"Out": x.reshape(n, oc, h * r, w * r)}


@register_op("shuffle_channel", inputs=[In("X")], outputs=[Out("Out")],
             attrs={"group": 1})
def _shuffle_channel(ins, attrs):
    x = ins["X"]
    g = int(attrs.get("group", 1))
    n, c, h, w = x.shape
    x = x.reshape(n, g, c // g, h, w)
    return {"Out": jnp.swapaxes(x, 1, 2).reshape(n, c, h, w)}


@register_op("space_to_depth", inputs=[In("X")], outputs=[Out("Out")],
             attrs={"blocksize": 1})
def _space_to_depth(ins, attrs):
    x = ins["X"]
    b = int(attrs.get("blocksize", 1))
    n, c, h, w = x.shape
    x = x.reshape(n, c, h // b, b, w // b, b)
    x = jnp.transpose(x, (0, 3, 5, 1, 2, 4))
    return {"Out": x.reshape(n, c * b * b, h // b, w // b)}


@register_op("temporal_shift", inputs=[In("X")], outputs=[Out("Out")],
             attrs={"seg_num": 1, "shift_ratio": 0.25})
def _temporal_shift(ins, attrs):
    x = ins["X"]  # [N*T, C, H, W]
    t = int(attrs.get("seg_num", 1))
    ratio = attrs.get("shift_ratio", 0.25)
    nt, c, h, w = x.shape
    n = nt // t
    c1 = int(c * ratio)
    c2 = int(c * 2 * ratio)
    x = x.reshape(n, t, c, h, w)
    fwd = jnp.concatenate([x[:, 1:, :c1], jnp.zeros_like(x[:, :1, :c1])],
                          axis=1)
    back = jnp.concatenate([jnp.zeros_like(x[:, :1, c1:c2]),
                            x[:, :-1, c1:c2]], axis=1)
    keep = x[:, :, c2:]
    out = jnp.concatenate([fwd, back, keep], axis=2)
    return {"Out": out.reshape(nt, c, h, w)}


@register_op("shard_index", inputs=[In("X", no_grad=True)],
             outputs=[Out("Out")],
             attrs={"index_num": 0, "nshards": 1, "shard_id": 0,
                    "ignore_value": -1}, grad=None)
def _shard_index(ins, attrs):
    x = ins["X"]
    index_num = int(attrs["index_num"])
    nshards = int(attrs["nshards"])
    shard_id = int(attrs["shard_id"])
    ignore = attrs.get("ignore_value", -1)
    shard_size = (index_num + nshards - 1) // nshards
    in_shard = (x // shard_size) == shard_id
    return {"Out": jnp.where(in_shard, x % shard_size, ignore)}


@register_op("multiplex",
             inputs=[In("X", duplicable=True), In("Ids", no_grad=True)],
             outputs=[Out("Out")])
def _multiplex(ins, attrs):
    xs = jnp.stack(ins["X"], axis=0)  # [K, N, ...]
    ids = ins["Ids"].reshape(-1).astype(jnp.int32)  # [N]
    rows = jnp.arange(ids.shape[0])
    return {"Out": xs[ids, rows]}


@register_op("crop", inputs=[In("X"), In("Y", dispensable=True,
                                         no_grad=True),
                             In("Offsets", dispensable=True, no_grad=True)],
             outputs=[Out("Out")],
             attrs={"offsets": [], "shape": []})
def _crop(ins, attrs):
    x = ins["X"]
    shape = attrs.get("shape") or list(ins["Y"].shape)
    offsets = attrs.get("offsets") or [0] * x.ndim
    slices = tuple(slice(int(o), int(o) + int(s))
                   for o, s in zip(offsets, shape))
    return {"Out": x[slices]}


@register_op("affine_channel",
             inputs=[In("X"), In("Scale"), In("Bias")],
             outputs=[Out("Out")], attrs={"data_layout": "NCHW"})
def _affine_channel(ins, attrs):
    x, scale, bias = ins["X"], ins["Scale"], ins["Bias"]
    c_axis = 1 if attrs.get("data_layout", "NCHW") == "NCHW" else x.ndim - 1
    shape = [1] * x.ndim
    shape[c_axis] = x.shape[c_axis]
    return {"Out": x * scale.reshape(shape) + bias.reshape(shape)}


@register_op("unfold", inputs=[In("X")], outputs=[Out("Y")],
             attrs={"kernel_sizes": [1, 1], "strides": [1, 1],
                    "paddings": [0, 0, 0, 0], "dilations": [1, 1]})
def _unfold(ins, attrs):
    """im2col (reference unfold_op.cc): [N,C,H,W] ->
    [N, C*kh*kw, L]."""
    x = ins["X"]
    kh, kw = attrs["kernel_sizes"]
    sh, sw = attrs.get("strides", [1, 1])
    pt, pl, pb, pr = (attrs.get("paddings", [0, 0, 0, 0]) + [0] * 4)[:4]
    dh, dw = attrs.get("dilations", [1, 1])
    n, c, h, w = x.shape
    x = jnp.pad(x, ((0, 0), (0, 0), (pt, pb), (pl, pr)))
    oh = (h + pt + pb - (dh * (kh - 1) + 1)) // sh + 1
    ow = (w + pl + pr - (dw * (kw - 1) + 1)) // sw + 1
    patches = []
    for i in range(kh):
        for j in range(kw):
            sub = x[:, :, i * dh:i * dh + oh * sh:sh,
                    j * dw:j * dw + ow * sw:sw]
            patches.append(sub)
    out = jnp.stack(patches, axis=2)  # [N, C, kh*kw, oh, ow]
    return {"Y": out.reshape(n, c * kh * kw, oh * ow)}


@register_op("affine_grid", inputs=[In("Theta"),
                                    In("OutputShape", dispensable=True,
                                       no_grad=True)],
             outputs=[Out("Output")],
             attrs={"output_shape": [], "align_corners": True})
def _affine_grid(ins, attrs):
    theta = ins["Theta"]  # [N, 2, 3]
    shape = attrs.get("output_shape") or [int(v) for v in
                                          np.asarray(ins["OutputShape"])]
    n, c, h, w = shape
    ys = jnp.linspace(-1.0, 1.0, h)
    xs = jnp.linspace(-1.0, 1.0, w)
    xg, yg = jnp.meshgrid(xs, ys)  # [h, w]
    ones = jnp.ones_like(xg)
    base = jnp.stack([xg, yg, ones], axis=-1)  # [h, w, 3]
    grid = jnp.einsum("hwk,njk->nhwj", base, theta)  # [n, h, w, 2]
    return {"Output": grid}


@register_op("selu", inputs=[In("X")], outputs=[Out("Out")],
             attrs={"scale": 1.0507009873554805,
                    "alpha": 1.6732632423543772})
def _selu(ins, attrs):
    x = ins["X"]
    scale = attrs.get("scale", 1.0507009873554805)
    alpha = attrs.get("alpha", 1.6732632423543772)
    return {"Out": scale * jnp.where(x > 0, x, alpha * (jnp.exp(x) - 1))}


@register_op("mean_iou",
             inputs=[In("Predictions", no_grad=True),
                     In("Labels", no_grad=True)],
             outputs=[Out("OutMeanIou"), Out("OutWrong"), Out("OutCorrect")],
             attrs={"num_classes": 2}, grad=None)
def _mean_iou(ins, attrs):
    pred = ins["Predictions"].reshape(-1).astype(jnp.int32)
    label = ins["Labels"].reshape(-1).astype(jnp.int32)
    k = int(attrs["num_classes"])
    correct = jnp.zeros(k, jnp.int32).at[jnp.where(
        pred == label, pred, k - 1)].add(
            (pred == label).astype(jnp.int32))
    pred_cnt = jnp.zeros(k, jnp.int32).at[pred].add(1)
    label_cnt = jnp.zeros(k, jnp.int32).at[label].add(1)
    union = pred_cnt + label_cnt - correct
    present = union > 0
    iou = jnp.where(present, correct / jnp.maximum(union, 1), 0.0)
    miou = iou.sum() / jnp.maximum(present.sum(), 1)
    # reference mean_iou_op.h counts a mismatch against BOTH classes
    wrong = (pred_cnt - correct) + (label_cnt - correct)
    return {"OutMeanIou": miou.astype(jnp.float32),
            "OutWrong": wrong,
            "OutCorrect": correct}


@register_op("bilinear_tensor_product",
             inputs=[In("X"), In("Y"), In("Weight"),
                     In("Bias", dispensable=True)],
             outputs=[Out("Out")])
def _bilinear_tensor_product(ins, attrs):
    x, y, w = ins["X"], ins["Y"], ins["Weight"]  # w: [size, dx, dy]
    out = jnp.einsum("bi,oij,bj->bo", x, w, y)
    if ins.get("Bias") is not None:
        out = out + ins["Bias"].reshape(1, -1)
    return {"Out": out}


@register_op("cos_sim", inputs=[In("X"), In("Y")],
             outputs=[Out("Out"), Out("XNorm", no_grad=True),
                      Out("YNorm", no_grad=True)])
def _cos_sim(ins, attrs):
    x, y = ins["X"], ins["Y"]
    xn = jnp.sqrt(jnp.sum(jnp.square(x), axis=-1, keepdims=True))
    yn = jnp.sqrt(jnp.sum(jnp.square(y), axis=-1, keepdims=True))
    sim = jnp.sum(x * y, axis=-1, keepdims=True) / \
        jnp.maximum(xn * yn, 1e-12)
    return {"Out": sim, "XNorm": xn, "YNorm": yn}


@register_op("bpr_loss", inputs=[In("X"), In("Label", no_grad=True)],
             outputs=[Out("Y")])
def _bpr_loss(ins, attrs):
    """Bayesian personalized ranking loss (reference bpr_loss_op.cc)."""
    x = ins["X"]  # [N, C] scores
    label = ins["Label"].reshape(-1).astype(jnp.int32)
    n, c = x.shape
    pos = x[jnp.arange(n), label][:, None]
    diff = x - pos
    lse = jnp.logaddexp(0.0, diff)  # stable log(1+e^x)
    mask = jnp.ones((n, c)).at[jnp.arange(n), label].set(0.0)
    return {"Y": (lse * mask).sum(axis=1, keepdims=True) / (c - 1)}


@register_op("teacher_student_sigmoid_loss",
             inputs=[In("X"), In("Label", no_grad=True)],
             outputs=[Out("Y")],
             attrs={"soft_max_up_bound": 15.0,
                    "soft_max_lower_bound": -15.0})
def _ts_sigmoid_loss(ins, attrs):
    """Exact reference piecewise formula
    (teacher_student_sigmoid_loss_op.h:44): label < -1 -> sp;
    label in [-1,0) -> sp - x; label in [0,1) -> sp + sp - x*label;
    label >= 1 -> (sp - x) + (sp - x*(label-1))."""
    x = ins["X"].reshape(-1)
    label = ins["Label"].reshape(-1)
    sp = jnp.maximum(x, 0.0) + jnp.logaddexp(0.0, -jnp.abs(x))
    y = jnp.where(
        label < -1.0, sp,
        jnp.where(label < 0.0, sp - x,
                  jnp.where(label < 1.0, sp + sp - x * label,
                            (sp - x) + (sp - x * (label - 1.0)))))
    return {"Y": y.reshape(-1, 1)}


@register_op("sigmoid_focal_loss",
             inputs=[In("X"), In("Label", no_grad=True),
                     In("FgNum", no_grad=True)],
             outputs=[Out("Out")],
             attrs={"gamma": 2.0, "alpha": 0.25})
def _sigmoid_focal_loss(ins, attrs):
    """Reference detection/sigmoid_focal_loss_op.cu: per-class focal
    loss; Label in [0, C] with 0 = background."""
    x = ins["X"]  # [N, C]
    label = ins["Label"].reshape(-1).astype(jnp.int32)  # [N]
    fg = jnp.maximum(ins["FgNum"].reshape(()).astype(x.dtype), 1.0)
    gamma = attrs.get("gamma", 2.0)
    alpha = attrs.get("alpha", 0.25)
    n, c = x.shape
    cls = jnp.arange(1, c + 1)[None, :]
    t = (label[:, None] == cls).astype(x.dtype)  # one-hot over classes
    p = jax.nn.sigmoid(x)
    ce = jnp.logaddexp(0.0, -jnp.abs(x)) + jnp.maximum(x, 0.0) - x * t
    # focal modulation
    pt = jnp.where(t > 0, p, 1 - p)
    af = jnp.where(t > 0, alpha, 1 - alpha)
    valid = (label[:, None] >= 0).astype(x.dtype)
    return {"Out": af * (1 - pt) ** gamma * ce * valid / fg}


@register_op("row_conv", inputs=[In("X"), In("Filter")],
             outputs=[Out("Out")])
def _row_conv(ins, attrs):
    """Lookahead row convolution over [N, T, D] with filter
    [future_ctx, D] (reference row_conv_op.cc, dense layout)."""
    x, f = ins["X"], ins["Filter"]
    ctx = f.shape[0]
    outs = jnp.zeros_like(x)
    for k in range(ctx):
        shifted = jnp.pad(x[:, k:], ((0, 0), (0, k), (0, 0)))
        outs = outs + shifted * f[k][None, None, :]
    return {"Out": outs}


@register_op("fsp", inputs=[In("X"), In("Y")], outputs=[Out("Out")])
def _fsp(ins, attrs):
    """Flow-of-solution-procedure matrix (reference fsp_op.cc):
    [N,C1,H,W] x [N,C2,H,W] -> [N,C1,C2]."""
    x, y = ins["X"], ins["Y"]
    n, c1, h, w = x.shape
    return {"Out": jnp.einsum("nchw,ndhw->ncd", x, y) / (h * w)}


@register_op("hash", inputs=[In("X", no_grad=True)], outputs=[Out("Out")],
             attrs={"num_hash": 1, "mod_by": 100000000}, grad=None)
def _hash(ins, attrs):
    """Multiplicative int hashing (reference hash_op.cc uses xxhash;
    the contract is a deterministic bucket id per (row, hash_idx))."""
    x = ins["X"].astype(jnp.uint32)  # [N, D] int ids
    num_hash = int(attrs.get("num_hash", 1))
    mod = int(attrs.get("mod_by", 100000000))
    outs = []
    for i in range(num_hash):
        seed = jnp.uint32(0x9E3779B1 * (i + 1) | 1)
        h = jnp.zeros(x.shape[:-1], jnp.uint32)
        for d in range(x.shape[-1]):
            h = (h ^ (x[..., d] * seed)) * jnp.uint32(0x85EBCA77)
        outs.append((h % jnp.uint32(mod)).astype(jnp.int64))
    out = jnp.stack(outs, axis=-1)[..., None]
    return {"Out": out}


@register_host_op("unique",
                  inputs=[In("X", no_grad=True)],
                  outputs=[Out("Out"), Out("Index")],
                  attrs={"dtype": 2})
def _unique(executor, op, scope):
    from ..core import dtypes as _dt

    x = np.asarray(executor._read_var(scope, op.input("X")[0])).reshape(-1)
    uniq, inv = np.unique(x, return_inverse=True)
    idx_dt = _dt.to_numpy_dtype(op.attrs.get("dtype", 2))
    executor._write_var(scope, op.output("Out")[0], uniq)
    executor._write_var(scope, op.output("Index")[0],
                        inv.astype(idx_dt))


@register_host_op("edit_distance",
                  inputs=[In("Hyps", no_grad=True),
                          In("Refs", no_grad=True)],
                  outputs=[Out("Out"), Out("SequenceNum")],
                  attrs={"normalized": True})
def _edit_distance(executor, op, scope):
    """Levenshtein distance per sequence pair (reference
    edit_distance_op.h). LoD inputs or same-length dense batches."""
    from ..core.tensor import LoDTensor

    def seqs(name):
        v = scope.find_var(name).raw()
        arr = np.asarray(v.array if isinstance(v, LoDTensor) else v)
        if isinstance(v, LoDTensor) and v.lod():
            off = v.lod()[-1]
            return [arr[off[i]:off[i + 1]].reshape(-1)
                    for i in range(len(off) - 1)]
        return [row.reshape(-1) for row in arr]

    hyps = seqs(op.input("Hyps")[0])
    refs = seqs(op.input("Refs")[0])
    out = []
    for h, r in zip(hyps, refs):
        m, n = len(h), len(r)
        dp = np.zeros((m + 1, n + 1), np.float32)
        dp[:, 0] = np.arange(m + 1)
        dp[0, :] = np.arange(n + 1)
        for i in range(1, m + 1):
            for j in range(1, n + 1):
                cost = 0 if h[i - 1] == r[j - 1] else 1
                dp[i, j] = min(dp[i - 1, j] + 1, dp[i, j - 1] + 1,
                               dp[i - 1, j - 1] + cost)
        d = dp[m, n]
        if op.attrs.get("normalized", True) and n > 0:
            d = d / n
        out.append([d])
    executor._write_var(scope, op.output("Out")[0],
                        np.asarray(out, np.float32))
    executor._write_var(scope, op.output("SequenceNum")[0],
                        np.asarray([len(out)], np.int64))


@register_op(
    "warpctc",
    inputs=[In("Logits"), In("Label", no_grad=True),
            In("LogitsLength", dispensable=True, no_grad=True)],
    outputs=[Out("Loss"), Out("WarpCTCGrad", dispensable=True,
                              no_grad=True)],
    attrs={"blank": 0, "norm_by_times": False},
)
def _warpctc(ins, attrs):
    """CTC loss over DENSE [B, T, C] logits and [B, L] labels
    (reference warpctc_op.cc wraps warp-ctc; here the forward algorithm
    runs as a lax.scan over time — pure XLA, trainable via auto-VJP).
    Label padding value must be negative or >= C (ignored)."""
    logits = ins["Logits"]
    labels = ins["Label"].astype(jnp.int32)
    blank = int(attrs.get("blank", 0))
    if logits.ndim == 2:
        logits = logits[None]
        labels = labels.reshape(1, -1)
    b, t, c = logits.shape
    log_probs = jax.nn.log_softmax(logits, axis=-1)
    if ins.get("LogitsLength") is not None:
        # padded timesteps emit blank with probability 1 (log-prob 0):
        # trailing forced blanks collapse, leaving the true-path prob
        lens = ins["LogitsLength"].reshape(-1).astype(jnp.int32)
        tmask = jnp.arange(t)[None, :] < lens[:, None]  # [b, t]
        blank_row = jnp.full((c,), -1e30).at[int(attrs.get("blank",
                                                           0))].set(0.0)
        log_probs = jnp.where(tmask[:, :, None], log_probs,
                              blank_row[None, None, :])
    L = labels.shape[1]
    valid_lab = (labels >= 0) & (labels < c)  # pad = negative or >= C
    # extended label sequence: blank l1 blank l2 ... blank, length 2L+1
    ext = jnp.full((b, 2 * L + 1), blank, jnp.int32)
    ext = ext.at[:, 1::2].set(jnp.where(valid_lab, labels, blank))
    lab_len = valid_lab.sum(axis=1)
    s_len = 2 * lab_len + 1
    neg_inf = jnp.float32(-1e30)

    # can transition s-2 -> s when ext[s] != blank and ext[s] != ext[s-2]
    skip_ok = jnp.zeros((b, 2 * L + 1), bool)
    if L > 0:
        skip_ok = skip_ok.at[:, 2:].set(
            (ext[:, 2:] != blank) & (ext[:, 2:] != ext[:, :-2]))

    alpha0 = jnp.full((b, 2 * L + 1), neg_inf)
    alpha0 = alpha0.at[:, 0].set(log_probs[:, 0, blank])
    if L > 0:
        alpha0 = alpha0.at[:, 1].set(
            jnp.where(lab_len > 0,
                      log_probs[jnp.arange(b), 0, ext[:, 1]], neg_inf))

    def step(alpha, lp_t):
        stay = alpha
        prev1 = jnp.concatenate(
            [jnp.full((b, 1), neg_inf), alpha[:, :-1]], axis=1)
        prev2 = jnp.concatenate(
            [jnp.full((b, 2), neg_inf), alpha[:, :-2]], axis=1)
        prev2 = jnp.where(skip_ok, prev2, neg_inf)
        merged = jnp.logaddexp(jnp.logaddexp(stay, prev1), prev2)
        emit = jnp.take_along_axis(lp_t, ext, axis=1)
        return merged + emit, None

    lp_seq = jnp.swapaxes(log_probs, 0, 1)  # [T, B, C]
    alpha, _ = jax.lax.scan(step, alpha0, lp_seq[1:])
    last = jnp.take_along_axis(alpha, (s_len - 1)[:, None],
                               axis=1)[:, 0]
    last2 = jnp.take_along_axis(
        alpha, jnp.maximum(s_len - 2, 0)[:, None], axis=1)[:, 0]
    ll = jnp.logaddexp(last, jnp.where(s_len >= 2, last2, neg_inf))
    return {"Loss": (-ll).reshape(b, 1)}


@register_host_op(
    "ctc_align",
    inputs=[In("Input", no_grad=True)],
    outputs=[Out("Output")],
    attrs={"blank": 0, "merge_repeated": True},
)
def _ctc_align(executor, op, scope):
    """CTC greedy-decode output alignment (reference ctc_align_op.h):
    merge repeats, drop blanks. Dense [B, T] argmax ids in, LoD out."""
    from ..core.tensor import LoDTensor

    ids = np.asarray(executor._read_var(scope, op.input("Input")[0]))
    blank = op.attrs.get("blank", 0)
    merge = op.attrs.get("merge_repeated", True)
    rows, lod = [], [0]
    for row in ids:
        prev = None
        seq = []
        for v in row.reshape(-1):
            if merge and prev is not None and v == prev:
                prev = v
                continue
            prev = v
            if v != blank:
                seq.append(v)
        rows.extend(seq)
        lod.append(len(rows))
    out = np.asarray(rows, ids.dtype).reshape(-1, 1) if rows else \
        np.full((1, 1), -1, ids.dtype)
    if not rows:
        lod = [0, 1]
    t = LoDTensor(out)
    t.set_lod([lod])
    executor._write_var(scope, op.output("Output")[0], t)


@register_op("sequence_reverse", inputs=[In("X")], outputs=[Out("Y")],
             needs_lod=True, infer_lod="propagate")
def _sequence_reverse(ins, attrs):
    """Reverse each LoD sequence (reference
    sequence_ops/sequence_reverse_op.h); dense inputs flip axis 0."""
    from .lod_utils import lod_offsets

    x = ins["X"]
    offsets = lod_offsets(attrs, "X")
    if offsets is None:
        return {"Y": jnp.flip(x, axis=0)}
    segs = [jnp.flip(x[offsets[i]:offsets[i + 1]], axis=0)
            for i in range(len(offsets) - 1)]
    return {"Y": jnp.concatenate(segs, axis=0)}


@register_host_op("lod_reset",
                  inputs=[In("X"), In("Y", dispensable=True,
                                      no_grad=True)],
                  outputs=[Out("Out")],
                  attrs={"target_lod": []})
def _lod_reset(executor, op, scope):
    """Re-stamp LoD from attr or Y's lod/values (reference
    lod_reset_op.h)."""
    from ..core.tensor import LoDTensor

    xv = scope.find_var(op.input("X")[0]).raw()
    arr = np.asarray(xv.array if isinstance(xv, LoDTensor) else xv)
    target = list(op.attrs.get("target_lod") or [])
    if not target and op.input("Y"):
        yv = scope.find_var(op.input("Y")[0]).raw()
        if isinstance(yv, LoDTensor) and yv.lod():
            target = list(yv.lod()[-1])
        else:
            target = [int(v) for v in np.asarray(
                yv.array if isinstance(yv, LoDTensor) else yv).reshape(-1)]
    t = LoDTensor(arr)
    t.set_lod([target])
    executor._write_var(scope, op.output("Out")[0], t)


@register_op(
    "linear_chain_crf",
    inputs=[In("Emission"), In("Transition"), In("Label", no_grad=True)],
    outputs=[Out("Alpha", no_grad=True), Out("EmissionExps", no_grad=True),
             Out("TransitionExps", no_grad=True), Out("LogLikelihood")],
)
def _linear_chain_crf(ins, attrs):
    """Linear-chain CRF negative log-likelihood over DENSE [B, T, K]
    emissions (reference linear_chain_crf_op.h works on LoD sequences;
    the padded-batch form is the TPU-native layout — pad with repeated
    last label and length masking upstream).

    Transition: [K+2, K] — row 0 start weights, row 1 end weights, rows
    2.. the KxK transition matrix, the reference's exact layout."""
    em = ins["Emission"]
    if em.ndim == 2:
        em = em[None]
    labels = ins["Label"].astype(jnp.int32)
    labels = labels.reshape(em.shape[0], -1)
    trans = ins["Transition"]
    k = em.shape[-1]
    start, end, T_mat = trans[0], trans[1], trans[2:]
    b, t, _ = em.shape

    # log partition via forward algorithm
    alpha0 = start[None, :] + em[:, 0]

    def fwd(alpha, e_t):
        scores = alpha[:, :, None] + T_mat[None, :, :] + e_t[:, None, :]
        return jax.nn.logsumexp(scores, axis=1), None

    alpha, _ = jax.lax.scan(fwd, alpha0,
                            jnp.swapaxes(em[:, 1:], 0, 1))
    log_z = jax.nn.logsumexp(alpha + end[None, :], axis=1)

    # gold path score
    rows = jnp.arange(b)
    gold = start[labels[:, 0]] + em[rows, 0, labels[:, 0]]
    for i in range(1, t):
        gold = gold + T_mat[labels[:, i - 1], labels[:, i]] + \
            em[rows, i, labels[:, i]]
    gold = gold + end[labels[:, -1]]
    return {"LogLikelihood": (log_z - gold).reshape(b, 1),
            "Alpha": alpha, "EmissionExps": jnp.exp(em),
            "TransitionExps": jnp.exp(trans)}


@register_op(
    "crf_decoding",
    inputs=[In("Emission", no_grad=True), In("Transition", no_grad=True),
            In("Label", dispensable=True, no_grad=True)],
    outputs=[Out("ViterbiPath")],
    grad=None,
)
def _crf_decoding(ins, attrs):
    """Viterbi decode (reference crf_decoding_op.h) over dense
    [B, T, K] emissions; returns the best path [B, T] (or a 0/1 match
    mask against Label when provided, like the reference)."""
    em = ins["Emission"]
    if em.ndim == 2:
        em = em[None]
    trans = ins["Transition"]
    start, end, T_mat = trans[0], trans[1], trans[2:]
    b, t, k = em.shape

    delta0 = start[None, :] + em[:, 0]

    def step(delta, e_t):
        scores = delta[:, :, None] + T_mat[None, :, :]
        best = jnp.max(scores, axis=1) + e_t
        arg = jnp.argmax(scores, axis=1)
        return best, arg

    delta, back = jax.lax.scan(step, delta0,
                               jnp.swapaxes(em[:, 1:], 0, 1))
    last = jnp.argmax(delta + end[None, :], axis=1)  # [b]

    def backtrack(state, bp_t):
        prev = jnp.take_along_axis(bp_t, state[:, None], axis=1)[:, 0]
        return prev, prev

    _, path_rev = jax.lax.scan(backtrack, last, back, reverse=True)
    path = jnp.concatenate([jnp.swapaxes(path_rev, 0, 1),
                            last[:, None]], axis=1)  # [b, t]
    if ins.get("Label") is not None:
        lab = ins["Label"].astype(jnp.int32).reshape(b, t)
        return {"ViterbiPath": (path == lab).astype(jnp.int64)}
    return {"ViterbiPath": path.astype(jnp.int64)}


@register_op("gather_tree",
             inputs=[In("Ids", no_grad=True), In("Parents", no_grad=True)],
             outputs=[Out("Out")], grad=None)
def _gather_tree(ins, attrs):
    """Beam-search backtrace (reference gather_tree_op.cc): walk parent
    pointers from the last step, yielding full beams [T, B, W]."""
    ids, parents = ins["Ids"], ins["Parents"]
    t, b, w = ids.shape
    beams = jnp.arange(w)[None, :].repeat(b, axis=0)  # [B, W]

    def step(state, tp):
        id_t, par_t = tp
        out_t = jnp.take_along_axis(id_t, state, axis=1)
        nxt = jnp.take_along_axis(par_t, state, axis=1)
        return nxt, out_t

    _, outs = jax.lax.scan(step, beams, (ids, parents), reverse=True)
    return {"Out": outs}


@register_op("random_crop",
             inputs=[In("X"), In("Seed", dispensable=True, no_grad=True)],
             outputs=[Out("Out"), Out("SeedOut", dispensable=True,
                                      no_grad=True)],
             attrs={"shape": [], "startup_seed": 0}, needs_rng=True,
             grad=None)
def _random_crop(ins, attrs):
    """Random spatial crop to attrs['shape'] (trailing dims; reference
    random_crop_op.h)."""
    from ..core.registry import RNG_SEED_ATTR

    x = ins["X"]
    shape = [int(s) for s in attrs["shape"]]
    nd = len(shape)
    key = jax.random.PRNGKey(ins[RNG_SEED_ATTR])
    starts = []
    for i, (full, want) in enumerate(zip(x.shape[-nd:], shape)):
        key, sub = jax.random.split(key)
        starts.append(jax.random.randint(sub, (), 0, full - want + 1))
    out = x
    for i, (st, want) in enumerate(zip(starts, shape)):
        axis = x.ndim - nd + i
        out = jax.lax.dynamic_slice_in_dim(out, st, want, axis=axis)
    return {"Out": out}


@register_op("spectral_norm",
             inputs=[In("Weight"), In("U", no_grad=True),
                     In("V", no_grad=True)],
             outputs=[Out("Out"), Out("UOut", no_grad=True),
                      Out("VOut", no_grad=True)],
             attrs={"dim": 0, "power_iters": 1, "eps": 1e-12})
def _spectral_norm(ins, attrs):
    """Weight / sigma_max via power iteration (reference
    spectral_norm_op.h). UOut/VOut are bound by the layer to the same
    persistable U/V vars, so the iterates warm-start across steps as the
    reference's in-place CalcMatrixSigmaAndNormWeight does; u/v are
    gradient-stopped before sigma, matching the reference grad kernel
    which treats the saved U/V as constants."""
    w = ins["Weight"]
    dim = int(attrs.get("dim", 0))
    eps = attrs.get("eps", 1e-12)
    mat = jnp.moveaxis(w, dim, 0).reshape(w.shape[dim], -1)
    u, v = ins["U"].reshape(-1), ins["V"].reshape(-1)
    for _ in range(int(attrs.get("power_iters", 1))):
        v = mat.T @ u
        v = v / (jnp.linalg.norm(v) + eps)
        u = mat @ v
        u = u / (jnp.linalg.norm(u) + eps)
    u = jax.lax.stop_gradient(u)
    v = jax.lax.stop_gradient(v)
    sigma = u @ mat @ v
    return {"Out": w / (sigma + eps), "UOut": u, "VOut": v}


def _data_norm_grad_maker(block, op, pending, finalize):
    """Grad maker for data_norm mirroring the reference's
    DataNormGradMaker (data_norm_op.cc:458-470): the grad op's
    BatchSize/BatchSum/BatchSquareSum OUTPUTS are bound to the forward's
    stat vars themselves, so each backward pass replaces the running
    stats with this batch's (N, Σx, Σ(x-mean)²+N·ε) — that in-place
    rebind IS the reference's stat-update rule."""
    from .. import framework
    from ..backward import _ensure_grad_var

    y_name = op.output("Y")[0]
    g_y = finalize(y_name)
    if g_y is None:
        return
    x_name = op.input("X")[0]
    if x_name in pending and pending[x_name]:
        gname = "%s@GRAD@RENAME@%d" % (x_name, len(pending[x_name]))
    else:
        gname = framework.grad_var_name(x_name)
    _ensure_grad_var(block, x_name, gname)
    pending.setdefault(x_name, []).append(gname)
    block.append_op(
        "data_norm_grad",
        inputs={"X": [x_name], "Means": [op.output("Means")[0]],
                "Scales": [op.output("Scales")[0]], "Y@GRAD": [g_y]},
        outputs={"X@GRAD": [gname],
                 "BatchSize": [op.input("BatchSize")[0]],
                 "BatchSum": [op.input("BatchSum")[0]],
                 "BatchSquareSum": [op.input("BatchSquareSum")[0]]},
        attrs=dict(op.attrs), infer_shape=False)


@register_op("data_norm",
             inputs=[In("X"), In("BatchSize", no_grad=True),
                     In("BatchSum", no_grad=True),
                     In("BatchSquareSum", no_grad=True)],
             outputs=[Out("Y"), Out("Means", no_grad=True),
                      Out("Scales", no_grad=True)],
             attrs={"epsilon": 1e-4},
             grad=_data_norm_grad_maker)
def _data_norm(ins, attrs):
    """Normalization by accumulated batch statistics (reference
    data_norm_op.cc): mean = sum/size, scale = sqrt(size/square_sum)."""
    x = ins["X"]
    eps = attrs.get("epsilon", 1e-4)
    size = ins["BatchSize"]
    mean = ins["BatchSum"] / size
    # reference data_norm_op.cc:209: scale = sqrt(size / square_sum)
    scale = jnp.sqrt(size / (ins["BatchSquareSum"] + eps))
    return {"Y": (x - mean[None, :]) * scale[None, :],
            "Means": mean, "Scales": scale}


@register_op("data_norm_grad",
             inputs=[In("X", no_grad=True), In("Means", no_grad=True),
                     In("Scales", no_grad=True), In("Y@GRAD", no_grad=True)],
             outputs=[Out("X@GRAD", no_grad=True),
                      Out("BatchSize", no_grad=True),
                      Out("BatchSum", no_grad=True),
                      Out("BatchSquareSum", no_grad=True)],
             attrs={"epsilon": 1e-4}, grad=None)
def _data_norm_grad(ins, attrs):
    """reference data_norm_op.cc:392-397 (dX = dY·scale) and :440-449
    (default non-slot stat update): size=N, sum=Σx,
    square_sum=Σ(x-mean)²+N·ε."""
    x = ins["X"]
    dy = ins["Y@GRAD"]
    eps = attrs.get("epsilon", 1e-4)
    n = float(x.shape[0])
    dx = dy * ins["Scales"][None, :]
    mean = ins["Means"]
    return {"X@GRAD": dx,
            "BatchSize": jnp.full((x.shape[-1],), n, x.dtype),
            "BatchSum": x.sum(axis=0),
            "BatchSquareSum": ((x - mean[None, :]) ** 2).sum(axis=0)
            + n * eps}


@register_op("center_loss",
             inputs=[In("X"), In("Label", no_grad=True),
                     In("Centers", no_grad=True),
                     In("CenterUpdateRate", no_grad=True)],
             outputs=[Out("CentersOut", no_grad=True), Out("SampleCenterDiff"),
                      Out("Loss")],
             attrs={"cluster_num": 0, "need_update": True})
def _center_loss(ins, attrs):
    """Center loss (reference center_loss_op.h): pull features toward
    per-class centers; centers update by the mean residual."""
    x = ins["X"]
    label = ins["Label"].reshape(-1).astype(jnp.int32)
    centers = ins["Centers"]
    alpha = ins["CenterUpdateRate"].reshape(())
    picked = centers[label]
    diff = x - picked
    loss = 0.5 * jnp.sum(jnp.square(diff), axis=-1, keepdims=True)
    if attrs.get("need_update", True):
        counts = jnp.zeros(centers.shape[0], x.dtype).at[label].add(1.0)
        sums = jnp.zeros_like(centers).at[label].add(diff)
        update = sums / (1.0 + counts)[:, None]
        centers = centers + alpha * update
    return {"CentersOut": centers, "SampleCenterDiff": diff,
            "Loss": loss}


@register_host_op("tensor_array_to_tensor",
                  inputs=[In("X", no_grad=True)],
                  outputs=[Out("Out"), Out("OutIndex")],
                  attrs={"axis": 0, "use_stack": False})
def _tensor_array_to_tensor(executor, op, scope):
    """Concat/stack a LoDTensorArray (reference
    tensor_array_to_tensor_op.cc)."""
    arr = scope.find_var(op.input("X")[0]).get_lod_tensor_array()
    axis = op.attrs.get("axis", 0)
    mats = [np.asarray(t.array if hasattr(t, "array") else t)
            for t in arr]
    if op.attrs.get("use_stack", False):
        out = np.stack(mats, axis=axis)
    else:
        out = np.concatenate(mats, axis=axis)
    executor._write_var(scope, op.output("Out")[0], out)
    executor._write_var(scope, op.output("OutIndex")[0],
                        np.asarray([m.shape[axis] for m in mats],
                                   np.int32))


@register_op("shuffle_batch",
             inputs=[In("X")],
             outputs=[Out("Out"), Out("ShuffleIdx", no_grad=True),
                      Out("SeedOut", no_grad=True, dispensable=True)],
             attrs={"startup_seed": 0}, needs_rng=True, grad=None)
def _shuffle_batch(ins, attrs):
    """Random shuffle of rows over all leading dims (reference
    contrib shuffle_batch_op.cc); last dim kept intact. startup_seed
    folds into the per-step stream (it seeds the engine, it does NOT
    freeze the permutation — each step still draws a fresh shuffle,
    matching the reference's evolving seed)."""
    from ..core.registry import RNG_SEED_ATTR

    x = ins["X"]
    lead = 1
    for s in x.shape[:-1]:
        lead *= s
    flat = x.reshape(lead, x.shape[-1])
    key = jax.random.fold_in(jax.random.PRNGKey(ins[RNG_SEED_ATTR]),
                             int(attrs.get("startup_seed", 0)))
    perm = jax.random.permutation(key, lead)
    # int32: jax's default int width here (int64 would truncate with a
    # warning unless x64 is enabled)
    return {"Out": flat[perm].reshape(x.shape),
            "ShuffleIdx": perm.astype(jnp.int32),
            "SeedOut": jnp.zeros((1,), jnp.int32)}


@register_op("shuffle_batch_grad",
             inputs=[In("ShuffleIdx", no_grad=True),
                     In("Out@GRAD", no_grad=True)],
             outputs=[Out("X@GRAD", no_grad=True)],
             attrs={"startup_seed": 0}, grad=None)
def _shuffle_batch_grad(ins, attrs):
    """Un-permute the gradient (reference shuffle_batch_op.cc grad:
    dX[perm[i]] = dOut[i])."""
    dout = ins["Out@GRAD"]
    perm = ins["ShuffleIdx"].reshape(-1).astype(jnp.int32)
    lead = perm.shape[0]
    flat = dout.reshape(lead, -1)
    dx = jnp.zeros_like(flat).at[perm].set(flat)
    return {"X@GRAD": dx.reshape(dout.shape)}


def _partial_slice(xs, start, length):
    outs = []
    for x in xs:
        s = start + x.shape[1] if start < 0 else start
        end = x.shape[1] if length < 0 else s + length
        outs.append(x[:, s:end])
    return outs


@register_op("partial_concat",
             inputs=[In("X", duplicable=True)], outputs=[Out("Out")],
             attrs={"start_index": 0, "length": -1})
def _partial_concat(ins, attrs):
    """Concat a column slice of every input (reference contrib
    partial_concat_op.cc)."""
    parts = _partial_slice(ins["X"], int(attrs.get("start_index", 0)),
                           int(attrs.get("length", -1)))
    return {"Out": jnp.concatenate(parts, axis=1)}


@register_op("partial_sum",
             inputs=[In("X", duplicable=True)], outputs=[Out("Out")],
             attrs={"start_index": 0, "length": -1})
def _partial_sum(ins, attrs):
    """Sum a column slice across inputs (reference contrib
    partial_sum_op.cc)."""
    parts = _partial_slice(ins["X"], int(attrs.get("start_index", 0)),
                           int(attrs.get("length", -1)))
    out = parts[0]
    for p in parts[1:]:
        out = out + p
    return {"Out": out}


@register_op(
    "recompute_barrier",
    inputs=[In("X", no_grad=True)],
    outputs=[Out("Out", no_grad=True)],
    grad=None,
)
def _recompute_barrier(ins, attrs):
    """The identity behind ``jax.lax.optimization_barrier``: what
    ``backward._emit_recompute_ops`` puts between a checkpoint value and the
    re-emitted segment that reads it, so that XLA cannot fold the segment's
    copy into its original."""
    return {"Out": jax.lax.optimization_barrier(ins["X"])}
