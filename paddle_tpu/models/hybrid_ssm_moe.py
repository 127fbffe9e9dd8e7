"""Hybrid state-space / mixture-of-experts decoder (the ``nemotron_h``
layout): pre-norm residual layers whose mixer is chosen by a pattern
string, one letter a layer (a transformer layer of attention then experts
is two letters), on a residual path that is an argument: a plain add, or
``n`` streams mixed by manifold-constrained hyper-connections
(``hyper=``, ``layers.mhc_pre`` / ``layers.mhc_post``):

- ``M``  Mamba-2: one input projection to gate, convolved ``x | B | C`` and
  step sizes; causal depthwise convolution + silu; the chunked selective
  scan (``layers.ssd_chunk_scan``: on a TPU, at widths that fill whole
  tiles, the Pallas kernels of ``ops/pallas/ssd_scan.py``; the same
  algorithm in XLA einsums everywhere else, ``ops.ssm_ops.scan_path``);
  gated RMS norm over groups; output projection;
- ``K``  Kimi Delta Attention: q, k, v each through a projection, a causal
  depthwise convolution and silu; a log-decay for every channel of a head
  through a low-rank pair of projections, a write strength a head; the
  gated delta rule by chunks (``layers.kda_chunk``, which takes the L2
  norms of q and k and the gates' activations inside, in float32; on a
  TPU, where the heads fill whole lane tiles, the Pallas kernels of
  ``ops/pallas/kda.py``, XLA einsums everywhere else,
  ``ops.kda_ops.kda_path``); an RMS
  norm a head under a sigmoid output gate made by another low-rank pair;
  output projection;
- ``C``  a doubly gated short convolution: one projection to three streams
  ``B | C | z``, ``B * z`` through a causal depthwise convolution of a few
  taps, the result times ``C`` (``layers.short_conv_gate``, which reads the
  three streams out of the projection's result in place), an output
  projection; no activation, no state beyond the taps;
- ``E``  routed experts, top-k of many without drops over the experts this
  program holds (``layers.moe_topk``: sigmoid or softmax scores, a
  ``relu(u W1)^2 W2`` or a gated ``(silu(u W1) * (u W3)) W2`` expert),
  beside a shared expert of the same form that every token passes (none
  with ``shared_dim = 0``);
- ``D``  a dense gated feed-forward layer, ``(silu(u W1) * (u W3)) W2``;
- ``L``  causal latent attention: queries through a low-rank latent with
  its own RMS norm (or straight from the input, ``q_rank`` None), keys and
  values through another, one key head shared by all query heads beside
  the per-head keys, rotary positions (given frequencies: YaRN's) on it
  and on the trailing ``rope_dim`` of a query's ``nope_dim + rope_dim``
  (or no positions at all, ``inv_freq`` None), a value dim of its own, on
  the streaming ``flash_attention`` kernels;
- ``*``  causal grouped-query attention on the ``flash_attention`` op, no
  positional encoding (as ``NemotronHAttention`` has none), or, by
  argument, with an RMS norm on every q and k head and rotary positions;
- ``S``  causal grouped-query attention over the keys an indexer selects
  for each query: per-head RMS norms on q and k, rotary positions from
  three position components, a lightning indexer (``attn_index_project``,
  ``attn_index_select``: the ``index_topk`` causal keys with the largest
  index score, one set a query for all heads) whose selection the
  ``flash_attention`` op applies inside its kernels, and the indexer's KL
  loss (``attn_index_loss``), collected for the caller to add to the loss.

The head is a matrix of its own, or the embedding table again
(``tied_head``).

Built from ``fluid.layers`` ops; nothing here knows a model's name, the
sizes are arguments.
"""
from __future__ import annotations


from .. import framework, layers
from ..param_attr import ParamAttr


def _proj(x, size):
    return layers.fc(x, size=size, num_flatten_dims=2, bias_attr=False)


def _relu2(x):
    r = layers.relu(x)
    return layers.elementwise_mul(r, r)


def _ffn(u, dim, hidden, form):
    """One feed-forward expert over every token, in ``moe_topk``'s forms:
    ``relu2`` ``relu(u W1)^2 W2``; ``swiglu`` ``(silu(u W1) * (u W3)) W2``
    (parameters in order W1, W3, W2)."""
    if form == "relu2":
        return _proj(_relu2(_proj(u, dim)), hidden)
    if form != "swiglu":
        raise ValueError("hybrid_ssm_moe: no expert %r" % (form,))
    gated = layers.elementwise_mul(layers.swish(_proj(u, dim)),
                                   _proj(u, dim))
    return _proj(gated, hidden)


def mamba2_mixer(u, hidden, num_heads, head_dim, n_groups, state_size,
                 conv_kernel=4, chunk=128, eps=1e-5):
    """u [B, T, hidden] (normed) -> [B, T, hidden]."""
    B, T, _ = u.shape
    inner = num_heads * head_dim
    bc = n_groups * state_size
    zxbcdt = _proj(u, 2 * inner + 2 * bc + num_heads)

    def cut(x, lo, hi):
        return layers.slice(x, axes=[2], starts=[lo], ends=[hi])

    z = cut(zxbcdt, 0, inner)
    xbc = layers.causal_conv1d(cut(zxbcdt, inner, 2 * inner + 2 * bc),
                               kernel_size=conv_kernel, act="silu")
    dt = cut(zxbcdt, 2 * inner + 2 * bc, 2 * inner + 2 * bc + num_heads)
    x = layers.reshape(cut(xbc, 0, inner), [B, T, num_heads, head_dim])
    b = layers.reshape(cut(xbc, inner, inner + bc),
                       [B, T, n_groups, state_size])
    c = layers.reshape(cut(xbc, inner + bc, inner + 2 * bc),
                       [B, T, n_groups, state_size])
    dt_bias = layers.create_parameter([num_heads], "float32")
    a_log = layers.create_parameter([num_heads], "float32")
    d_skip = layers.create_parameter([num_heads], "float32")
    a = layers.scale(layers.exp(a_log), scale=-1.0)
    y = layers.ssd_chunk_scan(x, dt, a, b, c, D=d_skip, dt_bias=dt_bias,
                              chunk=chunk)
    y = layers.rms_norm(layers.reshape(y, [B, T, inner]), gate=z,
                        groups=n_groups, epsilon=eps)
    return _proj(y, hidden)


def kda_mixer(u, hidden, num_heads, head_dim, *, conv_kernel=4, chunk=64,
              eps=1e-5, checkpoints=None):
    """u [B, T, hidden] (normed) -> Kimi Delta Attention. Parameters in
    order: for each of q, k, v its projection (to ``num_heads x head_dim``)
    and its convolution's taps; the decay's low-rank pair (``head_dim``
    wide between the two), the write strength's projection (to
    ``num_heads``), ``A_log`` [num_heads] and ``dt_bias`` [num_heads x
    head_dim]; the output gate's low-rank pair with the second's bias; the
    head norm's weight [head_dim]; the output projection. No other bias.
    ``checkpoints`` receives the delta rule's output: kept beside the
    sublayer's input, the op is not run again when the sublayer is
    recomputed (its gradient op reads its inputs alone, and what follows
    it reads its output). Every op is built inside ``name_scope("kda")``:
    a device trace tells the
    mixer's projections, convolutions, gate and norm from the other layers'
    ``mul`` and ``rms_norm``."""
    B, T, _ = u.shape
    inner = num_heads * head_dim

    def heads(x):
        return layers.reshape(x, [B, T, num_heads, head_dim])

    def convolved():
        return heads(layers.causal_conv1d(
            _proj(u, inner), kernel_size=conv_kernel, bias=False,
            act="silu"))

    with framework.name_scope("kda"):
        q, k, v = convolved(), convolved(), convolved()
        g = heads(_proj(_proj(u, head_dim), inner))
        beta = _proj(u, num_heads)
        a_log = layers.create_parameter([num_heads], "float32")
        dt_bias = layers.create_parameter([inner], "float32")
        o = layers.kda_chunk(q, k, v, g, beta, a_log, dt_bias, chunk=chunk)
        if checkpoints is not None:
            checkpoints.append(o)
        gate = layers.fc(_proj(u, head_dim), size=inner, num_flatten_dims=2)
        o = layers.rms_norm(o, gate=heads(gate), gating="sigmoid_after",
                            epsilon=eps)
        return _proj(layers.reshape(o, [B, T, inner]), hidden)


def short_conv_mixer(u, hidden, conv_kernel=3):
    """u [B, T, hidden] (normed) -> the doubly gated short convolution.
    Parameters in order: the input projection (to ``3 x hidden``, the
    streams ``B | C | z``), the taps [hidden, conv_kernel], the output
    projection. Every op is built inside ``name_scope("shortconv")``: a
    device trace tells the mixer's two ``mul``s from the other layers'."""
    with framework.name_scope("shortconv"):
        return _proj(layers.short_conv_gate(_proj(u, 3 * hidden),
                                            kernel_size=conv_kernel), hidden)


def moe_mixer(u, hidden, num_experts, top_k, expert_dim, shared_dim,
              held=None, scaling=1.0, correction_bias=None, loads=None,
              scoring="sigmoid", expert="relu2", route_eps=None):
    """u [B, T, hidden] (normed) -> routed (held experts' part) + shared."""
    B, T, _ = u.shape
    routed, load = layers.moe_topk(
        layers.reshape(u, [B * T, hidden]), num_experts, top_k, expert_dim,
        held=held, scaling=scaling, correction_bias=correction_bias,
        return_load=True, scoring=scoring, expert=expert, route_eps=route_eps)
    if loads is not None:
        loads.append(load)
    out = layers.reshape(routed, [B, T, hidden])
    if shared_dim:
        out = layers.elementwise_add(out, _ffn(u, shared_dim, hidden, expert))
    return out


def gqa_mixer(u, hidden, num_heads, num_kv_heads, head_dim, *,
              qk_norm_eps=None, rope_theta=None):
    """u [B, T, hidden] (normed) -> causal grouped-query attention.
    ``qk_norm_eps``: an RMS norm with a weight [head_dim] on every q and k
    head (None: none); ``rope_theta``: rotary positions ``0..T-1`` on the
    whole head after it, rotate-half form (None: no positions). Parameters
    in order: the q projection (and its head norm), the k projection (and
    its head norm), the v and the output projections."""
    B, T, _ = u.shape

    def heads(x, n):
        return layers.reshape(x, [B, T, n, head_dim])

    def placed(x):
        if qk_norm_eps is not None:
            x = layers.rms_norm(x, epsilon=qk_norm_eps)
        if rope_theta is not None:
            x = layers.rotary_embedding(x, theta=rope_theta)
        return x

    def major(x):
        return layers.transpose(x, [0, 2, 1, 3])

    q = major(placed(heads(_proj(u, num_heads * head_dim), num_heads)))
    k = major(placed(heads(_proj(u, num_kv_heads * head_dim), num_kv_heads)))
    v = major(heads(_proj(u, num_kv_heads * head_dim), num_kv_heads))
    ctx = layers.flash_attention(q, k, v, causal=True,
                                 scale=float(head_dim) ** -0.5)
    ctx = layers.reshape(layers.transpose(ctx, [0, 2, 1, 3]),
                         [B, T, num_heads * head_dim])
    return _proj(ctx, hidden)


def latent_mixer(u, hidden, num_heads, *, q_rank, kv_rank, nope_dim,
                 rope_dim, v_dim, inv_freq, scale=None, eps=1e-6):
    """u [B, T, hidden] (normed) -> causal latent attention. Parameters in
    order: the query's down-projection, its latent norm, its up-projection
    (to ``num_heads x (nope_dim + rope_dim)``; with ``q_rank`` None one
    projection straight from ``u`` and no latent); the key/value
    down-projection (to ``kv_rank + rope_dim``: the latent and ONE rotary key
    head), the latent's norm, its up-projection (to ``num_heads x (nope_dim
    + v_dim)``); the output projection. Rotary positions ``0..T-1`` turn the
    trailing ``rope_dim`` of every query head and the shared key head
    (``inv_freq``: the ``rope_dim / 2`` pairs' frequencies, as
    ``layers.yarn_inv_freq`` gives them; None: no positions anywhere, the
    ``rope_dim`` slices are plain dims). The shared key head is repeated
    beside each head's own keys in the program, so the kernels read plain
    [B, H, T, nope_dim + rope_dim] keys; v and the context are [B, H, T,
    v_dim]. ``scale``: the scores' (None: ``(nope_dim + rope_dim)^-0.5``).
    Every op is built inside
    ``name_scope("latent")``: a device trace tells the mixer's projections,
    norms and rotary from the other layers' ``mul`` and ``rms_norm``."""
    B, T, _ = u.shape
    qk_dim = nope_dim + rope_dim

    def cut(x, lo, hi, axis):
        return layers.slice(x, axes=[axis], starts=[lo], ends=[hi])

    def placed(x, offset):
        if inv_freq is None:
            return x
        return layers.rotary_embedding(x, offset=offset, inv_freq=inv_freq)

    with framework.name_scope("latent"):
        cq = u if q_rank is None else layers.rms_norm(_proj(u, q_rank),
                                                      epsilon=eps)
        q = placed(layers.reshape(_proj(cq, num_heads * qk_dim),
                                  [B, T, num_heads, qk_dim]), nope_dim)
        kva = _proj(u, kv_rank + rope_dim)
        ckv = layers.rms_norm(cut(kva, 0, kv_rank, 2), epsilon=eps)
        k_rope = placed(layers.reshape(
            cut(kva, kv_rank, kv_rank + rope_dim, 2), [B, T, 1, rope_dim]), 0)
        kv = layers.reshape(_proj(ckv, num_heads * (nope_dim + v_dim)),
                            [B, T, num_heads, nope_dim + v_dim])
        k = layers.concat([cut(kv, 0, nope_dim, 3),
                           layers.expand(k_rope, [1, 1, num_heads, 1])],
                          axis=3)
        v = cut(kv, nope_dim, nope_dim + v_dim, 3)
        q, k, v = (layers.transpose(x, [0, 2, 1, 3]) for x in (q, k, v))
        ctx = layers.flash_attention(q, k, v, causal=True,
                                     scale=scale or float(qk_dim) ** -0.5)
        ctx = layers.reshape(layers.transpose(ctx, [0, 2, 1, 3]),
                             [B, T, num_heads * v_dim])
        return _proj(ctx, hidden)


def indexed_gqa_mixer(u, hidden, num_heads, num_kv_heads, head_dim, *,
                      positions, index_heads, index_dim, index_topk,
                      rope_theta=10000.0, rope_sections=None,
                      index_rotary_dims=0, eps=1e-6, index_losses=None):
    """u [B, T, hidden] (normed) -> causal grouped-query attention over the
    ``index_topk`` keys the indexer selects for each query. ``positions``
    [3, B, T] int. Parameters in order: q, k, v projections, the q and k
    head norms, the indexer's (``layers.attn_index_project``), the output
    projection. The indexer reads ``u`` cut from the gradient and learns
    from its own loss alone, which goes to ``index_losses``."""
    B, T, _ = u.shape
    scale = float(head_dim) ** -0.5

    def heads(x, n):
        return layers.reshape(x, [B, T, n, head_dim])

    def placed(x):
        """Per-head RMS norm, rotary positions, head-major."""
        x = layers.rotary_embedding(layers.rms_norm(x, epsilon=eps),
                                    positions, theta=rope_theta,
                                    sections=rope_sections)
        return layers.transpose(x, [0, 2, 1, 3])

    q = heads(_proj(u, num_heads * head_dim), num_heads)
    k = heads(_proj(u, num_kv_heads * head_dim), num_kv_heads)
    v = heads(_proj(u, num_kv_heads * head_dim), num_kv_heads)
    q, k = placed(q), placed(k)
    qi, ki, w = layers.attn_index_project(
        u, positions, index_heads, index_dim, theta=rope_theta,
        sections=rope_sections, rotary_dims=index_rotary_dims, epsilon=eps)
    select = layers.attn_index_select(qi, ki, w, index_topk)
    ctx, lse = layers.flash_attention(
        q, k, layers.transpose(v, [0, 2, 1, 3]), causal=True, scale=scale,
        select=select, return_lse=True)
    if index_losses is not None:
        index_losses.append(layers.attn_index_loss(
            qi, ki, w, select, q, k, lse, scale))
    ctx = layers.reshape(layers.transpose(ctx, [0, 2, 1, 3]),
                         [B, T, num_heads * head_dim])
    return _proj(ctx, hidden)


def hybrid_ssm_moe(ids, pattern, vocab_rows, hidden, *, mamba_heads=64,
                   mamba_head_dim=64, n_groups=8, state_size=128,
                   conv_kernel=4, chunk=128, num_experts=128, top_k=6,
                   expert_dim=1856, shared_dim=3712, held=None,
                   routed_scaling=2.5, correction_bias=None, num_heads=32,
                   num_kv_heads=2, head_dim=128, eps=1e-5, loads=None,
                   checkpoints=None, scoring="sigmoid", expert="relu2",
                   indexed=None, latent=None, dense_dim=0, hyper=None,
                   kda=None, gqa=None, short_conv_kernel=3, route_eps=None,
                   tied_head=False):
    """Logits [B, T, vocab_rows] over int64 ids [B, T].

    ``pattern``: one letter a layer (see the module's docstring).
    ``vocab_rows``: the rows of the vocabulary this program holds (ids and
    loss are over them). ``held = [first, count]``: the experts of every
    ``E`` layer held here. ``correction_bias``: None, or one array
    [num_experts] for each ``E`` layer in order. ``loads``: a list that
    receives each ``E`` layer's ``Load`` variable, for a fetch.
    ``checkpoints``: a list that receives each layer's input (and a ``K``
    layer's delta-rule output) and the last layer's output, which is what
    ``RecomputeOptimizer._set_checkpoints`` takes to recompute a layer's
    activations from its input alone. ``scoring`` / ``expert``: the ``E``
    layers' router scores and expert form (``layers.moe_topk``).
    ``indexed``: the ``S`` layers' keyword arguments of
    ``indexed_gqa_mixer`` as one dict: ``positions`` [3, B, T] int, the
    ``rope_*`` and ``index_*`` sizes, and ``index_losses``, a list that
    receives each ``S`` layer's indexer loss [1] for the caller to add to
    the model's loss. ``latent``: the ``L`` layers' keyword arguments of
    ``latent_mixer`` as one dict (``num_heads`` is shared with the other
    attention letters). ``kda``: the ``K`` layers' arguments of
    ``kda_mixer`` as one dict, ``num_heads`` and ``head_dim`` among them.
    ``dense_dim``: the ``D`` layers' width. ``gqa``: the ``*`` layers'
    keyword arguments of ``gqa_mixer`` as one dict (``qk_norm_eps``,
    ``rope_theta``). ``short_conv_kernel``: the ``C`` layers' taps.
    ``route_eps``: what stands beside the chosen scores' sum in the ``E``
    layers' weights (None: ``moe_topk``'s own). ``tied_head``: the head is
    the embedding table (one parameter, used twice; its gradient is the sum
    of both uses) instead of a matrix of its own.
    ``hyper``: the residual path. None: ``x = x + mixer(rms_norm(x))``.
    A dict ``{"streams": n, ...}`` (the rest ``layers.mhc_pre``'s keyword
    arguments): ``n`` residual streams [B, n, T, hidden], each a copy of the
    embedding at the start, every sublayer reading ``h = H_pre . X`` and
    writing ``X' = H_res X + H_post (x) mixer(rms_norm(h))`` through maps
    made from the streams, the streams summed before the last norm; the
    checkpoints are then the streams at each sublayer's input."""
    table_name = (framework.unique_name.generate("tied_embedding")
                  if tied_head else None)
    x = layers.embedding(ids, size=[vocab_rows, hidden],
                         param_attr=ParamAttr(name=table_name))
    streams = 0
    if hyper is not None:
        hyper = dict(hyper)
        streams = hyper.pop("streams")
        x = layers.stack([x] * streams, axis=1)
    biases = iter(correction_bias or ())
    for kind in pattern:
        if checkpoints is not None:
            checkpoints.append(x)
        if streams:
            h, h_post, h_res = layers.mhc_pre(x, **hyper)
            u = layers.rms_norm(h, epsilon=eps)
        else:
            u = layers.rms_norm(x, epsilon=eps)
        if kind == "M":
            y = mamba2_mixer(u, hidden, mamba_heads, mamba_head_dim,
                             n_groups, state_size, conv_kernel, chunk, eps)
        elif kind == "E":
            y = moe_mixer(u, hidden, num_experts, top_k, expert_dim,
                          shared_dim, held, routed_scaling,
                          next(biases, None), loads, scoring, expert,
                          route_eps)
        elif kind == "*":
            y = gqa_mixer(u, hidden, num_heads, num_kv_heads, head_dim,
                          **(gqa or {}))
        elif kind == "C":
            y = short_conv_mixer(u, hidden, short_conv_kernel)
        elif kind == "S":
            y = indexed_gqa_mixer(u, hidden, num_heads, num_kv_heads,
                                  head_dim, eps=eps, **indexed)
        elif kind == "L":
            y = latent_mixer(u, hidden, num_heads, eps=eps, **latent)
        elif kind == "K":
            y = kda_mixer(u, hidden, eps=eps, checkpoints=checkpoints,
                          **kda)
        elif kind == "D":
            y = _ffn(u, dense_dim, hidden, "swiglu")
        else:
            raise ValueError("hybrid_ssm_moe: no layer kind %r" % kind)
        if streams:
            x = layers.mhc_post(x, h_res, h_post, y)
        else:
            x = layers.elementwise_add(x, y)
    if checkpoints is not None:
        checkpoints.append(x)
    if streams:
        x = layers.reduce_sum(x, dim=1)
    x = layers.rms_norm(x, epsilon=eps)
    if not tied_head:
        return _proj(x, vocab_rows)
    table = framework.default_main_program().global_block().var(table_name)
    # `transpose2` + `mul`, not `matmul`: that op type reads as attention's
    # in a device trace (benchmarks/layer_metrics/attention.py)
    return layers.mul(x, layers.transpose(table, [1, 0]), x_num_col_dims=2)
