"""Transformer encoder family (BERT-base config 3 / WMT config 4).

Parity model: the reference's transformer test configs
(/root/reference/python/paddle/fluid/tests/unittests/dist_transformer.py
and the fused multihead path operators/fused/multihead_matmul_op.cu).
Built from plain fluid.layers graph ops — under whole-program
compilation XLA fuses QKV projections and attention into MXU-shaped
matmuls, which is the TPU replacement for the reference's hand-fused
CUDA encoder kernels.
"""
from __future__ import annotations


from .. import layers


def _dense(x, size, act=None, name=None):
    return layers.fc(x, size=size, act=act, num_flatten_dims=2)


def _padding_bias(lengths, maxlen, batch, dtype="float32"):
    """Additive key-padding mask [B, 1, 1, maxlen]: 0 for visible keys,
    -1e9 past ``lengths``. Formula is 1e9*(vis-1) — bias BEFORE scale;
    scaling a -1e9 bias collapsed both cases to one float32 constant
    (a silent no-op mask, caught in round-5 review)."""
    vis = layers.cast(layers.sequence_mask(lengths, maxlen=int(maxlen)),
                      dtype)
    return layers.reshape(
        layers.scale(vis, scale=1e9, bias=-1.0, bias_after_scale=False),
        [batch, 1, 1, int(maxlen)])


def multi_head_attention(q_in, num_heads, d_model, dropout=0.0,
                         is_test=False, attn_bias=None, kv_in=None,
                         use_flash=None, kv_lengths=None, causal=False):
    """Attention over [B, T, D]: self-attention by default, or
    encoder-decoder cross attention when ``kv_in`` (the encoder output,
    [B, T_src, D]) is given. ``attn_bias`` is an additive mask
    broadcastable to [B, H, T_q, T_kv] (the reference's
    src_slf_attn_bias: 0 for visible positions, a large negative value
    for masked ones — padding or causal).

    ``kv_lengths`` ([B] int) is the KERNEL-SIDE padding mask: pass the
    per-example valid lengths instead of an additive bias and masked
    self-attention routes through the pallas flash kernels (padded key
    blocks are skipped entirely). ``causal=True`` composes with it
    (decoder self-attention). Use ``attn_bias`` only for masks that
    are not expressible as (causal x per-row-length).

    ``use_flash``: None = auto — the ``flash_attention`` op wherever
    the mask is expressible: self-attention with no additive
    ``attn_bias`` and no attention dropout in training, at ANY length.
    The op sees the shapes and picks the kernels (whole-tile "short"
    kernels up to T = 1024, streaming ones beyond, dense math off the
    TPU; ops/pallas/flash_attention.py), so no T x T matrix reaches HBM
    in either direction. What each path measures on the v5e: PERF.md
    section 6, "PR 25" and "PR 29". True/False force / forbid.

    On that branch q, k, v go to the op as the projections leave them,
    [B, T, H*hd], and the context comes back so: the program holds no
    head split or merge (the op's kernels take a head's lanes inside).
    The dense branch splits and merges heads as before."""
    B, T, D = q_in.shape
    kv = q_in if kv_in is None else kv_in
    T_kv = kv.shape[1]
    head = d_model // num_heads
    q = _dense(q_in, d_model)
    k = _dense(kv, d_model)
    v = _dense(kv, d_model)

    def split_heads(x, t):
        x = layers.reshape(x, [B, t, num_heads, head])
        return layers.transpose(x, [0, 2, 1, 3])  # [B, H, t, head]

    if use_flash is None:
        # self-attention only: the kernel grid assumes T_q == T_kv
        use_flash = attn_bias is None and kv_in is None and (
            is_test or dropout == 0)
    elif use_flash:
        # honor the force or say why it cannot be honored — silently
        # falling back would invalidate kernel benchmarks/debugging
        if attn_bias is not None:
            raise ValueError(
                "use_flash=True: the flash kernel has no additive-mask "
                "support; express the mask as causal=True and/or "
                "kv_lengths (padding)")
        if dropout != 0 and not is_test:
            raise ValueError(
                "use_flash=True: attention dropout is not supported in "
                "the flash kernel; set dropout=0")
    if use_flash and attn_bias is None and (is_test or dropout == 0):
        # no additive mask -> the flash_attention op: the T x T score
        # matrix never hits HBM in EITHER direction (the backward
        # recomputes the probabilities from the saved logsumexp).
        # Attention dropout keeps the dense lowering (no dropout state
        # in the kernels). kv_lengths rides into the kernel as the
        # padding mask.
        ctx = layers.flash_attention(q, k, v, causal=causal,
                                     scale=float(head) ** -0.5,
                                     lengths=kv_lengths,
                                     num_heads=num_heads)
        return _dense(ctx, d_model)
    q = split_heads(q, T)
    k, v = split_heads(k, T_kv), split_heads(v, T_kv)
    q = layers.scale(q, scale=float(head) ** -0.5)
    scores = layers.matmul(q, k, transpose_y=True)  # [B, H, T, T]
    if attn_bias is not None:
        scores = layers.elementwise_add(scores, attn_bias)
    if kv_lengths is not None:
        # dense fallback of the kernel-side padding mask
        scores = layers.elementwise_add(
            scores, _padding_bias(kv_lengths, T_kv, B,
                                  scores.dtype))
    if causal:
        scores = layers.elementwise_add(
            scores, _causal_bias(T, dtype=scores.dtype))
    weights = layers.softmax(scores)
    if dropout:
        weights = layers.dropout(
            weights, dropout_prob=dropout, is_test=is_test,
            dropout_implementation="upscale_in_train")
    ctx = layers.matmul(weights, v)  # [B, H, T, head]
    ctx = layers.transpose(ctx, [0, 2, 1, 3])
    ctx = layers.reshape(ctx, [B, T, d_model])
    return _dense(ctx, d_model)


def encoder_layer(x, num_heads, d_model, d_ff, dropout=0.0, is_test=False,
                  attn_bias=None, kv_lengths=None):
    attn = multi_head_attention(x, num_heads, d_model, dropout, is_test,
                                attn_bias, kv_lengths=kv_lengths)
    if dropout:
        attn = layers.dropout(attn, dropout_prob=dropout, is_test=is_test,
                              dropout_implementation="upscale_in_train")
    x = layers.layer_norm(layers.elementwise_add(x, attn),
                          begin_norm_axis=2)
    ff = _dense(x, d_ff, act="gelu")
    ff = _dense(ff, d_model)
    if dropout:
        ff = layers.dropout(ff, dropout_prob=dropout, is_test=is_test,
                            dropout_implementation="upscale_in_train")
    return layers.layer_norm(layers.elementwise_add(x, ff),
                             begin_norm_axis=2)


def transformer_encoder(src_ids, pos_ids, vocab_size, max_len=512,
                        num_layers=12, num_heads=12, d_model=768,
                        d_ff=3072, dropout=0.0, is_test=False,
                        attn_bias=None, src_lengths=None):
    """BERT-style encoder over int64 [B, T] token + position ids.
    ``attn_bias`` masks padding (additive, broadcastable to
    [B, H, T, T]); ``src_lengths`` ([B] int) is the same mask in
    kernel form — padded self-attention routes the pallas flash
    kernels. Returns [B, T, d_model] encodings."""
    emb = layers.embedding(src_ids, size=[vocab_size, d_model])
    pos = layers.embedding(pos_ids, size=[max_len, d_model])
    x = layers.elementwise_add(emb, pos)
    x = layers.layer_norm(x, begin_norm_axis=2)
    for _ in range(num_layers):
        x = encoder_layer(x, num_heads, d_model, d_ff, dropout, is_test,
                          attn_bias, kv_lengths=src_lengths)
    return x


def bert_base_pretrain(src_ids, pos_ids, masked_positions, vocab_size=30522,
                       max_len=512, num_layers=12, num_heads=12,
                       d_model=768, d_ff=3072, dropout=0.0, is_test=False,
                       attn_bias=None):
    """Masked-LM head over the encoder: predictions at masked positions.
    masked_positions: int64 [B, M] token indices into T; ``attn_bias``
    masks padding as in transformer_encoder."""
    enc = transformer_encoder(src_ids, pos_ids, vocab_size, max_len,
                              num_layers, num_heads, d_model, d_ff,
                              dropout, is_test, attn_bias)
    B, T, D = enc.shape
    M = masked_positions.shape[1]
    flat = layers.reshape(enc, [B * T, D])
    # flat row index = b*T + position
    row_base = layers.reshape(
        layers.range(0, B * T, T, "int64"), [B, 1])
    gather_idx = layers.reshape(
        layers.elementwise_add(masked_positions,
                               layers.expand(row_base, [1, M])),
        [B * M])
    picked = layers.gather(flat, gather_idx)  # [B*M, D]
    logits = layers.fc(picked, size=vocab_size, num_flatten_dims=1)
    return layers.reshape(logits, [B, M, vocab_size])


def decoder_layer(y, enc, num_heads, d_model, d_ff, dropout=0.0,
                  is_test=False, self_bias=None, cross_bias=None,
                  tgt_lengths=None):
    """Post-LN decoder block: causal self-attention, encoder-decoder
    cross attention, FFN (reference dist_transformer.py decoder stack).
    With ``tgt_lengths``, causal+padding self-attention routes the
    flash kernels (pass self_bias=None then)."""
    sa = multi_head_attention(y, num_heads, d_model, dropout, is_test,
                              self_bias, kv_lengths=tgt_lengths,
                              causal=tgt_lengths is not None)
    y = layers.layer_norm(layers.elementwise_add(y, sa),
                          begin_norm_axis=2)
    ca = multi_head_attention(y, num_heads, d_model, dropout, is_test,
                              cross_bias, kv_in=enc)
    y = layers.layer_norm(layers.elementwise_add(y, ca),
                          begin_norm_axis=2)
    ff = _dense(y, d_ff, act="gelu")
    ff = _dense(ff, d_model)
    return layers.layer_norm(layers.elementwise_add(y, ff),
                             begin_norm_axis=2)


def _causal_bias(T, dtype="float32"):
    """Additive causal mask [1, 1, T, T]: 0 on/below the diagonal,
    -1e9 above (future positions)."""
    import numpy as np

    m = np.triu(np.full((T, T), -1e9, dtype=dtype), k=1)
    return layers.assign(m.reshape(1, 1, T, T))


def transformer_wmt(src_ids, src_pos, tgt_ids, tgt_pos, vocab_size,
                    max_len=256, num_layers=6, num_heads=8, d_model=512,
                    d_ff=2048, dropout=0.0, is_test=False,
                    src_lengths=None, tgt_lengths=None):
    """Transformer-base seq2seq (WMT north-star config 4 — reference
    tests/unittests/dist_transformer.py): encoder stack over source
    tokens, decoder stack with causal self-attention + cross attention,
    projection to target vocab logits [B, T_tgt, V].

    With ``src_lengths``/``tgt_lengths`` ([B] int), the PADDED
    encoder self-attention and the causal+padded decoder
    self-attention route the pallas flash kernels (the realistic
    masked-training case); cross attention (rectangular T_tgt x T_src)
    stays dense with an additive bias built from ``src_lengths``."""
    enc = transformer_encoder(src_ids, src_pos, vocab_size, max_len,
                              num_layers, num_heads, d_model, d_ff,
                              dropout, is_test,
                              src_lengths=src_lengths)
    emb = layers.embedding(tgt_ids, size=[vocab_size, d_model])
    pos = layers.embedding(tgt_pos, size=[max_len, d_model])
    y = layers.layer_norm(layers.elementwise_add(emb, pos),
                          begin_norm_axis=2)
    B, T, _ = y.shape
    self_bias = None if tgt_lengths is not None else _causal_bias(int(T))
    cross_bias = None
    if src_lengths is not None:
        cross_bias = _padding_bias(src_lengths, src_ids.shape[1], B)
    for _ in range(num_layers):
        y = decoder_layer(y, enc, num_heads, d_model, d_ff, dropout,
                          is_test, self_bias=self_bias,
                          cross_bias=cross_bias,
                          tgt_lengths=tgt_lengths)
    logits = layers.fc(y, size=vocab_size, num_flatten_dims=2)
    return logits
