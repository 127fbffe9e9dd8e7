"""bert_base built through the program's public API, once for each driver.

``leaves`` maps the reference's leaf names to the program's parameters by the
order in which the model creates them (``reference.leaf_shapes`` is written in
that order) and the drivers check every shape.
"""
from __future__ import annotations

from .reference import leaf_shapes


def build_static(cfg, traffic):
    """Program -> Executor: ``models.bert_base_pretrain`` with the masked-LM
    loss, Adam and bf16 AMP, at the traffic's per-replica batch."""
    import paddle_tpu as fluid
    from paddle_tpu import models
    from paddle_tpu.contrib import mixed_precision as mp

    b, t, m = traffic["batch"], traffic["seq_len"], traffic["masked_positions"]
    v = cfg["vocab_size"]
    opt = cfg["optimizer"]
    main, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        src = fluid.data(name="src", shape=[b, t], dtype="int64")
        pos = fluid.data(name="pos", shape=[b, t], dtype="int64")
        mpos = fluid.data(name="mpos", shape=[b, m], dtype="int64")
        labels = fluid.data(name="labels", shape=[b, m, 1], dtype="int64")
        logits = models.bert_base_pretrain(
            src, pos, mpos, vocab_size=v,
            max_len=cfg["max_position_embeddings"],
            num_layers=cfg["num_hidden_layers"],
            num_heads=cfg["num_attention_heads"],
            d_model=cfg["hidden_size"], d_ff=cfg["intermediate_size"],
            dropout=cfg["hidden_dropout_prob"])
        loss = fluid.layers.mean(fluid.layers.softmax_with_cross_entropy(
            fluid.layers.reshape(logits, [b * m, v]),
            fluid.layers.reshape(labels, [b * m, 1])))
        optimizer = fluid.optimizer.AdamOptimizer(
            opt["learning_rate"], beta1=opt["beta1"], beta2=opt["beta2"],
            epsilon=opt["epsilon"])
        mp.decorate(optimizer).minimize(loss)
    names = [p.name for p in main.all_parameters()]
    return {"main": main, "startup": startup, "loss": loss,
            "leaves": dict(zip(leaf_shapes(cfg), names)),
            "moment": "%s_moment1_0", "moment_scale": 1.0 / (1 - opt["beta1"])}


def to_feed(batch):
    """The reference's batch in the shapes the static program declares."""
    rows, m = batch["labels"].shape
    return {"src": batch["src"], "pos": batch["pos"], "mpos": batch["mpos"],
            "labels": batch["labels"].reshape(rows, m, 1)}


class DygraphBert:
    """The imperative BERT step of ``bench.py:bench_dygraph_bert``: dygraph
    layers, loss over all positions, Adam. Call inside ``dygraph.guard``."""

    def __init__(self, cfg, traffic):
        import paddle_tpu as fluid
        from paddle_tpu.dygraph import Embedding, LayerNorm, Linear

        d, f = cfg["hidden_size"], cfg["intermediate_size"]
        self.cfg, self.traffic = cfg, traffic
        self.layers = fluid.layers
        named = {"emb": Embedding(size=[cfg["vocab_size"], d]),
                 "pos": Embedding(size=[cfg["max_position_embeddings"], d]),
                 "ln0": LayerNorm(d)}
        for i in range(cfg["num_hidden_layers"]):
            named.update({
                "l%d.q" % i: Linear(d, d), "l%d.k" % i: Linear(d, d),
                "l%d.v" % i: Linear(d, d), "l%d.o" % i: Linear(d, d),
                "l%d.ln1" % i: LayerNorm(d),
                "l%d.f1" % i: Linear(d, f, act="gelu"),
                "l%d.f2" % i: Linear(f, d), "l%d.ln2" % i: LayerNorm(d)})
        named["head"] = Linear(d, cfg["vocab_size"])
        self.named = named
        params = [p for layer in named.values() for p in layer.parameters()]
        self.leaves = dict(zip(leaf_shapes(cfg), params))
        opt = cfg["optimizer"]
        self.params = params
        self.optimizer = fluid.optimizer.AdamOptimizer(
            opt["learning_rate"], beta1=opt["beta1"], beta2=opt["beta2"],
            epsilon=opt["epsilon"], parameter_list=params)
        self.moment_scale = 1.0 / (1 - opt["beta1"])

    def moment(self, param):
        return self.optimizer._dygraph_state["%s_moment1" % param.name]

    def step(self, batch):
        """Forward, backward and update on one batch of VarBases; returns the
        loss VarBase."""
        L, n = self.layers, self.named
        cfg = self.cfg
        b, t = self.traffic["batch"], self.traffic["seq_len"]
        d, h = cfg["hidden_size"], cfg["num_attention_heads"]

        def heads(x):
            return L.transpose(L.reshape(x, [b, t, h, d // h]), [0, 2, 1, 3])

        x = n["ln0"](n["emb"](batch["src"]) + n["pos"](batch["pos"]))
        for i in range(cfg["num_hidden_layers"]):
            g = {k: n["l%d.%s" % (i, k)]
                 for k in ("q", "k", "v", "o", "ln1", "f1", "f2", "ln2")}
            q, k, v = heads(g["q"](x)), heads(g["k"](x)), heads(g["v"](x))
            s = L.matmul(q, k, transpose_y=True, alpha=float(d // h) ** -0.5)
            ctx = L.matmul(L.softmax(s), v)
            ctx = L.reshape(L.transpose(ctx, [0, 2, 1, 3]), [b, t, d])
            x = g["ln1"](x + g["o"](ctx))
            x = g["ln2"](x + g["f2"](g["f1"](x)))
        logits = L.reshape(n["head"](x), [b * t, cfg["vocab_size"]])
        loss = L.mean(L.softmax_with_cross_entropy(logits, batch["labels"]))
        loss.backward()
        self.optimizer.minimize(loss, parameter_list=self.params)
        for p in self.params:
            p.clear_gradient()
        return loss


def to_dygraph_batch(batch):
    """The reference's batch as the arrays the imperative step reads."""
    rows, t = batch["labels"].shape
    return {"src": batch["src"], "pos": batch["pos"],
            "labels": batch["labels"].reshape(rows * t, 1)}

