"""Driver of imperative (dygraph) training in its lazy mode,
``fluid.dygraph.guard(lazy=True)``: each step rebuilds the graph in Python,
``optimizer.minimize`` flushes it as one compiled call, and ``loss.numpy()``
brings the loss to the host."""
from __future__ import annotations

import contextlib

import numpy as np

from .static_executor import check_shapes


class Driver:
    def __init__(self, model, cfg, traffic, devices):
        self.model, self.cfg, self.traffic = model, cfg, traffic
        self.items_per_step = traffic["items_per_step"]
        self._guard = contextlib.ExitStack()

    def build(self):
        import paddle_tpu as fluid

        self._guard.enter_context(
            fluid.dygraph.guard(lazy=self.traffic["lazy"]))
        self.net = self.model.DygraphBert(self.cfg, self.traffic)

    def load(self, params, pool):
        check_shapes(self.net.leaves, params, lambda p: p.shape)
        for leaf, param in self.net.leaves.items():
            param.set_value(params[leaf])
        self.pool = [self.model.to_dygraph_batch(batch) for batch in pool]

    def step(self, i):
        import jax
        from paddle_tpu.dygraph import VarBase

        arrays = self.pool[i % len(self.pool)]
        loss = self.net.step({k: VarBase(v) for k, v in arrays.items()})
        with jax.profiler.TraceAnnotation("bench.fetch"):
            value = loss.numpy()
        return float(np.mean(value))

    def params(self):
        return {leaf: p.array for leaf, p in self.net.leaves.items()}

    def first_moment(self):
        return ({leaf: self.net.moment(p).array
                 for leaf, p in self.net.leaves.items()},
                self.net.moment_scale)

    def watched(self):
        from paddle_tpu import observability as obs

        return {"lazy.recompiles": obs.counter_value("lazy.recompiles")}

    def counters(self):
        from paddle_tpu import observability as obs

        return {"lazy.flushes": obs.counter_value("lazy.flushes"),
                "lazy.cache_hits": obs.counter_value("lazy.cache_hits")}

    def close(self):
        self.pool = self.net = None
        self._guard.close()
