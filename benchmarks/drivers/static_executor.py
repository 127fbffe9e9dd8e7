"""Driver of the Program -> Executor path on one chip.

The measured step is the loop a user of the reference API writes: one
``exe.run(program, feed=..., fetch_list=[loss])`` that returns the loss to the
host as numpy, so every step ends in its own device-to-host fetch.
"""
from __future__ import annotations

import numpy as np


def check_shapes(leaves, params, shape_of):
    for leaf, target in leaves.items():
        want, got = tuple(params[leaf].shape), tuple(shape_of(target))
        if want != got:
            raise SystemExit("benchmark: leaf %s is %r in the reference and "
                             "%r in the program" % (leaf, want, got))


class Driver:
    def __init__(self, model, cfg, traffic, devices):
        self.model, self.cfg, self.traffic = model, cfg, traffic
        self.devices = devices
        self.items_per_step = traffic["items_per_step"]

    def build(self):
        import paddle_tpu as fluid

        self.built = self.model.build_static(self.cfg, self.traffic)
        self.scope = fluid.Scope()
        self.exe = fluid.Executor(fluid.TPUPlace(0))
        with fluid.scope_guard(self.scope):
            self.exe.run(self.built["startup"])
        self.program = self.compiled()

    def compiled(self):
        return self.built["main"]

    def stage(self, array):
        """Batches are staged uncommitted, as ``bench.py:_device_feed`` does:
        a committed feed against uncommitted state recompiles the step."""
        return array

    def load(self, params, pool):
        from paddle_tpu.core.tensor import LoDTensor

        leaves = self.built["leaves"]
        check_shapes(leaves, params, lambda name: self._array(name).shape)
        for leaf, name in leaves.items():
            self.scope.find_var(name).get_tensor().set(params[leaf])
        self.pool = [{k: LoDTensor(self.stage(v))
                      for k, v in self.model.to_feed(batch).items()}
                     for batch in pool]

    def _array(self, name):
        return self.scope.find_var(name).get_tensor().array

    def step(self, i):
        import paddle_tpu as fluid

        with fluid.scope_guard(self.scope):
            (loss,) = self.exe.run(self.program,
                                   feed=self.pool[i % len(self.pool)],
                                   fetch_list=[self.built["loss"]])
        return float(np.mean(loss))

    def params(self):
        return {leaf: self._array(name)
                for leaf, name in self.built["leaves"].items()}

    def first_moment(self):
        """(per-leaf first-moment arrays, factor that turns the moment after
        one step into the gradient the optimizer was given)."""
        return ({leaf: self._array(self.built["moment"] % name)
                 for leaf, name in self.built["leaves"].items()},
                self.built["moment_scale"])

    def watched(self):
        """Counters that must not grow inside the window."""
        from paddle_tpu import observability as obs

        return {
            "executor.compiles": obs.counter_value("executor.compiles"),
            "executor.jit_traces": obs.counter_value("executor.jit_traces"),
            "executor.compile_fallbacks":
                obs.counter_value("executor.compile_fallbacks"),
            "executor.steps{path=interpreter}":
                obs.counter_value("executor.steps", path="interpreter"),
            "parallel.compiles": obs.counter_value("parallel.compiles"),
        }

    def counters(self):
        from paddle_tpu import observability as obs

        return {"executor.steps{path=compiled}":
                obs.counter_value("executor.steps", path="compiled"),
                "parallel.collective_ops":
                obs.counter_value("parallel.collective_ops")}

    def close(self):
        self.pool = self.scope = self.exe = self.program = self.built = None
