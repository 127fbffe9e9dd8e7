"""CompiledProgram / BuildStrategy / ExecutionStrategy.

Parity: /root/reference/python/paddle/fluid/compiler.py:87 (CompiledProgram,
with_data_parallel :160) + details/build_strategy.h knobs. TPU-native
semantics: ``with_data_parallel`` does NOT clone the graph per device with
SSA all-reduce op-handles (the reference's ParallelExecutor); it marks the
program for *mesh execution* — the whole-program trace is wrapped in
shard_map over a 1-D device mesh with the batch dim sharded and gradients
psum-ed where `c_allreduce`/loss-scaling ops appear (parallel/engine.py).
BuildStrategy knobs that are XLA-automatic (op fusion, memory reuse,
inplace) are accepted and ignored — the compiler does them.
"""
from __future__ import annotations

from typing import Optional


class ExecutionStrategy:
    def __init__(self):
        self.num_threads = 0
        self.num_iteration_per_drop_scope = 100
        self.num_iteration_per_run = 1
        self.use_thread_barrier = True


class BuildStrategy:
    """Knob disposition under the XLA model (details/build_strategy.h):

    - IMPLEMENTED here: ``sync_batch_norm`` (BN stats pmean across the
      mesh), ``gradient_scale_strategy`` (CoeffNumDevice = 1/n loss-grad
      scale; One = no scaling — the user's loss handles it).
    - SUBSUMED by the compiler (accepted, nothing to do): the fusion
      knobs (XLA fuses during lowering), ``enable_inplace`` /
      ``memory_optimize`` (buffer donation + XLA buffer assignment),
      ``fuse_all_reduce_ops`` (XLA groups collectives),
      ``remove_unnecessary_lock`` (no locks exist).
    - INERT and WARNED when enabled: ``enable_sequential_execution``,
      ``fuse_all_optimizer_ops`` (no analog; a perf knob silently
      ignored is worse than a warning).
    """

    class ReduceStrategy:
        AllReduce = 0
        Reduce = 1

    class GradientScaleStrategy:
        CoeffNumDevice = 0
        One = 1
        Customized = 2

    # accepted-and-ignored ON PURPOSE: XLA owns these optimizations
    _SUBSUMED = {"fuse_elewise_add_act_ops", "fuse_bn_act_ops",
                 "fuse_all_reduce_ops", "enable_inplace",
                 "memory_optimize", "remove_unnecessary_lock",
                 "reduce_strategy"}
    # no analog exists — enabling one warns
    _INERT = {"enable_sequential_execution", "fuse_all_optimizer_ops"}

    def __init__(self):
        self.reduce_strategy = BuildStrategy.ReduceStrategy.AllReduce
        self.gradient_scale_strategy = (
            BuildStrategy.GradientScaleStrategy.CoeffNumDevice)
        self.fuse_elewise_add_act_ops = False
        self.fuse_bn_act_ops = False
        self.fuse_all_reduce_ops = True
        self.fuse_all_optimizer_ops = False
        self.enable_inplace = True
        self.memory_optimize = None
        self.sync_batch_norm = False
        self.enable_sequential_execution = False
        self.remove_unnecessary_lock = True
        self.num_trainers = 1
        self.trainer_id = 0
        self.nccl_comm_num = 1

    def _warn_inert(self):
        import warnings

        for k in sorted(self._INERT):
            if getattr(self, k, False):
                warnings.warn(
                    "BuildStrategy.%s has no effect on the TPU/XLA "
                    "engine (no analog exists); the knob is ignored"
                    % k)


class CompiledProgram:
    def __init__(self, program_or_graph, build_strategy: Optional[BuildStrategy] = None):
        self._program = program_or_graph
        self._build_strategy = build_strategy or BuildStrategy()
        self._is_data_parallel = False
        self._loss_name = None
        self._exec_strategy = None
        self._places = None
        self._share_vars_from = None

    def with_data_parallel(self, loss_name=None, build_strategy=None,
                           exec_strategy=None, share_vars_from=None,
                           places=None):
        self._is_data_parallel = True
        self._loss_name = loss_name
        if build_strategy is not None:
            self._build_strategy = build_strategy
        self._exec_strategy = exec_strategy or ExecutionStrategy()
        self._places = places
        self._share_vars_from = share_vars_from
        return self

    # called by Executor.run
    def _run(self, executor, feed, fetch_list, scope, return_numpy):
        if not self._is_data_parallel:
            # inside the caller's executor/run: one root, one run counted
            return executor._run(self._program, scope, feed, fetch_list,
                                 return_numpy)
        # a pipelined program (PipelineOptimizer metadata) over a mesh
        # with a 'pp' axis routes to the pipeline engine — composes
        # with dp replicas and model axes (dp x pp x mp in one program)
        try:
            from jax.sharding import Mesh
        except Exception:  # pragma: no cover
            Mesh = ()
        mesh = self._places if isinstance(self._places, Mesh) else None
        if mesh is not None and \
                getattr(self._program, "_pipeline_meta", None) and \
                "pp" in mesh.axis_names:
            from .parallel.pipeline import run_pipeline_parallel

            return run_pipeline_parallel(
                executor._core, self._program, scope, feed, fetch_list,
                mesh=mesh, return_numpy=return_numpy)
        from .parallel.engine import run_data_parallel

        return run_data_parallel(
            executor._core, self._program, scope, feed, fetch_list,
            loss_name=self._loss_name, places=self._places,
            build_strategy=self._build_strategy, return_numpy=return_numpy)
