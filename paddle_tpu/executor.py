"""fluid.Executor — the user-facing program runner.

Parity: /root/reference/python/paddle/fluid/executor.py:437 (Executor,
feed/fetch handling :529-575, program cache :936, _run_parallel :627,
train_from_dataset :1187). TPU-native difference: instead of injecting
feed/fetch ops and running a C++ op loop, `run` stages feeds into the
scope and dispatches to either

- the whole-program XLA compiler (default for feed→fetch programs: the
  block is traced once into a jitted function, cached by shapes — this is
  where TPU throughput comes from), or
- the op-by-op CoreExecutor (programs with host ops / LoD dynamism).

`CompiledProgram`s route through the parallel engine (compiler.py).
"""
from __future__ import annotations

from typing import Dict, List, Optional, Sequence

import numpy as np

from . import framework
from .core import CoreExecutor, CPUPlace, Scope, TPUPlace, global_scope
from .core.registry import OpInfoMap
from .core.tensor import LoDTensor


def _as_place(place):
    if place is None:
        return CPUPlace()
    return place


class Executor:
    def __init__(self, place=None):
        self.place = _as_place(place)
        self._core = CoreExecutor(self.place)
        self._compiled_cache: Dict = {}
        self._traceable_cache: Dict = {}
        self._compile_fallbacks: Dict = {}
        self._lod_lowered_cache: Dict = {}
        self._infer_clone_cache: Dict = {}
        self._runs = 0   # the step= of this executor's spans
        self._closed = False

    def close(self):
        self._closed = True

    def run(
        self,
        program=None,
        feed=None,
        fetch_list=None,
        feed_var_name="feed",
        fetch_var_name="fetch",
        scope=None,
        return_numpy=True,
        use_program_cache=False,
        use_prune=False,
    ):
        from .compiler import CompiledProgram

        scope = scope if scope is not None else global_scope()
        if program is None:
            program = framework.default_main_program()

        feed = feed or {}
        fetch_list = list(fetch_list or [])

        from . import observability as _obs

        # one span around the whole call, whichever engine it ends in;
        # the spans opened inside it (the compiled step's, the
        # interpreter's, the mesh engines') inherit its step, the count
        # of this executor's runs
        self._runs += 1
        with _obs.tracing.span("executor/run", cat="step",
                               step=self._runs):
            if isinstance(program, CompiledProgram):
                return program._run(self, feed, fetch_list, scope,
                                    return_numpy)
            return self._run(program, scope, feed, fetch_list,
                             return_numpy)

    def _run(self, program, scope, feed, fetch_list, return_numpy):
        from .core.flags import flag as _flag

        # FLAGS_check_nan_inf needs the per-op interpreter (the check
        # runs after every op, reference operator.cc:1032)
        if not _flag("check_nan_inf"):
            import time

            from . import observability as _obs
            from .core.compiler_engine import (UntraceableProgramError,
                                               run_compiled_program)

            t_run = time.perf_counter() if _obs.enabled() else None
            with _obs.tracing.span("executor/prepare", cat="step"):
                run_args = self._compiled_run_args(program, feed,
                                                   fetch_list)
            if run_args is not None:
                try:
                    out = run_compiled_program(
                        self._core, run_args[0], scope, run_args[1],
                        fetch_list, return_numpy)
                except UntraceableProgramError as e:
                    # e.g. a while carry whose shape/dtype varies
                    # across trips — valid for the host interpreter,
                    # untraceable for lax.while_loop. Remember so
                    # later steps skip the doomed trace attempt —
                    # and SAY so: this is a large perf cliff that
                    # must not be silent. A failure AFTER the trace
                    # (kernel lowering, XLA compile) is not caught:
                    # a traceable program that does not compile is
                    # an error, not an interpreter run.
                    import warnings

                    from .core.compiler_engine import _program_version

                    warnings.warn(
                        "program %s falls back to op-by-op "
                        "interpretation (whole-program compile "
                        "failed: %r)" % (program._uid, e))
                    self._compile_fallbacks[
                        _program_version(program)] = repr(e)
                    _obs.inc("executor.compile_fallbacks")
                else:
                    if t_run is not None:
                        # host step latency: the whole call, fetch
                        # included (short of the close of executor/run)
                        _obs.observe("executor.step_ms",
                                     (time.perf_counter() - t_run) * 1e3,
                                     path="compiled")
                    # sampled in-production capture
                    # (PADDLE_TPU_SAMPLE_EVERY): every Nth
                    # successful compiled step re-profiles the
                    # live program into a rolling report for the
                    # steering daemon — default off, one branch
                    from .observability import capture as _capture

                    _capture.maybe_sample_step(
                        "executor", run_args[0], scope, run_args[1])
                    return out
        return self._core.run_program(program, scope, feed, fetch_list,
                                      return_numpy)

    def _compiled_run_args(self, program, feed, fetch_list):
        """(program, feed) to hand the whole-program compiler, or None
        when this program takes the interpreter."""
        from .core.compiler_engine import _program_version

        if _program_version(program) in self._compile_fallbacks:
            return None
        if self._can_whole_compile(program):
            return program, feed
        # LoD feeds + sequence ops: try the padded/masked
        # lowering (core/lod_lowering.py) so ragged text
        # programs still get the one-dispatch XLA path
        return self._lod_lowered(program, feed, fetch_list)

    def lower(self, program=None, feed=None, fetch_list=None,
              scope=None):
        """The ``jax.stages.Lowered`` of the whole-program step
        ``run`` would execute for this (program, feed, fetch_list) —
        ``.as_text()`` shows what the step contains, e.g. whether a
        Pallas kernel is in it as a ``tpu_custom_call``. It executes
        nothing."""
        from .core.compiler_engine import lower_compiled_program

        scope = scope if scope is not None else global_scope()
        if program is None:
            program = framework.default_main_program()
        return lower_compiled_program(self._core, program, scope,
                                      feed or {}, list(fetch_list or []))

    def _lod_lowered(self, program, feed, fetch_list):
        """(lowered_program, padded_feed) when every ragged feed pads
        into the compiled path, else None. The lowered clone is cached
        per program version; feeds re-pad every step (bucketed, so
        recompiles stay O(log max_len))."""
        from .core.compiler_engine import _program_version
        from .core.lod_lowering import (_len_name, build_lowered,
                                        pad_lod_feed)

        lod_with_levels = [(n, len(v.lod())) for n, v in feed.items()
                           if isinstance(v, LoDTensor) and v.lod()]
        if not lod_with_levels:
            return None
        if any(lv != 1 for _, lv in lod_with_levels):
            # multi-level lod (sub-sequences): padding flattens the
            # wrong level — interpreter only
            return None
        lod_feeds = sorted(n for n, _ in lod_with_levels)
        ver = (_program_version(program), tuple(lod_feeds))
        hit = self._lod_lowered_cache.get(ver)
        if hit is None:
            from . import observability as _obs
            from .core.compiler_engine import block_is_traceable
            from .core.lod_lowering import Decline

            built = build_lowered(program, lod_feeds)
            if isinstance(built, Decline):
                import warnings

                _obs.inc("lod_lowering.declines", op_type=built.op_type,
                         reason=built.reason)
                warnings.warn(
                    "LoD lowering declined for program %s (op #%d "
                    "%s: %s) — ragged steps take the op-by-op "
                    "interpreter" % (program._uid, built.op_index,
                                     built.op_type, built.reason))
                built = None
            elif not block_is_traceable(built[0].global_block()):
                built = None  # other blockers remain (while bodies...)
            self._lod_lowered_cache[ver] = built if built is not None \
                else False
            hit = self._lod_lowered_cache[ver]
        if hit is False:
            return None
        lowered, ragged_feeds, ragged_vars = hit
        # PER-CALL check (fetch_list varies between calls on the same
        # program): fetching a ragged intermediate would return PADDED
        # values — those calls take the interpreter, others stay
        # compiled
        names = {f if isinstance(f, str) else f.name for f in fetch_list}
        if names & ragged_vars:
            return None
        feed2 = {}
        for n, v in feed.items():
            if n in ragged_feeds:
                padded, lens = pad_lod_feed(v)
                feed2[n] = padded
                feed2[_len_name(n)] = lens
            else:
                feed2[n] = v
        return lowered, feed2

    def _can_whole_compile(self, program) -> bool:
        # sub-blocks (while/conditional bodies) are fine — they lower to
        # lax.while_loop/lax.cond if pure; any other host/LoD op drops
        # the program to the interpreter. Cached per program version:
        # this runs on every step.
        from .core.compiler_engine import _program_version, block_is_traceable

        ver = _program_version(program)
        hit = self._traceable_cache.get(ver)
        if hit is None:
            hit = block_is_traceable(program.global_block())
            self._traceable_cache[ver] = hit
            if not hit and len(program.global_block().ops) >= 64:
                # op-by-op interpretation of a big program is a 10-100x
                # perf cliff (one device dispatch per op per step) —
                # never take it silently (round-3 lesson: a single host
                # `range` op dropped the 1440-op BERT program to the
                # interpreter and the bench collapsed 30x)
                import warnings

                from .core.compiler_engine import untraceable_reasons

                warnings.warn(
                    "program %s (%d ops) is NOT whole-program "
                    "compilable and will run op-by-op on the "
                    "interpreter; blocking ops: %s"
                    % (program._uid, len(program.global_block().ops),
                       ", ".join(untraceable_reasons(
                           program.global_block())) or "?"))
        return hit

    # -- Dataset-driven training (reference train_from_dataset) -----------
    def train_from_dataset(self, program=None, dataset=None, scope=None,
                           thread=0, debug=False, fetch_list=None,
                           fetch_info=None, print_period=100):
        """Dataset-driven training through the trainer/device-worker
        stack (reference executor.py:1187 -> _prepare_trainer :1013 ->
        TrainerFactory): N Hogwild workers over disjoint dataset
        shards, shared scope, shared compiled step. Worker class and
        debug dumps come from ``program._fleet_opt`` like the
        reference's opt_info plumbing."""
        from .trainer_factory import TrainerDesc, TrainerFactory

        scope = scope or global_scope()
        program = program or framework.default_main_program()
        if dataset is None:
            raise ValueError("dataset is required")
        desc = TrainerDesc()
        desc.thread_num = int(thread) or getattr(dataset, "_thread_num",
                                                 0) or 1
        desc.fetch_vars = fetch_list or []
        desc.fetch_info = fetch_info or []
        desc.print_period = print_period
        desc.debug = debug
        fleet_opt = getattr(program, "_fleet_opt", None) or {}
        desc.device_worker = fleet_opt.get("worker_class", "Hogwild")
        desc.dump_fields = list(fleet_opt.get("dump_fields", []))
        desc.dump_fields_path = fleet_opt.get("dump_fields_path", "")
        desc.dump_param = list(fleet_opt.get("dump_param", []))
        trainer = TrainerFactory().create_trainer(desc)
        return trainer.run(program, dataset, scope, self)

    def infer_from_dataset(self, program=None, dataset=None, scope=None,
                           thread=0, debug=False, fetch_list=None,
                           fetch_info=None, print_period=100):
        """Side-effect-free dataset pass (reference executor.py:1120):
        runs a for_test clone — backward/optimizer ops pruned by op
        role — so parameters are NEVER mutated, unlike
        train_from_dataset. The clone is cached per program version: a
        fresh clone each call would recompile the XLA program every
        epoch."""
        from .core.compiler_engine import _program_version

        program = program or framework.default_main_program()
        ver = _program_version(program)
        clone = self._infer_clone_cache.get(ver)
        if clone is None:
            clone = program.clone(for_test=True)
            self._infer_clone_cache[ver] = clone
        return self.train_from_dataset(
            clone, dataset, scope, thread, debug, fetch_list,
            fetch_info, print_period)
