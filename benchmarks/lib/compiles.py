"""Counts and times every executable XLA builds in this process.

Copied from ``chip_smoke.py:_XlaCompiles`` (PR 21). ``builds`` counts backend
compiles whether the persistent cache served them or not; the executor's own
counters miss an XLA rebuild for a changed input layout, this does not.
"""
from __future__ import annotations


class XlaCompiles:
    def __init__(self):
        import jax

        self.builds = 0
        self.cache_hits = 0
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._timed)
        jax.monitoring.register_event_listener(self._event)

    def _timed(self, event, seconds, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.builds += 1
            self.seconds += seconds

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
