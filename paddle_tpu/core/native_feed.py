"""Native data-feed pipeline + async host->device staging.

Two halves of "the input pipeline never serializes with the device":

- ctypes binding for the C++ multi-slot reader (csrc/data_feed.cc):
  builds the shared library on first use (g++, baked into the image)
  and falls back cleanly (load() returns None) when no toolchain is
  available so the Python feed path takes over;
- ``AsyncDeviceFeeder``: a bounded double-buffer that stages the NEXT
  step's feed dict onto the device from a background thread while the
  device computes the current step. The compiled executor passes
  jax.Array feeds straight through (compiler_engine feed staging), so
  a feeder-supplied batch costs the step's critical path only the
  queue pop — ``feed.wait_ms`` measures exactly the stall that
  remains.
"""
from __future__ import annotations

import ctypes
import os
import queue
import subprocess
import threading
import time

import numpy as np


class AsyncDeviceFeeder:
    """Double-buffered host->device feed staging.

    Wraps an iterator of ``{name: np.ndarray}`` batches; a background
    thread keeps up to ``depth`` batches staged on ``device`` (via
    jax.device_put — async dispatch, so the transfer itself also
    overlaps the thread's next parse). Iterating yields dicts of
    jax.Arrays ready to feed ``Executor.run``; the consumer-side stall
    is recorded as ``feed.wait_ms``.

    ``close()`` (or exhaustion) joins the thread; the feeder is also a
    context manager. A ``depth`` of 2 is the classic double buffer:
    one batch in flight to the device while one is being consumed.
    """

    _DONE = object()

    def __init__(self, batches, depth: int = 2, device=None):
        if depth < 1:
            raise ValueError("AsyncDeviceFeeder depth must be >= 1")
        self._q: "queue.Queue" = queue.Queue(maxsize=depth)
        self._device = device
        self._err = None
        self._closed = False
        self._thread = threading.Thread(
            target=self._pump, args=(iter(batches),), daemon=True)
        self._thread.start()

    def _stage(self, batch):
        import jax

        return {k: jax.device_put(v, self._device)
                for k, v in batch.items()}

    def _put(self, item) -> bool:
        """Bounded put that re-checks the close flag: a close() racing
        a full queue must never strand this thread on a blocking put
        (at depth=1 the drain in close() and an in-flight put can
        refill the single slot — the classic shutdown deadlock)."""
        while not self._closed:
            try:
                self._q.put(item, timeout=0.05)
                return True
            except queue.Full:
                continue
        return False

    def _pump(self, it):
        try:
            for batch in it:
                if self._closed or not self._put(self._stage(batch)):
                    return
        except Exception as e:  # surfaced to the consumer on next()
            self._err = e
        finally:
            self._put(self._DONE)

    def __iter__(self):
        return self

    def __next__(self):
        t0 = time.perf_counter()
        item = self._q.get()
        from .. import observability as _obs

        if _obs.enabled():
            _obs.observe("feed.wait_ms",
                         (time.perf_counter() - t0) * 1e3)
        if item is self._DONE:
            if self._err is not None:
                err, self._err = self._err, None
                raise err
            raise StopIteration
        return item

    def close(self):
        self._closed = True
        # drain so the pump thread's bounded put unblocks promptly
        # (it also re-checks _closed itself, so even a refilled queue
        # cannot strand it)
        try:
            while True:
                self._q.get_nowait()
        except queue.Empty:
            pass
        self._thread.join(timeout=5)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False

_lock = threading.Lock()
_lib = None
_tried = False

_CSRC = os.path.join(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))), "csrc")
_SRC = os.path.join(_CSRC, "data_feed.cc")
_SO = os.path.join(_CSRC, "libptfeed.so")


def load():
    """The loaded library, building it if needed; None if unavailable."""
    global _lib, _tried
    with _lock:
        if _lib is not None or _tried:
            return _lib
        _tried = True
        if not os.path.exists(_SO) or (
                os.path.exists(_SRC)
                and os.path.getmtime(_SRC) > os.path.getmtime(_SO)):
            if not os.path.exists(_SRC):
                return None
            try:
                subprocess.run(
                    ["g++", "-O2", "-std=c++17", "-shared", "-fPIC",
                     _SRC, "-o", _SO, "-pthread"],
                    check=True, capture_output=True, timeout=120)
            except Exception:
                return None
        try:
            lib = ctypes.CDLL(_SO)
        except OSError:
            return None
        lib.ptfeed_create.restype = ctypes.c_void_p
        lib.ptfeed_create.argtypes = [
            ctypes.POINTER(ctypes.c_char_p), ctypes.c_int,
            ctypes.POINTER(ctypes.c_int), ctypes.c_int, ctypes.c_int,
            ctypes.c_int, ctypes.c_int]
        lib.ptfeed_next.restype = ctypes.c_int64
        lib.ptfeed_next.argtypes = [ctypes.c_void_p]
        lib.ptfeed_slot_size.restype = ctypes.c_int64
        lib.ptfeed_slot_size.argtypes = [ctypes.c_void_p, ctypes.c_int]
        lib.ptfeed_slot_fvals.restype = ctypes.POINTER(ctypes.c_float)
        lib.ptfeed_slot_fvals.argtypes = [ctypes.c_void_p, ctypes.c_int]
        lib.ptfeed_slot_ivals.restype = ctypes.POINTER(ctypes.c_int64)
        lib.ptfeed_slot_ivals.argtypes = [ctypes.c_void_p, ctypes.c_int]
        lib.ptfeed_slot_offsets.restype = ctypes.POINTER(ctypes.c_int64)
        lib.ptfeed_slot_offsets.argtypes = [ctypes.c_void_p, ctypes.c_int]
        lib.ptfeed_slot_num_offsets.restype = ctypes.c_int64
        lib.ptfeed_slot_num_offsets.argtypes = [ctypes.c_void_p,
                                                ctypes.c_int]
        lib.ptfeed_destroy.argtypes = [ctypes.c_void_p]
        _lib = lib
        return _lib


class NativeMultiSlotFeed:
    """Iterates (slot arrays, slot lod offsets) batches parsed by the
    C++ reader threads. slot_types: 'float' | 'int64' per slot."""

    def __init__(self, filelist, slot_types, batch_size, num_threads=2,
                 queue_capacity=16):
        lib = load()
        if lib is None:
            raise RuntimeError("native feed library unavailable")
        self._lib = lib
        self._types = [0 if t in ("float", "float32") else 1
                       for t in slot_types]
        files = (ctypes.c_char_p * len(filelist))(
            *[f.encode() for f in filelist])
        types = (ctypes.c_int * len(self._types))(*self._types)
        self._h = lib.ptfeed_create(files, len(filelist), types,
                                    len(self._types), batch_size,
                                    num_threads, queue_capacity)
        self._closed = False

    def __iter__(self):
        return self

    def __next__(self):
        if self._closed:
            raise StopIteration
        n = self._lib.ptfeed_next(self._h)
        if n == 0:
            raise StopIteration
        slots = []
        for s in range(len(self._types)):
            size = self._lib.ptfeed_slot_size(self._h, s)
            noff = self._lib.ptfeed_slot_num_offsets(self._h, s)
            offs = np.ctypeslib.as_array(
                self._lib.ptfeed_slot_offsets(self._h, s),
                shape=(noff,)).copy()
            if self._types[s] == 0:
                vals = np.ctypeslib.as_array(
                    self._lib.ptfeed_slot_fvals(self._h, s),
                    shape=(size,)).copy()
            else:
                vals = np.ctypeslib.as_array(
                    self._lib.ptfeed_slot_ivals(self._h, s),
                    shape=(size,)).copy()
            slots.append((vals, offs))
        return slots

    def close(self):
        if not self._closed and self._h:
            self._lib.ptfeed_destroy(self._h)
            self._closed = True

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass
