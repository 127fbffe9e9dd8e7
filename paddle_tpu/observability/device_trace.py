"""Device-truth profiling: XPlane capture, phase folding, host cross-check.

The PR-7 step profiler attributes step time by *host-side re-execution*
of phase slices — a measurement, but not device truth: dispatch floors,
sync overhead and XLA's scheduler all sit between the host numbers and
what the chip actually did. This module closes that gap:

**Capture** (``capture_xspace`` / ``device_profile_step``). One bench
step is re-jitted (with the ``jax.named_scope("<phase>/<op_type>")``
labels every trace gets at the shared trace entry in
``core/compiler_engine``) and run
a few times under ``jax.profiler`` — the same XPlane capture
TensorBoard's profiler plugin consumes. Compilation happens *before*
the trace starts, so the capture holds steady-state steps only.

**Parse** (``parse_xspace``). A minimal, dependency-free protobuf
wire-format reader for the XSpace container (planes → lines → events,
with the interned event/stat metadata tables) plus the serialized HLO
proto the ``/host:metadata`` plane carries per compiled module. Only
varint / length-delimited / fixed fields are touched; unknown fields
are skipped — the schema additions land as silently-ignored fields,
exactly the protobuf forward-compat contract. Nothing here imports
tensorflow or protobuf.

**Fold** (``fold_device_phases``). Device op events resolve to an HLO
instruction (by event name, or the ``hlo_op`` stat), the instruction's
``metadata.op_name`` carries the named_scope path, and the first path
component matching a known phase claims the interval. Per-phase device
time is the interval *union* (concurrent thunks don't double-count),
collective-vs-compute overlap and the busy-time critical path come
from the same ``analyze_timeline`` the host profiler uses — one
analyzer, two input sources. Ops whose scope resolves to no known
phase are tolerated (accounted as ``unattributed_ms``); a trace with
NO phase-attributed events folds to ``None`` and the caller keeps the
host numbers (the explicit fallback contract — a missing device story
must never fabricate one).

**Cross-check** (``cross_check``). Per-phase agreement ratio
``min(host, device) / max(host, device)`` plus a duration-weighted
overall ``agreement`` — surfaced in the bench ``profile`` block and
watched by ``tools/bench_diff.py``, so a silently-diverging host
estimate fails the perf gate instead of quietly steering the bucket
planner wrong.

Env contract: ``PADDLE_TPU_DEVICE_TRACE=1`` arms capture in bench runs
(multichip configs default it ON, single-chip OFF — the same
convention as ``PADDLE_TPU_PROFILE_BENCH``). Default-off costs one env
read; ci gate 4 guards it.
"""
from __future__ import annotations

import glob
import os
import struct
import tempfile
from typing import Dict, List, Optional, Tuple

__all__ = [
    "PHASES", "capture_enabled", "parse_xspace", "encode_xspace",
    "find_xplane_files", "load_trace_dir", "capture_xspace",
    "phase_of_op_name", "fold_device_phases", "cross_check",
    "device_profile_step",
]

PHASES = ("forward", "backward", "collective", "optimizer")


def capture_enabled(default: bool = False) -> bool:
    """``PADDLE_TPU_DEVICE_TRACE`` switch; unset keeps the caller's
    default (bench: ON for multichip configs, OFF single-chip)."""
    raw = os.environ.get("PADDLE_TPU_DEVICE_TRACE", "").strip().lower()
    if not raw:
        return bool(default)
    return raw in ("1", "true", "yes", "on")


# -- protobuf wire reader ---------------------------------------------------
#
# XSpace schema (tsl/profiler/protobuf/xplane.proto), fields used:
#   XSpace.planes=1
#   XPlane.name=2 .lines=3 .event_metadata=4(map) .stat_metadata=5(map)
#   XLine.name=2 .timestamp_ns=3 .events=4
#   XEvent.metadata_id=1 .offset_ps=2 .duration_ps=3 .stats=4
#   XStat.metadata_id=1 double=2 uint64=3 int64=4 str=5 bytes=6 ref=7
#   XEventMetadata.id=1 .name=2 .stats=5
#   XStatMetadata.id=1 .name=2
# HLO proto (xla/service/hlo.proto), fields used:
#   HloProto.hlo_module=1; HloModuleProto.computations=3
#   HloComputationProto.instructions=2
#   HloInstructionProto.name=1 .metadata=7; OpMetadata.op_name=2


def _read_varint(b: bytes, i: int) -> Tuple[int, int]:
    x = 0
    s = 0
    while True:
        c = b[i]
        i += 1
        x |= (c & 0x7F) << s
        if not (c & 0x80):
            return x, i
        s += 7
        if s > 70:
            raise ValueError("varint overflow")


def _iter_fields(b: bytes):
    """Yield (field_number, wire_type, value) over one message's bytes.
    value: int for varint fields, raw bytes otherwise."""
    i, n = 0, len(b)
    while i < n:
        key, i = _read_varint(b, i)
        fnum, wt = key >> 3, key & 7
        if wt == 0:
            v, i = _read_varint(b, i)
        elif wt == 1:
            v = b[i:i + 8]
            i += 8
        elif wt == 2:
            ln, i = _read_varint(b, i)
            v = b[i:i + ln]
            i += ln
        elif wt == 5:
            v = b[i:i + 4]
            i += 4
        else:
            raise ValueError("unsupported wire type %d" % wt)
        if i > n:
            raise ValueError("truncated field")
        yield fnum, wt, v


def _utf8(v) -> str:
    return v.decode("utf-8", "replace") if isinstance(v, bytes) else str(v)


def _decode_stat(b: bytes, stat_names: Dict[int, str]):
    """(stat_name, value) from one XStat. ``ref_value`` stats resolve
    through the interned stat_metadata table (XLA interns hlo_op names
    this way)."""
    name = None
    val = None
    for fn, _wt, v in _iter_fields(b):
        if fn == 1:
            name = stat_names.get(v, str(v))
        elif fn == 2:
            val = struct.unpack("<d", v)[0]
        elif fn in (3, 4):
            val = v
        elif fn == 5:
            val = _utf8(v)
        elif fn == 6:
            val = bytes(v)
        elif fn == 7:
            val = stat_names.get(v, v)
    return name, val


def _parse_map_entry(b: bytes):
    """(key:int, value:bytes) of one map<int64, Message> entry."""
    k, v = None, b""
    for fn, _wt, fv in _iter_fields(b):
        if fn == 1:
            k = fv
        elif fn == 2:
            v = fv
    return k, v


def _parse_hlo_op_names(hlo_proto: bytes) -> Dict[str, str]:
    """{instruction name: metadata.op_name} over every computation of
    an HloProto — the join key between a device op event and the
    named_scope path the annotated trace stamped on it."""
    out: Dict[str, str] = {}
    for fn, _wt, module in _iter_fields(hlo_proto):
        if fn != 1:
            continue
        for fn2, _wt2, comp in _iter_fields(module):
            if fn2 != 3:
                continue
            for fn3, _wt3, instr in _iter_fields(comp):
                if fn3 != 2:
                    continue
                iname = opname = None
                for fn4, _wt4, v4 in _iter_fields(instr):
                    if fn4 == 1:
                        iname = _utf8(v4)
                    elif fn4 == 7:
                        for fn5, _wt5, v5 in _iter_fields(v4):
                            if fn5 == 2:
                                opname = _utf8(v5)
                if iname and opname:
                    out[iname] = opname
    return out


def _parse_event_metadata(b: bytes) -> Dict:
    meta = {"name": "", "stats_raw": []}
    for fn, _wt, v in _iter_fields(b):
        if fn == 2:
            meta["name"] = _utf8(v)
        elif fn == 5:
            meta["stats_raw"].append(v)
    return meta


def _parse_line(b: bytes, emeta: Dict, smeta: Dict) -> Dict:
    name = ""
    ts_ns = 0
    event_bufs: List[bytes] = []
    for fn, _wt, v in _iter_fields(b):
        if fn == 2:
            name = _utf8(v)
        elif fn == 3:
            ts_ns = v
        elif fn == 4:
            event_bufs.append(v)
    events = []
    for eb in event_bufs:
        mid = None
        off_ps = 0
        dur_ps = 0
        stats: Dict[str, object] = {}
        for fn, _wt, v in _iter_fields(eb):
            if fn == 1:
                mid = v
            elif fn == 2:
                off_ps = v
            elif fn == 3:
                dur_ps = v
            elif fn == 4:
                try:
                    sname, sval = _decode_stat(v, smeta)
                except (ValueError, IndexError, struct.error):
                    continue
                if sname is not None:
                    stats[sname] = sval
        meta = emeta.get(mid) or {}
        events.append({"name": meta.get("name", ""),
                       "ts_ps": ts_ns * 1000 + off_ps,
                       "dur_ps": dur_ps, "stats": stats})
    return {"name": name, "timestamp_ns": ts_ns, "events": events}


def _parse_plane(b: bytes) -> Dict:
    name = ""
    line_bufs: List[bytes] = []
    emeta: Dict[int, Dict] = {}
    smeta: Dict[int, str] = {}
    for fn, _wt, v in _iter_fields(b):
        if fn == 2:
            name = _utf8(v)
        elif fn == 3:
            line_bufs.append(v)
        elif fn == 4:
            k, mv = _parse_map_entry(v)
            if k is not None:
                emeta[k] = _parse_event_metadata(mv)
        elif fn == 5:
            k, mv = _parse_map_entry(v)
            if k is not None:
                for fn2, _wt2, v2 in _iter_fields(mv):
                    if fn2 == 2:
                        smeta[k] = _utf8(v2)
    hlo: Dict[str, str] = {}
    for m in emeta.values():
        for sb in m["stats_raw"]:
            try:
                sname, sval = _decode_stat(sb, smeta)
            except (ValueError, IndexError, struct.error):
                continue
            if sname == "Hlo Proto" and isinstance(sval, bytes):
                try:
                    hlo.update(_parse_hlo_op_names(sval))
                except (ValueError, IndexError):
                    continue
    return {"name": name,
            "lines": [_parse_line(lb, emeta, smeta) for lb in line_bufs],
            "hlo_op_names": hlo}


def parse_xspace(data: bytes) -> Dict:
    """Decode one ``*.xplane.pb`` into ``{"planes": [...]}`` — each
    plane with its lines, timestamped events (name / ts_ps / dur_ps /
    stats) and any HLO instruction → op_name map embedded in its
    metadata. Raises ValueError on bytes that are not an XSpace."""
    planes = []
    for fn, _wt, v in _iter_fields(data):
        if fn == 1:
            planes.append(_parse_plane(v))
    return {"planes": planes}


# -- encoder (fixtures / tests) ---------------------------------------------


def _enc_varint(x: int) -> bytes:
    out = bytearray()
    while True:
        b = x & 0x7F
        x >>= 7
        if x:
            out.append(b | 0x80)
        else:
            out.append(b)
            return bytes(out)


def _enc_len(fnum: int, payload: bytes) -> bytes:
    return _enc_varint(fnum << 3 | 2) + _enc_varint(len(payload)) + payload


def _enc_int(fnum: int, v: int) -> bytes:
    return _enc_varint(fnum << 3) + _enc_varint(int(v))


def _enc_hlo_proto(op_names: Dict[str, str]) -> bytes:
    instrs = b""
    for iname, opname in sorted(op_names.items()):
        meta = _enc_len(2, opname.encode())
        instrs += _enc_len(2, _enc_len(1, iname.encode())
                           + _enc_len(7, meta))
    comp = _enc_len(1, b"main") + instrs
    module = _enc_len(1, b"module") + _enc_len(3, comp)
    return _enc_len(1, module)


def encode_xspace(space: Dict) -> bytes:
    """Inverse of ``parse_xspace`` for the subset the fold reads —
    canned-fixture XPlane bytes for tests, no device needed. Plane
    dicts: ``{"name", "lines": [{"name", "timestamp_ns", "events":
    [{"name", "ts_ps", "dur_ps", "stats": {str: str}}]}],
    "hlo_op_names": {instr: op_name}}``."""
    out = b""
    for plane in space.get("planes") or []:
        ev_names: Dict[str, int] = {}
        st_names: Dict[str, int] = {}

        def _ev_id(name: str) -> int:
            if name not in ev_names:
                ev_names[name] = len(ev_names) + 1
            return ev_names[name]

        def _st_id(name: str) -> int:
            if name not in st_names:
                st_names[name] = len(st_names) + 1
            return st_names[name]

        lines_b = b""
        for line in plane.get("lines") or []:
            ts_ns = int(line.get("timestamp_ns") or 0)
            evs_b = b""
            for ev in line.get("events") or []:
                body = _enc_int(1, _ev_id(ev.get("name") or ""))
                body += _enc_int(2, int(ev.get("ts_ps", 0)) - ts_ns * 1000)
                body += _enc_int(3, int(ev.get("dur_ps", 0)))
                for sn, sv in (ev.get("stats") or {}).items():
                    stat = _enc_int(1, _st_id(sn)) + _enc_len(
                        5, str(sv).encode())
                    body += _enc_len(4, stat)
                evs_b += _enc_len(4, body)
            lines_b += _enc_len(3, _enc_len(2, (line.get("name")
                                                or "").encode())
                                + _enc_int(3, ts_ns) + evs_b)
        hlo = plane.get("hlo_op_names") or {}
        hlo_meta = b""
        if hlo:
            stat = _enc_int(1, _st_id("Hlo Proto")) + _enc_len(
                6, _enc_hlo_proto(hlo))
            mod_meta = (_enc_int(1, len(ev_names) + 1)
                        + _enc_len(2, b"hlo_module")
                        + _enc_len(5, stat))
            hlo_meta = _enc_len(4, _enc_int(1, len(ev_names) + 1)
                                + _enc_len(2, mod_meta))
        emeta_b = b""
        for name, mid in ev_names.items():
            entry = _enc_int(1, mid) + _enc_len(2, name.encode())
            emeta_b += _enc_len(4, _enc_int(1, mid) + _enc_len(2, entry))
        smeta_b = b""
        for name, sid in st_names.items():
            entry = _enc_int(1, sid) + _enc_len(2, name.encode())
            smeta_b += _enc_len(5, _enc_int(1, sid) + _enc_len(2, entry))
        plane_b = (_enc_len(2, (plane.get("name") or "").encode())
                   + emeta_b + hlo_meta + smeta_b + lines_b)
        out += _enc_len(1, plane_b)
    return out


# -- capture ----------------------------------------------------------------


def find_xplane_files(trace_dir: str) -> List[str]:
    """``*.xplane.pb`` files of the NEWEST profiler run under
    ``trace_dir`` (jax writes ``plugins/profile/<stamp>/<host>.xplane.pb``
    per capture)."""
    runs = [d for d in glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*")) if os.path.isdir(d)]
    if runs:
        newest = max(runs, key=os.path.getmtime)
        return sorted(glob.glob(os.path.join(newest, "*.xplane.pb")))
    return sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                            recursive=True))


def load_trace_dir(trace_dir: str) -> Dict:
    """Parse every XPlane file of the newest capture under
    ``trace_dir`` into one merged ``{"planes": [...]}``; unreadable
    files are skipped (a torn capture degrades, never raises)."""
    planes: List[Dict] = []
    for path in find_xplane_files(trace_dir):
        try:
            with open(path, "rb") as f:
                planes.extend(parse_xspace(f.read())["planes"])
        except (OSError, ValueError, IndexError):
            continue
    return {"planes": planes}


def capture_xspace(run, trace_dir: Optional[str] = None) -> Dict:
    """Run ``run()`` under a ``jax.profiler`` trace and return the
    parsed XSpace. The caller is responsible for compiling OUTSIDE the
    capture window (or the trace times XLA's compiler, not the step).
    A caller-supplied ``trace_dir`` is kept on disk (TensorBoard can
    open it); without one, the scratch capture dir is removed once
    parsed — captures are MBs each and a CI host must not accumulate
    them."""
    import jax

    d = trace_dir or tempfile.mkdtemp(prefix="ptpu_devtrace_")
    jax.profiler.start_trace(d)
    try:
        run()
    finally:
        jax.profiler.stop_trace()
    try:
        return load_trace_dir(d)
    finally:
        if trace_dir is None:
            import shutil

            shutil.rmtree(d, ignore_errors=True)


# -- phase folding ----------------------------------------------------------


def phase_of_op_name(op_name) -> Optional[str]:
    """First path component of a named_scope path that names a known
    phase (``jit(step)/jit(main)/backward/mul_grad/...`` → "backward");
    None for unknown scopes — the caller tolerates them."""
    if not op_name:
        return None
    for part in str(op_name).split("/"):
        if part in PHASES:
            return part
    return None


def fold_device_phases(space: Dict, steps: int = 1) -> Optional[Dict]:
    """Fold a parsed XSpace's device op intervals back into per-phase
    timings.

    Resolution per event: its name (or ``hlo_op`` stat) looked up in
    the capture's HLO instruction → op_name map, then the op_name's
    named_scope path; an event whose name itself carries a phase path
    (TraceMe-style) resolves directly. Per-phase time is the interval
    UNION across all lines (concurrent thunks counted once);
    collective-vs-compute overlap and the busy critical path come from
    ``analyze_timeline`` — the same math as the host report, different
    evidence. Returns None when NO event resolves to a phase (empty or
    annotation-less trace) — the caller falls back to host numbers.
    """
    from .profiler import _union_length, analyze_timeline

    steps = max(1, int(steps))
    hlo: Dict[str, str] = {}
    for plane in space.get("planes") or []:
        hlo.update(plane.get("hlo_op_names") or {})
    spans: List[Tuple[str, float, float]] = []   # (phase, ts_ms, dur_ms)
    n_events = 0
    n_attr = 0
    unattributed_ps = 0
    for plane in space.get("planes") or []:
        for line in plane.get("lines") or []:
            for ev in line.get("events") or []:
                n_events += 1
                name = ev.get("name") or ""
                op_name = hlo.get(name)
                resolved = op_name is not None
                if op_name is None:
                    h = (ev.get("stats") or {}).get("hlo_op")
                    if isinstance(h, str):
                        op_name = hlo.get(h)
                        resolved = resolved or op_name is not None
                phase = phase_of_op_name(op_name) or phase_of_op_name(name)
                if phase is None:
                    if resolved:
                        # a genuine XLA op whose scope names no known
                        # phase — tolerated, but accounted
                        unattributed_ps += int(ev.get("dur_ps") or 0)
                    continue
                n_attr += 1
                spans.append((phase, ev.get("ts_ps", 0) / 1e9,
                              ev.get("dur_ps", 0) / 1e9))
    if not spans:
        return None
    tl = analyze_timeline(spans)
    phase_ms: Dict[str, float] = {}
    for ph in sorted({s[0] for s in spans}):
        phase_ms[ph] = _union_length(
            [(ts, ts + dur) for p, ts, dur in spans if p == ph]) / steps
    return {
        "device_phase_ms": phase_ms,
        "overlap_frac": tl["overlap_frac"],
        "critical_path_ms": tl["critical_path_ms"] / steps,
        "compute_ms": tl["compute_ms"] / steps,
        "collective_ms": tl["collective_ms"] / steps,
        "exposed_collective_ms": tl["exposed_collective_ms"] / steps,
        "unattributed_ms": unattributed_ps / 1e9 / steps,
        "n_events": n_events,
        "n_attributed": n_attr,
        "steps": steps,
        "source": "xplane",
    }


# -- host cross-check -------------------------------------------------------


def cross_check(host_phase_ms: Dict, device_phase_ms: Dict) -> Dict:
    """Per-phase agreement between the host-measured re-execution
    breakdown and the device-folded one: ``min/max`` ratio per phase
    (1.0 = perfect agreement, 0 = one side missing entirely) plus a
    duration-weighted overall ``agreement``. Host "collective" is the
    SERIAL microbench cost while the device side measures actual (often
    overlapped) collective intervals — disagreement there is signal,
    not error; the weighted overall number is what the perf gate
    watches for drift."""
    per: Dict[str, Dict] = {}
    num = den = 0.0
    for ph in sorted(set(host_phase_ms or {}) | set(device_phase_ms or {})):
        h = float((host_phase_ms or {}).get(ph) or 0.0)
        d = float((device_phase_ms or {}).get(ph) or 0.0)
        hi = max(h, d)
        ratio = (min(h, d) / hi) if hi > 0 else 1.0
        per[ph] = {"host_ms": h, "device_ms": d, "agreement": ratio}
        num += ratio * hi
        den += hi
    return {"per_phase": per,
            "agreement": (num / den) if den else None}


# -- one-call device profile of a static program ----------------------------


def device_profile_step(program, scope, feed, mesh=None,
                        axis_name: str = "dp", steps: int = 3,
                        trace_dir: Optional[str] = None,
                        seed: int = 0) -> Optional[Dict]:
    """Capture + fold a device-phase report for one runnable static
    program (same contract as ``profiler.profile_step``: startup run,
    rewrites applied; state is read, never written back).

    The step is re-jitted (its ops carry their phase scopes, as every
    trace's do), compiled before the capture window, then run
    ``steps`` times under the XPlane trace. Returns the folded report,
    or None when the trace carried no phase-attributed device events
    (the caller keeps the host-measured numbers)."""
    import jax
    import jax.numpy as jnp

    from . import profiler

    ctx = profiler._exec_inputs(program, scope, feed, mesh=mesh,
                                axis_name=axis_name)
    args = (ctx["state"], ctx["feed_vals"], jnp.uint32(seed))
    sync = profiler._whole_sync(ctx["ops"], ctx["persist_written"])
    fn = ctx["make_fn"](ctx["ops"], sync)
    jax.block_until_ready(fn(*args))   # compile OUTSIDE the capture

    def run():
        for _ in range(max(1, steps)):
            jax.block_until_ready(fn(*args))

    space = capture_xspace(run, trace_dir)
    return fold_device_phases(space, steps=steps)
