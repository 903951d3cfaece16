"""From a traced window to the phases of the L2L train step.

The program names its phases with ``jax.named_scope`` (the vocabulary is
``repro.core.spans``); each HLO instruction carries the scope path in its
``op_name``.  ``reduce`` puts each instant of the window (``trace.py``'s:
the first ``step`` span's start to the last one's end) down once:

* to the phase of the compute instruction running then, the innermost
  scope of its ``op_name`` (``spans.phase``), or ``unscoped`` where it
  has none; where two overlap, the one that started last;
* else to relay wait, where only async ``*-start`` / ``*-done``
  instructions on host memory run (``trace.py``'s rule, unchanged);
* else to idle.

So the phases, relay wait and idle add up to the window, and relay wait
and idle read as ``trace.reduce`` reads them.  Each idle gap is named by
the phase of the instruction that ends it, and by that instruction.

The ``op_name`` of each instruction comes from the trace itself: the
profiler keeps the optimized HLO of every program that ran in the
``/host:metadata`` plane (an ``HloProto`` per program, in the ``Hlo
Proto`` stat of the program's event metadata).  ``load`` reads those
protos with a small protobuf reader, so the benchmark needs no proto
library.  A traced run of ``run.py`` leaves its trace under
``TRACE_DIR``; ``read`` reduces it once for the metrics that read it and
prints the phases.  Nothing to read from a program without the scopes.
"""
from __future__ import annotations

import bisect
import functools
import heapq
import json
import re
from pathlib import Path

from chipbench import trace

TRACE_DIR = Path(__file__).resolve().parent / ".out" / "trace"
MODULE_LINE = "XLA Modules"

UNSCOPED = "unscoped"
WINDOW_END = "window end"


# ---------------------------------------------------------------------------
# the protobuf wire format, as far as the trace's HLO needs it
# ---------------------------------------------------------------------------
def _varint(buf, i: int):
    out = shift = 0
    while True:
        b = buf[i]
        out |= (b & 0x7F) << shift
        i += 1
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf):
    """(field number, value) of each field of one message: an int for a
    varint, a memoryview for a length-delimited field."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            v, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            v, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"protobuf wire type {wire} not read here")
        yield key >> 3, v


def _first(buf, number: int, default=None):
    return next((v for f, v in _fields(buf) if f == number), default)


def _op_names(hlo_proto) -> tuple:
    """(module name, {instruction: op_name}) of one ``HloProto``: its
    ``hlo_module`` (1), whose ``computations`` (3) hold ``instructions``
    (2) with a ``name`` (1) and ``metadata`` (7) whose ``op_name`` is 2."""
    module = _first(hlo_proto, 1)
    names = {}
    for f, comp in _fields(module):
        if f != 3:
            continue
        for g, ins in _fields(comp):
            if g != 2:
                continue
            name = meta = None
            for h, v in _fields(ins):
                if h == 1:
                    name = bytes(v).decode()
                elif h == 7:
                    meta = v
            op = _first(meta, 2) if meta is not None else None
            if op is not None:
                names[name] = bytes(op).decode()
    return bytes(_first(module, 1, b"")).decode(), names


def hlo_op_names(path) -> dict:
    """module name -> {instruction name -> op_name} for every program
    whose HLO the profile at ``path`` keeps.  XSpace: ``planes`` (1);
    XPlane: ``name`` (2), ``event_metadata`` (4, map entries of key 1 and
    value 2), ``stat_metadata`` (5); XEventMetadata: ``stats`` (5);
    XStat: ``metadata_id`` (1), ``bytes_value`` (6); XStatMetadata:
    ``id`` (1), ``name`` (2)."""
    data = memoryview(Path(path).read_bytes())
    out = {}
    for f, plane in _fields(data):
        if f != 1 or bytes(_first(plane, 2, b"")) != b"/host:metadata":
            continue
        stat_names = {}
        for g, entry in _fields(plane):
            if g == 5:
                meta = _first(entry, 2)
                stat_names[_first(meta, 1, 0)] = bytes(_first(meta, 2, b""))
        for g, entry in _fields(plane):
            if g != 4:
                continue
            for h, stat in _fields(_first(entry, 2)):
                if h == 5 and stat_names.get(
                        _first(stat, 1, 0)) == b"Hlo Proto":
                    module, names = _op_names(_first(stat, 6))
                    out.setdefault(module, {}).update(names)
    return out


def _waits(name: str, host: bool) -> bool:
    return host and name.endswith(("-start", "-done"))


def _split(intervals) -> dict:
    """label -> time of the union of labelled ``(start, end, label)``
    intervals, each instant to the interval that started last of those
    covering it."""
    out: dict = {}
    todo = sorted(intervals)
    active: list = []            # (-start, end, label), latest start first
    i, t = 0, None
    while True:
        while active and active[0][1] <= t:
            heapq.heappop(active)
        if not active:
            if i == len(todo):
                return out
            t = todo[i][0]
        while i < len(todo) and todo[i][0] <= t:
            s, e, label = todo[i]
            heapq.heappush(active, (-s, e, label))
            i += 1
        _, e, label = active[0]
        nxt = min(e, todo[i][0]) if i < len(todo) else e
        out[label] = out.get(label, 0) + (nxt - t)
        t = nxt


def reduce(events: dict, phase_of, top: int = 10) -> dict:
    """``events`` as ``load`` gives them (rows without an ``op_name``, as
    ``trace.load`` gives them, read as unscoped); ``phase_of(op_name)``
    is the phase of an ``op_name`` (None for none: ``spans.phase``).
    Times are whole nanoseconds, so the parts add up to the window
    exactly."""
    steps = [(s, s + d) for n, s, d in events["host_spans"]
             if n == trace.STEP]
    if not steps or not events["device_ops"]:
        return {}
    lo = round(min(s for s, _ in steps))
    hi = round(max(e for _, e in steps))
    ops = []
    for n, s, d, host, *op_name in events["device_ops"]:
        s, e = max(round(s), lo), min(round(s + d), hi)
        if e > s and not trace.CONTAINER.match(n):
            phase = phase_of(op_name[0]) if op_name and op_name[0] else None
            ops.append((n, s, e, host, phase or UNSCOPED))
    work = [(s, e, p) for n, s, e, host, p in ops if not _waits(n, host)]
    busy = trace._union([(s, e) for _, s, e, _, _ in ops])
    worked = trace._union([(s, e) for s, e, _ in work])
    waits = trace._union([(s, e) for n, s, e, host, _ in ops
                          if _waits(n, host)])
    ends_gap = {s: (p, n) for n, s, _, _, p in sorted(ops,
                                                       key=lambda o: -o[1])}
    edges = [lo] + [t for iv in busy for t in iv] + [hi]
    gaps = [(a, b) for a, b in zip(edges[::2], edges[1::2]) if b > a]
    per_op: dict = {}
    for n, s, e, _, p in ops:
        per_op[(n, p)] = per_op.get((n, p), 0) + (e - s)
    ns = 1e-9
    return {
        "window_ns": hi - lo,
        "relay_wait_ns": int(trace._length(trace._minus(waits, worked))),
        "idle_ns": (hi - lo) - int(trace._length(busy)),
        "phase_ns": dict(sorted(_split(work).items())),
        "idle_gaps": [[*ends_gap.get(b, (WINDOW_END, None)), (b - a) * ns]
                      for a, b in sorted(gaps, key=lambda g: g[0] - g[1])
                      [:top]],
        "device_ops": [[n, p, t * ns] for (n, p), t in sorted(
            per_op.items(), key=lambda kv: -kv[1])[:top]],
    }


def load(path) -> dict:
    """``trace.load``'s events of the profile at ``path``, each device
    instruction also with the ``op_name`` the trace's HLO gives it (None
    where it has none): ``device_ops`` rows are ``[name, start_ns,
    duration_ns, host, op_name]``.  An instruction belongs to the program
    of the ``XLA Modules`` event it runs in (else to the largest)."""
    from jax.profiler import ProfileData
    op_names = hlo_op_names(path)
    largest = max(op_names.values(), key=len, default={})
    ops, spans = [], []
    for plane in ProfileData.from_file(str(path)).planes:
        if trace.DEVICE_PLANE.match(plane.name):
            lines = {line.name: list(line.events) for line in plane.lines}
            runs = sorted((e.start_ns, e.start_ns + e.duration_ns,
                           re.sub(r"\(\d+\)$", "", e.name))
                          for e in lines.get(MODULE_LINE, []))
            starts = [r[0] for r in runs]
            for e in lines.get(trace.DEVICE_LINE, []):
                k = bisect.bisect_right(starts, e.start_ns) - 1
                names = (op_names.get(runs[k][2], largest)
                         if k >= 0 and e.start_ns < runs[k][1] else largest)
                name = trace._short(e.name)
                ops.append([name, e.start_ns, e.duration_ns,
                            trace.HOST_MEMORY in e.name, names.get(name)])
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans += [[e.name, e.start_ns, e.duration_ns]
                          for e in line.events if e.name in trace.SPANS]
    return {"device_ops": ops, "host_spans": spans}


@functools.lru_cache(maxsize=1)
def _read(path: str):
    try:
        from repro.core import spans
    except ImportError:           # a program without the scopes
        return None
    r = reduce(load(path), spans.phase)
    if not r or set(r["phase_ns"]) <= {UNSCOPED}:
        return None
    parts = {**r["phase_ns"], "relay wait": r["relay_wait_ns"],
             "idle": r["idle_ns"]}
    assert sum(parts.values()) == r["window_ns"], parts
    print("phases: " + json.dumps({
        "window_s": r["window_ns"] * 1e-9,
        "seconds": {k: v * 1e-9 for k, v in parts.items()},
        "device_ops": r["device_ops"], "idle_gaps": r["idle_gaps"]}),
        flush=True)
    return r


def read(ctx):
    """The reduction of the traced window ``run.py`` left under
    ``TRACE_DIR``, or None where there is none or it has no scopes."""
    files = sorted(TRACE_DIR.glob("**/*.xplane.pb")) if ctx["trace"] else []
    return _read(str(files[-1])) if files else None


def share(ctx, phase: str):
    """Percent of the traced window the chip spends in ``phase``."""
    r = read(ctx)
    if r is None:
        return None
    return 100.0 * r["phase_ns"].get(phase, 0) / r["window_ns"]
