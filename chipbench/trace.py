"""From a profiler trace of the measured window to busy, idle and wait.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` writes and keeps
two lists:

* ``device_ops``, ``[name, start_ns, duration_ns, host]``: the events of
  the ``XLA Ops`` line of each device plane (``/device:TPU:<n>``), one per
  HLO instruction the chip ran.  The trace names an event by the
  instruction's whole HLO text; ``name`` is the instruction's own name
  (``fusion.636``) and ``host`` says whether the text names the host
  memory space ``S(5)``, where the EPS keeps the stacked layers;
* ``host_spans``, ``[name, start_ns, duration_ns]``: the benchmark's own
  spans (``SPANS``), from whichever host thread recorded them.

``reduce`` turns them into the numbers the per-layer metrics read.  The
window is the first ``step`` span's start to the last one's end, on the
clock the trace gives host and device alike.  ``while`` (and
``conditional``, ``call``) events enclose the instructions of their
bodies, so they count for nothing themselves.  Busy time is the union of
the other instructions' intervals.  A relay wait is the part of an
async ``*-start`` or ``*-done`` instruction on host memory (the issue or
the end of a copy, slice or host computation that moves EPS rows in or
out) that no other instruction covers: the core has nothing to do but
wait for the EPS.  On one v5e these are ``dynamic-slice-start/-done`` (a
relay fetch; the start stalls when the transfers ahead of it have not
drained), ``dynamic-update-slice-start/-done`` (a write-back) and
``host-call.*.call-start/-done`` (a host-side copy of a stacked array).
"""
from __future__ import annotations

import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
DEVICE_LINE = "XLA Ops"
STEP = "step"
SPANS = (STEP, "feed", "train_step", "block")
CONTAINER = re.compile(r"^(while|conditional|call)(\.\d+)?$")
HOST_MEMORY = "S(5)"


def _short(hlo: str) -> str:
    return hlo.split(" = ", 1)[0].lstrip("%")


def load(path) -> dict:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(path))
    ops, spans = [], []
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name == DEVICE_LINE:
                    ops += [[_short(e.name), e.start_ns, e.duration_ns,
                             HOST_MEMORY in e.name] for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans += [[e.name, e.start_ns, e.duration_ns]
                          for e in line.events if e.name in SPANS]
    return {"device_ops": ops, "host_spans": spans}


def _union(intervals):
    """Sorted, merged [start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _length(intervals) -> float:
    return sum(e - s for s, e in intervals)


def _minus(a, b):
    """Parts of merged intervals ``a`` not covered by merged ``b``."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def reduce(events: dict, top: int = 10) -> dict:
    steps = [(s, s + d) for n, s, d in events["host_spans"] if n == STEP]
    if not steps or not events["device_ops"]:
        return {}
    lo, hi = min(s for s, _ in steps), max(e for _, e in steps)
    ops = [(n, max(s, lo), min(s + d, hi), host)
           for n, s, d, host in events["device_ops"]
           if s + d > lo and s < hi and not CONTAINER.match(n)]
    waiting = [host and n.endswith(("-start", "-done"))
               for n, _, _, host in ops]
    busy = _union([(s, e) for _, s, e, _ in ops])
    waits = _union([(s, e) for (_, s, e, _), w in zip(ops, waiting) if w])
    work = _union([(s, e) for (_, s, e, _), w in zip(ops, waiting) if not w])
    per_op: dict = {}
    for n, s, e, _ in ops:
        per_op[n] = per_op.get(n, 0) + (e - s)
    edges = [lo] + [t for iv in busy for t in iv] + [hi]
    gaps = [(a, b) for a, b in zip(edges[::2], edges[1::2]) if b > a]
    spans = sorted((s, s + d, n) for n, s, d in events["host_spans"]
                   if n != STEP)

    def doing(t):
        inner = [(e - s, n) for s, e, n in spans if s <= t < e]
        return min(inner)[1] if inner else "between spans"

    ns = 1e-9
    return {
        "window_s": (hi - lo) * ns,
        "busy_s": _length(busy) * ns,
        "relay_wait_s": _length(_minus(waits, work)) * ns,
        "has_transfers": any(host for *_, host in ops),
        "device_ops": [[n, t * ns] for n, t in sorted(
            per_op.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[doing((a + b) / 2), (b - a) * ns] for a, b in
                      sorted(gaps, key=lambda g: g[0] - g[1])[:top]],
    }
