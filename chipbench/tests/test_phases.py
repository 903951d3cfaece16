"""The phase reduction (``chipbench/phases.py``) on recorded v5e traces:

    PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest -q chipbench/tests/test_phases.py

``trace_phases.json`` is cut from a traced v5e run of
``bert-large.b32-s512`` with the program's scopes: three slices joined end
to end (the optimizer's stall with the backward around it, a layer's
forward, and the end of a step through the start of the next, with its
host-memory waits and idle gap), each instruction with the ``op_name``
the trace's HLO gives it.  ``trace_small.json`` is the older cut, with no
``op_name``.
"""
import json
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
for p in (ROOT, ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

from chipbench import phases, trace  # noqa: E402
from repro.core import spans  # noqa: E402

HERE = Path(__file__).resolve().parent


def _events(name: str) -> dict:
    return json.loads((HERE / name).read_text())


def _count(ev: dict) -> dict:
    """Each nanosecond of the window painted by hand: compute
    instructions in order of start (the longer first where two start
    together), so that the one that started last holds each instant;
    then relay waits where no compute runs."""
    steps = [(s, s + d) for n, s, d in ev["host_spans"] if n == "step"]
    lo, hi = round(min(s for s, _ in steps)), round(max(e for _, e in steps))
    labels = [phases.UNSCOPED] + list(spans.SCOPES)
    owner = np.full(hi - lo, -1)
    wait = np.zeros(hi - lo, bool)
    for name, s, d, host, *op in sorted(
            ev["device_ops"], key=lambda o: (o[1], -o[1] - o[2])):
        if trace.CONTAINER.match(name):
            continue
        a, b = max(round(s), lo) - lo, min(round(s + d), hi) - lo
        if b <= a:
            continue
        if host and name.endswith(("-start", "-done")):
            wait[a:b] = True
        else:
            phase = spans.phase(op[0]) if op and op[0] else None
            owner[a:b] = labels.index(phase or phases.UNSCOPED)
    got = {lab: int((owner == i).sum()) for i, lab in enumerate(labels)}
    return {"window_ns": hi - lo,
            "phase_ns": {k: v for k, v in got.items() if v},
            "relay_wait_ns": int((wait & (owner < 0)).sum()),
            "idle_ns": int((~wait & (owner < 0)).sum())}


def test_phases_wait_and_idle_add_up_to_the_window_to_the_nanosecond():
    ev = _events("trace_phases.json")
    r = phases.reduce(ev, spans.phase)
    want = _count(ev)
    assert {k: r[k] for k in want} == want
    assert (sum(r["phase_ns"].values()) + r["relay_wait_ns"]
            + r["idle_ns"]) == r["window_ns"]
    # the cut holds each phase a metric reads, a relay wait and a gap
    assert {spans.FORWARD, spans.BACKWARD, spans.OPTIMIZER} \
        <= set(r["phase_ns"])
    assert r["relay_wait_ns"] > 0 and r["idle_ns"] > 0


@pytest.mark.parametrize("name", ["trace_small.json", "trace_phases.json"])
def test_phases_read_relay_wait_and_idle_as_the_trace_reduction(name):
    """What ``trace.reduce`` gives stays what it gave, and the phases
    split its busy time without moving its relay wait or idle."""
    ev = _events(name)
    old = trace.reduce({**ev, "device_ops": [o[:4]
                                             for o in ev["device_ops"]]})
    r = phases.reduce(ev, spans.phase)
    assert r["window_ns"] == pytest.approx(old["window_s"] * 1e9, abs=1)
    assert r["relay_wait_ns"] == pytest.approx(old["relay_wait_s"] * 1e9,
                                               abs=1)
    assert r["idle_ns"] == pytest.approx(
        (old["window_s"] - old["busy_s"]) * 1e9, abs=1)
    assert [n for n, _, _ in r["device_ops"]] == \
        [n for n, _ in old["device_ops"]]
    if name == "trace_small.json":
        assert set(r["phase_ns"]) == {phases.UNSCOPED}
        assert (old["window_s"], old["busy_s"], old["relay_wait_s"]) == \
            (0.007, 0.004898953, 0.002611955)
        assert old["idle_gaps"][0] == ["block", 0.0021010020000000003]


def test_hlo_op_names_reads_the_profiles_own_hlo(tmp_path):
    """The op_name of each instruction, from the HLO the profiler keeps
    of every program that ran (here on the CPU)."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def f(x):
        with jax.named_scope(spans.FORWARD):
            y = x @ x
        with jax.named_scope(spans.OPTIMIZER):
            return jnp.sin(y) + 1.0

    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    f(x).block_until_ready()
    jax.profiler.stop_trace()
    path = next(tmp_path.glob("**/*.xplane.pb"))
    by_module = phases.hlo_op_names(path)
    got = {spans.phase(op) for op in by_module["jit_f"].values()}
    assert {spans.FORWARD, spans.OPTIMIZER} <= got
