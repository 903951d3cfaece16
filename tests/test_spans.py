"""The train step's scopes and relay byte counter, on the CPU at smoke
size.

``Engine.counters()`` adds each compiled step's relay bytes, counted at
the relay's move sites while the step traces, once per ``train_step``.
The CPU drops memory-space moves, so the tests hand the engine
placements that move nothing but count as enabled ones.  The scopes are
read from the ``op_name`` of each instruction of the compiled step.
"""
import re

import jax
import pytest

from conftest import make_batch
from repro import engine as engines
from repro.configs.base import get_config
from repro.core import eps, spans
from repro.core.schedule import ExecutionConfig
from repro.optim import adam

N, UB, B, S = 4, 2, 4, 16


def _cfg():
    return get_config("bert-large", "smoke").replace(dtype="float32",
                                                     n_layers=N)


def _moving():
    """Enabled placements that leave every array where it is."""
    same = lambda tree: tree
    p = eps.Placement(same, same, same, enabled=True)
    return eps.EPSPlacements((p,), (p,), p)


def _bytes(tree) -> int:
    return sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(tree))


# (settings, fetches and write-backs per step in units of one layer's
# weights W, its Adam slots O and one stashed boundary A, for N = 4)
CASES = {
    # ring of one: each pass fetches its N stops (the backward also the
    # stashed boundaries, a stop ahead) and re-fetches the clamped edge
    # stop once
    "prefetch1-g1": (dict(prefetch_depth=1),
                     dict(W=(N + 1) + (N + 1), O=N + 1, A=N + 1),
                     dict(W=N, O=N, A=N)),
    # ring of two: a trip runs 3 stops, so one trip covers stops 0-2 and
    # re-fetches the edge once (its fetches target stops 2, 3 and the
    # clamped 4); stop 3 runs after the scan on the slot the ring ends
    # with and fetches nothing
    "prefetch2-g1": (dict(prefetch_depth=2),
                     dict(W=(N + 1) + (N + 1), O=N + 1, A=N + 1),
                     dict(W=N, O=N, A=N)),
    # two layers a stop, no ring: each pass fetches every layer once
    "prefetch0-g2": (dict(layers_per_relay=2),
                     dict(W=N + N, O=N, A=N),
                     dict(W=N, O=N, A=N)),
    # two segments of two: the forward fetches 3 W a segment (2 stops and
    # the edge) and ships the segment's entry; the backward fetches the
    # entry, 1 W to recompute the one missing boundary (a one-stop ring
    # has no edge to re-fetch), ships it, and fetches 3 (W + O + A) for
    # the segment's vjp (2 stops and the edge)
    "stash2": (dict(prefetch_depth=1, stash_every=2),
               dict(W=2 * 3 + 2 * 1 + 2 * 3, O=2 * 3, A=2 * (1 + 3)),
               dict(W=N, O=N, A=2 + 2)),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_relay_bytes_per_step_match_a_hand_count(case):
    settings, fetch, writeback = CASES[case]
    cfg = _cfg()
    eng = engines.create("l2l-p", cfg,
                         ExecutionConfig(n_microbatches=UB, **settings),
                         placements=_moving())
    state = eng.init(jax.random.PRNGKey(0))
    unit = {"W": _bytes(state.params["groups"]) // N,
            "O": _bytes(state.opt_state["groups"]) // N,
            "A": B * S * cfg.d_model * 4}
    want = [sum(n * unit[k] for k, n in fetch.items()),
            sum(n * unit[k] for k, n in writeback.items())]
    batch = make_batch(cfg, B, S)
    for i in range(1, 3):
        state, _ = eng.train_step(state, batch)
        c = eng.counters()
        assert c == {"train_steps": i, "relay_fetch_bytes": i * want[0],
                     "relay_writeback_bytes": i * want[1]}
    # tracing the step again (as a lowering for its memory analysis
    # does) counts nothing
    jax.jit(eng.step_fn).lower(state, batch)
    assert eng.counters() == c


def test_compiled_step_carries_the_phase_scopes():
    """Every dot of the compiled step is in a phase: the layers' forward,
    backward or recompute, or the head; and every instruction of the
    optimizer's update is in ``l2l.optimizer``."""
    base = adam()
    marked = base._replace(update=jax.named_scope("adam.update")(
        base.update))
    cfg = _cfg()
    eng = engines.create(
        "l2l-p", cfg,
        ExecutionConfig(n_microbatches=UB, prefetch_depth=1, stash_every=2),
        optimizer=marked)
    hlo = jax.jit(eng.step_fn).lower(
        eng.abstract_state(), make_batch(cfg, B, S)).compile().as_text()
    dots, adam_ops = {}, 0
    for line in hlo.splitlines():
        name = re.search(r'op_name="([^"]*)"', line)
        if name is None:
            continue
        if re.search(r" dot\(", line):
            p = spans.phase(name.group(1))
            dots[p] = dots.get(p, 0) + 1
        if "/adam.update/" in name.group(1):
            assert spans.phase(name.group(1)) == spans.OPTIMIZER, line
            adam_ops += 1
    assert set(dots) == {spans.FORWARD, spans.BACKWARD, spans.RECOMPUTE,
                         spans.HEAD}, dots
    assert adam_ops > 0
