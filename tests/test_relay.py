"""Unified relay executor (repro.core.relay) invariants.

The relay executor composes weight streaming, the k-deep prefetch ring
(prefetch_depth), packed flat-buffer transport (pack_params) and G-layer
relay groups (layers_per_relay) exactly once, for every consumer scan
(train forward, reverse backward, trailing update, prefill, decode).
That composition must be a pure SCHEDULE/layout change: every (G, k,
pack) point computes bit-identical grads, updates, prefill logits and
decode steps to the plain per-layer scan — including depths NOT
divisible by G (remainder stop) and G > N (remainder-only, no main
scan).
"""
import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import make_batch
from repro.configs.base import get_config
from repro.core import relay
from repro.core.eps import noop_placement
from repro.core.schedule import ExecutionConfig
from repro.optim import adam

# {G} x {prefetch_depth} x {pack on/off}; n_layers=5 below makes G=2, 3
# leave a remainder stop and G=7 a remainder-only pass
GRID = list(itertools.product((1, 2, 3), (0, 1, 2), (False, True)))
EDGE = [(5, 1, False), (7, 2, True)]   # G == N and G > N


def _cfg(arch="bert-large", n_layers=5):
    return get_config(arch, "smoke").replace(dtype="float32",
                                             n_layers=n_layers)


def _assert_trees_bitwise(a, b, what):
    mismatched = [
        k for k, (x, y) in enumerate(zip(jax.tree.leaves(a),
                                         jax.tree.leaves(b)))
        if not bool(jnp.all(x == y))]
    assert not mismatched, f"{what}: leaves {mismatched} differ"


# ---------------------------------------------------------------------------
# relay_scan unit behavior (no engine): order, ys stacking, remainder
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("group,prefetch,reverse", [
    (1, 0, False), (1, 2, True), (2, 0, False), (2, 1, True),
    (3, 2, False), (3, 1, True), (7, 1, False), (5, 0, True),
    (1, 1, False), (1, 1, True), (1, 2, False), (2, 2, True)])
def test_relay_scan_visits_layers_in_order(group, prefetch, reverse):
    """Bodies run per layer, in direction order, and ys keep layer order
    regardless of grouping/prefetch/remainder handling."""
    n = 5
    stacked = {"w": jnp.arange(n, dtype=jnp.float32) + 1.0}
    xs = jnp.arange(n, dtype=jnp.float32) * 10.0

    def body(carry, slots, x):
        (slot,) = slots
        return carry + slot["w"], slot["w"] * 100.0 + x

    stream = relay.Stream(noop_placement(), stacked)
    total, ys = jax.jit(lambda: relay.relay_scan(
        body, jnp.float32(0.0), (stream,), xs=xs,
        reverse=reverse, group=group, prefetch=prefetch))()
    assert float(total) == sum(range(1, n + 1))
    np.testing.assert_array_equal(
        np.asarray(ys), (np.arange(n) + 1.0) * 100.0 + np.arange(n) * 10.0)


def test_relay_scan_reverse_carry_order():
    """A reverse relay must thread the carry from layer N-1 down to 0
    (order-sensitive carry), with any grouping."""
    n = 5
    stacked = jnp.arange(n, dtype=jnp.float32) + 1.0

    def body(carry, slots, x):
        return carry * 10.0 + slots[0], None

    ref = None
    for g, k in [(1, 0), (2, 1), (3, 2), (2, 2), (1, 1), (1, 2)]:
        out, _ = jax.jit(lambda g=g, k=k: relay.relay_scan(
            body, jnp.float32(0.0),
            (relay.Stream(noop_placement(), stacked),),
            reverse=True, group=g, prefetch=k))()
        ref = out if ref is None else ref
        assert float(out) == float(ref) == 54321.0


@pytest.mark.parametrize("n,group,prefetch,reverse", [
    (4, 1, 1, False), (4, 1, 1, True), (5, 1, 1, True), (6, 1, 2, False),
    (6, 1, 2, True), (5, 1, 2, True), (2, 1, 2, False), (7, 2, 1, True),
    (9, 2, 2, False)])
def test_relay_ring_bit_identical_to_plain_scan(n, group, prefetch,
                                                reverse):
    """The prefetch ring with several streams (weights, optimizer slots
    and a stash-like row relayed a stop ahead), device xs and per-layer
    ys computes what the plain per-layer scan computes, bit for bit:
    depths the ring's trips divide and depths they leave stops over."""
    # small whole numbers: every sum and product below is exact, so any
    # slot, row or order mix-up shows as a different number
    def ints(shape, seed):
        return jax.random.randint(jax.random.PRNGKey(seed), shape, -3,
                                  4).astype(jnp.float32)

    w = {"a": ints((n, 4, 4), 1), "b": ints((n, 4), 2)}
    opt = ints((n, 2, 4), 3)
    stash = ints((n, 4), 4)
    xs = jnp.arange(n, dtype=jnp.float32)

    def body(carry, slots, x):
        wl, ol, sl = slots
        h = carry * 3.0 + wl["a"].sum(0) + wl["b"] + sl * x
        return h, (ol * 2.0 + h, {"h": h - x})

    def run(G, K):
        streams = tuple(relay.Stream(noop_placement(), t)
                        for t in (w, opt, stash))
        return jax.jit(lambda: relay.relay_scan(
            body, jnp.ones((4,)), streams, xs=xs, reverse=reverse,
            group=G, prefetch=K))()

    want = run(1, 0)
    got = run(group, prefetch)
    _assert_trees_bitwise(got, want, f"n={n} G={group} k={prefetch}")


def test_n_stops():
    assert relay.n_stops(24, 1) == 24
    assert relay.n_stops(24, 4) == 6
    assert relay.n_stops(5, 2) == 3
    assert relay.n_stops(5, 3) == 2
    assert relay.n_stops(2, 7) == 1


# ---------------------------------------------------------------------------
# full train step: the (G, k, pack) grid is bit-identical for l2l + l2l-p
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("name", ["l2l", "l2l-p"])
def test_relay_train_step_bit_identical_across_grid(name, make_engine):
    """One optimizer step (trailing Alg-3 relay for l2l, eager Alg-4 for
    l2l-p) across the full {G} x {prefetch} x {pack} grid, n_layers=5 so
    G=2/3 exercise the remainder stop."""
    from repro.core import packing
    cfg = _cfg()
    batch = make_batch(cfg, 4, 16)
    ref = None
    for G, k, pk in GRID + EDGE:
        eng = make_engine(name, optimizer=adam(lr=1e-3),
                          exec_cfg=ExecutionConfig(
                              n_microbatches=2, prefetch_depth=k,
                              layers_per_relay=G, pack_params=pk),
                          cfg=cfg)
        state, m = eng.train_step(eng.init(jax.random.PRNGKey(0)), batch)
        params, opt = state.params, state.legacy_opt()
        if pk:
            opt = packing.unpack_opt_state(opt, params)
            params = packing.unpack_params(params)
        if ref is None:
            ref = (float(m["loss"]), params, opt)
            continue
        tag = f"{name} G={G} k={k} pack={pk}"
        assert float(m["loss"]) == ref[0], tag
        _assert_trees_bitwise(params, ref[1], f"{tag} params")
        _assert_trees_bitwise(opt, ref[2], f"{tag} opt state")


def test_relay_grads_cover_multi_group_and_mem_archs(make_engine):
    """Transition/mem handling (whisper enc-dec: two groups of different
    depth) and MoE/MLA layers go through the same grouped/ringed scans."""
    from repro.models.model import LayeredModel
    for arch in ("whisper-base", "deepseek-v2-lite-16b"):
        cfg = get_config(arch, "smoke").replace(dtype="float32")
        batch = make_batch(cfg, 4, 16)
        params = LayeredModel(cfg).init_params(jax.random.PRNGKey(0))
        outs = {}
        for G, k, pk in [(1, 0, False), (2, 2, True), (3, 1, False)]:
            eng = make_engine("l2l-p", arch, exec_cfg=ExecutionConfig(
                n_microbatches=2, prefetch_depth=k, layers_per_relay=G,
                pack_params=pk))
            outs[(G, k, pk)] = eng.grads(params, batch)
        ref = outs[(1, 0, False)]
        for key, (loss, g) in outs.items():
            assert float(loss) == float(ref[0]), f"{arch} {key}"
            _assert_trees_bitwise(g, ref[1], f"{arch} {key}")


def test_relay_prefill_and_decode_bit_identical(make_engine):
    cfg = _cfg("granite-3-8b")
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0,
                              cfg.vocab_size)
    outs = {}
    combos = [(1, 0, False), (2, 1, False), (3, 2, True), (2, 2, True)]
    for G, k, pk in combos:
        eng = make_engine("l2l", "granite-3-8b", exec_cfg=ExecutionConfig(
            n_microbatches=2, prefetch_depth=k, layers_per_relay=G,
            pack_params=pk), cfg=cfg)
        params = eng.model.init_params(jax.random.PRNGKey(0))
        logits = eng.prefill(params, {"tokens": make_batch(cfg, 4, 16)[
            "tokens"]})
        caches, last = eng.decode_init(params, toks, live_seq=16)
        step_logits, _ = eng.decode_step(
            params, caches, jnp.argmax(last, -1)[:, None].astype(jnp.int32),
            jnp.int32(8))
        outs[(G, k, pk)] = (logits, last, step_logits)
    for key in combos[1:]:
        for a, b in zip(outs[combos[0]], outs[key]):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                          err_msg=f"{key}")


# ---------------------------------------------------------------------------
# config validation + knob threading
# ---------------------------------------------------------------------------
def test_relay_knobs_validated():
    assert ExecutionConfig(prefetch_depth=2).prefetch_depth == 2
    assert ExecutionConfig(layers_per_relay=4).layers_per_relay == 4
    with pytest.raises(AssertionError):
        ExecutionConfig(prefetch_depth=-1)
    with pytest.raises(AssertionError):
        ExecutionConfig(layers_per_relay=0)


def test_registry_threads_group():
    from repro import engine as engines
    eng = engines.create("l2l-p", get_config("bert-large", "smoke"),
                         ExecutionConfig(n_microbatches=4),
                         exec_overrides={"layers_per_relay": 3,
                                         "prefetch_depth": 2})
    assert eng.exec_cfg.layers_per_relay == 3
    assert eng.exec_cfg.prefetch_depth == 2
