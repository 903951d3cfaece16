"""Double-buffered relay (ExecutionConfig.prefetch_depth) invariants.

The prefetch restructuring moves the per-layer weight fetch out of the
consuming scan iteration and into the previous one (carried HBM slot).
That must be a pure SCHEDULE change: depth 1 computes bit-identical
gradients, updates, prefill logits and decode steps to depth 0 for every
L2L schedule — and the analytic memory model must charge the second layer
slot (the paper's "the executing layer(s)'s footprint", plural)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import make_batch
from repro import engine as engines
from repro.configs.base import get_config
from repro.core.memory_model import estimate
from repro.core.schedule import ExecutionConfig
from repro.models.model import LayeredModel
from repro.optim import adam


def _cfg(arch="bert-large"):
    return get_config(arch, "smoke").replace(dtype="float32")


def _assert_trees_bitwise(a, b, what):
    mismatched = [
        k for k, (x, y) in enumerate(zip(jax.tree.leaves(a),
                                         jax.tree.leaves(b)))
        if not bool(jnp.all(x == y))]
    assert not mismatched, f"{what}: leaves {mismatched} differ"


@pytest.mark.parametrize("name", ["l2l", "l2l-p"])
def test_prefetch_grads_bit_identical(name, make_engine):
    cfg = _cfg()
    batch = make_batch(cfg, 4, 16)
    params = LayeredModel(cfg).init_params(jax.random.PRNGKey(0))
    outs = {}
    for pf in (0, 1, 2):
        eng = make_engine(name, exec_cfg=ExecutionConfig(
            n_microbatches=2, prefetch_depth=pf))
        outs[pf] = eng.grads(params, batch)
    for pf in (1, 2):
        assert float(outs[0][0]) == float(outs[pf][0])
        _assert_trees_bitwise(outs[0][1], outs[pf][1],
                              f"{name} grads, prefetch {pf}")


@pytest.mark.parametrize("name", ["l2l", "l2l-p"])
def test_prefetch_updates_bit_identical(name, make_engine):
    """Full train step: the trailing (Alg 3) and eager (Alg 4) optimizer
    relays must produce bit-identical new params AND opt state."""
    cfg = _cfg()
    batch = make_batch(cfg, 4, 16)
    states = {}
    for pf in (0, 1, 2):
        eng = make_engine(name, optimizer=adam(lr=1e-3),
                          exec_cfg=ExecutionConfig(n_microbatches=2,
                                                   prefetch_depth=pf))
        state, m = eng.train_step(eng.init(jax.random.PRNGKey(0)), batch)
        states[pf] = (state, float(m["loss"]))
    for pf in (1, 2):
        assert states[0][1] == states[pf][1]
        _assert_trees_bitwise(states[0][0].params, states[pf][0].params,
                              f"{name} params, prefetch {pf}")
        _assert_trees_bitwise(states[0][0].opt_state,
                              states[pf][0].opt_state,
                              f"{name} opt state, prefetch {pf}")


def test_prefetch_covers_multi_group_and_mem_archs(make_engine):
    """Transition/mem handling (whisper enc-dec) and MoE/MLA layers go
    through the same restructured scans."""
    for arch in ("whisper-base", "deepseek-v2-lite-16b"):
        cfg = _cfg(arch)
        batch = make_batch(cfg, 4, 16)
        params = LayeredModel(cfg).init_params(jax.random.PRNGKey(0))
        outs = {}
        for pf in (0, 1):
            eng = make_engine("l2l-p", arch, exec_cfg=ExecutionConfig(
                n_microbatches=2, prefetch_depth=pf))
            outs[pf] = eng.grads(params, batch)
        _assert_trees_bitwise(outs[0][1], outs[1][1], arch)


def test_prefetch_prefill_and_decode_bit_identical(make_engine):
    cfg = _cfg("granite-3-8b")
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0,
                              cfg.vocab_size)
    outs = {}
    for pf in (0, 1):
        eng = make_engine("l2l", "granite-3-8b", exec_cfg=ExecutionConfig(
            n_microbatches=2, prefetch_depth=pf))
        params = eng.model.init_params(jax.random.PRNGKey(0))
        logits = eng.prefill(params, {"tokens": make_batch(cfg, 4, 16)[
            "tokens"]})
        caches, last = eng.decode_init(params, toks, live_seq=16)
        step_logits, _ = eng.decode_step(
            params, caches, jnp.argmax(last, -1)[:, None].astype(jnp.int32),
            jnp.int32(8))
        outs[pf] = (logits, last, step_logits)
    for a, b in zip(outs[0], outs[1]):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# memory model: the 2-slot footprint
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("mode", ["l2l", "l2l_p"])
def test_memory_estimate_two_slot_footprint(mode):
    model = LayeredModel(get_config("bert-large"))
    r0 = estimate(model, batch=32, seq=512, n_microbatches=8, mode=mode,
                  offload_stash=True)
    r1 = estimate(model, batch=32, seq=512, n_microbatches=8, mode=mode,
                  offload_stash=True, prefetch_depth=1)
    # double buffering exactly doubles the device weight-transit slots...
    assert r1.params_device == 2 * r0.params_device
    # ...leaves EPS residency alone, and stays O(1) in depth
    assert r1.total_host == r0.total_host
    assert r1.total_device - r0.total_device == r0.params_device
    deep = LayeredModel(get_config("bert-large").replace(n_layers=96))
    rd = estimate(deep, batch=32, seq=512, n_microbatches=8, mode=mode,
                  offload_stash=True, prefetch_depth=1)
    assert rd.total_device == r1.total_device


def test_engine_memory_estimate_threads_prefetch(make_engine):
    e0 = make_engine("l2l-p", exec_cfg=ExecutionConfig(n_microbatches=2))
    e1 = make_engine("l2l-p", exec_cfg=ExecutionConfig(n_microbatches=2,
                                                       prefetch_depth=1))
    r0 = e0.memory_estimate(batch=8, seq=64)
    r1 = e1.memory_estimate(batch=8, seq=64)
    assert r1.params_device == 2 * r0.params_device
    # baseline mode has no relay; the knob must not perturb eq. (1)
    b0 = make_engine("baseline").memory_estimate(batch=8, seq=64)
    b1 = make_engine("baseline", exec_cfg=ExecutionConfig(
        n_microbatches=2, prefetch_depth=1)).memory_estimate(batch=8, seq=64)
    assert b0.params_device == b1.params_device


def test_registry_exec_overrides():
    eng = engines.create("l2l-p", get_config("bert-large", "smoke"),
                         ExecutionConfig(n_microbatches=4),
                         exec_overrides={"prefetch_depth": 1})
    assert eng.exec_cfg.prefetch_depth == 1
    assert eng.exec_cfg.n_microbatches == 4
    eng2 = engines.create("l2l", get_config("bert-large", "smoke"),
                          exec_overrides={"prefetch_depth": 1})
    assert eng2.exec_cfg.prefetch_depth == 1


def test_prefetch_depth_validated():
    # k >= 2 is a legal ring depth since the unified relay executor;
    # only negative depths are rejected
    assert ExecutionConfig(prefetch_depth=2).prefetch_depth == 2
    with pytest.raises(AssertionError):
        ExecutionConfig(prefetch_depth=-1)
