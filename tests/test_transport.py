"""Pallas relay transport (``ExecutionConfig.transport``) bit-identity.

``transport="pallas"`` routes every relay slot move — stream-in of the
next stop's weights and the boundary/grad/update write-back — through
the ``kernels/relay_copy`` double-buffered ``make_async_copy`` DMA
pipeline instead of scan-boundary ``device_put``s.  The move is a pure
copy, so EVERY output (loss, grads, updated params, optimizer state,
prefill logits, decode logits and caches) must be bit-identical to
``transport="xla"`` at every schedule point.

Grid: (G, prefetch, pack, K) x (l2l, l2l-p), CPU interpret mode.  A
representative diagonal runs in tier-1; the remaining cross terms are
``slow`` and run in the CI transport-smoke job.
"""
import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import make_batch
from repro import engine as engines
from repro.configs.base import get_config
from repro.core.schedule import ExecutionConfig
from repro.optim import adam


def _assert_bit_identical(a, b, ctx=""):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(la) == len(lb), ctx
    for x, y in zip(la, lb):
        assert np.array_equal(np.asarray(x), np.asarray(y)), ctx


def _train_outputs(name, transport, *, group, prefetch, pack, stash):
    cfg = get_config("bert-large", "smoke").replace(dtype="float32",
                                                    n_layers=5)
    ec = ExecutionConfig(n_microbatches=2, layers_per_relay=group,
                         prefetch_depth=prefetch, pack_params=pack,
                         stash_every=stash, transport=transport)
    eng = engines.create(name, cfg, ec, optimizer=adam(lr=1e-3),
                         donate=False)
    batch = make_batch(cfg, 2, 8)
    params = eng.model.init_params(jax.random.PRNGKey(0))
    loss, grads = eng.grads(params, batch)
    state, m = eng.train_step(eng.init(jax.random.PRNGKey(0)), batch)
    return (loss, grads, state.params, state.opt_state, m["loss"])


# every knob at both levels, engine x knob interactions on the diagonal;
# the full cross product rides in the slow grid below
FAST_GRID = [
    ("l2l", 1, 0, False, 1),
    ("l2l", 2, 2, True, 3),      # grouped + ring + packed + stash at once
    ("l2l-p", 1, 1, True, 1),
    ("l2l-p", 2, 0, False, 2),
]
# the slow grid's l2l half lives in test_transport_l2l_grid.py, so that
# test workers given one file each run the two halves side by side
FULL_GRID = [t for t in itertools.product(
    ("l2l", "l2l-p"), (1, 2), (0, 2), (False, True), (1, 3))
    if t not in FAST_GRID]


def check_train_point(name, group, prefetch, pack, stash):
    ox = _train_outputs(name, "xla", group=group, prefetch=prefetch,
                        pack=pack, stash=stash)
    op = _train_outputs(name, "pallas", group=group, prefetch=prefetch,
                        pack=pack, stash=stash)
    _assert_bit_identical(ox, op, f"{name} G={group} pf={prefetch} "
                                  f"pack={pack} K={stash}")


@pytest.mark.parametrize("name,group,prefetch,pack,stash", FAST_GRID)
def test_train_bit_identical(name, group, prefetch, pack, stash):
    """Grads, trailing/eager updates, and opt state are exactly equal."""
    check_train_point(name, group, prefetch, pack, stash)


@pytest.mark.slow
@pytest.mark.parametrize("name,group,prefetch,pack,stash",
                         [t for t in FULL_GRID if t[0] == "l2l-p"])
def test_train_bit_identical_full_grid(name, group, prefetch, pack, stash):
    check_train_point(name, group, prefetch, pack, stash)


# ---------------------------------------------------------------------------
# serve paths: prefill + decode tick under the weight-streaming relay
# ---------------------------------------------------------------------------
def _decode_outputs(transport, *, group, prefetch, pack):
    cfg = get_config("granite-3-8b", "smoke").replace(dtype="float32")
    ec = ExecutionConfig(weight_stream=True, layers_per_relay=group,
                         prefetch_depth=prefetch, pack_params=pack,
                         transport=transport)
    eng = engines.create("l2l", cfg, ec, donate=False)
    params = eng.model.init_params(jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0,
                              cfg.vocab_size)
    caches, last = eng.decode_init(params, toks, 16)
    outs = [last]
    tok = jnp.argmax(last, axis=-1)[:, None].astype(jnp.int32)
    for i in range(3):
        logits, caches = eng.decode_step(params, caches, tok,
                                         jnp.int32(8 + i))
        tok = jnp.argmax(logits[:, -1], axis=-1)[:, None].astype(jnp.int32)
        outs.append(logits)
    return outs, caches


@pytest.mark.parametrize("group,prefetch,pack", [
    (1, 0, False), (2, 1, True), (1, 2, True)])
def test_prefill_decode_bit_identical(group, prefetch, pack):
    """Prefill logits and every decode tick (logits AND caches) match."""
    ox, cx = _decode_outputs("xla", group=group, prefetch=prefetch,
                             pack=pack)
    op, cp = _decode_outputs("pallas", group=group, prefetch=prefetch,
                             pack=pack)
    _assert_bit_identical((ox, cx), (op, cp),
                          f"G={group} pf={prefetch} pack={pack}")


# ---------------------------------------------------------------------------
# config plumbing + memory accounting
# ---------------------------------------------------------------------------
def test_transport_validated():
    with pytest.raises(AssertionError):
        ExecutionConfig(transport="dma")


def test_baseline_normalizes_transport():
    """Baseline has no relay, so its config drops the pallas transport —
    one cache entry, no dead kernel in the program."""
    cfg = get_config("bert-large", "smoke")
    eng = engines.create("baseline", cfg,
                         ExecutionConfig(transport="pallas"), donate=False)
    assert eng.exec_cfg.transport == "xla"


def test_pallas_transport_refused_on_tpu(monkeypatch):
    """The chip's compiler refuses the relay's row DMAs (see
    tests/test_tpu_compile.py), so a TPU engine says so at creation."""
    cfg = get_config("bert-large", "smoke")
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(NotImplementedError, match="transport='pallas'"):
        engines.create("l2l-p", cfg, ExecutionConfig(transport="pallas"))
    engines.create("l2l-p", cfg, ExecutionConfig(transport="xla"))


def test_memory_model_counts_double_buffer():
    """transport="pallas" adds the kernel's two in-flight DMA chunks to
    the device budget; "xla" adds nothing."""
    cfg = get_config("bert-large", "smoke")
    eng = engines.create("l2l", cfg, ExecutionConfig(transport="pallas"),
                         donate=False)
    rep_p = eng.memory_estimate(batch=2, seq=8)
    rep_x = eng.memory_estimate(batch=2, seq=8, transport="xla")
    assert rep_p.transport_buffer > 0
    assert rep_x.transport_buffer == 0
    assert (rep_p.total_device - rep_x.total_device
            == rep_p.transport_buffer)
    from repro.serve.engine import ServeConfig
    scfg = ServeConfig(max_batch=2, page_size=8, n_pages=8, max_seq=16)
    sp = eng.serve_memory_estimate(scfg, weight_stream=True)
    sx = eng.serve_memory_estimate(scfg, weight_stream=True,
                                   transport="xla")
    assert sp.transport_buffer > 0 and sx.transport_buffer == 0
