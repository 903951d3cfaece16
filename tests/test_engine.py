"""Engine facade API tests: registry behaviour, cross-engine gradient
parity on a tiny dense model, TrainState round-trips, and the lifecycle
surface (init / train_step / prefill / decode / memory_estimate)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import make_batch
from repro import engine as engines
from repro.configs.base import get_config
from repro.core.schedule import ExecutionConfig
from repro.engine import TrainState
from repro.optim import adam


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------
def test_registry_lists_all_schedules():
    names = engines.available()
    assert {"baseline", "l2l", "l2l-p"} <= set(names)


def test_registry_unknown_name_raises_with_available_names():
    with pytest.raises(ValueError) as ei:
        engines.create("no-such-engine", get_config("bert-large", "smoke"))
    msg = str(ei.value)
    assert "no-such-engine" in msg
    for name in ("baseline", "l2l", "l2l-p"):
        assert name in msg


def test_registry_is_open_for_extension():
    @engines.register("test-alias-l2lp")
    class AliasEngine(engines.L2LPEngine):
        name = "test-alias-l2lp"

    try:
        assert "test-alias-l2lp" in engines.available()
        eng = engines.create("test-alias-l2lp",
                             get_config("bert-large", "smoke"))
        assert eng.name == "test-alias-l2lp"
        assert eng.exec_cfg.eager_optimizer
    finally:
        engines.registry._REGISTRY.pop("test-alias-l2lp", None)


# ---------------------------------------------------------------------------
# parity: every registered engine computes identical grads on a tiny
# dense model (the paper's Alg 2 == Alg 3 == Alg 4 identity)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("name", engines.available())
def test_engine_parity_grads(name, make_engine):
    cfg = get_config("bert-large", "smoke").replace(dtype="float32")
    batch = make_batch(cfg, 4, 16)
    ref = make_engine("baseline")
    eng = make_engine(name)
    params = ref.model.init_params(jax.random.PRNGKey(0))
    l_ref, g_ref = ref.grads(params, batch)
    l, g = eng.grads(params, batch)
    assert abs(float(l_ref) - float(l)) < 1e-4, name
    err = max(jax.tree.leaves(jax.tree.map(
        lambda a, b: float(jnp.max(jnp.abs(a - b))), g_ref, g)))
    assert err < 1e-4, (name, err)


@pytest.mark.parametrize("name", engines.available())
def test_engine_lifecycle_train_step(name, make_engine):
    cfg = get_config("bert-large", "smoke").replace(dtype="float32")
    batch = make_batch(cfg, 4, 16)
    eng = make_engine(name, optimizer=adam(lr=1e-3))
    state = eng.init(jax.random.PRNGKey(0))
    assert int(state.step) == 0
    new_state, metrics = eng.train_step(state, batch)
    assert isinstance(new_state, TrainState)
    assert int(new_state.step) == 1
    assert jnp.isfinite(metrics["loss"])
    moved = jax.tree.map(lambda a, b: bool(jnp.any(a != b)),
                         state.params, new_state.params)
    assert any(jax.tree.leaves(moved)), name


# ---------------------------------------------------------------------------
# TrainState
# ---------------------------------------------------------------------------
def test_train_state_legacy_roundtrip(make_engine):
    eng = make_engine("l2l-p")
    state = eng.init(jax.random.PRNGKey(0))
    back = TrainState.from_legacy(state.params, state.legacy_opt())
    assert jax.tree.structure(back) == jax.tree.structure(state)
    assert back.loss_scale is None
    assert set(state.opt_state) == {"embed", "head", "groups"}


def test_train_state_is_jit_transparent(make_engine):
    eng = make_engine("baseline")
    state = eng.init(jax.random.PRNGKey(0))

    @jax.jit
    def bump(s):
        return s.replace(step=s.step + 1)

    assert int(bump(state).step) == 1


def test_engine_save_restore_roundtrip(tmp_path, make_engine):
    eng = make_engine("l2l-p")
    state = eng.init(jax.random.PRNGKey(0))
    eng.save(str(tmp_path), state, step=7)
    restored, step = eng.restore(str(tmp_path))
    assert step == 7
    assert jax.tree.all(jax.tree.map(
        lambda a, b: bool(jnp.all(jnp.asarray(a) == jnp.asarray(b))),
        state.params, restored.params))


@pytest.mark.parametrize("name", ["baseline", "l2l-p"])
def test_init_state_runs_the_steps_program(name, make_engine):
    """``init`` hands over a state the step's own outputs look like, so
    the first step compiles the program every later step runs."""
    cfg = get_config("bert-large", "smoke").replace(dtype="float32")
    eng = make_engine(name)
    state = eng.init(jax.random.PRNGKey(0))
    assert all(a.committed for a in jax.tree.leaves(state))
    for seed in (0, 1):
        state, _ = eng.train_step(state, make_batch(cfg, 4, 16, seed=seed))
    assert eng._fns["train_step"]._cache_size() == 1


def test_init_and_restore_put_stacked_rows_in_the_eps(tmp_path, make_engine):
    """The stacked rows and their optimizer slots go through the EPS
    placements' host move at init and at restore (pinned_host on TPU)."""
    from repro.core.eps import EPSPlacements, Placement, noop_placement
    moved = []

    def host(tree):
        moved.append(tree)
        return tree

    ident = lambda t: t
    eps_host = Placement(host, ident, ident)
    eng = make_engine("l2l-p", placements=EPSPlacements(
        (eps_host,), (eps_host,), noop_placement()))
    state = eng.init(jax.random.PRNGKey(0))
    assert moved == [state.params["groups"][0],
                     state.opt_state["groups"][0]]
    eng.save(str(tmp_path), state, step=1)
    restored, _ = eng.restore(str(tmp_path))
    assert moved[2:] == [restored.params["groups"][0],
                         restored.opt_state["groups"][0]]


# ---------------------------------------------------------------------------
# inference + analysis surface
# ---------------------------------------------------------------------------
def test_engine_prefill_and_decode(make_engine):
    eng = make_engine("l2l", "granite-3-8b", dtype=None,
                      exec_cfg=ExecutionConfig())
    cfg = eng.model.cfg
    params = eng.model.init_params(jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0,
                              cfg.vocab_size)
    caches, logits = eng.decode_init(params, toks, live_seq=16)
    assert logits.shape == (2, cfg.vocab_size)
    tok = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
    logits2, caches = eng.decode_step(params, caches, tok, jnp.int32(8))
    assert logits2.shape[-1] == cfg.vocab_size

    batch = make_batch(cfg, 4, 16)
    out = eng.prefill(params, {"tokens": batch["tokens"]})
    assert out.shape == (4, cfg.vocab_size)
    assert bool(jnp.all(jnp.isfinite(out.astype(jnp.float32))))


def test_engine_memory_estimate_modes(make_engine):
    reports = {}
    for name in engines.available():
        eng = make_engine(name, exec_cfg=ExecutionConfig(
            n_microbatches=8, offload_stash=(name == "l2l-p")))
        reports[name] = eng.memory_estimate(batch=32, seq=128)
    # the L2L device footprint must undercut the baseline's
    base = reports["baseline"].total_device + reports["baseline"].opt_state
    assert reports["l2l"].total_device < base
    assert reports["l2l-p"].total_device < base
    # l2l-p offloads the stash to the EPS host
    assert reports["l2l-p"].stash_on_host


def test_exec_cfg_normalized_per_engine(make_engine):
    ec = ExecutionConfig(n_microbatches=2, eager_optimizer=True)
    assert make_engine("l2l", exec_cfg=ec).exec_cfg.eager_optimizer is False
    ec2 = ExecutionConfig(n_microbatches=2, eager_optimizer=False)
    assert make_engine("l2l-p",
                       exec_cfg=ec2).exec_cfg.eager_optimizer is True


def test_grads_accepts_state_or_params(make_engine):
    cfg = get_config("bert-large", "smoke").replace(dtype="float32")
    batch = make_batch(cfg, 4, 16)
    eng = make_engine("l2l")
    state = eng.init(jax.random.PRNGKey(0))
    l1, g1 = eng.grads(state, batch)
    l2, g2 = eng.grads(state.params, batch)
    assert float(l1) == float(l2)
    np.testing.assert_array_equal(
        np.asarray(jax.tree.leaves(g1)[0]), np.asarray(jax.tree.leaves(g2)[0]))
