"""Compile rehearsal for one TPU v5e chip, with no chip attached.

The TPU compiler is installed with JAX; it compiles for a chip that is
described (``jax.experimental.topologies``) and refuses what the chip
would refuse: slices not aligned to the (8, 128) tiling, DMAs from
memory spaces a kernel cannot address, programs that do not fit.
Interpret mode, which every other kernel test uses, sees none of that.
So this file compiles each Pallas kernel of the main path at BERT-Large
widths, and the ``l2l-p`` train step itself, for a described v5e, and
pins down that the relay's copy kernel is still refused.

Nothing here runs: results and times need the chip (``chip_smoke.py``).
The topology is described inside a fixture, so that only the test worker
that is handed this file loads the TPU library.
"""
import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

BERT = dict(B=8, H=16, S=512, D=64, d=1024)
# one packed layer of BERT-Large: its f32 segment, in elements
ADAM_SEGMENT = 12_596_224


@pytest.fixture(scope="module")
def topo():
    """A described v5e.  Without the TPU compiler (libtpu) there is
    nothing to rehearse with, and only that skips; any other failure to
    describe the chip is an error."""
    pytest.importorskip("libtpu", reason="the TPU compiler (libtpu) is "
                        "not installed")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    finally:
        jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _arg(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


def _has_kernel(compiled) -> bool:
    return "tpu_custom_call" in compiled.as_text()


def test_flash_attention_fwd_bwd_compile(one_chip):
    from repro.kernels import ops
    B, H, S, D = BERT["B"], BERT["H"], BERT["S"], BERT["D"]
    q = _arg((B, S, H, D), jnp.bfloat16, one_chip)

    def loss(q, k, v):
        o = ops.flash_attention(q, k, v, causal=True, interpret=False)
        return o.astype(jnp.float32).sum()

    fwd = _compile(functools.partial(ops.flash_attention, causal=True,
                                     interpret=False), q, q, q)
    bwd = _compile(jax.grad(loss, argnums=(0, 1, 2)), q, q, q)
    assert _has_kernel(fwd) and _has_kernel(bwd)


def test_rmsnorm_compile(one_chip):
    from repro.kernels.rmsnorm import rmsnorm_2d
    R, d = BERT["B"] * BERT["S"], BERT["d"]
    c = _compile(functools.partial(rmsnorm_2d, interpret=False),
                 _arg((R, d), jnp.bfloat16, one_chip),
                 _arg((d,), jnp.float32, one_chip))
    assert _has_kernel(c)


def test_fused_adam_flat_compile(one_chip):
    """The packed update's path: ``ops.fused_adam`` pads the segment to
    the kernel block and runs ``fused_adam_flat`` over it."""
    from repro.kernels import ops
    vec = _arg((ADAM_SEGMENT,), jnp.float32, one_chip)
    scal = _arg((), jnp.float32, one_chip)
    c = _compile(functools.partial(ops.fused_adam, interpret=False),
                 vec, vec, vec, vec, scal, scal)
    assert _has_kernel(c)


def test_relay_copy_refused_by_chip_compiler(topo, one_chip):
    """Why the engines refuse ``transport="pallas"`` on TPU: the chip's
    compiler refuses ``copy_rows`` at BERT-Large's packed layer width,
    from HBM (a 1-row slice of the ``(N, W)`` buffer breaks the (8, 128)
    tiling) and from pinned_host (a memory space the kernel cannot
    address).  Once this compiles, ``PALLAS_TRANSPORT_ON_TPU`` can go."""
    from repro.kernels import relay_copy
    host = SingleDeviceSharding(topo.devices[0], memory_kind="pinned_host")
    start = _arg((), jnp.int32, one_chip)
    for sh, why in ((one_chip, "aligned to tiling"),
                    (host, "memory space")):
        with pytest.raises(Exception, match=why):
            _compile(functools.partial(relay_copy.copy_rows, size=1,
                                       interpret=False),
                     _arg((24, ADAM_SEGMENT), jnp.float32, sh), start)


def test_l2l_p_train_step_compile(topo, one_chip, monkeypatch):
    """BERT-Large at full width, l2l-p with the production train settings
    (``launch.build.make_exec_cfg``): the stacked layers and their Adam
    slots are host arguments of the compiled step, and only the embedding,
    the head and their slots are device arguments."""
    from repro import engine as engines
    from repro.configs.base import get_config
    from repro.core import eps
    from repro.core.schedule import ExecutionConfig
    from repro.engine import TrainState

    dev = topo.devices[0]
    host_sh = SingleDeviceSharding(dev, memory_kind="pinned_host")
    dev_sh = SingleDeviceSharding(dev, memory_kind="device")
    # the placements ask the backend, which is the CPU here: steer them
    # to the described chip
    single = eps.single_device_placement
    monkeypatch.setattr(eps, "memories_supported", lambda: True)
    monkeypatch.setattr(eps, "single_device_placement",
                        lambda device=None: single(dev))

    cfg = get_config("bert-large", "full")
    ec = ExecutionConfig(n_microbatches=4, offload_stash=True,
                         weight_stream=True, prefetch_depth=1)
    eng = engines.create("l2l-p", cfg, ec, donate=False)
    st = eng.abstract_state()
    put = lambda tree, sh: jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sh), tree)
    state = TrainState(
        params={**put(st.params, dev_sh),
                "groups": put(st.params["groups"], host_sh)},
        opt_state={**put(st.opt_state, dev_sh),
                   "groups": put(st.opt_state["groups"], host_sh)},
        step=put(st.step, dev_sh))
    B, S = 32, 512
    batch = {"tokens": _arg((B, S), jnp.int32, dev_sh),
             "targets": _arg((B, S), jnp.int32, dev_sh),
             "mask": _arg((B, S), jnp.float32, dev_sh)}
    mem = jax.jit(eng.step_fn).lower(state, batch).compile() \
        .memory_analysis()

    stacked = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(
        (st.params["groups"], st.opt_state["groups"])))
    assert stacked > 3.5e9        # 24 layers of weights, m and v in f32
    assert mem.host_argument_size_in_bytes >= stacked
    assert mem.argument_size_in_bytes < stacked
