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
import re

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


@pytest.fixture(scope="module")
def l2l_p_step(topo):
    """BERT-Large at full width, l2l-p with the production train settings
    (``launch.build.make_exec_cfg``), compiled once for the described
    chip: the compiled step and the engine's abstract state."""
    from repro import engine as engines
    from repro.configs.base import get_config
    from repro.core import eps
    from repro.core.schedule import ExecutionConfig
    from repro.engine import TrainState

    dev = topo.devices[0]
    host_sh = SingleDeviceSharding(dev, memory_kind="pinned_host")
    dev_sh = SingleDeviceSharding(dev, memory_kind="device")
    put = lambda tree, sh: jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sh), tree)
    B, S = 32, 512
    batch = {"tokens": _arg((B, S), jnp.int32, dev_sh),
             "targets": _arg((B, S), jnp.int32, dev_sh),
             "mask": _arg((B, S), jnp.float32, dev_sh)}
    # the placements ask the backend, which is the CPU here: steer them
    # to the described chip
    single = eps.single_device_placement
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(eps, "memories_supported", lambda: True)
        mp.setattr(eps, "single_device_placement",
                   lambda device=None: single(dev))
        ec = ExecutionConfig(n_microbatches=4, offload_stash=True,
                             weight_stream=True, prefetch_depth=1)
        eng = engines.create("l2l-p", get_config("bert-large", "full"), ec,
                             donate=False)
        st = eng.abstract_state()
        state = TrainState(
            params={**put(st.params, dev_sh),
                    "groups": put(st.params["groups"], host_sh)},
            opt_state={**put(st.opt_state, dev_sh),
                       "groups": put(st.opt_state["groups"], host_sh)},
            step=put(st.step, dev_sh))
        compiled = jax.jit(eng.step_fn).lower(state, batch).compile()
    return compiled, st


def test_l2l_p_train_step_compile(l2l_p_step):
    """The stacked layers and their Adam slots are host arguments of the
    compiled step, and only the embedding, the head and their slots are
    device arguments."""
    compiled, st = l2l_p_step
    mem = compiled.memory_analysis()
    stacked = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(
        (st.params["groups"], st.opt_state["groups"])))
    assert stacked > 3.5e9        # 24 layers of weights, m and v in f32
    assert mem.host_argument_size_in_bytes >= stacked
    assert mem.argument_size_in_bytes < stacked


# moves on host memory that the compiler makes with no op_name: the
# step-end host computations that copy a stacked array host to host, and
# the ring's first fetch of each stream, a slice at constant indices that
# the compiler rebuilds as an async slice: of an entry parameter, or of
# the stacked boundaries the forward scan returns (the backward's stash)
UNSCOPED_MOVES = (
    re.compile(r"^%host-call(\.\d+)?\.cloned\.\d+\.call-(start|done) = "
               r".* custom-call-(start|done)\("),
    re.compile(r"^%dynamic-slice-start\.\d+ = .* dynamic-slice-start\("
               r"%(state_[\w.]+|get-tuple-element\.\d+)"
               r"(, (/\*index=\d+\*/)?%constant\.\d+)+\), "
               r"dynamic_slice_sizes=\{[\d,]+\}$"),
)


def test_l2l_p_relay_moves_carry_relay_scopes(l2l_p_step):
    """Every async move on host memory (``S(5)``) is a relay move by its
    scope, ``relay.fetch`` or ``relay.writeback``, or one the compiler
    made with no scope (``UNSCOPED_MOVES``)."""
    from repro.core import spans
    moves, unscoped = {spans.FETCH: 0, spans.WRITEBACK: 0}, []
    for line in l2l_p_step[0].as_text().splitlines():
        line = line.strip()
        if "S(5)" not in line or not re.search(r" [\w-]+-(start|done)\(",
                                               line):
            continue
        name = re.search(r'op_name="([^"]*)"', line)
        if name is None:
            unscoped.append(line)
            continue
        assert spans.phase(name.group(1)) in moves, line
        moves[spans.phase(name.group(1))] += 1
    assert moves[spans.FETCH] and moves[spans.WRITEBACK]
    assert all(any(p.match(ln) for p in UNSCOPED_MOVES)
               for ln in unscoped), unscoped


def _computations(hlo: str) -> dict:
    """name -> instruction lines of each computation of ``as_text()``."""
    comps, cur = {}, None
    for line in hlo.splitlines():
        head = re.match(r"^(?:ENTRY )?%?([\w.\-]+) \(.*\{$", line)
        if head and not line.startswith(" "):
            cur = comps.setdefault(head.group(1), [])
        elif line.startswith("}"):
            cur = None
        elif cur is not None:
            cur.append(line.strip())
    return comps


def test_l2l_p_backward_fetches_overlap_the_vjp(l2l_p_step):
    """In the backward relay's body, each stop issues the fetch of the
    stop K ahead (weights, Adam slots and stashed boundary, on ``S(5)``)
    before its microbatch vjp loop: the fetch lands in a slot that no
    value of the stop occupies, so neither the vjp nor the optimizer
    update that reads the slot last holds it back.  A trip runs K + 1
    stops, so before the j-th vjp loop of the body the next-stop fetches
    of j + 1 stops have been issued.  Checked for the transfers of 16 MiB
    and more, which carry most of a stop's bytes (the MLP's weights and
    their Adam slots, 100.7 of 151 MB, and the 33.6 MB boundary); the
    compiler issues the smaller ones where it sees fit."""
    from repro.core import spans

    def vjp_loop(line):
        name = re.search(r'op_name="([^"]*)"', line)
        return (" while(" in line and name is not None
                and spans.phase(name.group(1)) == spans.BACKWARD)

    def fetched_bytes(line):
        out = re.search(r"\), (\w+)\[([\d,]*)\]", line)
        n = 1
        for d in filter(None, out.group(2).split(",")):
            n *= int(d)
        return n * jnp.dtype(out.group(1).replace("bf16", "bfloat16")
                             .replace("f32", "float32")).itemsize

    bodies = [lines for lines in
              _computations(l2l_p_step[0].as_text()).values()
              if any(vjp_loop(ln) for ln in lines)]
    assert len(bodies) == 1, len(bodies)
    (body,) = bodies
    loops = [i for i, ln in enumerate(body) if vjp_loop(ln)]
    fetches = [(i, fetched_bytes(ln)) for i, ln in enumerate(body)
               if re.search(r"dynamic-slice-start\(", ln) and "S(5)" in ln]
    # every stop fetches 16 leaves of BERT-Large's layer, each with two
    # Adam slots, and the stashed boundary
    assert len(fetches) == len(loops) * (16 * 3 + 1)
    big = [i for i, b in fetches if b >= 16 * 2**20]
    per_stop = len(big) // len(loops)
    assert per_stop == 2 * 3 + 1
    for j, loop in enumerate(loops):
        issued = sum(1 for i in big if i < loop)
        assert issued >= (j + 1) * per_stop, (j, issued)
