"""Bring-up smoke run of the L2L-p relay on one TPU chip.

    python chip_smoke.py

BERT-Large at its published width (24 layers, d=1024, 364.5M parameters,
random weights from a seed) trains through the library's entry point,
``engines.create``, in this one process.  The run stops at the first
check that fails, with a non-zero exit and no result line.

1. Device: JAX must see a TPU.  Anything else is an error, not a fallback.
2. Main: ``l2l-p`` with the production train settings of
   ``launch.build.make_exec_cfg`` (weight_stream, offload_stash, prefetch
   depth 1, one layer per relay stop, xla transport, unpacked), Adam,
   batch 32 x 512 in 4 microbatches of synthetic data, one warm-up step
   and four more.  Checks: every loss is finite; the stacked layer rows
   and their Adam slots are in pinned_host right after init and after
   the last step; nothing compiles after the warm-up step; the device's
   bytes in use stay below those stacked bytes after init and after the
   last step, so the model does not live in HBM between steps.
3. Reference: the ``baseline`` engine (the whole model in HBM) on the same
   config, seed, data and microbatches, with per-layer rematerialization:
   without it this batch needs 18.4 GB of HBM, more than the chip's 15.75
   GB (compiled for a described v5e).  Its step-0 and step-1 losses agree
   with the main phase's within ``LOSS_RTOL``.
4. Packed: ``pack_params=True`` for two steps, which runs the fused Adam
   kernel (``kernels.fused_adam``) on the chip, against the main phase's
   first two losses.  Its transport is xla: the engines refuse
   ``transport="pallas"`` on TPU (``engine.PALLAS_TRANSPORT_ON_TPU``).

Times printed here are smoke readings of one run, not benchmarks.  The
last line of standard output is the one JSON result.
"""
from __future__ import annotations

import dataclasses
import json
import math
import statistics
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax
import numpy as np

from repro import engine as engines
from repro.configs.base import INPUT_SHAPES, get_config
from repro.data.synthetic import DataConfig, SyntheticLM
from repro.launch import compile_cache
from repro.launch.build import make_exec_cfg
from repro.optim import adam

ARCH = "bert-large"
BATCH, SEQ = 32, 512
SEED = 0
LR = 1e-4              # BERT's published pre-training peak learning rate
MAIN_STEPS = 5         # one warm-up step and four more
COMPARED_STEPS = 2
# Relative loss gap allowed between two schedules of the same model, fixed
# before the first chip run (the reasoning is in CHANGES.md).
LOSS_RTOL = 2e-3
COMPILE_EVENTS = ("/jax/core/compile/jaxpr_to_mlir_module_duration",
                  "/jax/core/compile/backend_compile_duration")


def check(ok: bool, what: str):
    if not ok:
        raise SystemExit(f"chip_smoke: FAILED: {what}")


class CompileCounter:
    """Lowerings and backend compiles in this process, from JAX's own
    monitoring events (a persistent-cache hit still lowers)."""

    def __init__(self):
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if event in COMPILE_EVENTS:
            self.n += 1


def device_check():
    devices = jax.devices()
    dev = devices[0]
    check(dev.platform == "tpu",
          f"JAX found no TPU (platform {dev.platform!r})")
    print(f"device: {dev.device_kind}, {len(devices)} device(s), "
          f"bytes_limit {dev.memory_stats()['bytes_limit']}")
    return dev


def synthetic_batches(cfg, n: int) -> list:
    data = SyntheticLM(DataConfig(vocab_size=cfg.vocab_size, seq_len=SEQ,
                                  global_batch=BATCH, seed=SEED))
    return [data.batch(i) for i in range(n)]


def stacked(state) -> list:
    return jax.tree.leaves((state.params["groups"],
                            state.opt_state["groups"]))


def check_eps(state, when: str):
    kinds = {a.sharding.memory_kind for a in stacked(state)}
    check(kinds == {"pinned_host"},
          f"stacked rows and slots {when} are in {sorted(kinds)}, "
          f"not pinned_host")


def train(eng, state, batches) -> tuple:
    """(state, losses, seconds per step); each step is timed to the end
    of its device work."""
    losses, secs = [], []
    for b in batches:
        t = time.perf_counter()
        state, m = eng.train_step(state, b)
        jax.block_until_ready((state, m))
        secs.append(time.perf_counter() - t)
        losses.append(float(m["loss"]))
    return state, losses, secs


def compare(name: str, got: list, want: list):
    gaps = [abs(a - b) / abs(b) for a, b in zip(got, want)]
    print(f"{name}: losses {got} vs l2l-p {want}: relative gaps {gaps} "
          f"(limit {LOSS_RTOL})")
    for i, g in enumerate(gaps):
        check(g <= LOSS_RTOL,
              f"{name} step-{i} loss is {g:.3e} from l2l-p's "
              f"(limit {LOSS_RTOL})")


def main_phase(dev, cfg, ec, batches, counter) -> list:
    eng = engines.create("l2l-p", cfg, ec, optimizer=adam(LR))
    t = time.perf_counter()
    state = eng.init(jax.random.PRNGKey(SEED))
    jax.block_until_ready(state)
    init_s = time.perf_counter() - t
    eps_bytes = sum(a.nbytes for a in stacked(state))
    check_eps(state, "after init")
    stats = dev.memory_stats()
    peak_init = stats["peak_bytes_in_use"]
    print(f"main: init {init_s:.2f} s; stacked rows + Adam slots "
          f"{eps_bytes} B; after init bytes_in_use {stats['bytes_in_use']}, "
          f"peak_bytes_in_use {peak_init}")
    check(stats["bytes_in_use"] < eps_bytes,
          f"{stats['bytes_in_use']} B in use on the device after init, "
          f"not below the {eps_bytes} B of stacked state")

    # the program the engine's jitted step runs, compiled once more for
    # its memory analysis (the persistent cache serves the second compile)
    t = time.perf_counter()
    compiled = jax.jit(eng.step_fn, donate_argnums=(0,)).lower(
        state, batches[0]).compile()
    compile_s = time.perf_counter() - t
    mem = compiled.memory_analysis()
    est = eng.memory_estimate(batch=BATCH, seq=SEQ)

    state, losses, secs = train(eng, state, batches[:1])
    before = counter.n
    state, more, steady = train(eng, state, batches[1:])
    losses += more
    compiles = counter.n - before
    print(f"main: losses {losses}; compiles after the warm-up step "
          f"{compiles}")
    check(all(math.isfinite(x) for x in losses), "a main-phase loss is "
          "not finite")
    check(compiles == 0, f"{compiles} compiles after the warm-up step")
    check_eps(state, "after the last step")
    stats = dev.memory_stats()
    peak = stats["peak_bytes_in_use"]
    check(stats["bytes_in_use"] < eps_bytes,
          f"{stats['bytes_in_use']} B in use on the device after the last "
          f"step, not below the {eps_bytes} B of stacked state")
    who = ("set by init, which builds the state on the device before "
           "moving it to the host: not the step's footprint"
           if peak == peak_init else "set by the steps")
    print(f"main: after the last step bytes_in_use {stats['bytes_in_use']}, "
          f"peak_bytes_in_use {peak} ({who}); compiled step: device "
          f"arguments {mem.argument_size_in_bytes}, device temp "
          f"{mem.temp_size_in_bytes}, host arguments "
          f"{mem.host_argument_size_in_bytes}, host temp "
          f"{mem.host_temp_size_in_bytes}; memory_estimate: device "
          f"{est.total_device}, host {est.total_host}")
    print(f"main (smoke reading, not a benchmark): compile {compile_s:.2f} "
          f"s, warm-up step {secs[0]:.3f} s, steady "
          f"{statistics.median(steady):.3f} s/step over {len(steady)} "
          f"steps")
    return losses


def compared_phase(name: str, engine: str, cfg, ec, batches, want: list):
    eng = engines.create(engine, cfg, ec, optimizer=adam(LR))
    state = eng.init(jax.random.PRNGKey(SEED))
    _, losses, _ = train(eng, state, batches[:COMPARED_STEPS])
    compare(name, losses, want[:COMPARED_STEPS])


def main(argv=None) -> int:
    dev = device_check()
    print(f"compile cache: {compile_cache.enable()}")
    counter = CompileCounter()
    cfg = get_config(ARCH, "full")
    mesh = jax.sharding.Mesh(np.asarray(jax.devices()[:1]), ("data",))
    ec = make_exec_cfg(INPUT_SHAPES["train_4k"], cfg, mesh)
    check(ec.n_microbatches == 4 and ec.transport == "xla"
          and not ec.pack_params, f"unexpected train settings {ec}")
    batches = synthetic_batches(cfg, MAIN_STEPS)

    want = main_phase(dev, cfg, ec, batches, counter)
    compared_phase("baseline", "baseline", cfg,
                   dataclasses.replace(ec, remat=True), batches, want)
    compared_phase("packed", "l2l-p", cfg,
                   dataclasses.replace(ec, pack_params=True), batches, want)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
