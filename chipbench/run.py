"""Run one benchmark cell once, in this process, on the chip it finds.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

In order: check that JAX sees a TPU (anything else is an error: exit 1,
no result); print the device and host; keep JAX's compile cache in
``<checkout>/.jax_cache``; build the cell's batches from the seed; create
the ``l2l-p`` engine through ``repro.engine.create`` with the production
settings; ``Engine.init`` from the seed; take the check's first steps
through ``Engine.train_step`` (the first compiles the one step shape);
measure for ``--seconds``; free the program's state; run the plain
reference over the same first steps and compare.

The last line of standard output is one JSON object.  With ``--trace 0``
its metrics are the cell's end-to-end metrics; with ``--trace 1`` the
window runs under the profiler and the metrics are the per-layer ones,
each read by ``chipbench/metrics/<name>.py``.  The numbers compared, each
beside its limit, are the last lines of standard error and the last key
of the JSON line.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import resource  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import jax  # noqa: E402

from chipbench import cell as cells  # noqa: E402
from chipbench import check, data, flops, peaks, program, trace  # noqa: E402
from chipbench.reference import train as reference  # noqa: E402

ROOT = cells.ROOT
CACHE = ROOT / ".jax_cache"
TRACE_DIR = ROOT / "chipbench" / ".out" / "trace"
COMPILE_EVENTS = ("/jax/core/compile/jaxpr_to_mlir_module_duration",
                  "/jax/core/compile/backend_compile_duration")


class CompileCounter:
    """Lowerings and backend compiles in this process, from JAX's own
    monitoring events (a persistent-cache hit still lowers)."""

    def __init__(self):
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if event in COMPILE_EVENTS:
            self.n += 1


def fail(msg: str):
    raise SystemExit(f"chipbench: {msg}")


def check_device(chips: int):
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        fail(f"JAX found no TPU (platform {dev.platform!r}); this benchmark "
             f"measures the chip and has no fallback")
    if len(devices) < chips:
        fail(f"the cell needs {chips} chips, JAX found {len(devices)}")
    return dev


def describe_host(dev):
    import importlib.metadata as md
    mem = next((ln.split()[1] for ln in open("/proc/meminfo")
                if ln.startswith("MemTotal:")), "?")
    try:
        libtpu = md.version("libtpu")
    except md.PackageNotFoundError:
        libtpu = "not installed"
    print(f"device: platform {dev.platform}, kind {dev.device_kind}, "
          f"{len(jax.devices())} device(s), bytes_limit "
          f"{(dev.memory_stats() or {}).get('bytes_limit')}; host MemTotal "
          f"{mem} kB; jax {jax.__version__}, libtpu {libtpu}", flush=True)


def enable_compile_cache():
    """A fixed directory inside the checkout, whatever the environment
    says, so that parent and change never share one, and every program
    cached (the small eager ones of ``Engine.init`` too)."""
    jax.config.update("jax_compilation_cache_dir", str(CACHE))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def run_key(seed: int):
    """A PRNG key from any whole seed (more than 32 bits too)."""
    return jax.random.fold_in(jax.random.PRNGKey(seed % 2**32), seed >> 32)


def first_steps(c: dict, seed: int) -> dict:
    """Set-up: the batches, the engine, its init from the seed and the
    check's first steps through ``Engine.train_step`` (the first of them
    compiles the cell's one step shape), with the program's readings and
    the seconds each part of the set-up took."""
    conf, traffic, wl = c["config"], c["traffic"], c["workload"]
    parts, t = {"start": time.perf_counter() - T0}, [time.perf_counter()]

    def lap(name):
        now = time.perf_counter()
        parts[name], t[0] = now - t[0], now

    cfg = program.program_config(conf)
    key = run_key(seed)
    n_check = wl["check"]["steps"]
    host_batches = data.make_batches(traffic, conf["config"]["vocab_size"],
                                     seed)
    if len(host_batches) < n_check:
        fail(f"traffic {wl['traffic']} cycles fewer batches than the "
             f"check's {n_check} steps")
    batches = [jax.device_put(b) for b in host_batches]
    lap("batches")
    eng = program.build(conf, cfg, traffic)
    lap("engine")
    # the init's own arrays leave the device once its moves to the host
    # are done; waiting for them keeps the weights below from adding to
    # the init's peak
    state = jax.block_until_ready(eng.init(key))
    lap("init")
    state = jax.block_until_ready(program.load_weights(state, conf, key))
    lap("weights")
    got = {"losses": []}
    for i in range(n_check):
        state, m = eng.train_step(state, batches[i])
        got["losses"].append(float(m["loss"]))
        lap(f"step{i}")
        if i == 0:
            got["grad_norms"] = program.grad_readings(
                state, conf["optimizer"]["b1"])
            lap("grad_readings")
    got["change_norms"] = program.change_readings(state, conf, key)
    jax.block_until_ready(state)
    lap("change_readings")
    return {"eng": eng, "state": state, "batches": batches, "key": key,
            "checked": host_batches[:n_check], "got": got, "parts": parts}


def run(c: dict, seed: int, seconds: float, traced: bool) -> dict:
    """One run of cell ``c`` (as ``cell.load`` gives it); the result line."""
    conf, traffic, wl = c["config"], c["traffic"], c["workload"]
    n_check = wl["check"]["steps"]
    tokens = traffic["batch"] * traffic["seq"]
    counter = CompileCounter()
    s = first_steps(c, seed)
    eng, state, batches = s.pop("eng"), s.pop("state"), s.pop("batches")

    # the measured window: whole steps, each to the end of its device work
    if traced:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(TRACE_DIR), profiler_options=opts)
    compiles0 = counter.n
    setup_s = time.perf_counter() - T0
    start = time.perf_counter()
    steps = failed = 0
    end = start
    while end - start < seconds:
        b = n_check + steps
        with jax.profiler.StepTraceAnnotation(trace.STEP, step_num=b):
            with jax.profiler.TraceAnnotation("feed"):
                batch = batches[b % len(batches)]
            with jax.profiler.TraceAnnotation("train_step"):
                state, m = eng.train_step(state, batch)
            with jax.profiler.TraceAnnotation("block"):
                jax.block_until_ready(state)
                failed += not math.isfinite(float(m["loss"]))
        steps += 1
        end = time.perf_counter()
    if traced:
        jax.profiler.stop_trace()
    window_compiles = counter.n - compiles0
    tokens_per_s = steps * tokens / (end - start)

    dev = jax.devices()[0]
    peak_hbm = (dev.memory_stats() or {}).get("peak_bytes_in_use", 0)
    peak_host = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": peak_hbm}
    out = {"attempted": steps, "failed": failed}
    if traced:
        files = sorted(TRACE_DIR.glob("**/*.xplane.pb"))
        reduced = trace.reduce(trace.load(files[-1])) if files else {}
        ctx = {
            "window_compiles": window_compiles,
            "tokens_per_s": tokens_per_s,
            "flops_per_token": flops.flops_per_token(conf["config"],
                                                     traffic["seq"]),
            "peak": peaks.peak(dev.device_kind),
            "trace": reduced,
            "state": state,
            "step_memory": jax.jit(eng.step_fn, donate_argnums=(0,)).lower(
                state, batches[0]).compile().memory_analysis(),
        }
        metrics = {}
        for m_ in c["per_layer"]:
            v = cells.metric_reader(m_["name"])(ctx)
            if v is not None:
                metrics[m_["name"]] = {"value": v, "unit": m_["unit"]}
        if reduced:
            device.update(busy_s=reduced["busy_s"],
                          window_s=reduced["window_s"])
            out["breakdown"] = {"device_ops": reduced["device_ops"],
                                "idle_gaps": reduced["idle_gaps"]}
        del ctx
    else:
        e2e = {"train_tokens_per_s": tokens_per_s,
               "peak_hbm_gb": peak_hbm / 1e9,
               "peak_host_gb": peak_host / 1e9, "setup_s": setup_s}
        metrics = {m_["name"]: {"value": e2e[m_["name"]], "unit": m_["unit"]}
                   for m_ in c["end_to_end"]}
    print(f"window: {steps} steps in {end - start:.3f} s, "
          f"{window_compiles} compiles; set-up {setup_s:.3f} s: "
          + ", ".join(f"{k} {v:.3f}" for k, v in s["parts"].items()),
          flush=True)

    # the program's state goes before the reference needs the chip
    del state, m, batch, batches, eng
    gc.collect()
    t = time.perf_counter()
    want = reference.train(conf["config"], conf["optimizer"], s["key"],
                           s["checked"])
    print(f"reference: {n_check} steps in {time.perf_counter() - t:.3f} s; "
          f"losses {want['losses']}, program {s['got']['losses']}",
          flush=True)
    correct, rows = check.verdict(check.gaps(s["got"], want),
                                  wl["check"]["limits"])
    return {"correct": correct, **out, "metrics": metrics, "device": device,
            "checks": {n: {"value": v, "limit": lim} for n, v, lim in rows}}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    a = ap.parse_args(argv)
    c = cells.load(a.workload)
    dev = check_device(c["chips"])
    describe_host(dev)
    enable_compile_cache()
    result = run(c, a.seed, a.seconds, bool(a.trace))
    for name, v in result["checks"].items():
        print(f"check {name} {v['value']!r} limit {v['limit']!r}",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
