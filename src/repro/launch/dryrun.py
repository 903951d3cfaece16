"""Multi-pod dry-run: lower + compile every (architecture x input shape)
on the production meshes and extract the roofline terms.

Usage::

    PYTHONPATH=src python -m repro.launch.dryrun --arch all --shape all \
        --mesh single            # 16x16 (256 chips) + roofline terms
    PYTHONPATH=src python -m repro.launch.dryrun --mesh multi  # 2x16x16

Results land in experiments/dryrun/<mesh>/<arch>__<shape>.json and feed
EXPERIMENTS.md §Dry-run and §Roofline.
"""
import os

# a CPU lowering tool: it never takes an accelerator, and it adds its
# host device count to whatever XLA_FLAGS the caller set (before any
# backend starts: the count is fixed then)
os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ["XLA_FLAGS"] = " ".join(dict.fromkeys(
    os.environ.get("XLA_FLAGS", "").split()
    + ["--xla_force_host_platform_device_count=512"]))

import argparse
import json
import re
import time
import traceback

import jax
import numpy as np

from repro.configs.base import INPUT_SHAPES, get_config, list_archs
from repro.launch import mesh as mesh_mod
from repro.launch.build import SkipCombo, build

COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
               "collective-permute")

_DTYPE_BYTES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "f8": 1,
                "s64": 8, "s32": 4, "s16": 2, "s8": 1,
                "u64": 8, "u32": 4, "u16": 2, "u8": 1, "pred": 1}

_SHAPE_RE = re.compile(r"=\s*(?:\()?\s*(\w+)\[([\d,]*)\]")


def parse_collective_bytes(hlo_text: str) -> dict:
    """Per-device bytes moved by collectives, by op kind (result-shape
    convention: the bytes that land on each device)."""
    out = {c: {"count": 0, "bytes": 0} for c in COLLECTIVES}
    for line in hlo_text.splitlines():
        stripped = line.strip()
        for c in COLLECTIVES:
            # match the op name after '=' e.g. '%x = bf16[..] all-reduce('
            if f" {c}(" in stripped or f" {c}-start(" in stripped:
                m = _SHAPE_RE.search(stripped)
                if not m:
                    continue
                dt, dims = m.group(1), m.group(2)
                nbytes = _DTYPE_BYTES.get(dt, 4)
                n = 1
                if dims:
                    for d in dims.split(","):
                        n *= int(d)
                out[c]["count"] += 1
                out[c]["bytes"] += n * nbytes
                break
    return out


def cost_get(ca, key: str) -> float:
    # jax returns cost_analysis() as a dict on recent versions, a
    # one-element list of dicts on older ones — accept both
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else None
    return float(ca.get(key, 0.0)) if ca else 0.0


# ---------------------------------------------------------------------------
# Depth probes: XLA's cost_analysis counts while-loop bodies ONCE, so the
# scanned (depth-N) program under-reports FLOPs/bytes/collectives by the
# trip count.  We compile small UNROLLED depths (all-groups-1, then 2 for
# one group at a time) and extrapolate exactly linearly to the full depth.
# ---------------------------------------------------------------------------
def group_depths(cfg):
    if cfg.family == "audio":
        return (cfg.n_encoder_layers, cfg.n_layers)
    if cfg.family == "moe" and cfg.first_dense_layers:
        return (cfg.first_dense_layers,
                cfg.n_layers - cfg.first_dense_layers)
    return (cfg.n_layers,)


def with_depths(cfg, depths):
    if cfg.family == "audio":
        enc, dec = depths
        return cfg.replace(n_encoder_layers=enc, n_layers=dec)
    if cfg.family == "moe" and cfg.first_dense_layers:
        d0, d1 = depths
        return cfg.replace(first_dense_layers=d0, n_layers=d0 + d1)
    (d,) = depths
    return cfg.replace(n_layers=d)


def _cost_vector(built, mesh):
    with mesh:
        compiled = jax.jit(built.fn, in_shardings=built.in_shardings,
                           out_shardings=built.out_shardings
                           ).lower(*built.args).compile()
        ca = compiled.cost_analysis()
        hlo = compiled.as_text()
    coll = parse_collective_bytes(hlo)
    vec = {"flops": cost_get(ca, "flops"),
           "bytes_accessed": cost_get(ca, "bytes accessed")}
    for c, v in coll.items():
        vec[f"coll_bytes::{c}"] = float(v["bytes"])
        vec[f"coll_count::{c}"] = float(v["count"])
    return vec


def probe_costs(arch, shape_name, mesh, variant, exec_overrides,
                rule_overrides, cfg_patch=None):
    """Unrolled reduced-depth compiles + exact linear extrapolation."""
    cfg = get_config(arch, variant)
    if cfg_patch:
        cfg = cfg.replace(**cfg_patch)
    full = group_depths(cfg)
    G = len(full)
    probe_exec = dict(exec_overrides or {})
    # the probes compile tiny unrolled depths for cost extrapolation —
    # depth gating is irrelevant there (and depth-1 probes violate the
    # dynamic_depth capacity/K divisibility), so probe statically
    probe_exec.update(n_microbatches=1, unroll_layers=True,
                      dynamic_depth=False)
    base_depths = tuple(1 for _ in full)
    probes = [base_depths] + [
        tuple(2 if j == i else 1 for j in range(G)) for i in range(G)]
    vecs = []
    for d in probes:
        built = build(arch, shape_name, mesh, variant=variant,
                      exec_overrides=probe_exec,
                      rule_overrides=rule_overrides,
                      cfg_override=with_depths(cfg, d))
        vecs.append(_cost_vector(built, mesh))
    keys = vecs[0].keys()
    total = {}
    for k in keys:
        t = vecs[0][k]
        for i in range(G):
            delta = max(vecs[1 + i][k] - vecs[0][k], 0.0)
            t += (full[i] - 1) * delta
        total[k] = t
    # analytic correction: rwkv's wkv recurrence is a while loop over seq
    # even when layers are unrolled — its flops are added from the closed
    # form (6 * d * head_dim flops per token per layer, x4 for fwd+bwd+
    # recompute in training, x1 in inference).
    if cfg.family == "ssm":
        shape = INPUT_SHAPES[shape_name]
        dp = max(1, int(np.prod([mesh.shape[a] for a in ("pod", "data")
                                 if a in mesh.shape])))
        if shape.kind == "decode":
            toks_per_dev = shape.global_batch / min(dp, shape.global_batch)
        else:
            toks_per_dev = shape.global_batch * shape.seq_len / dp
        factor = 4.0 if shape.kind == "train" else 1.0
        wkv = (6.0 * cfg.d_model * cfg.rwkv_head_dim * toks_per_dev
               * cfg.n_layers * factor)
        total["flops"] += wkv
        total["wkv_analytic_flops"] = wkv
    return total, [dict(v) for v in vecs]


def run_one(arch: str, shape_name: str, multi_pod: bool,
            exec_overrides=None, rule_overrides=None, variant="full",
            probes: bool = True, cfg_patch=None) -> dict:
    t0 = time.time()
    mesh = mesh_mod.make_production_mesh(multi_pod=multi_pod)
    n_chips = int(np.prod(list(mesh.shape.values())))
    rec = {"arch": arch, "shape": shape_name,
           "mesh": "2x16x16" if multi_pod else "16x16", "chips": n_chips,
           "status": "ok", "cfg_patch": cfg_patch or {}}
    cfg_override = None
    if cfg_patch:
        cfg_override = get_config(arch, variant).replace(**cfg_patch)
    try:
        built = build(arch, shape_name, mesh, variant=variant,
                      exec_overrides=exec_overrides,
                      rule_overrides=rule_overrides,
                      cfg_override=cfg_override)
    except SkipCombo as e:
        rec.update(status="skip", reason=str(e))
        return rec
    with mesh:
        jitted = jax.jit(built.fn, in_shardings=built.in_shardings,
                         out_shardings=built.out_shardings)
        lowered = jitted.lower(*built.args)
        compiled = lowered.compile()
        ma = compiled.memory_analysis()
        hlo = compiled.as_text()
    rec.update(
        meta=built.meta,
        memory={
            "argument_bytes": ma.argument_size_in_bytes,
            "output_bytes": ma.output_size_in_bytes,
            "temp_bytes": ma.temp_size_in_bytes,
            "alias_bytes": ma.alias_size_in_bytes,
            "host_argument_bytes": ma.host_argument_size_in_bytes,
            "host_temp_bytes": ma.host_temp_size_in_bytes,
            "host_output_bytes": ma.host_output_size_in_bytes,
        },
        collectives_scanned=parse_collective_bytes(hlo),
        hlo_bytes=len(hlo),
    )
    if probes:
        total, probe_vecs = probe_costs(arch, shape_name, mesh, variant,
                                        exec_overrides, rule_overrides,
                                        cfg_patch=cfg_patch)
        coll = {c: {"count": int(total.get(f"coll_count::{c}", 0)),
                    "bytes": int(total.get(f"coll_bytes::{c}", 0))}
                for c in COLLECTIVES}
        rec.update(
            cost={"flops": total["flops"],
                  "bytes_accessed": total["bytes_accessed"]},
            collectives=coll,
            probe_vectors=probe_vecs,
        )
    rec["compile_s"] = round(time.time() - t0, 1)
    return rec


def roofline_terms(rec: dict, cfg, shape) -> dict:
    """Per-device cost_analysis numbers -> the three roofline terms (s).

    Convention: compiled per-device HLO FLOPs/bytes ARE already the
    per-chip share, so term = per_device_quantity / per_chip_rate (equal to
    the spec's total/(chips*rate))."""
    peak = mesh_mod.PEAK_FLOPS_BF16
    hbm = mesh_mod.HBM_BW
    ici = mesh_mod.ICI_BW
    flops = rec["cost"]["flops"]
    byts = rec["cost"]["bytes_accessed"]
    cbytes = sum(v["bytes"] for v in rec["collectives"].values())
    compute_t = flops / peak
    memory_t = byts / hbm
    coll_t = cbytes / ici
    dom = max(("compute", compute_t), ("memory", memory_t),
              ("collective", coll_t), key=lambda kv: kv[1])[0]
    # model flops (useful work)
    n_active = cfg.param_count(active_only=True)
    chips = rec["chips"]
    if shape.kind == "train":
        D = shape.seq_len * shape.global_batch
        model_flops = 6 * n_active * D
    elif shape.kind == "prefill":
        D = shape.seq_len * shape.global_batch
        model_flops = 2 * n_active * D
    else:
        model_flops = 2 * n_active * shape.global_batch
    useful = model_flops / chips / max(flops, 1.0)
    return {"compute_s": compute_t, "memory_s": memory_t,
            "collective_s": coll_t, "collective_bytes_per_dev": cbytes,
            "dominant": dom, "model_flops_total": model_flops,
            "useful_flops_ratio": useful}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", default="single", choices=["single", "multi",
                                                         "both"])
    ap.add_argument("--variant", default="full")
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--tag", default="baseline")
    ap.add_argument("--optimized", action="store_true",
                    help="apply the §Perf beyond-paper fixes (grouped GQA "
                         "decode + local MoE dispatch) on top of the "
                         "paper-faithful schedule")
    ap.add_argument("--prefetch", type=int, default=None,
                    help="override ExecutionConfig.prefetch_depth (the "
                         "build default is 1: double-buffered EPS relay): "
                         "0 compiles the serialized fetch-in-iteration "
                         "schedule, k >= 1 a k-deep prefetch ring — for "
                         "A/B HLO comparison across depths")
    ap.add_argument("--group", type=int, default=None,
                    help="override ExecutionConfig.layers_per_relay "
                         "(build default 1): relay G stacked layers per "
                         "stop — one DMA per stop covers the group; the "
                         "device weight footprint grows to G*(1+prefetch) "
                         "layer slots while the stop count drops to "
                         "ceil(N/G)")
    ap.add_argument("--pack", type=int, default=None, choices=[0, 1],
                    help="override ExecutionConfig.pack_params (build "
                         "default 0): 1 compiles the packed flat-buffer "
                         "relay — one host<->HBM copy per relay stop per "
                         "direction — for A/B HLO comparison")
    ap.add_argument("--stash-every", type=int, default=None,
                    help="override ExecutionConfig.stash_every (build "
                         "default 1): K > 1 compiles the constant-memory "
                         "stash — only every K-th layer boundary is "
                         "checkpointed (ceil(N/K) stashed) and the "
                         "reverse relay recomputes the rest by "
                         "re-streaming each K-segment forward — for A/B "
                         "host/device byte comparison")
    ap.add_argument("--transport", default=None,
                    choices=["xla", "pallas"],
                    help="override ExecutionConfig.transport (build "
                         "default 'xla'): 'pallas' lowers every relay "
                         "slot move through the double-buffered "
                         "make_async_copy DMA pipeline "
                         "(kernels/relay_copy) instead of scan-boundary "
                         "device_puts — for A/B of the emitted "
                         "copy/compute overlap structure")
    ap.add_argument("--n-layers", type=int, default=None,
                    help="override the arch's depth (layers of the main/"
                         "decoder group) — for depth sweeps of the "
                         "compiled program; the tag gains a -nN suffix "
                         "so sweep records never collide")
    ap.add_argument("--dynamic-depth", type=int, default=None,
                    choices=[0, 1],
                    help="override ExecutionConfig.dynamic_depth (build "
                         "default 0): 1 compiles the runtime-depth "
                         "program — the step takes a traced n_layers "
                         "operand and ONE compile serves every depth <= "
                         "capacity (single-group archs; tag suffix -dyn)")
    ap.add_argument("--segment-scan", type=int, default=None,
                    choices=[0, 1],
                    help="override ExecutionConfig.segment_scan (build "
                         "default 1): 0 compiles the historical unrolled "
                         "per-segment program (~3*ceil(N/K) relay "
                         "instances) for compile-time A/Bs against the "
                         "O(1)-in-depth segment-scan driver")
    ap.add_argument("--tiers", type=int, default=None, choices=[2, 3],
                    help="override ExecutionConfig.tiers (build default "
                         "2): 3 enables the storage-tier EPS — the cold "
                         "stacked-state tail lives in the on-disk "
                         "SegmentStore and is staged around each jitted "
                         "call.  The compiled program is identical (the "
                         "disk tier sits OUTSIDE jit); the A/B is over "
                         "the recorded exec metadata + the memory "
                         "model's host/disk byte split")
    args = ap.parse_args()
    cfg_patch = dict({"grouped_decode_attn": True, "moe_ep_constraint": True}
                     if args.optimized else {})
    if args.n_layers is not None:
        cfg_patch["n_layers"] = args.n_layers
    cfg_patch = cfg_patch or None
    exec_overrides = {}
    if args.prefetch is not None:
        exec_overrides["prefetch_depth"] = args.prefetch
    if args.group is not None:
        exec_overrides["layers_per_relay"] = args.group
    if args.pack is not None:
        exec_overrides["pack_params"] = bool(args.pack)
    if args.stash_every is not None:
        exec_overrides["stash_every"] = args.stash_every
    if args.tiers is not None:
        exec_overrides["tiers"] = args.tiers
    if args.transport is not None:
        exec_overrides["transport"] = args.transport
    if args.dynamic_depth is not None:
        exec_overrides["dynamic_depth"] = bool(args.dynamic_depth)
    if args.segment_scan is not None:
        exec_overrides["segment_scan"] = bool(args.segment_scan)
    exec_overrides = exec_overrides or None
    if args.optimized and args.tag == "baseline":
        args.tag = "optimized"
    # compose the knob values into the tag (with --optimized / custom
    # tags) so no A/B sweep ever overwrites another's records under the
    # same directory: every non-default multi-valued knob is spelled out
    if args.prefetch == 0:
        args.tag += "-noprefetch"
    elif args.prefetch is not None and args.prefetch != 1:
        args.tag += f"-pf{args.prefetch}"
    if args.group is not None and args.group != 1:
        args.tag += f"-g{args.group}"
    if args.pack == 1:
        args.tag += "-packed"
    if args.stash_every is not None and args.stash_every != 1:
        args.tag += f"-s{args.stash_every}"
    if args.tiers is not None and args.tiers != 2:
        args.tag += f"-t{args.tiers}"
    if args.transport == "pallas":
        args.tag += "-xcopy"
    # depth sweeps and dynamic-depth / unrolled-program A/Bs get their own
    # record directories too — two runs differing only in depth (or only
    # in the program driver) must never overwrite each other
    if args.n_layers is not None:
        args.tag += f"-n{args.n_layers}"
    if args.dynamic_depth == 1:
        args.tag += "-dyn"
    if args.segment_scan == 0:
        args.tag += "-unrolled"

    archs = list_archs() if args.arch == "all" else args.arch.split(",")
    archs = [a for a in archs if a != "bert-large"]
    shapes = (list(INPUT_SHAPES) if args.shape == "all"
              else args.shape.split(","))
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]

    failures = []
    for multi in meshes:
        mdir = os.path.join(args.out, args.tag,
                            "multi" if multi else "single")
        os.makedirs(mdir, exist_ok=True)
        for arch in archs:
            for shape_name in shapes:
                out_path = os.path.join(mdir, f"{arch}__{shape_name}.json")
                try:
                    rec = run_one(arch, shape_name, multi,
                                  variant=args.variant,
                                  exec_overrides=exec_overrides,
                                  cfg_patch=cfg_patch)
                    if rec["status"] == "ok":
                        cfg = get_config(arch, args.variant)
                        rec["roofline"] = roofline_terms(
                            rec, cfg, INPUT_SHAPES[shape_name])
                except Exception as e:   # noqa: BLE001 — report, keep going
                    rec = {"arch": arch, "shape": shape_name,
                           "status": "error", "error": repr(e),
                           "trace": traceback.format_exc()[-2000:]}
                    failures.append((arch, shape_name, repr(e)))
                with open(out_path, "w") as f:
                    json.dump(rec, f, indent=1)
                status = rec["status"]
                extra = ""
                if status == "ok":
                    r = rec.get("roofline", {})
                    extra = (f" compile={rec['compile_s']}s "
                             f"dom={r.get('dominant','?')}")
                print(f"[{'multi' if multi else 'single'}] "
                      f"{arch} x {shape_name}: {status}{extra}", flush=True)
    if failures:
        print(f"\n{len(failures)} FAILURES:")
        for a, s, e in failures:
            print(f"  {a} x {s}: {e}")
        raise SystemExit(1)
    print("\nALL DRY-RUNS OK")


if __name__ == "__main__":
    main()
