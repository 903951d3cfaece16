"""Perf hillclimb runner (§Perf): re-lowers the chosen (arch x shape)
pairs with candidate optimizations and reports before/after roofline
terms.  Results land in experiments/dryrun/perf/ and EXPERIMENTS.md.

    PYTHONPATH=src python -m repro.launch.perf [--pair NAME] [--multi]
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

from repro.configs.base import INPUT_SHAPES, get_config
from repro.launch.dryrun import roofline_terms, run_one

# (name, arch, shape, variants) — each variant is (label, kwargs for run_one)
EXPERIMENTS = [
    ("moe_ep", "deepseek-v2-lite-16b", "train_4k", [
        ("opt1_ep_constraint", {"cfg_patch": {"moe_ep_constraint": True}}),
        ("opt2_ep_cap_1_0", {"cfg_patch": {
            "moe_ep_constraint": True, "capacity_factor": 1.0}}),
    ]),
    ("moe_tp_grok", "grok-1-314b", "train_4k", [
        ("opt1_tp_constraint", {"cfg_patch": {"moe_ep_constraint": True}}),
        ("opt2_tp_cap_1_0", {"cfg_patch": {
            "moe_ep_constraint": True, "capacity_factor": 1.0}}),
    ]),
    ("gqa_decode", "granite-3-8b", "decode_32k", [
        ("opt1_grouped_attn", {"cfg_patch": {"grouped_decode_attn": True}}),
        ("opt2_grouped_attn_batchseq", {
            "cfg_patch": {"grouped_decode_attn": True},
            "rule_overrides": {"kv": None}}),
    ]),
    ("dense_train", "command-r-35b", "train_4k", [
        ("opt1_fullchunk", {"cfg_patch": {"attn_chunk": 0}}),
        ("opt2_chunk2048", {"cfg_patch": {"attn_chunk": 2048}}),
        ("opt3_chunk128", {"cfg_patch": {"attn_chunk": 128}}),
    ]),
]


def summarize(rec):
    if rec.get("status") != "ok":
        return rec.get("status", "?")
    r = rec["roofline"]
    return (f"compute={r['compute_s']*1e3:9.1f}ms "
            f"memory={r['memory_s']*1e3:9.1f}ms "
            f"collective={r['collective_s']*1e3:9.2f}ms "
            f"dom={r['dominant']:10s} useful={r['useful_flops_ratio']*100:5.1f}%")


def run_variant(arch, shape_name, multi, out_dir, label, kw):
    rec = run_one(arch, shape_name, multi, variant="full",
                  exec_overrides=kw.get("exec_overrides"),
                  rule_overrides=kw.get("rule_overrides"),
                  cfg_patch=kw.get("cfg_patch"))
    if rec["status"] == "ok":
        cfg = get_config(arch)
        if kw.get("cfg_patch"):
            cfg = cfg.replace(**kw["cfg_patch"])
        rec["roofline"] = roofline_terms(rec, cfg, INPUT_SHAPES[shape_name])
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, f"{arch}__{shape_name}__{label}.json"),
              "w") as f:
        json.dump(rec, f, indent=1)
    print(f"  {label:28s} {summarize(rec)}", flush=True)
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--pair", default="all")
    ap.add_argument("--multi", action="store_true")
    ap.add_argument("--out", default="experiments/dryrun/perf")
    args = ap.parse_args()

    for name, arch, shape_name, variants in EXPERIMENTS:
        if args.pair != "all" and args.pair != name:
            continue
        print(f"\n== {name}: {arch} x {shape_name} "
              f"({'multi' if args.multi else 'single'} pod)")
        run_variant(arch, shape_name, args.multi, args.out,
                    "baseline", {})
        for label, kw in variants:
            run_variant(arch, shape_name, args.multi, args.out, label, kw)


if __name__ == "__main__":
    main()
