"""Readings that the limits of a cell's check are set from, on the chip.

    python3 chipbench/calibrate.py --workload <cell> --seeds 1,2,...  \
        [--control-seeds 3] [--out <file.jsonl>]

In one process, for each seed: the program's first steps as a run takes
them (``run.first_steps``) and the float32 reference, giving the gaps of a
sound run.  On the first ``--control-seeds`` seeds also:

* ``control``: the reference put in the program's place, computed in
  scaled fp8 (``reference.model.Arith``), the precision below the
  configuration's bfloat16;
* ``half_batch``: the reference put in the program's place with half of
  each batch left out and the mean taken over the rest.

A state left unchanged reads 1 on ``grad_gap`` and ``change_gap`` by the
definition of those gaps, and needs no run.  One JSON line per seed and
reading goes to standard output and to ``--out``.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from chipbench import cell as cells  # noqa: E402
from chipbench import check, run as runner  # noqa: E402
from chipbench.reference import train as reference  # noqa: E402


def half(b: dict) -> dict:
    n = b["tokens"].shape[0] // 2
    return {k: v[:n] for k, v in b.items()}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--out")
    a = ap.parse_args(argv)
    c = cells.load(a.workload)
    dev = runner.check_device(c["chips"])
    runner.describe_host(dev)
    runner.enable_compile_cache()
    conf, traffic = c["config"], c["traffic"]
    cc, opt = conf["config"], conf["optimizer"]
    B, S = traffic["batch"], traffic["seq"]
    refs = {"reference": reference.Reference(cc, opt, B, S),
            "control": reference.Reference(cc, opt, B, S, "fp8"),
            "half_batch": reference.Reference(cc, opt, B // 2, S)}
    sink = open(a.out, "a") if a.out else None
    for n, seed in enumerate(int(x) for x in a.seeds.split(",")):
        t = time.perf_counter()
        s = runner.first_steps(c, seed)
        del s["eng"], s["state"], s["batches"]
        gc.collect()
        t_prog = time.perf_counter() - t
        found = {}
        for name, ref in refs.items():
            if name != "reference" and n >= a.control_seeds:
                continue
            t = time.perf_counter()
            batches = (s["checked"] if name != "half_batch"
                       else [half(b) for b in s["checked"]])
            found[name] = reference.train(cc, opt, s["key"], batches,
                                          ref=ref)
            found[name]["seconds"] = time.perf_counter() - t
        want = found["reference"]
        rows = [{"reading": "program", "gaps": check.gaps(s["got"], want),
                 "losses": s["got"]["losses"], "seconds": t_prog}]
        rows += [{"reading": k, "gaps": check.gaps(v, want),
                  "losses": v["losses"], "seconds": v["seconds"]}
                 for k, v in found.items() if k != "reference"]
        rows.append({"reading": "reference", "losses": want["losses"],
                     "seconds": want["seconds"]})
        for r in rows:
            line = json.dumps({"workload": a.workload, "seed": seed, **r})
            print(line, flush=True)
            if sink:
                sink.write(line + "\n")
                sink.flush()
    if sink:
        sink.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
