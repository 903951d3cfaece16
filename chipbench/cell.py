"""Finding a cell's pieces by name.

``BENCHMARK.json`` (at the root of the checkout) lists the cells and the
metrics.  Everything that belongs to one cell, configuration, traffic mix
or per-layer metric sits in a file of its own under ``chipbench/``:

* ``workloads/<cell>.json``: its configuration, traffic, why, and the
  limits of its correctness check;
* ``configs/<config>.json``: the configuration as run, with its source,
  the block family it is built from (``"reference"``: a module under
  ``chipbench/``, such as ``reference/decoder.py``) and its CPU test
  sizes (``"smoke"``);
* ``traffic/<traffic>.json``: batch, sequence, batches cycled, documents;
* ``metrics/<metric>.py``: the reader of one per-layer metric.

So a later change adds a cell, a configuration, a block family or a
metric by adding files and entries, without editing any file here.
"""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent


def _json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load(name: str) -> dict:
    bench = _json(ROOT / "BENCHMARK.json")
    entry = {w["name"]: w for w in bench["workloads"]}.get(name)
    if entry is None:
        raise SystemExit(f"chipbench: no workload {name!r} in BENCHMARK.json")
    wl = _json(HERE / "workloads" / f"{name}.json")
    if (wl["config"], wl["traffic"]) != (entry["config"], entry["traffic"]):
        raise SystemExit(f"chipbench: workloads/{name}.json and "
                         f"BENCHMARK.json name different pieces")

    return {
        "name": name, "chips": entry["chips"], "workload": wl,
        "config": _json(HERE / "configs" / f"{wl['config']}.json"),
        "traffic": _json(HERE / "traffic" / f"{wl['traffic']}.json"),
        "end_to_end": bench["end_to_end"],
        "per_layer": bench["per_layer"],
    }


def _module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_reader(name: str):
    """The ``read`` function of ``metrics/<name>.py``."""
    return _module(HERE / "metrics" / f"{name}.py",
                   f"chipbench_metric_{name}").read


def family(conf: dict):
    """The block family module that configuration file ``conf`` names
    (``"reference"``, a path under ``chipbench/``): see
    ``reference/model.py`` for what it gives."""
    path = HERE / conf["reference"]
    return _module(path, f"chipbench_family_{path.stem}")
