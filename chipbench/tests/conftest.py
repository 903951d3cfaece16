"""CPU tests of the benchmark's yardstick, at smoke size:

    PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest -q chipbench/tests
"""
import copy
import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
for p in (ROOT, ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
# each configuration's first cell: the smoke tests run once a configuration
FIRST_CELLS = sorted(
    next(w["name"] for w in BENCH["workloads"] if w["config"] == cf["name"])
    for cf in BENCH["configs"])


def smoke_cell(name: str) -> dict:
    """The cell ``name`` as ``cell.load`` gives it, cut to the program's
    smoke preset (its configuration file's ``"smoke"`` sizes) and a batch
    of 4 x 64 tokens."""
    from chipbench import cell
    c = copy.deepcopy(cell.load(name))
    conf = c["config"]
    conf["program"] = dict(conf["program"], variant="smoke", replace={})
    conf["config"].update(conf["smoke"])
    c["traffic"] = dict(c["traffic"], batch=4, seq=64, doc_len=[16, 64])
    return c


def grads_by_name(tree) -> dict:
    """Norms of a params-shaped tree, named as the reference names its
    readings (``layers.<i>`` through every layer group)."""
    import jax
    import jax.numpy as jnp

    from chipbench import program

    def per_leaf(path, a):
        if path.startswith("groups"):
            return jax.vmap(jnp.linalg.norm)(a)
        return jnp.linalg.norm(a)
    return program._readings(tree, per_leaf)


@pytest.fixture(params=FIRST_CELLS)
def cell_name(request):
    return request.param
