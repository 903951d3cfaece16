"""CPU tests of the benchmark's yardstick, at smoke size:

    PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest -q chipbench/tests
"""
import copy
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
for p in (ROOT, ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

# the program's smoke presets (configs/*.py ``smoke()``), in the file's keys
SMOKE = {
    "bert-large.b32-s512": dict(
        hidden_size=128, num_hidden_layers=2, num_attention_heads=4,
        num_key_value_heads=4, head_dim=32, intermediate_size=256,
        vocab_size=512),
    "granite-3-8b-l4.b4-s4096": dict(
        hidden_size=256, num_hidden_layers=2, num_attention_heads=8,
        num_key_value_heads=2, head_dim=32, intermediate_size=512,
        vocab_size=512, attention_multiplier=32 ** -0.5),
}


def smoke_cell(name: str) -> dict:
    """The cell ``name`` as ``cell.load`` gives it, cut to the program's
    smoke preset and a batch of 4 x 64 tokens."""
    from chipbench import cell
    c = copy.deepcopy(cell.load(name))
    conf = c["config"]
    conf["program"] = dict(conf["program"], variant="smoke", replace={})
    conf["config"].update(SMOKE[name])
    c["traffic"] = dict(c["traffic"], batch=4, seq=64, doc_len=[16, 64])
    return c


@pytest.fixture(params=sorted(SMOKE))
def cell_name(request):
    return request.param
