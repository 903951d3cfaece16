"""Model FLOPs of one trained token, from a configuration's shapes.

The PaLM convention (Chowdhery et al. 2022, appendix B): 6 FLOPs per
matmul parameter per token for the forward and backward passes (the
layers' projections and the output head; the embedding gather is no
matmul), plus 12 * layers * seq * heads * head_dim for attention's score
and value products.  Recomputed work does not count, so a schedule that
recomputes layers does not raise its utilization by doing so.  Where a
token uses only some of a layer's weights (routed experts), only those
count.

Each block family computes its own (``flops_per_token(c, seq)`` of the
module that the configuration file's ``"reference"`` key names), from the
file's keys in Hugging Face's names.
"""
from __future__ import annotations

from chipbench import cell


def flops_per_token(conf: dict, seq: int) -> float:
    """FLOPs per trained token of configuration file ``conf`` at ``seq``."""
    return cell.family(conf).flops_per_token(conf["config"], seq)
