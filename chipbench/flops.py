"""Model FLOPs of one trained token, from a configuration's shapes.

The PaLM convention (Chowdhery et al. 2022, appendix B): 6 FLOPs per
matmul parameter per token for the forward and backward passes (the
layers' projections and the output head; the embedding gather is no
matmul), plus 12 * layers * seq * heads * head_dim for attention's score
and value products.  Recomputed work does not count, so a schedule that
recomputes layers does not raise its utilization by doing so.

The keys are the configuration file's, in Hugging Face's names.
"""
from __future__ import annotations


def layer_matmul_params(c: dict) -> int:
    d, h = c["hidden_size"], c["num_attention_heads"]
    kv = c.get("num_key_value_heads", h)
    dh = c.get("head_dim", d // h)
    attn = 2 * d * h * dh + 2 * d * kv * dh          # q, o and k, v
    n_mats = 3 if c["hidden_act"] == "silu" else 2   # gated (SwiGLU) or not
    return attn + n_mats * d * c["intermediate_size"]


def matmul_params(c: dict) -> int:
    return (c["num_hidden_layers"] * layer_matmul_params(c)
            + c["hidden_size"] * c["vocab_size"])


def flops_per_token(c: dict, seq: int) -> float:
    h = c["num_attention_heads"]
    dh = c.get("head_dim", c["hidden_size"] // h)
    return 6.0 * matmul_params(c) + 12.0 * c["num_hidden_layers"] * seq * h * dh
