"""The decoder block family of the plain float32 reference.

One stacked layer group of pre-norm decoder blocks, in two forms chosen
by the configuration file's ``norm`` and ``hidden_act``:

* ``layernorm`` blocks (BERT-Large as run): pre-LN, biased q/k/v/o
  projections, RoPE, causal softmax attention, the tanh GELU MLP with
  biases (Hendrycks & Gimpel 2016; the original BERT code's form);
* ``rmsnorm`` blocks (Granite-3.0 as run): pre-RMSNorm, grouped-query
  attention with RoPE (theta from the file), the SwiGLU MLP (Shazeer 2020).

No auxiliary loss.  FLOPs follow ``chipbench/flops.py``'s convention.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from chipbench.reference import model as M

# what the configuration file says, against the program's field for it
PROGRAM_FIELDS = {
    "hidden_size": "d_model", "num_hidden_layers": "n_layers",
    "num_attention_heads": "n_heads", "num_key_value_heads": "n_kv_heads",
    "head_dim": "d_head", "intermediate_size": "d_ff",
    "vocab_size": "vocab_size", "rope_theta": "rope_theta",
    "tie_word_embeddings": "tie_embeddings", "attention_bias": "qkv_bias",
    "compute_dtype": "dtype", "param_dtype": "param_dtype",
}


def program_rules(c: dict, cfg) -> list:
    """The norm and activation, which the program names its own way:
    [(key, file's value, program's value)] for each that departs."""
    bad = []
    eps = c.get("layer_norm_eps", c.get("rms_norm_eps"))
    if eps != cfg.norm_eps or c["norm"] != cfg.norm_type:
        bad.append(("norm", (c["norm"], eps), (cfg.norm_type, cfg.norm_eps)))
    if (c["hidden_act"] == "silu") != cfg.gated_mlp:
        bad.append(("hidden_act", c["hidden_act"], cfg.act))
    return bad


# ---------------------------------------------------------------------------
# parameters
# ---------------------------------------------------------------------------
def layer_shapes(c: dict) -> dict:
    """One layer's parameters: name -> (shape, init)."""
    d, h = c["hidden_size"], c["num_attention_heads"]
    kv, dh, ff = c["num_key_value_heads"], c["head_dim"], c["intermediate_size"]
    ln = ({"scale": ((d,), "ones"), "bias": ((d,), "zeros")}
          if c["norm"] == "layernorm" else {"scale": ((d,), "ones")})
    attn = {"wq": ((d, h, dh), "normal"), "wk": ((d, kv, dh), "normal"),
            "wv": ((d, kv, dh), "normal"), "wo": ((h, dh, d), "normal")}
    if c["attention_bias"]:
        attn.update(bq=((h, dh), "zeros"), bk=((kv, dh), "zeros"),
                    bv=((kv, dh), "zeros"), bo=((d,), "zeros"))
    if c["hidden_act"] == "silu":
        mlp = {"w_gate": ((d, ff), "normal"), "w_in": ((d, ff), "normal"),
               "w_out": ((ff, d), "normal")}
    else:
        mlp = {"w_in": ((d, ff), "normal"), "b_in": ((ff,), "zeros"),
               "w_out": ((ff, d), "normal"), "b_out": ((d,), "zeros")}
    return {"attn": attn, "ln1": dict(ln), "ln2": dict(ln), "mlp": mlp}


def param_tree(c: dict) -> dict:
    """Every stored array as (shape, init), nested as the program stores
    them: one layer group whose arrays are stacked over a leading layer
    axis, the embedding, and the head."""
    n, d = c["num_hidden_layers"], c["hidden_size"]
    ln_f = {"scale": ((d,), "ones")}
    if c["norm"] == "layernorm":
        ln_f["bias"] = ((d,), "zeros")
    head = {"ln_f": ln_f}
    if not c["tie_word_embeddings"]:
        head["out"] = ((d, c["vocab_size"]), "normal")
    layers = {k: {n_: ((n,) + s, i) for n_, (s, i) in v.items()}
              for k, v in layer_shapes(c).items()}
    return {"embed": {"tok": ((c["vocab_size"], d), "normal")},
            "groups": (layers,), "head": head}


# ---------------------------------------------------------------------------
# the block
# ---------------------------------------------------------------------------
def gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(math.sqrt(2.0 / math.pi)
                                     * (x + 0.044715 * x ** 3)))


def layer(c, ar: M.Arith, w, x, q_block: int, group: int):
    """One pre-norm decoder block: x (b, s, d) -> (x, 0.0)."""
    a_w, m_w = w["attn"], w["mlp"]
    h = M.norm(c, w["ln1"], x)
    q = ar.einsum("bsd,dhe->bshe", h, a_w["wq"])
    k = ar.einsum("bsd,dhe->bshe", h, a_w["wk"])
    v = ar.einsum("bsd,dhe->bshe", h, a_w["wv"])
    if c["attention_bias"]:
        q, k, v = q + a_w["bq"], k + a_w["bk"], v + a_w["bv"]
    q, k = M.rope(q, c["rope_theta"]), M.rope(k, c["rope_theta"])
    o = ar.einsum("bshe,hed->bsd", M.attention(c, ar, q, k, v, q_block),
                  a_w["wo"])
    x = x + (o + a_w["bo"] if c["attention_bias"] else o)
    h = M.norm(c, w["ln2"], x)
    no_aux = jnp.float32(0.0)
    if c["hidden_act"] == "silu":
        u = jax.nn.silu(ar.einsum("bsd,df->bsf", h, m_w["w_gate"])) \
            * ar.einsum("bsd,df->bsf", h, m_w["w_in"])
        return x + ar.einsum("bsf,fd->bsd", u, m_w["w_out"]), no_aux
    u = gelu_tanh(ar.einsum("bsd,df->bsf", h, m_w["w_in"]) + m_w["b_in"])
    return (x + ar.einsum("bsf,fd->bsd", u, m_w["w_out"]) + m_w["b_out"],
            no_aux)


# ---------------------------------------------------------------------------
# FLOPs (PaLM's convention, chipbench/flops.py)
# ---------------------------------------------------------------------------
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
