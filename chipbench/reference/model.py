"""Plain float32 reference of the decoder blocks the benchmark trains.

Written from the published equations and from the configuration file's
keys (Hugging Face names), in straightforward ``jax.numpy``; it imports
nothing of the program under test.  One function per block family:

* ``layernorm`` blocks (BERT-Large as run): pre-LN, biased q/k/v/o
  projections, RoPE, causal softmax attention, the tanh GELU MLP with
  biases (Hendrycks & Gimpel 2016; the original BERT code's form);
* ``rmsnorm`` blocks (Granite-3.0 as run): pre-RMSNorm, grouped-query
  attention with RoPE (theta from the file), the SwiGLU MLP (Shazeer 2020).

RoPE rotates each pair of dims (2i, 2i+1) by position * theta^(-2i/d_head)
(Su et al., RoFormer).  Every contraction goes through one ``Arith``, so
the same code computes in float32 (``highest`` matmul precision, the
reference) or in scaled fp8 (the control).  The arrays are named and
stacked as the program stores them, so that the benchmark can make the
weights once, here, for both.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

E4M3_MAX = float(jnp.finfo(jnp.float8_e4m3fn).max)
E5M2_MAX = float(jnp.finfo(jnp.float8_e5m2).max)


def _fp8_round(x, dtype, top):
    """x rounded to a per-tensor scaled fp8 grid (amax maps to ``top``)."""
    amax = jnp.max(jnp.abs(x))
    s = jnp.where(amax > 0, amax / top, 1.0)
    return ((x / s).astype(dtype).astype(jnp.float32)) * s


@jax.custom_vjp
def _fwd_fp8(x):
    return _fp8_round(x, jnp.float8_e4m3fn, E4M3_MAX)


_fwd_fp8.defvjp(lambda x: (_fwd_fp8(x), None), lambda _, g: (g,))


@jax.custom_vjp
def _bwd_fp8(y):
    return y


_bwd_fp8.defvjp(lambda y: (y, None),
                lambda _, g: (_fp8_round(g, jnp.float8_e5m2, E5M2_MAX),))


class Arith:
    """How the reference contracts: ``float32`` at ``highest`` precision,
    or ``fp8``: operands rounded to scaled e4m3 in the forward pass and
    the incoming gradients to scaled e5m2 in the backward pass, with
    float32 accumulation (the usual fp8 training recipe)."""

    def __init__(self, mode: str = "float32"):
        if mode not in ("float32", "fp8"):
            raise ValueError(f"unknown arithmetic {mode!r}")
        self.mode = mode

    def einsum(self, spec, a, b):
        hi = jax.lax.Precision.HIGHEST
        if self.mode == "float32":
            return jnp.einsum(spec, a, b, precision=hi)
        return _bwd_fp8(jnp.einsum(spec, _fwd_fp8(a), _fwd_fp8(b),
                                   precision=hi))


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


def _is_spec(x) -> bool:
    return isinstance(x, tuple) and len(x) == 2 and isinstance(x[1], str)


def init_params(c: dict, key) -> dict:
    """The run's weights, from its key: N(0, initializer_range) for every
    weight array and the embedding, zeros for biases, ones for norm
    scales, one key per array (split in the tree's sorted order).  Jit
    it to make them on the device in one call."""
    leaves, treedef = jax.tree.flatten(param_tree(c), is_leaf=_is_spec)
    keys = jax.random.split(key, len(leaves))
    std = c["initializer_range"]

    def leaf(k, spec):
        shape, init = spec
        if init == "zeros":
            return jnp.zeros(shape, jnp.float32)
        if init == "ones":
            return jnp.ones(shape, jnp.float32)
        return jax.random.normal(k, shape, jnp.float32) * std

    return jax.tree.unflatten(treedef, [leaf(k, s) for k, s in
                                        zip(keys, leaves)])


# ---------------------------------------------------------------------------
# blocks
# ---------------------------------------------------------------------------
def norm(c, w, x):
    if c["norm"] == "layernorm":
        mu = x.mean(-1, keepdims=True)
        var = ((x - mu) ** 2).mean(-1, keepdims=True)
        return (x - mu) / jnp.sqrt(var + c["layer_norm_eps"]) * w["scale"] \
            + w["bias"]
    ms = (x * x).mean(-1, keepdims=True)
    return x / jnp.sqrt(ms + c["rms_norm_eps"]) * w["scale"]


def rope(x, theta):
    """x: (b, s, heads, dh); rotates pairs (2i, 2i+1)."""
    s, dh = x.shape[1], x.shape[-1]
    inv = theta ** (-jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv      # (s, dh/2)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                     axis=-1).reshape(x.shape)


def gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(math.sqrt(2.0 / math.pi)
                                     * (x + 0.044715 * x ** 3)))


def attention(c, ar: Arith, q, k, v, q_block: int):
    """Causal softmax attention, exact, one block of queries at a time so
    that the (queries x keys) scores of a long sequence fit."""
    b, s, h, dh = q.shape
    rep = h // k.shape[2]
    k, v = jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)
    scale = c.get("attention_multiplier", 1.0 / math.sqrt(dh))
    nb = s // q_block
    key_pos = jnp.arange(s)

    @jax.checkpoint
    def block(args):
        qi, i = args
        sc = ar.einsum("bqhd,bkhd->bhqk", qi * scale, k)
        q_pos = i * q_block + jnp.arange(q_block)
        sc = jnp.where(key_pos[None, :] <= q_pos[:, None], sc, -jnp.inf)
        return ar.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(sc, axis=-1), v)

    qs = q.reshape(b, nb, q_block, h, dh).swapaxes(0, 1)
    o = jax.lax.map(block, (qs, jnp.arange(nb)))
    return o.swapaxes(0, 1).reshape(b, s, h, dh)


def layer(c, ar: Arith, w, x, q_block: int):
    """One pre-norm decoder block: x (b, s, d) -> (b, s, d)."""
    a_w, m_w = w["attn"], w["mlp"]
    h = norm(c, w["ln1"], x)
    q = ar.einsum("bsd,dhe->bshe", h, a_w["wq"])
    k = ar.einsum("bsd,dhe->bshe", h, a_w["wk"])
    v = ar.einsum("bsd,dhe->bshe", h, a_w["wv"])
    if c["attention_bias"]:
        q, k, v = q + a_w["bq"], k + a_w["bk"], v + a_w["bv"]
    q, k = rope(q, c["rope_theta"]), rope(k, c["rope_theta"])
    o = ar.einsum("bshe,hed->bsd", attention(c, ar, q, k, v, q_block),
                  a_w["wo"])
    x = x + (o + a_w["bo"] if c["attention_bias"] else o)
    h = norm(c, w["ln2"], x)
    if c["hidden_act"] == "silu":
        u = jax.nn.silu(ar.einsum("bsd,df->bsf", h, m_w["w_gate"])) \
            * ar.einsum("bsd,df->bsf", h, m_w["w_in"])
        return x + ar.einsum("bsf,fd->bsd", u, m_w["w_out"])
    u = gelu_tanh(ar.einsum("bsd,df->bsf", h, m_w["w_in"]) + m_w["b_in"])
    return x + ar.einsum("bsf,fd->bsd", u, m_w["w_out"]) + m_w["b_out"]


def head_loss_sum(c, ar: Arith, static, x, targets, mask):
    """Summed masked next-token cross-entropy of x (t, d)."""
    h = norm(c, static["head"]["ln_f"], x)
    w = (static["embed"]["tok"].T if c["tie_word_embeddings"]
         else static["head"]["out"])
    logits = ar.einsum("td,dv->tv", h, w)
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, targets[:, None], axis=-1)[:, 0]
    return jnp.sum((lse - gold) * mask)
