"""What every block family of the plain float32 reference shares.

Written from the published equations and from the configuration file's
keys (Hugging Face names), in straightforward ``jax.numpy``; it imports
nothing of the program under test.  A block family is a module of its
own (``reference/decoder.py``, ...), named by the configuration file's
``"reference"`` key; it gives:

* ``param_tree(c)``: every stored array as ``(shape, init)``, nested as
  the program stores them: ``embed``, ``head`` and ``groups``, a tuple of
  layer groups in the program's order, each stacked over a leading axis
  of its rows;
* ``layer(c, ar, w, x, q_block, group)``: one layer of group ``group``,
  ``x`` (b, s, d) -> ``(x, aux)``, aux a float32 scalar: the layer's
  auxiliary loss over the block's sequences (0.0 where there is none);
* ``flops_per_token(c, seq)``: the model FLOPs one trained token costs;
* ``PROGRAM_FIELDS`` (file key -> the program's field) and
  ``program_rules(c, cfg)``: what ``program.program_config`` checks;
* optionally ``head_loss_sum``, in place of the one here.

Here: ``Arith``, the fp8 rounding, ``init_params`` over any such tree,
RMS and layer norm, RoPE, causal attention and the head's loss.  RoPE
rotates each pair of dims (2i, 2i+1) by position * theta^(-2i/d_head)
(Su et al., RoFormer).  Every contraction goes through one ``Arith``, so
the same code computes in float32 (``highest`` matmul precision, the
reference) or in scaled fp8 (the control).  The arrays are named and
stacked as the program stores them, so that the benchmark can make the
weights once, here, for both.
"""
from __future__ import annotations

import functools
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
def _is_spec(x) -> bool:
    return isinstance(x, tuple) and len(x) == 2 and isinstance(x[1], str)


def init_params(tree: dict, std: float, key) -> dict:
    """The run's weights for a ``param_tree``, from its key: N(0, std) for
    every weight array and the embedding, zeros for biases, ones for norm
    scales, one key per array (split in the tree's sorted order)."""
    leaves, treedef = jax.tree.flatten(tree, is_leaf=_is_spec)
    keys = jax.random.split(key, len(leaves))

    def leaf(k, spec):
        shape, init = spec
        if init == "zeros":
            return jnp.zeros(shape, jnp.float32)
        if init == "ones":
            return jnp.ones(shape, jnp.float32)
        return jax.random.normal(k, shape, jnp.float32) * std

    return jax.tree.unflatten(treedef, [leaf(k, s) for k, s in
                                        zip(keys, leaves)])


def make_params(fam, c: dict, key) -> dict:
    """``init_params`` of family ``fam``'s tree at the file's
    ``initializer_range``, made on the device in one jitted call."""
    return jax.jit(functools.partial(init_params, fam.param_tree(c),
                                     c["initializer_range"]))(key)


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


def attention(c, ar: Arith, q, k, v, q_block: int):
    """Causal softmax attention, exact, one block of queries at a time so
    that the (queries x keys) scores of a long sequence fit.  The values'
    head size may differ from the queries' (as in latent attention)."""
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
    return o.swapaxes(0, 1).reshape(b, s, h, v.shape[-1])


def head_loss_sum(c, ar: Arith, static, x, targets, mask):
    """Summed masked next-token cross-entropy of x (t, d)."""
    h = norm(c, static["head"]["ln_f"], x)
    w = (static["embed"]["tok"].T if c["tie_word_embeddings"]
         else static["head"]["out"])
    logits = ar.einsum("td,dv->tv", h, w)
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, targets[:, None], axis=-1)[:, 0]
    return jnp.sum((lse - gold) * mask)
