"""Jit'd public wrappers around the Pallas kernels.

The TPU lowering is the target; on any other backend the kernels run in
Pallas interpret mode, the validation harness.  ``interpret_default`` is
the one place that choice is made: the raw kernels take ``interpret``
as a required argument, and these wrappers (and ``relay_copy``) fill it
from here when the caller passes None.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

import functools

from repro.kernels.flash_attention import (flash_attention_bwd_bhsd,
                                           flash_attention_fwd_bhsd)
from repro.kernels.fused_adam import fused_adam_flat
from repro.kernels.rmsnorm import rmsnorm_2d


def interpret_default() -> bool:
    """Interpret mode everywhere but on a TPU backend."""
    return jax.default_backend() != "tpu"


# ---------------------------------------------------------------------------
# Differentiable flash attention (custom VJP: FA-2 recompute backward).
# The L2L engine's per-layer vjp recompute hits this twice per layer per
# microbatch; the recompute backward keeps zero probability blocks in HBM.
# ---------------------------------------------------------------------------
@functools.partial(jax.custom_vjp,
                   nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _fa(q, k, v, causal, window, soft_cap, block_q, block_k, interpret):
    o, _ = flash_attention_fwd_bhsd(
        q, k, v, causal=causal, window=window, soft_cap=soft_cap,
        block_q=block_q, block_k=block_k, interpret=interpret)
    return o


def _fa_fwd(q, k, v, causal, window, soft_cap, block_q, block_k, interpret):
    o, lse = flash_attention_fwd_bhsd(
        q, k, v, causal=causal, window=window, soft_cap=soft_cap,
        block_q=block_q, block_k=block_k, interpret=interpret)
    return o, (q, k, v, o, lse)


def _fa_bwd(causal, window, soft_cap, block_q, block_k, interpret, res, do):
    assert soft_cap == 0.0, "soft-capped attention bwd not supported"
    q, k, v, o, lse = res
    dq, dk, dv = flash_attention_bwd_bhsd(
        q, k, v, o, lse, do, causal=causal, window=window,
        block_q=block_q, block_k=block_k, interpret=interpret)
    return dq, dk, dv


_fa.defvjp(_fa_fwd, _fa_bwd)


def flash_attention(q, k, v, *, causal=True, window=0, soft_cap=0.0,
                    block_q=128, block_k=128, interpret=None):
    """q,k,v: (B,S,H,D) (model layout) -> (B,S,H,D).  Differentiable
    (custom VJP with recompute backward)."""
    interpret = interpret_default() if interpret is None else interpret
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    o = _fa(qt, kt, vt, causal, window, soft_cap, block_q, block_k,
            interpret)
    return o.transpose(0, 2, 1, 3)


def fused_adam(p, g, m, v, a, clip_scale, *, b1=0.9, b2=0.999, eps=1e-8,
               wd=0.0, wd_form=None, interpret=None):
    """Arbitrary-shaped params: flattens, pads to the block size, runs the
    fused kernel, restores shape.  Returns (p', m', v')."""
    interpret = interpret_default() if interpret is None else interpret
    shape = p.shape
    n = p.size
    block = min(16384, max(128, 1 << (n - 1).bit_length()))
    block = min(block, 16384)
    pad = (-n) % block
    def prep(x, dt):
        return jnp.pad(x.reshape(-1).astype(dt), (0, pad))
    p2, m2, v2 = fused_adam_flat(
        prep(p, p.dtype), prep(g, jnp.float32), prep(m, jnp.float32),
        prep(v, jnp.float32), jnp.asarray(a, jnp.float32),
        jnp.asarray(clip_scale, jnp.float32),
        b1=b1, b2=b2, eps=eps, wd=wd, wd_form=wd_form, block=block,
        interpret=interpret)
    unpad = lambda x: x[:n].reshape(shape)
    return unpad(p2), unpad(m2), unpad(v2)


def rmsnorm(x, scale, *, eps=1e-6, interpret=None):
    """x: (..., d) -> same shape.  Forward only — see ``rmsnorm_diff``."""
    interpret = interpret_default() if interpret is None else interpret
    shape = x.shape
    x2 = x.reshape(-1, shape[-1])
    r = x2.shape[0]
    # pad rows to a friendly block
    block = min(256, r)
    pad = (-r) % block
    if pad:
        x2 = jnp.pad(x2, ((0, pad), (0, 0)))
    o = rmsnorm_2d(x2, scale, eps=eps, block_rows=block, interpret=interpret)
    return o[:r].reshape(shape)


# ---------------------------------------------------------------------------
# Differentiable fused RMSNorm: Pallas forward, reference-recompute backward
# (pallas_call has no transpose rule; the bwd re-derives from (x, scale) —
# the same recompute discipline the flash-attention VJP above uses).  This
# is what models/common.apply_norm dispatches to when the fused path is
# enabled (REPRO_PALLAS_RMSNORM / use_pallas_rmsnorm).
# ---------------------------------------------------------------------------
def _rmsnorm_reference(x, scale, eps):
    # must mirror models.common.apply_norm's rmsnorm branch exactly — the
    # backward below differentiates THIS
    xf = x.astype(jnp.float32)
    ms = (xf * xf).mean(-1, keepdims=True)
    out = xf * jax.lax.rsqrt(ms + eps) * scale.astype(jnp.float32)
    return out.astype(x.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _rmsnorm_ad(x, scale, eps, interpret):
    return rmsnorm(x, scale, eps=eps, interpret=interpret)


def _rmsnorm_ad_fwd(x, scale, eps, interpret):
    return rmsnorm(x, scale, eps=eps, interpret=interpret), (x, scale)


def _rmsnorm_ad_bwd(eps, interpret, res, g):
    x, scale = res
    _, vjp = jax.vjp(lambda xx, ss: _rmsnorm_reference(xx, ss, eps),
                     x, scale)
    return vjp(g)


_rmsnorm_ad.defvjp(_rmsnorm_ad_fwd, _rmsnorm_ad_bwd)


def rmsnorm_diff(x, scale, *, eps=1e-6, interpret=None):
    """Differentiable fused RMSNorm: x (..., d), scale (d,) -> (..., d)."""
    interpret = interpret_default() if interpret is None else interpret
    return _rmsnorm_ad(x, scale, eps, interpret)
