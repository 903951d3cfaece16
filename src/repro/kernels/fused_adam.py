"""Pallas fused Adam (+ clip scale) update.

Direct answer to the paper's Fig. 6: "Optimizer (gradient clipping and
update)" is 25% of L2L step time because the reference EPS runs an unfused
optimizer.  One fused elementwise kernel reads (p, g, m, v) once, applies
the clip scale, both moment updates and the parameter delta, and writes
(p', m', v') once — 7 HBM streams instead of the ~17 of an unfused chain,
and zero temp traffic.

Scalars (effective step size ``a`` with bias correction baked in, clip
scale) arrive via SMEM so one compiled kernel serves every step.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _adam_kernel(scal_ref, p_ref, g_ref, m_ref, v_ref,
                 po_ref, mo_ref, vo_ref, *, b1, b2, eps, wd, wd_form):
    a = scal_ref[0]          # lr * sqrt(1-b2^t)/(1-b1^t)
    clip = scal_ref[1]       # gradient scale from clipping
    g = g_ref[...].astype(jnp.float32) * clip
    m = b1 * m_ref[...] + (1.0 - b1) * g
    v = b2 * v_ref[...] + (1.0 - b2) * g * g
    p = p_ref[...].astype(jnp.float32)
    # wd_form is static and keyed on the optimizer FAMILY (not wd's
    # value): each branch reproduces the per-leaf optimizer's exact
    # association — adamw: p - a*(m/(sqrt v+eps) + wd*p), even at wd=0;
    # adam: p - (a*m)/(sqrt v+eps) — so packed updates are bit-identical
    # to optim.adam / optim.adamw.
    if wd_form:
        upd = m / (jnp.sqrt(v) + eps) + wd * p
        po_ref[...] = (p - a * upd).astype(po_ref.dtype)
    else:
        po_ref[...] = (p - a * m / (jnp.sqrt(v) + eps)).astype(po_ref.dtype)
    mo_ref[...] = m
    vo_ref[...] = v


@functools.partial(jax.jit, static_argnames=(
    "b1", "b2", "eps", "wd", "wd_form", "block", "interpret"))
def fused_adam_flat(p, g, m, v, a, clip_scale, *, b1=0.9, b2=0.999,
                    eps=1e-8, wd=0.0, wd_form=None, block=16384,
                    interpret):
    """All arrays 1-D of equal length (pad to block multiple).  ``a`` and
    ``clip_scale`` are f32 scalars (traced).  ``wd_form`` forces the
    adamw update association even when wd == 0 (None = infer from wd)."""
    n = p.shape[0]
    block = min(block, n)
    assert n % block == 0, f"{n} % {block}"
    scal = jnp.stack([a.astype(jnp.float32),
                      clip_scale.astype(jnp.float32)])
    kern = functools.partial(_adam_kernel, b1=b1, b2=b2, eps=eps, wd=wd,
                             wd_form=bool(wd) if wd_form is None
                             else wd_form)
    grid = (n // block,)
    bspec = pl.BlockSpec((block,), lambda i: (i,))
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),
                  bspec, bspec, bspec, bspec],
        out_specs=(bspec, bspec, bspec),
        out_shape=(jax.ShapeDtypeStruct((n,), p.dtype),
                   jax.ShapeDtypeStruct((n,), jnp.float32),
                   jax.ShapeDtypeStruct((n,), jnp.float32)),
        interpret=interpret,
    )(scal, p, g, m, v)
