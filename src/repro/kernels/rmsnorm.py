"""Pallas fused RMSNorm (forward).

Every L2L layer boundary runs a norm on the streamed activations; fusing
the mean-square reduction with the scale keeps it one HBM round trip.
Rows are tiled in VMEM blocks of (block_rows, d); the feature dim stays
whole (d <= a few K for all assigned archs, well within VMEM).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _rmsnorm_kernel(x_ref, s_ref, o_ref, *, eps):
    x = x_ref[...].astype(jnp.float32)
    ms = jnp.mean(x * x, axis=-1, keepdims=True)
    o_ref[...] = (x * jax.lax.rsqrt(ms + eps)
                  * s_ref[...].astype(jnp.float32)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("eps", "block_rows",
                                             "interpret"))
def rmsnorm_2d(x, scale, *, eps=1e-6, block_rows=256, interpret):
    """x: (R, d), scale: (d,) -> (R, d)."""
    R, d = x.shape
    block_rows = min(block_rows, R)
    assert R % block_rows == 0
    kern = functools.partial(_rmsnorm_kernel, eps=eps)
    return pl.pallas_call(
        kern,
        grid=(R // block_rows,),
        in_specs=[pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
                  pl.BlockSpec((d,), lambda i: (0,))],
        out_specs=pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((R, d), x.dtype),
        interpret=interpret,
    )(x, scale)
