"""Pallas TPU flash attention (forward).

The L2L recompute path runs each layer's forward TWICE (eq. 6) — so the
attention forward is the hottest kernel in the schedule and the paper's
"higher effective TFLOPs from memory savings" argument lands exactly here:
blockwise online-softmax keeps the (Sq, Sk) score matrix out of HBM and the
working set in VMEM, sized by the BlockSpecs below.

Grid: (B, H, nQ, nK) — the innermost nK dimension iterates KV blocks while
VMEM scratch (m, l, acc) carries the online-softmax state across them; the
output block is written on the last KV block.  Causal and sliding-window
masks are computed from global block indices (no mask tensors in HBM), and
fully-masked (q,k) block pairs are skipped via the mask check inside —
on TPU the index_map still walks them, so the causal speedup comes from the
early-exit ``wrap`` below being compiled into a cheap branch.

Layouts: q,k,v as (B, H, S, D) with D and the S blocks aligned to the MXU
(block defaults 128/512 lanes).  fp32 accumulation regardless of input dtype.
Per-row statistics (the online-softmax m and l, the saved lse, the
backward's delta) are (rows, 1) columns, so every block's last two dims
meet the TPU's (8, 128) rule: a row block of 8k and a full unit dim.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1.0e30


def _block_mask(iq, ik, block_q, block_k, causal, window):
    q_pos = iq * block_q + jax.lax.broadcasted_iota(jnp.int32,
                                                    (block_q, block_k), 0)
    k_pos = ik * block_k + jax.lax.broadcasted_iota(jnp.int32,
                                                    (block_q, block_k), 1)
    allow = jnp.ones((block_q, block_k), jnp.bool_)
    if causal:
        allow &= k_pos <= q_pos
    if window > 0:
        allow &= (q_pos - k_pos) < window
    return allow


def _dot_nt(a, b):
    """a @ b.T without materializing the transpose (MXU-native form)."""
    return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _dot_tn(a, b):
    """a.T @ b without materializing the transpose."""
    return jax.lax.dot_general(a, b, (((0,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _fa_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr, *,
               scale: float, causal: bool, window: int, soft_cap: float,
               block_q: int, block_k: int, n_k: int):
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0].astype(jnp.float32) * scale          # (bq, d)
    k = k_ref[0, 0].astype(jnp.float32)                  # (bk, d)
    s = _dot_nt(q, k)                                    # (bq, bk)
    if soft_cap > 0:
        s = soft_cap * jnp.tanh(s / soft_cap)

    allow = _block_mask(iq, ik, block_q, block_k, causal, window)
    s = jnp.where(allow, s, NEG_INF)

    m_prev = m_scr[...]                                  # (bq, 1)
    m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m_prev - m_new)
    l_scr[...] = l_scr[...] * alpha + p.sum(axis=1, keepdims=True)
    acc_scr[...] = acc_scr[...] * alpha + jnp.dot(
        p, v_ref[0, 0].astype(jnp.float32),
        preferred_element_type=jnp.float32)
    m_scr[...] = m_new

    @pl.when(ik == n_k - 1)
    def _flush():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0] = (acc_scr[...] / l).astype(o_ref.dtype)
        lse_ref[0, 0] = m_scr[...] + jnp.log(l)


@functools.partial(jax.jit, static_argnames=(
    "causal", "window", "soft_cap", "block_q", "block_k", "interpret"))
def flash_attention_fwd_bhsd(q, k, v, *, causal=True, window=0,
                             soft_cap=0.0, block_q=128, block_k=128,
                             interpret):
    """q,k,v: (B,H,S,D) -> (o (B,H,S,D), lse (B,H,S,1))."""
    B, H, Sq, D = q.shape
    Sk = k.shape[2]
    block_q = min(block_q, Sq)
    block_k = min(block_k, Sk)
    assert Sq % block_q == 0 and Sk % block_k == 0, \
        f"seq ({Sq},{Sk}) must tile by ({block_q},{block_k})"
    n_q, n_k = Sq // block_q, Sk // block_k
    scale = 1.0 / math.sqrt(D)

    kern = functools.partial(
        _fa_kernel, scale=scale, causal=causal, window=window,
        soft_cap=soft_cap, block_q=block_q, block_k=block_k, n_k=n_k)

    return pl.pallas_call(
        kern,
        grid=(B, H, n_q, n_k),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, D), lambda b, h, iq, ik: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, block_k, D), lambda b, h, iq, ik: (b, h, ik, 0)),
            pl.BlockSpec((1, 1, block_k, D), lambda b, h, iq, ik: (b, h, ik, 0)),
        ],
        out_specs=(
            pl.BlockSpec((1, 1, block_q, D),
                         lambda b, h, iq, ik: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, block_q, 1),
                         lambda b, h, iq, ik: (b, h, iq, 0)),
        ),
        out_shape=(jax.ShapeDtypeStruct((B, H, Sq, D), q.dtype),
                   jax.ShapeDtypeStruct((B, H, Sq, 1), jnp.float32)),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, D), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)


# ===========================================================================
# Backward (flash-attention-2 style): recompute p from (q,k,lse); no stored
# probability blocks — this is the §Perf "memory-bound train" lever: the
# jnp chunked attention's scan-vjp stashes fp32 p blocks (~3.4 s of the
# command-r train_4k memory term); the kernel recomputes them in VMEM.
# dq kernel: grid (B,H,nQ,nK), dq accumulates in VMEM scratch over k blocks.
# dkv kernel: grid (B,H,nK,nQ), dk/dv accumulate over q blocks.
# delta = rowsum(do * o) is a cheap jnp elementwise pass.
# ===========================================================================
def _fa_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
                  dq_scr, *, scale, causal, window, block_q, block_k, n_k):
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    q = q_ref[0, 0].astype(jnp.float32) * scale
    k = k_ref[0, 0].astype(jnp.float32)
    v = v_ref[0, 0].astype(jnp.float32)
    do = do_ref[0, 0].astype(jnp.float32)
    lse = lse_ref[0, 0]                                  # (bq, 1)
    delta = delta_ref[0, 0]                              # (bq, 1)

    s = _dot_nt(q, k)
    allow = _block_mask(iq, ik, block_q, block_k, causal, window)
    s = jnp.where(allow, s, NEG_INF)
    p = jnp.exp(s - lse)
    dp = _dot_nt(do, v)
    ds = p * (dp - delta)
    dq_scr[...] += jnp.dot(ds, k, preferred_element_type=jnp.float32)

    @pl.when(ik == n_k - 1)
    def _flush():
        dq_ref[0, 0] = (dq_scr[...] * scale).astype(dq_ref.dtype)


def _fa_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                   dk_ref, dv_ref, dk_scr, dv_scr, *, scale, causal,
                   window, block_q, block_k, n_q):
    ik = pl.program_id(2)
    iq = pl.program_id(3)

    @pl.when(iq == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    q = q_ref[0, 0].astype(jnp.float32) * scale
    k = k_ref[0, 0].astype(jnp.float32)
    v = v_ref[0, 0].astype(jnp.float32)
    do = do_ref[0, 0].astype(jnp.float32)
    lse = lse_ref[0, 0]                                  # (bq, 1)
    delta = delta_ref[0, 0]                              # (bq, 1)

    s = _dot_nt(q, k)
    allow = _block_mask(iq, ik, block_q, block_k, causal, window)
    s = jnp.where(allow, s, NEG_INF)
    p = jnp.exp(s - lse)                                 # (bq, bk)
    dv_scr[...] += _dot_tn(p, do)
    dp = _dot_nt(do, v)
    ds = p * (dp - delta)
    dk_scr[...] += _dot_tn(ds, q)

    @pl.when(iq == n_q - 1)
    def _flush():
        # q arrived pre-scaled, so ds^T @ qs already carries the 1/sqrt(D)
        dk_ref[0, 0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_scr[...].astype(dv_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "causal", "window", "block_q", "block_k", "interpret"))
def flash_attention_bwd_bhsd(q, k, v, o, lse, do, *, causal=True, window=0,
                             block_q=128, block_k=128, interpret):
    """-> (dq, dk, dv), all (B,H,S,D).  soft_cap unsupported in bwd (the
    models that train with the kernel don't cap; grok's capped logits are
    in the head, not attention)."""
    B, H, Sq, D = q.shape
    Sk = k.shape[2]
    block_q = min(block_q, Sq)
    block_k = min(block_k, Sk)
    n_q, n_k = Sq // block_q, Sk // block_k
    scale = 1.0 / math.sqrt(D)
    delta = (do.astype(jnp.float32) * o.astype(jnp.float32)).sum(
        -1, keepdims=True)

    q_spec_q = pl.BlockSpec((1, 1, block_q, D),
                            lambda b, h, iq, ik: (b, h, iq, 0))
    k_spec_q = pl.BlockSpec((1, 1, block_k, D),
                            lambda b, h, iq, ik: (b, h, ik, 0))
    r_spec_q = pl.BlockSpec((1, 1, block_q, 1),
                            lambda b, h, iq, ik: (b, h, iq, 0))

    dq = pl.pallas_call(
        functools.partial(_fa_dq_kernel, scale=scale, causal=causal,
                          window=window, block_q=block_q, block_k=block_k,
                          n_k=n_k),
        grid=(B, H, n_q, n_k),
        in_specs=[q_spec_q, k_spec_q, k_spec_q, q_spec_q, r_spec_q,
                  r_spec_q],
        out_specs=q_spec_q,
        out_shape=jax.ShapeDtypeStruct((B, H, Sq, D), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, D), jnp.float32)],
        interpret=interpret,
    )(q, k, v, do, lse, delta)

    q_spec_k = pl.BlockSpec((1, 1, block_q, D),
                            lambda b, h, ik, iq: (b, h, iq, 0))
    k_spec_k = pl.BlockSpec((1, 1, block_k, D),
                            lambda b, h, ik, iq: (b, h, ik, 0))
    r_spec_k = pl.BlockSpec((1, 1, block_q, 1),
                            lambda b, h, ik, iq: (b, h, iq, 0))

    dk, dv = pl.pallas_call(
        functools.partial(_fa_dkv_kernel, scale=scale, causal=causal,
                          window=window, block_q=block_q, block_k=block_k,
                          n_q=n_q),
        grid=(B, H, n_k, n_q),
        in_specs=[q_spec_k, k_spec_k, k_spec_k, q_spec_k, r_spec_k,
                  r_spec_k],
        out_specs=(k_spec_k, k_spec_k),
        out_shape=(jax.ShapeDtypeStruct((B, H, Sk, D), k.dtype),
                   jax.ShapeDtypeStruct((B, H, Sk, D), v.dtype)),
        scratch_shapes=[pltpu.VMEM((block_k, D), jnp.float32),
                        pltpu.VMEM((block_k, D), jnp.float32)],
        interpret=interpret,
    )(q, k, v, do, lse, delta)
    return dq, dk, dv
