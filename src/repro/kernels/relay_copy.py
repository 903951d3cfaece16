"""Pallas double-buffered relay copy — the ``transport="pallas"`` slot mover.

The relay executor (``core.relay``) historically moves each stop's slot
with ``jax.device_put`` at scan boundaries and relies on XLA's
latency-hiding scheduler to keep the ring's copies in flight while a slot
computes.  That works, but the overlap is a scheduler HEURISTIC — nothing
in the emitted program *forces* the stop-``i+1`` stream-in to proceed
while stop ``i``'s layers run.  This kernel makes the copy itself a
Pallas DMA pipeline, the ``emit_pipeline`` idiom by hand:

* the slot arrives as a stacked ``(N, W)`` row-major buffer (exactly what
  ``core.packing``'s per-dtype flat segments are — one contiguous DMA
  operand; unpacked pytree leaves are reshaped to the same layout),
* the copy is split into a static chunk plan (one chunk per stacked row
  for multi-row slots; single-row slots split the row in half so two DMAs
  can still overlap),
* chunks are moved by ``pltpu.make_async_copy`` through TWO rotating DMA
  semaphores: chunk ``i``'s wait is interleaved with chunk ``i+2``'s
  start, so two transfers are always in flight — overlap guaranteed by
  the semaphores, not by scheduler luck.

The chip's compiler refuses this kernel: a 1-row slice of a 2-D
``(N, W)`` buffer is not aligned to the (8, 128) tiling, and a
``pinned_host`` source is a memory space the kernel cannot address
(``tests/test_tpu_compile.py``).  The engines therefore refuse
``transport="pallas"`` on TPU (``engine.PALLAS_TRANSPORT_ON_TPU``).  On
any other backend the kernel runs in interpret mode, and its semantics —
bit-exact movement of rows ``[start, start+size)`` — are what the
transport tests pin down.  The kernel never needs a
custom VJP: ``relay_scan``'s fetch is not differentiated (the backward
vjp closes over the already-fetched slot), and the write-back direction
is an identity copy on the produced values.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.ops import interpret_default


def _chunk_plan(size: int, width: int) -> tuple:
    """Static (row, col_lo, col_hi) DMA chunks for a (size, width) slot.

    Multi-row slots move one chunk per stacked row (relay rows are large
    — one packed dtype segment each — so per-row DMAs pipeline well);
    a single-row slot is split into two half-row chunks so the two DMA
    semaphores still have two transfers to rotate through.
    """
    if size >= 2 or width < 2:
        return tuple((r, 0, width) for r in range(size))
    h = width // 2
    return ((0, 0, h), (0, h, width))


def _copy_kernel(start_ref, src_ref, dst_ref, sems, *, chunks):
    s = start_ref[0]

    def dma(idx):
        r, c0, c1 = chunks[idx]
        return pltpu.make_async_copy(
            src_ref.at[pl.ds(s + r, 1), pl.ds(c0, c1 - c0)],
            dst_ref.at[pl.ds(r, 1), pl.ds(c0, c1 - c0)],
            sems.at[idx % 2])

    n = len(chunks)
    for i in range(min(2, n)):
        dma(i).start()
    for i in range(n):
        dma(i).wait()
        if i + 2 < n:
            dma(i + 2).start()


@functools.partial(jax.jit, static_argnames=("size", "interpret"))
def copy_rows(src, start, *, size: int, interpret=None):
    """Rows ``[start, start+size)`` of a stacked ``(N, W)`` buffer, moved
    by the double-buffered DMA pipeline.  ``start`` may be traced (it is
    the relay scan's stop index); ``size`` is static.  Bit-exact to
    ``jax.lax.dynamic_slice_in_dim(src, start, size)``."""
    interpret = interpret_default() if interpret is None else interpret
    n, w = src.shape
    chunks = _chunk_plan(size, w)
    start = jnp.asarray(start, jnp.int32).reshape((1,))
    return pl.pallas_call(
        functools.partial(_copy_kernel, chunks=chunks),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),
                  pl.BlockSpec(memory_space=pltpu.ANY)],
        out_specs=pl.BlockSpec(memory_space=pltpu.ANY),
        out_shape=jax.ShapeDtypeStruct((size, w), src.dtype),
        scratch_shapes=[pltpu.SemaphoreType.DMA((2,))],
        interpret=interpret,
    )(start, src)


def _flat_width(shape) -> int:
    w = 1
    for d in shape[1:]:
        w *= d
    return w


def fetch_slot(stacked, start, size: int, *, squeeze: bool = False,
               interpret=None):
    """Stream-in of one relay stop: ``size`` stacked rows of every leaf of
    a ``(N, ...)`` tree (plain pytree or ``packing.Packed`` — both are
    tree_mapped uniformly), each moved through ``copy_rows``.

    ``squeeze`` drops the leading axis for the G=1 single-layer slot
    (matching ``relay.layer_slice``'s keepdims=False).  Degenerate leaves
    (empty rows) fall back to a plain dynamic slice — there is nothing
    for a DMA pipeline to overlap.
    """
    def one(a):
        w = _flat_width(a.shape)
        if a.shape[0] == 0 or w == 0:
            out = jax.lax.dynamic_slice_in_dim(a, start, size, axis=0)
        else:
            out = copy_rows(a.reshape((a.shape[0], w)), start,
                            size=size, interpret=interpret)
            out = out.reshape((size,) + a.shape[1:])
        return out[0] if squeeze else out
    return jax.tree.map(one, stacked)


def writeback_slot(tree, *, interpret=None):
    """Write-back of one relay stop's products (updated weights/opt
    slots, shipped grads, boundary stash): the same DMA pipeline run in
    the device->EPS direction — an identity copy over the produced
    buffer, chunked and semaphore-paced, issued BEFORE the host
    placement so the outbound transfer is pipelined like the inbound
    one.  The whole leaf moves as ONE flat row split into two half-row
    chunks — a per-row plan over an arbitrary product leaf could unroll
    thousands of DMA starts.  Leaves too small to chunk pass through
    untouched."""
    def one(a):
        if a.ndim == 0 or a.size < 2:
            return a
        out = copy_rows(a.reshape((1, a.size)), 0, size=1,
                        interpret=interpret)
        return out.reshape(a.shape)
    return jax.tree.map(one, tree)
