"""Unified relay executor — the ONE place that issues layer-relay DMAs.

Every layer-major scan in the repo (L2L train forward, reverse backward,
Alg-3 trailing update, prefill, serve-decode) is the same composition:

* one or more **streams** — stacked ``(N, ...)`` host-resident trees
  (weights, shipped gradients, optimizer slots; plain pytrees or
  ``packing.Packed`` flat buffers) relayed stop-by-stop into device HBM,
* a **prefetch ring** — ``prefetch_depth + 1`` HBM slots, generalizing
  the old two-slot double buffer: the DMA for stop ``i + k`` is issued at
  the top of stop ``i``'s body, so up to ``k`` transfers are in flight
  while one slot computes (``prefetch_depth = 0`` keeps the historical
  fetch-inside-the-iteration schedule).  The fetch lands in the slot that
  stop ``i - 1`` has finished with, never in the one stop ``i`` computes
  on, so it overlaps all of stop ``i`` (in the backward: the vjp and the
  optimizer update that reads the slot last).  A while loop keeps each
  carried value in one buffer, so the slots are kept apart by the loop's
  shape: one trip runs ``k + 1`` stops (``_ring_scan``).  Folding them
  back into one carried slot that a stop both reads and refills makes
  every fetch wait for that stop's last read.
* **layer groups** — ``layers_per_relay = G`` relays G stacked layers per
  stop: ONE dynamic-slice + ``device_put`` per stream covers G layers
  (one copy per leaf, or one per dtype segment when packed), and the body
  runs per layer over the G-layer sub-stack inside the stop.  The paper's
  §3.1 "the executing **layer(s)**" is plural exactly here: the device
  footprint is G·(1 + prefetch_depth) layer slots, traded against
  ceil(N/G) relay stops instead of N.

``relay_scan`` owns all of that; consumers only write a per-layer body.
The composition is a pure SCHEDULE/layout change: for any (G,
prefetch_depth, pack_params) the math is bit-identical to the G=1,
depth-0, unpacked scan (asserted by tests/test_relay.py).

Mechanics worth knowing:

* The main scan runs over the ``N // G`` full stops; a depth not
  divisible by G leaves a short remainder stop of ``N mod G`` layers that
  is executed outside the scan (after it in a forward pass, before it in
  a reverse pass, preserving layer order) with its own — unoverlapped —
  fetch.  With G = 1 there is never a remainder and the emitted program
  is exactly the historical per-layer scan.
* Nothing blocks inside jit: fetches are plain ``jax.device_put`` whose
  results are consumed one or more stops later, so XLA's latency-hiding
  scheduler keeps ring copies in flight while the current slot
  computes; each fetch lands by the end of the stop that issued it.  On
  backends that drop memory-space transfers (CPU — see
  ``eps.memories_supported``) the restructured scan computes identical
  results with no-op moves.
* ``ys`` keep layer order: a reverse scan stacks a layer's outputs at its
  forward index (matching ``lax.scan(reverse=True)`` semantics).  Without
  a ring, grouped stops stack their G per-layer outputs in forward order
  before the scan stacks the stops; the ring writes each layer's outputs
  at its index into ``(N, ...)`` buffers it carries, and slices ``xs`` the
  same way, so no host-memory array is reshaped.
* ``active=(lo, hi)`` + ``idle_body`` gate each stop with a traced layer
  window: stops outside it run the idle body (pass activations through,
  re-ship slots so inactive rows stay bit-frozen).  This is how
  ``segment_scan`` runs a traced segment window inside one scan and how
  ``dynamic_depth`` masks layers past the runtime depth.  ``active=None``
  keeps the emitted program byte-identical to the historical one.
* ``segment_scan`` (below) wraps ``relay_scan`` callers that used to
  unroll one relay per K-segment: ONE outer ``lax.scan`` over the
  ``N // K`` full segments with a traced segment start drives dynamic
  slices of the stacked streams; the ``N mod K`` remainder is a static
  epilogue outside the scan.  The compiled program becomes O(1) in
  depth while staying bit-identical to the unrolled form.
* Every host<->HBM move goes through ``to_device`` / ``to_host`` under
  the ``relay.fetch`` / ``relay.writeback`` scopes (``core.spans``), and
  the scans run under ``relay.scan``.  Inside ``count_bytes`` each move
  adds its bytes, once for every trip of the scans it runs in: the
  engine keeps the total per compiled step (``Engine.counters()``).
"""
from __future__ import annotations

import collections
import contextlib
import contextvars
from typing import Any, Callable, NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp

# the scalar ``lax.scan`` broadcasts its ys buffers from, in a memory
# space; JAX has no public form of it yet
from jax._src.lax.lax import empty2 as _empty
from jax.experimental.layout import Layout, with_layout_constraint

from repro.core import spans
from repro.core.eps import Placement

# kernels.relay_copy, imported once per process.  The pallas transport's
# ``fetch`` runs once per relay stop per trace — a module-level lazy
# import keeps Python's import machinery out of every fetch.
_RELAY_COPY = None


def _relay_copy():
    global _RELAY_COPY
    if _RELAY_COPY is None:
        from repro.kernels import relay_copy
        _RELAY_COPY = relay_copy
    return _RELAY_COPY


# ===========================================================================
# Moves: every host<->HBM transfer of a relay goes through to_device /
# to_host, under the relay.* scopes, and is counted while a step traces
# ===========================================================================
# the bytes of the program being traced, by direction; None outside
# ``count_bytes``
_COUNT: contextvars.ContextVar = contextvars.ContextVar("relay_bytes",
                                                       default=None)


@contextlib.contextmanager
def count_bytes():
    """Count the relay bytes of the program traced inside the block: a
    ``Counter`` of ``"fetch"`` (host -> HBM) and ``"writeback"`` (HBM ->
    host) bytes, each move counted once for every trip of the scans that
    ``relay_scan`` and ``segment_scan`` run it in.  Only moves through an
    enabled ``Placement`` count: a no-op placement moves nothing."""
    count = collections.Counter()
    token = _COUNT.set(count)
    try:
        yield count
    finally:
        _COUNT.reset(token)


def _counted_into(count, fn: Callable, *args):
    """``fn`` traced with its moves counted into ``count`` (None leaves
    them out)."""
    token = _COUNT.set(count)
    try:
        return fn(*args)
    finally:
        _COUNT.reset(token)


def _tally(direction: str, placement: Placement, tree):
    count = _COUNT.get()
    if count is not None and placement.enabled:
        count[direction] += sum(a.size * a.dtype.itemsize
                                for a in jax.tree.leaves(tree))


def to_device(placement: Placement, tree, grouped: bool = False):
    """Fetch ``tree`` from the EPS into HBM (``dev_grouped`` for a G-layer
    slot): the relay's host -> HBM move."""
    move = (placement.dev_grouped or placement.dev) if grouped \
        else placement.dev
    with jax.named_scope(spans.FETCH):
        _tally("fetch", placement, tree)
        return move(tree)


def to_host(placement: Placement, tree):
    """Ship ``tree`` from HBM back to the EPS: the relay's HBM -> host
    move."""
    with jax.named_scope(spans.WRITEBACK):
        _tally("writeback", placement, tree)
        return placement.host(tree)


def _scan(body: Callable, init, xs, **kw):
    """``lax.scan`` over a relay's stops or segments.  The scan's own
    slicing of ``xs`` and stacking of ``ys`` run under ``spans.SCAN``.
    The body's moves count once per trip: each trace of the body counts
    afresh, so a body traced again replaces its count and never adds to
    it."""
    trips = jax.tree.leaves(xs)[0].shape[0]
    outer = _COUNT.get()
    per_trip = collections.Counter()

    def counted(*args):
        per_trip.clear()
        return _counted_into(per_trip, body, *args)

    with jax.named_scope(spans.SCAN):
        out = jax.lax.scan(body if outer is None else counted, init, xs,
                           **kw)
    if outer is not None:
        outer.update({k: v * trips for k, v in per_trip.items()})
    return out


class Stream(NamedTuple):
    """One stacked host-resident tree relayed by a ``relay_scan``."""
    placement: Placement
    stacked: Any                 # (N, ...) tree (possibly packing.Packed)


def layer_slice(stacked, i):
    """Slice layer ``i`` out of a stacked ``(N, ...)`` tree with a traced
    index (the same dynamic-slice class of op the scan itself emits)."""
    return jax.tree.map(
        lambda a: jax.lax.dynamic_index_in_dim(a, i, 0, keepdims=False),
        stacked)


def group_slice(stacked, start, size: int):
    """Slice ``size`` consecutive layers starting at ``start`` (traced or
    static) — the G-layer relay slot, leading axis kept."""
    return jax.tree.map(
        lambda a: jax.lax.dynamic_slice_in_dim(a, start, size, axis=0),
        stacked)


def _index(tree, j: int):
    return jax.tree.map(lambda a: a[j], tree)


def _stack(ys_list):
    return jax.tree.map(lambda *ls: jnp.stack(ls), *ys_list)


def n_stops(n_layers: int, group: int) -> int:
    """Relay stops one pass makes over ``n_layers`` (ceil division)."""
    g = max(1, group)
    return -(-n_layers // g)


def stop_bounds(n_layers: int, group: int, start: int = 0) -> tuple:
    """Static ``(lo, hi)`` layer ranges of each relay stop over
    ``n_layers`` layers beginning at ``start`` — G full stops plus the
    short remainder, ``n_stops(n_layers, group)`` entries.  This is the
    chunk schedule the storage tier's disk prefetch ring shares with the
    in-jit relay (``core.tierstore``): one contiguous pread per stop."""
    g = max(1, group)
    return tuple((start + lo, start + min(lo + g, n_layers))
                 for lo in range(0, n_layers, g))


def segment_bounds(n_layers: int, every: int) -> tuple:
    """Static ``(start, stop)`` layer ranges of the stash segments when
    only every ``every``-th boundary is checkpointed
    (``ExecutionConfig.stash_every``): boundaries sit at layer indices
    = 0 (mod K), so segments are ``[0, K), [K, 2K), ...`` with a short
    remainder segment at the end when K does not divide N.  One entry per
    stored boundary — ``len(segment_bounds(n, K)) == ceil(n / K)``."""
    k = max(1, int(every))
    return tuple((s, min(s + k, n_layers))
                 for s in range(0, n_layers, k))


def relay_scan(body: Callable, init, streams: Sequence[Stream], *,
               xs=None, reverse: bool = False, group: int = 1,
               prefetch: int = 0, unroll=False, transport: str = "xla",
               active: Optional[tuple] = None,
               idle_body: Optional[Callable] = None):
    """Run ``body`` once per layer under the unified relay schedule.

    ``body(carry, slots, x) -> (carry, ys)`` is PER LAYER:

    * ``slots`` — tuple of HBM-resident single-layer trees, one per
      stream (already fetched; no leading axis),
    * ``x`` — the layer's slice of ``xs`` (None when ``xs`` is None),
    * ``ys`` — per-layer outputs, stacked to ``(N, ...)`` in layer order
      (or None).

    Returns ``(carry, ys)`` like ``lax.scan``; ``reverse=True`` walks
    layers N-1..0 but still stacks ``ys`` in forward order.

    ``transport`` picks the slot mover: ``"xla"`` (historical) slices +
    ``device_put``s and lets XLA schedule the copies; ``"pallas"`` moves
    every slot through ``kernels.relay_copy``'s double-buffered
    ``make_async_copy`` pipeline, so the ring's overlap is enforced by
    DMA semaphores inside the emitted kernel.  Pure transport — results
    are bit-identical (tests/test_transport.py).

    ``active`` makes the trip count a RUNTIME value: a traced half-open
    ``(lo, hi)`` window of local layer indices.  Rows inside the window
    run ``body``; rows outside run ``idle_body`` (same signature, same
    output avals — typically the carry passed through untouched and the
    incoming slots re-shipped) under a per-layer ``lax.cond``, so ONE
    compiled program serves every window value — the mechanism behind
    ``ExecutionConfig.dynamic_depth``.  ``active=None`` (the default)
    emits the historical ungated program unchanged.

    ``prefetch = K >= 1`` runs the K-deep ring (``_ring_scan``): each stop
    fetches the stop K ahead into a slot no value of its own occupies.
    ``prefetch = 0`` fetches each stop's slot at the top of its iteration.
    """
    streams = tuple(streams)
    assert streams, "relay_scan needs at least one stream"
    n = jax.tree.leaves(streams[0].stacked)[0].shape[0]
    G = max(1, int(group))
    K = max(0, int(prefetch))
    S = n // G                    # full stops covered by the main scan
    R = n - S * G                 # remainder stop (0 when G divides N)

    if active is None:
        def call_body(carry, slots, x, idx):
            return body(carry, slots, x)
    else:
        assert idle_body is not None, \
            "relay_scan(active=...) needs an idle_body with matching " \
            "output structure"
        lo, hi = active

        def call_body(carry, slots, x, idx):
            on = jnp.logical_and(idx >= lo, idx < hi)
            return jax.lax.cond(on,
                                lambda c: body(c, slots, x),
                                lambda c: _counted_into(None, idle_body, c,
                                                        slots, x),
                                carry)

    def fetch(start, size: int):
        """ONE host->HBM copy per stream (per leaf / dtype segment) for a
        ``size``-layer slot."""
        with jax.named_scope(spans.FETCH):
            if transport == "pallas":
                # the copy IS the transfer: rows [start, start+size) of
                # every leaf/segment move through the double-buffered DMA
                # kernel (squeezed to the single-layer layout when G == 1,
                # matching layer_slice below)
                relay_copy = _relay_copy()
                slots = tuple(
                    relay_copy.fetch_slot(s.stacked, start, size,
                                          squeeze=(G == 1))
                    for s in streams)
                for s, slot in zip(streams, slots):
                    _tally("fetch", s.placement, slot)
                return slots
            if G == 1:
                return tuple(
                    to_device(s.placement, layer_slice(s.stacked, start))
                    for s in streams)
            return tuple(
                to_device(s.placement, group_slice(s.stacked, start, size),
                          grouped=True)
                for s in streams)

    def run_layers(carry, slots, start, size: int):
        """Per-layer loop over one fetched G-layer slot (static trips):
        the carry and the per-layer ys, in layer order."""
        x_stop = None if xs is None else group_slice(xs, start, size)
        ys = [None] * size
        order = range(size - 1, -1, -1) if reverse else range(size)
        for j in order:
            slot_j = tuple(_index(s, j) for s in slots)
            x_j = None if x_stop is None else _index(x_stop, j)
            carry, ys[j] = call_body(carry, slot_j, x_j, start + j)
        return carry, ys

    def run_stop(carry, slots, start, size: int):
        carry, ys = run_layers(carry, slots, start, size)
        if all(y is None for y in ys):
            return carry, None
        return carry, _stack(ys)

    def run_remainder(carry):
        return run_stop(carry, fetch(S * G, R), S * G, R)

    if K and S:
        return _ring_scan(body, call_body, run_layers, fetch, init, streams,
                          xs, n, G, K, reverse, unroll)

    # no ring: each stop fetches its own slot at the top of its iteration
    ys_rem = None
    if reverse and R:
        # reverse execution visits the trailing short stop first
        carry, ys_rem = run_remainder(init)
        init = carry

    ys_main = None
    if S > 0:
        idxs = jnp.arange(S)
        if G == 1 and transport == "xla" and active is None:
            # historical per-layer scan, reproduced exactly: streams and
            # xs ride the scan's native xs slicing; the fetch happens at
            # the top of the consuming iteration
            def stop_body(carry, scan_x):
                host_slots, x = scan_x
                slots = tuple(to_device(s.placement, t)
                              for s, t in zip(streams, host_slots))
                return body(carry, slots, x)

            carry, ys_main = _scan(
                stop_body, init, (tuple(s.stacked for s in streams), xs),
                reverse=reverse, unroll=unroll)
        elif G == 1:
            # pallas transport can't ride the scan's native xs slicing —
            # the DMA kernel must issue the copy itself, so the stop
            # index drives an explicit per-layer fetch (same schedule:
            # fetch at the top of the consuming iteration).  A gated
            # (``active``) xla relay routes here too: the cond needs the
            # layer index the native-xs path never sees.
            def stop_body(carry, scan_x):
                i, x = scan_x
                return call_body(carry, fetch(i, 1), x, i)

            carry, ys_main = _scan(stop_body, init, (idxs, xs),
                                   reverse=reverse, unroll=unroll)
        else:
            def stop_body(carry, i):
                return run_stop(carry, fetch(i * G, G), i * G, G)

            carry, ys_main = _scan(stop_body, init, idxs,
                                   reverse=reverse, unroll=unroll)
    else:
        carry = init

    if not reverse and R:
        carry, ys_rem = run_remainder(carry)

    return carry, _combine_ys(ys_main, ys_rem, S, G)


def _ring_scan(body, call_body, run_layers, fetch, init, streams, xs,
               n: int, G: int, K: int, reverse: bool, unroll):
    """``relay_scan``'s K-deep prefetch ring (``prefetch = K >= 1``).

    The ring holds K + 1 slots, and the fetch for walk position p + K
    lands in the one that walk position p - 1 has finished with, never in
    the slot stop p computes on.  A while loop carries each value in one
    buffer, so the slots are kept apart by the loop's shape: one scan
    trip runs K + 1 consecutive stops.  The carry holds the K slots the
    trip starts with, each refilled by the stop after the one that reads
    it; the stop that reads the K + 1-th slot finds it in a value of the
    trip's own, fetched at the trip's first stop.  Folding the ring back
    into one carried slot that is read and refilled in the same stop
    makes every fetch wait for the last read of its stop, the optimizer
    update in the backward.

    A stop reads its slice of ``xs`` and writes its ys at its layer
    index (``ys`` are (n, ...) buffers in the carry), so no stacked
    host-memory array is reshaped.  The ``S mod (K + 1)`` stops the full
    trips leave run after the scan on the slots the ring ends with and
    fetch nothing; a fetch in the scan past the walk's end re-fetches its
    last stop (clamped) and is dropped.
    """
    S, R = divmod(n, G)
    P = K + 1
    T, r = divmod(S, P)

    def stop_at(p):
        """The stop at walk position ``p``."""
        return S - 1 - p if reverse else p

    def one_layer():
        slots = fetch(0, G)
        if G > 1:
            slots = tuple(_index(s, 0) for s in slots)
        x = None if xs is None else layer_slice(xs, 0)
        return body(init, slots, x)[1]

    jaxpr, shapes = _counted_into(
        None, jax.make_jaxpr(one_layer, return_shape=True))

    def buffer(aval):
        # what ``lax.scan`` builds its own ys from: a broadcast scalar in
        # the ys' memory space, which the compiler allocates in place (a
        # ``device_put`` of zeros would fill HBM and copy it to the host)
        return jax.lax.broadcast(
            _empty(aval.dtype, memory_space=aval.memory_space),
            (n,) + aval.shape)

    bufs = jax.tree.unflatten(jax.tree.structure(shapes),
                              [buffer(a) for a in jaxpr.out_avals])

    # a stacked host array the ring writes keeps the device's default
    # layout, the one the EPS holds between steps; left free, it takes the
    # layout of the rows written into it, and the step ends by copying it
    dev = next((s.placement.device for s in streams
                if s.placement.device is not None), None)

    def settle(buf):
        if (dev is None
                or jax.typeof(buf).memory_space != jax.memory.Space.Host):
            return buf
        return with_layout_constraint(buf, Layout.from_pjrt_layout(
            dev.client.get_default_layout(buf.dtype, buf.shape, dev)))

    def put(bufs, ys, start):
        for j, y in enumerate(ys):
            bufs = jax.tree.map(
                lambda b, v, i=start + j: settle(
                    jax.lax.dynamic_update_index_in_dim(b, v, i, 0)),
                bufs, y)
        return bufs

    def run(carry, bufs, slots, stop):
        if G == 1:
            x = None if xs is None else layer_slice(xs, stop)
            carry, y = call_body(carry, slots, x, stop)
            return carry, put(bufs, [y], stop)
        carry, ys = run_layers(carry, slots, stop * G, G)
        return carry, put(bufs, ys, stop * G)

    def run_remainder(carry, bufs):
        carry, ys = run_layers(carry, fetch(S * G, R), S * G, R)
        return carry, put(bufs, ys, S * G)

    carry = init
    if reverse and R:
        carry, bufs = run_remainder(carry, bufs)
    ring = tuple(fetch(stop_at(p) * G, G) for p in range(min(K, S)))
    if T:
        def trip(state, t):
            carry, held, bufs = state
            held = list(held) + [None]
            for j in range(P):
                p = t * P + j
                fetched = fetch(stop_at(jnp.minimum(p + K, S - 1)) * G, G)
                carry, bufs = run(carry, bufs, held[j], stop_at(p))
                # the fetch lands by the end of the stop that issued it,
                # in the slot the stop before has finished with (the
                # trip's first stop: the trip's own K + 1-th slot)
                carry, held[j - 1] = jax.lax.optimization_barrier(
                    (carry, fetched))
            return (carry, tuple(held[:K]), bufs), None

        (carry, ring, bufs), _ = _scan(trip, (carry, ring, bufs),
                                       jnp.arange(T), unroll=unroll)
    for j in range(r):
        carry, bufs = run(carry, bufs, ring[j], stop_at(T * P + j))
    if not reverse and R:
        carry, bufs = run_remainder(carry, bufs)
    return carry, bufs


def _combine_ys(ys_main, ys_rem, n_full_stops: int, group: int):
    """(S, G, ...) main-scan ys + (R, ...) remainder ys -> (N, ...)."""
    if group == 1 or ys_main is None:
        return ys_main if ys_rem is None else ys_rem
    flat = jax.tree.map(
        lambda a: a.reshape((n_full_stops * group,) + a.shape[2:]), ys_main)
    if ys_rem is None:
        return flat
    return jax.tree.map(lambda a, b: jnp.concatenate([a, b], axis=0),
                        flat, ys_rem)


# ===========================================================================
# Segment-major driver: ONE scan over the stash segments
# ===========================================================================
def segment_scan(seg_body: Callable, init, *, n_layers: int, every: int,
                 xs=None, xs_rem=None, reverse: bool = False,
                 n_active=None, unroll=False):
    """Drive ``seg_body`` over the ``segment_bounds(n_layers, every)``
    stash segments through ONE ``lax.scan`` — the program stops growing
    with depth.

    The historical constant-memory stash (``ExecutionConfig.stash_every``
    = K > 1) unrolled one relay per segment per phase: ~3·ceil(N/K) scan
    instances in the lowered train step, so trace/compile time and
    program size grew linearly with depth.  Here the ``N // K`` full
    segments ride one outer scan whose carry walks the segment schedule
    (the segment start is ``si * K``, a traced index feeding
    ``group_slice``'s dynamic slices), and the short remainder segment
    (``N mod K`` layers — a different trip count, hence a different
    program) runs OUTSIDE the scan: after it on a forward walk, before
    it on a reverse walk, exactly where the unrolled schedule placed it.

    ``seg_body(carry, start, size, x_seg, window) -> (carry, ys)``:

    * ``start`` — traced index of the segment's first layer,
    * ``size``  — STATIC segment length (K, or the remainder),
    * ``x_seg`` — this segment's slice of ``xs`` (scanned segments) /
      ``xs_rem`` (the remainder); None when not provided,
    * ``window`` — None, or a traced ``(lo, hi)`` local active-row
      window (``n_active`` mode) to forward to ``relay_scan(active=...)``.

    ``n_active`` (a traced layer count) gates segments for runtime-
    dynamic depth: every segment gets ``window = (0, clip(n_active -
    start, 0, K))``.  Dynamic bounds cannot split a remainder out of the
    scan (the split point would be value-dependent), so ``n_active``
    requires ``every`` to divide ``n_layers`` — the CAPACITY depth;
    the runtime depth may land anywhere inside a segment.

    Returns ``(carry, ys_scan, ys_rem)``: the scanned segments' stacked
    ys (leading axis = number of full segments) and the remainder's ys
    (None when there is no remainder).  Per-layer ys flatten back to
    layer order with ``flatten_segments``.
    """
    n = int(n_layers)
    K = min(max(1, int(every)), n)
    S = n // K                    # full segments covered by the scan
    R = n - S * K                 # short remainder segment (< K layers)
    if n_active is not None:
        assert R == 0, \
            f"dynamic depth needs stash_every ({K}) to divide the " \
            f"capacity depth ({n})"

    def window(si):
        if n_active is None:
            return None
        return (jnp.int32(0), jnp.clip(n_active - si * K, 0, K))

    def scan_body(carry, scan_x):
        si, x_seg = scan_x
        return seg_body(carry, si * K, K, x_seg, window(si))

    carry, ys_rem = init, None
    if reverse and R:
        carry, ys_rem = seg_body(carry, S * K, R, xs_rem, None)
    ys_scan = None
    if S:
        carry, ys_scan = _scan(
            scan_body, carry, (jnp.arange(S), xs), reverse=reverse,
            unroll=unroll)
    if not reverse and R:
        carry, ys_rem = seg_body(carry, S * K, R, xs_rem, None)
    return carry, ys_scan, ys_rem


def flatten_segments(ys_scan, ys_rem):
    """(S, K, ...) segment-scanned per-layer ys + (R, ...) remainder ys
    -> (N, ...) in layer order (either side may be None)."""
    if ys_scan is None:
        return ys_rem
    flat = jax.tree.map(
        lambda a: a.reshape((a.shape[0] * a.shape[1],) + a.shape[2:]),
        ys_scan)
    if ys_rem is None:
        return flat
    return jax.tree.map(lambda a, b: jnp.concatenate([a, b], axis=0),
                        flat, ys_rem)
