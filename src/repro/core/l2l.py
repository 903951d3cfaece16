"""L2L (layer-to-layer) execution engine — Algorithms 3 and 4 of the paper.

The loop inversion is the whole trick: the LAYER loop is outer, the
MICROBATCH loop is inner.  In JAX the outer loop is a relay scan over the
group's stacked ``(N_layers, ...)`` parameters — when those live in
``pinned_host`` (ExecutionConfig.weight_stream) each relay stop is a
host->HBM copy, i.e. the EPS feeding the device one slot at a time.

Forward (Alg 3 lines 2-6):   for l in layers: for u in microbatches:
    run layer l on microbatch u; stash ONLY the layer-boundary activation
    (optionally offloaded to pinned_host — eq. (4) constant memory).

Backward (Alg 3 lines 7-11 / Alg 4): reverse relay over layers; per
microbatch, RECOMPUTE the layer forward via ``jax.vjp`` from the stashed
boundary input (the paper's rematerialization), accumulate (dw, dx, dmem).

Constant-memory stash (``ExecutionConfig.stash_every`` = K > 1): the
forward stashes only the boundaries at layer indices = 0 (mod K) within
each group — ceil(N/K) checkpoints instead of N, so even the offloaded
stash stops growing with depth.  The backward walks the K-segments in
reverse; on arriving at a segment it re-streams that segment's weights
FORWARD through ``relay_scan`` (the same prefetch ring / G-grouping /
packed transport as every other relay) to recompute the K-1 missing
boundaries from the stored entry — each re-hosted into the stash tier as
it is produced and fetched back one layer at a time by the segment's
recompute-vjp backward relay (the K=1 protocol), so the device boundary
working set stays O(1) in both N and K.  Chen-style sublinear
checkpointing composed into the relay: one extra layer-forward for K-1
of every K layers, bit-identical gradients and updates for every (K, G,
prefetch, pack) point (tests/test_stash.py).  K = 1 emits the historical
single-scan schedule unchanged.  K > 1 used to unroll ~3·ceil(N/K)
relay instances (fwd + recompute + bwd per segment) — with
``ExecutionConfig.segment_scan`` (default on) each phase is instead ONE
outer ``segment_scan`` over the N//K full segments (traced segment
start, static remainder epilogue), so the compiled program is O(1) in
depth; ``segment_scan=False`` re-emits the historical unrolled program
bit-identically.  ``dynamic_depth`` builds on that: the step takes the
live layer count as a traced int32 operand (``n_active``), layers past
it ride idle ``lax.cond`` branches that pass activations through and
re-ship their param/optimizer rows bit-frozen, so ONE compiled program
serves every depth up to the capacity the weights were sized at.
With ``eager_optimizer`` (Alg 4 / L2L-p) the optimizer for layer l runs
inside the same reverse step, overlapping the backward of layer l-1 —
and because the body's dw is produced under pjit, the per-layer gradient
all-reduce is issued layer-by-layer too ("parallel reduce").

Gradient identity: this computes exactly the gradients of
baseline-with-accumulated-gradients (Algorithm 2) — asserted by tests.

Relay transport: every layer scan here (train forward, reverse backward,
Alg-3 trailing update, prefill) is a per-layer body handed to
``repro.core.relay.relay_scan``, which owns the EPS transport exactly
once — weight streaming, the ``prefetch_depth``-deep ring of in-flight
HBM slots, ``pack_params`` flat-buffer slots, and ``layers_per_relay``
G-layer relay groups (one DMA covers G stacked layers; the paper §3.1's
"the executing layer(s)", plural).  Every (G, prefetch_depth,
pack_params) combination computes bit-identical results
(tests/test_relay.py, tests/test_prefetch.py, tests/test_packing.py).

Packed relay (``ExecutionConfig.pack_params``): the stacked group params
(and, in L2L-p, the optimizer slots) arrive as ``packing.Packed`` flat
buffers — one contiguous segment per dtype — so each relay stop moves
ONE large array per direction instead of N per-leaf copies.  The bodies
unpack a zero-copy device-side view for the layer apply, keep every
gradient-side reduction (scale, clip, finiteness) on the original tree
so the math is bit-identical to the unpacked schedule, and run the eager
optimizer directly on the flat segments through ``Optimizer.flat_update``
(the fused Pallas kernel) when available, falling back to unpack ->
per-leaf update -> repack otherwise (tests/test_packing.py).
"""
from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp

from repro.core import packing, spans
from repro.core.eps import EPSPlacements, make_placements
from repro.core.relay import (Stream, flatten_segments, group_slice,
                              relay_scan, segment_bounds, segment_scan,
                              to_device, to_host)
from repro.core.schedule import ExecutionConfig
from repro.optim import Optimizer, clip_by_norm, tree_global_norm


def _reshape_ub(tree, ub: int):
    def one(a):
        assert a.shape[0] % ub == 0, \
            f"batch {a.shape[0]} not divisible by n_microbatches {ub}"
        return a.reshape(ub, a.shape[0] // ub, *a.shape[1:])
    return jax.tree.map(one, tree)


def _tree_add(a, b):
    return jax.tree.map(jnp.add, a, b)


def _tree_zeros_f32(tree):
    return jax.tree.map(lambda x: jnp.zeros(x.shape, jnp.float32), tree)


def _seg_slice(tree, s0: int, s1: int):
    """Static layer-range slice of a stacked (N, ...) tree (plain or
    ``packing.Packed`` — both slice on the leading stacked axis)."""
    return jax.tree.map(lambda a: a[s0:s1], tree)


def _concat_segs(trees):
    return jax.tree.map(lambda *ls: jnp.concatenate(ls, axis=0), *trees)


def _make_ship(transport: str) -> Callable:
    """The write-back half of the relay transport: ``ship(placement,
    tree)`` re-hosts a relay stop's products (boundary stash, shipped
    grads, updated weights / optimizer slots) through ``relay.to_host``.
    Under ``transport="pallas"`` the produced buffer first moves through
    the same double-buffered DMA pipeline the stream-in uses
    (``kernels.relay_copy.writeback_slot`` — an identity copy, so the math
    is untouched), pacing the outbound transfer with semaphores exactly
    like the inbound one."""
    if transport == "pallas":
        from repro.kernels import relay_copy

        def ship(placement, tree):
            with jax.named_scope(spans.WRITEBACK):
                return to_host(placement, relay_copy.writeback_slot(tree))
        return ship
    return to_host


def _make_packed_update(optimizer: Optimizer, exec_cfg: ExecutionConfig,
                        run_opt) -> Callable:
    """Per-layer optimizer step on ``Packed`` flat buffers.

    Fused path: when the optimizer exposes ``flat_update`` (adam/adamw ->
    kernels/fused_adam_flat) and the slots are Adam-shaped, the update
    runs ONCE per dtype segment — one kernel over the whole layer instead
    of a per-leaf chain — reading the (possibly low-precision) weight
    segment and the f32 master moments that stay EPS-resident.  Fallback
    (lamb/sgd/collector, or host_optimizer which must run on the EPS
    host): unpack -> per-leaf ``run_opt`` -> repack.  Both paths are
    bit-identical to the unpacked schedule."""
    @jax.named_scope(spans.OPTIMIZER)
    def packed_update(dw, opt_l, w_pk, step_i):
        spec = w_pk.spec
        slots = tuple(sorted(opt_l))
        if (optimizer.flat_update is not None and slots == ("m", "v")
                and not exec_cfg.host_optimizer):
            g_pk = dw if packing.is_packed(dw) \
                else packing.pack(dw, spec=spec, stacked=False)
            new_p, new_m, new_v = {}, {}, {}
            for key in sorted(w_pk.segs):
                p2, m2, v2 = optimizer.flat_update(
                    w_pk.segs[key], g_pk.segs[key],
                    opt_l["m"].segs[key], opt_l["v"].segs[key], step_i)
                new_p[key], new_m[key], new_v[key] = p2, m2, v2
            return (packing.Packed(new_p, spec),
                    {"m": packing.Packed(new_m, spec),
                     "v": packing.Packed(new_v, spec)})
        dw_t = packing.unpack(dw) if packing.is_packed(dw) else dw
        nw, no = run_opt(dw_t, packing.unpack_opt(spec, opt_l),
                         packing.unpack(w_pk), step_i)
        return (packing.pack(nw, spec=spec, stacked=False),
                packing.pack_opt(spec, no, stacked=False))
    return packed_update


# ===========================================================================
# Training step factory
# ===========================================================================
def make_train_step(model, optimizer: Optimizer, exec_cfg: ExecutionConfig,
                    placements: Optional[EPSPlacements] = None) -> Callable:
    """Returns step(params, opt_state, batch) -> (params', opt_state',
    metrics).  ``opt_state`` = {"step": i32, "embed":..., "head":...,
    "groups": (stacked per group,)} — build with ``init_opt_state``."""
    if placements is None:
        placements = make_placements(exec_cfg, len(model.groups))
    UB = exec_cfg.n_microbatches
    PF = exec_cfg.prefetch_depth
    PK = exec_cfg.pack_params
    G = exec_cfg.layers_per_relay
    SE = exec_cfg.stash_every
    TR = exec_cfg.transport
    UNROLL = exec_cfg.unroll_layers
    SEG = exec_cfg.segment_scan
    DYN = exec_cfg.dynamic_depth
    if DYN:
        assert len(model.groups) == 1, \
            "dynamic_depth supports single-group models " \
            "(one traced depth bound)"
        assert model.groups[0].n_layers % SE == 0, \
            "dynamic_depth needs stash_every to divide the capacity depth"
    ship = _make_ship(TR)

    @jax.named_scope(spans.OPTIMIZER)
    def run_opt(grads, opt_l, w, step_i):
        """Apply the optimizer — on the EPS host when host_optimizer (the
        paper's CPU optimizer, eq. (6) O_tc; L2L-p overlaps it)."""
        if exec_cfg.host_optimizer:
            from jax.experimental.compute_on import compute_on
            with compute_on("device_host"):
                return optimizer.update(grads, opt_l, w, step_i)
        return optimizer.update(grads, opt_l, w, step_i)

    packed_update = _make_packed_update(optimizer, exec_cfg, run_opt)

    def step(params, opt_state, batch, n_active=None):
        if DYN:
            assert n_active is not None, \
                "dynamic_depth: the step takes a traced n_layers operand"
            n_act = jnp.asarray(n_active, jnp.int32)
            act_win = (jnp.int32(0), n_act)     # active layer-row window
        else:
            assert n_active is None, \
                "n_layers operand needs ExecutionConfig.dynamic_depth"
            n_act = act_win = None
        static = {"embed": params["embed"], "head": params["head"]}
        batch_ub = _reshape_ub(batch, UB)
        W_total = jnp.maximum(batch["mask"].sum(), 1.0)
        amp = exec_cfg.loss_scale_init > 0
        S_loss = (opt_state["loss_scale"]["scale"] if amp
                  else jnp.float32(1.0))

        # ------------------------------------------------------------
        # FORWARD: layer-major relay through the groups
        # ------------------------------------------------------------
        @jax.named_scope(spans.HEAD)
        def prep_one(b):
            x, _ = model.prepare(static, b)
            return x
        x_ub = jax.lax.map(prep_one, batch_ub)            # (UB, Bub, S, d)

        ub_slice = jax.tree.map(lambda a: a[0], batch_ub)
        # per group: boundary inputs — one stacked (N, UB, Bub, S, d)
        # tree with stash_every=1; with K > 1 a PYTHON LIST of the
        # ceil(N/K) segment-entry checkpoint trees (kept unstacked so
        # each stays in the stash placement's memory space — stacking
        # would materialize the checkpoints outside pinned_host on TPU;
        # the backward recomputes the in-between boundaries from them)
        stashes = []
        group_inputs = []     # x_ub at entry of each group (== stash[:,0])
        mems = []             # per group: mem_ub or None
        aux_total = jnp.float32(0.0)

        for gi, group in enumerate(model.groups):
            if gi > 0:
                x_prev = x_ub
                with jax.named_scope(spans.HEAD):
                    x_ub = jax.lax.map(
                        lambda b_x: model.transition_x(gi, static, b_x[1],
                                                       b_x[0]),
                        (batch_ub, x_prev))
                    mem_ub = (jax.lax.map(
                        lambda b_x: model.transition_mem(gi, static, b_x[1],
                                                         b_x[0]),
                        (batch_ub, x_prev)) if group.has_mem else None)
                group_inputs.append(x_prev)   # saved for transition vjp
            else:
                mem_ub = None
                group_inputs.append(None)
            mems.append(mem_ub)
            ctx = model.train_ctx(ub_slice, group)
            wp = placements.weights[gi]

            @jax.named_scope(spans.FORWARD)
            def fwd_body(x_c, slots, _x, _g=group, _ctx=ctx, _mem=mem_ub,
                         _stash=True):
                """Microbatch loop of one layer (slot already in HBM)."""
                (w,) = slots
                if PK:
                    w = packing.unpack(w)   # zero-copy views on the buffer
                def ub_body(aux_c, args):
                    if _mem is None:
                        x_i = args
                        y, aux = _g.apply(w, x_i, None, _ctx)
                    else:
                        x_i, m_i = args
                        y, aux = _g.apply(w, x_i, m_i, _ctx)
                    return aux_c + aux.astype(jnp.float32), y
                xs = x_c if _mem is None else (x_c, _mem)
                aux_g, y_ub = jax.lax.scan(ub_body, jnp.float32(0.0), xs)
                return y_ub, ((ship(placements.stash, x_c), aux_g)
                              if _stash else aux_g)

            if SE == 1:
                fwd_idle = None
                if DYN:
                    def fwd_idle(x_c, slots, _x):
                        # inactive layer: activations pass through
                        # untouched; the boundary ships anyway (the ys
                        # avals must match the live branch)
                        return x_c, (ship(placements.stash, x_c),
                                     jnp.float32(0.0))
                x_ub, (stash_g, aux_per_layer) = relay_scan(
                    fwd_body, x_ub, (Stream(wp, params["groups"][gi]),),
                    group=G, prefetch=PF, unroll=UNROLL, transport=TR,
                    active=act_win, idle_body=fwd_idle)
                stashes.append(stash_g)
                aux_total = aux_total + aux_per_layer.sum() / UB
            else:
                # constant-memory stash: checkpoint ONLY each K-segment's
                # entry boundary; the segment's layers relay through the
                # same executor (ring/grouping/packing intact) without
                # emitting per-layer stash ys.
                def fwd_nostash(x_c, slots, x, _b=fwd_body):
                    return _b(x_c, slots, x, _stash=False)

                if not SEG:
                    # historical unrolled per-segment relays — one
                    # program instance per segment, kept as the
                    # compile-time A/B baseline (segment_scan=False)
                    stash_segs = []
                    for s0, s1 in segment_bounds(group.n_layers, SE):
                        stash_segs.append(ship(placements.stash, x_ub))
                        x_ub, aux_per_layer = relay_scan(
                            fwd_nostash, x_ub,
                            (Stream(wp, _seg_slice(params["groups"][gi],
                                                   s0, s1)),),
                            group=G, prefetch=PF, unroll=UNROLL,
                            transport=TR)
                        aux_total = aux_total + aux_per_layer.sum() / UB
                    stashes.append(stash_segs)
                else:
                    # segment-major: ONE outer scan walks the full
                    # K-segments (traced start -> dynamic weight slices);
                    # aux accumulation rides the carry so the float adds
                    # keep the unrolled left-to-right order, and the
                    # entry checkpoints become the outer scan's ys (the
                    # same ship-into-stash-tier protocol K=1 uses).
                    fwd_idle = None
                    if DYN:
                        def fwd_idle(x_c, slots, _x):
                            return x_c, jnp.float32(0.0)

                    w_g = params["groups"][gi]

                    def seg_fwd(carry, s0, size, _x, win, _wp=wp,
                                _w=w_g, _idle=fwd_idle):
                        x_c, aux_c = carry
                        entry = ship(placements.stash, x_c)
                        x_c, aux_per_layer = relay_scan(
                            fwd_nostash, x_c,
                            (Stream(_wp, group_slice(_w, s0, size)),),
                            group=G, prefetch=PF, unroll=UNROLL,
                            transport=TR, active=win, idle_body=_idle)
                        return (x_c, aux_c + aux_per_layer.sum() / UB), \
                            entry

                    (x_ub, aux_total), st_scan, st_rem = segment_scan(
                        seg_fwd, (x_ub, aux_total),
                        n_layers=group.n_layers, every=SE,
                        n_active=n_act, unroll=UNROLL)
                    stashes.append((st_scan, st_rem))

        # ------------------------------------------------------------
        # HEAD: loss + dL/dx per microbatch (also d_static from the head)
        # ------------------------------------------------------------
        def head_ub(carry, args):
            d_static_acc, loss_acc = carry
            x_i, b_i = args
            def f(s, xx):
                ls, ws = model.head_loss(s, xx, b_i)
                return ls
            loss_i, vjp = jax.vjp(f, static, x_i)
            ds_i, dx_i = vjp(S_loss / W_total)
            return (_tree_add(d_static_acc, jax.tree.map(
                lambda a: a.astype(jnp.float32), ds_i)),
                loss_acc + loss_i), dx_i

        with jax.named_scope(spans.HEAD):
            (d_static, loss_sum), dx_ub = jax.lax.scan(
                head_ub, (_tree_zeros_f32(static), jnp.float32(0.0)),
                (x_ub, batch_ub))
        loss = loss_sum / W_total + aux_total

        # ------------------------------------------------------------
        # BACKWARD: reverse relay; recompute-vjp per layer; eager opt
        # ------------------------------------------------------------
        new_group_params = [None] * len(model.groups)
        new_group_opt = [None] * len(model.groups)
        group_grads = [None] * len(model.groups)  # only if not eager
        gnorm_sq = jnp.float32(0.0)
        nonfinite = jnp.int32(0)
        opt_step = opt_state["step"]

        for gi in reversed(range(len(model.groups))):
            group = model.groups[gi]
            ctx = model.train_ctx(ub_slice, group)
            mem_ub = mems[gi]
            has_mem = mem_ub is not None
            wp, op = placements.weights[gi], placements.opts[gi]

            dmem_ub = (jax.tree.map(
                lambda a: jnp.zeros(a.shape, a.dtype), mem_ub)
                if has_mem else None)

            @jax.named_scope(spans.BACKWARD)
            def bwd_body(core, slots, _x, _g=group, _ctx=ctx,
                         _mem=mem_ub, _wp=wp, _op=op, _has_mem=has_mem):
                """Recompute-vjp microbatch loop (+ eager opt) of one
                layer; the slots (weights, the optimizer's with the eager
                update, the stashed boundary last) are already the
                HBM-resident slices.  With pack_params the vjp
                differentiates the UNPACKED view and every gradient-side
                reduction below stays on the tree, so the packed
                schedule's math is bit-identical."""
                w_dev, stash_dev = slots[0], slots[-1]
                opt_l = slots[1] if len(slots) > 2 else None
                dx_c, dmem_c, gn_c, nf_c = core
                w_tree = packing.unpack(w_dev) if PK else w_dev

                def ub_body(dw_acc, args):
                    if _has_mem:
                        x_in, dx_i, m_i = args
                        def f(ww, xx, mm):
                            return _g.apply(ww, xx, mm, _ctx)
                        _, vjp = jax.vjp(f, w_tree, x_in, m_i)
                        dw_i, dxin_i, dmem_i = vjp(
                            (dx_i, S_loss / UB))
                    else:
                        x_in, dx_i = args
                        def f(ww, xx):
                            return _g.apply(ww, xx, None, _ctx)
                        _, vjp = jax.vjp(f, w_tree, x_in)
                        dw_i, dxin_i = vjp((dx_i, S_loss / UB))
                        dmem_i = None
                    dw_acc = _tree_add(dw_acc, jax.tree.map(
                        lambda a: a.astype(jnp.float32), dw_i))
                    ys = (dxin_i, dmem_i) if _has_mem else dxin_i
                    return dw_acc, ys

                args = (stash_dev, dx_c, _mem) if _has_mem \
                    else (stash_dev, dx_c)
                dw, ys = jax.lax.scan(
                    ub_body, _tree_zeros_f32(w_tree), args)
                if _has_mem:
                    dxin_ub, dmem_ub_l = ys
                    dmem_c = _tree_add(dmem_c, dmem_ub_l)
                else:
                    dxin_ub = ys
                dw = jax.tree.map(lambda g: g / S_loss, dw)
                finite_l = jnp.all(jnp.stack([
                    jnp.all(jnp.isfinite(g)) for g in jax.tree.leaves(dw)]))
                if exec_cfg.clip_mode == "per_layer":
                    dw, _ = clip_by_norm(dw, exec_cfg.clip_norm)
                gn_c = gn_c + jnp.where(finite_l,
                                        tree_global_norm(dw) ** 2, 0.0)
                if exec_cfg.eager_optimizer:
                    new_w, new_opt = (packed_update if PK else run_opt)(
                        dw, opt_l, w_dev, opt_step)
                    if amp:
                        # L2L-adapted AMP: a non-finite layer skips ITS
                        # update (eager updates can't await a global check)
                        with jax.named_scope(spans.OPTIMIZER):
                            new_w = jax.tree.map(
                                lambda n, o: jnp.where(finite_l, n, o),
                                new_w, w_dev)
                            new_opt = jax.tree.map(
                                lambda n, o: jnp.where(finite_l, n, o),
                                new_opt, opt_l)
                    # the next layer's vjp waits for this update: the
                    # ring refills this layer's slot once the update has
                    # read it, and left free the scheduler defers the
                    # update, and the refill with it, past that vjp
                    (new_w, new_opt), dxin_ub = jax.lax.optimization_barrier(
                        ((new_w, new_opt), dxin_ub))
                    out = (ship(_wp, new_w), ship(_op, new_opt))
                else:
                    # Alg 3: gradients are shipped to the EPS (host) and the
                    # update happens in a trailing layer loop — packed, the
                    # shipment is one flat f32 segment aligned to the
                    # weight layout instead of N leaf copies.
                    out = ship(_wp,
                               packing.pack(dw, spec=w_dev.spec,
                                            stacked=False)
                               if PK else dw)
                nf_c = nf_c + jnp.where(finite_l, 0, 1)
                return (dxin_ub, dmem_c, gn_c, nf_c), out

            bwd_idle = None
            if DYN:
                def bwd_idle(core, slots, _x, _wp=wp, _op=op):
                    """Inactive layer: the carry (dx, dmem, gnorm,
                    nonfinite) passes through untouched; the write-back
                    ys re-ship the incoming rows (eager: the row's
                    params/opt slots stay bit-identical) or a zero
                    gradient (trailing-update mode)."""
                    if exec_cfg.eager_optimizer:
                        return core, (ship(_wp, slots[0]),
                                      ship(_op, slots[1]))
                    w_tree = packing.unpack(slots[0]) if PK else slots[0]
                    dw0 = _tree_zeros_f32(w_tree)
                    return core, ship(
                        _wp,
                        packing.pack(dw0, spec=slots[0].spec,
                                     stacked=False) if PK else dw0)

            core0 = (dx_ub, dmem_ub, gnorm_sq, nonfinite)
            if SE == 1:
                streams = [Stream(wp, params["groups"][gi])]
                if exec_cfg.eager_optimizer:
                    # L2L-p: the optimizer slice rides the same relay
                    # ring; the updated-weight write-back (stacked ys) is
                    # consumed only after the scan — it overlaps the next
                    # backward.
                    streams.append(Stream(op, opt_state["groups"][gi]))
                # the stashed boundaries ride the ring too, a stop ahead
                streams.append(Stream(placements.stash, stashes[gi]))
                core0, outs = relay_scan(
                    bwd_body, core0, streams, reverse=True,
                    group=G, prefetch=PF, unroll=UNROLL, transport=TR,
                    active=act_win, idle_body=bwd_idle)
            else:
                # Constant-memory stash: walk the K-segments in reverse.
                # Each segment first re-streams its weights FORWARD
                # through the relay executor (same ring/grouping/packing)
                # to recompute the K-1 boundaries between its stored
                # entry checkpoint and the next one, then runs the
                # recompute-vjp backward relay over the segment.  Each
                # recomputed boundary is RE-HOSTED into the stash
                # placement as it is produced and fetched back one layer
                # at a time by the backward (exactly the K=1 protocol),
                # so the device never holds more than one boundary of
                # recompute working set regardless of K.
                @jax.named_scope(spans.RECOMPUTE)
                def rec_body(x_c, slots, _x, _g=group, _ctx=ctx,
                             _mem=mem_ub):
                    """One layer of the boundary-recompute forward: the
                    same microbatch loop as the forward relay (aux
                    discarded); ys = the layer's OUTPUT boundary, placed
                    into the stash tier."""
                    (w,) = slots
                    if PK:
                        w = packing.unpack(w)
                    def ub_body(_, args):
                        if _mem is None:
                            y, _aux = _g.apply(w, args, None, _ctx)
                        else:
                            x_i, m_i = args
                            y, _aux = _g.apply(w, x_i, m_i, _ctx)
                        return None, y
                    xs_l = x_c if _mem is None else (x_c, _mem)
                    _, y_ub = jax.lax.scan(ub_body, None, xs_l)
                    return y_ub, ship(placements.stash, y_ub)

                if not SEG:
                    # historical unrolled per-segment relays
                    # (segment_scan=False compile-time A/B baseline)
                    bounds = segment_bounds(group.n_layers, SE)
                    outs_segs = [None] * len(bounds)
                    for si in reversed(range(len(bounds))):
                        s0, s1 = bounds[si]
                        entry = stashes[gi][si]          # host-placed
                        if s1 - s0 > 1:
                            _, rec_bounds = relay_scan(
                                rec_body, to_device(placements.stash, entry),
                                (Stream(wp,
                                        _seg_slice(params["groups"][gi],
                                                   s0, s1 - 1)),),
                                group=G, prefetch=PF, unroll=UNROLL,
                                transport=TR)
                            # entry + outputs of layers s0..s1-2
                            # == boundaries of layers s0..s1-1
                            seg_stash = jax.tree.map(
                                lambda e, bs: jnp.concatenate(
                                    [e[None], bs], axis=0),
                                entry, rec_bounds)
                        else:
                            seg_stash = jax.tree.map(
                                lambda a: a[None], entry)
                        seg_streams = [Stream(
                            wp, _seg_slice(params["groups"][gi], s0, s1))]
                        if exec_cfg.eager_optimizer:
                            seg_streams.append(Stream(op, _seg_slice(
                                opt_state["groups"][gi], s0, s1)))
                        seg_streams.append(Stream(placements.stash,
                                                  seg_stash))
                        core0, outs_segs[si] = relay_scan(
                            bwd_body, core0, seg_streams,
                            reverse=True, group=G, prefetch=PF,
                            unroll=UNROLL, transport=TR)
                    # per-segment write-backs concatenate to the (N, ...)
                    # group tree; re-state the EPS placement on the
                    # result so it lands host-resident like the K=1
                    # scan-stacked ys
                    outs = _concat_segs(outs_segs)
                else:
                    # segment-major: the reverse walk over segments is
                    # ONE outer scan (the entry checkpoints ride its xs);
                    # each iteration re-streams its segment's weights
                    # forward to recompute the missing boundaries, then
                    # runs the recompute-vjp backward — exactly the
                    # unrolled schedule, with a traced segment start
                    # feeding dynamic weight/opt slices.
                    rec_idle = None
                    if DYN:
                        def rec_idle(x_c, slots, _x):
                            return x_c, ship(placements.stash, x_c)

                    w_g = params["groups"][gi]
                    o_g = (opt_state["groups"][gi]
                           if exec_cfg.eager_optimizer else None)

                    def seg_bwd(core, s0, size, entry, win, _wp=wp,
                                _op=op, _w=w_g, _o=o_g, _ri=rec_idle):
                        if size > 1:
                            # active rows [0, hi): the recompute needs
                            # boundaries 1..hi-1 = outputs of rows
                            # 0..hi-2, so its window is (0, hi-1)
                            rec_win = (None if win is None else
                                       (win[0],
                                        jnp.maximum(win[1] - 1, 0)))
                            _, rec_bounds = relay_scan(
                                rec_body, to_device(placements.stash, entry),
                                (Stream(_wp,
                                        group_slice(_w, s0, size - 1)),),
                                group=G, prefetch=PF, unroll=UNROLL,
                                transport=TR, active=rec_win,
                                idle_body=_ri)
                            # entry + outputs of rows 0..size-2
                            # == boundaries of rows 0..size-1
                            seg_stash = jax.tree.map(
                                lambda e, bs: jnp.concatenate(
                                    [e[None], bs], axis=0),
                                entry, rec_bounds)
                        else:
                            seg_stash = jax.tree.map(
                                lambda a: a[None], entry)
                        seg_streams = [Stream(
                            _wp, group_slice(_w, s0, size))]
                        if exec_cfg.eager_optimizer:
                            seg_streams.append(Stream(
                                _op, group_slice(_o, s0, size)))
                        seg_streams.append(Stream(placements.stash,
                                                  seg_stash))
                        return relay_scan(
                            bwd_body, core, seg_streams,
                            reverse=True, group=G, prefetch=PF,
                            unroll=UNROLL, transport=TR, active=win,
                            idle_body=bwd_idle)

                    st_scan, st_rem = stashes[gi]
                    core0, outs_scan, outs_rem = segment_scan(
                        seg_bwd, core0, n_layers=group.n_layers,
                        every=SE, xs=st_scan, xs_rem=st_rem,
                        reverse=True, n_active=n_act, unroll=UNROLL)
                    outs = flatten_segments(outs_scan, outs_rem)
                # re-state the EPS placement on the stitched result so it
                # lands host-resident like the K=1 scan-stacked ys
                outs = ((wp.host(outs[0]), op.host(outs[1]))
                        if exec_cfg.eager_optimizer else wp.host(outs))
            dx_ub, dmem_ub, gnorm_sq, nonfinite = core0
            if exec_cfg.eager_optimizer:
                new_group_params[gi], new_group_opt[gi] = outs
            else:
                group_grads[gi] = outs

            # ---- transition vjp back to the previous group -----------
            if gi > 0:
                x_prev_ub = group_inputs[gi]

                def trans_ub(d_static_acc, args):
                    b_i, xp_i, dxin_i, dmem_i = args
                    def fx(s, xp):
                        return model.transition_x(gi, s, xp, b_i)
                    _, vjp_x = jax.vjp(fx, static, xp_i)
                    ds_x, dxp_x = vjp_x(dxin_i)
                    if dmem_i is not None:
                        def fm(s, xp):
                            return model.transition_mem(gi, s, xp, b_i)
                        _, vjp_m = jax.vjp(fm, static, xp_i)
                        ds_m, dxp_m = vjp_m(dmem_i)
                        ds_x = _tree_add(ds_x, ds_m)
                        dxp_x = dxp_x + dxp_m
                    return _tree_add(d_static_acc, jax.tree.map(
                        lambda a: a.astype(jnp.float32), ds_x)), dxp_x

                if has_mem:
                    with jax.named_scope(spans.HEAD):
                        d_static, dx_ub = jax.lax.scan(
                            trans_ub, d_static,
                            (batch_ub, x_prev_ub, dx_ub, dmem_ub))
                else:
                    def trans_ub_nomem(d_static_acc, args):
                        b_i, xp_i, dxin_i = args
                        def fx(s, xp):
                            return model.transition_x(gi, s, xp, b_i)
                        _, vjp_x = jax.vjp(fx, static, xp_i)
                        ds_x, dxp_x = vjp_x(dxin_i)
                        return _tree_add(d_static_acc, jax.tree.map(
                            lambda a: a.astype(jnp.float32), ds_x)), dxp_x
                    with jax.named_scope(spans.HEAD):
                        d_static, dx_ub = jax.lax.scan(
                            trans_ub_nomem, d_static,
                            (batch_ub, x_prev_ub, dx_ub))

        # ---- prepare (embedding) vjp ---------------------------------
        def prep_ub(d_static_acc, args):
            b_i, dx_i = args
            def f(s):
                x, _ = model.prepare(s, b_i)
                return x
            _, vjp = jax.vjp(f, static)
            (ds_i,) = vjp(dx_i)
            return _tree_add(d_static_acc, jax.tree.map(
                lambda a: a.astype(jnp.float32), ds_i)), None

        with jax.named_scope(spans.HEAD):
            d_static, _ = jax.lax.scan(prep_ub, d_static, (batch_ub, dx_ub))
        gnorm_sq = gnorm_sq + tree_global_norm(d_static) ** 2

        # ------------------------------------------------------------
        # UPDATES (trailing update: static params; layer params if not eager)
        # ------------------------------------------------------------
        d_static = jax.tree.map(lambda g: g / S_loss, d_static)
        finite_s = jnp.all(jnp.stack([
            jnp.all(jnp.isfinite(g)) for g in jax.tree.leaves(d_static)]))
        nonfinite = nonfinite + jnp.where(finite_s, 0, 1)
        if exec_cfg.clip_mode == "per_layer":
            d_static, _ = clip_by_norm(d_static, exec_cfg.clip_norm)
        with jax.named_scope(spans.OPTIMIZER):
            new_static, new_static_opt = optimizer.update(
                d_static,
                {"embed": opt_state["embed"], "head": opt_state["head"]},
                static, opt_step)
            if amp:
                new_static = jax.tree.map(
                    lambda n, o: jnp.where(finite_s, n, o), new_static,
                    static)
                new_static_opt = jax.tree.map(
                    lambda n, o: jnp.where(finite_s, n, o), new_static_opt,
                    {"embed": opt_state["embed"],
                     "head": opt_state["head"]})

        if not exec_cfg.eager_optimizer:
            # Alg 3: separate trailing loop over layers (still layer-major).
            # Triple relay: weight, gradient (shipped to the EPS by the
            # backward, same placement as weights) and optimizer slots of
            # the NEXT stop stream in while this one updates.
            for gi, group in enumerate(model.groups):
                wp, op = placements.weights[gi], placements.opts[gi]
                streams = (Stream(wp, params["groups"][gi]),
                           Stream(wp, group_grads[gi]),
                           Stream(op, opt_state["groups"][gi]))

                def upd_body(_, slots, _x, _wp=wp, _op=op):
                    w, g, o = slots
                    nw, no = (packed_update if PK else run_opt)(
                        g, o, w, opt_step)
                    return None, (ship(_wp, nw), ship(_op, no))

                upd_idle = None
                if DYN:
                    def upd_idle(_, slots, _x, _wp=wp, _op=op):
                        # inactive row: no update — re-ship the incoming
                        # rows so adam's moment decay never touches them
                        w, g, o = slots
                        return None, (ship(_wp, w),
                                      ship(_op, o))

                _, (nw_g, no_g) = relay_scan(
                    upd_body, None, streams,
                    group=G, prefetch=PF, unroll=UNROLL, transport=TR,
                    active=act_win, idle_body=upd_idle)
                new_group_params[gi] = nw_g
                new_group_opt[gi] = no_g

        new_params = {"embed": new_static["embed"],
                      "head": new_static["head"],
                      "groups": tuple(new_group_params)}
        new_opt = {"step": opt_step + 1,
                   "embed": new_static_opt["embed"],
                   "head": new_static_opt["head"],
                   "groups": tuple(new_group_opt)}
        metrics = {"loss": loss, "aux": aux_total,
                   "grad_norm": jnp.sqrt(gnorm_sq),
                   "weight_sum": W_total}
        if exec_cfg.skip_nonfinite:
            # anomaly sentinel: ANY non-finite layer/static gradient
            # rejects the whole step — params, opt slots and the step
            # counter come back bit-identical to the pre-step state
            # (``jnp.where`` passes the prior operand through untouched),
            # whatever the (G, prefetch, pack, K) relay produced above.
            # The AMP loss scale (attached below) still adapts on a
            # rejected step, so overflow recovery converges.
            bad = nonfinite > 0

            def keep(new, old):
                return jax.tree.map(
                    lambda a, o: jnp.where(bad, o, a), new, old)

            new_params = keep(new_params, params)
            new_opt = {k: keep(new_opt[k], opt_state[k])
                       for k in ("step", "embed", "head", "groups")}
            metrics["skipped_steps"] = jnp.where(bad, 1, 0).astype(jnp.int32)
            metrics["nonfinite_layers"] = nonfinite
        if amp:
            ls = opt_state["loss_scale"]
            any_bad = nonfinite > 0
            good = jnp.where(any_bad, 0, ls["good_steps"] + 1)
            scale = jnp.where(any_bad,
                              jnp.maximum(ls["scale"] * 0.5, 1.0),
                              ls["scale"])
            grow = good >= exec_cfg.loss_scale_growth
            scale = jnp.where(grow, scale * 2.0, scale)
            good = jnp.where(grow, 0, good)
            new_opt["loss_scale"] = {"scale": scale, "good_steps": good}
            metrics["loss_scale"] = scale
            metrics["nonfinite_layers"] = nonfinite
        return new_params, new_opt, metrics

    return step


# ===========================================================================
# Prefill (inference forward): layer-major relay, no stash, no backward
# ===========================================================================
def make_prefill_fn(model, exec_cfg: ExecutionConfig,
                    placements: Optional[EPSPlacements] = None) -> Callable:
    """Returns prefill(params, batch) -> last-token logits (B, vocab).
    Exercises the full prefill compute with the L2L weight relay."""
    if placements is None:
        placements = make_placements(exec_cfg, len(model.groups))
    UB = exec_cfg.n_microbatches
    PF = exec_cfg.prefetch_depth
    PK = exec_cfg.pack_params
    G = exec_cfg.layers_per_relay
    TR = exec_cfg.transport
    DYN = exec_cfg.dynamic_depth
    if DYN:
        assert len(model.groups) == 1, \
            "dynamic_depth supports single-group models"

    def prefill(params, batch, n_active=None):
        if DYN:
            assert n_active is not None, \
                "dynamic_depth: prefill takes a traced n_layers operand"
            act_win = (jnp.int32(0), jnp.asarray(n_active, jnp.int32))
        else:
            assert n_active is None, \
                "n_layers operand needs ExecutionConfig.dynamic_depth"
            act_win = None
        static = {"embed": params["embed"], "head": params["head"]}
        batch_ub = _reshape_ub(batch, UB)
        ub_slice = jax.tree.map(lambda a: a[0], batch_ub)

        def prep_one(b):
            x, _ = model.prepare(static, b)
            return x
        x_ub = jax.lax.map(prep_one, batch_ub)

        for gi, group in enumerate(model.groups):
            if gi > 0:
                x_prev = x_ub
                x_ub = jax.lax.map(
                    lambda b_x: model.transition_x(gi, static, b_x[1], b_x[0]),
                    (batch_ub, x_prev))
                mem_ub = (jax.lax.map(
                    lambda b_x: model.transition_mem(gi, static, b_x[1],
                                                     b_x[0]),
                    (batch_ub, x_prev)) if group.has_mem else None)
            else:
                mem_ub = None
            ctx = model.train_ctx(ub_slice, group)
            wp = placements.weights[gi]

            def fwd_body(x_c, slots, _x, _g=group, _ctx=ctx, _mem=mem_ub):
                (w,) = slots
                if PK:
                    w = packing.unpack(w)
                def ub_body(_, args):
                    if _mem is None:
                        y, _aux = _g.apply(w, args, None, _ctx)
                    else:
                        x_i, m_i = args
                        y, _aux = _g.apply(w, x_i, m_i, _ctx)
                    return None, y
                xs = x_c if _mem is None else (x_c, _mem)
                _, y_ub = jax.lax.scan(ub_body, None, xs)
                return y_ub, None

            fwd_idle = None
            if DYN:
                def fwd_idle(x_c, slots, _x):
                    return x_c, None

            x_ub, _ = relay_scan(
                fwd_body, x_ub, (Stream(wp, params["groups"][gi]),),
                group=G, prefetch=PF, unroll=exec_cfg.unroll_layers,
                transport=TR, active=act_win, idle_body=fwd_idle)

        # last-position logits per microbatch
        def head_one(x_i):
            return model.decode_logits(static, x_i[:, -1:, :])[:, 0]
        logits_ub = jax.lax.map(head_one, x_ub)
        return logits_ub.reshape(-1, logits_ub.shape[-1])

    return prefill


# ===========================================================================
# Loss+grads only (no optimizer) — for equivalence tests & benchmarks
# ===========================================================================
def make_grads_fn(model, exec_cfg: ExecutionConfig,
                  placements: Optional[EPSPlacements] = None) -> Callable:
    """Returns grads(params, batch) -> (loss, grads) computed with the L2L
    schedule (layer-major, recompute).  Used to assert gradient identity
    with Algorithm 2 and by the Alg-3 benchmarks."""
    # deliberate WHITELIST of the schedule/layout knobs (not a
    # dataclasses.replace): the grad-collector path must not inherit
    # update-time behavior — amp loss scaling (its loss_scale opt state
    # is never initialized here), host_optimizer, clipping
    cfg_noeager = ExecutionConfig(
        n_microbatches=exec_cfg.n_microbatches,
        offload_stash=exec_cfg.offload_stash,
        weight_stream=exec_cfg.weight_stream,
        stash_every=exec_cfg.stash_every,
        segment_scan=exec_cfg.segment_scan,
        dynamic_depth=exec_cfg.dynamic_depth,
        prefetch_depth=exec_cfg.prefetch_depth,
        pack_params=exec_cfg.pack_params,
        layers_per_relay=exec_cfg.layers_per_relay,
        unroll_layers=exec_cfg.unroll_layers,
        transport=exec_cfg.transport,
        eager_optimizer=False, clip_mode="none")
    return _make_loss_and_grads(model, cfg_noeager, placements)


def _make_loss_and_grads(model, exec_cfg, placements=None):
    """L2L forward+backward that RETURNS grads (Alg 3 without the update)."""
    if placements is None:
        placements = make_placements(exec_cfg, len(model.groups))

    base_step = make_train_step(
        model, _grad_collector(), exec_cfg, placements)

    def fn(params, batch, n_active=None):
        opt = init_opt_state(_grad_collector(), params)
        new_params, new_opt, metrics = base_step(params, opt, batch,
                                                 n_active)
        # _grad_collector stores grads in the "m" slot of the opt state
        # (packed groups hold it as one weight-aligned flat f32 segment —
        # unpack so callers always see the plain grad pytree)
        is_slot = lambda x: isinstance(x, dict) and set(x.keys()) == {"m"}
        unwrap = lambda t: jax.tree.map(lambda s: s["m"], t, is_leaf=is_slot)
        grads = {
            "embed": unwrap(new_opt["embed"]),
            "head": unwrap(new_opt["head"]),
            "groups": tuple(
                packing.unpack(g) if packing.is_packed(g) else g
                for g in (unwrap(g) for g in new_opt["groups"])),
        }
        return metrics["loss"], grads

    return fn


def _grad_collector() -> Optimizer:
    """An 'optimizer' that stores the gradient into its state and leaves
    params untouched — lets tests extract L2L grads through the normal
    step machinery."""
    def init(params):
        return jax.tree.map(
            lambda p: {"m": jnp.zeros(p.shape, jnp.float32)}, params)

    def update(grads, state, params, step):
        flat_g, treedef = jax.tree.flatten(grads)
        flat_s = [{"m": g.astype(jnp.float32)} for g in flat_g]
        return params, jax.tree.unflatten(treedef, flat_s)

    return Optimizer("collect", init, update)


# ===========================================================================
# Optimizer state init
# ===========================================================================
def init_opt_state(optimizer: Optimizer, params,
                   exec_cfg: Optional[ExecutionConfig] = None) -> dict:
    def group_opt(g):
        # packed group: slot-major flat buffers aligned to the weight spec
        if packing.is_packed(g):
            return packing.pack_opt(g.spec, optimizer.init(packing.unpack(g)))
        return optimizer.init(g)

    state = {
        "step": jnp.zeros((), jnp.int32),
        "embed": optimizer.init(params["embed"]),
        "head": optimizer.init(params["head"]),
        "groups": tuple(group_opt(g) for g in params["groups"]),
    }
    if exec_cfg is not None and exec_cfg.loss_scale_init > 0:
        state["loss_scale"] = {
            "scale": jnp.float32(exec_cfg.loss_scale_init),
            "good_steps": jnp.zeros((), jnp.int32)}
    return state
