"""Eager Param-Server (EPS): tier-chain memory placement.

The paper's EPS is a host process owning the model + optimizer state,
relaying layers to the device and eagerly reducing/optimizing.  The
placement is a tier CHAIN — device HBM <- pinned host <- mmap/NVMe:

* the two IN-JIT tiers are XLA memory spaces, ``pinned_host`` (host DRAM
  behind the chip's DMA engines) and ``device`` (HBM); a ``Placement``
  bundles the device_put helpers the L2L scans use,
* the third tier sits BELOW the process: with ``ExecutionConfig.tiers=3``
  the cold tail of the stacked state lives in a verified on-disk
  ``core.tierstore.SegmentStore`` and crosses the disk boundary around
  each jitted call (``TierChain.stage_in``/``stage_out``), under a host
  byte budget.  ``EPSPlacements.disk`` carries that tier's static spec
  (``TierChainSpec``); the live store/chain is built by the Engine.

``Placement`` helpers:

* ``host(tree)``       — put a pytree into pinned_host, preserving sharding
* ``dev(tree)``        — fetch into device HBM (the per-layer "relay")
* ``dev_grouped(tree)`` — fetch a G-layer relay SLOT (leading stop axis)
  into HBM; on a mesh the layer-slice pspecs shift one dim right
  (``P(None, *spec)``), elsewhere it is ``dev``.

This module only builds placements; the scan-level relay logic — which
layer/group a slot holds, how many DMAs are in flight — lives entirely in
``repro.core.relay`` (the one module issuing relay DMAs), and the disk
tier's staging/healing in ``repro.core.tierstore``.

Shardings are explicit NamedShardings derived from the param/activation
PartitionSpecs because ``jax.device_put`` inside jit needs a concrete
sharding (memory-kind-only transfers still re-state the spec).
"""
from __future__ import annotations

from typing import Any, Callable, NamedTuple, Optional

import jax
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding


class Placement(NamedTuple):
    host: Callable                           # tree -> tree (pinned_host)
    dev: Callable                            # tree -> tree (device HBM)
    dev_grouped: Optional[Callable] = None   # G-layer slot -> device HBM
    enabled: bool = True
    device: Any = None                       # the one device, if one


def noop_placement() -> Placement:
    ident = lambda t: t
    return Placement(ident, ident, ident, enabled=False)


def memories_supported() -> bool:
    """True when the backend honors memory-space transfers inside jit.

    Verified empirically: the CPU backend silently DROPS
    ``jax.device_put(x, <memory_kind>)`` during lowering (zero
    pinned_host/annotate ops in the StableHLO) and its SPMD partitioner
    rejects memory-kind output annotations.  On TPU the same program text
    lowers to host-offload annotate custom calls.  All placements degrade
    to no-ops on unsupported backends — the L2L schedule (loop inversion,
    recompute, eager updates) is unchanged; only the physical two-tier
    residency needs TPU.  See DESIGN.md and EXPERIMENTS.md §Dry-run.
    """
    return jax.default_backend() == "tpu"


def single_device_placement(device=None) -> Placement:
    """For single-host tests/benchmarks: one device, two memory spaces."""
    dev = device or jax.devices()[0]
    h = SingleDeviceSharding(dev, memory_kind="pinned_host")
    d = SingleDeviceSharding(dev, memory_kind="device")

    def to(tree, sh):
        return jax.tree.map(lambda a: jax.device_put(a, sh), tree)

    move = lambda t: to(t, d)
    return Placement(lambda t: to(t, h), move, move, device=dev)


def mesh_placement(mesh, pspec_tree) -> Placement:
    """Sharded placement: pspec_tree mirrors the trees that will be moved
    (or is a single P applied to every leaf).  ``dev_grouped`` moves a
    G-layer relay slot: the per-layer-slice specs apply one dim to the
    right of the (never sharded) leading stop axis."""

    def build(tree, kind, lead=False):
        def one(a, spec):
            if lead:
                spec = P(None, *spec)
            sh = NamedSharding(mesh, spec, memory_kind=kind)
            return jax.device_put(a, sh)
        if isinstance(pspec_tree, P):
            return jax.tree.map(lambda a: one(a, pspec_tree), tree)
        return jax.tree.map(one, tree, pspec_tree)

    return Placement(lambda t: build(t, "pinned_host"),
                     lambda t: build(t, "device"),
                     lambda t: build(t, "device", lead=True))


class TierChainSpec(NamedTuple):
    """Static config of the third (disk) tier — HBM <- host <- THIS.

    Built from an ExecutionConfig by ``tier_spec``; the Engine turns it
    into a live ``core.tierstore.SegmentStore`` + ``TierChain``.  Unlike
    the memory-space tiers this one works on EVERY backend: it is host
    numpy I/O around the jit boundary, not an in-program annotation."""
    host_budget: int         # resident stacked-state byte budget (0 = none:
                             # demote everything — the fully-streamed mode)
    directory: str           # segment-store root ("" = engine temp dir)
    retries: int             # transient-read retry budget
    backoff_s: float         # initial exponential-backoff delay


def tier_spec(exec_cfg) -> Optional[TierChainSpec]:
    """The disk-tier spec of an ExecutionConfig, or None for the
    historical two-tier placement (``tiers=2``)."""
    if getattr(exec_cfg, "tiers", 2) < 3:
        return None
    return TierChainSpec(
        host_budget=int(getattr(exec_cfg, "host_budget_bytes", 0)),
        directory=str(getattr(exec_cfg, "tier_dir", "")),
        retries=int(getattr(exec_cfg, "tier_retries", 3)),
        backoff_s=float(getattr(exec_cfg, "tier_backoff_s", 0.01)))


class EPSPlacements(NamedTuple):
    """Per-use-site placements for one training/serving setup.

    ``weights[g]`` / ``opts[g]`` move one relay slot of group g (a layer
    slice, or a G-layer sub-stack via ``dev_grouped``); ``stash`` moves
    boundary-activation trees (a single P is broadcast to every leaf).
    The slot schedule itself (prefetch ring, layer groups) is
    ``repro.core.relay``'s job.  ``disk`` extends the chain below host
    DRAM (``tiers=3``): the static ``TierChainSpec`` of the verified
    NVMe segment store, or None for the two-tier placement."""
    weights: tuple           # tuple[Placement], one per layer group
    opts: tuple              # tuple[Placement], one per layer group
    stash: Placement
    disk: Optional[TierChainSpec] = None


def pspecs_like(pspec_tree, target_tree):
    """Broadcast a param-shaped pspec tree onto a state tree whose leaves
    replace each param leaf with a subtree of same-shaped arrays (adam m/v)."""
    is_p = lambda x: isinstance(x, P)
    flat_p, treedef = jax.tree.flatten(pspec_tree, is_leaf=is_p)
    flat_t = treedef.flatten_up_to(target_tree)
    out = [jax.tree.map(lambda _, _p=p: _p, t) for p, t in zip(flat_p, flat_t)]
    return jax.tree.unflatten(treedef, out)


def make_placements(exec_cfg, n_groups: int, mesh=None,
                    weight_pspecs=None, opt_pspecs=None,
                    stash_pspec=None) -> EPSPlacements:
    """Single-device (tests/benchmarks) or mesh-sharded placements.

    ``weight_pspecs``/``opt_pspecs``: per-group pspec trees for one layer
    slice; required when mesh is given and streaming is on."""
    noop = noop_placement()
    disk = tier_spec(exec_cfg)
    if not memories_supported():
        # backend drops memory-space transfers inside jit (CPU): the two
        # IN-JIT tiers become logical-only; the L2L schedule itself is
        # unchanged.  The disk tier is host-side I/O around the jit
        # boundary, so it stays PHYSICAL on every backend.
        return EPSPlacements((noop,) * n_groups, (noop,) * n_groups, noop,
                             disk)
    if mesh is None:
        single = single_device_placement()
        w = single if exec_cfg.weight_stream else noop
        s = single if exec_cfg.offload_stash else noop
        return EPSPlacements((w,) * n_groups, (w,) * n_groups, s, disk)
    ws = tuple(mesh_placement(mesh, weight_pspecs[g]) for g in range(n_groups)) \
        if exec_cfg.weight_stream else (noop,) * n_groups
    os_ = tuple(mesh_placement(mesh, opt_pspecs[g]) for g in range(n_groups)) \
        if exec_cfg.weight_stream else (noop,) * n_groups
    st = mesh_placement(mesh, stash_pspec if stash_pspec is not None else P()) \
        if exec_cfg.offload_stash else noop
    return EPSPlacements(ws, os_, st, disk)
