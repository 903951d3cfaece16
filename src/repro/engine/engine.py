"""The Engine facade — the one public way to run any execution schedule.

An Engine owns a model, an ExecutionConfig, an optimizer and the EPS
placements, and exposes the full lifecycle over a single ``TrainState``
pytree::

    from repro import engine as engines

    eng = engines.create("l2l-p", get_config("bert-large", "smoke"),
                         ExecutionConfig(n_microbatches=4))
    state = eng.init(jax.random.PRNGKey(0))
    state, metrics = eng.train_step(state, batch)     # lazily jitted
    logits = eng.prefill(state.params, batch)
    eng.save(ckpt_dir, state)

Registered schedules:

* ``baseline`` — Algorithms 1/2 (conventional execution; microbatch loop
  inner, monolithic update).
* ``l2l``      — Algorithm 3 (layer-major relay, trailing optimizer).
* ``l2l-p``    — Algorithm 4 (layer-major relay, eager per-layer
  optimizer overlapped with the backward).

The ``repro.core`` kernels (``l2l``/``baseline``/``decode``) stay
internal: every consumer — launchers, benchmarks, examples, tests — goes
through this facade, so new schedules (pipelined, multi-device relay)
only have to subclass ``Engine`` and ``@register`` themselves.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax

from repro.checkpoint import io as ckpt_io
from repro.configs.base import ModelConfig
from repro.core import baseline as _baseline, decode as _decode, l2l as _l2l
from repro.core import packing, relay
from repro.core.memory_model import MemoryReport, estimate
from repro.core.schedule import ExecutionConfig
from repro.engine.placement import placements_for
from repro.engine.registry import register
from repro.engine.state import TrainState
from repro.models.model import LayeredModel
from repro.optim import Optimizer, adam


PALLAS_TRANSPORT_ON_TPU = (
    "transport='pallas' does not run on TPU: the relay's stacked rows are "
    "packed (N, W) segments and per-leaf biases and norm scales, and the "
    "chip's compiler refuses a 1-row DMA slice of such a 2-D buffer "
    "('Slice shape along dimension 0 must be aligned to tiling (8)'). "
    "The chip takes a pinned_host row DMA only along an untiled leading "
    "axis, of f32 rows shaped (8k, C >= 256), a layout the relay's "
    "buffers do not have.  Use transport='xla'.")


class Engine:
    """Base facade: lifecycle + lazy jit over a schedule's kernels.

    Subclasses implement ``_make_step_kernel``/``_make_grads_kernel``/
    ``_init_opt_legacy`` and set ``name``/``memory_mode``.
    """
    name = "base"
    memory_mode = "baseline"

    def __init__(self, model, exec_cfg: Optional[ExecutionConfig] = None, *,
                 optimizer: Optional[Optimizer] = None, mesh=None,
                 rules=None, placements=None, donate: bool = True):
        if isinstance(model, ModelConfig):
            model = LayeredModel(model)
        self.model = model
        self.exec_cfg = self._normalize_cfg(exec_cfg or ExecutionConfig())
        if (self.exec_cfg.transport == "pallas"
                and jax.default_backend() == "tpu"):
            raise NotImplementedError(PALLAS_TRANSPORT_ON_TPU)
        self.optimizer = optimizer or adam()
        self.mesh = mesh
        self._rules = rules
        self._placements = placements
        self._donate = donate
        self._fns: dict = {}        # lazily built kernels / jitted wrappers
        # the relay bytes of each compiled train step, by the batch's
        # shapes (``relay.count_bytes``), and the run's totals
        self._step_bytes: dict = {}
        self._counters = {"train_steps": 0, "relay_fetch_bytes": 0,
                          "relay_writeback_bytes": 0}

    # -- schedule-specific hooks (override in subclasses) -------------------
    def _normalize_cfg(self, exec_cfg: ExecutionConfig) -> ExecutionConfig:
        return exec_cfg

    def _make_step_kernel(self):
        raise NotImplementedError

    def _make_grads_kernel(self):
        raise NotImplementedError

    def _init_opt_legacy(self, params) -> dict:
        raise NotImplementedError

    # -- placements ---------------------------------------------------------
    @property
    def placements(self):
        if self._placements is None:
            self._placements = placements_for(
                self.model, self.exec_cfg, mesh=self.mesh, rules=self._rules,
                optimizer=self.optimizer)
        return self._placements

    # -- storage tier (ExecutionConfig.tiers = 3) ---------------------------
    @property
    def tier(self):
        """The live disk-tier adapter (``core.tierstore.TierChain``), or
        None for the historical two-tier placement.  Built lazily from
        ``placements.disk``; the segment store lives in
        ``exec_cfg.tier_dir`` (a fresh temp dir when unset).  Around
        every jitted call the chain re-materializes the demoted cold row
        tail of each layer group and writes updated rows back through
        verified, crash-consistent segment files — byte-identical to the
        host-only relay (tests/test_tierstore.py)."""
        spec = self.placements.disk
        if spec is None:
            return None
        if "tier" not in self._fns:
            import tempfile
            from repro.core import tierstore
            root = spec.directory or tempfile.mkdtemp(prefix="eps-tier-")
            store = tierstore.SegmentStore(
                root, retries=spec.retries, backoff_s=spec.backoff_s)
            self._fns["tier"] = tierstore.TierChain(
                store, host_budget=spec.host_budget,
                layers_per_relay=self.exec_cfg.layers_per_relay,
                prefetch_depth=self.exec_cfg.prefetch_depth)
        return self._fns["tier"]

    def _materialize(self, params):
        """Params with demoted groups re-read from the segment store
        (identity-cached inside the chain) — every read verified, retried
        on transient errors, quarantined + rebuilt on checksum failure."""
        tier = self.tier
        return params if tier is None else tier.materialize_params(params)

    # -- packed relay (ExecutionConfig.pack_params) -------------------------
    def _relay_params(self, params):
        """Params in the layout the relay kernels expect: with
        ``pack_params`` the stacked layer groups are coalesced into
        per-dtype flat buffers (``core.packing``) so each EPS relay is one
        large DMA per layer.  Idempotent — already-packed groups pass
        through, so callers may hand either layout to ``grads`` /
        ``prefill`` / ``decode_*``.  The last conversion is cached by
        object identity: a serving loop that calls ``decode_step`` with
        the same unpacked params every token packs once, not per token
        (params trees are never mutated in place anywhere in this repo)."""
        if not self.exec_cfg.pack_params:
            return params
        if all(packing.is_packed(g) for g in params["groups"]):
            return params
        cached = self._fns.get("_pack_cache")
        if cached is not None and cached[0] is params:
            return cached[1]
        packed = packing.pack_params(params)
        self._fns["_pack_cache"] = (params, packed)
        return packed

    # -- state lifecycle ----------------------------------------------------
    def init(self, rng) -> TrainState:
        """Materialize parameters + optimizer state from a PRNG key, placed
        where the train step keeps them (``_place``).  With the storage
        tier enabled the fresh state is adopted by the TierChain:
        segments written to the store, cold rows demoted."""
        params = self.model.init_params(rng)
        if self.exec_cfg.pack_params:
            # not through _relay_params: its identity cache would pin the
            # unpacked device copy for the engine's lifetime
            params = packing.pack_params(params)
        state = self._place(
            TrainState.from_legacy(params, self._init_opt_legacy(params)))
        if self.tier is not None:
            state = self.tier.adopt(state, step=0)
        return state

    def _place(self, state: TrainState) -> TrainState:
        """Commit a fresh or restored state to where the train step keeps
        it, so that the first step runs the same program as every later
        one.  Every leaf is committed to the device, as the step's outputs
        are (jit keys its cache on that), and ``_to_eps`` puts the stacked
        group rows where the schedule relays them from.  Mesh engines
        place their state through their own shardings."""
        if self.mesh is not None:
            return state
        return self._to_eps(jax.device_put(state, jax.devices()[0]))

    def _to_eps(self, state: TrainState) -> TrainState:
        """Schedules without a relay keep the whole state on the device."""
        return state

    def abstract_state(self) -> TrainState:
        """ShapeDtypeStruct TrainState (for lowering / restore targets)."""
        params_abs = jax.eval_shape(self._relay_params,
                                    self.model.abstract_params())
        opt_abs = jax.eval_shape(self._init_opt_legacy, params_abs)
        return TrainState.from_legacy(params_abs, opt_abs)

    def state_fingerprint(self) -> str:
        """Stable identity of the on-disk state layout: a checkpoint is
        only restorable into the same (arch, depth, width, vocab,
        optimizer) tuple.  Relay knobs (pack/group/prefetch/K) are
        deliberately absent — checkpoints interchange across them."""
        cfg = self.model.cfg
        return (f"{cfg.name}:L{cfg.n_layers}:d{cfg.d_model}:"
                f"v{cfg.vocab_size}:opt={self.optimizer.name}")

    def save(self, directory: str, state: TrainState,
             step: Optional[int] = None, prefix: str = "ckpt",
             keep_last: int = 0) -> str:
        """Checkpoints are always written in the UNPACKED pytree layout —
        a packed engine's flat buffers are converted through their
        PackSpecs first, so checkpoints interchange freely between
        pack_params on/off (tests/test_packing.py).  The write is
        crash-consistent (staged + fsynced + atomically renamed, crc32
        per array in the manifest — ``checkpoint.io``); ``keep_last=N``
        prunes all but the N newest snapshots after the save."""
        if self.tier is not None:
            # checkpoints hold the FULL state; also make this directory
            # the quarantine-rebuild source for the segment store
            state = self.tier.stage_in(state)
            self.tier.attach_checkpoints(directory, prefix, self)
        step = int(state.step) if step is None else int(step)
        params, opt = state.params, state.legacy_opt()
        if self.exec_cfg.pack_params:
            opt = packing.unpack_opt_state(opt, params)
            params = packing.unpack_params(params)
        return ckpt_io.save_train_state(
            directory, params, opt, step, prefix=prefix,
            keep_last=keep_last, fingerprint=self.state_fingerprint())

    def restore(self, directory: str, step: Optional[int] = None,
                like: Optional[TrainState] = None, prefix: str = "ckpt"):
        """Returns (TrainState, step).  ``like`` defaults to the engine's
        abstract state; packed engines restore the unpacked checkpoint
        layout and re-pack.  With ``step=None`` the newest snapshot that
        passes crc32 + fingerprint verification is used — a corrupt or
        half-written snapshot falls back to the previous good one."""
        like = like if like is not None else self.abstract_state()
        like_p, like_o = like.params, like.legacy_opt()
        if self.exec_cfg.pack_params:
            like_o = jax.eval_shape(packing.unpack_opt_state, like_o, like_p)
            like_p = jax.eval_shape(packing.unpack_params, like_p)
        params, opt, step = ckpt_io.restore_train_state(
            directory, like_p, like_o, step=step, prefix=prefix,
            fingerprint=self.state_fingerprint())
        if self.exec_cfg.pack_params:
            params = packing.pack_params(params)
            opt = packing.pack_opt_state(opt, params)
        state = self._place(TrainState.from_legacy(params, opt))
        if self.tier is not None:
            state = self.tier.adopt(state, step=step)
            self.tier.attach_checkpoints(directory, prefix, self)
        return state, step

    # -- training -----------------------------------------------------------
    # -- runtime-dynamic depth ---------------------------------------------
    def _depth_operand(self, n_layers):
        """The traced int32 depth operand for a dynamic-depth call
        (defaults to the capacity depth); asserts the knob elsewhere."""
        import jax.numpy as jnp
        if not self.exec_cfg.dynamic_depth:
            assert n_layers is None, \
                "n_layers needs ExecutionConfig.dynamic_depth"
            return None
        cap = sum(g.n_layers for g in self.model.groups)
        n = cap if n_layers is None else int(n_layers)
        assert 0 <= n <= cap, f"n_layers {n} exceeds capacity {cap}"
        return jnp.asarray(n, jnp.int32)

    @property
    def step_fn(self):
        """Unjitted (state, batch[, n_layers]) -> (state, metrics) — for
        callers that manage jit/shardings themselves (dry-run lowering).
        With ``dynamic_depth`` the traced ``n_layers`` operand is part of
        the signature: one compiled program serves every depth."""
        if "step_fn" not in self._fns:
            kernel = self._make_step_kernel()

            def step(state: TrainState, batch, *n_layers):
                with relay.count_bytes() as moved:
                    new_p, new_o, metrics = kernel(
                        state.params, state.legacy_opt(), batch, *n_layers)
                self._step_bytes[_shapes(batch)] = moved
                return TrainState.from_legacy(new_p, new_o), metrics

            self._fns["step_fn"] = step
        return self._fns["step_fn"]

    def train_step(self, state: TrainState, batch, n_layers=None):
        """One optimizer step: (state, batch) -> (state, metrics).  With
        the storage tier the demoted cold rows are staged in from the
        segment store before the jitted step and the updated rows staged
        back out (verified, crash-consistent) after it.  With
        ``dynamic_depth``, ``n_layers`` (<= capacity, default capacity)
        picks the runtime depth without retracing."""
        if "train_step" not in self._fns:
            donate = (0,) if self._donate else ()
            self._fns["train_step"] = jax.jit(self.step_fn,
                                              donate_argnums=donate)
        tier = self.tier
        if tier is not None:
            state = tier.stage_in(state)
        n_op = self._depth_operand(n_layers)
        args = (state, batch) if n_op is None else (state, batch, n_op)
        state, metrics = self._fns["train_step"](*args)
        moved = self._step_bytes[_shapes(batch)]
        self._counters["train_steps"] += 1
        self._counters["relay_fetch_bytes"] += moved["fetch"]
        self._counters["relay_writeback_bytes"] += moved["writeback"]
        if tier is not None:
            state = tier.stage_out(state)
        return state, metrics

    def counters(self) -> dict:
        """The run's totals since the engine was made: ``train_steps``
        taken, and the bytes their relay moved host -> HBM
        (``relay_fetch_bytes``) and HBM -> host
        (``relay_writeback_bytes``).  Each compiled step's bytes are
        counted at the relay's move sites while it traces
        (``core.relay.count_bytes``); a call adds them on the host, with
        no device sync."""
        return dict(self._counters)

    # -- gradients (no update) ---------------------------------------------
    @property
    def grads_fn(self):
        """Unjitted (params, batch) -> (loss, grads)."""
        if "grads_fn" not in self._fns:
            self._fns["grads_fn"] = self._make_grads_kernel()
        return self._fns["grads_fn"]

    def grads(self, state_or_params, batch, n_layers=None):
        if "grads" not in self._fns:
            self._fns["grads"] = jax.jit(self.grads_fn)
        params = getattr(state_or_params, "params", state_or_params)
        n_op = self._depth_operand(n_layers)
        args = () if n_op is None else (n_op,)
        return self._fns["grads"](
            self._relay_params(self._materialize(params)), batch, *args)

    # -- inference ----------------------------------------------------------
    @property
    def prefill_fn(self):
        """Unjitted (params, batch) -> last-token logits (B, vocab)."""
        if "prefill_fn" not in self._fns:
            self._fns["prefill_fn"] = _l2l.make_prefill_fn(
                self.model, self.exec_cfg, self.placements)
        return self._fns["prefill_fn"]

    def prefill(self, state_or_params, batch, n_layers=None):
        if "prefill" not in self._fns:
            self._fns["prefill"] = jax.jit(self.prefill_fn)
        params = getattr(state_or_params, "params", state_or_params)
        n_op = self._depth_operand(n_layers)
        args = () if n_op is None else (n_op,)
        return self._fns["prefill"](
            self._relay_params(self._materialize(params)), batch, *args)

    @property
    def decode_step_fn(self):
        """Unjitted (params, caches, token, cur_pos) -> (logits, caches)."""
        if "decode_step_fn" not in self._fns:
            self._fns["decode_step_fn"] = _decode.make_serve_step(
                self.model, self.exec_cfg, self.placements)
        return self._fns["decode_step_fn"]

    def decode_init(self, state_or_params, tokens, live_seq: int,
                    frames=None, n_layers=None):
        """Prefill the decode caches from a prompt.
        Returns (caches, last_logits)."""
        params = getattr(state_or_params, "params", state_or_params)
        return _decode.prefill(self.model,
                               self._relay_params(self._materialize(params)),
                               tokens, live_seq,
                               exec_cfg=self.exec_cfg, frames=frames,
                               n_layers=n_layers)

    def decode_step(self, state_or_params, caches, token, cur_pos,
                    n_layers=None):
        if "decode_step" not in self._fns:
            self._fns["decode_step"] = jax.jit(self.decode_step_fn)
        params = getattr(state_or_params, "params", state_or_params)
        n_op = self._depth_operand(n_layers)
        args = () if n_op is None else (n_op,)
        return self._fns["decode_step"](
            self._relay_params(self._materialize(params)), caches,
            token, cur_pos, *args)

    # -- continuous-batching serve ------------------------------------------
    def serve_session(self, state_or_params, serve_cfg=None, **kw):
        """Open a continuous-batching serve session (``repro.serve``):
        a paged-KV ServeEngine over this engine's model, relay knobs and
        placements.  ``serve_cfg`` is a ``ServeConfig``; keyword shape
        knobs (max_batch, page_size, ...) build one when omitted::

            srv = eng.serve_session(params, max_batch=8, max_seq=64)
            srv.submit(prompt_ids, max_new=32)
            done = srv.run()
        """
        from repro.serve.engine import ServeConfig, ServeEngine
        params = self._materialize(
            getattr(state_or_params, "params", state_or_params))
        if serve_cfg is None:
            serve_cfg = ServeConfig(**kw)
        return ServeEngine(self, params, serve_cfg)

    def serve_memory_estimate(self, serve_cfg, **kw) -> MemoryReport:
        """Analytic serve-mode byte split (paged pool + slot state +
        relay transit) for this engine's knobs at a ServeConfig shape."""
        from repro.core.memory_model import estimate_serve
        kw.setdefault("weight_stream", self.exec_cfg.weight_stream)
        kw.setdefault("prefetch_depth", self.exec_cfg.prefetch_depth)
        kw.setdefault("pack_params", self.exec_cfg.pack_params)
        kw.setdefault("layers_per_relay", self.exec_cfg.layers_per_relay)
        kw.setdefault("transport", self.exec_cfg.transport)
        return estimate_serve(
            self.model, max_batch=serve_cfg.max_batch,
            page_size=serve_cfg.page_size, n_pages=serve_cfg.n_pages,
            max_seq=serve_cfg.max_seq,
            prefill_chunk=serve_cfg.prefill_chunk, **kw)

    # -- analysis -----------------------------------------------------------
    def memory_estimate(self, *, batch: int, seq: int,
                        **kw) -> MemoryReport:
        """Analytic two-tier device/EPS byte split (paper eqs. 1-4) for
        this engine's schedule at the given shape."""
        kw.setdefault("n_microbatches", self.exec_cfg.n_microbatches)
        kw.setdefault("offload_stash", self.exec_cfg.offload_stash)
        kw.setdefault("stash_every", self.exec_cfg.stash_every)
        kw.setdefault("segment_scan", self.exec_cfg.segment_scan)
        kw.setdefault("prefetch_depth", self.exec_cfg.prefetch_depth)
        kw.setdefault("pack_params", self.exec_cfg.pack_params)
        kw.setdefault("layers_per_relay", self.exec_cfg.layers_per_relay)
        kw.setdefault("tiers", self.exec_cfg.tiers)
        kw.setdefault("host_budget", self.exec_cfg.host_budget_bytes)
        kw.setdefault("transport", self.exec_cfg.transport)
        return estimate(self.model, batch=batch, seq=seq,
                        mode=self.memory_mode, **kw)

    def describe(self) -> dict:
        return {"engine": self.name,
                "arch": self.model.cfg.name,
                "exec": dataclasses.asdict(self.exec_cfg)}


def _shapes(batch) -> tuple:
    """What a compiled step's relay bytes depend on besides the engine:
    the shapes and dtypes of the batch."""
    return tuple((a.shape, str(a.dtype)) for a in jax.tree.leaves(batch))


# ===========================================================================
# Registered schedules
# ===========================================================================
@register("baseline")
class BaselineEngine(Engine):
    """Algorithms 1/2: conventional execution; Alg 2 (gradient
    accumulation) when ``n_microbatches > 1``."""
    name = "baseline"

    def _normalize_cfg(self, exec_cfg):
        # conventional execution has no relay — the packed flat-buffer
        # layout, the pallas copy transport and the relay's runtime-
        # dynamic depth gating are L2L concerns; the baseline kernels
        # speak pytrees and never issue relay copies
        return dataclasses.replace(exec_cfg, pack_params=False,
                                   transport="xla", dynamic_depth=False)

    @property
    def memory_mode(self):
        return "baseline_remat" if self.exec_cfg.remat else "baseline"

    def _make_step_kernel(self):
        return _baseline.make_train_step(self.model, self.optimizer,
                                         self.exec_cfg)

    def _make_grads_kernel(self):
        return _baseline.make_grads_fn(self.model, self.exec_cfg)

    def _init_opt_legacy(self, params):
        return _baseline.init_opt_state(self.optimizer, params)


class _L2LBase(Engine):
    def _to_eps(self, state):
        """Stacked group rows and their optimizer slots in the EPS: the
        placements' host memory space (pinned_host on TPU with
        ``weight_stream``, an identity where the placements are no-ops),
        the same space the step hands them back in."""
        pl = self.placements

        def host(places, groups):
            return tuple(p.host(g) for p, g in zip(places, groups))

        return state.replace(
            params={**state.params,
                    "groups": host(pl.weights, state.params["groups"])},
            opt_state={**state.opt_state,
                       "groups": host(pl.opts, state.opt_state["groups"])})

    def _make_step_kernel(self):
        return _l2l.make_train_step(self.model, self.optimizer,
                                    self.exec_cfg, self.placements)

    def _make_grads_kernel(self):
        return _l2l.make_grads_fn(self.model, self.exec_cfg,
                                  self.placements)

    def _init_opt_legacy(self, params):
        return _l2l.init_opt_state(self.optimizer, params, self.exec_cfg)


@register("l2l")
class L2LEngine(_L2LBase):
    """Algorithm 3: layer-major relay; gradients shipped to the EPS and
    applied in a trailing layer loop."""
    name = "l2l"
    memory_mode = "l2l"

    def _normalize_cfg(self, exec_cfg):
        return dataclasses.replace(exec_cfg, eager_optimizer=False)


@register("l2l-p")
class L2LPEngine(_L2LBase):
    """Algorithm 4 (L2L-p): the optimizer for layer l runs inside the
    reverse scan, overlapping the backward of layer l-1, with per-layer
    eager gradient reduction."""
    name = "l2l-p"
    memory_mode = "l2l_p"

    def _normalize_cfg(self, exec_cfg):
        return dataclasses.replace(exec_cfg, eager_optimizer=True)
