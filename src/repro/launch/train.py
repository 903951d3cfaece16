"""Training driver.

End-to-end single-host training through the Engine facade (L2L-p by
default, Alg-3 L2L or the baseline for comparison) on the synthetic LM
pipeline::

    PYTHONPATH=src python -m repro.launch.train --arch bert-large \
        --engine l2l-p --steps 300 --batch 32 --seq 128 --ub 4

It runs on one device, unsharded: with ``--weight-stream`` on a TPU the
stacked layers and their optimizer slots live in pinned host memory and
are relayed to HBM one layer at a time (on CPU the placements are
logical only).  Run as a script, it keeps JAX's compile cache in
``JAX_COMPILATION_CACHE_DIR`` or ``<checkout>/.jax_cache``
(``launch.compile_cache``).  Checkpoints via the engine's save/restore.

Preemption safety: checkpoints are crash-consistent (staged + fsynced +
atomically renamed, crc32-verified on restore — ``repro.checkpoint.io``),
``--resume auto`` restarts from the newest snapshot that verifies, and
SIGTERM/SIGINT finish the in-flight step, save a snapshot plus a
``PREEMPTED.json`` marker, and exit cleanly — a killed-and-resumed run
reaches a final state bit-identical to an uninterrupted one
(tests/test_faults.py), because every step i is a pure function of
(state, batch(i)) with per-step-seeded data.
"""
from __future__ import annotations

import argparse
import json
import os
import signal
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import engine as engines
from repro.checkpoint import io as ckpt_io
from repro.configs.base import get_config
from repro.launch import compile_cache
from repro.core.schedule import ExecutionConfig
from repro.data.synthetic import DataConfig, SyntheticLM, add_modality_stubs
from repro.optim.optimizers import get_optimizer, make_schedule

PREEMPT_MARKER = "PREEMPTED.json"


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="bert-large")
    ap.add_argument("--variant", default="smoke",
                    choices=["smoke", "full"])
    ap.add_argument("--engine", default="l2l",
                    choices=["l2l", "l2l-p", "baseline"])
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=32)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--ub", type=int, default=4)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--warmup", type=int, default=20)
    ap.add_argument("--lr-schedule", default="cosine")
    ap.add_argument("--optimizer", default="adam",
                    choices=["adam", "adamw", "lamb", "sgd"])
    ap.add_argument("--clip", type=float, default=1.0)
    ap.add_argument("--no-eager", action="store_true",
                    help="with --engine l2l: trailing optimizer (Alg 3) "
                         "instead of the eager L2L-p schedule")
    ap.add_argument("--offload-stash", action="store_true")
    ap.add_argument("--stash-every", type=int, default=1,
                    help="K = layers per stashed boundary: checkpoint "
                         "only every K-th layer-boundary activation "
                         "(ceil(N/K) instead of N) and recompute the "
                         "in-between boundaries during the reverse relay "
                         "by re-streaming each segment's weights forward "
                         "(1 = historical stash-every-layer)")
    ap.add_argument("--weight-stream", action="store_true")
    ap.add_argument("--prefetch", type=int, default=0,
                    help="k = depth of the EPS relay prefetch ring: the "
                         "DMA for relay stop i+k is issued while stop i "
                         "computes (0 = serialized fetch, 1 = the "
                         "classic double buffer, k>1 = deeper ring)")
    ap.add_argument("--group", type=int, default=1,
                    help="G = layers per relay stop: one DMA covers G "
                         "stacked layers and the microbatch loop runs "
                         "the G-layer sub-stack (device weight footprint "
                         "G*(1+prefetch) layer slots)")
    ap.add_argument("--pack", action="store_true",
                    help="packed relay: coalesce each layer into one "
                         "flat buffer per dtype (one DMA per layer per "
                         "direction) and run the eager optimizer fused "
                         "on the flat segments")
    ap.add_argument("--transport", default="xla",
                    choices=["xla", "pallas"],
                    help="relay slot mover: 'xla' = device_put at scan "
                         "boundaries (overlap by XLA's scheduler), "
                         "'pallas' = double-buffered make_async_copy DMA "
                         "pipeline (overlap enforced by kernel "
                         "semaphores; bit-identical)")
    ap.add_argument("--tiers", type=int, default=2, choices=[2, 3],
                    help="memory tier chain: 2 = HBM <- pinned host "
                         "(historical), 3 = + verified on-disk "
                         "SegmentStore — the cold stacked-state tail "
                         "lives on NVMe and is staged around every step "
                         "(bit-identical; self-healing from checkpoints)")
    ap.add_argument("--host-budget", type=int, default=0,
                    help="with --tiers 3: resident stacked-state byte "
                         "budget — layer rows beyond it demote to disk "
                         "coldest-first (0 = demote everything, the "
                         "fully-streamed mode)")
    ap.add_argument("--tier-dir", default="",
                    help="with --tiers 3: segment-store root directory "
                         "(default: a fresh temp dir)")
    ap.add_argument("--host-optimizer", action="store_true",
                    help="run the optimizer on the EPS host "
                         "(compute_on 'device_host')")
    ap.add_argument("--skip-nonfinite", action="store_true",
                    help="anomaly sentinel: reject any step whose "
                         "gradients contain inf/nan — params, opt slots "
                         "and step counter stay bit-identical and the "
                         "step is counted in skipped_steps")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=0)
    ap.add_argument("--keep-last", type=int, default=0,
                    help="retention: keep only the newest N snapshots "
                         "(0 = keep all)")
    ap.add_argument("--resume", default="",
                    help="'auto' = restart from the newest VERIFIED "
                         "snapshot in --ckpt-dir (fresh run when none); "
                         "or an explicit checkpoint directory (errors "
                         "when it holds no good snapshot)")
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--step-delay-ms", type=int, default=0,
                    help="sleep after every step — widens the "
                         "kill/preemption window for the deterministic "
                         "fault-injection harness (repro.testing.faults)")
    ap.add_argument("--d-model", type=int, default=0,
                    help="override width (e.g. ~100M model)")
    ap.add_argument("--n-layers", type=int, default=0)
    ap.add_argument("--dynamic-depth", action="store_true",
                    help="depth as a RUNTIME value: the jitted step takes "
                         "a traced n_layers operand (--run-layers, default "
                         "the full capacity depth), so one compiled "
                         "program serves every depth <= capacity")
    ap.add_argument("--run-layers", type=int, default=0,
                    help="with --dynamic-depth: the runtime depth operand "
                         "(0 = the config's capacity depth)")
    args = ap.parse_args(argv)

    # historical CLI: "--engine l2l" means the eager L2L-p schedule unless
    # --no-eager asks for the Alg-3 trailing-update variant.
    engine_name = args.engine
    if engine_name == "l2l" and not args.no_eager:
        engine_name = "l2l-p"
    elif engine_name == "l2l-p" and args.no_eager:
        ap.error("--no-eager contradicts --engine l2l-p "
                 "(use --engine l2l --no-eager for Algorithm 3)")

    cfg = get_config(args.arch, args.variant)
    over = {"max_seq_len": max(cfg.max_seq_len, args.seq)}
    if args.d_model:
        over.update(d_model=args.d_model,
                    d_ff=args.d_model * 4,
                    n_heads=max(1, args.d_model // 64),
                    n_kv_heads=max(1, min(cfg.n_kv_heads,
                                          args.d_model // 64)))
    if args.n_layers:
        over["n_layers"] = args.n_layers
    cfg = cfg.replace(**over)

    opt = get_optimizer(
        args.optimizer,
        schedule=make_schedule(args.lr, warmup=args.warmup,
                               total=args.steps, kind=args.lr_schedule))
    exec_cfg = ExecutionConfig(
        n_microbatches=args.ub,
        offload_stash=args.offload_stash,
        stash_every=args.stash_every,
        weight_stream=args.weight_stream,
        prefetch_depth=args.prefetch,
        layers_per_relay=args.group,
        pack_params=args.pack,
        transport=args.transport,
        tiers=args.tiers,
        host_budget_bytes=args.host_budget,
        tier_dir=args.tier_dir,
        host_optimizer=args.host_optimizer,
        skip_nonfinite=args.skip_nonfinite,
        dynamic_depth=args.dynamic_depth,
        clip_mode="per_layer" if args.clip > 0 else "none",
        clip_norm=args.clip)
    if args.run_layers and not args.dynamic_depth:
        ap.error("--run-layers needs --dynamic-depth")
    run_layers = ((args.run_layers or cfg.n_layers)
                  if args.dynamic_depth else None)
    eng = engines.create(engine_name, cfg, exec_cfg, optimizer=opt)
    print(f"arch={cfg.name} engine={eng.name} params="
          f"{cfg.param_count()/1e6:.1f}M layers={cfg.n_layers} "
          f"d={cfg.d_model}")

    # ---- resume: newest verified snapshot wins; corrupt ones fall back
    start_step = 0
    resumed_from = None
    if args.resume:
        resume_dir = args.ckpt_dir if args.resume == "auto" else args.resume
        assert resume_dir, "--resume auto needs --ckpt-dir"
        good = ckpt_io.latest_good(resume_dir,
                                   fingerprint=eng.state_fingerprint())
        if good is not None:
            state, start_step = eng.restore(resume_dir, step=good)
            resumed_from = good
            print(f"resumed from {resume_dir} at step {start_step} "
                  f"(verified snapshot)", flush=True)
        elif args.resume != "auto":
            raise SystemExit(
                f"--resume {resume_dir}: no verifiable checkpoint")
        else:
            state = eng.init(jax.random.PRNGKey(args.seed))
    else:
        state = eng.init(jax.random.PRNGKey(args.seed))

    # ---- preemption: finish the in-flight step, save, exit resumable
    stop = {"sig": None}

    def _on_signal(signum, frame):
        stop["sig"] = signum

    old_handlers = {s: signal.signal(s, _on_signal)
                    for s in (signal.SIGTERM, signal.SIGINT)}

    def save_snapshot(step):
        eng.save(args.ckpt_dir, state, step=step,
                 keep_last=args.keep_last)
        return step

    data = SyntheticLM(DataConfig(vocab_size=cfg.vocab_size,
                                  seq_len=args.seq,
                                  global_batch=args.batch,
                                  seed=args.seed))
    losses = []
    skipped = 0
    compile_s = 0.0
    preempted = False
    last_saved = start_step if resumed_from is not None else None
    t0 = time.time()
    first = True
    for i in range(start_step, args.steps):
        # per-step seeded stub rng: batch(i) is a pure function of i, so
        # a resumed run replays the identical data stream
        rng = np.random.default_rng((args.seed, i))
        batch_np = add_modality_stubs(data.batch(i), cfg, rng)
        batch = {k: jnp.asarray(v) for k, v in batch_np.items()}
        if run_layers is None:
            state, metrics = eng.train_step(state, batch)
        else:
            state, metrics = eng.train_step(state, batch, run_layers)
        loss = float(metrics["loss"])
        losses.append(loss)
        skipped += int(metrics.get("skipped_steps", 0))
        if first:
            # the FIRST EXECUTED step includes the jit compile — on a
            # fresh run and equally on a --resume run, whose new process
            # re-jits on its first step: report it separately on both
            # paths and restart the s/step clock so the average is
            # steady-state only.
            first = False
            compile_s = time.time() - t0
            t0 = time.time()
            print(f"step {i:5d}  loss {loss:8.4f}  gnorm "
                  f"{float(metrics['grad_norm']):8.3f}  "
                  f"(compile+first step: {compile_s:.2f}s)", flush=True)
        elif (i - start_step) % args.log_every == 0 or i == args.steps - 1:
            dt = time.time() - t0
            print(f"step {i:5d}  loss {loss:8.4f}  gnorm "
                  f"{float(metrics['grad_norm']):8.3f}  "
                  f"{dt/max(i - start_step, 1):.2f}s/step", flush=True)
        if args.step_delay_ms:
            time.sleep(args.step_delay_ms / 1e3)
        if args.ckpt_dir and args.ckpt_every and \
                (i + 1) % args.ckpt_every == 0:
            last_saved = save_snapshot(i + 1)
        if stop["sig"] is not None:
            # in-flight step finished above — snapshot and leave a
            # resumable marker, then exit cleanly
            preempted = True
            if args.ckpt_dir:
                if last_saved != i + 1:
                    last_saved = save_snapshot(i + 1)
                with open(os.path.join(args.ckpt_dir, PREEMPT_MARKER),
                          "w") as f:
                    json.dump({"step": i + 1, "signal": int(stop["sig"]),
                               "total_steps": args.steps}, f)
            break
    t_loop_end = time.time()
    for s, h in old_handlers.items():
        signal.signal(s, h)
    # final save — exactly once even when steps is divisible by
    # --ckpt-every (the loop's periodic save already covered it)
    if args.ckpt_dir and not preempted and last_saved != args.steps:
        last_saved = save_snapshot(args.steps)
    if args.ckpt_dir and not preempted:
        marker = os.path.join(args.ckpt_dir, PREEMPT_MARKER)
        if os.path.exists(marker):
            os.remove(marker)
    # steady-state s/step over the post-compile steps THIS process ran
    # (len(losses) counts executed steps: a resumed run starts empty)
    steady = (round((t_loop_end - t0) / (len(losses) - 1), 4)
              if len(losses) > 1 else None)
    print(json.dumps({"final_loss": losses[-1] if losses else None,
                      "mean_last10": (float(np.mean(losses[-10:]))
                                      if losses else None),
                      "initial_loss": losses[0] if losses else None,
                      "compile_s": round(compile_s, 2),
                      "steady_s_per_step": steady,
                      "run_layers": run_layers,
                      "steps": args.steps,
                      "final_step": int(state.step),
                      "resumed_from": resumed_from,
                      "preempted": preempted,
                      "skipped_steps": skipped,
                      "tier_metrics": (eng.tier.metrics
                                       if eng.tier is not None else None)}))
    return losses


if __name__ == "__main__":
    compile_cache.enable()
    main()
