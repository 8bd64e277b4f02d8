"""Training launcher: a thin shim over ``training.job.TrainingJob``.

The training loop, heartbeat cadence, checkpoint cadence, DP scaling,
and crash recovery all live in the job object (the same one the
step-driven tests and the thread-backed runtime drive); this module only
parses flags, builds the token log, and reports progress.  The
``ProcessSupervisor`` in ``launch/cluster.py`` wraps this entry point to
get Let-It-Crash at the OS-process level — on a silent heartbeat it
kills the process and relaunches with ``--resume``, and the job rebuilds
from the event-sourced checkpoint + token log at the exact committed
stream position.

Usage:
  PYTHONPATH=src python -m repro.launch.train --arch llama3.2-1b --steps 50
  ... --resume --checkpoint-dir /tmp/ckpt       # resume after a crash
  ... --dp 2 --elastic --max-dp 4               # autoscaled DP elasticity
  ... --scale-at 10:4 --kill-worker-at 6        # scripted scale/chaos drill
"""

from __future__ import annotations

import argparse
import json
import os
import time
from typing import Optional

import jax.numpy as jnp

from repro.config import TrainingConfig, get_arch
from repro.core.elastic import AutoscalerConfig
from repro.data.pipeline import build_token_log
from repro.launch.compile_cache import enable_compile_cache
from repro.models.zoo import build_model
from repro.telemetry.metrics import MetricsHub
from repro.training.job import TrainingJob


def heartbeat(path: Optional[str], step: int) -> None:
    """Touch the heartbeat file the supervisor (cluster.py) watches."""
    if path:
        with open(path, "w") as fh:
            fh.write(f"{step} {time.time()}\n")


def parse_scale_at(spec: Optional[str]) -> dict:
    """``"10:4,20:2"`` -> {10: 4, 20: 2} (scripted scale events)."""
    out = {}
    if spec:
        for part in spec.split(","):
            step, units = part.split(":")
            out[int(step)] = int(units)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3.2-1b")
    ap.add_argument("--full-size", action="store_true",
                    help="full config (default: smoke config, CPU-sized)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch-size", type=int, default=8)
    ap.add_argument("--seq-len", type=int, default=64)
    ap.add_argument("--partitions", type=int, default=3)
    ap.add_argument("--num-docs", type=int, default=4096)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--schedule", default="cosine")
    ap.add_argument("--microbatch", type=int, default=0)
    ap.add_argument("--grad-compression", default="none")
    ap.add_argument("--checkpoint-dir", default=None)
    ap.add_argument("--checkpoint-every", type=int, default=20)
    ap.add_argument("--async-ckpt", action="store_true",
                    help="write-behind checkpointing: snapshots + journal "
                         "lines land off the step barrier; offsets commit "
                         "as each step's journal ticket resolves")
    ap.add_argument("--ckpt-shards", type=int, default=1,
                    help="snapshot shard files per checkpoint (manifest-"
                         "committed; restore merges any shard layout)")
    ap.add_argument("--handoff", action="store_true",
                    help="live state handoff: stream the sharded state "
                         "through a durable topic at remesh points so a "
                         "healing process resumes at the exact handoff "
                         "step instead of replaying from the last snapshot")
    ap.add_argument("--handoff-every", type=int, default=0,
                    help="also publish a full handoff every N steps "
                         "(0: only at remesh points)")
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--heartbeat-file", default=None)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--data-seed", type=int, default=0)
    ap.add_argument("--log-every", type=int, default=10)
    # -- elasticity / chaos (the live pool event surface) ------------------
    ap.add_argument("--dp", type=int, default=1,
                    help="initial data-parallel degree (pool workers)")
    ap.add_argument("--max-dp", type=int, default=8)
    ap.add_argument("--elastic", action="store_true",
                    help="autoscale DP on stream backlog (queue-depth policy)")
    ap.add_argument("--mesh", action="store_true",
                    help="device-level DP: scale events reshard onto a new "
                         "mesh (needs >= dp * model-parallel devices)")
    ap.add_argument("--model-parallel", type=int, default=1)
    ap.add_argument("--scale-at", default=None, metavar="STEP:UNITS[,..]",
                    help="scripted scale events, e.g. 10:4,20:2")
    ap.add_argument("--kill-worker-at", type=int, default=0,
                    help="chaos drill: silence a DP worker at this step")
    ap.add_argument("--crash-at-step", type=int, default=0,
                    help="failure drill: hard-exit at this step")
    ap.add_argument("--heartbeat-timeout", type=float, default=5.0,
                    help="pool-level worker heartbeat timeout (now-ticks)")
    # accepted for back-compat with older drill scripts; the ordered
    # pipeline derives queue count and routing from the partition count
    ap.add_argument("--queues", type=int, default=None, help=argparse.SUPPRESS)
    ap.add_argument("--scheduler", default=None, help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = get_arch(args.arch, smoke=not args.full_size)
    tcfg = TrainingConfig(
        learning_rate=args.lr,
        schedule=args.schedule,
        warmup_steps=max(args.steps // 10, 1),
        decay_steps=args.steps,
        stable_steps=max(args.steps // 2, 1),
        microbatch_size=args.microbatch,
        grad_compression=args.grad_compression,
    )
    model = build_model(cfg, compute_dtype=jnp.float32)
    log = build_token_log(
        vocab_size=cfg.vocab_size,
        num_docs=args.num_docs,
        doc_len=args.seq_len + 1,
        partitions=args.partitions,
        seed=args.data_seed,
    )

    scale_at = parse_scale_at(args.scale_at)
    handoff = None
    if args.handoff:
        from repro.checkpoint.handoff import StateHandoffChannel
        from repro.data.topics import MessageLog

        # The handoff topic must survive process death, but the
        # launcher's token log is regenerated per process — so the
        # channel rides its own spilled broker under the checkpoint dir
        # (JSONL spill + manifest; ``reopen`` replays it on resume).
        hdir = os.path.join(
            args.checkpoint_dir or "/tmp/reactive-liquid", "handoff-log"
        )
        try:
            hlog = MessageLog.reopen(hdir)
        except FileNotFoundError:
            hlog = MessageLog(spill_dir=hdir)
        handoff = StateHandoffChannel(hlog, shards=max(args.ckpt_shards, 1))
    hub = MetricsHub()
    t0 = time.time()

    def on_step(step: int, metrics) -> None:
        heartbeat(args.heartbeat_file, step)
        if step % args.log_every == 0 or step == args.steps:
            hub.ingest(job.pool.merged_metrics())
            print(json.dumps({
                "step": step,
                "loss": round(float(metrics["loss"]), 4),
                "lr": round(float(metrics["lr"]), 6),
                "grad_norm": round(float(metrics["grad_norm"]), 3),
                "dp": job.dp,
                "tokens": hub.counter("train.tokens"),
                "wall_s": round(time.time() - t0, 1),
            }), flush=True)
        if step in scale_at:
            print(f"[scale] step {step}: dp {job.dp} -> {scale_at[step]}",
                  flush=True)
            job.request_scale(scale_at[step])
        if args.kill_worker_at and step == args.kill_worker_at:
            victim = job.kill_worker(0)
            print(f"[chaos] step {step}: silenced {victim}", flush=True)
        if args.crash_at_step and step == args.crash_at_step:
            print(f"[drill] hard crash at step {step}", flush=True)
            os._exit(42)  # no cleanup — Let-It-Crash

    job = TrainingJob(
        model, cfg, tcfg, log,
        batch_size=args.batch_size,
        seq_len=args.seq_len,
        dp=args.dp,
        max_dp=args.max_dp,
        elastic=args.elastic,
        autoscaler=AutoscalerConfig(
            min_workers=1, max_workers=args.max_dp,
            high_watermark=8.0, low_watermark=0.25, cooldown=5.0,
        ),
        heartbeat_timeout=args.heartbeat_timeout,
        checkpoint_dir=args.checkpoint_dir,
        checkpoint_every=args.checkpoint_every,
        async_checkpoint=args.async_ckpt,
        ckpt_shards=args.ckpt_shards,
        handoff=handoff,
        handoff_every=args.handoff_every,
        resume=args.resume,
        use_mesh=args.mesh,
        model_parallel=args.model_parallel,
        seed=args.seed,
        on_step=on_step,
    )
    if args.resume:
        print(f"[resume] restored step={job.applied_step()} "
              f"source={job.resume_source} "
              f"offsets={job.committed_offsets()}", flush=True)

    final_step = job.run(args.steps)
    hub.ingest(job.pool.merged_metrics())
    print(json.dumps({
        "final_step": final_step,
        "final_loss": job.losses[-1] if job.losses else None,
        "first_loss": job.losses[0] if job.losses else None,
        "dp": job.dp,
        "rescales": len(job.scale_log),
        "restarts": job.counter("train.trainer_restarts"),
        "tokens": hub.counter("train.tokens"),
    }), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
