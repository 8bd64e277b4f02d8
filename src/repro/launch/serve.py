"""Serving driver: the reactive elastic pool over continuous-batched
decoding — the request queue is the elasticity signal, replicas scale out
across a traffic spike and drain back afterwards.

Two admission modes:

  * direct (default) — requests go straight into the pool's bounded
    ingress mailbox (``ElasticServingPool.submit``); overflow sheds or
    defers.
  * ``--log-backed`` — requests are appended to a durable ``requests``
    topic and flow through the virtual messaging layer into the same
    pool (``ServingJob``); completions land in a ``responses`` topic, so
    with ``--spill-dir`` the whole process can die and replay.

Usage:
  PYTHONPATH=src python -m repro.launch.serve --arch llama3.2-1b \
      --requests 32 --slots 4
  PYTHONPATH=src python -m repro.launch.serve --arch minicpm-2b \
      --full-size --paged --slots 8 --max-len 1024   # published widths, bf16
  PYTHONPATH=src python -m repro.launch.serve --stub --spike  # fast demo
  PYTHONPATH=src python -m repro.launch.serve --stub --log-backed \
      --kill-replica 0                        # chaos over the log
  PYTHONPATH=src python -m repro.launch.serve --stub --nodes 3 \
      --fail-prob 0.5                         # node-level chaos
  PYTHONPATH=src python -m repro.launch.serve --stub --nodes 2 --straggler 0
  PYTHONPATH=src python -m repro.launch.serve --stub \
      --tenants hi,mid,lo --priorities 2,1,0 --slo-ms 30,50,80 \
      --costs 0.25,0.5,1.0                    # multi-tenant fleet demo

Node-level chaos (``--nodes``/``--fail-prob``/``--straggler``) places the
replicas on a ``core.cluster.Cluster``: a node failure silences every
resident replica at once (generalizing the single-replica
``--kill-replica`` hook), the pool's supervisor relocates them to the
healthiest live node, and a straggler node dilates its residents — the
same placement layer the paper-figure simulations drive.
"""

from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.config import get_arch
from repro.core.elastic import AutoscalerConfig
from repro.launch.chaos import add_chaos_flags, build_cluster
from repro.launch.compile_cache import enable_compile_cache
from repro.models.zoo import build_model
from repro.serving import ElasticServingPool, Request, ServingJob


def build(args):
    """(model, params, vocab) for the served config.  ``--full-size``
    serves the published widths in bf16, weights included (MiniCPM-2B's
    2.7 B params in float32 would take 10.9 GB of a 16 GB v5e); the
    smoke config stays float32 for the CPU.  Params are made by one
    jitted init, so the device never holds a second, unstacked copy."""
    if args.stub:
        from repro.models.stub import StubModel

        model = StubModel()
        return model, model.init(jax.random.PRNGKey(args.seed)), 90
    cfg = get_arch(args.arch, smoke=not args.full_size)
    dtype = jnp.bfloat16 if args.full_size else jnp.float32
    model = build_model(cfg, compute_dtype=dtype, param_dtype=dtype)
    params = jax.jit(model.init)(jax.random.PRNGKey(args.seed))
    return model, params, cfg.vocab_size


def paged_spec(args):
    """The replica's page pool for ``--paged`` (None without it)."""
    if not args.paged:
        return None
    from repro.models.layers import PagedSpec

    per_slot = -(-args.max_len // args.page_size)
    return PagedSpec(num_pages=args.pages or 1 + args.slots * per_slot,
                     page_size=args.page_size)


def pool_kwargs(args, cluster=None) -> dict:
    """``ElasticServingPool`` / ``ServingJob`` keyword arguments from the
    command line."""
    return dict(
        paged=paged_spec(args),
        admission=args.admission,
        cluster=cluster,
        restart_cost=(args.restart_cost if cluster is not None else 0.0),
        slots_per_replica=args.slots,
        max_len=args.max_len,
        temperature=args.temperature,
        max_replicas=args.max_replicas,
        initial_units=1 if args.spike else args.slots,
        ingress_capacity=args.ingress_capacity,
        overflow=args.overflow,
        policy=args.policy,
        # A replica's decode batch and page pool are sized for all its
        # slots whatever its cap, so a cap below them only queues work
        # beside idle slots: the pool keeps one whole replica open and
        # scales by replicas.  --spike shows the slot ramp from one.
        autoscaler=AutoscalerConfig(high_watermark=4.0, low_watermark=0.5,
                                    min_workers=1 if args.spike else args.slots,
                                    cooldown=0.0, step_fraction=1.0),
        heartbeat_timeout=5.0,
    )


def run_fleet(args) -> int:
    """Multi-tenant fleet demo (``--tenants``): N co-resident serving
    pools on one cluster, cost-weighted packing + cross-pool priority
    preemption, vs ``--fleet-mode static`` partitioning."""
    from repro.serving.fleet import FleetManager, TenantSpec

    model, params, vocab = build(args)
    names = [s for s in args.tenants.split(",") if s]

    def per_tenant(flag, default, cast=float):
        vals = [cast(x) for x in flag.split(",")] if flag else []
        vals += [cast(default)] * (len(names) - len(vals))
        return vals[: len(names)]

    priorities = per_tenant(args.priorities, 0, int)
    slos = per_tenant(args.slo_ms, 50.0)   # 1 virtual tick ~ 1 ms
    costs = per_tenant(args.costs, 0.5)
    specs = [
        TenantSpec(
            name=n, model=model, params=params, priority=p, slo_ticks=s,
            cost=c, weight=(2.0 if c >= 1.0 else 1.0), slots=args.slots,
            max_len=args.max_len, max_replicas=args.max_replicas,
        )
        for n, p, s, c in zip(names, priorities, slos, costs)
    ]
    fm = FleetManager(specs, num_nodes=args.nodes or 6, cores=2,
                      mode=args.fleet_mode)
    rng = np.random.default_rng(args.seed)
    t0 = time.time()
    duration = max(args.requests, 10)
    killed = None
    now = 0.0
    for tick in range(duration):
        for i, name in enumerate(names):
            # the first (highest-listed) tenant bursts 3x mid-run; the
            # fleet hands it the others' idle capacity, static cannot.
            n_req = 3 if i == 0 and duration // 3 <= tick < 2 * duration // 3 else 1
            for _ in range(n_req):
                plen = int(rng.integers(2, 8))
                fm.submit(name, [int(x) for x in rng.integers(0, vocab, plen)],
                          now=now, max_new_tokens=args.max_new_tokens)
        if args.kill_replica >= 0 and tick == 5:
            killed = fm.kill_replica(names[0], args.kill_replica)
        fm.step(now)
        now += 1.0
    while fm.pending_work() > 0 and now < duration + 2_000:
        fm.step(now)
        now += 1.0
    summary = fm.stats()
    summary["killed_replica"] = killed
    summary["ticks"] = int(now)
    summary["wall_s"] = round(time.time() - t0, 2)
    print(json.dumps(summary))
    return 0


def make_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3.2-1b")
    ap.add_argument("--full-size", action="store_true",
                    help="published config in bf16 (default: smoke config, "
                         "float32, CPU-sized)")
    ap.add_argument("--stub", action="store_true",
                    help="arithmetic stub model (no weights, instant)")
    ap.add_argument("--requests", type=int, default=32)
    ap.add_argument("--slots", type=int, default=4,
                    help="decode slots per batcher replica")
    ap.add_argument("--max-replicas", type=int, default=2)
    ap.add_argument("--policy", default="jsq",
                    help="admission policy: fcfs|round_robin|jsq|pow2|edf")
    ap.add_argument("--ingress-capacity", type=int, default=0,
                    help=">0 bounds the request mailbox (backpressure)")
    ap.add_argument("--overflow", default="shed", choices=("shed", "defer"))
    ap.add_argument("--max-new-tokens", type=int, default=12)
    ap.add_argument("--max-len", type=int, default=64)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--spike", action="store_true",
                    help="bursty open-loop arrivals instead of one batch")
    ap.add_argument("--kill-replica", type=int, default=-1,
                    help="chaos: kill this replica index mid-run")
    ap.add_argument("--log-backed", action="store_true",
                    help="admit through the durable requests topic "
                         "(ServingJob) instead of the bare ingress")
    ap.add_argument("--spill-dir", default=None,
                    help="with --log-backed: JSONL-spill the message log "
                         "here (survives process death)")
    ap.add_argument("--partitions", type=int, default=2,
                    help="with --log-backed: requests-topic partitions")
    ap.add_argument("--paged", action="store_true",
                    help="paged KV cache: slots hold only the pages their "
                         "request fills (shared pool + page tables)")
    ap.add_argument("--pages", type=int, default=0,
                    help="with --paged: pool pages per replica incl. the "
                         "reserved scratch page (0 = enough for all slots)")
    ap.add_argument("--page-size", type=int, default=16,
                    help="with --paged: tokens per KV page")
    ap.add_argument("--admission", default="continuous",
                    choices=("continuous", "per_request"),
                    help="per_request = gang admission (static-batching "
                         "baseline for the bench grid)")
    ap.add_argument("--split-prefill", action="store_true",
                    help="with --log-backed: run prefill as its own "
                         "elastic stage (prefill/decode disaggregation)")
    ap.add_argument("--tenants", default=None,
                    help="comma-separated tenant names: serve them as a "
                         "multi-tenant fleet on one cluster (FleetManager) "
                         "instead of a single pool")
    ap.add_argument("--priorities", default=None,
                    help="with --tenants: comma ints, higher wins "
                         "arbitration/preemption (default all 0)")
    ap.add_argument("--slo-ms", default=None,
                    help="with --tenants: comma per-tenant SLO deadlines "
                         "(virtual ticks ~ ms; default 50)")
    ap.add_argument("--costs", default=None,
                    help="with --tenants: comma per-token decode costs "
                         "t_p (model size proxy; default 0.5)")
    ap.add_argument("--fleet-mode", default="fleet",
                    choices=("fleet", "static"),
                    help="with --tenants: shared cluster + arbitration, "
                         "or static per-tenant partitions (A/B baseline)")
    add_chaos_flags(ap, fail_interval=15.0, fail_restart=8.0)
    ap.add_argument("--seed", type=int, default=0)
    return ap


def main(argv=None) -> int:
    args = make_parser().parse_args(argv)
    enable_compile_cache()

    if args.tenants:
        return run_fleet(args)

    cluster, engine, injector = build_cluster(args)
    model, params, vocab = build(args)
    kwargs = pool_kwargs(args, cluster)
    paged = kwargs["paged"]
    if args.log_backed:
        job = ServingJob(model, params, spill_dir=args.spill_dir,
                         partitions=args.partitions,
                         split_prefill=args.split_prefill, **kwargs)
        pool = job.pool
    else:
        job = None
        pool = ElasticServingPool(model, params, **kwargs)

    rng = np.random.default_rng(args.seed)

    def make_request():
        plen = int(rng.integers(2, 8))
        return Request(
            prompt=[int(x) for x in rng.integers(0, vocab, plen)],
            max_new_tokens=args.max_new_tokens,
        )

    t0 = time.time()
    tick = 0
    # With overflow="defer" the submitter owns the retry: rejected
    # requests park here and re-submit each tick (closed-loop retry).
    # Log-backed submits never reject — the log is the buffer.
    pending = []

    def submit(req, now):
        if job is not None:
            job.submit(req, now=now)
        elif not pool.submit(req, now=now) and args.overflow == "defer":
            pending.append(req)
    if args.spike:
        # open-loop bursty arrivals: a calm head, a 4x spike holding half
        # the traffic, a calm tail; exactly args.requests in total (the
        # trailing ticks are trimmed when a tiny n can't fill the shape)
        n = args.requests
        schedule = ([1] * max(n // 4, 1) + [4] * max(n // 8, 1)
                    + [1] * max(n - n // 4 - 4 * max(n // 8, 1), 0))
        excess = sum(schedule) - n
        while excess > 0 and schedule:
            cut = min(schedule[-1], excess)
            schedule[-1] -= cut
            excess -= cut
            if schedule[-1] == 0:
                schedule.pop()
        arrivals = iter(schedule)
    else:
        for _ in range(args.requests):
            submit(make_request(), now=0.0)
        arrivals = iter(())

    killed = None
    # Pull exactly one arrival count per tick; `upcoming` doubles as the
    # termination peek so the drain check never eats a burst.
    upcoming = next(arrivals, None)
    while True:
        retry, pending[:] = pending[:], []
        for req in retry:
            submit(req, now=float(tick))
        for _ in range(upcoming or 0):
            submit(make_request(), now=float(tick))
        upcoming = next(arrivals, None)
        if args.kill_replica >= 0 and tick == 5 and pool.replicas:
            killed = pool.kill_replica(args.kill_replica)
        if engine is not None:
            engine.run_until(float(tick))  # node chaos rides the heap
        if job is not None:
            job.step(float(tick))
            drained = job.pending() == 0
        else:
            pool.step(float(tick))
            drained = (pool.queue_depth() == 0 and pool.occupancy() == 0
                       and not pending)
        tick += 1
        if drained and upcoming is None:
            break
        if tick > 100_000:
            break

    wall = time.time() - t0
    lat = [r.completed_at - r.enqueued_at for r in pool.completed] or [0.0]
    targets = [t for (_, t, _, _) in pool.occupancy_log]
    replicas = [n for (_, _, _, n) in pool.occupancy_log]
    summary = {
        "mode": "log" if job is not None else "direct",
        "policy": pool.policy_name,
        "requests_completed": len(pool.completed),
        "shed": pool.metrics.value("serve.shed"),
        "deferred": pool.metrics.value("serve.deferred"),
        "readmitted": pool.metrics.value("serve.readmitted"),
        "killed_replica": killed,
        "nodes": args.nodes,
        "node_failures": injector.failures if injector else 0,
        "node_restores": injector.restores if injector else 0,
        "relocations": (
            pool.metrics.value("serve.replica_relocations")
            if cluster is not None else 0
        ),
        "decode_ticks": pool.steps,
        "wall_s": round(wall, 2),
        "p50_latency_ticks": round(float(np.percentile(lat, 50)), 1),
        "p99_latency_ticks": round(float(np.percentile(lat, 99)), 1),
        "peak_target_units": max(targets),
        "peak_replicas": max(replicas),
        "final_target_units": targets[-1],
        "scale_events": [
            (t, size, reason) for (t, size, reason)
            in pool.controller.scale_events
        ],
    }
    if paged is not None:
        summary["paged"] = {
            "pages": paged.num_pages,
            "page_size": paged.page_size,
            "pages_in_use": pool.total_pages_in_use(),
            "preemptions": sum(r.preemptions for r in pool.replicas),
            "admit_stalls": sum(r.admit_stalls for r in pool.replicas),
        }
    if job is not None:
        summary["durable_responses"] = len(job.responses())
        summary["committed_offsets"] = job.committed_offsets()
        summary["replay_deduped"] = pool.metrics.value("serve.replay_deduped")
        job.close()
    print(json.dumps(summary))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
