"""Multi-stage dataflow driver: an N-stage chain of elastic pools over
durable topics, stepped on a virtual clock with chaos and spikes.

Each stage multiplies its input by a per-stage factor (cheap, checkable
work); stage i is deliberately slower than its neighbors when
``--slow-stage`` names it, which is the scenario where the graph's
backpressure wiring earns its keep: watch ``peak_lag`` on the slow
stage's input topic with ``--no-backpressure`` vs. the default.

Usage:
  PYTHONPATH=src python -m repro.launch.dataflow --stages 3 --messages 200
  PYTHONPATH=src python -m repro.launch.dataflow --stages 3 --spike \
      --kill-stage-at 8:stage1          # chaos: kill stage1's workers at t=8
  PYTHONPATH=src python -m repro.launch.dataflow --slow-stage 1 \
      --no-backpressure                 # let the intermediate topic balloon
  PYTHONPATH=src python -m repro.launch.dataflow --nodes 3 --cores 2 \
      --fail-prob 0.5                   # node-level chaos via the cluster
  PYTHONPATH=src python -m repro.launch.dataflow --nodes 3 --straggler 0

Node-level chaos (``--nodes``/``--fail-prob``/``--straggler``) runs the
whole graph on a ``core.cluster.Cluster``: stage workers carry nodes, a
node failure silences every resident worker at once (the supervisor
relocates them to the healthiest live node after ``--restart-cost``), and
a straggler node dilates its residents' step budgets — the same actuator
path the paper-figure simulations drive.
"""

from __future__ import annotations

import argparse
import json

from repro.core.dataflow import Stage, StageGraph
from repro.core.elastic import AutoscalerConfig
from repro.data.topics import MessageLog
from repro.core.simulation import WorkloadConfig
from repro.launch.chaos import (
    add_chaos_flags,
    apply_arrival_flags,
    build_cluster,
)
from repro.launch.compile_cache import enable_compile_cache


def build_graph(args, cluster=None) -> StageGraph:
    log = MessageLog(spill_dir=args.spill_dir)
    for i in range(args.stages + 1):
        log.create_topic(f"t{i}", args.partitions)
    graph = StageGraph(
        log,
        backpressure=not args.no_backpressure,
        throttle_low=args.throttle_low,
        throttle_high=args.throttle_high,
    )
    for i in range(args.stages):
        def make_process(factor: int):
            def process(msg):
                return [msg.payload * factor]
            return process

        graph.add(Stage(
            f"stage{i}",
            log,
            f"t{i}",
            f"t{i + 1}",
            process=make_process(i + 2),
            key_fn=(str if args.keyed else None),
            initial_tasks=args.initial_tasks,
            mailbox_capacity=args.mailbox_capacity,
            step_budget=(args.slow_budget if i == args.slow_stage else 8),
            scheduler=args.policy,
            autoscaler=AutoscalerConfig(
                high_watermark=8.0, low_watermark=1.0, min_workers=1,
                max_workers=args.max_tasks, cooldown=0.0,
            ),
            heartbeat_timeout=args.heartbeat_timeout,
            cluster=cluster,
            restart_cost=args.restart_cost,
        ))
    return graph


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--stages", type=int, default=3)
    ap.add_argument("--messages", type=int, default=200)
    ap.add_argument("--partitions", type=int, default=3)
    ap.add_argument("--initial-tasks", type=int, default=2)
    ap.add_argument("--max-tasks", type=int, default=16)
    ap.add_argument("--policy", default="jsq")
    ap.add_argument("--mailbox-capacity", type=int, default=4,
                    help="per-task mailbox bound (0 = unbounded): bounded "
                         "mailboxes park overload in the durable topic, "
                         "where backpressure can see it")
    ap.add_argument("--keyed", action="store_true",
                    help="keyed inter-stage re-partitioning (key = value)")
    ap.add_argument("--spike", action="store_true",
                    help="bursty open-loop arrivals instead of preload")
    ap.add_argument("--kill-stage-at", default=None, metavar="T:STAGE",
                    help="chaos: at tick T, kill every worker of STAGE "
                         "(e.g. 8:stage1)")
    ap.add_argument("--slow-stage", type=int, default=-1,
                    help="index of a deliberately slow stage")
    ap.add_argument("--slow-budget", type=int, default=1,
                    help="per-tick step budget of the slow stage's tasks")
    ap.add_argument("--no-backpressure", action="store_true")
    ap.add_argument("--throttle-low", type=int, default=16)
    ap.add_argument("--throttle-high", type=int, default=64)
    ap.add_argument("--heartbeat-timeout", type=float, default=3.0)
    add_chaos_flags(ap, fail_interval=20.0, fail_restart=10.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--spill-dir", default=None)
    ap.add_argument("--max-ticks", type=int, default=100_000)
    args = ap.parse_args(argv)
    enable_compile_cache()

    cluster, engine, injector = build_cluster(args)
    graph = build_graph(args, cluster=cluster)
    head = graph.stage("stage0")

    if args.spike:
        n = args.messages
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
    elif args.diurnal > 0.0:
        # Day/night arrival shaping: pace the submissions over one
        # --diurnal-period using the closed-form arrival integral.
        wl = WorkloadConfig(
            total_messages=args.messages, partitions=1,
            arrival_rate=args.messages / args.diurnal_period,
        )
        apply_arrival_flags(args, wl)
        schedule, prev = [], 0
        while prev < args.messages:
            cur = min(wl.arrived(float(len(schedule) + 1)), args.messages)
            schedule.append(cur - prev)
            prev = cur
        arrivals = iter(schedule)
    else:
        for i in range(args.messages):
            head.submit(i, key=(str(i) if args.keyed else None), now=0.0)
        arrivals = iter(())

    kill_at, kill_stage = None, None
    if args.kill_stage_at:
        t_s, kill_stage = args.kill_stage_at.split(":", 1)
        kill_at = int(t_s)

    paced = args.spike or args.diurnal > 0.0
    tick, submitted, killed = 0, 0 if paced else args.messages, None
    upcoming = next(arrivals, None)
    while tick < args.max_ticks:
        for _ in range(upcoming or 0):
            head.submit(submitted, now=float(tick))
            submitted += 1
        upcoming = next(arrivals, None)
        if kill_at is not None and tick == kill_at:
            killed = graph.kill_stage(kill_stage)
        if engine is not None:
            engine.run_until(float(tick))  # node chaos rides the heap
        graph.step(float(tick))
        tick += 1
        if upcoming is None and graph.pending() == 0 and tick > 2:
            break

    terminal = graph.terminal_stages()[0]
    summary = {
        "stages": args.stages,
        "backpressure": not args.no_backpressure,
        "messages": args.messages,
        "ticks": tick,
        "terminal_outputs": len(terminal.outputs()),
        "killed": killed,
        "nodes": args.nodes,
        "node_failures": injector.failures if injector else 0,
        "node_restores": injector.restores if injector else 0,
        "relocations": sum(
            s.pool.counter("stage.task_relocations")
            for s in graph.stages.values()
        ) if cluster is not None else 0,
        "per_stage": {
            name: {
                "processed": s.pool.counter("task.processed"),
                "published": s.pool.counter("stage.published"),
                "restarts": s.pool.counter(f"stage.{'task'}_restarts"),
                "throttled": s.pool.counter("stage.throttled"),
                "peak_input_lag": graph.peak_lag(name),
                "committed": s.committed_offsets(),
                "final_tasks": len(s.pool.active_workers()),
            }
            for name, s in graph.stages.items()
        },
    }
    print(json.dumps(summary))
    graph.close()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
