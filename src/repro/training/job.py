"""Elastic training over the log: the training counterpart of
``ServingJob``, re-based on the shared ``ElasticPool`` control plane.

The same five-layer path that serves traffic now trains the model:

  ``tokens`` topic (messaging layer)
    → ``TokenPipeline`` in *ordered, manual-commit* mode (virtual
      messaging: partition-affine forwarding, strict partition-rotation
      hand-out — the batch sequence is a pure function of the committed
      offsets)
      → pool ingress ``Mailbox`` (asynchronous messaging: per-step DP
        shard messages)
        → ``TrainerWorker`` pool (processing layer: one supervised,
          killable worker per DP replica)
          → barrier collect → the jit'd global train step
            → event-sourced checkpoint journal → offset commit

Three contracts:

  * **Commit-after-journal** (exactly-once consumption): token offsets
    commit only after the optimizer step that consumed them is durably
    journaled.  A chaos-killed trainer process rebuilds from the newest
    snapshot and replays the uncommitted suffix — the replayed steps
    consume the identical documents (ordered mode), so an uninterrupted
    run and a kill-and-resume run reach **bitwise-identical** params.
  * **Barrier-synchronous DP**: each global batch is split into one
    shard message per DP replica; the optimizer step fires only when
    every shard of step N has been processed (harvested first-wins, so
    at-least-once redelivery after a worker kill cannot double-apply).
    Which worker processed which shard never affects the result — the
    batch is reassembled by shard index, not worker order.
  * **Scale is a live pool event**: the autoscaler's decision actuates
    through the pool's ``on_scale`` hook as snapshot →
    ``mesh_for_devices`` at the new DP degree → ``reshard_state`` →
    resume at the exact stream position.  Without a mesh (CPU tier-1)
    the same hook re-shapes the shard fan-out; the stream position and
    batch sequence are DP-degree-independent by construction, so a
    2→4→3 run consumes exactly the documents a fixed-degree run would.

The data-plane compute stays one XLA computation sharded over the mesh
(GSPMD *is* the real DP); the pool workers are the control-plane replica
proxies — per-replica supervision, heartbeat, data accounting — which is
the repo's standing split (DESIGN.md assumption notes).
"""

from __future__ import annotations

import itertools
import time
from collections import deque
from typing import Any, Callable, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.checkpoint.handoff import StateHandoffChannel, WorkerHandoffChannel
from repro.checkpoint.store import CheckpointStore
from repro.config.base import ArchConfig, TrainingConfig
from repro.core.elastic import AutoscalerConfig
from repro.core.messages import Message
from repro.core.pool import ElasticPool, WorkerBase
from repro.core.supervision import Supervisor
from repro.data.pipeline import PipelineConfig, TokenPipeline
from repro.data.topics import MessageLog
from repro.distributed.elastic_mesh import (
    mesh_for_devices,
    reshard_state,
    state_shard_axes,
)
from repro.distributed.param_shardings import make_rules
from repro.distributed.sharding import axis_rules
from repro.telemetry.profile import span
from repro.training.train_step import init_train_state, make_train_step

_worker_ids = itertools.count()


class TokenIngestStage:
    """The training job's token-ingestion front half as a dataflow
    stage: ``tokens`` topic → ordered manual-commit ``TokenPipeline`` →
    shard messages → ``TrainerWorker`` pool → barrier step → journal →
    offset commit.  It satisfies the ``StageGraph`` protocol (``name`` /
    ``in_topic`` / ``out_topic`` / ``pool`` / ``step`` / ``pending`` /
    ``input_lag`` / ``committed_offsets``), so a training job can sit as
    the terminal stage of a graph — an upstream preprocessing stage
    publishing into the tokens topic is throttled by training backlog
    exactly like any other producer stage.  The "publish" that gates the
    commit is the event-sourced checkpoint journal: commit-after-journal
    is this stage's instance of chained commit-after-publish."""

    def __init__(self, job: "TrainingJob") -> None:
        self.job = job
        self.name = f"train:{job.pipeline.config.topic}"
        self.in_topic = job.pipeline.topic
        self.out_topic = None
        self.pool = job.pool

    def input_lag(self) -> int:
        return self.job.pipeline.lag()

    def committed_offsets(self) -> Dict[int, int]:
        return self.job.pipeline.offsets()

    def pending(self) -> int:
        return self.job.backlog()

    def kill_worker(self, index: int = 0) -> str:
        return self.pool.kill_worker(index)

    def kill_all_workers(self) -> List[str]:
        return [self.pool.kill_worker(i) for i in range(len(self.pool.workers))]

    def close(self) -> None:
        pass

    def step(self, now: float = 0.0) -> int:
        """One training round: assemble shard messages from the ordered
        stream, report stream backlog as rejected demand, run the pool
        (dispatch/process/collect/supervise/autoscale), then fire every
        complete barrier.  Returns optimizer steps applied.  The whole
        round is the pool's tick span (``train.tick``)."""
        job = self.job
        with span(job.pool.tick_span, tick=job.pool.steps):
            job._now = max(job._now, now)
            job._drain_commit_gate(now)  # land any newly durable commits
            with span("train.assemble"):
                job._assemble(now)
            if job.pool.elastic:
                lag_batches = job.pipeline.lag() // job.batch_size
                if lag_batches:
                    job.pool.note_rejected(min(lag_batches, job.autoscale_lag_cap))
            job.pool.round(now)
            return job._fire_barriers(now)


class TrainerWorker(WorkerBase):
    """One DP replica's control-plane proxy: a supervised, killable,
    drainable pool worker.  ``step`` consumes shard messages from its
    mailbox and parks them as ready; shards stay *in-flight* (part of
    ``drain_for_readmission``) until the job's barrier collect harvests
    them, so a kill between processing and harvest loses nothing."""

    def __init__(self, name: str, shard_budget: int = 8) -> None:
        super().__init__(name)
        self.shard_budget = shard_budget
        self._ready: List[Message] = []

    def step(self, now: float = 0.0) -> int:
        n = 0
        while n < self.shard_budget and self.alive:
            msg = self.mailbox.get()
            if msg is None:
                break
            rows = msg.payload["rows"]
            self.metrics.incr("train.shards")
            self.metrics.incr("train.tokens", int(rows.size))
            self._ready.append(msg)
            n += 1
        return n

    def load(self) -> int:
        return self.mailbox.depth() + len(self._ready)

    def inflight(self) -> int:
        return len(self._ready)

    def take_ready(self) -> List[Message]:
        out, self._ready = self._ready, []
        return out

    def drain_for_readmission(self) -> List[Message]:
        out = list(self._ready)
        self._ready = []
        out.extend(self.mailbox.drain())
        return out

    def export_carry(self) -> List[Message]:
        """Processed shards awaiting the barrier harvest: handoff-able
        results, not work to recompute.  Exported shards leave
        ``_ready`` so the subsequent drain re-admits only the mailbox."""
        out, self._ready = self._ready, []
        return out

    def import_carry(self, msgs: Sequence[Message]) -> int:
        """Adopt a predecessor's processed shards directly into the
        ready set — the barrier harvests them without a recompute step
        (the healing worker's last-delta catch-up)."""
        self._ready.extend(msgs)
        return len(msgs)


class TrainingJob:
    """DP training as a reactive job over the durable ``tokens`` topic.

    Drives identically under all three live tiers (DESIGN §3): the
    step-driven tests/benches call :meth:`step`, ``ThreadedRuntime``
    drives the same method under wall-clock supervision, and
    ``launch/train.py`` + ``launch/cluster.py`` wrap it in an OS process
    that the ``ProcessSupervisor`` Let-It-Crash restarts with
    ``resume=True``.
    """

    def __init__(
        self,
        model: Any,
        arch_cfg: ArchConfig,
        tcfg: TrainingConfig,
        log: MessageLog,
        *,
        topic: str = "tokens",
        batch_size: int = 8,
        seq_len: int = 64,
        dp: int = 1,
        max_dp: int = 8,
        elastic: bool = False,
        autoscaler: Optional[AutoscalerConfig] = None,
        autoscale_lag_cap: int = 64,
        heartbeat_timeout: float = 5.0,
        max_inflight_steps: int = 2,
        shard_budget: int = 8,
        consume_batch: int = 16,
        checkpoint_dir: Optional[str] = None,
        checkpoint_every: int = 20,
        async_checkpoint: bool = False,
        ckpt_shards: int = 1,
        commit_gate_cap: int = 8,
        handoff: Optional[StateHandoffChannel] = None,
        handoff_every: int = 0,
        resume: bool = False,
        use_mesh: bool = False,
        model_parallel: int = 1,
        train_step_fn: Optional[Callable] = None,
        seed: int = 0,
        on_step: Optional[Callable[[int, Dict], None]] = None,
    ) -> None:
        self.model = model
        self.arch_cfg = arch_cfg
        self.tcfg = tcfg
        self.batch_size = batch_size
        self.seq_len = seq_len
        self.max_dp = max(int(max_dp), 1)
        self.dp = min(max(int(dp), 1), self.max_dp)
        self.model_parallel = max(int(model_parallel), 1)
        self.max_inflight_steps = max(int(max_inflight_steps), 1)
        self.autoscale_lag_cap = autoscale_lag_cap
        self.checkpoint_every = checkpoint_every
        self.on_step = on_step
        self.seed = seed
        self._now = 0.0
        # Async checkpointing: snapshots and journal lines flow through
        # the store's write-behind worker; token offsets commit only as
        # each step's journal-complete ticket resolves (the commit gate
        # that preserves commit-after-journal off the barrier).
        self._async = bool(async_checkpoint)
        self.commit_gate_cap = max(int(commit_gate_cap), 1)
        self._pending_commits: deque = deque()  # (step, offsets, rr, ticket)
        # Live state handoff: full sharded state streamed through a
        # durable topic at remesh points (and every ``handoff_every``
        # steps), so a healing process resumes from the handoff step
        # instead of replaying from the last periodic snapshot.
        self.handoff = handoff
        self.handoff_every = max(int(handoff_every), 0)
        self.resume_source: Optional[str] = None
        self.handoff_deltas_applied = 0
        # Wall-clock the caller's thread spends blocked inside snapshot
        # writes — the stall the async path takes off the barrier.
        self.ckpt_stalls: List[float] = []

        self.pipeline = TokenPipeline(
            log,
            PipelineConfig(
                topic=topic,
                partitions=log.get(topic).num_partitions,
                batch_size=batch_size,
                seq_len=seq_len,
                consume_batch=consume_batch,
                ordered=True,
                commit_policy="manual",
            ),
        )

        # -- mesh (device-level DP) ------------------------------------------
        self.mesh = None
        self.rules = None
        if use_mesh:
            n_dev = jax.device_count()
            self._feasible = [
                d for d in range(1, self.max_dp + 1)
                if d * self.model_parallel <= n_dev and batch_size % d == 0
            ]
            if self.dp not in self._feasible:
                raise ValueError(
                    f"dp={self.dp} infeasible: need dp*mp <= {n_dev} devices "
                    f"and batch_size % dp == 0 (feasible: {self._feasible})"
                )
            self.mesh = mesh_for_devices(
                self.dp * self.model_parallel, self.model_parallel
            )
            self.rules = make_rules(arch_cfg, self.mesh)
        else:
            self._feasible = list(range(1, self.max_dp + 1))

        # -- train state (init or event-sourced restore) ---------------------
        self.store = (
            CheckpointStore(
                checkpoint_dir, shards=max(int(ckpt_shards), 1),
                async_io=self._async,
            )
            if checkpoint_dir else None
        )
        self._raw_step = make_train_step(model, tcfg)
        state, start = None, 0
        if resume and (self.store is not None or self.handoff is not None):
            template = jax.eval_shape(
                lambda r: init_train_state(model, tcfg, r),
                jax.random.PRNGKey(seed),
            )
            template = jax.tree.map(
                lambda s: jnp.zeros(s.shape, s.dtype), template
            )
            # Newest durable position wins between the disk snapshot and
            # the live handoff channel; ties go to the handoff (same
            # state, no disk read).  Resuming from the handoff is the
            # last-delta catch-up: replay starts at the handoff step, not
            # the last periodic snapshot.
            snap = (
                self.store.restore_latest(template)
                if self.store is not None else None
            )
            hand = (
                self.handoff.latest_state(template)
                if self.handoff is not None else None
            )
            best = None
            if snap is not None:
                best = ("snapshot", snap[0], snap[1])
            if hand is not None and (
                best is None
                or int(hand[1]["step"]) >= int(best[2]["step"])
            ):
                best = ("handoff", hand[0], hand[1])
            if best is not None:
                self.resume_source, state, meta = best
                if self.resume_source == "handoff":
                    self.handoff_deltas_applied = len(hand[2])
                start = int(meta["step"])
                stream = meta.get("stream")
                if stream:
                    self.pipeline.restore_stream_state(stream)
                elif start > 0:
                    # A snapshot with params at step S but no stream
                    # position would silently rewind the token stream to
                    # offset 0 and double-consume the first S batches.
                    # (Pre-TrainingJob checkpoints carry a carry-mode
                    # "pipeline" dict that cannot map onto ordered mode.)
                    raise RuntimeError(
                        f"checkpoint at step {start} has no 'stream' "
                        "resume point (written by an incompatible "
                        "driver?) — refusing to resume with a rewound "
                        "token stream"
                    )
        if state is None:
            state = init_train_state(model, tcfg, jax.random.PRNGKey(seed))
        if self.mesh is not None:
            state = reshard_state(state, arch_cfg, self.mesh)
        self.state = state
        # Checkpoint shard axes follow the live sharding assignment, so
        # per-shard writes cut along device-shard boundaries; without a
        # mesh the planner's axis-0 default applies.
        self._shard_axes = (
            state_shard_axes(self.state, arch_cfg, self.mesh)
            if self.mesh is not None else None
        )
        # Stream cursor as of the last *applied* step.  In async mode
        # committed offsets lag the applied step (commits wait on the
        # journal gate), so snapshots/handoffs pair the state with this
        # tracked cursor, never the lagging committed one.
        st0 = self.pipeline.stream_state()
        self._cursor_offsets: Dict[str, int] = dict(st0["offsets"])
        self._cursor_rr = st0["rr"]
        if train_step_fn is not None and self.mesh is None:
            self._jit = train_step_fn
        else:
            self._jit = jax.jit(self._raw_step)

        # -- step bookkeeping -------------------------------------------------
        self._applied = start          # last optimizer step durably applied
        self._assembled = start        # last step whose shards were cut
        self._batch_meta: Dict[int, Dict] = {}   # step -> offsets/shards
        self._arrived: Dict[tuple, Dict] = {}    # (step, shard) -> payload
        self.step_offsets: Dict[int, Dict[int, int]] = {}  # audit trail
        self._stop_at: Optional[int] = None  # run()'s exact-stop bound
        self.losses: List[float] = []
        self.scale_log: List[tuple] = []  # (now, old_dp, new_dp, mesh_shape)

        # -- the control plane -------------------------------------------------
        # With handoff enabled, a restarted trainer's processed-but-
        # unharvested shards are carried to its replacement (keyed by
        # (step, shard)) instead of re-admitted for recompute.
        self.worker_handoff = (
            WorkerHandoffChannel(
                log, topic=f"{topic}.worker-handoff",
                key_fn=lambda m: (m.payload["step"], m.payload["shard"]),
            )
            if handoff is not None else None
        )
        self.pool = ElasticPool(
            "train",
            lambda: TrainerWorker(
                f"train:dp{next(_worker_ids)}", shard_budget=shard_budget
            ),
            scheduler="round_robin",
            initial_units=self.dp,
            units_per_worker=1,
            max_workers=self.max_dp,
            autoscaler=autoscaler or AutoscalerConfig(
                min_workers=1,
                max_workers=self.max_dp,
                high_watermark=8.0,
                low_watermark=0.25,
                cooldown=5.0,
            ),
            elastic=elastic,
            reconcile_on="delta",
            heartbeat_timeout=heartbeat_timeout,
            ingress_capacity=0,        # unbounded central ingress
            ingress_name="train-ingress",
            overflow="defer",
            retire_mode="redistribute",
            collect=self._harvest,
            on_scale=self._actuate_scale,
            handoff=self.worker_handoff,
            metric_prefix="train",
            worker_noun="trainer",
        )
        # The ingestion front half as a graph-mountable stage (the main
        # loop below is a delegation to it).
        self.stage = TokenIngestStage(self)

    # -- views -----------------------------------------------------------------
    @property
    def metrics(self):
        return self.pool.metrics

    @property
    def supervisor(self) -> Supervisor:
        return self.pool.supervisor

    def counter(self, name: str) -> int:
        return self.pool.counter(name)

    def applied_step(self) -> int:
        return self._applied

    def total_processed(self) -> int:
        return self._applied

    def committed_offsets(self) -> Dict[int, int]:
        return self.pipeline.offsets()

    def backlog(self) -> int:
        """Zero only when every assembled step has been applied, no shard
        is queued or in flight, and the stream cannot fill another batch."""
        pending = (
            (self._assembled - self._applied)
            + self.pool.queue_depth()
            + self.pool.occupancy()
        )
        return pending + self.pipeline.lag() // self.batch_size

    # -- chaos / scaling hooks ---------------------------------------------------
    def kill_worker(self, index: int = 0) -> str:
        return self.pool.kill_worker(index)

    def kill_process(self) -> int:
        """Chaos: whole-process death.  Queued write-behind work is lost
        (never reaches disk) — a rebuilt job sees exactly the directory
        a crashed process would leave.  Returns discarded writes."""
        return self.store.kill() if self.store is not None else 0

    def request_scale(self, units: int) -> None:
        """Manual DP scaling through the same actuation path as the
        autoscaler (``on_scale``: snapshot → remesh → reshard)."""
        self.pool.set_target_units(units)

    # -- checkpointing -------------------------------------------------------------
    def _stream_cursor(self) -> Dict:
        """Stream resume point as of the last applied step (equals
        ``pipeline.stream_state()`` whenever the commit gate is empty)."""
        return {"offsets": dict(self._cursor_offsets), "rr": self._cursor_rr}

    def save_checkpoint(self):
        """Snapshot at the applied step.  Sync store: blocks for the
        full write and returns the path.  Async store: pins a host copy,
        submits to the write-behind worker, returns the manifest's
        commit ticket — the caller's stall is the pin, not the write."""
        if self.store is None:
            return None
        t0 = time.perf_counter()
        kwargs = dict(
            step=self._applied,
            extra={"stream": self._stream_cursor()},
            shard_axes=self._shard_axes,
        )
        if self.store.writer is not None:
            out = self.store.save_async(self.state, **kwargs)
        else:
            out = self.store.save(self.state, **kwargs)
        self.ckpt_stalls.append(time.perf_counter() - t0)
        return out

    def _publish_handoff(self) -> None:
        if self.handoff is None:
            return
        self.handoff.publish_state(
            self.state,
            step=self._applied,
            meta={"stream": self._stream_cursor()},
            shard_axes=self._shard_axes,
        )

    def _drain_commit_gate(self, now: float, wait: bool = False) -> int:
        """Commit-after-journal, asynchronously: pop pending commits in
        step order, committing each only once its journal-complete
        ticket resolved.  A failed write blocks every later commit (the
        replay window stays open — exactly the sync contract)."""
        n = 0
        while self._pending_commits:
            step, offsets, rr, ticket = self._pending_commits[0]
            if ticket is not None and not ticket.done():
                if not wait:
                    break
                ticket.wait(60.0)
            if ticket is not None and ticket.error is not None:
                break  # journal line lost: never commit past it
            self._pending_commits.popleft()
            self.pipeline.commit(offsets, now=now, rr=rr)
            self.step_offsets[step] = dict(offsets)
            n += 1
        return n

    def flush_durability(self, now: Optional[float] = None) -> None:
        """Drain the write-behind worker and the commit gate: when this
        returns, every journaled step is on disk and committed."""
        if self.store is not None:
            self.store.flush()
        self._drain_commit_gate(self._now if now is None else now, wait=True)

    # -- internals ------------------------------------------------------------------
    def _assemble(self, now: float) -> None:
        """Cut global batches from the ordered stream into per-replica
        shard messages, bounded by ``max_inflight_steps`` and by the
        commit gate (a stalled write-behind worker backpressures intake
        instead of growing the uncommitted suffix unboundedly).  The
        batch sequence itself is a pure function of the prefetch cursor,
        so gating *when* batches are cut never changes *which* documents
        each step consumes."""
        while (
            (self._assembled - self._applied) < self.max_inflight_steps
            and len(self._pending_commits) <= self.commit_gate_cap
        ):
            docs = self.pipeline.next_docs(self.batch_size)
            if docs is None:
                return
            rows = np.stack(
                [np.asarray(m.payload, dtype=np.int32) for m in docs]
            )
            if rows.shape[1] != self.seq_len + 1:
                raise ValueError(
                    f"documents must be seq_len+1={self.seq_len + 1} tokens "
                    f"for exact-offset training, got {rows.shape[1]} "
                    "(build the token log with doc_len=seq_len+1)"
                )
            step_id = self._assembled + 1
            # Strict per-partition order makes the consumed offsets a
            # contiguous prefix: commit target = max offset + 1.
            offsets: Dict[int, int] = {}
            for m in docs:
                offsets[m.partition] = max(
                    offsets.get(m.partition, -1), m.offset
                )
            offsets = {p: o + 1 for p, o in offsets.items()}
            n_shards = max(min(self.dp, len(rows)), 1)
            self._batch_meta[step_id] = {
                "offsets": offsets,
                "shards": n_shards,
                # rotation cursor as of this batch — committed alongside
                # its offsets so checkpoints never pair committed offsets
                # with the prefetch cursor
                "rr": self.pipeline.rotation_cursor(),
            }
            for s, idx in enumerate(np.array_split(np.arange(len(rows)), n_shards)):
                self.pool.offer(Message(
                    topic="train",
                    payload={
                        "step": step_id,
                        "shard": s,
                        "start": int(idx[0]),
                        "rows": rows[idx],
                    },
                    created_at=now,
                ))
            self._assembled = step_id

    def _harvest(self, now: float) -> None:
        """Pool collect hook (runs before supervision may replace worker
        objects): move processed shards into the barrier table,
        first-wins — at-least-once redelivery cannot double-apply."""
        del now
        for worker in self.pool.workers:
            take = getattr(worker, "take_ready", None)
            if take is None:
                continue
            for msg in take():
                d = msg.payload
                key = (d["step"], d["shard"])
                if d["step"] <= self._applied or key in self._arrived:
                    self.pool.metrics.incr("train.shard_dupes")
                    continue
                self._arrived[key] = d

    def batch_sharding(self) -> Optional[NamedSharding]:
        """Layout of a global batch on the mesh: rows split over the data
        axis, so each DP replica receives only its own rows (None
        without a mesh)."""
        if self.mesh is None:
            return None
        return NamedSharding(self.mesh, P(self.rules["batch"]))

    def _run_step(self, rows: np.ndarray):
        jb = {"tokens": rows[:, :-1], "labels": rows[:, 1:]}
        if self.mesh is None:
            return self._jit(self.state, jax.tree.map(jnp.asarray, jb))
        jb = jax.device_put(jb, self.batch_sharding())
        with self.mesh, axis_rules(self.rules):
            return self._jit(self.state, jb)

    def _fire_barriers(self, now: float) -> int:
        """Apply every optimizer step whose DP shards have all arrived,
        strictly in step order (synchronous DP).  Journal first, commit
        offsets second — the manual-commit contract."""
        fired = 0
        while True:
            if self._stop_at is not None and self._applied >= self._stop_at:
                break  # run(N) means exactly N, whatever the resume parity
            nxt = self._applied + 1
            meta = self._batch_meta.get(nxt)
            if meta is None:
                break
            keys = [(nxt, s) for s in range(meta["shards"])]
            if any(k not in self._arrived for k in keys):
                break
            with span("train.step", step=nxt):
                with span("train.upload"):
                    parts = sorted(
                        (self._arrived.pop(k) for k in keys),
                        key=lambda d: d["start"],
                    )
                    arr = np.concatenate([d["rows"] for d in parts], axis=0)
                    self.state, m = self._run_step(arr)
                self._applied = nxt
                del self._batch_meta[nxt]
                with span("train.loss_wait"):
                    loss = float(m["loss"])
                self.losses.append(loss)
                self.pool.metrics.incr("train.steps")
                with span("train.commit"):
                    self._commit_step(nxt, meta, loss, now)
            if self.on_step is not None:
                self.on_step(nxt, m)
            fired += 1
        return fired

    def _commit_step(self, nxt: int, meta: Dict, loss: float,
                     now: float) -> None:
        """After optimizer step ``nxt``: advance the stream cursor, journal
        the step, commit its offsets (or queue them behind the journal's
        write), snapshot and hand off on their cadence."""
        # Advance the applied-step stream cursor (what snapshots and
        # handoffs pair with the state).
        for p, o in meta["offsets"].items():
            self._cursor_offsets[str(p)] = o
        self._cursor_rr = meta["rr"]
        # Durable journal FIRST...
        if self.store is not None:
            self.store.record_step(
                nxt, offsets=meta["offsets"], metrics={"loss": loss}
            )
        do_snap = (
            self.store is not None
            and self.checkpoint_every
            and nxt % self.checkpoint_every == 0
        )
        if self._async:
            # ...then the offsets commit when the journal line (and,
            # on snapshot steps, the manifest — same FIFO, so later)
            # lands durably: the gate replaces the synchronous write.
            ticket = (
                self.store.last_write_ticket()
                if self.store is not None else None
            )
            if do_snap:
                ticket = self.save_checkpoint() or ticket
            self._pending_commits.append(
                (nxt, meta["offsets"], meta["rr"], ticket)
            )
            self._drain_commit_gate(now)
        else:
            # ...then the token offsets may commit.
            self.pipeline.commit(meta["offsets"], now=now, rr=meta["rr"])
            self.step_offsets[nxt] = dict(meta["offsets"])
            if do_snap:
                self.save_checkpoint()
        if self.handoff is not None and self.handoff_every:
            if nxt % self.handoff_every == 0:
                self._publish_handoff()
            else:
                self.handoff.publish_delta(
                    nxt,
                    {"offsets": {str(p): o
                                 for p, o in meta["offsets"].items()},
                     "rr": meta["rr"]},
                )

    def _actuate_scale(self, old_units: int, new_units: int) -> None:
        """The pool's scale decision becomes a physical re-layout:
        flush complete barriers, snapshot, remesh at the new DP degree,
        reshard the live state, resume at the exact stream position."""
        new_dp = self._clamp_feasible(new_units)
        if new_dp != new_units:
            self.pool.controller.target_size = new_dp
        if new_dp == self.dp:
            return
        self._fire_barriers(self._now)
        # Departing layout streams its state through the handoff topic —
        # the healing layout (or a healing process) resumes from this
        # exact step.  With an async store the safety snapshot is a
        # write-behind submit; only the legacy sync store still stalls
        # the remesh barrier for a full disk write.
        if self.handoff is not None:
            self._publish_handoff()
        if self.store is not None:
            self.save_checkpoint()
        mesh_shape = None
        if self.mesh is not None:
            self.mesh = mesh_for_devices(
                new_dp * self.model_parallel, self.model_parallel
            )
            self.rules = make_rules(self.arch_cfg, self.mesh)
            self.state = reshard_state(self.state, self.arch_cfg, self.mesh)
            self._jit = jax.jit(self._raw_step)  # re-trace under the new mesh
            self._shard_axes = state_shard_axes(
                self.state, self.arch_cfg, self.mesh
            )
            mesh_shape = dict(self.mesh.shape)
        self.scale_log.append((self._now, self.dp, new_dp, mesh_shape))
        self.pool.metrics.incr("train.rescales")
        self.dp = new_dp

    def _clamp_feasible(self, units: int) -> int:
        """Nearest feasible DP degree in the direction of the request
        (mesh mode: dp*mp must fit the devices and divide the batch)."""
        units = max(1, min(int(units), self.max_dp))
        if units in self._feasible:
            return units
        if units > self.dp:
            higher = [d for d in self._feasible if d >= units]
            if higher:
                return higher[0]
        lower = [d for d in self._feasible if d <= units]
        return lower[-1] if lower else self._feasible[0]

    def as_stage(self) -> TokenIngestStage:
        """Mount point for ``core.dataflow.StageGraph``: add the return
        value to a graph whose upstream stage publishes into the tokens
        topic, and the graph clock drives training."""
        return self.stage

    # -- main loop ----------------------------------------------------------------
    def step(self, now: float = 0.0) -> int:
        """One training round, delegated to the ingest stage (assemble →
        pool → barrier).  Returns optimizer steps applied this round."""
        return self.stage.step(now)

    def run(
        self,
        steps: int,
        now: float = 0.0,
        dt: float = 1.0,
        max_rounds: int = 100_000,
    ) -> int:
        """Step until exactly ``steps`` optimizer steps applied or the
        stream is exhausted.  Returns the final applied step.  The bound
        is exact whatever step the run resumed from: a round that could
        fire several barriers stops at ``steps`` instead of overshooting
        (resume parity must not change where a run lands)."""
        self._stop_at = steps
        try:
            for _ in range(max_rounds):
                if self._applied >= steps:
                    break
                fired = self.step(now)
                now += dt
                if fired == 0 and self.backlog() == 0:
                    break  # stream exhausted below one global batch
        finally:
            self._stop_at = None
        if self.store is not None:
            self.save_checkpoint()
        if self._async or self._pending_commits:
            self.flush_durability(now)
        return self._applied
