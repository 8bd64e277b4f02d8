"""Continuous batching scheduler (reactive serving layer).

Requests arrive in a mailbox (asynchronous messaging layer); the batcher
holds a fixed-slot decode batch and, whenever a slot frees (EOS or
max-new-tokens), admits the next request from the queue — the serving
analogue of the elastic task pool: the queue depth is the scaling signal,
slots are tasks, and the admission policy is the message-distribution
scheduler (FCFS here; priority policies plug in the same way).

Slot state lives in the shared KV cache; admission resets a slot's cache
rows via the prefill path with the model's cache update at position 0.
Shapes stay static (slots, max_len) so the decode step never recompiles —
the elasticity is in *occupancy*, not in tensor shapes (TPU-friendly).

Paged mode (``paged=PagedSpec(...)``) swaps the per-slot ``[max_len]``
cache rows for a shared page pool behind per-slot page tables: a slot
holds only the pages its request actually fills, pages are granted one at
a time as the decode position crosses page boundaries, and a slot that
cannot get its next page is *preempted* — pages freed, request requeued
undecoded (Let-It-Crash: recompute beats repair).  Shapes are still
static (``[P, page, ...]`` pools, ``[slots, n_pages]`` tables), so paging
changes occupancy economics without ever recompiling the decode step.

``admission="per_request"`` is the measurement baseline: gang admission
(a batch is admitted only when every slot is empty and runs to
completion) — classic static batching, what the continuous+paged bench
grid compares against.
"""

from __future__ import annotations

import itertools
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.config.base import FFNKind
from repro.core.messages import Mailbox, Message
from repro.models.zoo import Model
from repro.serving.kv_cache import PagedSpec, PagePool
from repro.serving.serve_step import make_decode_step, make_prefill_step
from repro.telemetry.profile import span

_req_ids = itertools.count()


def ensure_req_ids_above(floor: int) -> None:
    """Advance the request-id counter past ``floor``.

    Request ids are process-local; a restarted serving process would
    reissue ids that already live in a durable requests/responses log and
    collide with the exactly-once dedup there.  ``ServingJob`` calls this
    with the highest id found in the log it reopens."""
    global _req_ids
    nxt = next(_req_ids)
    _req_ids = itertools.count(max(nxt, floor + 1))


@dataclass
class Request:
    prompt: List[int]
    max_new_tokens: int = 16
    req_id: int = field(default_factory=lambda: next(_req_ids))
    # SLO hints consumed by the deadline admission policy (core.scheduler);
    # deadline is absolute time, priority breaks ties (higher = sooner).
    deadline: Optional[float] = None
    priority: int = 0
    # Owning tenant (multi-tenant fleet): stamped by FleetManager.submit
    # and carried through every payload round-trip so shed/fail events are
    # attributable per tenant in the bench, not inferred.
    tenant: Optional[str] = None
    # Pinned first token, set by the dedicated prefill stage when the
    # serving job splits prefill from decode (``split_prefill``).  The
    # decode stage re-materializes the KV state locally at admission but
    # *trusts* this token — it is durable in the prefilled topic, so a
    # replayed decode emits the identical stream.
    first_token: Optional[int] = None
    # filled on completion; enqueued_at is stamped once, on the first
    # successful admission — defer-mode retries and Let-It-Crash
    # re-admissions must not reset the latency clock.
    output: Optional[List[int]] = None
    enqueued_at: Optional[float] = None
    completed_at: float = 0.0
    restarts: int = 0  # times re-admitted after a replica death
    # Why an empty completion happened ("invalid" | "oversize" | "shed");
    # None for a normally decoded request.
    fail_reason: Optional[str] = None

    def reset_for_readmission(self) -> "Request":
        """Back to the not-yet-decoded state (Let-It-Crash re-admission)."""
        self.output = None
        self.completed_at = 0.0
        self.fail_reason = None
        self.restarts += 1
        return self


class ContinuousBatcher:
    def __init__(
        self,
        model: Model,
        params: Any,
        slots: int = 4,
        max_len: int = 128,
        eos_token: int = -1,  # -1: run to max_new_tokens
        temperature: float = 0.0,
        queue: Optional[Mailbox] = None,
        prefill_step=None,
        decode_step=None,
        name: str = "serve-requests",
        paged: Optional[PagedSpec] = None,
        admission: str = "continuous",  # "continuous" | "per_request"
    ) -> None:
        self.model = model
        self.params = params
        self.name = name
        self.slots = slots
        self.max_len = max_len
        self.eos = eos_token
        # The queue and the jit'd steps are injectable so a pool of replicas
        # can share one mailbox namespace and one compiled step (a replica
        # spawned mid-spike must not pay a retrace: cache shapes are
        # identical across replicas by construction).
        self.queue = queue if queue is not None else Mailbox(name)
        self.prefill_step = prefill_step or make_prefill_step(model)
        self.decode_step = decode_step or make_decode_step(model, temperature)
        # Elasticity knob: how many of the static slots admission may fill.
        # Shapes never change — an occupancy cap below `slots` just leaves
        # batch rows idle (TPU-friendly elasticity, see module docstring).
        self.target_occupancy = slots
        self.completed: List[Request] = []
        # slot state
        self.active: List[Optional[Request]] = [None] * slots
        self.positions = np.zeros((slots,), dtype=np.int32)
        self.budgets = np.zeros((slots,), dtype=np.int32)
        self.cur_tokens = np.zeros((slots, 1), dtype=np.int32)
        self.outputs: List[List[int]] = [[] for _ in range(slots)]
        # one shared cache; slot b owns batch row b.  Per-slot prefill uses
        # a single-row cache then writes the rows back.
        if admission not in ("continuous", "per_request"):
            raise ValueError(f"unknown admission mode {admission!r}")
        self.admission = admission
        self.paged = paged
        self.cache = model.init_cache(slots, max_len, paged=paged)
        self.page_pool: Optional[PagePool] = None
        if paged is not None:
            self.page_pool = PagePool(paged)
            # host mirror of the per-slot page tables; pushed to the
            # device cache once per dirty tick, not once per mutation.
            self._page_table = np.zeros(
                (slots, paged.pages_per_slot(max_len)), dtype=np.int32
            )
            self._table_dirty = False
            self.slot_pages: List[List[int]] = [[] for _ in range(slots)]
        # requests that could not be admitted for lack of pages — or were
        # preempted mid-decode — wait here, ahead of the queue and sorted
        # by arrival, until a finish or preemption frees pages.
        self._stalled: List[Message] = []
        self.preemptions = 0
        self.admit_stalls = 0
        self.rejected_oversize = 0
        self.rejected_invalid = 0
        # CRDT MetricsReplica, assigned by the owning pool worker; when set,
        # the serving-local counters above are mirrored into it so the
        # fleet bench reads every tenant uniformly through the hub.
        self.metrics = None
        self.rng = jax.random.PRNGKey(0)
        self.steps = 0
        # (row, expert) assignments of the decoded rows that landed on the
        # routed experts this chip holds, summed over the MoE layers (models
        # with an expert share).
        self.held_expert_rows = 0

    def _note(self, name: str, amount: int = 1) -> None:
        if self.metrics is not None:
            self.metrics.incr(name, amount)

    def _note_page_peak(self) -> None:
        if self.metrics is not None and self.page_pool is not None:
            self.metrics.record_max(
                "serve.page_high_watermark", self.page_pool.high_watermark
            )

    # -- API --------------------------------------------------------------
    def submit(self, req: Request, now: float = 0.0) -> None:
        if req.enqueued_at is None:
            req.enqueued_at = now
        self.queue.put(Message(topic="serve", payload=req, created_at=now))

    def queue_depth(self) -> int:
        return self.queue.depth() + len(self._stalled)

    def occupancy(self) -> int:
        return sum(1 for r in self.active if r is not None)

    def set_target_occupancy(self, n: int) -> None:
        """Clamp admission to ``n`` of the static slots (0..slots).

        Slots above the target finish their in-flight request and then stay
        empty — scale-in never cancels running work."""
        self.target_occupancy = max(0, min(int(n), self.slots))

    # -- internals ----------------------------------------------------------
    def _admit(self, slot: int, req: Request) -> bool:
        """Prefill ``req`` into slot ``slot``.  Returns False when paged
        mode cannot grant the prompt's pages (caller stalls the request;
        slot state is untouched).  The ``serve.admit`` span ends once the
        first token is on the host."""
        ids = None
        if self.paged is not None:
            ids = self._alloc_prompt_pages(req)
            if ids is None:
                return False
        with span("serve.admit", req=req.req_id, prompt_len=len(req.prompt),
                  slot=slot):
            if ids is None:
                next_tok = self._prefill_dense(slot, req)
            else:
                next_tok = self._prefill_paged(slot, req, ids)
            first = (
                req.first_token if req.first_token is not None
                else int(next_tok[0])
            )
        self.active[slot] = req
        self.positions[slot] = len(req.prompt)
        self.budgets[slot] = req.max_new_tokens - 1
        self.cur_tokens[slot, 0] = first
        self.outputs[slot] = [first]
        return True

    def _prefill_dense(self, slot: int, req: Request) -> jax.Array:
        """Dense admission: prefill a single-row cache, then write it into
        the shared cache at row ``slot``.  Returns the first decoded
        token (on the device)."""
        from jax.tree_util import DictKey, tree_map_with_path

        with span("serve.prefill"):
            prompt = jnp.asarray(req.prompt, dtype=jnp.int32)[None, :]
            row_cache = self.model.init_cache(1, self.max_len)
            next_tok, row_cache = self.prefill_step(
                self.params, {"tokens": prompt}, row_cache
            )

        # Leaves under "periods" are stacked [n_periods, B, ...] (batch is
        # axis 1); everything else leads with batch.
        def write_row(path, full, row):
            in_periods = any(
                isinstance(p, DictKey) and p.key == "periods"
                for p in path[:1]
            )
            if in_periods:
                return full.at[:, slot].set(row[:, 0])
            return full.at[slot].set(row[0])

        with span("serve.merge"):
            self.cache = tree_map_with_path(write_row, self.cache, row_cache)
        return next_tok

    def _alloc_prompt_pages(self, req: Request) -> Optional[List[int]]:
        """The shared pool's pages for ``req``'s prompt, or None when the
        pool cannot grant them right now."""
        assert self.paged is not None and self.page_pool is not None
        ids = self.page_pool.alloc(self.page_pool.pages_for(len(req.prompt)))
        if ids is None:
            self._note("serve.page_alloc_failures")
            return None
        self._note_page_peak()
        return ids

    def _prefill_paged(self, slot: int, req: Request,
                       ids: List[int]) -> jax.Array:
        """Paged admission: prefill into a single-row scratch pool, then
        copy the filled pages into the shared pool at the granted
        ``ids``.  Returns the first decoded token (on the device)."""
        from jax.tree_util import DictKey, tree_map_with_path

        def leaf_key(path) -> Optional[str]:
            last = path[-1]
            return last.key if isinstance(last, DictKey) else None

        need = len(ids)
        # Scratch pool: page 0 reserved + exactly the prompt's pages,
        # mapped 1:1 onto temp ids 1..need.
        row_spec = PagedSpec(num_pages=need + 1, page_size=self.paged.page_size)
        tmp_table = np.zeros((1, row_spec.pages_per_slot(self.max_len)),
                             dtype=np.int32)
        tmp_table[0, :need] = np.arange(1, need + 1)

        with span("serve.prefill"):
            prompt = jnp.asarray(req.prompt, dtype=jnp.int32)[None, :]
            row_cache = self.model.init_cache(1, self.max_len, paged=row_spec)
            tmp_dev = jnp.asarray(tmp_table)

            def set_tmp_table(path, leaf):
                if leaf_key(path) == "page_table":
                    return jnp.broadcast_to(tmp_dev, leaf.shape).astype(leaf.dtype)
                return leaf

            row_cache = tree_map_with_path(set_tmp_table, row_cache)
            next_tok, row_cache = self.prefill_step(
                self.params, {"tokens": prompt}, row_cache
            )

        def merge(path, full, row):
            key = leaf_key(path)
            in_periods = any(
                isinstance(p, DictKey) and p.key == "periods" for p in path[:1]
            )
            if (key or "").endswith("_pages"):
                # copy the scratch pages (temp ids 1..need) onto the
                # granted shared ids — the gather map, inverted.
                if in_periods:
                    return full.at[:, ids_arr].set(row[:, 1:need + 1])
                return full.at[ids_arr].set(row[1:need + 1])
            if key == "page_table":
                return full  # host mirror is authoritative; synced below
            if in_periods:
                return full.at[:, slot].set(row[:, 0])
            return full.at[slot].set(row[0])

        with span("serve.merge"):
            ids_arr = jnp.asarray(ids, dtype=jnp.int32)
            self.cache = tree_map_with_path(merge, self.cache, row_cache)
            self.slot_pages[slot] = list(ids)
            self._page_table[slot] = 0
            self._page_table[slot, :need] = ids
            self._table_dirty = True
        return next_tok

    def _release_pages(self, slot: int) -> None:
        if self.paged is None:
            return
        if self.slot_pages[slot]:
            self.page_pool.free(self.slot_pages[slot])
            self.slot_pages[slot] = []
        self._page_table[slot] = 0  # back to the scratch page
        self._table_dirty = True
        self._reset_slot_pos(slot)

    def _reset_slot_pos(self, slot: int) -> None:
        """Zero the device-cache decode position of a freed slot.

        An empty slot still rides the jit'd decode step (shapes are
        static), so its cache ``pos`` keeps advancing every tick; left
        alone it runs past ``n_pages * page_size`` and the kv-append
        page-table lookup goes out of range (the kernel and wrapper
        clamp that read defensively, but resetting here keeps the slot
        well inside its table between admissions)."""
        from jax.tree_util import DictKey, tree_map_with_path

        def zero(path, leaf):
            last = path[-1]
            if not (isinstance(last, DictKey) and last.key == "pos"):
                return leaf
            in_periods = any(
                isinstance(p, DictKey) and p.key == "periods"
                for p in path[:1]
            )
            if in_periods:
                return leaf.at[:, slot].set(0)
            return leaf.at[slot].set(0)

        self.cache = tree_map_with_path(zero, self.cache)

    def _sync_page_table(self) -> None:
        if self.paged is None or not self._table_dirty:
            return
        from jax.tree_util import DictKey, tree_map_with_path

        def set_table(path, leaf):
            last = path[-1]
            if isinstance(last, DictKey) and last.key == "page_table":
                # Uploaded anew for every leaf, so no two leaves share a
                # buffer: the decode step donates the cache, and a buffer
                # cannot be donated twice.
                table = jnp.asarray(self._page_table, dtype=leaf.dtype)
                return jnp.broadcast_to(table, leaf.shape)
            return leaf

        self.cache = tree_map_with_path(set_table, self.cache)
        self._table_dirty = False

    def _stall(self, msg: Message) -> None:
        """Park ``msg`` for retry ahead of the live queue, keeping
        ``_stalled`` sorted by arrival (enqueued_at, then req_id).  A
        preempted request is by construction the oldest work in flight —
        appended at the tail it would requeue behind younger stalled
        arrivals and become the repeat preemption victim under pressure;
        sorted insertion preserves the documented arrival-order
        fairness no matter how entries got here."""

        def key(m: Message):
            r = m.payload
            at = r.enqueued_at if r.enqueued_at is not None else m.created_at
            return (at, r.req_id)

        idx = len(self._stalled)
        for i, other in enumerate(self._stalled):
            if key(msg) < key(other):
                idx = i
                break
        self._stalled.insert(idx, msg)

    def _preempt(self, slot: int) -> None:
        """Evict a running slot: free its pages, requeue the request
        undecoded (ahead of the queue).  The continuous-batching analogue
        of Let-It-Crash — recompute beats repairing a half-paged slot."""
        req = self.active[slot]
        self.active[slot] = None
        self.outputs[slot] = []
        self.budgets[slot] = 0
        self.positions[slot] = 0
        self._release_pages(slot)
        self.preemptions += 1
        self._note("serve.slot_preemptions")
        if req is not None:
            req.reset_for_readmission()
            self._stall(
                Message(topic="serve", payload=req,
                        created_at=req.enqueued_at or 0.0)
            )

    def _ensure_pages(self) -> None:
        """Grant each active slot the page its next write lands in;
        preempt slots the pool cannot serve."""
        if self.paged is None:
            return
        for slot in range(self.slots):
            if self.active[slot] is None:
                continue
            idx = int(self.positions[slot]) // self.paged.page_size
            if idx < len(self.slot_pages[slot]):
                continue
            got = self.page_pool.alloc(1)
            if got is None:
                self._note("serve.page_alloc_failures")
                self._preempt(slot)
                continue
            self._note_page_peak()
            self._page_table[slot, len(self.slot_pages[slot])] = got[0]
            self.slot_pages[slot].extend(got)
            self._table_dirty = True

    def _note_expert_rows(self, per_row: np.ndarray) -> None:
        """Sum the decoded rows' held-expert assignments into the stats;
        the span carries the step's count and the experts held."""
        held = int(sum(int(per_row[s]) for s in range(self.slots)
                       if self.active[s] is not None))
        self.held_expert_rows += held
        cfg = self.model.cfg
        layers = sum(cfg.layer_spec(i).ffn == FFNKind.MOE
                     for i in range(cfg.num_layers))
        with span("serve.expert_rows", assignments=held,
                  experts=cfg.moe.held_experts, layers=layers):
            pass

    def _finish(self, slot: int, now: float) -> None:
        req = self.active[slot]
        with span("serve.finish", req=req.req_id if req is not None else -1,
                  tokens=len(self.outputs[slot])):
            if req is not None:
                req.output = list(self.outputs[slot])
                req.completed_at = now
                self.completed.append(req)
            self.active[slot] = None
            self.outputs[slot] = []
            self.budgets[slot] = 0
            self._release_pages(slot)

    def _next_message(self) -> Optional[Message]:
        """Stalled requests (blocked on pages earlier) go first, keeping
        arrival order; then the live queue."""
        if self._stalled:
            return self._stalled.pop(0)
        return self.queue.get()

    def step(self, now: float = 0.0) -> int:
        """Admit from queue (up to the occupancy target), run one decode
        step for occupied slots."""
        occupied = self.occupancy()
        # per_request (static batching baseline): gang admission — a new
        # batch may only form once every slot of the old one has finished.
        gang_blocked = self.admission == "per_request" and occupied > 0
        for slot in range(self.slots):
            if gang_blocked or occupied >= self.target_occupancy:
                break
            if self.active[slot] is None:
                msg = self._next_message()
                if msg is None:
                    break
                req = msg.payload
                if not req.prompt or len(req.prompt) > self.max_len - 1:
                    # Unservable at any pool state: an empty prompt has
                    # nothing to prefill (and would build a zero-page
                    # PagedSpec), and a prompt at/over max_len leaves no
                    # room for even one decoded token (paged mode would
                    # also overrun the slot's page-table width).  Fail
                    # fast instead of crashing the tick.
                    self.rejected_invalid += 1
                    self._note("serve.rejected_invalid")
                    req.fail_reason = "invalid"
                    req.output = []
                    req.completed_at = now
                    self.completed.append(req)
                    continue
                if (
                    self.paged is not None
                    and not self.page_pool.fits(
                        min(len(req.prompt) + req.max_new_tokens, self.max_len)
                    )
                ):
                    # Larger than the whole pool: it could never run even
                    # with every page to itself — fail it rather than
                    # livelock through endless preemption.
                    self.rejected_oversize += 1
                    self._note("serve.rejected_oversize")
                    req.fail_reason = "oversize"
                    req.output = []
                    req.completed_at = now
                    self.completed.append(req)
                    continue
                if not self._admit(slot, req):
                    # pool can't grant the prompt's pages right now; wait
                    # at the head of the line for a finish/preemption.
                    self.admit_stalls += 1
                    self._note("serve.admit_stalls")
                    self._stall(msg)
                    break
                occupied += 1

        if self.occupancy() == 0:
            return 0

        # Grant each slot the page its next token lands in (may preempt).
        with span("serve.pages"):
            self._ensure_pages()
            rows = self.occupancy()
            if rows:
                self._sync_page_table()
        if rows == 0:
            return 0

        with span("serve.decode", rows=rows):
            tokens = jnp.asarray(self.cur_tokens)
            positions = jnp.asarray(self.positions)
            next_tok, self.cache, self.rng = self.decode_step(
                self.params, tokens, self.cache, positions, self.rng
            )
        with span("serve.token_wait"):
            if "expert_rows" in self.cache:
                next_np, expert_rows = jax.device_get(
                    (next_tok, self.cache["expert_rows"]))
            else:
                next_np, expert_rows = np.asarray(next_tok), None
        if expert_rows is not None:
            self._note_expert_rows(expert_rows)
        decoded = 0
        for slot in range(self.slots):
            if self.active[slot] is None:
                continue
            decoded += 1
            tok = int(next_np[slot])
            self.outputs[slot].append(tok)
            self.positions[slot] += 1
            self.budgets[slot] -= 1
            self.cur_tokens[slot, 0] = tok
            hit_eos = self.eos >= 0 and tok == self.eos
            if self.budgets[slot] <= 0 or hit_eos or (
                self.positions[slot] >= self.max_len - 1
            ):
                self._finish(slot, now)
        self.steps += 1
        return decoded

    def run_until_drained(self, max_steps: int = 10_000, now: float = 0.0) -> int:
        n = 0
        for _ in range(max_steps):
            if self.occupancy() == 0 and self.queue_depth() == 0:
                break
            n += self.step(now)
        return n
