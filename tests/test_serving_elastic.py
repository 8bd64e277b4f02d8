"""Elastic serving loop: autoscaled occupancy, replica scale-out, bounded
ingress backpressure, chaos-kill re-admission, and admission-policy
selection — all deterministic via the arithmetic stub model (no weights)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.elastic import AutoscalerConfig, split_units
from repro.core.messages import Mailbox, Message
from repro.core.scheduler import DeadlineScheduler, make_scheduler
from repro.models.stub import StubModel
from repro.serving import ContinuousBatcher, ElasticServingPool, Request


@pytest.fixture(scope="module")
def stub():
    model = StubModel()
    params = model.init(jax.random.PRNGKey(0))
    return model, params


def greedy_reference(model, params, prompt, n_new):
    toks = list(prompt)
    out = []
    for _ in range(n_new):
        logits, _ = model.train_logits(
            params, {"tokens": jnp.asarray(toks, dtype=jnp.int32)[None]}
        )
        nxt = int(jnp.argmax(logits[0, -1]))
        out.append(nxt)
        toks.append(nxt)
    return out


def make_pool(stub, **kwargs):
    model, params = stub
    defaults = dict(slots_per_replica=2, max_replicas=2, initial_units=1,
                    heartbeat_timeout=3.0)
    defaults.update(kwargs)
    return ElasticServingPool(model, params, **defaults)


# --- building blocks ----------------------------------------------------------


def test_split_units_fills_replicas_first():
    assert split_units(1, 4) == [1]
    assert split_units(4, 4) == [4]
    assert split_units(5, 4) == [4, 1]
    assert split_units(8, 4) == [4, 4]
    assert split_units(0, 4) == [1]  # never below one unit


def test_mailbox_try_put_and_put_front():
    box = Mailbox("t", capacity=2)
    assert box.try_put(Message(topic="x", payload=1))
    assert box.try_put(Message(topic="x", payload=2))
    assert not box.try_put(Message(topic="x", payload=3))  # full: no raise
    assert box.dropped == 1
    box.put_front(Message(topic="x", payload=0))  # re-admission ignores cap
    assert box.depth() == 3
    assert box.get().payload == 0


def test_deadline_scheduler_orders_by_urgency():
    sched = make_scheduler("edf")
    assert isinstance(sched, DeadlineScheduler)
    lax = Message(topic="s", payload=Request(prompt=[1], deadline=50.0))
    urgent = Message(topic="s", payload=Request(prompt=[2], deadline=1.0))
    none = Message(topic="s", payload=Request(prompt=[3]))
    assert [m.payload.deadline for m in sched.order([lax, none, urgent])] == [
        1.0, 50.0, None,
    ]
    # priority breaks in when no deadline is set (higher = sooner)
    hi = Message(topic="s", payload=Request(prompt=[4], priority=9))
    assert sched.order([none, hi])[0] is hi
    # ...but any deadline outranks bare priority, and negative priority
    # yields even to neutral traffic
    bg = Message(topic="s", payload=Request(prompt=[5], priority=-5))
    assert [m.payload.prompt[0] for m in sched.order([bg, hi, none, lax])] \
        == [1, 4, 3, 5]  # deadline, then hi-pri, neutral, deprioritized
    assert [m.payload.prompt[0] for m in sched.order([hi, urgent])] == [2, 4]


def test_stub_batcher_matches_full_forward(stub):
    """Anchor: continuous batching over the stub reproduces the reference
    token-for-token, so every pool test below checks real decode output."""
    model, params = stub
    b = ContinuousBatcher(model, params, slots=2, max_len=32)
    prompts = [[5, 9, 2], [7, 1], [11]]
    for p in prompts:
        b.submit(Request(prompt=p, max_new_tokens=5))
    b.run_until_drained()
    assert len(b.completed) == 3
    for r in b.completed:
        assert r.output == greedy_reference(model, params, r.prompt, 5)


def test_occupancy_target_caps_admission(stub):
    model, params = stub
    b = ContinuousBatcher(model, params, slots=4, max_len=32)
    b.set_target_occupancy(2)
    for i in range(6):
        b.submit(Request(prompt=[i + 1], max_new_tokens=4))
    b.step()
    assert b.occupancy() == 2  # half the static slots stay idle
    b.set_target_occupancy(4)
    b.step()
    assert b.occupancy() == 4
    b.run_until_drained()
    assert len(b.completed) == 6


# --- elasticity ---------------------------------------------------------------


def test_autoscaler_scales_occupancy_up_and_back_down(stub):
    """Acceptance: a burst drives the slot-unit target from 1 to the
    maximum (spawning a second replica) and idleness brings it back."""
    pool = make_pool(stub)
    for i in range(24):
        pool.submit(Request(prompt=[i % 5 + 1], max_new_tokens=6), now=0.0)
    now = 1.0
    for _ in range(200):
        if pool.queue_depth() == 0 and pool.occupancy() == 0:
            break
        pool.step(now)
        now += 1.0
    # a few idle steps so the scale-in side of the hysteresis fires
    for _ in range(3):
        pool.step(now)
        now += 1.0
    targets = [t for (_, t, _, _) in pool.occupancy_log]
    occupancies = [o for (_, _, o, _) in pool.occupancy_log]
    replicas = [n for (_, _, _, n) in pool.occupancy_log]
    assert max(targets) == 4, targets          # scaled out to the cap
    assert targets[-1] == 1, targets           # and back down after the spike
    assert max(occupancies) >= 3               # the slots actually filled
    assert occupancies[-1] == 0
    assert max(replicas) == 2                  # true scale-out, not one box
    assert len(pool.completed) == 24
    model, params = stub
    for r in pool.completed:
        assert r.output == greedy_reference(model, params, r.prompt, 6)


def _launcher_targets(stub, argv):
    """The unit targets of a pool built as the serving launcher builds it,
    through a burst of 24 requests and a few idle steps."""
    from repro.launch import serve

    model, params = stub
    args = serve.make_parser().parse_args(["--stub", "--slots", "2",
                                           "--max-replicas", "2", *argv])
    pool = ElasticServingPool(model, params, **serve.pool_kwargs(args))
    for i in range(24):
        pool.submit(Request(prompt=[i % 5 + 1], max_new_tokens=6), now=0.0)
    now = 1.0
    while pool.queue_depth() or pool.occupancy():
        pool.step(now)
        now += 1.0
    for _ in range(3):
        pool.step(now)
        now += 1.0
    assert len(pool.completed) == 24
    return [t for (_, t, _, _) in pool.occupancy_log]


def test_launcher_keeps_one_replicas_slots_open(stub):
    """The launcher's pool scales by whole replicas and never caps a
    replica below its slots; --spike keeps the ramp from one slot."""
    targets = _launcher_targets(stub, [])
    assert min(targets) == 2 and max(targets) == 4, targets
    assert targets[-1] == 2, targets
    spike = _launcher_targets(stub, ["--spike"])
    assert min(spike) == 1 and max(spike) == 4, spike


def test_scale_in_drains_without_cancelling(stub):
    pool = make_pool(stub, initial_units=4)  # start wide: 2 replicas
    assert len(pool.active_replicas()) == 2
    reqs = [Request(prompt=[i + 1], max_new_tokens=10) for i in range(4)]
    for r in reqs:
        pool.submit(r, now=0.0)
    now = 1.0
    for _ in range(50):
        if pool.queue_depth() == 0 and pool.occupancy() == 0:
            break
        pool.step(now)
        now += 1.0
    # backlog/worker fell below the low watermark long before the decode
    # budget ran out: replicas drained away, yet every request completed.
    assert len(pool.completed) == 4
    assert pool.metrics.value("serve.replica_draining") >= 1
    assert all(len(r.output) == 10 for r in pool.completed)


# --- backpressure -------------------------------------------------------------


def test_bounded_ingress_sheds_overflow(stub):
    pool = make_pool(stub, ingress_capacity=3, overflow="shed")
    accepted = [
        pool.submit(Request(prompt=[1], max_new_tokens=2), now=0.0)
        for _ in range(8)
    ]
    assert sum(accepted) == 3
    assert pool.metrics.value("serve.shed") == 5
    assert len(pool.shed) == 5
    pool.run_until_drained()
    assert len(pool.completed) == 3  # shed requests are gone for good


def test_defer_mode_rejects_without_dropping(stub):
    pool = make_pool(stub, ingress_capacity=2, overflow="defer")
    assert pool.submit(Request(prompt=[1], max_new_tokens=2), now=0.0)
    assert pool.submit(Request(prompt=[2], max_new_tokens=2), now=0.0)
    req = Request(prompt=[3], max_new_tokens=2)
    assert not pool.submit(req, now=0.0)          # caller owns the retry
    assert pool.metrics.value("serve.deferred") == 1
    assert not pool.shed
    pool.step(1.0)                                 # frees ingress space
    assert pool.submit(req, now=1.0)               # retry now fits
    pool.run_until_drained(now=2.0)
    assert len(pool.completed) == 3


# --- resilience ---------------------------------------------------------------


def test_replica_kill_readmits_and_completes_exactly_once(stub):
    model, params = stub
    pool = make_pool(stub, initial_units=4, heartbeat_timeout=2.0)
    reqs = [Request(prompt=[i % 5 + 1], max_new_tokens=8) for i in range(12)]
    for r in reqs:
        pool.submit(r, now=0.0)
    now = 1.0
    for _ in range(3):
        pool.step(now)
        now += 1.0
    killed = pool.kill_replica(0)
    assert pool.occupancy() > 0, "work must be in flight at the kill"
    for _ in range(100):
        if pool.queue_depth() == 0 and pool.occupancy() == 0:
            break
        pool.step(now)
        now += 1.0
    assert len(pool.completed) == 12
    assert sorted(r.req_id for r in pool.completed) == sorted(
        r.req_id for r in reqs
    )
    assert pool.metrics.value("serve.replica_restarts") == 1
    assert pool.metrics.value("serve.readmitted") > 0
    assert any(r.restarts > 0 for r in pool.completed)
    assert any(e[1] == "restarted" and e[2] == killed
               for e in pool.supervisor.events)
    # re-decoded from scratch: outputs still exact
    for r in pool.completed:
        assert r.output == greedy_reference(model, params, r.prompt, 8)


def test_kill_before_first_step_still_recovers(stub):
    """A replica killed before it ever heartbeats must still be detected
    (detectors are seeded at supervise time) — no trapped requests."""
    pool = make_pool(stub, heartbeat_timeout=2.0)
    req = Request(prompt=[3], max_new_tokens=3)
    pool.submit(req, now=0.0)
    pool.kill_replica(0)  # before any pool.step
    now = 1.0
    for _ in range(50):
        if pool.queue_depth() == 0 and pool.occupancy() == 0:
            break
        pool.step(now)
        now += 1.0
    assert len(pool.completed) == 1
    assert pool.metrics.value("serve.replica_restarts") == 1


def test_deferred_retry_keeps_latency_clock(stub):
    """enqueued_at is stamped at the first submit attempt, so the wait in
    a defer-retry loop shows up in the measured latency."""
    pool = make_pool(stub, ingress_capacity=1, overflow="defer")
    first = Request(prompt=[1], max_new_tokens=2)
    parked = Request(prompt=[2], max_new_tokens=2)
    assert pool.submit(first, now=0.0)
    assert not pool.submit(parked, now=0.0)   # rejected, clock started
    pool.step(1.0)
    assert pool.submit(parked, now=5.0)       # retried much later
    pool.run_until_drained(now=6.0)
    assert parked.enqueued_at == 0.0          # not reset by the retry
    assert parked.completed_at - parked.enqueued_at >= 6.0


# --- admission policies -------------------------------------------------------


@pytest.mark.parametrize("policy,expected", [
    ("fcfs", "round_robin"),
    ("round_robin", "round_robin"),
    ("jsq", "jsq"),
    ("pow2", "pow2"),
    ("edf", "edf"),
])
def test_policy_selection(stub, policy, expected):
    pool = make_pool(stub, policy=policy)
    assert pool.scheduler.name == expected
    assert pool.policy_name == policy


def test_unknown_policy_rejected(stub):
    with pytest.raises(ValueError):
        make_pool(stub, policy="lifo")


def test_load_aware_policy_beats_fcfs_tail_with_straggler(stub):
    """Acceptance: on a bursty open-loop trace against a pool with one
    slow replica, JSQ's p99 completion time beats blind FCFS round-robin
    (bench_serving sweeps this across seeds; one seed suffices here)."""
    model, params = stub

    def p99(policy):
        pool = ElasticServingPool(
            model, params, slots_per_replica=4, max_replicas=4,
            initial_units=16, policy=policy,
            replica_queue_capacity=64,
            replica_speeds=[1.0, 1.0, 1.0, 0.25],
            autoscaler=AutoscalerConfig(high_watermark=1e9, low_watermark=-1.0),
            heartbeat_timeout=1e12,
        )
        rng = np.random.default_rng(0)
        arrivals = []
        for t in range(240):
            rate = 2.2 if 40 <= t < 100 else 0.9
            for _ in range(rng.poisson(rate)):
                arrivals.append(
                    (t, [int(x) for x in rng.integers(1, 90, 2)],
                     int(rng.integers(2, 24)))
                )
        i, t = 0, 0
        while i < len(arrivals) or pool.queue_depth() or pool.occupancy():
            while i < len(arrivals) and arrivals[i][0] <= t:
                _, prompt, n = arrivals[i]
                pool.submit(Request(prompt=prompt, max_new_tokens=n),
                            now=float(t))
                i += 1
            pool.step(float(t))
            t += 1
            assert t < 5000
        lat = [r.completed_at - r.enqueued_at for r in pool.completed]
        return float(np.percentile(lat, 99))

    assert p99("jsq") < p99("fcfs")


def test_edf_urgent_request_overtakes_lax_backlog(stub):
    """One slot, three queued requests: under EDF the late-submitted but
    urgent request decodes first; under FCFS it decodes last."""
    def completion_order(policy):
        pool = make_pool(stub, slots_per_replica=1, max_replicas=1,
                         initial_units=1)
        pool.scheduler = make_scheduler(policy)
        lax1 = Request(prompt=[1], max_new_tokens=4, deadline=100.0)
        lax2 = Request(prompt=[2], max_new_tokens=4, deadline=100.0)
        urgent = Request(prompt=[3], max_new_tokens=4, deadline=1.0)
        for r in (lax1, lax2, urgent):
            pool.submit(r, now=0.0)
        pool.run_until_drained(now=1.0)
        return [r.req_id for r in pool.completed], (lax1, lax2, urgent)

    order_edf, (l1, _, urgent) = completion_order("edf")
    assert order_edf[0] == urgent.req_id
    order_fcfs, (l1, _, urgent) = completion_order("fcfs")
    assert order_fcfs[0] == l1.req_id
    assert order_fcfs[-1] == urgent.req_id
