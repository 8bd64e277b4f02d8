"""The readers of the program's spans (``program_spans.py`` and the
metrics built on it), on small traces recorded on the CPU with spans
named as the program names them."""

import time

import jax
import jax.numpy as jnp
import pytest
from jax.profiler import TraceAnnotation

import smoke  # noqa: F401
import common
import program_spans as P
import traces as T

SERVE = ("admit_ms.serve", "tick_host_ms.serve", "idle_share.serve.admit",
         "idle_share.serve.tick")
TRAIN = ("host_ms_per_step.train", "idle_share.train.input")
HOST_S = 0.01  # host work in each tick, outside every wait


def cpu_lines(plane, line):
    if plane == "/host:CPU" and line.startswith("tf_XLAPjRtCpuClient"):
        return "ops"
    return None


def serve_ticks(f, x, n):
    for i in range(n):
        with TraceAnnotation("serve.tick", tick=i):
            with TraceAnnotation("serve.admit", req=i, prompt_len=4, slot=0):
                f(x).block_until_ready()
            time.sleep(HOST_S)
            with TraceAnnotation("serve.decode", rows=1):
                y = f(x)
            with TraceAnnotation("serve.token_wait"):
                y.block_until_ready()
        time.sleep(HOST_S / 2)  # the harness between ticks


def train_ticks(f, x, n):
    for i in range(n):
        with TraceAnnotation("train.tick", tick=i):
            with TraceAnnotation("train.assemble"):
                time.sleep(HOST_S)
            with TraceAnnotation("train.step", step=i + 1):
                with TraceAnnotation("train.upload"):
                    y = f(x)
                with TraceAnnotation("train.loss_wait"):
                    y.block_until_ready()
                with TraceAnnotation("train.commit"):
                    time.sleep(HOST_S / 2)


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    """Four traces under one directory, oldest first: serving with 3
    ticks, training with 2, no program spans, serving with 2 ticks."""
    root = tmp_path_factory.mktemp("traces")
    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((256, 256))
    f(x).block_until_ready()
    out = {}
    for name, body in (("serve3", lambda: serve_ticks(f, x, 3)),
                       ("train", lambda: train_ticks(f, x, 2)),
                       ("bare", lambda: [f(x).block_until_ready() for _ in range(3)]),
                       ("serve2", lambda: serve_ticks(f, x, 2))):
        d = str(root / name)
        with T.capture(d):
            with TraceAnnotation(T.WINDOW):
                body()
        out[name] = {"trace": T.reduce(d, classify=cpu_lines)}
        time.sleep(0.05)  # distinct modification times
    return str(root), out


@pytest.fixture
def read(recorded, monkeypatch):
    monkeypatch.setattr(P, "TRACES", recorded[0])
    return lambda metric, ctx: common.reader(metric)(ctx)


@pytest.mark.parametrize("cell,ticks", [("serve3", 3), ("serve2", 2)])
def test_the_trace_of_the_window_is_picked(recorded, cell, ticks):
    root, ctx = recorded
    found = P.named(ctx[cell], "serve.tick", trace_dir=root)
    assert [s.stats["tick"] for s in found] == list(range(ticks))
    assert [s.stats["req"] for s in P.named(ctx[cell], "serve.admit", trace_dir=root)] \
        == list(range(ticks))
    lo, hi = ctx[cell]["trace"].window
    assert all(lo <= s.start <= s.end <= hi for s in P.spans(ctx[cell], trace_dir=root))


def test_serving_readers(read, recorded):
    ctx = recorded[1]["serve3"]
    v = {m: read(m, ctx) for m in SERVE}
    idle = read("idle_share.serve", ctx)
    assert v["idle_share.serve.admit"] >= 0 and v["idle_share.serve.tick"] > 0
    assert v["idle_share.serve.admit"] + v["idle_share.serve.tick"] <= idle + 1e-9
    assert v["tick_host_ms.serve"] >= HOST_S * 1e3
    assert 0 < v["admit_ms.serve"] < v["tick_host_ms.serve"]


def test_training_readers(read, recorded):
    ctx = recorded[1]["train"]
    per_step = read("host_ms_per_step.train", ctx)
    # assembly and commit sleep 1.5 x HOST_S a tick, one step a tick
    assert per_step >= 1.5 * HOST_S * 1e3
    share = read("idle_share.train.input", ctx)
    assert HOST_S * 2 / ctx["trace"].window_s * 100 * 0.9 <= share
    assert share <= read("idle_share.train", ctx) + 1e-9


@pytest.mark.parametrize("metric", SERVE + TRAIN)
def test_a_trace_without_program_spans_reads_none(read, recorded, metric):
    assert read(metric, recorded[1]["bare"]) is None


def test_a_window_in_no_trace_reads_none(recorded):
    root, ctx = recorded
    other = T.Summary(window=(0, 1), devices=ctx["bare"]["trace"].devices, spans=[])
    assert P.spans({"trace": other}, trace_dir=root) is None


def test_idle_under_spans_on_hand_made_intervals():
    ms = 1_000_000
    dev = T.Device(ops=[("fusion.1", 0, 10 * ms), ("fusion.2", 20 * ms, 5 * ms)])
    ctx = {"trace": T.Summary(window=(0, 50 * ms), devices=[dev], spans=[])}
    # idle: 10-20 and 25-50 ms
    assert P.idle_s(ctx, [(5 * ms, 30 * ms)]) == pytest.approx(0.015)
    inside = [P.Span("serve.tick", 5 * ms, 30 * ms, {})]
    outside = [P.Span("serve.admit", 8 * ms, 22 * ms, {})]
    assert P.idle_share(ctx, inside, outside) == pytest.approx(5 / 50 * 100)
    held = P.within(inside + [P.Span("serve.tick", 40 * ms, 45 * ms, {})],
                    outside + [P.Span("serve.admit", 28 * ms, 41 * ms, {})])
    assert [[s.start for s in h] for h in held] == [[8 * ms], []]
