"""The trace reduction, on a small trace recorded on the CPU and on
hand-made events."""

import time

import jax
import jax.numpy as jnp
import pytest
from jax.profiler import TraceAnnotation

import smoke  # noqa: F401
import traces as T


def cpu_lines(plane, line):
    if plane == "/host:CPU" and line.startswith("tf_XLAPjRtCpuClient"):
        return "ops"
    return None


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    d = str(tmp_path_factory.mktemp("trace"))
    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((256, 256))
    f(x).block_until_ready()
    with T.capture(d):
        with TraceAnnotation(T.WINDOW):
            for _ in range(3):
                with TraceAnnotation("bench.pool_step"):
                    f(x).block_until_ready()
                with TraceAnnotation("bench.wait_for_arrival"):
                    time.sleep(0.02)
    return T.reduce(d, classify=cpu_lines)


def test_recorded_window_and_busy(recorded):
    s = recorded
    assert 0.06 <= s.window_s < 5.0
    assert 0 < s.busy_s < s.window_s
    n, t = s.ops(r"dot")
    assert n >= 3 and 0 < t <= s.busy_s + 1e-9


def test_recorded_gaps_are_labelled_by_host_span(recorded):
    idle = dict(recorded.idle_by_span())
    assert idle.get("bench.wait_for_arrival", 0) >= 0.05
    total_idle = sum(v for _, v in recorded.gaps())
    assert total_idle == pytest.approx(recorded.window_s - recorded.busy_s, rel=1e-6)


def test_tpu_line_names():
    assert T.tpu_lines("/device:TPU:0", "XLA Ops") == "ops"
    assert T.tpu_lines("/device:TPU:3", "XLA Modules") == "modules"
    assert T.tpu_lines("/device:TPU:0 SparseCore", "XLA Ops") is None
    assert T.tpu_lines("/host:CPU", "XLA Ops") is None


def summary():
    ms = 1_000_000
    dev = T.Device(
        ops=[("fusion.1", 0, 10 * ms), ("all-reduce.7", 5 * ms, 10 * ms),
             ("fusion.2", 20 * ms, 5 * ms), ("all-reduce.8", 30 * ms, 4 * ms)],
        modules=[("jit_train_step(1)", 0, 25 * ms), ("jit_train_step(1)", 30 * ms, 4 * ms)])
    spans = [("bench.job_step", 24 * ms, 31 * ms)]
    return T.Summary(window=(0, 50 * ms), devices=[dev, dev], spans=spans)


def test_union_collectives_and_grouping():
    s = summary()
    assert s.busy_s == pytest.approx(0.024)        # 0-15, 20-25, 30-34 ms
    assert s.window_s == pytest.approx(0.05)
    coll, exposed = s.collectives()
    assert coll == pytest.approx(0.014)
    assert exposed == pytest.approx(0.009)         # 10-15 and 30-34 ms
    assert s.modules(r"train_step") == (2, pytest.approx(0.029))
    top = dict(s.top_ops())
    assert top["all-reduce"] == pytest.approx(0.014)
    assert top["fusion"] == pytest.approx(0.015)


def test_hlo_text_names():
    name = ("%fusion.378.remat5 = (f32[2,1024]{1,0:T(2,128)S(1)}, f32[2,1024,122753]"
            "{1,2,0:T(8,128)}) fusion(f32[122753,2304]{1,0:T(8,128)} %x), kind=kOutput")
    assert T.op_kind(name) == "fusion.378.remat5"
    assert T.op_label(name) == "fusion (f32[2,1024], f32[2,1024,122753])"
    assert T.op_label("%all-reduce.7 = f32[4,2304]{1,0} all-reduce(f32[4,2304]{1,0} %g)") == \
        "all-reduce f32[4,2304]"


def test_gap_labels():
    s = summary()
    gaps = s.gaps()
    assert [g[0] for g in gaps] == ["host", "bench.job_step", "host"]
    assert [g[1] for g in gaps] == pytest.approx([0.005, 0.005, 0.016])
    assert s.longest_gaps(1) == [["host", pytest.approx(0.016)]]


def test_merge_and_overlap():
    assert T.merge([(5, 7), (0, 2), (1, 3)]) == [(0, 3), (5, 7)]
    assert T.clip([(0, 3), (5, 7)], 2, 6) == [(2, 3), (5, 6)]
    assert T._overlap([(0, 10)], [(2, 3), (5, 12)]) == 6
    assert T.stable_name("fusion.12.3") == "fusion"
    assert T.stable_name("fusion.378.remat5") == "fusion"
