"""The harness on the CPU at smoke size: seeded traffic, tails over all
requests, cells added by data alone, per-layer readers, whole runs."""

import json
import shutil

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import TraceAnnotation

import smoke
import common
import program_spans as P
import run
import traffic
import traces as T
from serve import ServeCell, Track, end_to_end
from test_span_readers import cpu_lines, serve_ticks, train_ticks

SEED = 2**31 + 11


def mix(name):
    return common.cell(name)["traffic"]


def test_same_seed_same_requests_and_other_seed_same_work():
    m = mix("minicpm-2b-serve.chat")
    a = traffic.requests(m, SEED, 30, 122753)
    b = traffic.requests(m, SEED, 30, 122753)
    c = traffic.requests(m, SEED + 1, 30, 122753)
    assert [(r.due, r.max_new, r.prompt.tolist()) for r in a] == \
           [(r.due, r.max_new, r.prompt.tolist()) for r in b]
    assert len(a) == len(c) == round(m["rate_per_s"] * 30)
    assert sorted(len(r.prompt) for r in a) == sorted(len(r.prompt) for r in c)
    assert sorted(r.max_new for r in a) == sorted(r.max_new for r in c)
    gaps = lambda rs: sorted(np.round(np.diff([0.0] + [r.due for r in rs]), 9))  # noqa: E731
    assert gaps(a) == gaps(c)
    assert [r.due for r in a] != [r.due for r in c]
    assert {len(r.prompt) for r in a} <= set(m["prompt_lengths"])
    assert all(r.due < 30 for r in a)
    assert np.median([len(r.prompt) for r in a]) == pytest.approx(m["prompt_median"], rel=0.35)


def test_same_seed_same_token_log():
    m = mix("minicpm-2b-train.dp1")
    a = traffic.token_rows(m, SEED, 6, 1024, 122753)
    b = traffic.token_rows(m, SEED, 6, 1024, 122753)
    c = traffic.token_rows(m, SEED + 1, 6, 1024, 122753)
    assert a.shape == (6, 1025) and a.dtype == np.int32
    assert (a == b).all() and not (a == c).all()
    assert a.min() >= 2 and a.max() < 122753
    assert (a == traffic.EOS).sum() >= 2      # documents are packed


def test_tails_cover_every_request_with_unfinished_as_missing():
    tracks = {
        0: Track(due=0.0, plen=8, max_new=3, prompt=np.zeros(8), admitted=0.1,
                 times=[0.2, 0.2, 0.3], output=[1, 2, 3]),
        1: Track(due=1.0, plen=8, max_new=3, prompt=np.zeros(8), admitted=1.5,
                 times=[2.0]),
        2: Track(due=2.5, plen=8, max_new=3, prompt=np.zeros(8)),
    }
    w = ServeCell._summarise(None, tracks, [0.0], 4.0, 2, 3, 30, 24)
    assert w["ttft_s"] == pytest.approx([0.2, 1.0, 1.5])
    assert w["itl_s"] == pytest.approx([0.0, 0.1])
    assert w["tokens"] == 4 and w["attempted"] == 3 and w["finished"] == 1
    e = end_to_end(w)
    assert e["ttft_p95_ms"] == pytest.approx(1450.0)
    assert e["output_tokens_per_s"] == pytest.approx(1.0)


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    """A trace recorded on the CPU whose window holds serving ticks and
    training steps under the program's span names."""
    d = str(tmp_path_factory.mktemp("trace"))
    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((256, 256))
    f(x).block_until_ready()
    with T.capture(d):
        with TraceAnnotation(T.WINDOW):
            serve_ticks(f, x, 3)
            train_ticks(f, x, 2)
    return d, T.reduce(d, classify=cpu_lines)


@pytest.fixture
def fake_ctx(recorded, monkeypatch):
    """The recorded window and its program spans, with the device's
    kernels and programs made by hand at its start (the CPU runs none of
    the chip's), and counters as a window would give them."""
    trace_dir, tr = recorded
    monkeypatch.setattr(P, "TRACES", trace_dir)
    ms, lo = 1_000_000, tr.window[0]
    at = lambda evs: [(n, lo + s, d) for n, s, d in evs]  # noqa: E731
    dev = T.Device(ops=at([("_paged_decode_kernel.3", 0, 2 * ms), ("fusion.1", 2 * ms, 3 * ms),
                           ("all-reduce.1", 6 * ms, 1 * ms)]),
                   modules=at([("jit_decode_step(9)", 0, 5 * ms),
                               ("jit_prefill_step(2)", 5 * ms, 1 * ms),
                               ("jit_train_step(3)", 6 * ms, 2 * ms)]))
    s = T.Summary(window=tr.window, devices=[dev], spans=tr.spans)
    return {"trace": s, "peaks": common.peaks("TPU v5 lite"), "chips": 1, "compile_s": 3.0,
            "memory_peak_bytes": 13e9, "window_s": tr.window_s,
            "end_to_end": {"ttft_p95_ms": 690.0},
            "counters": {"queue_wait_s": [0.1, 0.2, 0.3], "decode_steps": 2, "decode_rows": 10,
                         "prefill_tokens": 500, "decode_flops": 1e9, "attn_flops": 1e6,
                         "attn_bytes": 1e6, "steps": 1, "tokens": 2048, "flops_per_token": 1e7}}


def test_every_per_layer_metric_has_a_reader_that_reads(fake_ctx):
    man = common.manifest()
    for m in man["per_layer"]:
        v = common.reader(m["name"])(fake_ctx)
        assert isinstance(v, float) and v > 0, m["name"]


def test_readers_find_nothing_in_an_empty_window(fake_ctx):
    ctx = fake_ctx
    ctx["trace"] = T.Summary(window=(0, 1), devices=[T.Device(ops=[("x", 0, 1)])], spans=[])
    ctx["counters"], ctx["end_to_end"] = {}, {}
    for name in ("decode_step_ms", "prefill_ms_per_ktok", "paged_decode_attention_roofline",
                 "mfu.decode_step", "train_step_ms", "queue_wait_p50_ms.serve",
                 "slots_occupied_mean.serve", "ttft_p95_ms.saturated"):
        assert common.reader(name)(ctx) is None, name


def test_no_chip_exits_2_without_a_result(capsys):
    rc = run.main(["--workload", "minicpm-2b-serve.chat", "--seed", "1", "--seconds", "1"])
    out = capsys.readouterr()
    assert rc == 2 and out.out == "" and "needs a TPU" in out.err


@pytest.mark.parametrize("name", ["minicpm-2b-serve.chat", "minicpm-2b-train.dp1"])
def test_whole_run_is_correct_at_smoke_size(name):
    c = smoke.cell(name)
    r = run.run(c, SEED, 2.0, False, smoke.cpu_device(c))
    assert r["correct"], r["checks"]
    assert set(r["metrics"]) == {m["name"] for m in c["end_to_end"]}
    assert list(r)[-1] == "checks"
    assert r["attempted"] > 0 and r["failed"] == 0


def test_a_traffic_file_and_manifest_entry_make_a_cell(tmp_path, monkeypatch):
    bench = tmp_path / "bench"
    shutil.copytree(common.BENCH, bench, ignore=shutil.ignore_patterns("__pycache__", "tests"))
    man = common.manifest()
    man["workloads"].append({"name": "minicpm-2b-serve.tiny", "config": "minicpm-2b-serve",
                             "traffic": "tiny", "chips": 1, "why": "test"})
    for m in man["end_to_end"] + man["per_layer"]:
        if "minicpm-2b-serve.chat" in m.get("workloads", []):
            m["workloads"].append("minicpm-2b-serve.tiny")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(man))
    tiny = dict(mix("minicpm-2b-serve.chat"), rate_per_s=5.0)
    (bench / "traffic" / "tiny.json").write_text(json.dumps(tiny))
    shutil.copy(bench / "limits" / "minicpm-2b-serve.chat.json",
                bench / "limits" / "minicpm-2b-serve.tiny.json")
    monkeypatch.setattr(common, "ROOT", tmp_path)
    monkeypatch.setattr(common, "BENCH", bench)
    c = smoke.cell("minicpm-2b-serve.tiny")
    assert c["traffic"]["rate_per_s"] == 40.0   # smoke size sets its own rate
    r = run.run(c, SEED, 1.5, False, smoke.cpu_device(c))
    assert r["correct"] and r["attempted"] == 60
