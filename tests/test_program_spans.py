"""The program's spans (``telemetry.profile.span``) in a profiler trace
recorded on the CPU: the serving tick's and the training step's spans,
each nested in the span that caused it and carrying its metadata, and
the same served tokens and losses with the profiler on and off."""

import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.config import TrainingConfig, get_arch
from repro.data.pipeline import build_token_log
from repro.models.zoo import build_model
from repro.serving import ElasticServingPool, PagedSpec, Request
from repro.training.job import TrainingJob
from repro.training.train_step import make_train_step

# Each span's parent: the innermost program span that holds it.
PARENTS = {
    "serve": {
        "serve.tick": None,
        "serve.dispatch": "serve.tick",
        "serve.admit": "serve.tick",
        "serve.prefill": "serve.admit",
        "serve.merge": "serve.admit",
        "serve.pages": "serve.tick",
        "serve.decode": "serve.tick",
        "serve.token_wait": "serve.tick",
        "serve.finish": "serve.tick",
        "serve.autoscale": "serve.tick",
    },
    "train": {
        "train.tick": None,
        "train.assemble": "train.tick",
        "train.dispatch": "train.tick",
        "train.autoscale": "train.tick",
        "train.step": "train.tick",
        "train.upload": "train.step",
        "train.loss_wait": "train.step",
        "train.commit": "train.step",
    },
}

PROMPTS = [[3, 1, 4, 1, 5], [9, 2, 6], [5, 3, 5, 8, 9], [7, 9, 3]]
MAX_NEW = [6, 4, 5, 3]
TRAIN_STEPS = 2


def traced(log_dir, fn):
    """``fn()`` under the profiler; its result and the program's spans as
    ``(name, start_ns, end_ns, stats)``, in start order."""
    jax.profiler.start_trace(log_dir)
    try:
        out = fn()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True)
    spans = [
        (ev.name, ev.start_ns, ev.start_ns + ev.duration_ns, dict(ev.stats))
        for plane in ProfileData.from_file(path).planes
        if plane.name.startswith("/host")
        for line in plane.lines
        for ev in line.events
        if ev.name.startswith(("serve.", "train."))
    ]
    return out, sorted(spans, key=lambda s: (s[1], -s[2]))


def parent(spans, i):
    """Name of the innermost other span that holds span ``i``."""
    _, s, e, _ = spans[i]
    holders = [(b - a, n) for j, (n, a, b, _) in enumerate(spans)
               if j != i and a <= s and e <= b]
    return min(holders)[1] if holders else None


@pytest.fixture(scope="module")
def served():
    cfg = get_arch("minicpm-2b", smoke=True)
    model = build_model(cfg, compute_dtype=jnp.float32)
    params = model.init(jax.random.PRNGKey(0))

    def serve():
        pool = ElasticServingPool(
            model, params, slots_per_replica=2, max_len=32, max_replicas=1,
            initial_units=2, paged=PagedSpec(num_pages=17, page_size=4),
        )
        reqs = [Request(prompt=p, max_new_tokens=n) for p, n in zip(PROMPTS, MAX_NEW)]
        for r in reqs:
            pool.submit(r, now=0.0)
        pool.run_until_drained(max_steps=200)
        return reqs

    return serve


@pytest.fixture(scope="module")
def trained():
    cfg = get_arch("llama3.2-1b", smoke=True)
    tcfg = TrainingConfig(learning_rate=1e-3, warmup_steps=0, schedule="constant")
    model = build_model(cfg, compute_dtype=jnp.float32)
    step_fn = jax.jit(make_train_step(model, tcfg))

    def train():
        log = build_token_log(cfg.vocab_size, 32, doc_len=17, partitions=2)
        job = TrainingJob(model, cfg, tcfg, log, batch_size=4, seq_len=16,
                          dp=1, max_dp=1, train_step_fn=step_fn)
        job.run(TRAIN_STEPS)
        return job.losses

    return train


@pytest.fixture(scope="module")
def runs(served, trained, tmp_path_factory):
    """Each half run once under the profiler and once without it."""
    out = {}
    for kind, fn in (("serve", served), ("train", trained)):
        on, spans = traced(str(tmp_path_factory.mktemp(kind)), fn)
        out[kind] = {"on": on, "off": fn(), "spans": spans}
    return out


@pytest.mark.parametrize("kind", ["serve", "train"])
def test_every_span_appears_nested_in_its_cause(runs, kind):
    spans = runs[kind]["spans"]
    want = PARENTS[kind]
    assert {n for n, *_ in spans} == set(want)
    for i, (name, *_) in enumerate(spans):
        assert parent(spans, i) == want[name], name


@pytest.mark.parametrize("kind", ["serve", "train"])
def test_results_are_the_same_with_the_profiler_on_and_off(runs, kind):
    on, off = runs[kind]["on"], runs[kind]["off"]
    if kind == "serve":
        on, off = [r.output for r in on], [r.output for r in off]
        assert [len(o) for o in on] == MAX_NEW
    else:
        assert len(on) == TRAIN_STEPS and np.all(np.isfinite(on))
    assert on == off


def test_each_admission_and_finish_carries_its_request(runs):
    spans = runs["serve"]["spans"]
    reqs = {r.req_id: r for r in runs["serve"]["on"]}
    admits = [st for n, _, _, st in spans if n == "serve.admit"]
    assert len(admits) == len(reqs)
    assert sorted(st["req"] for st in admits) == sorted(reqs)
    for st in admits:
        assert st["prompt_len"] == len(reqs[st["req"]].prompt)
        assert 0 <= st["slot"] < 2
    finished = {st["req"]: st["tokens"] for n, _, _, st in spans if n == "serve.finish"}
    assert finished == {k: len(r.output) for k, r in reqs.items()}
    ticks = [st["tick"] for n, _, _, st in spans if n == "serve.tick"]
    assert ticks == list(range(len(ticks)))
    assert all(0 < st["rows"] <= 2 for n, _, _, st in spans if n == "serve.decode")


def test_each_optimizer_step_is_one_span(runs):
    spans = runs["train"]["spans"]
    steps = [st["step"] for n, _, _, st in spans if n == "train.step"]
    assert steps == list(range(1, TRAIN_STEPS + 1))
    for name in ("train.upload", "train.loss_wait", "train.commit"):
        assert sum(n == name for n, *_ in spans) == TRAIN_STEPS
