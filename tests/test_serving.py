"""Serving layer: prefill/decode steps, continuous batcher semantics."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import get_arch
from repro.models.zoo import build_model
from repro.serving.batcher import ContinuousBatcher, Request
from repro.serving.serve_step import make_decode_step, make_prefill_step


@pytest.fixture(scope="module")
def setup():
    cfg = get_arch("llama3.2-1b", smoke=True)
    model = build_model(cfg, compute_dtype=jnp.float32)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


def greedy_reference(model, params, prompt, n_new):
    """Reference decode: rerun the full forward for every new token."""
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


def test_batcher_matches_full_forward_decoding(setup):
    cfg, model, params = setup
    prompts = [[5, 9, 2], [7, 1, 1, 3], [11]]
    n_new = 5
    b = ContinuousBatcher(model, params, slots=2, max_len=32)
    for p in prompts:
        b.submit(Request(prompt=p, max_new_tokens=n_new))
    b.run_until_drained()
    assert len(b.completed) == 3
    by_prompt = {tuple(r.prompt): r.output for r in b.completed}
    for p in prompts:
        ref = greedy_reference(model, params, p, n_new)
        assert by_prompt[tuple(p)] == ref, f"prompt {p}"


def test_batcher_continuous_admission(setup):
    """More requests than slots: queue drains as slots free (continuous
    batching), every request completes exactly once."""
    cfg, model, params = setup
    b = ContinuousBatcher(model, params, slots=2, max_len=32)
    reqs = [Request(prompt=[i + 2, i + 3], max_new_tokens=3) for i in range(7)]
    for r in reqs:
        b.submit(r)
    assert b.queue_depth() == 7
    b.run_until_drained()
    assert len(b.completed) == 7
    assert sorted(r.req_id for r in b.completed) == sorted(r.req_id for r in reqs)
    assert all(len(r.output) == 3 for r in b.completed)
    assert b.occupancy() == 0


def test_batcher_eos_frees_slot_early(setup):
    cfg, model, params = setup
    # discover the first greedy token for a probe prompt, use it as "EOS"
    probe = greedy_reference(model, params, [4, 4], 1)[0]
    b = ContinuousBatcher(model, params, slots=1, max_len=32, eos_token=probe)
    b.submit(Request(prompt=[4, 4], max_new_tokens=10))
    b.run_until_drained()
    (done,) = b.completed
    assert done.output[-1] == probe
    assert len(done.output) < 10  # stopped early on EOS


def test_prefill_step_returns_argmax(setup):
    cfg, model, params = setup
    prefill = make_prefill_step(model)
    toks = jnp.asarray([[3, 5, 7, 9]], dtype=jnp.int32)
    cache = model.init_cache(1, 16)
    nxt, cache2 = prefill(params, {"tokens": toks}, cache)
    logits, _ = model.train_logits(params, {"tokens": toks})
    assert int(nxt[0]) == int(jnp.argmax(logits[0, -1]))
    # cache positions advanced
    flat = jax.tree.leaves(
        jax.tree.map(lambda x: x, cache2)
    )
    assert any((np.asarray(x) == 4).all() for x in flat if np.asarray(x).ndim <= 2)


def test_paged_batcher_matches_dense_on_real_model(setup):
    """Device-side paging on a real transformer: the paged batcher (page
    pool + page tables + the jnp paged decode the CPU serves with)
    produces exactly the tokens the dense full-forward reference does,
    and returns every page."""
    from repro.serving.kv_cache import PagedSpec

    cfg, model, params = setup
    prompts = [[5, 9, 2], [7, 1, 1, 3], [11]]
    n_new = 5
    paged = PagedSpec(num_pages=1 + 2 * 4, page_size=8)  # 2 slots x 32/8
    b = ContinuousBatcher(model, params, slots=2, max_len=32, paged=paged)
    for p in prompts:
        b.submit(Request(prompt=p, max_new_tokens=n_new))
    b.run_until_drained()
    assert len(b.completed) == 3
    by_prompt = {tuple(r.prompt): r.output for r in b.completed}
    for p in prompts:
        assert by_prompt[tuple(p)] == greedy_reference(model, params, p, n_new)
    assert b.page_pool.in_use == 0
    assert b.page_pool.leaked() == 0


def test_paged_batcher_through_pallas_kernels_matches_dense(setup,
                                                           monkeypatch):
    """The TPU branch of the paged decode (Pallas kv-append + paged
    flash-decoding inside the jitted step), run in Pallas's interpreter:
    the same tokens as the dense full-forward reference."""
    from repro.kernels import platform
    from repro.kernels.decode_attention import ops
    from repro.serving.kv_cache import PagedSpec

    monkeypatch.setattr(platform, "compiled_kernels", lambda: True)
    monkeypatch.setattr(ops, "resolve_interpret", lambda interpret: True)
    cfg, model, params = setup
    prompts = [[5, 9, 2], [7, 1, 1, 3]]
    n_new = 4
    paged = PagedSpec(num_pages=1 + 2 * 4, page_size=8)
    b = ContinuousBatcher(model, params, slots=2, max_len=32, paged=paged)
    jaxpr = jax.make_jaxpr(b.decode_step)(
        params, jnp.zeros((2, 1), jnp.int32), b.cache,
        jnp.zeros((2,), jnp.int32), b.rng,
    )
    assert "pallas_call" in str(jaxpr)
    for p in prompts:
        b.submit(Request(prompt=p, max_new_tokens=n_new))
    b.run_until_drained()
    by_prompt = {tuple(r.prompt): r.output for r in b.completed}
    for p in prompts:
        assert by_prompt[tuple(p)] == greedy_reference(model, params, p, n_new)
    assert b.page_pool.leaked() == 0


def test_paged_release_resets_device_cache_pos(setup):
    """Regression: a freed slot's device-cache ``pos`` used to keep the
    finished request's length and then grow every tick the slot idled,
    eventually walking the kv-append page-table lookup off the slot's
    row.  Releasing a slot must zero its pos across every layer cache."""
    from jax.tree_util import DictKey, tree_flatten_with_path

    from repro.serving.kv_cache import PagedSpec

    cfg, model, params = setup
    paged = PagedSpec(num_pages=1 + 4, page_size=8)
    b = ContinuousBatcher(model, params, slots=1, max_len=32, paged=paged)
    b.submit(Request(prompt=[5, 9, 2], max_new_tokens=4))
    b.run_until_drained()
    assert len(b.completed) == 1
    pos_leaves = [
        leaf for path, leaf in tree_flatten_with_path(b.cache)[0]
        if any(isinstance(p, DictKey) and p.key == "pos" for p in path)
    ]
    assert pos_leaves, "paged transformer cache must carry pos leaves"
    for leaf in pos_leaves:
        assert int(jnp.max(jnp.abs(leaf))) == 0


def _spy_on_donation(batcher):
    """Wrap ``batcher``'s decode step to keep the page-pool leaves of
    every cache it was handed."""
    from jax.tree_util import tree_flatten_with_path

    step, handed = batcher.decode_step, []

    def spy(params, tokens, cache, positions, rng):
        out = step(params, tokens, cache, positions, rng)
        handed.extend(leaf for path, leaf in tree_flatten_with_path(cache)[0]
                      if str(path[-1].key).endswith("_pages"))
        return out

    batcher.decode_step = spy
    return handed


@pytest.mark.parametrize("kernels", ["jnp", "pallas"])
def test_donated_decode_survives_preemption_release_and_merge(
        setup, monkeypatch, kernels):
    """The decode step donates the cache, so the pools it was handed are
    gone after it and the batcher goes on from the step's output.  Two
    slots, four requests and five usable pages of four tokens: between
    donated steps a running slot is preempted, finished slots release
    their pages and reset their positions, and later requests are
    admitted through the eager page merge; every request still gets the
    dense reference's tokens, on the jnp path and through the Pallas
    kernels (interpreted)."""
    from repro.kernels import platform
    from repro.kernels.decode_attention import ops
    from repro.serving.kv_cache import PagedSpec

    if kernels == "pallas":
        monkeypatch.setattr(platform, "compiled_kernels", lambda: True)
        monkeypatch.setattr(ops, "resolve_interpret", lambda interpret: True)
    cfg, model, params = setup
    prompts = [[5, 9, 2], [7, 1, 3], [11, 4, 6], [3, 8, 2]]
    n_new = 8
    b = ContinuousBatcher(model, params, slots=2, max_len=32,
                          paged=PagedSpec(num_pages=1 + 5, page_size=4))
    handed = _spy_on_donation(b)
    for p in prompts:
        b.submit(Request(prompt=p, max_new_tokens=n_new))
    b.run_until_drained()
    assert b.preemptions > 0, "the pool was never tight"
    assert len(b.completed) == len(prompts)
    assert handed and all(leaf.is_deleted() for leaf in handed)
    by_prompt = {tuple(r.prompt): r.output for r in b.completed}
    for p in prompts:
        assert by_prompt[tuple(p)] == greedy_reference(model, params, p, n_new)
    assert b.page_pool.in_use == 0 and b.page_pool.leaked() == 0


def test_replicas_sharing_one_donated_decode_step_serve_their_own(setup):
    """Two replicas of one ``ElasticServingPool`` share its compiled,
    donating decode step, each over its own paged cache: each serves
    requests, and every request gets the dense reference's tokens."""
    from repro.core.elastic import AutoscalerConfig
    from repro.serving import ElasticServingPool
    from repro.serving.kv_cache import PagedSpec

    cfg, model, params = setup
    pool = ElasticServingPool(
        model, params, slots_per_replica=2, max_len=32, max_replicas=2,
        initial_units=4, paged=PagedSpec(num_pages=1 + 8, page_size=8),
        autoscaler=AutoscalerConfig(min_workers=4, max_workers=4,
                                    cooldown=0.0),
    )
    prompts = [[i % 7 + 2, i % 5 + 1, 3] for i in range(6)]
    n_new = 5
    now = 0.0
    for p in prompts:
        pool.submit(Request(prompt=p, max_new_tokens=n_new), now=now)
    while pool.queue_depth() or pool.occupancy():
        pool.step(now)
        now += 1.0
    replicas = pool.replicas
    assert len(replicas) == 2
    assert replicas[0].decode_step is replicas[1].decode_step
    assert all(r.steps > 0 for r in replicas), [r.steps for r in replicas]
    assert len(pool.completed) == len(prompts)
    for r in pool.completed:
        assert r.output == greedy_reference(model, params, r.prompt, n_new)


def test_donated_decode_serves_unscanned_layers(monkeypatch):
    """Layers outside the scan (here two remainder layers beside one
    scanned period of three) keep a pool of their own each, which the
    kernels take as the stacked pool's free view at layer 0.  Their page
    tables must not share a buffer, since the donating decode step
    cannot take one buffer twice; the batcher still gives the dense
    reference's tokens through the Pallas kernels (interpreted)."""
    import dataclasses

    from repro.config.base import LayerSpec
    from repro.kernels import platform
    from repro.kernels.decode_attention import ops
    from repro.serving.kv_cache import PagedSpec

    monkeypatch.setattr(platform, "compiled_kernels", lambda: True)
    monkeypatch.setattr(ops, "resolve_interpret", lambda interpret: True)
    cfg = dataclasses.replace(get_arch("llama3.2-1b", smoke=True),
                              num_layers=5, pattern=(LayerSpec(),) * 3)
    model = build_model(cfg, compute_dtype=jnp.float32)
    params = model.init(jax.random.PRNGKey(1))
    assert len(model.init_cache(1, 32)["remainder"]) == 2
    prompts = [[5, 9, 2], [7, 1, 3]]
    n_new = 4
    b = ContinuousBatcher(model, params, slots=2, max_len=32,
                          paged=PagedSpec(num_pages=1 + 8, page_size=8))
    for p in prompts:
        b.submit(Request(prompt=p, max_new_tokens=n_new))
    b.run_until_drained()
    by_prompt = {tuple(r.prompt): r.output for r in b.completed}
    for p in prompts:
        assert by_prompt[tuple(p)] == greedy_reference(model, params, p, n_new)
