"""DeepSeek-V2-Lite's mechanisms at the program's smoke preset on the
CPU, on seeded random weights, against the plain float32 reference
(``bench/archs/deepseek-v2-lite.py``) and the kernels' jnp references.
Logits are compared, not tokens.

Tolerances.  Program and reference both compute in float32 here, so
they differ by summation order alone: the program's blockwise online
softmax, its absorbed decode (q W_UK^T against the latent, W_UV after)
and its pool round trip against the reference's up-projected full
attention.  That moves a logit by some 1e-6 of the logits' standard
deviation; the limits are 2e-4 of it (logits) and 1e-5 relative
(kernels, single layers), where bfloat16 arithmetic would be off by
some 1e-2.
"""

import copy
import dataclasses
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1] / "bench"
for p in (BENCH, BENCH / "drivers"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

import common  # noqa: E402
import weights as W  # noqa: E402

from repro.config import get_arch  # noqa: E402
from repro.kernels.decode_attention import (  # noqa: E402
    paged_latent_append,
    paged_latent_append_ref,
    paged_latent_decode,
    paged_latent_decode_ref,
)
from repro.kernels.decode_attention.kernel import paged_latent_decode_fwd  # noqa: E402
from repro.models import layers as L  # noqa: E402
from repro.models import moe as MOE  # noqa: E402
from repro.models.zoo import build_model  # noqa: E402

SEED = 2**31 + 17

# The serving configuration cut to the program's smoke preset: one dense
# layer then two MoE layers holding experts 2-5 of 8, top-3, two shared.
SMOKE = {
    "smoke": True, "hidden_size": 64, "intermediate_size": 128,
    "moe_intermediate_size": 32, "num_attention_heads": 4,
    "num_key_value_heads": 4, "num_hidden_layers": 3, "kv_lora_rank": 32,
    "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
    "n_routed_experts": 4, "num_experts_per_tok": 3, "vocab_size": 512,
    "published": {"n_routed_experts": 8},
}


def smoke_config() -> dict:
    conf = copy.deepcopy(common.load_json(
        common.BENCH / "configs" / "deepseek-v2-lite-serve.json"))
    conf.update(copy.deepcopy(SMOKE))
    conf["rope_scaling"].update(factor=4, original_max_position_embeddings=32)
    conf["deployment"]["first_held_expert"] = 2
    return conf


@pytest.fixture(scope="module")
def setup():
    conf = smoke_config()
    A = common.arch(conf)
    s = A.sizes(conf)
    cfg = A.program_arch(conf)
    assert cfg == get_arch("deepseek-v2-lite", smoke=True)
    model = build_model(cfg, compute_dtype=jnp.float32, param_dtype=jnp.float32)
    params = W.program_params(A, SEED, s, jnp.float32)
    want = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    assert jax.tree.structure(want) == jax.tree.structure(params)
    return A, s, cfg, model, params


def _with_key(tree, key, fn):
    """``tree`` with every leaf named ``key`` replaced by ``fn(leaf)``."""
    return jax.tree_util.tree_map_with_path(
        lambda path, x: fn(x) if path[-1].key == key else x, tree)


@pytest.mark.parametrize("decode", ["jnp", "kernel"])
def test_prefill_then_latent_pool_decode_matches_reference(setup, decode,
                                                           monkeypatch):
    """(a) Two prompts of different lengths are prefilled one at a time
    into one latent page pool, as the batcher admits them, then decoded
    together for 12 steps through it (teacher-forced): every logit
    against the reference's full forward pass over prompt and tokens."""
    A, s, cfg, model, params = setup
    if decode == "kernel":
        from repro.kernels import platform
        from repro.kernels.decode_attention import ops

        monkeypatch.setattr(platform, "compiled_kernels", lambda: True)
        monkeypatch.setattr(ops, "resolve_interpret", lambda interpret: True)
    page, max_len, n_new = 8, 64, 12
    rng = np.random.default_rng(0)
    lens = [21, 34]
    seqs = [rng.integers(3, s["vocab"], n + n_new).astype(np.int32) for n in lens]
    tables = np.zeros((2, max_len // page), np.int32)
    tables[0, :5] = [3, 9, 1, 4, 12]
    tables[1, :7] = [2, 11, 5, 6, 7, 8, 10]
    spec = L.PagedSpec(num_pages=13, page_size=page)

    prefill = jax.jit(model.prefill, static_argnames=("last_only",))
    decode_step = jax.jit(model.decode_step)
    pool = None
    firsts = []
    for row, n in enumerate(lens):
        cache = model.init_cache(1, max_len, paged=spec)
        cache = _with_key(cache, "page_table", lambda t, r=row: jnp.broadcast_to(
            jnp.asarray(tables[r:r + 1]), t.shape))
        if pool is not None:
            cache = _with_key(cache, "latent_pages", lambda _, p=pool: p.pop(0))
        logits, cache = prefill(params, {"tokens": jnp.asarray(seqs[row][None, :n])},
                                      cache, last_only=True)
        firsts.append(logits[0, -1])
        pool = [x for p, x in jax.tree_util.tree_flatten_with_path(cache)[0]
                if p[-1].key == "latent_pages"]

    cache = model.init_cache(2, max_len, paged=spec)
    cache = _with_key(cache, "page_table",
                      lambda t: jnp.broadcast_to(jnp.asarray(tables), t.shape))
    cache = _with_key(cache, "latent_pages", lambda _: pool.pop(0))
    cache = _with_key(cache, "pos", lambda p: jnp.broadcast_to(
        jnp.asarray(lens, jnp.int32), p.shape))
    got = [[f] for f in firsts]
    for t in range(n_new - 1):
        tok = jnp.asarray([[q[n + t]] for q, n in zip(seqs, lens)], jnp.int32)
        logits, cache = decode_step(params, tok, cache,
                                          jnp.asarray(lens, jnp.int32) + t)
        for row in range(2):
            got[row].append(logits[row, 0])

    both = np.zeros((2, max(lens) + n_new), np.int32)
    for row, q in enumerate(seqs):
        both[row, :len(q)] = q
    h, head = A.hidden(SEED, s, both, jnp.float32)
    for row, n in enumerate(lens):
        want = np.asarray(jnp.einsum("td,vd->tv", h[row, n - 1:n + n_new - 1], head,
                                     precision="highest"))
        diff = np.abs(np.asarray(jnp.stack(got[row])) - want).max()
        assert diff <= 2e-4 * want.std(), (row, diff, want.std())


def test_absorbed_decode_matches_upprojected_per_layer(setup):
    """(b) For each layer's weights, one query per row scored in the
    absorbed form against the latent rows agrees with the keys and
    values up-projected per head, over ragged live lengths (1 among
    them)."""
    _, s, cfg, _, params = setup
    rng = np.random.default_rng(1)
    m = cfg.mla
    b, S, h = 3, 40, cfg.num_heads
    rows = jnp.asarray(rng.standard_normal((b, S, L.latent_width(cfg))), jnp.float32)
    q_nope = jnp.asarray(rng.standard_normal((b, 1, h, m.qk_nope_head_dim)), jnp.float32)
    q_rope = jnp.asarray(rng.standard_normal((b, 1, h, m.qk_rope_head_dim)), jnp.float32)
    kv_len = jnp.asarray([1, 17, 40], jnp.int32)
    layers = [params["lead"][0]["attn"]] + [
        jax.tree.map(lambda x, i=i: x[i], params["periods"][0]["attn"])
        for i in range(cfg.num_layers - 1)]
    for p in layers:
        a = L.absorbed_attend(p, q_nope, q_rope, rows, kv_len, cfg)
        u = L.upprojected_attend(p, q_nope, q_rope, rows, (kv_len - 1)[:, None],
                                 kv_len, cfg, block=16)
        np.testing.assert_allclose(np.asarray(a), np.asarray(u), rtol=1e-5,
                                   atol=1e-5 * float(jnp.abs(u).max()))


@pytest.mark.parametrize("layer", [pytest.param(0, id="first"),
                                   pytest.param(1, id="middle"),
                                   pytest.param(2, id="last")])
@pytest.mark.parametrize("pages_per_block", [None, 2])
def test_paged_latent_decode_and_append_match_reference(pages_per_block, layer):
    """(c) The kernel and its append in interpret mode on one layer of a
    stacked pool of three against the jnp references on that layer's
    pool: a ragged page table, rows of 1, 20 and 48 live tokens, a grid
    of one block and of several; the append leaves the other layers
    bitwise as they were."""
    rng = np.random.default_rng(2)
    n_layers, n_pool, page, w, h, v = 3, 20, 8, 128, 4, 32
    stack = jnp.asarray(rng.standard_normal((n_layers, n_pool, page, w)),
                        jnp.float32)
    pages = stack[layer]
    table = jnp.asarray([[3, 7, 1, 0, 0, 0],
                         [2, 0, 0, 0, 0, 0],
                         [5, 6, 8, 9, 10, 11]], jnp.int32)
    kv_len = jnp.asarray([20, 1, 48], jnp.int32)
    q = jnp.asarray(rng.standard_normal((3, h, w)), jnp.float32)
    want = paged_latent_decode_ref(q, pages, table, kv_len, v, 0.3)
    if pages_per_block is None:
        got = paged_latent_decode(q, stack, table, kv_len, layer, value_dim=v,
                                  sm_scale=0.3, interpret=True)
    else:
        got = paged_latent_decode_fwd(q, stack, table, kv_len, layer, v, 0.3,
                                      pages_per_block=pages_per_block,
                                      interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)

    row = jnp.asarray(rng.standard_normal((3, w)), jnp.float32)
    pos = jnp.asarray([20, 1, 47], jnp.int32)
    want = np.asarray(paged_latent_append_ref(row, pages, table, pos))
    before = np.asarray(stack)
    got = np.asarray(paged_latent_append(row, stack, table, pos, layer,
                                         interpret=True))
    np.testing.assert_array_equal(got[layer], want)
    others = [i for i in range(n_layers) if i != layer]
    np.testing.assert_array_equal(got[others], before[others])


def _share(params, first, held):
    p = dict(params)
    for k in ("w_gate", "w_up", "w_down"):
        p[k] = params[k][first:first + held]
    return p


@pytest.mark.parametrize("held", [1, 2, 4])
def test_expert_shares_add_up_to_the_uncut_layer(held):
    """(d) Over every share of the experts (8 / held chips), the held
    experts' parts, with the shared experts counted once, add up to the
    uncut layer, and every (token, expert) assignment lands on exactly
    one share."""
    cfg = get_arch("deepseek-v2-lite", smoke=True)
    e = cfg.moe.num_experts
    full = dataclasses.replace(cfg.moe, held_experts=e, first_held=0)
    params = MOE.init_moe(jax.random.PRNGKey(3),
                          dataclasses.replace(cfg, moe=full), jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 9, cfg.d_model), jnp.float32)
    want, _, rows_all = MOE.moe_share(params, x, full)
    shared = L.mlp(params["shared"], x)
    total, rows = shared, 0
    for first in range(0, e, held):
        moe = dataclasses.replace(full, held_experts=held, first_held=first)
        y, _, r = MOE.moe_share(_share(params, first, held), x, moe)
        total, rows = total + (y - shared), rows + r
    np.testing.assert_allclose(np.asarray(total), np.asarray(want), rtol=1e-5,
                               atol=1e-5 * float(jnp.abs(want).max()))
    assert (np.asarray(rows) == np.asarray(rows_all)).all()
    assert int(rows_all.sum()) == x.shape[0] * x.shape[1] * cfg.moe.top_k


def test_mixtral_moe_path_unchanged():
    """(e) Mixtral's preset keeps its own path: all experts held, no
    shared experts, top-k weights renormalised, capacity dispatch (its
    smoke preset is dropless); the layer equals that rule written out."""
    cfg = get_arch("mixtral-8x7b", smoke=True)
    assert cfg.moe.held_experts == 0 and cfg.moe.shared_experts == 0
    assert cfg.moe.renormalize
    params = MOE.init_moe(jax.random.PRNGKey(5), cfg, jnp.float32)
    assert "shared" not in params
    assert params["w_gate"].shape == (cfg.moe.num_experts, cfg.d_model, cfg.d_ff)
    x = jax.random.normal(jax.random.PRNGKey(6), (2, 7, cfg.d_model), jnp.float32)
    y, _ = MOE.moe_apply(params, x, cfg.moe)
    with jax.default_matmul_precision("highest"):
        xf = x.reshape(-1, cfg.d_model)
        probs = jax.nn.softmax(xf @ params["router"], axis=-1)
        top, idx = jax.lax.top_k(probs, cfg.moe.top_k)
        top = top / top.sum(-1, keepdims=True)
        want = jnp.zeros_like(xf)
        for k in range(cfg.moe.top_k):
            wg, wu, wd = (params[n][idx[:, k]] for n in ("w_gate", "w_up", "w_down"))
            h = jax.nn.silu(jnp.einsum("nd,ndf->nf", xf, wg)) * jnp.einsum(
                "nd,ndf->nf", xf, wu)
            want = want + top[:, k:k + 1] * jnp.einsum("nf,nfd->nd", h, wd)
    np.testing.assert_allclose(np.asarray(y).reshape(-1, cfg.d_model), np.asarray(want),
                               rtol=1e-4, atol=1e-4 * float(jnp.abs(want).max()))
