"""Per-kernel validation: interpret-mode Pallas vs pure-jnp oracles,
swept over shapes and dtypes, plus hypothesis property tests on the
kernels' invariants."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_support import given, settings, st

from repro.kernels.decode_attention.ops import (
    decode_attention,
    paged_decode_attention,
    paged_kv_append,
)
from repro.kernels.decode_attention.ref import (
    decode_attention_ref,
    gather_pages,
    paged_decode_attention_ref,
    paged_kv_append_ref,
)
from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.moe_gating.ops import moe_gating
from repro.kernels.moe_gating.ref import moe_gating_ref
from repro.kernels.ssd_scan.ops import ssd_chunked
from repro.kernels.ssd_scan.ref import ssd_chunked_ref, ssd_sequential_ref
from repro.kernels.tcmm_assign.ops import tcmm_assign
from repro.kernels.tcmm_assign.ref import tcmm_assign_ref

K = jax.random.PRNGKey

TOLS = {jnp.float32: dict(rtol=1e-5, atol=1e-5), jnp.bfloat16: dict(rtol=2e-2, atol=2e-2)}


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "b,t,h,hkv,d,causal,window",
    [
        (1, 128, 4, 4, 64, True, 0),     # MHA causal
        (2, 256, 8, 2, 64, True, 0),     # GQA
        (1, 256, 4, 1, 128, True, 64),   # sliding window, MQA
        (2, 128, 4, 2, 32, False, 0),    # bidirectional (encoder)
        (1, 512, 2, 2, 64, True, 128),   # longer seq + window
    ],
)
def test_flash_attention_matches_ref(b, t, h, hkv, d, causal, window, dtype):
    ks = jax.random.split(K(0), 3)
    q = jax.random.normal(ks[0], (b, t, h, d), dtype=dtype)
    k = jax.random.normal(ks[1], (b, t, hkv, d), dtype=dtype)
    v = jax.random.normal(ks[2], (b, t, hkv, d), dtype=dtype)
    out = flash_attention(
        q, k, v, causal=causal, window=window, block_q=64, block_k=64,
        interpret=True,
    )
    ref = attention_ref(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(
        np.asarray(out, dtype=np.float32), np.asarray(ref, dtype=np.float32),
        **TOLS[dtype],
    )


def test_flash_attention_q_offset_decode_chunk():
    """Chunked prefill: q block at offset into a longer KV context."""
    ks = jax.random.split(K(1), 3)
    b, t, s, h, d = 1, 64, 256, 2, 64
    q = jax.random.normal(ks[0], (b, t, h, d))
    k = jax.random.normal(ks[1], (b, s, h, d))
    v = jax.random.normal(ks[2], (b, s, h, d))
    out = flash_attention(
        q, k, v, causal=True, q_offset=192, block_q=64, block_k=64,
        interpret=True,
    )
    ref = attention_ref(q, k, v, causal=True, q_offset=192)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-5, atol=1e-5)


@settings(max_examples=10, deadline=None)
@given(
    t=st.sampled_from([128, 256]),
    h=st.sampled_from([2, 4]),
    seed=st.integers(0, 2**31 - 1),
)
def test_flash_attention_rows_sum_to_one_property(t, h, seed):
    """Softmax property: with v = identity-ish all-ones, output rows == 1."""
    ks = jax.random.split(K(seed), 2)
    q = jax.random.normal(ks[0], (1, t, h, 64))
    k = jax.random.normal(ks[1], (1, t, h, 64))
    v = jnp.ones((1, t, h, 64))
    out = flash_attention(q, k, v, causal=True, block_q=64, block_k=64,
                          interpret=True)
    np.testing.assert_allclose(np.asarray(out), 1.0, rtol=1e-4, atol=1e-4)


def test_flash_attention_rows_sum_to_one_smoke():
    """Single-seed version of the softmax property; runs without hypothesis."""
    ks = jax.random.split(K(11), 2)
    t, h = 128, 2
    q = jax.random.normal(ks[0], (1, t, h, 64))
    k = jax.random.normal(ks[1], (1, t, h, 64))
    v = jnp.ones((1, t, h, 64))
    out = flash_attention(q, k, v, causal=True, block_q=64, block_k=64,
                          interpret=True)
    np.testing.assert_allclose(np.asarray(out), 1.0, rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# decode attention
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "b,s,h,hkv,d,window",
    [
        (2, 256, 8, 2, 64, 0),
        (1, 512, 4, 1, 128, 0),
        (4, 256, 8, 8, 64, 0),
        (2, 512, 8, 2, 64, 128),  # sliding-window decode
    ],
)
def test_decode_attention_matches_ref(b, s, h, hkv, d, window, dtype):
    ks = jax.random.split(K(2), 4)
    q = jax.random.normal(ks[0], (b, h, d), dtype=dtype)
    kc = jax.random.normal(ks[1], (b, s, hkv, d), dtype=dtype)
    vc = jax.random.normal(ks[2], (b, s, hkv, d), dtype=dtype)
    kv_len = jax.random.randint(ks[3], (b,), 1, s + 1)
    out = decode_attention(q, kc, vc, kv_len, window=window, block_k=128,
                           interpret=True)
    ref = decode_attention_ref(q, kc, vc, kv_len, window=window)
    np.testing.assert_allclose(
        np.asarray(out, dtype=np.float32), np.asarray(ref, dtype=np.float32),
        **TOLS[dtype],
    )


def test_decode_attention_matches_flash_with_full_prefix():
    """decode(q over full cache) == last row of flash over the sequence."""
    ks = jax.random.split(K(3), 3)
    b, s, h, d = 2, 256, 4, 64
    q_full = jax.random.normal(ks[0], (b, s, h, d))
    k = jax.random.normal(ks[1], (b, s, h, d))
    v = jax.random.normal(ks[2], (b, s, h, d))
    flash = flash_attention(q_full, k, v, causal=True, block_q=64,
                            block_k=64, interpret=True)
    dec = decode_attention(
        q_full[:, -1], k, v, jnp.full((b,), s, dtype=jnp.int32),
        block_k=128, interpret=True,
    )
    np.testing.assert_allclose(
        np.asarray(dec), np.asarray(flash[:, -1]), rtol=1e-5, atol=1e-5
    )


def test_decode_attention_kv_len_zero_emits_zero():
    """A fresh slot (kv_len == 0) attends to nothing: the defined output
    is exactly zero — on the kernel AND the reference (a bare softmax
    over an all-masked row would emit a uniform garbage mixture)."""
    ks = jax.random.split(K(20), 3)
    b, s, h, d = 3, 256, 4, 64
    q = jax.random.normal(ks[0], (b, h, d))
    kc = jax.random.normal(ks[1], (b, s, h, d))
    vc = jax.random.normal(ks[2], (b, s, h, d))
    kv_len = jnp.asarray([0, 17, 0], dtype=jnp.int32)
    out = np.asarray(decode_attention(q, kc, vc, kv_len, block_k=128,
                                      interpret=True))
    ref = np.asarray(decode_attention_ref(q, kc, vc, kv_len))
    np.testing.assert_array_equal(out[0], 0.0)
    np.testing.assert_array_equal(out[2], 0.0)
    np.testing.assert_array_equal(ref[0], 0.0)
    np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(out[1], ref[1], rtol=1e-5, atol=1e-5)
    assert np.abs(out[1]).max() > 0  # the live row is untouched by the fix


def test_decode_attention_kv_len_full_cache():
    """kv_len == S on every row (a slot that spent its whole budget):
    no off-by-one at the cache's end."""
    ks = jax.random.split(K(21), 3)
    b, s, h, d = 2, 256, 4, 64
    q = jax.random.normal(ks[0], (b, h, d))
    kc = jax.random.normal(ks[1], (b, s, h, d))
    vc = jax.random.normal(ks[2], (b, s, h, d))
    kv_len = jnp.full((b,), s, dtype=jnp.int32)
    out = decode_attention(q, kc, vc, kv_len, block_k=128, interpret=True)
    ref = decode_attention_ref(q, kc, vc, kv_len)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_decode_attention_wrapper_validation():
    """The wrapper rejects (eagerly, before tracing) the inputs the
    kernel would otherwise mishandle silently."""
    ks = jax.random.split(K(22), 3)
    b, s, h, d = 2, 128, 2, 64
    q = jax.random.normal(ks[0], (b, h, d))
    kc = jax.random.normal(ks[1], (b, s, h, d))
    vc = jax.random.normal(ks[2], (b, s, h, d))
    with pytest.raises(TypeError, match="integer-typed"):
        decode_attention(q, kc, vc, jnp.asarray([4.0, 8.0]), interpret=True)
    with pytest.raises(ValueError, match="exceeds the cache"):
        decode_attention(q, kc, vc, jnp.asarray([4, s + 1]), interpret=True)
    with pytest.raises(ValueError, match="negative"):
        decode_attention(q, kc, vc, jnp.asarray([-1, 4]), interpret=True)
    with pytest.raises(ValueError, match="block_k"):
        decode_attention(q, kc, vc, jnp.asarray([4, 8]), block_k=0,
                         interpret=True)


# ---------------------------------------------------------------------------
# paged decode attention
# ---------------------------------------------------------------------------


# Layers of the stacked pools the paged kernels take, and the ones a
# test runs them on: the first, a middle and the last.
LAYERS = 3
LAYER_CASES = [pytest.param(0, id="first"), pytest.param(1, id="middle"),
               pytest.param(2, id="last")]


def _random_paged_cache(seed, b, n_slot_pages, page, hkv, d, pool_pages,
                        dtype=jnp.float32, layers=LAYERS):
    """Lane-dense stacked pool tensors ``[L, P, page, Hkv*d]``, every
    layer its own random pages, + a page table of distinct ids >= 1
    (page 0 is the reserved scratch page — real slots never map to
    it)."""
    ks = jax.random.split(K(seed), 3)
    shape = (layers, pool_pages, page, hkv * d)
    k_pages = jax.random.normal(ks[0], shape, dtype)
    v_pages = jax.random.normal(ks[1], shape, dtype)
    perm = jax.random.permutation(ks[2], jnp.arange(1, pool_pages))
    table = perm[: b * n_slot_pages].reshape(b, n_slot_pages)
    return k_pages, v_pages, table.astype(jnp.int32)


def _dense(pages, table, d):
    """The gathered dense cache ``[B, S, Hkv, d]`` the dense kernel reads."""
    dense = gather_pages(pages, table)
    return dense.reshape(dense.shape[:2] + (-1, d))


@pytest.mark.parametrize(
    "kv_len,window,h,hkv,d,dtype",
    [
        # full budget / crossing page 1->2 / fresh slot; G = 2
        pytest.param([32, 9, 0], 0, 4, 2, 64, jnp.float32, id="kv_len0-0"),
        # sliding window straddling the 16-boundary
        pytest.param([32, 17, 8], 6, 4, 2, 64, jnp.float32, id="kv_len1-6"),
        # MHA, two heads of 64 to a 128-lane chunk
        pytest.param([32, 9, 1], 0, 4, 4, 64, jnp.float32, id="mha"),
        pytest.param([31, 16, 3], 0, 8, 2, 128, jnp.float32, id="gqa4-d128"),
        # a 64-lane row: the whole row is one chunk
        pytest.param([32, 24, 5], 0, 8, 2, 32, jnp.float32, id="d32"),
        pytest.param([32, 9, 2], 5, 8, 2, 64, jnp.bfloat16, id="bf16"),
    ],
)
@pytest.mark.parametrize("layer", LAYER_CASES)
def test_paged_decode_matches_dense_gather(kv_len, window, h, hkv, d,
                                           dtype, layer):
    """Paged kernel == dense kernel == oracle over the gathered cache of
    the named layer of the stacked pool.  The table is a random
    permutation, so a row's pages are scattered through the pool (the
    gather really is exercised), and every layer's pages differ, so a
    read of the wrong layer cannot pass."""
    b, page, n = 3, 8, 4  # n*page = 32 tokens/slot
    kp, vp, table = _random_paged_cache(23, b, n, page, hkv, d, 1 + b * n,
                                        dtype)
    q = jax.random.normal(K(24), (b, h, d), dtype)
    kv = jnp.asarray(kv_len, dtype=jnp.int32)
    out = paged_decode_attention(q, kp, vp, table, kv, layer, window=window,
                                 interpret=True)
    kl, vl = kp[layer], vp[layer]
    dense = decode_attention(q, _dense(kl, table, d), _dense(vl, table, d),
                             kv, window=window, block_k=128, interpret=True)
    ref = paged_decode_attention_ref(q, kl, vl, table, kv, window=window)
    tol = TOLS[dtype]
    f32 = lambda x: np.asarray(x, dtype=np.float32)
    np.testing.assert_allclose(f32(out), f32(ref), **tol)
    np.testing.assert_allclose(f32(out), f32(dense), **tol)


@settings(max_examples=8, deadline=None)
@given(seed=st.integers(0, 2**31 - 1))
def test_paged_vs_dense_decode_property(seed):
    """Property: for any page permutation, ragged kv_lens (0..full) and
    window, the paged kernel equals the dense kernel over the gather."""
    b, h, hkv, d, page, n = 4, 4, 2, 32, 8, 3
    kp, vp, table = _random_paged_cache(seed, b, n, page, hkv, d,
                                        1 + b * n + 2)
    rng = np.random.RandomState(seed % (2**31 - 1))
    kv = jnp.asarray(rng.randint(0, n * page + 1, size=b), dtype=jnp.int32)
    window = int(rng.choice([0, 5, page + 1]))
    layer = int(rng.randint(0, LAYERS))
    q = jax.random.normal(K(seed % 997), (b, h, d))
    out = paged_decode_attention(q, kp, vp, table, kv, layer, window=window,
                                 interpret=True)
    dense = decode_attention(q, _dense(kp[layer], table, d),
                             _dense(vp[layer], table, d),
                             kv, window=window, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(dense),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("layer", LAYER_CASES)
def test_paged_kv_append_matches_ref_at_page_boundaries(layer):
    """Append into the named layer of the stacked pools at a page's
    first row, last row, and mid-page; each new row is written whole
    into its page, and every other row of that page, every other page
    and every other layer stays bitwise identical (in-place aliasing is
    exact).  Rows of two heads of 64, one of 128 and three of 32."""
    b, page, n = 4, 8, 3
    # start / last-of-0 / first-of-1 / mid-page of 2
    pos = jnp.asarray([0, 7, 8, 19], dtype=jnp.int32)
    others = [i for i in range(LAYERS) if i != layer]
    for hkv, d in [(2, 64), (1, 128), (3, 32)]:
        kp, vp, table = _random_paged_cache(25, b, n, page, hkv, d,
                                            1 + b * n)
        before = {"k": np.asarray(kp), "v": np.asarray(vp)}
        ks = jax.random.split(K(26), 2)
        new = {"k": jax.random.normal(ks[0], (b, hkv, d)),
               "v": jax.random.normal(ks[1], (b, hkv, d))}
        # ref first: the kernel donates (aliases) the pool buffers.
        rk, rv = paged_kv_append_ref(new["k"], new["v"], kp[layer],
                                     vp[layer], table, pos)
        k2, v2 = paged_kv_append(new["k"], new["v"], kp, vp, table, pos,
                                 layer, interpret=True)
        np.testing.assert_array_equal(np.asarray(k2)[layer], np.asarray(rk))
        np.testing.assert_array_equal(np.asarray(v2)[layer], np.asarray(rv))
        tab = np.asarray(table)
        for name, stack in (("k", np.asarray(k2)), ("v", np.asarray(v2))):
            np.testing.assert_array_equal(stack[others],
                                          before[name][others])
            after, prior = stack[layer], before[name][layer]
            written = np.zeros(after.shape[:2], dtype=bool)
            for row in range(b):
                p = int(pos[row])
                pid, off = tab[row, p // page], p % page
                written[pid, off] = True
                np.testing.assert_array_equal(
                    after[pid, off], np.asarray(new[name])[row].reshape(-1)
                )
            np.testing.assert_array_equal(after[~written], prior[~written])


def test_paged_kv_append_traced_oob_pos_lands_in_own_last_page():
    """Regression: an idle batcher slot's cache pos keeps advancing past
    ``n_pages * page_size`` (empty slots still ride the static-shape
    decode step).  Traced (jitted serving path) OOB pos must be clamped
    so the garbage write lands in the slot's OWN last table entry — the
    scratch page 0 for an idle, all-zero table row — never via an
    undefined OOB table read into a live request's pages."""
    b, hkv, d, page, n = 2, 2, 32, 4, 2
    kp, vp, table = _random_paged_cache(31, b, n, page, hkv, d, 1 + b * n,
                                        layers=1)
    table = table.at[1].set(0)  # row 1 idle: back to the scratch page
    ks = jax.random.split(K(32), 2)
    kn = jax.random.normal(ks[0], (b, hkv, d))
    vn = jax.random.normal(ks[1], (b, hkv, d))
    pos = jnp.asarray([2, n * page + 57], dtype=jnp.int32)
    before_k = np.asarray(kp)[0]
    append = jax.jit(
        lambda *a: paged_kv_append(*a, interpret=True)
    )  # traced operands: the concrete range-check cannot fire
    k2, v2 = append(kn, vn, kp, vp, table, pos, 0)
    k2 = np.asarray(k2)[0]
    tab = np.asarray(table)
    # live row 0: written exactly where expected
    np.testing.assert_array_equal(k2[tab[0, 0], 2],
                                  np.asarray(kn)[0].reshape(-1))
    # idle row 1: only the scratch page may have changed — every other
    # pool page is bitwise identical apart from row 0's single write
    untouched = [
        pid for pid in range(1, kp.shape[1]) if pid != tab[0, 0]
    ]
    np.testing.assert_array_equal(k2[untouched], before_k[untouched])


def test_paged_wrapper_validation():
    b, hkv, d, page, n = 2, 2, 64, 8, 2
    kp, vp, table = _random_paged_cache(27, b, n, page, hkv, d, 1 + b * n)
    q = jax.random.normal(K(28), (b, 4, d))
    kv = jnp.asarray([3, 5], dtype=jnp.int32)
    with pytest.raises(TypeError, match="integer-typed"):
        paged_decode_attention(q, kp, vp, table.astype(jnp.float32), kv, 0,
                               interpret=True)
    with pytest.raises(ValueError, match="exceeds the cache"):
        # kv_len beyond what the table can address
        paged_decode_attention(q, kp, vp, table,
                               jnp.asarray([n * page + 1, 0]), 0,
                               interpret=True)
    with pytest.raises(ValueError, match="exceeds the cache"):
        # page id beyond the pool
        bad = table.at[0, 0].set(kp.shape[1])
        paged_decode_attention(q, kp, vp, bad, kv, 0, interpret=True)
    with pytest.raises(ValueError, match="exceeds the cache"):
        # layer beyond the stack
        paged_decode_attention(q, kp, vp, table, kv, LAYERS, interpret=True)
    with pytest.raises(TypeError, match="integer-typed"):
        paged_decode_attention(q, kp, vp, table, kv, 1.0, interpret=True)
    with pytest.raises(ValueError, match="layer must be a scalar"):
        paged_decode_attention(q, kp, vp, table, kv, jnp.zeros(1, jnp.int32),
                               interpret=True)
    with pytest.raises(ValueError, match="page_table must be"):
        paged_decode_attention(q, kp, vp, table[0], kv, 0, interpret=True)
    with pytest.raises(ValueError, match=r"Hkv\*D"):
        # the retired [L, P, page, Hkv, D] pool
        paged_decode_attention(q, kp.reshape(LAYERS, -1, page, hkv, d),
                               vp.reshape(LAYERS, -1, page, hkv, d), table,
                               kv, 0, interpret=True)
    with pytest.raises(ValueError, match=r"Hkv\*D"):
        # one layer's pool, not the stack: its view pool[None] is
        paged_decode_attention(q, kp[0], vp[0], table, kv, 0, interpret=True)
    kn = jax.random.normal(K(29), (b, hkv, d))
    with pytest.raises(ValueError, match="exceeds the cache"):
        # concrete append position past the slot's table capacity
        paged_kv_append(kn, kn, kp, vp, table,
                        jnp.asarray([0, n * page]), 0, interpret=True)
    with pytest.raises(ValueError, match="negative"):
        paged_kv_append(kn, kn, kp, vp, table, jnp.asarray([0, 1]), -1,
                        interpret=True)
    with pytest.raises(ValueError, match=r"Hkv\*D"):
        # a new row as wide as one head, not the pool's row
        paged_kv_append(kn[:, :1], kn[:, :1], kp, vp, table,
                        jnp.asarray([0, 1]), 0, interpret=True)


# ---------------------------------------------------------------------------
# moe gating
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "n,e,k,cap,block_n",
    [
        (256, 8, 2, 48, 128),    # contended capacity
        (512, 8, 2, 1024, 256),  # dropless
        (256, 128, 1, 4, 128),   # llama4-style: 128 experts top-1
        (128, 16, 2, 24, 128),   # jamba-style
        (512, 4, 2, 128, 64),    # small E, many blocks
    ],
)
def test_moe_gating_matches_ref(n, e, k, cap, block_n):
    logits = jax.random.normal(K(4), (n, e))
    ki, gi, pi, mi = moe_gating(logits, top_k=k, capacity=cap,
                                block_n=block_n, interpret=True)
    kr, gr, pr, mr = moe_gating_ref(logits, top_k=k, capacity=cap,
                                    block_n=block_n)
    np.testing.assert_array_equal(np.asarray(ki), np.asarray(kr))
    np.testing.assert_allclose(np.asarray(gi), np.asarray(gr), rtol=1e-5, atol=1e-6)
    np.testing.assert_array_equal(np.asarray(pi), np.asarray(pr))
    np.testing.assert_array_equal(np.asarray(mi), np.asarray(mr))


@settings(max_examples=15, deadline=None)
@given(seed=st.integers(0, 2**31 - 1), e=st.sampled_from([4, 8, 16]))
def test_moe_gating_invariants(seed, e):
    """Invariants: gates sum to 1; positions within an expert are unique;
    kept positions < capacity; top-1 choice has the max prob."""
    n, k, cap = 128, 2, 16
    logits = jax.random.normal(K(seed), (n, e))
    idx, gates, pos, keep = moe_gating(logits, top_k=k, capacity=cap,
                                       block_n=64, interpret=True)
    idx, gates, pos, keep = map(np.asarray, (idx, gates, pos, keep))
    np.testing.assert_allclose(gates.sum(axis=1), 1.0, rtol=1e-5)
    assert (pos[keep] < cap).all()
    # per-expert uniqueness of assigned positions
    for ee in range(e):
        taken = pos[(idx == ee)]
        assert len(np.unique(taken)) == len(taken)
    # rank-0 really is the argmax
    probs = np.asarray(jax.nn.softmax(logits, axis=-1))
    np.testing.assert_array_equal(idx[:, 0], probs.argmax(axis=1))


# ---------------------------------------------------------------------------
# ssd scan
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "b,t,h,p,n,chunk",
    [
        (1, 128, 2, 64, 64, 32),
        (2, 256, 4, 32, 128, 64),
        (1, 64, 8, 64, 16, 16),   # jamba-ish small state
        (2, 128, 1, 128, 128, 128),  # single chunk == T
    ],
)
def test_ssd_kernel_matches_sequential(b, t, h, p, n, chunk, dtype):
    ks = jax.random.split(K(5), 4)
    x = jax.random.normal(ks[0], (b, t, h, p), dtype=dtype)
    a = jax.nn.sigmoid(jax.random.normal(ks[1], (b, t, h))).astype(dtype)
    B = jax.random.normal(ks[2], (b, t, n), dtype=dtype)
    C = jax.random.normal(ks[3], (b, t, n), dtype=dtype)
    y_k, s_k = ssd_chunked(x, a, B, C, chunk, interpret=True)
    y_r, s_r = ssd_sequential_ref(x, a, B, C)
    tol = dict(rtol=2e-4, atol=2e-4) if dtype == jnp.float32 else dict(rtol=5e-2, atol=5e-2)
    np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_r), **tol)
    np.testing.assert_allclose(np.asarray(s_k), np.asarray(s_r), **tol)


def test_ssd_chunked_ref_matches_sequential_with_state():
    """The model-layer chunked path (used in the dry-run) also equals the
    sequential scan, including a nonzero initial state."""
    ks = jax.random.split(K(6), 5)
    b, t, h, p, n, chunk = 2, 128, 2, 32, 64, 32
    x = jax.random.normal(ks[0], (b, t, h, p))
    a = jax.nn.sigmoid(jax.random.normal(ks[1], (b, t, h)))
    B = jax.random.normal(ks[2], (b, t, n))
    C = jax.random.normal(ks[3], (b, t, n))
    s0 = jax.random.normal(ks[4], (b, h, n, p))
    y_c, s_c = ssd_chunked_ref(x, a, B, C, chunk, initial_state=s0)
    y_s, s_s = ssd_sequential_ref(x, a, B, C, initial_state=s0)
    np.testing.assert_allclose(np.asarray(y_c), np.asarray(y_s), rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(s_c), np.asarray(s_s), rtol=2e-4, atol=2e-4)
    # kernel path with initial state (wrapper folds it in linearly)
    y_k, s_k = ssd_chunked(x, a, B, C, chunk, initial_state=s0, interpret=True)
    np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_s), rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(s_k), np.asarray(s_s), rtol=2e-4, atol=2e-4)


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 2**31 - 1))
def test_ssd_state_linearity_property(seed):
    """SSD is linear in x: scan(2x) == 2*scan(x)."""
    ks = jax.random.split(K(seed), 4)
    b, t, h, p, n = 1, 64, 2, 16, 16
    x = jax.random.normal(ks[0], (b, t, h, p))
    a = jax.nn.sigmoid(jax.random.normal(ks[1], (b, t, h)))
    B = jax.random.normal(ks[2], (b, t, n))
    C = jax.random.normal(ks[3], (b, t, n))
    y1, s1 = ssd_chunked(x, a, B, C, 16, interpret=True)
    y2, s2 = ssd_chunked(2 * x, a, B, C, 16, interpret=True)
    np.testing.assert_allclose(np.asarray(y2), 2 * np.asarray(y1), rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(s2), 2 * np.asarray(s1), rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# tcmm assignment
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "n,m,f,n_valid",
    [(512, 64, 4, 64), (1024, 512, 8, 100), (256, 16, 128, 16), (512, 128, 4, 1)],
)
def test_tcmm_assign_matches_ref(n, m, f, n_valid, dtype):
    ks = jax.random.split(K(7), 2)
    pts = jax.random.normal(ks[0], (n, f), dtype=dtype) * 3
    cents = jax.random.normal(ks[1], (m, f), dtype=dtype) * 3
    valid = jnp.arange(m) < n_valid
    idx_k, d_k = tcmm_assign(pts, cents, valid, block_n=256, interpret=True)
    idx_r, d_r = tcmm_assign_ref(pts, cents, valid)
    tol = dict(rtol=1e-4, atol=1e-4) if dtype == jnp.float32 else dict(rtol=5e-2, atol=5e-1)
    np.testing.assert_allclose(np.asarray(d_k), np.asarray(d_r), **tol)
    if dtype == jnp.float32:
        np.testing.assert_array_equal(np.asarray(idx_k), np.asarray(idx_r))
    assert (np.asarray(idx_k) < n_valid).all()  # never picks invalid rows


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 2**31 - 1))
def test_tcmm_assign_exact_match_property(seed):
    """A point equal to a valid centroid must map to it with distance ~0."""
    ks = jax.random.split(K(seed), 1)[0]
    m, f = 32, 4
    cents = jax.random.normal(ks, (m, f)) * 5
    pts = jnp.tile(cents[7][None], (64, 1))
    valid = jnp.ones((m,), dtype=bool)
    idx, d = tcmm_assign(pts, cents, valid, block_n=64, interpret=True)
    assert (np.asarray(idx) == 7).all()
    np.testing.assert_allclose(np.asarray(d), 0.0, atol=1e-4)
