"""Compiles for a described TPU v5e, no chip attached.

The TPU compiler is installed with JAX, so the main path's kernels and
MiniCPM-2B's full-width paged decode step can be compiled for a v5e that
is described and not attached: Mosaic's block-tiling checks and the
chip's memory bound run here, where interpret mode checks neither.  The
topology is described inside a module fixture (only the worker that runs
this file loads the TPU library), and JAX's persistent compilation cache
is off around these compiles, since entries written for a described chip
cannot be read back without one.

The last test is the CPU half of the same path: with bf16 compute and
float32 params (``build_model``'s defaults) every architecture traces its
prefill, its decode over dense and paged caches, and its train step.
"""

from __future__ import annotations

import math
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.config import TrainingConfig, get_arch, list_archs
from repro.kernels import platform
from repro.kernels.decode_attention.kernel import (
    paged_decode_attention_fwd,
    paged_kv_append_fwd,
    paged_latent_append_fwd,
    paged_latent_decode_fwd,
)
from repro.kernels.tcmm_assign.kernel import tcmm_assign_fwd
from repro.models.layers import PagedSpec
from repro.models.zoo import build_model
from repro.serving.serve_step import make_decode_step
from repro.training.train_step import init_train_state, make_train_step

HBM_BYTES = 16e9  # one v5e chip
MINICPM = dict(slots=8, max_len=1024, page=16, pages=513,  # chip_smoke.py
               layers=40)
# bench/configs/deepseek-v2-lite-serve.json: 640-lane latent rows, the
# leading dense layer's pool and the 26 scanned layers' stacked one
DEEPSEEK = dict(slots=16, max_len=8192, page=16, pages=5121, heads=16,
                width=640, latent=512, layers=26)


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no TPU compiler in this install
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.experimental.compilation_cache import compilation_cache

    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


def _sds(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize("hkv,groups,d", [
    pytest.param(36, 1, 64, id="36-1"),
    pytest.param(8, 4, 64, id="8-4"),
    pytest.param(8, 4, 128, id="8-4-128"),
])
def test_paged_decode_kernel_compiles_for_v5e(one_chip, hkv, groups, d):
    """MiniCPM's MHA widths (36 kv heads of 64) and GQA layouts with
    head_dim 64 and 128, pages of 16: each block is a whole lane-dense
    page, every kv head side by side."""
    b, page = MINICPM["slots"], MINICPM["page"]
    pool = (MINICPM["layers"], MINICPM["pages"], page, hkv * d)
    n = MINICPM["max_len"] // page
    _compile(
        paged_decode_attention_fwd,
        _sds(one_chip, (b, hkv * groups, d), jnp.bfloat16),
        _sds(one_chip, pool, jnp.bfloat16),
        _sds(one_chip, pool, jnp.bfloat16),
        _sds(one_chip, (b, n), jnp.int32),
        _sds(one_chip, (b,), jnp.int32),
        _sds(one_chip, (), jnp.int32),
    )


def test_paged_kv_append_compiles_for_v5e(one_chip):
    b, hkv, d, page = MINICPM["slots"], 36, 64, MINICPM["page"]
    pool = (MINICPM["layers"], MINICPM["pages"], page, hkv * d)
    n = MINICPM["max_len"] // page
    _compile(
        paged_kv_append_fwd,
        _sds(one_chip, (b, hkv, d), jnp.bfloat16),
        _sds(one_chip, (b, hkv, d), jnp.bfloat16),
        _sds(one_chip, pool, jnp.bfloat16),
        _sds(one_chip, pool, jnp.bfloat16),
        _sds(one_chip, (b, n), jnp.int32),
        _sds(one_chip, (b,), jnp.int32),
        _sds(one_chip, (), jnp.int32),
    )


def test_paged_latent_decode_kernel_compiles_for_v5e(one_chip):
    """DeepSeek-V2-Lite's latent decode: 16 heads over 640-lane rows in
    pages of 16, 32 pages a grid step, 16 slots x 8192 tokens, one layer
    of the 26 scanned layers' stacked pool."""
    c = DEEPSEEK
    n = c["max_len"] // c["page"]
    _compile(
        lambda q, p, t, k, ly: paged_latent_decode_fwd(q, p, t, k, ly,
                                                       c["latent"], 0.1),
        _sds(one_chip, (c["slots"], c["heads"], c["width"]), jnp.bfloat16),
        _sds(one_chip, (c["layers"], c["pages"], c["page"], c["width"]),
             jnp.bfloat16),
        _sds(one_chip, (c["slots"], n), jnp.int32),
        _sds(one_chip, (c["slots"],), jnp.int32),
        _sds(one_chip, (), jnp.int32),
    )


def test_paged_latent_append_compiles_for_v5e(one_chip):
    c = DEEPSEEK
    _compile(
        paged_latent_append_fwd,
        _sds(one_chip, (c["slots"], c["width"]), jnp.bfloat16),
        _sds(one_chip, (c["layers"], c["pages"], c["page"], c["width"]),
             jnp.bfloat16),
        _sds(one_chip, (c["slots"], c["max_len"] // c["page"]), jnp.int32),
        _sds(one_chip, (c["slots"],), jnp.int32),
        _sds(one_chip, (), jnp.int32),
    )


@pytest.mark.parametrize("centroids", [256, 512])
def test_tcmm_assign_compiles_for_v5e(one_chip, centroids):
    """One trajectory point against the micro-cluster table, as
    ``apps/tcmm.py`` calls it (512 = ``TCMMConfig.max_micro_clusters``)."""
    _compile(
        lambda p, c, v: tcmm_assign_fwd(p, c, v, block_n=1),
        _sds(one_chip, (1, 4), jnp.float32),
        _sds(one_chip, (centroids, 4), jnp.float32),
        _sds(one_chip, (centroids,), jnp.bool_),
    )


def _decode_step(one_chip, arch: str, sizes: dict, pages: int):
    """The served decode step of ``arch`` at its published widths, bf16,
    ``sizes``' slots x max_len tokens in pages of 16, compiled for one
    v5e.  Returns the config, the cache's shapes and the compiled step.
    The described chip is not the default backend, so the platform check
    is steered to the TPU branch by the calling test."""
    cfg = get_arch(arch)
    model = build_model(cfg, compute_dtype=jnp.bfloat16,
                        param_dtype=jnp.bfloat16)

    def on_chip(tree):
        return jax.tree.map(
            lambda x: _sds(one_chip, x.shape, x.dtype), tree
        )

    params = on_chip(jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    spec = PagedSpec(num_pages=pages, page_size=sizes["page"])
    cache = on_chip(jax.eval_shape(
        lambda: model.init_cache(sizes["slots"], sizes["max_len"],
                                 paged=spec)
    ))
    b = sizes["slots"]
    compiled = make_decode_step(model).lower(
        params,
        _sds(one_chip, (b, 1), jnp.int32),
        cache,
        _sds(one_chip, (b,), jnp.int32),
        _sds(one_chip, (2,), jnp.uint32),
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return cfg, cache, compiled


def _minicpm_decode_step(one_chip, pages: int):
    cfg, _, compiled = _decode_step(one_chip, "minicpm-2b", MINICPM, pages)
    return cfg, compiled


def test_minicpm_full_width_paged_decode_step_compiles_for_v5e(
    one_chip, monkeypatch
):
    """The decode step holds the Pallas kernels and fits one chip at
    ``chip_smoke.py``'s 513 pages."""
    monkeypatch.setattr(platform, "compiled_kernels", lambda: True)
    _, compiled = _minicpm_decode_step(one_chip, MINICPM["pages"])
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes)
    assert total < HBM_BYTES, f"{total / 1e9:.2f} GB does not fit one v5e"


def test_minicpm_decode_step_keeps_the_pool_row_major(one_chip,
                                                      monkeypatch):
    """At the serving benchmark's 385 pages, the stacked page pools enter
    the decode step row-major, the layout the paged kernels read, and no
    layer's pool slice is copied or transposed on its way to or from
    them.  A pool ``[.., P, page, Hkv, D]`` with D = 64 took the page
    axis as its minor-most dim instead, and every layer paid a transpose
    of its slice each way around the kernels.  (That nothing slices a
    layer's pool out at all is
    ``test_decode_step_updates_the_page_pools_in_place``'s.)"""
    monkeypatch.setattr(platform, "compiled_kernels", lambda: True)
    pages, page = 385, MINICPM["page"]
    cfg, compiled = _minicpm_decode_step(one_chip, pages)
    cache_formats = compiled.input_formats[0][2]
    pools = [
        (path, fmt) for path, fmt
        in jax.tree_util.tree_flatten_with_path(cache_formats)[0]
        if path[-1].key in ("k_pages", "v_pages")
    ]
    assert len(pools) == 2
    for path, fmt in pools:
        m2m = fmt.layout.major_to_minor
        assert m2m == tuple(range(len(m2m))), (path, m2m)
    hkv, d = cfg.num_kv_heads, cfg.resolved_head_dim
    pool_slice = re.compile(
        rf"\[(?:1,)?{pages},{page},(?:{hkv * d}|{hkv},{d})\]"
    )
    op = re.compile(r"\s*(?:ROOT )?%(\S+) = (.+?) ([\w-]+)\(")
    moved = []
    for line in compiled.as_text().splitlines():
        m = op.match(line)
        if m is None or not pool_slice.search(m.group(2)):
            continue
        name, kind = m.group(1), m.group(3)
        if (kind in ("copy", "copy-start", "transpose")
                or name.startswith(("copy", "transpose"))):
            moved.append(line.strip()[:160])
    assert not moved, moved


def test_deepseek_full_width_decode_step_compiles_for_v5e(one_chip,
                                                          monkeypatch):
    """DeepSeek-V2-Lite's served decode step (27 layers, 8 of 64 experts
    held, bf16) over the benchmark's latent pool holds the latent
    kernels, fits one chip (the step donates the cache, so its output
    pool is its input's buffer), and takes every layer's pool
    row-major."""
    monkeypatch.setattr(platform, "compiled_kernels", lambda: True)
    _, _, compiled = _decode_step(one_chip, "deepseek-v2-lite", DEEPSEEK,
                                  DEEPSEEK["pages"])
    text = compiled.as_text()
    assert "_paged_latent_decode_jit" in text and "_latent_append_jit" in text
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes)
    assert total < HBM_BYTES, f"{total / 1e9:.2f} GB does not fit one v5e"
    pools = [fmt for path, fmt in jax.tree_util.tree_flatten_with_path(
        compiled.input_formats[0][2])[0] if path[-1].key == "latent_pages"]
    assert len(pools) == 2  # the leading dense layer's and the scanned ones'
    for fmt in pools:
        m2m = fmt.layout.major_to_minor
        assert m2m == tuple(range(len(m2m))), m2m


@pytest.mark.parametrize("arch,sizes,pages", [
    pytest.param("minicpm-2b", MINICPM, 385, id="minicpm-385"),
    pytest.param("deepseek-v2-lite", DEEPSEEK, DEEPSEEK["pages"],
                 id="deepseek-5121"),
])
def test_decode_step_updates_the_page_pools_in_place(one_chip, monkeypatch,
                                                     arch, sizes, pages):
    """At the serving benchmark's pools, the decode step updates every
    page pool in place: the scan carries the stacked pools and the
    kernels index them by layer, so no op makes a layer's pool slice
    ``[P, page, W]`` or a whole pool ``[L, P, page, W]`` by a copy,
    transpose, dynamic-slice or dynamic-update-slice, fused or not (the
    fusions are named for the op they do, as
    ``dynamic-slice_bitcast_fusion``).  The step donates the cache: its
    module aliases every ``*_pages`` parameter to an output, and the
    aliased bytes hold at least one whole pool.  Sliced per layer, the
    pools cost MiniCPM-2B's step about a third of its device time."""
    monkeypatch.setattr(platform, "compiled_kernels", lambda: True)
    cfg, cache, compiled = _decode_step(one_chip, arch, sizes, pages)
    pools = [leaf for path, leaf in jax.tree_util.tree_flatten_with_path(cache)[0]
             if str(path[-1].key).endswith("_pages")]
    assert pools
    width = pools[0].shape[-1]
    pool_shape = re.compile(rf"\[(?:\d+,)?{pages},{sizes['page']},{width}\]")
    moves = ("copy", "transpose", "dynamic-slice", "dynamic-update-slice")
    op = re.compile(r"\s*(?:ROOT )?%(\S+) = (.+?) ([\w-]+)\(")
    text = compiled.as_text()
    moved = []
    for line in text.splitlines():
        m = op.match(line)
        if m is None or not pool_shape.search(m.group(2)):
            continue
        name, kind = m.group(1), m.group(3)
        if kind.startswith(moves) or any(mv in name for mv in moves):
            moved.append(line.strip()[:160])
    assert not moved, moved

    header = text.splitlines()[0]
    aliased = {int(n) for n in re.findall(r"\(\s*(\d+), \{\}",
                                          header.split("entry_computation")[0])}
    params = re.findall(r"%(\S*_pages\S*) = \S+ parameter\((\d+)\)", text)
    assert len(params) == len(pools), params
    assert all(int(n) in aliased for _, n in params), (params, aliased)
    one_pool = max(math.prod(p.shape) * p.dtype.itemsize for p in pools)
    assert compiled.memory_analysis().alias_size_in_bytes >= one_pool


@pytest.mark.parametrize("arch", list_archs())
def test_bf16_compute_f32_params_trace(arch):
    """build_model's default dtypes (bf16 compute, float32 params) trace
    for prefill, decode over a dense and a paged cache, and the train
    step; the residual stream and the caches stay bf16."""
    cfg = get_arch(arch, smoke=True)
    model = build_model(cfg)
    assert model.compute_dtype == jnp.bfloat16
    assert model.param_dtype == jnp.float32
    key = jax.random.PRNGKey(0)
    b, t = 2, 8
    tokens = jax.ShapeDtypeStruct((b, t), jnp.int32)
    batch = {"tokens": tokens, "labels": tokens}
    frontend = None
    if cfg.encoder_layers > 0:
        frontend = jax.ShapeDtypeStruct((b, cfg.encoder_seq, cfg.d_model),
                                        jnp.bfloat16)
    elif cfg.frontend_tokens > 0:
        frontend = jax.ShapeDtypeStruct((b, cfg.frontend_tokens, cfg.d_model),
                                        jnp.bfloat16)
    if frontend is not None:
        batch["frontend"] = frontend

    tcfg = TrainingConfig()
    state = jax.eval_shape(lambda k: init_train_state(model, tcfg, k), key)
    _, metrics = jax.eval_shape(make_train_step(model, tcfg), state, batch)
    assert metrics["loss"].dtype == jnp.float32

    params = jax.eval_shape(model.init, key)
    prompt = {k: v for k, v in batch.items() if k != "labels"}
    cross = frontend if cfg.encoder_layers > 0 else None
    for paged in (None, PagedSpec(num_pages=9, page_size=8)):
        cache = jax.eval_shape(lambda: model.init_cache(b, 32, paged=paged))
        _, cache = jax.eval_shape(
            lambda p, x, c: model.prefill(p, x, c, last_only=True),
            params, prompt, cache,
        )
        logits, cache2 = jax.eval_shape(
            lambda p, tk, c, pos, f: model.decode_step(p, tk, c, pos,
                                                       frontend=f),
            params, jax.ShapeDtypeStruct((b, 1), jnp.int32), cache,
            jax.ShapeDtypeStruct((b,), jnp.int32), cross,
        )
        assert logits.shape == (b, 1, cfg.vocab_size)
        for before, after in zip(jax.tree.leaves(cache),
                                 jax.tree.leaves(cache2)):
            assert before.dtype == after.dtype
