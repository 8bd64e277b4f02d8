#!/usr/bin/env python3
"""Smoke test on the chip: the serving path at MiniCPM-2B's published widths.

Default phase, one TPU chip.  MiniCPM-2B (40 layers, d_model 2304, 36 MHA
heads of 64, d_ff 5760, vocab 122753) is built from ``--seed`` by the
serving launcher's own builder, in bf16, and served by
``ElasticServingPool`` over the paged ``ContinuousBatcher``: one replica,
8 slots, ``max_len`` 1024, pages of 16.  Eight greedy requests (prompts
of 128 and 512 tokens, 32 new tokens each) must all complete with 32
tokens and leave no page leaked.  Two comparisons must hold: the paged
decode kernel against ``kernels/decode_attention/ref.py`` at the served
widths, and the first decode step's logits against a cache-free forward
over prompt + first token.  The decode step's HLO must hold the Pallas
kernels (``tpu_custom_call``).

``--chips 4`` runs only the elastic DP remesh, on four chips:
``TrainingJob(use_mesh=True)`` at MiniCPM-2B's widths cut to 4 layers,
DP 2 scaled to DP 4 at step 3 and stopped at step 6, against a fixed DP-4
run from the same seed and token stream.

Every phase prints its findings as JSON lines; the last line of stdout is
``{"ok": true, "device": {...}}``.  Any failure, or a backend other than
TPU, exits non-zero without that line.  One process holds the chip.

Usage:
  python chip_smoke.py [--seed 0]
  python chip_smoke.py --chips 4
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

ARCH = "minicpm-2b"


class SmokeFailure(RuntimeError):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def emit(**fields) -> None:
    print(json.dumps(fields), flush=True)


class CompileClock:
    """Seconds JAX spends tracing, lowering and compiling (its own
    monitoring events), so set-up time can be told apart from run time."""

    EVENTS = (
        "/jax/core/compile/jaxpr_trace_duration",
        "/jax/core/compile/jaxpr_to_mlir_module_duration",
        "/jax/core/compile/backend_compile_duration",
    )

    def __init__(self) -> None:
        import jax

        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **kwargs) -> None:
        if event in self.EVENTS:
            self.seconds += duration


# ---------------------------------------------------------------------------
# default phase: serve MiniCPM-2B on one chip
# ---------------------------------------------------------------------------


def check_paged_kernel(seed: int, batch: int, heads: int, head_dim: int,
                       page: int, num_pages: int, max_len: int) -> dict:
    """Compiled paged decode kernel vs the float32 oracle, bf16 inputs."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels.decode_attention import (
        paged_decode_attention,
        paged_decode_attention_ref,
    )

    n_slot = max_len // page
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    shape = (num_pages, page, heads * head_dim)  # lane-dense pool
    q = jax.random.normal(ks[0], (batch, heads, head_dim), jnp.bfloat16)
    k_pages = jax.random.normal(ks[1], shape, jnp.bfloat16)
    v_pages = jax.random.normal(ks[2], shape, jnp.bfloat16)
    perm = jax.random.permutation(ks[3], jnp.arange(1, num_pages))
    table = perm[: batch * n_slot].reshape(batch, n_slot).astype(jnp.int32)
    # ragged lengths: one token, page boundaries either side, a full slot
    lens = np.linspace(1, max_len, batch).astype(np.int32)
    lens[: min(batch, 3)] = [1, page, page + 1][: min(batch, 3)]
    kv_len = jnp.asarray(lens)

    # one layer's pool: the stacked pool's free view, at layer 0
    out = paged_decode_attention(q, k_pages[None], v_pages[None], table,
                                 kv_len, 0)
    with jax.default_matmul_precision("highest"):
        ref = jax.jit(paged_decode_attention_ref)(
            q, k_pages, v_pages, table, kv_len
        )
    out = np.asarray(out, dtype=np.float32)
    ref = np.asarray(ref, dtype=np.float32)
    # Both sides accumulate in float32 and round once to bf16; they differ
    # only in summation order, so a result may land one bf16 ulp
    # (2**-7 relative) away.  atol covers outputs near zero.
    rtol, atol = 2.0 ** -7, 1e-3
    err = np.abs(out - ref)
    ok = bool(np.all(err <= atol + rtol * np.abs(ref)))
    return {"max_abs_err": float(err.max()), "rtol": rtol, "atol": atol,
            "ok": ok, "kv_len": lens.tolist()}


def check_decode_logits(model, params, prompt, max_len: int, page: int,
                        prefill_step) -> dict:
    """First decode step through the paged cache vs a cache-free forward
    over prompt + first token (the model's own train-path forward)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.models.layers import PagedSpec

    n_slot = max_len // page
    cache = model.init_cache(1, max_len,
                             paged=PagedSpec(num_pages=1 + n_slot,
                                             page_size=page))
    table = jnp.arange(1, n_slot + 1, dtype=jnp.int32)[None]

    def with_table(path, leaf):
        if getattr(path[-1], "key", None) == "page_table":
            return jnp.broadcast_to(table, leaf.shape)
        return leaf

    cache = jax.tree_util.tree_map_with_path(with_table, cache)
    tokens = jnp.asarray(prompt, dtype=jnp.int32)[None]
    first, cache = prefill_step(params, {"tokens": tokens}, cache)
    positions = jnp.asarray([len(prompt)], dtype=jnp.int32)
    dec, _ = jax.jit(model.decode_step)(params, first[:, None], cache,
                                        positions)
    full = jnp.concatenate([tokens, first[:, None]], axis=1)
    ref, _ = jax.jit(model.train_logits)(params, {"tokens": full})
    dec = np.asarray(dec[0, -1], dtype=np.float32)
    ref = np.asarray(ref[0, -1], dtype=np.float32)
    # Both paths run bf16 activations through 40 layers but round at
    # different points (the kernel keeps softmax weights in float32, the
    # dense path rounds them to bf16; prefill and the cache-free forward
    # tile their matmuls differently).  Rounding alone put the largest
    # error at 0.28% of the largest logit on a v5e at these widths, and
    # at 1.4-2.0% at d_model 256 on the CPU.  2% keeps that margin, while
    # a step as coarse as fp8's (16x bf16's) or a wrong page, position or
    # mask would exceed it.
    rel_tol = 2e-2
    scale = float(np.abs(ref).max())
    err = float(np.abs(dec - ref).max())
    return {"max_abs_err": err, "max_abs_logit": scale, "rel_tol": rel_tol,
            "argmax_equal": bool(dec.argmax() == ref.argmax()),
            "ok": err <= rel_tol * scale}


def serve_phase(seed: int, *, full_size: bool = True, slots: int = 8,
                max_len: int = 1024, page: int = 16,
                prompt_lens=(128, 512), new_tokens: int = 32) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.launch import serve
    from repro.serving import ElasticServingPool, Request

    clock = CompileClock()
    argv = ["--arch", ARCH, "--paged", "--slots", str(slots),
            "--max-len", str(max_len), "--page-size", str(page),
            "--max-replicas", "1", "--max-new-tokens", str(new_tokens),
            "--seed", str(seed)]
    if full_size:
        argv.append("--full-size")
    args = serve.make_parser().parse_args(argv)

    t0 = time.perf_counter()
    model, params, vocab = serve.build(args)
    jax.block_until_ready(params)
    cfg = model.cfg
    n_params = sum(int(x.size) for x in jax.tree.leaves(params))
    emit(phase="build", arch=cfg.name, layers=cfg.num_layers,
         d_model=cfg.d_model, heads=cfg.num_heads, kv_heads=cfg.num_kv_heads,
         head_dim=cfg.resolved_head_dim, d_ff=cfg.d_ff,
         vocab=cfg.vocab_size, params=n_params,
         param_dtype=jnp.dtype(model.param_dtype).name,
         seconds=time.perf_counter() - t0)

    pool = ElasticServingPool(model, params, **serve.pool_kwargs(args))
    spec = pool.paged
    check(spec is not None, "serving pool is not paged")

    kern = check_paged_kernel(seed, slots, cfg.num_kv_heads,
                              cfg.resolved_head_dim, page, spec.num_pages,
                              max_len)
    emit(phase="check_paged_kernel", **kern)
    check(kern["ok"], "paged decode kernel disagrees with ref.py")

    rng = np.random.default_rng(seed)
    prompts = [rng.integers(0, vocab, prompt_lens[i % len(prompt_lens)])
               .tolist() for i in range(slots)]
    logits = check_decode_logits(model, params, prompts[0], max_len, page,
                                 pool.prefill_step)
    emit(phase="check_decode_logits", prompt_len=len(prompts[0]), **logits)
    check(logits["ok"], "first decode step's logits disagree with the "
                        "cache-free forward")

    compile_before = clock.seconds
    t0 = time.perf_counter()
    requests = [Request(prompt=p, max_new_tokens=new_tokens) for p in prompts]
    for req in requests:
        check(pool.submit(req, now=0.0), "ingress refused a request")
    tick = 0
    while pool.queue_depth() or pool.occupancy():
        pool.step(float(tick))
        tick += 1
        check(tick <= 10 * new_tokens * len(requests),
              f"serving did not drain in {tick} ticks")
    serve_s = time.perf_counter() - t0
    compile_s = clock.seconds - compile_before

    replica = pool.replicas[0]
    done = {r.req_id: r for r in pool.completed}
    counts = [len(done[r.req_id].output or []) if r.req_id in done else 0
              for r in requests]
    leaked = sum(r.page_pool.leaked() for r in pool.replicas)
    in_use = pool.total_pages_in_use()

    tokens = jnp.zeros((slots, 1), jnp.int32)
    positions = jnp.zeros((slots,), jnp.int32)
    hlo = pool.decode_step.lower(params, tokens, replica.cache, positions,
                                 replica.rng).as_text()
    stats = jax.devices()[0].memory_stats() or {}
    emit(phase="serve", replicas=len(pool.replicas), slots=slots,
         max_len=max_len, page_size=page, pool_pages=spec.num_pages,
         requests=len(requests), completed=len(done),
         prompt_lens=[len(p) for p in prompts], new_tokens=counts,
         leaked_pages=leaked, pages_in_use=in_use,
         decode_ticks=tick, tpu_custom_call="tpu_custom_call" in hlo,
         serve_wall_s=serve_s, compile_s_in_window=compile_s,
         compile_s_total=clock.seconds,
         peak_bytes_in_use=stats.get("peak_bytes_in_use"),
         note="smoke run: wall and compile seconds are set-up figures, "
              "not speed")
    check(len(done) == len(requests), f"{len(done)}/{len(requests)} completed")
    check(all(n == new_tokens for n in counts),
          f"token counts {counts}, wanted {new_tokens} each")
    check(all(done[r.req_id].fail_reason is None for r in requests),
          "a request failed")
    check(leaked == 0 and in_use == 0, f"pages leaked={leaked} in_use={in_use}")
    check("tpu_custom_call" in hlo, "decode step holds no Pallas kernel")


# ---------------------------------------------------------------------------
# --chips 4: the elastic DP remesh
# ---------------------------------------------------------------------------


def remesh_phase(seed: int, *, layers: int = 4, full_width: bool = True,
                 batch: int = 8, seq_len: int = 256, scale_at: int = 3,
                 steps: int = 6) -> None:
    import dataclasses

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.config import TrainingConfig, get_arch
    from repro.data.pipeline import build_token_log
    from repro.models.zoo import build_model
    from repro.training.job import TrainingJob

    n_dev = len(jax.devices())
    check(n_dev >= 4, f"--chips 4 needs 4 devices, found {n_dev}")
    published = get_arch(ARCH, smoke=not full_width)
    cfg = dataclasses.replace(published, num_layers=layers)
    model = build_model(cfg, compute_dtype=jnp.bfloat16,
                        param_dtype=jnp.float32)
    tcfg = TrainingConfig(learning_rate=1e-4, warmup_steps=0,
                          schedule="constant")
    n_params = sum(
        int(np.prod(x.shape))
        for x in jax.tree.leaves(jax.eval_shape(model.init,
                                                jax.random.PRNGKey(0)))
    )
    emit(phase="remesh_config", arch=cfg.name, d_model=cfg.d_model,
         heads=cfg.num_heads, d_ff=cfg.d_ff, vocab=cfg.vocab_size,
         layers=f"{published.num_layers} -> {layers} (depth cut to fit "
                "float32 params, grads and Adam state on each chip)",
         params=n_params,
         f32_params_grads_adam_gb=4 * n_params * 4 / 1e9,
         batch=batch, seq_len=seq_len)

    def run(dp: int, scale_to=None):
        log = build_token_log(cfg.vocab_size, num_docs=steps * batch,
                              doc_len=seq_len + 1, partitions=4, seed=seed)

        def on_step(step, metrics):
            if scale_to is not None and step == scale_at:
                job.request_scale(scale_to)

        job = TrainingJob(model, cfg, tcfg, log, batch_size=batch,
                          seq_len=seq_len, dp=dp, max_dp=4, use_mesh=True,
                          seed=seed, on_step=on_step)
        job.run(steps)
        return job

    clock = CompileClock()
    t0 = time.perf_counter()
    elastic = run(2, scale_to=4)
    after = {
        "mesh": dict(elastic.mesh.shape),
        "scale_log": [[o, n] for (_, o, n, _) in elastic.scale_log],
        "param_devices": sorted({len(x.sharding.device_set)
                                 for x in jax.tree.leaves(elastic.state.params)}),
    }
    bs = elastic.batch_sharding()
    after["batch_devices"] = len(bs.device_set)
    after["batch_shard_rows"] = bs.shard_shape((batch, seq_len))[0]
    e_losses, e_offsets = list(elastic.losses), dict(elastic.step_offsets)
    del elastic
    fixed = run(4)
    f_losses, f_offsets = list(fixed.losses), dict(fixed.step_offsets)
    del fixed
    # The runs split each batch 2 and 4 ways for three steps, so their
    # reductions run in different orders over bf16 activations; Adam
    # then turns near-zero gradient differences into steps of lr size.
    # 1% of the loss bounds that drift over six steps.
    rtol = 1e-2
    diffs = [abs(a - b) / abs(b) for a, b in zip(e_losses, f_losses)]
    emit(phase="remesh", **after, steps=len(e_losses),
         elastic_losses=e_losses, fixed_losses=f_losses,
         max_rel_loss_diff=max(diffs) if diffs else None, rtol=rtol,
         offsets_equal=e_offsets == f_offsets,
         wall_s=time.perf_counter() - t0, compile_s=clock.seconds,
         note="smoke run: wall and compile seconds are not speed")
    check(after["scale_log"] == [[2, 4]], f"scale log {after['scale_log']}")
    check(after["mesh"].get("data") == 4, f"mesh {after['mesh']}")
    check(after["param_devices"] == [4],
          f"param leaves span {after['param_devices']} devices")
    check(after["batch_devices"] == 4 and after["batch_shard_rows"] * 4 == batch,
          "the batch is not split over the 4 devices")
    check(len(e_losses) == steps and len(f_losses) == steps,
          f"steps run: {len(e_losses)} and {len(f_losses)}")
    check(e_offsets == f_offsets, "committed offsets differ")
    check(all(np.isfinite(e_losses)) and max(diffs) <= rtol,
          f"losses differ by up to {max(diffs)}")


# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the elastic DP remesh, on four chips")
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found platform {platform!r} "
              f"({devices[0].device_kind})", file=sys.stderr)
        return 2

    from repro.launch.compile_cache import enable_compile_cache

    emit(phase="device", platform=platform,
         device_kind=devices[0].device_kind, count=len(devices),
         compile_cache=enable_compile_cache())
    try:
        if args.chips == 4:
            remesh_phase(args.seed)
        else:
            serve_phase(args.seed)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
