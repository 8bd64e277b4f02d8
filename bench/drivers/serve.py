"""A serving cell: MiniCPM-2B behind ``ElasticServingPool`` on one chip.

Set-up makes the weights on the device from the seed, builds the pool
as the serving launcher builds it, and sends one request of each prompt
length the mix uses, twice over, so that every program the window runs
is compiled (or loaded from the cache) before it opens.  The window is
an open loop on the wall clock: each request is submitted when it is
due, ``pool.step(now)`` runs with the wall time as ``now``, and after
every step the harness reads what a client would have received from
each slot.  Once the window closes, memory is read, the pool is freed
and the reference checks a sample of the finished requests.
"""

from __future__ import annotations

import gc
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

import costs
import model as M
import reference
import traffic
import weights as W
from common import BenchError, log, percentile


@dataclass
class Track:
    due: float
    plen: int
    max_new: int
    prompt: np.ndarray
    admitted: Optional[float] = None
    times: List[float] = field(default_factory=list)
    output: Optional[List[int]] = None
    fail: Optional[str] = None


class ServeCell:
    def __init__(self, c: dict, seed: int, origin: float):
        from repro.launch import serve
        from repro.models.zoo import build_model
        from repro.serving import ElasticServingPool

        conf, self.mix = c["config"], c["traffic"]
        self.c, self.seed, self.origin = c, seed, origin
        self.s = M.sizes(conf)
        sv = conf["serving"]
        dtype = jnp.dtype(sv["dtype"])
        arch = M.program_arch(conf)
        self.model = build_model(arch, compute_dtype=dtype, param_dtype=dtype)
        params = W.program_params(seed, self.s, dtype)
        want = jax.eval_shape(self.model.init, jax.random.PRNGKey(0))
        if jax.tree.structure(want) != jax.tree.structure(params) or any(
                a.shape != b.shape for a, b in zip(jax.tree.leaves(want),
                                                   jax.tree.leaves(params))):
            raise BenchError("bench/weights.py's layout is not the program's")
        argv = ["--arch", conf["arch"], "--paged", "--slots", str(sv["slots"]),
                "--max-len", str(sv["max_len"]), "--page-size", str(sv["page_size"]),
                "--pages", str(sv["pages"]), "--max-replicas", str(sv["replicas"]),
                "--temperature", str(sv["temperature"])]
        args = serve.make_parser().parse_args(argv)
        self.pool = ElasticServingPool(self.model, params, **serve.pool_kwargs(args))
        del params
        self._warm_up()

    def now(self) -> float:
        return time.perf_counter() - self.origin

    def _warm_up(self) -> None:
        from repro.serving import Request

        prompts = traffic.warmup_prompts(self.mix, self.s["vocab"])
        for order in (prompts, prompts[::-1]):
            for p in order:
                self.pool.submit(Request(prompt=p.tolist(), max_new_tokens=3),
                                 now=self.now())
            while self.pool.queue_depth() or self.pool.occupancy():
                self.pool.step(self.now())
        jax.block_until_ready(self.pool.replicas[0].cache)

    # -- the window ---------------------------------------------------------
    def window(self, reqs: List[traffic.Req], seconds: float) -> dict:
        from repro.serving import Request

        pool = self.pool
        tracks: Dict[int, Track] = {}
        late: List[float] = []
        steps = rows = kv_tokens = prefill_tokens = 0
        nc = len(pool.completed)
        i = 0
        t0 = time.perf_counter()
        clock = lambda: time.perf_counter() - t0  # noqa: E731
        with TraceAnnotation("bench.window"):
            while True:
                t = clock()
                if t >= seconds:
                    break
                with TraceAnnotation("bench.submit"):
                    while i < len(reqs) and reqs[i].due <= t:
                        r = reqs[i]
                        req = Request(prompt=r.prompt.tolist(), max_new_tokens=r.max_new)
                        tracks[req.req_id] = Track(r.due, len(r.prompt), r.max_new, r.prompt)
                        late.append(t - r.due)
                        if not pool.submit(req, now=self.now()):
                            tracks[req.req_id].fail = "refused"
                        i += 1
                if pool.queue_depth() == 0 and pool.occupancy() == 0:
                    nxt = reqs[i].due if i < len(reqs) else seconds
                    with TraceAnnotation("bench.wait_for_arrival"):
                        time.sleep(max(0.0, min(nxt, seconds) - clock()))
                    continue
                before = {(k, s): (r.req_id, int(rep.positions[s]))
                          for k, rep in enumerate(pool.replicas)
                          for s, r in enumerate(rep.active) if r is not None}
                with TraceAnnotation("bench.pool_step"):
                    decoded = pool.step(self.now())
                with TraceAnnotation("bench.observe"):
                    t = clock()
                    if decoded:
                        steps += 1
                        rows += decoded
                    # every slot active before the step decoded at its
                    # position; a slot admitted in it decoded after its prompt
                    kv_tokens += sum(pos + 1 for _, pos in before.values())
                    for k, rep in enumerate(pool.replicas):
                        for s, r in enumerate(rep.active):
                            if r is None:
                                continue
                            tr = tracks[r.req_id]
                            if before.get((k, s), (None,))[0] != r.req_id:
                                kv_tokens += tr.plen + 1
                            if tr.admitted is None:
                                tr.admitted = t
                                prefill_tokens += tr.plen
                            n = len(rep.outputs[s])
                            tr.times.extend([t] * (n - len(tr.times)))
                    for r in pool.completed[nc:]:
                        tr = tracks[r.req_id]
                        if tr.admitted is None and r.fail_reason is None:
                            tr.admitted = t  # admitted and finished in one step
                            prefill_tokens += tr.plen
                            kv_tokens += tr.plen + 1
                        tr.output = list(r.output or [])
                        tr.fail = r.fail_reason
                        tr.times.extend([t] * (len(tr.output) - len(tr.times)))
                    nc = len(pool.completed)
        end = clock()
        return self._summarise(tracks, late, end, steps, rows, kv_tokens,
                               prefill_tokens)

    def _summarise(self, tracks, late, end, steps, rows, kv_tokens,
                   prefill_tokens) -> dict:
        ttft, itl, delivered, waits = [], [], 0, []
        for tr in tracks.values():
            first = tr.times[0] if tr.times else None
            ttft.append((first if first is not None else end) - tr.due)
            itl.extend(np.diff(tr.times).tolist())
            delivered += len(tr.times)
            if tr.admitted is not None:
                waits.append(tr.admitted - tr.due)
        failed = sum(1 for tr in tracks.values() if tr.fail is not None)
        return {
            "tracks": tracks, "window_s": end, "attempted": len(tracks),
            "failed": failed, "ttft_s": ttft, "itl_s": itl,
            "tokens": delivered, "queue_wait_s": waits, "late_s": late,
            "decode_steps": steps, "decode_rows": rows, "kv_tokens": kv_tokens,
            "prefill_tokens": prefill_tokens,
            "finished": sum(1 for tr in tracks.values() if tr.output is not None),
        }

    # -- after the window -----------------------------------------------------
    def free(self) -> None:
        self.pool = None
        self.model = None
        gc.collect()


def end_to_end(w: dict) -> dict:
    return {
        "ttft_p95_ms": percentile(w["ttft_s"], 95) * 1e3,
        "itl_p95_ms": percentile(w["itl_s"], 95) * 1e3,
        "output_tokens_per_s": w["tokens"] / w["window_s"],
    }


def sample(w: dict, mix: dict, seed: int) -> List[Track]:
    """Finished requests for the check, drawn by the seed: the one with
    the most served tokens, then others until the mix's token count."""
    done = [tr for tr in w["tracks"].values()
            if tr.output is not None and tr.fail is None]
    if not done:
        return []
    done.sort(key=lambda tr: (-len(tr.output), tr.due))
    rng = np.random.default_rng(seed)
    rest = [done[j] for j in rng.permutation(np.arange(1, len(done)))]
    picked, n = [done[0]], len(done[0].output)
    for tr in rest:
        if n >= mix["check_tokens"] or len(picked) >= mix["check_requests"]:
            break
        picked.append(tr)
        n += len(tr.output)
    return picked


def check(w: dict, picked: List[Track], c: dict, seed: int, control=False) -> dict:
    """The numbers that decide ``correct``, each with its limit."""
    s = M.sizes(c["config"])
    dtype = jnp.dtype(c["config"]["serving"]["dtype"])
    lim = c["limits"]
    done = [tr for tr in w["tracks"].values() if tr.output is not None]
    miscount = sum(1 for tr in done if tr.fail is None and len(tr.output) != tr.max_new)
    gaps = []
    for j in range(0, len(picked), 8):
        part = picked[j:j + 8]
        gaps += reference.served_gaps(
            seed, s, [np.concatenate([tr.prompt, tr.output]) for tr in part],
            [tr.plen for tr in part], dtype, control=control)
    served = [np.concatenate([tr.prompt[-1:], tr.output]) for tr in picked]
    log(phase="check_sample", requests=len(picked), gaps=gaps,
        repeat_share=float(np.mean(np.concatenate([q[1:] == q[:-1] for q in served])))
        if served else None)
    return {
        "served_logit_gap": {"value": max(gaps) if gaps else None,
                             "limit": lim["served_logit_gap"],
                             "tokens": int(sum(len(tr.output) for tr in picked))},
        "token_count_errors": {"value": miscount, "limit": 0},
        "failed_requests": {"value": w["failed"], "limit": 0},
        "finished_requests": {"value": len(done), "limit": 1, "at_least": True},
    }


def counters(w: dict, c: dict) -> dict:
    """What the per-layer readers take from a serving window."""
    s = M.sizes(c["config"])
    return {
        "queue_wait_s": w["queue_wait_s"],
        "decode_steps": w["decode_steps"],
        "decode_rows": w["decode_rows"],
        "kv_tokens": w["kv_tokens"],
        "prefill_tokens": w["prefill_tokens"],
        "decode_flops": costs.decode_flops(s, w["decode_rows"], w["kv_tokens"]),
        "attn_flops": costs.paged_attention_flops(s, w["kv_tokens"]) * s["layers"],
        "attn_bytes": costs.paged_attention_bytes(
            s, w["kv_tokens"], w["decode_rows"], 2) * s["layers"],
    }
