"""A training cell: the configuration's model, trained by ``TrainingJob``
over a token log, at the data parallelism of the mix.

Set-up writes the token log from the seed, builds the job with the
seeded weights, and runs its first three steps through ``job.run``
(which calls ``job.step``, the window's own call, on the same log).
Those steps compile the train step; the readings for the check are
taken from the job's state after step 1 (the first gradient, from
Adam's first moment) and after step 3 (the change of every weight).
The window then drives ``job.step(now)`` on the wall clock.
"""

from __future__ import annotations

import dataclasses
import gc
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

import common
import reference
import traffic
import weights as W
from common import BenchError

CHECK_STEPS = 3


class TrainCell:
    def __init__(self, c: dict, seed: int, seconds: float):
        from repro.config import TrainingConfig
        from repro.data.topics import MessageLog
        from repro.models.zoo import Model
        from repro.training.job import TrainingJob

        conf, mix = c["config"], c["traffic"]
        t = conf["training"]
        self.arch = common.arch(conf)
        self.c, self.seed, self.s = c, seed, self.arch.sizes(conf)
        self.dp = mix["dp"]
        if len(jax.devices()) < self.dp:
            raise BenchError(f"dp {self.dp} needs {self.dp} devices")
        self.rows = t["rows_per_chip"] * self.dp
        self.seq = t["seq_len"]
        program = self.arch.program_arch(conf)
        s, wseed, bench_arch = self.s, seed, self.arch

        @dataclasses.dataclass(frozen=True)
        class SeededModel(Model):
            """The program's model, initialised from the benchmark's seed."""

            def init(self, rng):
                del rng
                return W.program_params(bench_arch, wseed, s, jnp.dtype(t["param_dtype"]))

        self.model = SeededModel(program, compute_dtype=jnp.dtype(t["compute_dtype"]),
                                 param_dtype=jnp.dtype(t["param_dtype"]))
        self.tcfg = TrainingConfig(
            learning_rate=t["learning_rate"], weight_decay=t["weight_decay"],
            beta1=t["beta1"], beta2=t["beta2"], eps=t["eps"],
            grad_clip_norm=t["grad_clip_norm"], schedule=t["schedule"],
            warmup_steps=t["warmup_steps"], stable_steps=t["stable_steps"],
            decay_steps=t["decay_steps"], param_dtype=t["param_dtype"],
            compute_dtype=t["compute_dtype"],
            optimizer_state_dtype=t["optimizer_state_dtype"])
        # enough rows for the check's steps and a window at the chips' peak
        peak_steps = int(seconds * 40) + CHECK_STEPS + 8
        self.log_rows = traffic.token_rows(mix, seed, peak_steps * self.rows,
                                           self.seq, s["vocab"])
        log = MessageLog()
        log.create_topic("tokens", 1)
        for r in self.log_rows:
            log.publish("tokens", payload=r)
        self.job = TrainingJob(self.model, program, self.tcfg, log,
                               batch_size=self.rows, seq_len=self.seq, dp=self.dp,
                               max_dp=self.dp, use_mesh=True, seed=0)
        self.readings = self._first_steps()

    def _first_steps(self) -> dict:
        job, b1, A = self.job, self.tcfg.beta1, self.arch
        job.run(1)
        grads = jax.jit(lambda mu: {k: v / (1.0 - b1)
                                    for k, v in W.leaf_norms(mu, A.leaf_name).items()})(
            job.state.opt.mu)
        job.run(CHECK_STEPS)
        key, s = W.base_key(self.seed), self.s
        pdt = jnp.dtype(self.c["config"]["training"]["param_dtype"])
        delta = jax.jit(lambda p, k: W.leaf_norms(jax.tree.map(
            jnp.subtract, p, A.program_tree(k, s, pdt)), A.leaf_name))(job.state.params, key)
        offsets = {k: dict(v) for k, v in job.step_offsets.items()}
        want = {k: {0: k * self.rows} for k in range(1, CHECK_STEPS + 1)}
        return {"losses": list(job.losses[:CHECK_STEPS]),
                "grad_norms": {k: float(v) for k, v in grads.items()},
                "delta_norms": {k: float(v) for k, v in delta.items()},
                "offsets_ok": all(offsets.get(k) == v for k, v in want.items())}

    def window(self, seconds: float) -> dict:
        job = self.job
        start = job.applied_step()
        losses0 = len(job.losses)
        t0 = time.perf_counter()
        with TraceAnnotation("bench.window"):
            while time.perf_counter() - t0 < seconds:
                with TraceAnnotation("bench.job_step"):
                    job.step(100.0 + time.perf_counter() - t0)
        end = time.perf_counter() - t0
        steps = job.applied_step() - start
        losses = job.losses[losses0:]
        return {"window_s": end, "steps": steps,
                "tokens": steps * self.rows * self.seq,
                "nonfinite": int(sum(not np.isfinite(x) for x in losses))}

    def batches(self, n: int):
        return [self.log_rows[k * self.rows:(k + 1) * self.rows] for k in range(n)]

    def free(self) -> None:
        self.job = None
        self.model = None
        gc.collect()


def gaps(prog: dict, ref: dict) -> dict:
    """The three compared numbers.  Leaves whose reference gradient is
    under a thousandth of the median leaf's are left out (they move by
    round-off alone); each other leaf's gap between the two norms is
    measured against the leaf's own reference norm, and the worst leaf
    counts."""
    g_ref, d_ref = ref["grad_norms"], ref["delta_norms"]
    g_med = float(np.median(list(g_ref.values())))
    keep = [k for k, v in g_ref.items() if v >= 1e-3 * g_med]
    loss = max(abs(a - b) / abs(b) for a, b in zip(prog["losses"], ref["losses"]))
    rel = lambda p, r: {k: abs(p[k] - r[k]) / r[k] for k in keep}  # noqa: E731
    grad, delta = rel(prog["grad_norms"], g_ref), rel(prog["delta_norms"], d_ref)
    return {"loss_gap": loss, "grad_norm_gap": max(grad.values()),
            "delta_norm_gap": max(delta.values()),
            "worst_leaves": [max(grad, key=grad.get), max(delta, key=delta.get)],
            "left_out": sorted(set(g_ref) - set(keep))}


def check(cell: "TrainCell", w: dict, c: dict, control=False, half=False) -> dict:
    ref = reference.train_steps(cell.arch, cell.seed, cell.s, c["config"]["training"],
                                cell.batches(CHECK_STEPS), lowp=control, half=half)
    g = gaps(cell.readings, ref)
    lim = c["limits"]
    # a number without a limit in the cell's file is reported, not compared
    out = {k: {"value": g[k], "limit": lim[k]}
           for k in ("loss_gap", "grad_norm_gap", "delta_norm_gap") if k in lim}
    out["offsets_ok"] = {"value": int(cell.readings["offsets_ok"]), "limit": 1,
                         "at_least": True}
    out["nonfinite_losses"] = {"value": w["nonfinite"], "limit": 0}
    return out, g, ref
