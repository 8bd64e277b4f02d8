"""The DeepSeek-V2-Lite serving cell through the benchmark's drivers at
the program's smoke preset, on the CPU: one window, its check against
the plain reference, and its counters, on a copy of the benchmark (as
``bench/tests/test_second_arch.py`` adds an architecture to one); then
the same window checked against a reference with one weight negated, in
a second copy whose architecture module is loaded apart, must fail.

The limit (``LIMIT``) was set from readings at this size on the CPU,
2-s windows: the program's served gap 0.0066-0.043 on 8 seeds; the
float8 control 0.69-1.03 and layer 1's ``attn.wo`` negated in the
reference 5.5-6.9, on 4 of them.
"""

import copy
import json
import shutil
import sys
import time
from pathlib import Path

import jax.numpy as jnp
import pytest

BENCH = Path(__file__).resolve().parents[1] / "bench"
for p in (BENCH, BENCH / "drivers"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

import common  # noqa: E402
import run  # noqa: E402
import serve  # noqa: E402
import traffic  # noqa: E402
from test_deepseek import smoke_config  # noqa: E402

CELL = "deepseek-v2-lite-serve.long-context"
SEED = 2**31 + 29
SECONDS = 2.0
LIMIT = 0.15
MIX = {"kind": "serve", "loop": "open, Poisson-like gaps", "rate_per_s": 12.0,
       "prompt_lengths": [8, 16, 24, 40], "prompt_median": 16, "prompt_sigma": 0.6,
       "output_median": 12, "output_sigma": 0.6, "output_min": 4, "output_max": 40,
       "max_total": 127, "check_tokens": 120, "check_requests": 8}


def smoke_cell(root: Path, monkeypatch) -> dict:
    """The cell read from the copy at ``root``, cut to the smoke preset."""
    monkeypatch.setattr(common, "ROOT", root)
    monkeypatch.setattr(common, "BENCH", root / "bench")
    c = copy.deepcopy(common.cell(CELL))
    serving = dict(c["config"]["serving"], slots=4, max_len=128, pages=33)
    c["config"] = dict(smoke_config(), serving=serving)
    c["traffic"], c["limits"] = dict(MIX), {"served_logit_gap": LIMIT}
    return c


@pytest.fixture(scope="module")
def trees(tmp_path_factory):
    roots = [tmp_path_factory.mktemp(n) for n in ("tree", "fault")]
    for r in roots:
        shutil.copytree(common.BENCH, r / "bench",
                        ignore=shutil.ignore_patterns("__pycache__", "tests"))
        shutil.copy(common.ROOT / "BENCHMARK.json", r / "BENCHMARK.json")
    return roots


def test_window_check_and_counters(trees, monkeypatch):
    c = smoke_cell(trees[0], monkeypatch)
    assert json.loads((trees[0] / "BENCHMARK.json").read_text()) == json.loads(
        (BENCH.parent / "BENCHMARK.json").read_text())
    A = common.arch(c["config"])
    assert Path(A.__file__).is_relative_to(trees[0])
    cell = serve.ServeCell(c, SEED, origin=time.perf_counter())
    w = cell.window(traffic.requests(c["traffic"], SEED, SECONDS, cell.s["vocab"]), SECONDS)
    held = cell.pool.replicas[0].held_expert_rows
    cell.free()
    picked = serve.sample(w, c["traffic"], SEED)
    checks = serve.check(w, picked, c, SEED)
    assert run.passes(checks), checks
    assert checks["served_logit_gap"]["tokens"] >= 60, checks

    s = A.sizes(c["config"])
    rows, kv = w["decode_rows"], w["kv_tokens"]
    counters = serve.counters(w, c)
    assert rows > 0 and kv > rows
    # 2 H (576 + 512) per live token and layer at the published widths;
    # here 2 * 4 * (40 + 32), over 3 layers
    assert counters["mla_flops"] == 2.0 * 4 * (40 + 32) * kv * 3
    assert counters["mla_bytes"] == (kv * 40 + rows * 4 * (40 + 32)) * 2 * 3
    assert counters["decode_flops"] == A.decode_flops(s, rows, kv) > counters["mla_flops"]
    # each decoded row makes top_k assignments in each of 2 MoE layers;
    # the held 4 of 8 take some of them
    assert 0 < held < rows * s["top_k"] * 2

    # the fault: layer 1's attention output negated in the reference
    cf = smoke_cell(trees[1], monkeypatch)
    F = common.arch(cf["config"])
    assert F is not A
    layer = F.layer

    def altered(key, index, s, dtype, dense):
        p = layer(key, index, s, dtype, dense)
        p["attn.wo"] = jnp.where(index == 1, -p["attn.wo"], p["attn.wo"])
        return p

    monkeypatch.setattr(F, "layer", altered)
    bad = serve.check(w, picked, cf, SEED)
    assert not run.passes(bad), bad
    assert bad["served_logit_gap"]["value"] > bad["served_logit_gap"]["limit"]
