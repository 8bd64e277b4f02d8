"""A second architecture added to a copy of the benchmark as new files
only: the program's Command R+ smoke preset (GQA 6 over 2, a parallel
attention+FFN block, tied), with its ``bench/archs`` module, its
configuration, a traffic file and a limits file from ``second_arch/``,
and its entries appended to the manifest.  One serving window, its check
and its counters then run through the drivers as they are, on the CPU.

The limit (``second_arch/limits``, 0.08) was set from readings at this
size on the CPU, 2-s windows: the program's gap 0.0019-0.0262 on 8
seeds; the float8 control 0.180-0.336 and layer 0's ``attn.wo`` negated
in the reference 5.02-5.61, on 4 of them."""

import json
import shutil
import time
from pathlib import Path

import jax.numpy as jnp
import pytest

import smoke  # noqa: F401  (puts bench/ and src/ on the path)
import common
import run
import serve
import traffic

NEW = Path(__file__).parent / "second_arch"
ADD = json.loads((NEW / "manifest.json").read_text())
CELL = ADD["workloads"][0]["name"]
SEED = 2**31 + 41
SECONDS = 2.0


def new_files():
    """The files the second architecture adds, relative to the checkout."""
    return sorted(Path("bench") / f.relative_to(NEW) for f in NEW.rglob("*")
                  if f.is_file() and f.parent != NEW and "__pycache__" not in f.parts)


def add_architecture(root: Path) -> None:
    """A copy of the benchmark (``BENCHMARK.json`` and ``bench/`` but its
    tests) under ``root``, with the new files added and the manifest's
    entries appended: the cell reports what the chat cell reports."""
    shutil.copytree(common.BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    for rel in new_files():
        dst = root / rel
        assert not dst.exists(), rel
        shutil.copy(NEW / rel.relative_to("bench"), dst)
    man = common.manifest()
    man["configs"] += ADD["configs"]
    man["workloads"] += ADD["workloads"]
    for m in man["end_to_end"] + man["per_layer"]:
        if ADD["metrics_like"] in m.get("workloads", []):
            m["workloads"].append(CELL)
    (root / "BENCHMARK.json").write_text(json.dumps(man, indent=2))


def use(root: Path, monkeypatch) -> dict:
    monkeypatch.setattr(common, "ROOT", root)
    monkeypatch.setattr(common, "BENCH", root / "bench")
    return common.cell(CELL)


@pytest.fixture(scope="module")
def trees(tmp_path_factory):
    """Two such copies: the second gets the planted fault."""
    roots = [tmp_path_factory.mktemp(n) for n in ("tree", "fault")]
    for r in roots:
        add_architecture(r)
    return roots


def test_only_new_files_and_manifest_entries_are_added(trees):
    root, tree = trees[0], common.ROOT
    added = set(new_files())
    copied = {p.relative_to(root) for p in root.rglob("*")
              if p.is_file() and "__pycache__" not in p.parts}
    mine = {p.relative_to(tree) for p in common.BENCH.rglob("*")
            if p.is_file() and "__pycache__" not in p.parts and "tests" not in p.parts}
    assert added and not added & mine
    assert copied == mine | added | {Path("BENCHMARK.json")}
    for rel in mine:
        assert (root / rel).read_bytes() == (tree / rel).read_bytes(), rel
    man, was = json.loads((root / "BENCHMARK.json").read_text()), common.manifest()
    for key in ("configs", "workloads"):
        assert man[key] == was[key] + ADD[key]
    for key in ("end_to_end", "per_layer"):
        for m in man[key]:
            if CELL in m.get("workloads", []):
                m["workloads"].remove(CELL)
        assert man[key] == was[key]
    assert {k: v for k, v in man.items() if k not in ("configs", "workloads", "end_to_end",
                                                      "per_layer")} == \
        {k: v for k, v in was.items() if k not in ("configs", "workloads", "end_to_end",
                                                   "per_layer")}


def test_serving_window_check_and_counters(trees, monkeypatch):
    c = use(trees[0], monkeypatch)
    A = common.arch(c["config"])
    assert Path(A.__file__).is_relative_to(trees[0])
    cell = serve.ServeCell(c, SEED, origin=time.perf_counter())
    w = cell.window(traffic.requests(c["traffic"], SEED, SECONDS, cell.s["vocab"]), SECONDS)
    cell.free()
    picked = serve.sample(w, c["traffic"], SEED)
    checks = serve.check(w, picked, c, SEED)
    assert run.passes(checks), checks
    assert checks["served_logit_gap"]["tokens"] >= 60, checks

    s = A.sizes(c["config"])
    counters = serve.counters(w, c)
    rows, kv = w["decode_rows"], w["kv_tokens"]
    assert rows > 0 and kv > rows
    assert counters["attn_flops"] == 4.0 * 6 * 16 * kv * 2
    assert counters["attn_bytes"] == (2.0 * kv * 2 * 16 + 2.0 * rows * 6 * 16) * 2 * 2
    assert counters["decode_flops"] == A.decode_flops(s, rows, kv) > counters["attn_flops"]

    # the fault: one weight of the reference altered, in the other copy,
    # whose module is loaded (and its reference compiled) apart
    cf = use(trees[1], monkeypatch)
    F = common.arch(cf["config"])
    assert F is not A
    layer = F.layer

    def altered(key, index, s, dtype):
        p = layer(key, index, s, dtype)
        p["attn.wo"] = jnp.where(index == 0, -p["attn.wo"], p["attn.wo"])
        return p

    monkeypatch.setattr(F, "layer", altered)
    bad = serve.check(w, picked, cf, SEED)
    assert not run.passes(bad), bad
    assert bad["served_logit_gap"]["value"] > bad["served_logit_gap"]["limit"]
