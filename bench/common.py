"""What every cell shares: the manifest, the chip check, the compile
cache, the compile clock, the peaks table and the metric readers."""

from __future__ import annotations

import importlib.util
import json
import math
import sys
from pathlib import Path
from typing import Callable, List, Optional

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
CACHE_DIR = ROOT / ".jax_cache"


class BenchError(RuntimeError):
    """A cell that cannot run as the manifest describes it."""


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def manifest() -> dict:
    path = ROOT / "BENCHMARK.json"
    if not path.exists():
        raise BenchError(f"no manifest at {path}")
    return load_json(path)


def cell(name: str, man: Optional[dict] = None) -> dict:
    """The workload entry ``name`` with its configuration and traffic
    files read in: ``{"workload", "config", "traffic", "limits"}``."""
    man = man or manifest()
    work = {w["name"]: w for w in man["workloads"]}
    if name not in work:
        raise BenchError(f"no workload {name!r} in BENCHMARK.json")
    w = work[name]
    conf = {c["name"]: c for c in man["configs"]}[w["config"]]
    return {
        "workload": w,
        "config": load_json(ROOT / conf["file"]),
        "traffic": load_json(BENCH / "traffic" / f"{w['traffic']}.json"),
        "limits": load_json(BENCH / "limits" / f"{name}.json"),
        "end_to_end": [m for m in man["end_to_end"]
                       if name in m.get("workloads", [name])],
        "per_layer": [m for m in man["per_layer"]
                      if name in m.get("workloads", [name])],
    }


def devices(chips: int) -> dict:
    """The chips JAX sees, or an error where they are not TPUs or too few."""
    import jax

    devs = jax.devices()
    kind = devs[0].device_kind
    if devs[0].platform != "tpu":
        raise BenchError(f"needs a TPU; JAX found {devs[0].platform!r} ({kind})")
    if len(devs) < chips:
        raise BenchError(f"needs {chips} chips; JAX found {len(devs)} ({kind})")
    return {"platform": devs[0].platform, "kind": kind, "count": chips}


def enable_compile_cache() -> str:
    """JAX's persistent compilation cache, at a fixed path in the checkout,
    for every program however short its compile."""
    import jax

    CACHE_DIR.mkdir(parents=True, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return str(CACHE_DIR)


class CompileClock:
    """Seconds and count of JAX's tracing, lowering and compiling, from its
    own monitoring events."""

    EVENTS = (
        "/jax/core/compile/jaxpr_trace_duration",
        "/jax/core/compile/jaxpr_to_mlir_module_duration",
        "/jax/core/compile/backend_compile_duration",
    )
    BACKEND = "/jax/core/compile/backend_compile_duration"

    def __init__(self) -> None:
        import jax

        self.seconds = 0.0
        self.compiles = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **kwargs) -> None:
        if event in self.EVENTS:
            self.seconds += duration
        if event == self.BACKEND:
            self.compiles += 1


def peaks(kind: str) -> dict:
    table = load_json(BENCH / "peaks.json")["devices"]
    if kind not in table:
        raise BenchError(f"device kind {kind!r} is not in bench/peaks.json")
    return table[kind]


def percentile(values: List[float], q: float) -> float:
    """The q-th percentile (0-100) of all values, linear between ranks."""
    if not values:
        raise BenchError("percentile of no values")
    v = sorted(values)
    k = (len(v) - 1) * q / 100.0
    lo, hi = math.floor(k), math.ceil(k)
    return v[lo] + (v[hi] - v[lo]) * (k - lo)


def reader(metric: str) -> Callable[[dict], Optional[float]]:
    """The reader of a per-layer metric: ``bench/metrics/<name>.py``'s
    ``read(ctx)``, which returns the value or None where it finds
    nothing to read."""
    path = BENCH / "metrics" / f"{metric}.py"
    if not path.exists():
        raise BenchError(f"no reader {path} for per-layer metric {metric!r}")
    spec = importlib.util.spec_from_file_location(f"metric_{metric}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def log(**fields) -> None:
    """One JSON line on standard error (standard output's last line is
    the result)."""
    print(json.dumps(fields), file=sys.stderr, flush=True)
