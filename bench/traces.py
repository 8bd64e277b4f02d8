"""From a profiler trace to numbers: device busy time, per-program and
per-operation time, collective time and the device's idle gaps, each
gap labelled by the harness's host span in force at that moment.

``capture`` records a window with ``jax.profiler``; ``reduce`` reads the
newest ``.xplane.pb`` under a directory.  A TPU trace has one plane per
chip (``/device:TPU:<n>``) with an ``XLA Ops`` line, one event per
operation, and an ``XLA Modules`` line, one event per program run.  The
harness's spans (``TraceAnnotation``) are on the host plane.
"""

from __future__ import annotations

import contextlib
import glob
import os
import re
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

# Names the harness gives its host spans.  The window span bounds the
# traced window; the others label idle gaps.
WINDOW = "bench.window"
SPANS = ("bench.submit", "bench.pool_step", "bench.wait_for_arrival",
         "bench.job_step", "bench.observe")
COLLECTIVE = re.compile(r"all-reduce|all-gather|reduce-scatter|all-to-all|"
                        r"collective-permute", re.IGNORECASE)

Interval = Tuple[float, float]


@contextlib.contextmanager
def capture(log_dir: str):
    import jax

    os.makedirs(log_dir, exist_ok=True)
    jax.profiler.start_trace(log_dir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def merge(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


def total(intervals: Sequence[Interval]) -> float:
    return sum(e - s for s, e in intervals)


def stable_name(name: str) -> str:
    """An op's name without XLA's instance number and copy suffixes
    (``fusion.12.remat3`` -> ``fusion``), so the same op groups across
    runs."""
    return re.sub(r"(\.(\d+|remat\d*|clone))+$", "", name)


def op_kind(name: str) -> str:
    """The instruction of an ``XLA Ops`` event, whose name is the HLO
    text (``%fusion.3 = f32[8]{0} fusion(...)`` -> ``fusion.3``)."""
    return name.partition(" = ")[0].lstrip("%")


def op_label(name: str) -> str:
    """An op's instruction kind and result type, without layouts:
    ``fusion f32[2,1024,122753]``."""
    head, _, rest = name.partition(" = ")
    rest = re.sub(r"\{[^}]*\}", "", rest)
    ty = rest[:rest.find(")") + 1] if rest.startswith("(") else rest.split(" ")[0]
    return f"{stable_name(head.lstrip('%'))} {ty}".strip()[:160]


# Ops that run other ops (a scan's loop): counted in busy time, not as ops.
NESTING = ("while", "conditional", "call")


@dataclass
class Device:
    ops: List[Tuple[str, float, float]] = field(default_factory=list)
    modules: List[Tuple[str, float, float]] = field(default_factory=list)


@dataclass
class Summary:
    """A traced window reduced to what the metric readers need.  Times in
    nanoseconds inside, seconds out."""

    window: Interval
    devices: List[Device]
    spans: List[Tuple[str, float, float]]

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    def busy(self, dev: Device) -> List[Interval]:
        return clip(merge((s, s + d) for _, s, d in dev.ops), *self.window)

    @property
    def busy_s(self) -> float:
        """Seconds in which some operation ran, averaged over the chips."""
        return sum(total(self.busy(d)) for d in self.devices) * 1e-9 / len(self.devices)

    def _in_window(self, events):
        lo, hi = self.window
        return [(n, s, d) for n, s, d in events if s >= lo and s + d <= hi]

    def modules(self, pattern: str) -> Tuple[int, float]:
        """Runs of programs whose name matches ``pattern`` and their
        seconds, per chip (averaged over the chips)."""
        rx = re.compile(pattern)
        n = t = 0.0
        for dev in self.devices:
            ev = [(s, d) for name, s, d in self._in_window(dev.modules)
                  if rx.search(name)]
            n += len(ev)
            t += sum(d for _, d in ev)
        k = len(self.devices)
        return n / k, t * 1e-9 / k

    def ops(self, pattern: str) -> Tuple[int, float]:
        """Operations whose name matches ``pattern``: count and seconds per
        chip (averaged over the chips)."""
        rx = re.compile(pattern)
        n = t = 0.0
        for dev in self.devices:
            ev = [d for name, s, d in self._in_window(dev.ops) if rx.search(name)]
            n += len(ev)
            t += sum(ev)
        k = len(self.devices)
        return n / k, t * 1e-9 / k

    def collectives(self) -> Tuple[float, float]:
        """Seconds of collective operations per chip, and the part of them
        during which no other operation ran on that chip."""
        t = exposed = 0.0
        for dev in self.devices:
            ev = self._in_window(dev.ops)
            is_coll = [bool(COLLECTIVE.search(op_kind(n))) for n, _, _ in ev]
            coll = merge((s, s + d) for (n, s, d), c in zip(ev, is_coll) if c)
            other = merge((s, s + d) for (n, s, d), c in zip(ev, is_coll)
                          if not c and stable_name(op_kind(n)) not in NESTING)
            t += total(coll)
            exposed += total(coll) - _overlap(coll, other)
        k = len(self.devices)
        return t * 1e-9 / k, exposed * 1e-9 / k

    def top_ops(self, n: int = 10) -> List[List]:
        acc: Dict[str, float] = defaultdict(float)
        for dev in self.devices:
            for name, s, d in self._in_window(dev.ops):
                if stable_name(op_kind(name)) in NESTING:
                    continue
                acc[op_label(name)] += d * 1e-9 / len(self.devices)
        return [[k, v] for k, v in sorted(acc.items(), key=lambda kv: -kv[1])[:n]]

    def gaps(self) -> List[Tuple[str, float]]:
        """Every idle gap of the first chip in the window, labelled by the
        innermost harness span that holds its middle."""
        lo, hi = self.window
        busy = self.busy(self.devices[0])
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        spans = sorted(((e - s, n, s, e) for n, s, e in self.spans))
        out = []
        for s, e in zip(edges[0::2], edges[1::2]):
            if e <= s:
                continue
            mid = (s + e) / 2
            label = next((n for _, n, a, b in spans if a <= mid <= b), "host")
            out.append((label, (e - s) * 1e-9))
        return out

    def idle_by_span(self, n: int = 10) -> List[List]:
        acc: Dict[str, float] = defaultdict(float)
        for label, sec in self.gaps():
            acc[label] += sec
        return [[k, v] for k, v in sorted(acc.items(), key=lambda kv: -kv[1])[:n]]

    def longest_gaps(self, n: int = 10) -> List[List]:
        return [[k, v] for k, v in sorted(self.gaps(), key=lambda kv: -kv[1])[:n]]


def _overlap(a: Sequence[Interval], b: Sequence[Interval]) -> float:
    i = j = 0
    out = 0.0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if e > s:
            out += e - s
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def newest_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def tpu_lines(plane_name: str, line_name: str) -> Optional[str]:
    """Which device line an event list is: ``ops``, ``modules`` or None."""
    if not re.fullmatch(r"/device:TPU:\d+", plane_name):
        return None
    return {"XLA Ops": "ops", "XLA Modules": "modules"}.get(line_name)


def reduce(log_dir: str, classify=tpu_lines) -> Summary:
    """Reads the newest trace under ``log_dir``.  ``classify(plane, line)``
    says which lines hold device operations and programs; the default
    reads TPU planes."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(newest_xplane(log_dir))
    devices: Dict[str, Device] = {}
    spans: List[Tuple[str, float, float]] = []
    window = None
    for plane in pd.planes:
        for line in plane.lines:
            kind = classify(plane.name, line.name)
            if kind is not None:
                dev = devices.setdefault(plane.name, Device())
                getattr(dev, kind).extend(
                    (ev.name, ev.start_ns, ev.duration_ns) for ev in line.events)
                continue
            if not plane.name.startswith("/host"):
                continue
            for ev in line.events:
                if ev.name == WINDOW:
                    window = (ev.start_ns, ev.start_ns + ev.duration_ns)
                elif ev.name in SPANS:
                    spans.append((ev.name, ev.start_ns, ev.start_ns + ev.duration_ns))
    if window is None:
        raise ValueError(f"the trace under {log_dir} has no {WINDOW!r} span")
    if not devices:
        raise ValueError(f"the trace under {log_dir} has no device operations")
    return Summary(window=window, devices=[devices[k] for k in sorted(devices)],
                   spans=spans)
