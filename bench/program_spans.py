"""The program's own spans in a traced window, and the device's idle time
under them.

The program marks each phase of the serving tick and the training step
with a ``TraceAnnotation`` (``serve.*``, ``train.*``; see
``repro.telemetry.profile``).  They land on the host plane of the same
``.xplane.pb`` as the device planes, so they share the device's clock.
``traces.reduce`` keeps only the harness's spans; this module reads the
program's from the trace whose ``bench.window`` is the window that
``ctx["trace"]`` was reduced from.  Where the program has no such spans
(a program older than them), every function here returns None, and the
readers with it.
"""

from __future__ import annotations

import glob
import os
from bisect import bisect_left
from dataclasses import dataclass
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

import traces as T

PREFIXES = ("serve.", "train.")
TRACES = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                      ".bench_traces")


@dataclass(frozen=True)
class Span:
    name: str
    start: float  # ns, the trace's clock
    end: float
    stats: Dict

    @property
    def ms(self) -> float:
        return (self.end - self.start) * 1e-6


def _read(path: str) -> Tuple[Optional[T.Interval], List[Span]]:
    """A trace file's ``bench.window`` and its program spans, in start
    order."""
    from jax.profiler import ProfileData

    window, found = None, []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name == T.WINDOW:
                    window = (ev.start_ns, ev.start_ns + ev.duration_ns)
                elif ev.name.startswith(PREFIXES):
                    found.append(Span(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns,
                                      dict(ev.stats)))
    return window, sorted(found, key=lambda s: (s.start, -s.end))


def _find(want: T.Interval, trace_dir: str) -> Optional[List[Span]]:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True),
                   key=os.path.getmtime, reverse=True)
    for path in paths:
        window, found = _read(path)
        if window == want:
            lo, hi = want
            return [s for s in found if s.start >= lo and s.end <= hi] or None
    return None


def spans(ctx: dict, trace_dir: Optional[str] = None) -> Optional[List[Span]]:
    """The program's spans inside the traced window of ``ctx``, in start
    order: from the ``.xplane.pb`` under ``trace_dir`` (default
    ``.bench_traces`` at the checkout's root) whose ``bench.window``
    equals ``ctx["trace"].window``.  None where no trace has that window
    or it holds no program span.  The file is read once per ``ctx``:
    every reader asks."""
    memo = ctx.setdefault("program_spans", {})
    key = ("spans", trace_dir or TRACES)
    if key not in memo:
        memo[key] = _find(ctx["trace"].window, key[1])
    return memo[key]


def named(ctx: dict, *names: str, trace_dir: Optional[str] = None) -> Optional[List[Span]]:
    """The window's spans with one of ``names``; None where there are none."""
    found = [s for s in spans(ctx, trace_dir) or () if s.name in names]
    return found or None


def within(outer: Sequence[Span], inner: Sequence[Span]) -> List[List[Span]]:
    """For each span of ``outer``, the spans of ``inner`` that it holds."""
    inner = sorted(inner, key=lambda s: s.start)
    starts = [s.start for s in inner]
    out = []
    for o in outer:
        i = bisect_left(starts, o.start)
        held = []
        while i < len(inner) and inner[i].start <= o.end:
            if inner[i].end <= o.end:
                held.append(inner[i])
            i += 1
        out.append(held)
    return out


def idle_s(ctx: dict, intervals: Iterable[T.Interval]) -> float:
    """Seconds of the window in which the first chip ran no operation
    and the host was inside one of ``intervals``."""
    tr = ctx["trace"]
    memo = ctx.setdefault("program_spans", {})
    if "busy" not in memo:
        memo["busy"] = tr.busy(tr.devices[0])
    busy = memo["busy"]
    held = T.merge(T.clip(list(intervals), *tr.window))
    # idle under the intervals = |held| - |held and busy|
    #                           = |held or busy| - |busy|
    return (T.total(T.merge(held + busy)) - T.total(busy)) * 1e-9


def idle_share(ctx: dict, inside: Sequence[Span], outside: Sequence[Span] = ()) -> float:
    """Share (%) of the window in which the first chip ran no operation
    and the host was inside a span of ``inside`` but in none of
    ``outside``."""
    iv = lambda found: [(s.start, s.end) for s in found]  # noqa: E731
    out = idle_s(ctx, iv(outside))
    return (idle_s(ctx, iv(inside) + iv(outside)) - out) / ctx["trace"].window_s * 100.0
