"""From a profiler trace to the numbers the per-layer metrics read.

The JAX profiler writes an ``.xplane.pb``.  Its ``/device:TPU:<n>`` planes
carry the device's operations on the line ``XLA Ops``; the host plane
carries the benchmark's own spans, ``jax.profiler.TraceAnnotation`` events
whose names start with ``bench.``.  Both are on one clock.  The span
``bench.window`` marks the measured window.

The functions below work on plain lists of ``(start_s, end_s, name)`` so
that they can be checked on a constructed trace.
"""

from __future__ import annotations

import bisect
import glob
import os
import re
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"
#: what the host was doing when no benchmark span was open
OUTSIDE_SPANS = "host.outside_bench_spans"

Interval = Tuple[float, float, str]


@dataclass
class Trace:
    ops: Dict[int, List[Interval]]   # device id -> its operations, sorted
    spans: List[Interval]            # the benchmark's host spans, sorted
    window: Tuple[float, float]      # the measured window

    def merged(self, device: int) -> List[Tuple[float, float]]:
        return union(self.ops.get(device, []))


def read(log_dir: str) -> Trace:
    """The newest trace under ``log_dir``, reduced to ops and spans."""
    from jax.profiler import ProfileData

    files = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    data = ProfileData.from_file(files[-1])
    ops: Dict[int, List[Interval]] = {}
    spans: List[Interval] = []
    for plane in data.planes:
        dev = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if dev is not None and line.name == OPS_LINE:
                ops.setdefault(int(dev.group(1)), []).extend(
                    (e.start_ns * 1e-9, e.end_ns * 1e-9, e.name)
                    for e in line.events)
            elif dev is None and plane.name.startswith("/host"):
                spans.extend((e.start_ns * 1e-9, e.end_ns * 1e-9, e.name)
                             for e in line.events
                             if e.name.startswith(SPAN_PREFIX))
    windows = [s for s in spans if s[2] == WINDOW_SPAN]
    if not windows:
        raise ValueError(f"the trace holds no {WINDOW_SPAN} span")
    for v in ops.values():
        v.sort()
    spans.sort()
    return Trace(ops=ops, spans=spans,
                 window=(windows[-1][0], windows[-1][1]))


def union(intervals: Sequence[Tuple]) -> List[Tuple[float, float]]:
    """Overlapping or touching intervals merged, sorted by start."""
    out: List[List[float]] = []
    for s, e, *_ in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


class Busy:
    """Seconds of disjoint sorted intervals inside any ``[lo, hi]``, by
    bisection and a running sum."""

    def __init__(self, merged: Sequence[Tuple[float, float]]):
        self.starts = [s for s, _ in merged]
        self.ends = [e for _, e in merged]
        self.cum = [0.0]
        for s, e in merged:
            self.cum.append(self.cum[-1] + (e - s))

    def between(self, lo: float, hi: float) -> float:
        i = bisect.bisect_right(self.ends, lo)      # first ending after lo
        j = bisect.bisect_left(self.starts, hi)     # first starting at hi
        if j <= i:
            return 0.0
        total = self.cum[j] - self.cum[i]
        total -= max(0.0, lo - self.starts[i])
        total -= max(0.0, self.ends[j - 1] - hi)
        return total


def busy(merged: Sequence[Tuple[float, float]], lo: float,
         hi: float) -> float:
    """Seconds of ``merged`` (disjoint intervals) inside ``[lo, hi]``."""
    return Busy(merged).between(lo, hi)


def gaps(merged: Sequence[Tuple[float, float]], lo: float,
         hi: float) -> List[Tuple[float, float]]:
    """The idle intervals of ``[lo, hi]`` between ``merged`` intervals."""
    out, t = [], lo
    for s, e in merged:
        if e <= lo:
            continue
        if s >= hi:
            break
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if t < hi:
        out.append((t, hi))
    return out


def idle_by_span(gap_list: Sequence[Tuple[float, float]],
                 spans: Sequence[Interval]) -> Dict[str, float]:
    """Idle seconds per host span: each gap split among the spans (other
    than the window) that overlap it, the rest given to
    ``OUTSIDE_SPANS``.  The benchmark's spans do not nest except inside
    the window."""
    inner = [sp for sp in spans if sp[2] != WINDOW_SPAN]
    starts = [s for s, _, _ in inner]
    out: Dict[str, float] = {}
    for s, e in gap_list:
        covered = 0.0
        i = max(0, bisect.bisect_right(starts, s) - 1)
        for a, b, n in inner[i:bisect.bisect_left(starts, e)]:
            overlap = min(b, e) - max(a, s)
            if overlap > 0:
                out[n] = out.get(n, 0.0) + overlap
                covered += overlap
        if e - s > covered:
            out[OUTSIDE_SPANS] = out.get(OUTSIDE_SPANS, 0.0) + (e - s - covered)
    return out


def op_label(hlo_text: str) -> str:
    """``%name = type{layout} kind(args...)`` -> ``name kind type``."""
    parts = hlo_text.split(" ")
    if len(parts) < 4 or parts[1] != "=":
        return hlo_text[:80]
    return (f"{parts[0].lstrip('%')} {parts[3].split('(')[0]} "
            f"{parts[2].split('{')[0]}")


def op_seconds(ops: Sequence[Interval], lo: float, hi: float,
               pattern: Optional[str] = None) -> Dict[str, float]:
    """Device seconds per operation label inside ``[lo, hi]``, of the
    operations whose HLO text matches ``pattern`` (all when None)."""
    rx = re.compile(pattern) if pattern is not None else None
    out: Dict[str, float] = {}
    for s, e, name in ops:
        if e <= lo or s >= hi or (rx is not None and not rx.search(name)):
            continue
        label = op_label(name)
        out[label] = out.get(label, 0.0) + min(e, hi) - max(s, lo)
    return out


def span_self_seconds(spans: Sequence[Interval], name: str,
                      merged: Sequence[Tuple[float, float]]
                      ) -> List[float]:
    """For each span called ``name``: its length minus the device's busy
    time inside it."""
    b = Busy(merged)
    return [(e - s) - b.between(s, e) for s, e, n in spans if n == name]


def top(d: Dict[str, float], k: int = 10) -> List[List]:
    return [[n, v] for n, v in sorted(d.items(), key=lambda kv: -kv[1])[:k]]
