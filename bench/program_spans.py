"""The program's own spans in a profiler trace, beside the device's work.

The serving engine marks its hot path with ``jax.profiler.TraceAnnotation``
spans named ``engine.*`` and ``compile.*`` (``repro/serving/spans.py``).
They land on the host plane of the ``.xplane.pb``, on the clock of the
device operations that ``bench/trace.py`` reads; ``bench.trace.read`` keeps
only the benchmark's ``bench.*`` spans, and :func:`read` here the program's.
The spans come from one thread and nest by time: a span's children are
the spans that lie inside it.

Run as a script, it measures one cell's traced window and prints the split
of its calls (:func:`split`) as one JSON line::

    python3 bench/program_spans.py --workload flavor_lstm.single --seed <n>
    python3 bench/program_spans.py --workload quickdraw_lstm.backlog \
        --seed <n> --root engine.predict

No metric of ``BENCHMARK.json`` reads these spans yet: the benchmark's
runner hands its readers ``bench.trace.read``'s ``Trace`` alone.
"""

from __future__ import annotations

import bisect
import glob
import os
import sys
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Tuple

if __name__ == "__main__":
    _ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path[:0] = [os.path.join(_ROOT, "src"), _ROOT]

from bench import trace as tr  # noqa: E402
from bench.trace import Interval  # noqa: E402

PREFIXES = ("engine.", "compile.")
#: what the host was doing when no program span was open
OUTSIDE = "host.outside_program_spans"
ROOT = "engine.predict_one"
STAGES = ("engine.put", "engine.dispatch", "engine.fetch")


def read(log_dir: str) -> List[Interval]:
    """The program's spans in the newest trace under ``log_dir``, sorted."""
    from jax.profiler import ProfileData

    files = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    out: List[Interval] = []
    for plane in ProfileData.from_file(files[-1]).planes:
        if plane.name.startswith("/host"):
            for line in plane.lines:
                out.extend((e.start_ns * 1e-9, e.end_ns * 1e-9, e.name)
                           for e in line.events
                           if e.name.startswith(PREFIXES))
    return sorted(out)


def _inside(spans: Sequence[Interval], starts: Sequence[float], i: int
            ) -> List[Interval]:
    """The spans other than ``spans[i]`` that lie inside it."""
    s, e, _ = spans[i]
    return [spans[k] for k in range(bisect.bisect_left(starts, s),
                                    bisect.bisect_right(starts, e))
            if k != i and spans[k][1] <= e]


def _covered(spans: Sequence[Interval]) -> float:
    return sum(b - a for a, b in tr.union(spans))


def self_seconds(spans: Sequence[Interval], name: str) -> List[float]:
    """For each span called ``name`` (``spans`` sorted): its length minus
    the part of it that its child spans cover."""
    starts = [s for s, _, _ in spans]
    return [(e - s) - _covered(_inside(spans, starts, i))
            for i, (s, e, n) in enumerate(spans) if n == name]


def innermost(spans: Sequence[Interval]) -> List[Interval]:
    """The time the spans cover, cut into disjoint pieces, each named after
    the deepest span open over it.  A span that outlasts its parent is cut
    at the parent's end."""
    out: List[Interval] = []
    stack: List[Tuple[float, str]] = []       # (end, name) of open spans
    t = 0.0

    def piece(a, b, name):
        if b > a:
            out.append((a, b, name))
    for s, e, n in sorted(spans, key=lambda sp: (sp[0], -sp[1])):
        while stack and stack[-1][0] <= s:
            end, name = stack.pop()
            piece(t, end, name)
            t = end
        if stack:
            piece(t, s, stack[-1][1])
            e = min(e, stack[-1][0])
        stack.append((e, n))
        t = s
    while stack:
        end, name = stack.pop()
        piece(t, end, name)
        t = end
    return out


def idle_by_innermost_span(gap_list: Sequence[Tuple[float, float]],
                           spans: Sequence[Interval]) -> Dict[str, float]:
    """Idle seconds per program span: each instant of each gap given to the
    deepest span open over it, the rest to ``OUTSIDE``."""
    pieces = innermost(spans)
    ends = [e for _, e, _ in pieces]
    out: Dict[str, float] = {}
    for s, e in gap_list:
        covered = 0.0
        for a, b, n in pieces[bisect.bisect_right(ends, s):]:
            if a >= e:
                break
            overlap = min(b, e) - max(a, s)
            if overlap > 0:
                out[n] = out.get(n, 0.0) + overlap
                covered += overlap
        if e - s > covered:
            out[OUTSIDE] = out.get(OUTSIDE, 0.0) + (e - s - covered)
    return out


@dataclass
class Call:
    """One program call: a root span, the seconds spent in each span
    inside it by name, and the device operations that started inside it."""

    start: float
    end: float
    stages: Dict[str, float]
    self_s: float
    dispatch_start: Optional[float]      # of the first engine.dispatch
    fetch_end: Optional[float]           # of the last engine.fetch
    first_op_start: Optional[float]      # None: no device op in the call
    last_op_end: Optional[float]


def calls(spans: Sequence[Interval], ops: Sequence[Interval],
          window: Tuple[float, float], root: str = ROOT) -> List[Call]:
    """The calls whose ``root`` span lies inside ``window``; ``spans`` are
    the program's, ``ops`` one device's operations, both sorted."""
    lo, hi = window
    starts = [s for s, _, _ in spans]
    op_starts = [s for s, _, _ in ops]
    out: List[Call] = []
    for i, (s, e, n) in enumerate(spans):
        if n != root or s < lo or e > hi:
            continue
        inner = _inside(spans, starts, i)
        stages: Dict[str, float] = {}
        for a, b, m in inner:
            stages[m] = stages.get(m, 0.0) + (b - a)
        dispatch = [a for a, _, m in inner if m == "engine.dispatch"]
        fetch = [b for _, b, m in inner if m == "engine.fetch"]
        mine = ops[bisect.bisect_left(op_starts, s):
                   bisect.bisect_right(op_starts, e)]
        out.append(Call(
            start=s, end=e, stages=stages,
            self_s=(e - s) - _covered(inner),
            dispatch_start=min(dispatch) if dispatch else None,
            fetch_end=max(fetch) if fetch else None,
            first_op_start=mine[0][0] if mine else None,
            last_op_end=max(b for _, b, _ in mine) if mine else None))
    return out


def waits(call_list: Sequence[Call]) -> List[Tuple[float, float]]:
    """For each call with a device operation: the launch wait, from the
    start of ``engine.dispatch`` to the start of the first operation, and
    the answer wait, from the end of the last operation to the end of
    ``engine.fetch``.  A negative wait is impossible in time, so it
    measures how far the device's clock is off the host's."""
    return [(c.first_op_start - c.dispatch_start, c.fetch_end - c.last_op_end)
            for c in call_list if c.first_op_start is not None
            and c.dispatch_start is not None and c.fetch_end is not None]


def mean_us(values: Sequence[float]) -> Optional[float]:
    """Mean of seconds in us; None when there is nothing to average."""
    return 1e6 * sum(values) / len(values) if values else None


def split(trace: tr.Trace, spans: Sequence[Interval], device: int,
          root: str = ROOT) -> Dict[str, object]:
    """The ``root`` calls of the traced window, in us: the mean of each
    stage, of the root's self time and of the two waits (None where no
    call has what they read), the mean ``bench.call`` around them, the
    share of calls whose device operations lie between the start of
    ``engine.dispatch`` and the end of ``engine.fetch``, and the device's
    idle seconds by the innermost program span."""
    lo, hi = trace.window
    got = calls(spans, trace.ops.get(device, []), trace.window, root)
    w = waits(got)
    inside = sum(a >= 0 and b >= 0 for a, b in w)
    out: Dict[str, object] = {"calls": len(got)}
    for name in STAGES:
        out[name.split(".")[1] + "_us"] = mean_us(
            [c.stages.get(name, 0.0) for c in got])
    out.update(
        self_us=mean_us([c.self_s for c in got]),
        bench_call_us=mean_us([e - s for s, e, n in trace.spans
                               if n == "bench.call" and lo <= s
                               and e <= hi]),
        launch_wait_us=mean_us([a for a, _ in w]),
        answer_wait_us=mean_us([b for _, b in w]),
        in_order_share=100.0 * inside / len(w) if w else None,
        idle_by_innermost_span=tr.top(idle_by_innermost_span(
            tr.gaps(trace.merged(device), lo, hi), spans)))
    return out


def main(argv=None) -> int:
    """Set up one cell as ``bench/run.py`` does, measure an untraced
    window, then a traced one, and print its :func:`split`."""
    import argparse
    import json
    import shutil

    from bench import drive, run, spec, traffic

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", default="flavor_lstm.single")
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=run.TRACE_SECONDS)
    ap.add_argument("--root", default=ROOT,
                    help="the span of one call (engine.predict for the "
                    "predict entry point)")
    args = ap.parse_args(argv)
    cell = spec.resolve(spec.load_benchmark(run.ROOT), args.workload,
                        run.ROOT)

    import jax

    devices = jax.devices()[:cell.chips]
    jax.config.update("jax_compilation_cache_dir",
                      os.path.join(run.CACHE_DIR, "jax"))
    config, family = cell.config, cell.family
    words = traffic.seed_words(args.seed)
    log_dir = os.path.join(run.TRACE_DIR, cell.name + ".program")
    with jax.default_matmul_precision(config["matmul_precision"]):
        params = family.make_params(config, int(words[0]), devices[0])
        x = family.make_inputs(config, cell.mix, int(words[1]))
        driver = family.DRIVERS[cell.mix["entry"]](
            cell, params, x, words, devices, os.path.join(
                run.CACHE_DIR, f"engine-{config['matmul_precision']}"))
        driver.warm()
        driver.window(args.seconds, drive.Spans(False))
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        shutil.rmtree(log_dir, ignore_errors=True)
        jax.profiler.start_trace(log_dir, profiler_options=opts)
        try:
            driver.window(args.seconds, drive.Spans(True))
        finally:
            jax.profiler.stop_trace()
    out = split(tr.read(log_dir), read(log_dir), devices[0].id, args.root)
    shutil.rmtree(log_dir, ignore_errors=True)
    print(json.dumps({"workload": cell.name, "seed": args.seed, **out}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
