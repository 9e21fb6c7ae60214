"""Mean host time per served call (a ``flush``, or one ``predict_one``)
that the device was not busy: the call's span minus the device's busy
time inside it, from the profiler trace."""

from bench import trace as tr

SPANS = ("bench.flush", "bench.call")


def read(run):
    if run.trace is None:
        return None
    merged = run.trace.merged(run.device_ids[0])
    for name in SPANS:
        self_s = tr.span_self_seconds(run.trace.spans, name, merged)
        if self_s:
            return 1e6 * sum(self_s) / len(self_s)
    return None
