"""99th percentile over the window's events of the time from an event's due
time to the start of the flush that served it (host clock, around the
engine's ``submit`` and ``flush``)."""

from bench.check import pct


def read(run):
    w = run.record.queue_wait_s
    return None if w is None or not len(w) else 1e6 * pct(w, 99)
