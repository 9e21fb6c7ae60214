"""99th percentile of per-event latency over every answered event of the
window (host clock)."""

from bench.check import pct


def read(run):
    lat = run.record.latency_s
    return None if lat is None or not len(lat) else 1e6 * pct(lat, 99)
