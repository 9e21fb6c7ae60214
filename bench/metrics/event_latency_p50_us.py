"""Median per-event latency over every answered event of the window, from
due (open loop) or issue (closed loop) to the answer on the host (host
clock)."""

from bench.check import pct


def read(run):
    lat = run.record.latency_s
    return None if lat is None or not len(lat) else 1e6 * pct(lat, 50)
