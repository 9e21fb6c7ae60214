"""Seconds from process start to the start of the measured window: imports,
TPU start-up, weights, event pool, engines and warm-up (host clock)."""


def read(run):
    return run.setup_s
