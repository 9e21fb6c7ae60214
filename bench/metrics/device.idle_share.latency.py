"""Share of the traced window in which no operation ran on the chip:
1 - (union of device op intervals) / window (profiler trace)."""


def read(run):
    idle = run.idle_share()
    return None if idle is None else 100.0 * idle
