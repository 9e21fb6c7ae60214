"""Drive one cell: build the system under test, warm the shapes the cell's
traffic uses, and run the measured window through the entry point the
traffic mix names (``entry``).  The configuration's family module
(``bench/families/<family>.py``) maps each entry name to its ``Driver``
subclass (``DRIVERS``) and documents what its entries do.

Every event is timed on the host clock: from when it was due (open loop)
or issued (closed loop) until its answer is back on the host as NumPy.

What an event is depends on the family.  For a model that answers each
input once, as a tagger does, an event is one input, and ``idx`` holds
its place in the pool.  For a family that generates tokens:

* an event is one generated token, answered when it is back on the host;
* ``idx`` has one entry per token (the pool index of the token's request),
  so ``events_per_s`` is generated tokens per second;
* a token's ``latency_s`` is its gap since the previous token of the same
  request; for a request's first token it is the time since the request
  was issued (closed loop) or fell due (open loop).  So the event latency
  percentiles are the token gap users feel, and each request's wait for
  its first token is counted once.

``Record.answers`` holds whatever the family's ``compare`` reads, one row
per event.
"""

from __future__ import annotations

import contextlib
from dataclasses import dataclass, field
from typing import Dict, List, Optional

import numpy as np

from bench import traffic


@dataclass
class Record:
    """What a measured window produced, on the host clock."""

    idx: np.ndarray                  # pool index of each answered event
    answers: np.ndarray              # one row per answered event
    attempted: int
    failed: int                      # failed, shed or never answered
    t_begin: float
    t_end: float                     # when the last answer came back
    calls: int                       # calls into the entry point
    latency_s: Optional[np.ndarray] = None
    queue_wait_s: Optional[np.ndarray] = None
    lateness_s: Optional[np.ndarray] = None
    winners: Dict[str, int] = field(default_factory=dict)


def joined(records: List[Record]) -> Record:
    """The answers of several windows as one record, for the check."""
    return Record(
        idx=np.concatenate([r.idx for r in records]),
        answers=np.concatenate([r.answers for r in records]),
        attempted=sum(r.attempted for r in records),
        failed=sum(r.failed for r in records),
        t_begin=records[0].t_begin, t_end=records[-1].t_end,
        calls=sum(r.calls for r in records))


class Spans:
    """Benchmark spans in the profiler's trace; free when not tracing."""

    def __init__(self, on: bool):
        self.on = on

    def __call__(self, name: str):
        if not self.on:
            return contextlib.nullcontext()
        import jax

        return jax.profiler.TraceAnnotation(name)


class Driver:
    """Base: the input pool (``x``, the family's ``make_inputs``), the
    engines built, counts of what they compiled."""

    def __init__(self, cell, params, x, words: np.ndarray,
                 devices: List, cache_dir: str):
        self.cell, self.mix, self.config = cell, cell.mix, cell.config
        self.params, self.x, self.devices = params, x, devices
        self.order = traffic.pool_order(len(x), int(words[2]))
        self.arrival_word = int(words[3])
        self.cache_dir = cache_dir

    def engines(self) -> List:
        raise NotImplementedError

    def executables(self) -> List:
        return [exe for eng in self.engines()
                for exes in eng.executables().values() for exe in exes]

    def warm(self) -> None:
        raise NotImplementedError

    def window(self, seconds: float, span: Spans) -> Record:
        raise NotImplementedError
