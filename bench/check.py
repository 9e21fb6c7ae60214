"""What decides ``correct``, and what the metric readers are given.

Every answer that the timed path produced in the window is compared with
the reference of the configuration's family (``bench/families/``): the
family's ``compare`` gives its own checks, each beside its limit (the
configuration's ``limits``), which come first; every family shares these:

``missing_answers``             events attempted and not answered: 0;
``executables_without_kernel``  served executables without a compiled
                                Mosaic kernel (``tpu_custom_call``), on a
                                TPU: 0;
``compiles_in_window``          executables compiled or loaded inside the
                                measured window: 0.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Callable, Dict, List, Optional

import numpy as np

from bench import trace as tr


@dataclass
class Run:
    """One run as the metric readers see it."""

    cell: object                 # bench.spec.Cell
    record: object               # bench.drive.Record
    trace: Optional[tr.Trace]    # None unless the run was traced
    setup_s: float
    peaks: Dict                  # bench/peaks.json's entry for this device
    device_ids: List[int]        # the chips the cell uses

    @property
    def model(self) -> Dict:
        return self.cell.config["model"]

    @property
    def window_s(self) -> float:
        """From the window's start to the last answer, on the host clock."""
        return self.record.t_end - self.record.t_begin

    def idle_share(self) -> Optional[float]:
        """1 - (union of device op intervals) / traced window, averaged
        over the cell's chips."""
        if self.trace is None or not any(d in self.trace.ops
                                         for d in self.device_ids):
            return None
        lo, hi = self.trace.window
        shares = [tr.busy(self.trace.merged(d), lo, hi) / (hi - lo)
                  for d in self.device_ids]
        return 1.0 - sum(shares) / len(shares)


def pct(values, q: float) -> float:
    return float(np.percentile(np.asarray(values), q))


def reference_for(idx: np.ndarray, x: np.ndarray,
                  probabilities: Callable[[np.ndarray], np.ndarray]
                  ) -> np.ndarray:
    """Reference probabilities for every pool event in ``idx`` (rows of the
    other events are NaN)."""
    used = np.unique(idx)
    out = None
    if len(used):
        p = probabilities(x[used])
        out = np.full((len(x), p.shape[1]), np.nan, np.float32)
        out[used] = p
    return out


def compare(record, family_checks: Dict, *, missing_kernel: int,
            compiles_in_window: int) -> Dict:
    """The family's checks of ``record``'s answers, then the shared ones."""
    return {
        **family_checks,
        "missing_answers": {"value": int(record.failed), "limit": 0},
        "executables_without_kernel": {"value": int(missing_kernel),
                                       "limit": 0},
        "compiles_in_window": {"value": int(compiles_in_window), "limit": 0},
    }


def correct(checks: Dict) -> bool:
    return all(c["value"] is not None and c["value"] <= c["limit"]
               for c in checks.values())


def lateness_line(late: np.ndarray) -> str:
    """How late the open-loop generator sent its events."""
    if not len(late):
        return "generator lateness: no event was sent"
    return (f"generator lateness: p50 {1e6 * pct(late, 50)} us, p99 "
            f"{1e6 * pct(late, 99)} us, max {1e6 * float(late.max())} us "
            f"over {len(late)} events")


def device_busy(run: Run) -> Dict[str, float]:
    """``busy_s`` averaged over the cell's chips, and ``window_s``."""
    lo, hi = run.trace.window
    b = [tr.busy(run.trace.merged(d), lo, hi) for d in run.device_ids]
    return {"busy_s": sum(b) / len(b), "window_s": hi - lo}


def breakdown(run: Run) -> Dict[str, List]:
    """The device operations that took most time, and the device's idle
    time by the benchmark span the host was in, over the cell's chips."""
    lo, hi = run.trace.window
    ops: Dict[str, float] = {}
    idle: Dict[str, float] = {}
    for d in run.device_ids:
        for k, v in tr.op_seconds(run.trace.ops.get(d, []), lo, hi).items():
            ops[k] = ops.get(k, 0.0) + v
        gaps = tr.gaps(run.trace.merged(d), lo, hi)
        for k, v in tr.idle_by_span(gaps, run.trace.spans).items():
            idle[k] = idle.get(k, 0.0) + v
    return {"device_ops": tr.top(ops), "idle_gaps": tr.top(idle)}
