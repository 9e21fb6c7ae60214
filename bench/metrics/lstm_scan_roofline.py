"""The LSTM layer's share of its roofline: the least time the chip could
take for the layer's work on the events answered in the traced window
(the larger of its operations over the bf16 peak and its bytes over the
HBM bandwidth, counted from shapes by ``bench/work.py``), over the device
time of the operations that carry the layer out (profiler trace).

Those operations are matched by ``PATTERN`` on their HLO text: the Pallas
scan kernel's custom call, which the program names after the jitted
``_lstm_scan_jit``.  Prints which bound applies.

The kernel runs float32 at the ``highest`` precision
(``#tpu.contract_precision<fp32>``), several bf16 passes a product (six,
if Mosaic does as XLA does), so where the compute bound applies the share
cannot pass about 1/6 (16.7%) of the bf16 roofline."""

import sys

from bench import trace as tr
from bench import work

PATTERN = r"^%_lstm_scan_jit"


def read(run):
    if run.trace is None or run.model["cell"] != "lstm":
        return None
    lo, hi = run.trace.window
    device_s = sum(sum(tr.op_seconds(run.trace.ops.get(d, []), lo, hi,
                                     PATTERN).values())
                   for d in run.device_ids)
    n = len(run.record.idx)
    if device_s <= 0 or not n:
        return None
    flop_s = n * work.rnn_flops_per_event(run.model) \
        / run.peaks["bf16_flops_per_s"]
    byte_s = work.rnn_bytes(run.model, n, run.record.calls) \
        / run.peaks["hbm_bytes_per_s"]
    bound = "compute" if flop_s >= byte_s else "memory"
    print(f"lstm_scan_roofline: {bound}-bound ({flop_s} s of operations, "
          f"{byte_s} s of bytes at peak) against {device_s} s of kernel "
          f"time", file=sys.stderr)
    return 100.0 * max(flop_s, byte_s) / device_s
