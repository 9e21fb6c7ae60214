"""The whole model step's share of the chips' bf16 peak: answered events
per second times the model's operations per event (``bench/work.py``),
over chips times peak (host clock, from the untraced window).

bf16 is the peak because the MXU multiplies bf16.  The configurations run
float32 at the ``highest`` matmul precision, which XLA takes as six bf16
passes per product, so a step made only of such products cannot pass
about 1/6 (16.7%) of this peak; the passes count against the step."""

from bench import work


def read(run):
    n = len(run.record.idx)
    if not n:
        return None
    rate = n / run.window_s
    flops = work.model_flops_per_event(run.model)
    peak = len(run.device_ids) * run.peaks["bf16_flops_per_s"]
    return 100.0 * rate * flops / peak
