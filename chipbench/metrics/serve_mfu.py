"""Whole step against the chip's peak: model FLOPs of every prompt token
prefilled and every token decoded in the traced window, from the
configuration's shapes, over the window and the bf16 peak, in percent."""
from chipbench.readout import Run


def read(run: Run):
    if run.trace is None:
        return None
    w = run.work
    flops = 0
    for t in run.ticks:
        flops += sum(w.prefill_flops(s, n, last) for s, n, last in t.prefill_chunks)
        flops += sum(w.decode_lane_flops(k) for k in t.decode_keys)
    return 100.0 * flops / run.trace.window_s / run.peaks["bf16_flops_per_s"]
