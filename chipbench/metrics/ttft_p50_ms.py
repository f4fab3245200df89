"""Median time to first token, over every request whose first token
arrived in the window (from due time in an open loop, send time in a
closed one)."""
from chipbench.readout import Run, percentile, ttft_samples


def read(run: Run):
    p = percentile(ttft_samples(run), 50)
    return None if p is None else p * 1e3
