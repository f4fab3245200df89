"""99th percentile over every client-visible gap between consecutive
tokens of one request, across all requests."""
from chipbench.readout import Run, itl_samples, percentile


def read(run: Run):
    p = percentile(itl_samples(run), 99)
    return None if p is None else p * 1e3
