"""90th percentile of the time to first token, over the same samples as
``ttft_p50_ms``."""
from chipbench.readout import Run, percentile, ttft_samples


def read(run: Run):
    p = percentile(ttft_samples(run), 90)
    return None if p is None else p * 1e3
