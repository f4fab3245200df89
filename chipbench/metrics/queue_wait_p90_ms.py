"""Engine scheduler: 90th percentile of the wait from submit to the tick
that admitted the request to a slot (QUEUED to PREFILLING, as the harness
sees it after each step), over requests admitted in the window."""
from chipbench.readout import Run, percentile


def read(run: Run):
    waits = [r.admitted - r.sent for r in run.requests
             if r.admitted is not None and run.in_window(r.admitted)]
    p = percentile(waits, 90)
    return None if p is None else p * 1e3
