"""Output tokens that reached the client in the window, per second of it."""
from chipbench.readout import Run


def read(run: Run):
    n = sum(1 for r in run.requests for t in r.token_times if run.in_window(t))
    return n / run.seconds
