"""Device: share of the traced window in which no operation ran on the
chip (one minus the union of device op intervals over the window), in
percent."""
from chipbench.readout import Run


def read(run: Run):
    if run.trace is None:
        return None
    return 100.0 * (1.0 - run.trace.busy_s / run.trace.window_s)
