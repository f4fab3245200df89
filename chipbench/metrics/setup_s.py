"""Seconds from process start to the opening of the window: weights,
engine, compilation or cache loads, warm-up and the cell's start."""
from chipbench.readout import Run


def read(run: Run):
    return run.setup_s
