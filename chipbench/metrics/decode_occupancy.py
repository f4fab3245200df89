"""Engine scheduler: lanes busy per decode step, as tokens that decode
steps delivered in the window over the engine's count of steps that
advanced at least one lane (``decode_steps_advanced``)."""
from chipbench.readout import Run


def read(run: Run):
    steps = sum(t.decode_steps_advanced for t in run.ticks)
    toks = sum(len(t.decode_keys) for t in run.ticks)
    return toks / steps if steps else None
