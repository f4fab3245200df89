"""Engine scheduler: share of the prompt positions the prefill dispatches
computed in the window that held no real prompt token, from the engine's
counters: 1 - ``prefill_tokens`` / ``prefill_positions``, in percent."""
from chipbench.readout import Run


def read(run: Run):
    positions = run.counters.get("prefill_positions", 0)
    if not positions:
        return None
    return 100.0 * (1.0 - run.counters["prefill_tokens"] / positions)
