"""Decode step against the chip: the least time the decode work of the
traced window needs (weights read once per step; exact attention reads
the valid K/V rows, A^3 the walk's sorted entries and the candidate
budget's rows; or the FLOP bound, where larger) over the device time of
the decode programs, in percent."""
from chipbench.readout import Run


def read(run: Run):
    sec = run.program_seconds("decode")
    if not sec:
        return None
    least = sum(run.work.decode_step_least_s(t.decode_steps, t.decode_keys,
                                             run.peaks)
                for t in run.ticks if t.decode_dispatches)
    return 100.0 * least / sec
