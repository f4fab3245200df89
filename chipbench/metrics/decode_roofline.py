"""Decode step against the chip: the least time that the decode work of
the ticks the device trace covers needs, over the device time of the
decode programs (``jit_decode_block*``), in percent. The least time is
the kind's ``Work.decode_step_least_s``: the larger of the FLOP bound and
the byte bound. At a few lanes the byte bound sets it; for ``dense_gqa``
that is every weight but the embedding table read once per step, plus
each lane's valid K/V rows."""
from chipbench.readout import Run


def read(run: Run):
    sec = run.program_seconds("jit_decode_block")
    if not sec:
        return None
    end = run.t0 + run.trace.covered_s
    least = sum(run.work.decode_step_least_s(t.decode_steps, t.decode_keys,
                                             run.peaks)
                for t in run.ticks if t.decode_dispatches and t.start < end)
    return 100.0 * least / sec
