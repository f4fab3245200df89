"""Step programs: device milliseconds of the decode programs per decode
step (a dispatch of T scanned steps counts T)."""
from chipbench.readout import Run


def read(run: Run):
    sec = run.program_seconds("decode")
    steps = sum(t.decode_steps for t in run.ticks)
    if sec is None or steps == 0:
        return None
    return sec * 1e3 / steps
