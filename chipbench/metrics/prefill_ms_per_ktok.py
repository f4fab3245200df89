"""Step programs: device milliseconds of the prefill programs per
thousand real prompt tokens they prefilled in the traced window."""
from chipbench.readout import Run


def read(run: Run):
    sec = run.program_seconds("prefill")
    toks = sum(n for t in run.ticks for _s, n, _l in t.prefill_chunks)
    if sec is None or toks == 0:
        return None
    return sec * 1e3 / (toks / 1e3)
