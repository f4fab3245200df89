"""Step programs: device milliseconds of the prefill programs
(``jit_prefill_chunk*``) per thousand real prompt tokens they prefilled:
the ``tokens`` of the ``serve.dispatch.prefill`` spans where the device
trace covers the window."""
from chipbench.readout import Run


def read(run: Run):
    sec = run.program_seconds("jit_prefill_chunk")
    if sec is None:
        return None
    toks = run.trace.span_arg("serve.dispatch.prefill", "tokens")
    return sec * 1e3 / (toks / 1e3) if toks else None
