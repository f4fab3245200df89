"""Step programs: device milliseconds of the decode programs
(``jit_decode_block*``) per decode step, a dispatch of T scanned steps
counting T. The steps are the ``steps`` of the ``serve.dispatch.decode``
spans where the device trace covers the window, the same stretch as the
program time (the engine's ``decode_steps`` counter spans the whole
window, and the device trace can end before it)."""
from chipbench.readout import Run


def read(run: Run):
    sec = run.program_seconds("jit_decode_block")
    if sec is None:
        return None
    steps = run.trace.span_arg("serve.dispatch.decode", "steps")
    return sec * 1e3 / steps if steps else None
