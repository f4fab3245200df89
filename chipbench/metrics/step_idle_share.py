"""Engine scheduler: share of the traced window in which the device was
idle while a ``serve.tick`` span was open on the host (the engine's own
time between and around its programs), over the part of the window the
device trace covers, in percent."""
from chipbench.readout import Run


def read(run: Run):
    tr = run.trace
    if tr is None or not tr.devices or "serve.tick" not in tr.spans:
        return None
    return 100.0 * tr.spans["serve.tick"]["idle_s"] / tr.covered_s
