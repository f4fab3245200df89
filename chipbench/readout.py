"""What one run recorded, in the form the metric readers take it.

All times are seconds on the client's host clock (``time.perf_counter``).
A reader (``metrics/<name>.py``) gets a :class:`Run` and returns a number,
or None where the run holds nothing for it to read. Besides what the
client saw, a run holds the engine's counters over the window
(``counters``: the change of every numeric key of ``engine.stats``) and,
in a traced run, the reduced trace (``trace``: device seconds per
program and per op, the engine's ``serve.*`` spans with their counts,
seconds, device idle and summed args, and how much of the window the
device trace covers), so a reader of a new program, span or counter name
is one new file.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from chipbench.tracereduce import Trace


@dataclasses.dataclass
class Req:
    prompt_len: int
    max_new: int
    due: float                 # when the client meant to send it
    sent: float                # when it was submitted
    uid: int = -1
    admitted: Optional[float] = None    # start of the tick that admitted it
    done: Optional[float] = None        # end of the tick that ended it
    status: str = "queued"
    token_times: List[float] = dataclasses.field(default_factory=list)
    tokens: Optional[List[int]] = None  # the served tokens, once finished
    prompt: Optional[np.ndarray] = None


@dataclasses.dataclass
class Tick:
    start: float
    end: float
    prefill_dispatches: int
    decode_dispatches: int
    prefill_chunks: List[Tuple[int, int, bool]]  # (start, length, last) per lane
    decode_steps: int           # scan iterations dispatched
    decode_steps_advanced: int
    decode_keys: List[int]      # keys attended by each decoded token delivered


@dataclasses.dataclass
class Run:
    t0: float
    t1: float
    setup_s: float
    loop: str
    requests: List[Req]
    ticks: List[Tick]           # the ticks the window's loop ran
    work: Any                   # the model kind's Work (references/<kind>.py)
    peaks: dict
    trace: Optional[Trace] = None
    counters: Dict[str, float] = dataclasses.field(default_factory=dict)

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0

    def in_window(self, t: float) -> bool:
        return self.t0 <= t <= self.t1

    def program_seconds(self, prefix: str) -> Optional[float]:
        """Device seconds of the programs whose names start with
        ``prefix``, where the device trace covers the window; None where
        no such program ran there."""
        if self.trace is None:
            return None
        got = [p["device_s"] for name, p in self.trace.programs.items()
               if name.startswith(prefix)]
        return sum(got) if got else None


def percentile(values: Sequence[float], q: float) -> Optional[float]:
    """The q-th percentile of all ``values``, interpolating linearly
    between order statistics (numpy's default); None for no values."""
    v = sorted(values)
    if not v:
        return None
    pos = (len(v) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(v) - 1)
    return v[lo] + (v[hi] - v[lo]) * (pos - lo)


def ttft_samples(run: Run) -> List[float]:
    """Seconds from due (open loop) or sent (closed loop) to the first
    token, for every request whose first token arrived in the window."""
    out = []
    for r in run.requests:
        if r.token_times and run.in_window(r.token_times[0]):
            origin = r.due if run.loop == "open" else r.sent
            out.append(r.token_times[0] - origin)
    return out


def itl_samples(run: Run) -> List[float]:
    """Every gap between consecutive tokens of one request whose later
    token arrived in the window."""
    out = []
    for r in run.requests:
        tt = r.token_times
        out.extend(b - a for a, b in zip(tt, tt[1:]) if run.in_window(b))
    return out
