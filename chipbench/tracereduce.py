"""Reduction of a profiler trace to what the per-layer readers take.

The harness's traced run writes one ``.xplane.pb``; :func:`read` reads it
once and :func:`reduce` keys it by name. The window is the
``chipbench.window`` host span. From each TPU plane come the module
events (one per run of a compiled program, named after it:
``jit_decode_block(12)``) and the op events; from the host, the harness's
``chipbench.*`` spans and the engine's ``serve.*`` spans with their args.
Nothing here knows a program, span or op by name but the window: a new
program or span is read as it comes.

The TPU keeps about 3.9 million op events of a trace and drops the rest.
Where a ``serve.dispatch.*`` span starts after the first device's last
module event, the device trace was cut there, and what is read beside the
engine's spans (programs, ops, spans, idle under a span) is of the part
the device trace covers (``covered_s``; ``cut_s`` is the rest). ``busy_s``
is of the whole window, so the lost tail counts as idle in
``device_idle_share``.
"""
from __future__ import annotations

import bisect
import collections
import dataclasses
import itertools
import re
from pathlib import Path
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

HOST_PREFIX = "chipbench."
WINDOW = "chipbench.window"
SPAN_PREFIX = "serve."
DISPATCH = "serve.dispatch."
OTHER = "other"
_PROGRAM_NAME = re.compile(r"[A-Za-z0-9_.\-]+")


@dataclasses.dataclass(slots=True)
class Event:
    name: str
    start: float      # ns
    dur: float        # ns
    args: Optional[dict] = None

    @property
    def end(self) -> float:
        return self.start + self.dur


@dataclasses.dataclass
class TraceEvents:
    """What the reduction reads from one trace."""
    modules: Dict[str, List[Event]]   # device plane -> module events
    ops: Dict[str, List[Event]]       # device plane -> op events
    host: List[Event]                 # chipbench.* and serve.* host spans


@dataclasses.dataclass
class Trace:
    """One traced window, reduced. Seconds are means over the devices;
    module counts are of the first device."""
    window_s: float
    covered_s: float
    cut_s: float
    devices: int
    busy_s: float                     # union of op intervals, whole window
    idle_s: float                     # device idle in the covered window
    # program name -> {"device_s", "modules", "idle_s": idle while it runs}
    programs: Dict[str, dict]
    ops: Dict[str, float]             # op name -> device seconds
    # serve.* span name -> {"count", "seconds", "idle_s": device idle while
    # one is open, "args": {numeric arg: sum}}
    spans: Dict[str, dict]
    device_ops: List[Tuple[str, float]]   # top "<program>:<op>" by seconds
    idle_gaps: List[Tuple[str, float]]    # idle seconds by chipbench.* span

    def span_arg(self, name: str, arg: str) -> float:
        """Sum of ``arg`` over the covered window's ``name`` spans."""
        return self.spans.get(name, {}).get("args", {}).get(arg, 0)


def newest_xplane(trace_dir: Path) -> Path:
    files = sorted(Path(trace_dir).glob("plugins/profile/*/*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def read(path: Path) -> TraceEvents:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(path))
    modules: Dict[str, List[Event]] = {}
    ops: Dict[str, List[Event]] = {}
    host: List[Event] = []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name in ("XLA Modules", "XLA Ops"):
                    out = modules if line.name == "XLA Modules" else ops
                    out[plane.name] = [Event(e.name, e.start_ns,
                                             e.duration_ns)
                                       for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith((HOST_PREFIX, SPAN_PREFIX)):
                        host.append(Event(e.name, e.start_ns, e.duration_ns,
                                          {k: v for k, v in e.stats}))
    return TraceEvents(modules, ops, host)


def summary(ev: TraceEvents) -> dict:
    """Counts for the log: module events by name, ops and host spans."""
    names = collections.Counter(m.name for mods in ev.modules.values()
                                for m in mods)
    return {"devices": sorted(ev.modules),
            "modules": names.most_common(8),
            "ops": {k: len(v) for k, v in ev.ops.items()},
            "host_spans": len(ev.host)}


def program_of(module: str) -> str:
    """``jit_decode_block(12)`` -> ``jit_decode_block``."""
    m = _PROGRAM_NAME.match(module)
    return m.group(0) if m else module


def union(intervals: Iterable[Tuple[float, float]], lo: float,
          hi: float) -> List[Tuple[float, float]]:
    """Union of [start, end) intervals, clipped to [lo, hi)."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def gaps(busy: Sequence[Tuple[float, float]], lo: float,
         hi: float) -> List[Tuple[float, float]]:
    out, cur = [], lo
    for s, e in busy:
        if s > cur:
            out.append((cur, s))
        cur = max(cur, e)
    if hi > cur:
        out.append((cur, hi))
    return out


class Measure:
    """How much of a sorted list of disjoint intervals lies inside any
    [a, b), in logarithmic time (the device's idle gaps number millions)."""

    def __init__(self, intervals: Sequence[Tuple[float, float]]):
        self.starts = [s for s, _e in intervals]
        self.ends = [e for _s, e in intervals]
        self.cum = list(itertools.accumulate(
            (e - s for s, e in intervals), initial=0.0))

    def within(self, a: float, b: float) -> float:
        i = bisect.bisect_right(self.ends, a)     # the first ending after a
        j = bisect.bisect_left(self.starts, b)    # the first starting at b
        if i >= j:
            return 0.0
        return (self.cum[j] - self.cum[i] - max(0.0, a - self.starts[i])
                - max(0.0, self.ends[j - 1] - b))

    def total(self) -> float:
        return self.cum[-1]

    def over(self, intervals: Iterable[Tuple[float, float]]) -> float:
        """Length inside a set of disjoint intervals."""
        return sum(self.within(a, b) for a, b in intervals)


def host_label(spans: Sequence[Event], starts: Sequence[float],
               t: float) -> str:
    """The ``chipbench.*`` host span (flat, sorted by start in ``spans``
    with their ``starts``) that holds time ``t``."""
    i = bisect.bisect_right(starts, t) - 1
    if i >= 0 and t < spans[i].end:
        return spans[i].name[len(HOST_PREFIX):]
    return OTHER


def trace_end(ev: TraceEvents, hi: float) -> float:
    """``hi``, or the end of the first device's last module event where a
    dispatch span starts after it: the device trace was cut there."""
    mods = next((m for _p, m in sorted(ev.modules.items()) if m), None)
    if not mods:
        return hi
    last = max(m.end for m in mods)
    if any(s.start > last for s in ev.host if s.name.startswith(DISPATCH)):
        return min(hi, last)
    return hi


def _by_name(events: Iterable[Event]) -> Dict[str, List[Event]]:
    out: Dict[str, List[Event]] = collections.defaultdict(list)
    for e in events:
        out[e.name].append(e)
    return out


def reduce(ev: TraceEvents, top: int = 10) -> Trace:
    windows = [e for e in ev.host if e.name == WINDOW]
    if not windows:
        raise ValueError("trace holds no chipbench.window span")
    w = max(windows, key=lambda e: e.start)
    lo, win_hi = w.start, w.end
    hi = trace_end(ev, win_hi)
    n_dev = max(1, len(ev.modules))
    flat = sorted((e for e in ev.host
                   if e.name.startswith(HOST_PREFIX) and e.name != WINDOW),
                  key=lambda e: e.start)
    flat_starts = [e.start for e in flat]
    serve = _by_name(e for e in ev.host
                     if e.name.startswith(SPAN_PREFIX) and lo <= e.start < hi)
    spans: Dict[str, dict] = {}
    for name, es in sorted(serve.items()):
        args: Dict[str, float] = collections.Counter()
        for e in es:
            for k, v in (e.args or {}).items():
                if not k.startswith("_") and isinstance(v, (int, float)):
                    args[k] += v
        spans[name] = {"count": len(es), "seconds": sum(e.dur for e in es) / 1e9,
                       "idle_s": 0.0, "args": dict(args)}
    span_open = {name: union(((e.start, e.end) for e in es), lo, hi)
                 for name, es in serve.items()}

    busy_ns = idle_ns = 0.0
    programs: Dict[str, dict] = {}
    ops: Dict[str, float] = collections.Counter()
    labelled: Dict[str, float] = collections.Counter()
    idle_gaps: Dict[str, float] = collections.Counter()
    for k, (plane, mods) in enumerate(sorted(ev.modules.items())):
        mods = sorted(mods, key=lambda m: m.start)
        dev_ops = ev.ops.get(plane) or mods
        busy = union(((o.start, o.end) for o in dev_ops), lo, win_hi)
        busy_ns += sum(e - s for s, e in busy)
        window_idle = gaps(busy, lo, win_hi)
        for s, e in window_idle:
            idle_gaps[host_label(flat, flat_starts, (s + e) / 2)] += e - s
        idle = Measure([(s, min(e, hi)) for s, e in window_idle if s < hi])
        idle_ns += idle.total()
        for name, open_ in span_open.items():
            spans[name]["idle_s"] += idle.over(open_) / 1e9 / n_dev
        for name, runs in _by_name(mods).items():
            p = programs.setdefault(program_of(name), {
                "device_s": 0.0, "modules": 0, "idle_s": 0.0})
            running = union(((m.start, m.end) for m in runs), lo, hi)
            p["device_s"] += sum(e - s for s, e in running) / 1e9 / n_dev
            p["idle_s"] += idle.over(running) / 1e9 / n_dev
            if k == 0:
                p["modules"] += sum(1 for m in runs if lo <= m.start < hi)
        starts = [m.start for m in mods]
        names = [program_of(m.name) for m in mods]
        for o in dev_ops:
            if lo <= o.start < win_hi:
                i = bisect.bisect_right(starts, o.start) - 1
                prog = names[i] if i >= 0 and o.start < mods[i].end else OTHER
                ops[o.name] += o.dur
                labelled[f"{prog}:{o.name}"] += o.dur
    programs = {n: p for n, p in sorted(programs.items())
                if p["device_s"] or p["modules"]}
    scale = 1e9 * n_dev
    return Trace(
        window_s=(win_hi - lo) / 1e9, covered_s=(hi - lo) / 1e9,
        cut_s=(win_hi - hi) / 1e9, devices=len(ev.modules),
        busy_s=busy_ns / scale, idle_s=idle_ns / scale, programs=programs,
        ops={n: v / scale for n, v in ops.items()}, spans=spans,
        device_ops=[(n, v / scale) for n, v in
                    sorted(labelled.items(), key=lambda kv: -kv[1])[:top]],
        idle_gaps=[(n, v / scale) for n, v in
                   sorted(idle_gaps.items(), key=lambda kv: -kv[1])[:top]])
