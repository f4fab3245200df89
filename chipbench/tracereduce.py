"""Reduction of a profiler trace to the numbers the per-layer readers use.

The three step programs of the engine are all jitted from functions
named ``step``, so the trace alone cannot tell prefill from decode. The
harness records the kind of every dispatch it caused, in order; the
device's module events of those programs, in time order, are matched to
that list one to one.
"""
from __future__ import annotations

import bisect
import collections
import dataclasses
from pathlib import Path
from typing import Dict, Iterable, List, Sequence, Tuple

STEP_MODULE = "jit_step"
HOST_PREFIX = "chipbench."
WINDOW = "chipbench.window"


@dataclasses.dataclass
class Event:
    name: str
    start: float      # ns
    dur: float        # ns

    @property
    def end(self) -> float:
        return self.start + self.dur


@dataclasses.dataclass
class TraceEvents:
    """What the reduction reads from one trace, per device."""
    modules: Dict[str, List[Event]]   # device plane -> module events
    ops: Dict[str, List[Event]]       # device plane -> op events
    host: List[Event]                 # chipbench.* annotations


@dataclasses.dataclass
class Reduced:
    window_s: float
    busy_s: float                          # mean over the devices
    programs: List[Tuple[str, float]]      # (kind, device seconds) per dispatch
    matched: bool
    device_ops: List[Tuple[str, float]]    # top ops by device seconds
    idle_gaps: List[Tuple[str, float]]     # idle seconds by host activity


def newest_xplane(trace_dir: Path) -> Path:
    files = sorted(Path(trace_dir).glob("plugins/profile/*/*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def read_xplane(path: Path) -> TraceEvents:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(path))
    modules: Dict[str, List[Event]] = {}
    ops: Dict[str, List[Event]] = {}
    host: List[Event] = []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name == "XLA Modules":
                    modules[plane.name] = [Event(e.name, e.start_ns,
                                                 e.duration_ns)
                                           for e in line.events]
                elif line.name == "XLA Ops":
                    ops[plane.name] = [Event(e.name, e.start_ns,
                                             e.duration_ns)
                                       for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(HOST_PREFIX):
                        host.append(Event(e.name, e.start_ns, e.duration_ns))
    return TraceEvents(modules, ops, host)


def summary(ev: TraceEvents) -> dict:
    """Counts for the log: module events by name, ops and host spans."""
    names = collections.Counter(m.name for mods in ev.modules.values()
                                for m in mods)
    return {"devices": sorted(ev.modules),
            "modules": names.most_common(8),
            "ops": {k: len(v) for k, v in ev.ops.items()},
            "host_spans": len(ev.host)}


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


def host_label(spans: Sequence[Event], starts: Sequence[float],
               t: float) -> str:
    """The ``chipbench.*`` host span (flat, sorted by start in ``spans``
    with their ``starts``) that holds time ``t``."""
    i = bisect.bisect_right(starts, t) - 1
    if i >= 0 and t < spans[i].end:
        return spans[i].name[len(HOST_PREFIX):]
    return "other"


def reduce(ev: TraceEvents, kinds: Sequence[str], top: int = 10) -> Reduced:
    windows = [e for e in ev.host if e.name == WINDOW]
    if not windows:
        raise ValueError("trace holds no chipbench.window span")
    w = windows[-1]
    lo, hi = w.start, w.end
    if not ev.modules:
        raise ValueError("trace holds no device module events")
    busy_total, programs, matched = 0.0, [], True
    op_time: Dict[str, float] = collections.Counter()
    idle: Dict[str, float] = collections.Counter()
    host = sorted((e for e in ev.host if e.name != WINDOW),
                  key=lambda e: e.start)
    host_starts = [e.start for e in host]
    for plane, mods in sorted(ev.modules.items()):
        steps = sorted((m for m in mods if m.name.startswith(STEP_MODULE)),
                       key=lambda m: m.start)
        if len(steps) != len(kinds):
            matched = False
        elif not programs:
            programs = [(k, m.dur / 1e9) for k, m in zip(kinds, steps)]
        ops = ev.ops.get(plane) or mods
        busy = union(((o.start, o.end) for o in ops), lo, hi)
        busy_total += sum(e - s for s, e in busy)
        starts = [m.start for m in steps]
        for o in ops:
            if not (lo <= o.start < hi):
                continue
            i = bisect.bisect_right(starts, o.start) - 1
            kind = "other"
            if matched and i >= 0 and o.start < steps[i].end:
                kind = kinds[i]
            op_time[f"{kind}:{o.name}"] += o.dur
        for s, e in gaps(busy, lo, hi):
            idle[host_label(host, host_starts, (s + e) / 2)] += e - s
    n_dev = len(ev.modules)
    return Reduced(
        window_s=(hi - lo) / 1e9,
        busy_s=busy_total / n_dev / 1e9,
        programs=programs if matched else [],
        matched=matched,
        device_ops=[(k, v / 1e9 / n_dev)
                    for k, v in sorted(op_time.items(), key=lambda kv: -kv[1])[:top]],
        idle_gaps=[(k, v / 1e9 / n_dev)
                   for k, v in sorted(idle.items(), key=lambda kv: -kv[1])[:top]])
