"""The step programs and the engine's tick phases in a profiler trace.

    python3 chipbench/programtrace.py --workload <name> --seed <n> --seconds <s>

From the root of a checkout, on a TPU. Runs the cell once as
``chipbench/run.py --trace 0`` does, but with the profiler on from set-up
to the end of the window, so the end-to-end metrics it prints are the
cost of tracing against a ``--trace 0`` run of the same seed. Then it
reduces the trace to what the engine's names make readable, and prints
one JSON line:

- device seconds and module count of each step program in the window
  (``jit_decode_block``, ``jit_prefill_chunk``, ...: the module names),
  beside the engine's ``serve.dispatch.*`` spans in the window and the
  tokens, padded positions and decode steps those spans carry;
- the device's idle time in the window, each idle nanosecond charged to
  the innermost ``serve.*`` span open over it on the host, or to
  ``outside_engine`` (the client's waits, submits and bookkeeping); and
  apart, the part of it that fell while a program was running (gaps
  between one program's ops, which the host does not cause);
- ``readings``: the step-program and scheduler numbers derived from
  these (``decode_program_ms``, ``prefill_program_ms_per_ktok``,
  ``prefill_padding_share``, ``step_idle_share``).

The device's trace buffer fills before a long trace ends, and the TPU
drops the events after that. Where dispatch spans continue past the
last module event, the window is cut there (``cut_s``) and every number
is of the part the device trace covers (``covered_s``); device idle
past the cut would be an artefact.

A program that names no modules and opens no ``serve.*`` spans (an
older engine) reads empty tables and no readings, and does not fail.
"""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import bisect  # noqa: E402
import collections  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import re  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Dict, List, Optional, Sequence, Tuple  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from chipbench import tracereduce as TR  # noqa: E402

SPAN_PREFIX = "serve."
TICK = "serve.tick"
OUTSIDE = "outside_engine"
DISPATCH = "serve.dispatch."
DECODE_PROGRAM = "jit_decode_block"
PREFILL_PROGRAM = "jit_prefill_chunk"
_PROGRAM_NAME = re.compile(r"[A-Za-z0-9_.\-]+")


@dataclasses.dataclass
class Span:
    name: str
    start: float      # ns
    dur: float        # ns
    args: dict

    @property
    def end(self) -> float:
        return self.start + self.dur


@dataclasses.dataclass
class ProgramEvents:
    """What the reduction reads from one trace."""
    modules: Dict[str, List[TR.Event]]   # device plane -> module events
    ops: Dict[str, List[TR.Event]]       # device plane -> op events
    spans: List[Span]                    # serve.* host spans
    window: Optional[TR.Event]           # the last chipbench.window


def program_of(module: str) -> str:
    """``jit_decode_block(12)`` -> ``jit_decode_block``."""
    m = _PROGRAM_NAME.match(module)
    return m.group(0) if m else module


def read(path: Path) -> ProgramEvents:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(path))
    modules: Dict[str, List[TR.Event]] = {}
    ops: Dict[str, List[TR.Event]] = {}
    spans: List[Span] = []
    windows: List[TR.Event] = []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name in ("XLA Modules", "XLA Ops"):
                    out = modules if line.name == "XLA Modules" else ops
                    out[plane.name] = [
                        TR.Event(e.name, e.start_ns, e.duration_ns)
                        for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        spans.append(Span(e.name, e.start_ns,
                                          e.duration_ns,
                                          {n: v for n, v in e.stats}))
                    elif e.name == TR.WINDOW:
                        windows.append(TR.Event(e.name, e.start_ns,
                                                e.duration_ns))
    return ProgramEvents(modules, ops, spans,
                         max(windows, key=lambda w: w.start, default=None))


def phase_segments(spans: Sequence[Span], lo: float, hi: float
                   ) -> List[Tuple[float, float, str, bool]]:
    """[lo, hi) cut into pieces ``(start, end, label, in_tick)``: the
    label is the innermost ``serve.*`` span open over the piece (the one
    opened last), else ``outside_engine``; ``in_tick`` says whether a
    ``serve.tick`` is open over it. ``spans`` are sorted by start, the
    longer first, so an inner span that opens with its parent comes
    later."""
    edges = []
    for i, s in enumerate(spans):
        if s.dur > 0:
            edges.append((s.start, 1, i))
            edges.append((s.end, 0, i))     # closes sort before opens
    edges.sort()
    out: List[Tuple[float, float, str, bool]] = []
    open_: List[int] = []
    cur = lo
    for t, kind, i in edges + [(hi, 0, -1)]:
        t = min(max(t, lo), hi)
        if t > cur:
            if open_:
                inner = max(open_, key=lambda j: (spans[j].start, j))
                label = spans[inner].name
                in_tick = any(spans[j].name == TICK for j in open_)
            else:
                label, in_tick = OUTSIDE, False
            out.append((cur, t, label, in_tick))
            cur = t
        if i < 0:
            break
        if kind:
            open_.append(i)
        else:
            open_.remove(i)
    return out


def idle_by_phase(idle: Sequence[Tuple[float, float]],
                  segments: Sequence[Tuple[float, float, str, bool]]
                  ) -> Tuple[Dict[str, float], float]:
    """Each idle interval's ns summed per segment label; also the idle ns
    under an open ``serve.tick``. Both lists are sorted and disjoint."""
    by: Dict[str, float] = collections.Counter()
    in_tick = 0.0
    starts = [s for s, _e, _l, _t in segments]
    for a, b in idle:
        j = max(0, bisect.bisect_right(starts, a) - 1)
        while j < len(segments) and segments[j][0] < b:
            s, e, label, tick = segments[j]
            overlap = min(b, e) - max(a, s)
            if overlap > 0:
                by[label] += overlap
                if tick:
                    in_tick += overlap
            j += 1
    return dict(by), in_tick


def intersect(a: Sequence[Tuple[float, float]],
              b: Sequence[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Intersection of two sorted lists of disjoint intervals."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if s < e:
            out.append((s, e))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def _clipped(start: float, end: float, lo: float, hi: float) -> float:
    return max(0.0, min(end, hi) - max(start, lo))


def trace_end(ev: ProgramEvents, hi: float) -> float:
    """``hi``, or the end of the first device's last module event where a
    dispatch span starts after it: the device trace was cut there."""
    mods = next((m for _p, m in sorted(ev.modules.items()) if m), None)
    if not mods:
        return hi
    last = max(m.end for m in mods)
    if any(s.start > last for s in ev.spans if s.name.startswith(DISPATCH)):
        return min(hi, last)
    return hi


def reduce(ev: ProgramEvents) -> dict:
    """Seconds are means over the devices; counts are of the first."""
    if ev.window is None:
        raise ValueError("trace holds no chipbench.window span")
    lo, win_hi = ev.window.start, ev.window.end
    hi = trace_end(ev, win_hi)
    spans = sorted(ev.spans, key=lambda s: (s.start, -s.dur))
    inside = [s for s in spans if lo <= s.start < hi]
    disp = collections.Counter()
    for s in inside:
        if s.name == "serve.dispatch.prefill":
            disp["prefill"] += 1
            disp["prefill_tokens"] += int(s.args.get("tokens", 0))
            disp["prefill_positions"] += int(s.args.get("positions", 0))
        elif s.name == "serve.dispatch.decode":
            disp["decode"] += 1
            disp["decode_steps"] += int(s.args.get("steps", 0))
    n_dev = max(1, len(ev.modules))
    programs: Dict[str, Dict[str, float]] = {}
    idle_by: Dict[str, float] = collections.Counter()
    in_program_by: Dict[str, float] = collections.Counter()
    idle_total = idle_tick = 0.0
    segments = phase_segments(spans, lo, hi)
    for k, (plane, mods) in enumerate(sorted(ev.modules.items())):
        for m in mods:
            seconds = _clipped(m.start, m.end, lo, hi) / 1e9 / n_dev
            counted = k == 0 and lo <= m.start < hi
            if seconds or counted:
                p = programs.setdefault(program_of(m.name),
                                        {"modules": 0, "device_s": 0.0})
                p["modules"] += counted
                p["device_s"] += seconds
        ops = ev.ops.get(plane) or mods
        busy = TR.union(((o.start, o.end) for o in ops), lo, hi)
        idle = TR.gaps(busy, lo, hi)
        # idle while a program runs: gaps between its ops, not the host's
        running = TR.union(((m.start, m.end) for m in mods), lo, hi)
        by, tick = idle_by_phase(idle, segments)
        in_program, _ = idle_by_phase(intersect(idle, running), segments)
        for out, part in ((idle_by, by), (in_program_by, in_program)):
            for label, ns in part.items():
                out[label] += ns / 1e9 / n_dev
        idle_total += sum(b - a for a, b in idle) / 1e9 / n_dev
        idle_tick += tick / 1e9 / n_dev
    return {"window_s": (win_hi - lo) / 1e9,
            "covered_s": (hi - lo) / 1e9,
            "cut_s": (win_hi - hi) / 1e9,
            "devices": len(ev.modules),
            "programs": dict(sorted(programs.items())),
            "dispatches": dict(disp),
            "ticks": sum(1 for s in inside if s.name == TICK),
            "idle_s": idle_total,
            "idle_in_tick_s": idle_tick,
            "idle_by_phase": dict(sorted(idle_by.items(),
                                         key=lambda kv: -kv[1])),
            "in_program_idle_by_phase": dict(in_program_by)}


def device_s(red: dict, prefix: str) -> Optional[float]:
    """Device seconds in the window of the programs named ``prefix*``."""
    got = [p["device_s"] for name, p in red["programs"].items()
           if name.startswith(prefix)]
    return sum(got) if got else None


def readings(red: dict) -> dict:
    """The step-program and scheduler numbers of one reduced trace; a
    number whose inputs the trace lacks is left out."""
    out = {}
    d = red["dispatches"]
    dec, pre = device_s(red, DECODE_PROGRAM), device_s(red, PREFILL_PROGRAM)
    if dec is not None and d.get("decode_steps"):
        out["decode_program_ms"] = dec * 1e3 / d["decode_steps"]
    if pre is not None and d.get("prefill_tokens"):
        out["prefill_program_ms_per_ktok"] = (pre * 1e3
                                              / (d["prefill_tokens"] / 1e3))
    if d.get("prefill_positions"):
        out["prefill_padding_share"] = 100.0 * (
            1.0 - d["prefill_tokens"] / d["prefill_positions"])
    if red["ticks"] and red["devices"]:
        out["step_idle_share"] = (100.0 * red["idle_in_tick_s"]
                                  / red["covered_s"])
    return out


def profile_cell(cell, seed: int, seconds: float, t_process: float,
                 program=None) -> dict:
    """One run of ``cell`` with the profiler on throughout, reduced."""
    import jax
    from chipbench import harness
    trace_dir = harness.OUT / f"programtrace-{cell.name}"
    shutil.rmtree(trace_dir, ignore_errors=True)
    jax.profiler.start_trace(str(trace_dir))
    try:
        out = harness.run_cell(cell, seed, seconds, False, t_process,
                               program=program, check=False)
    finally:
        jax.profiler.stop_trace()
    red = reduce(read(TR.newest_xplane(trace_dir)))
    shutil.rmtree(trace_dir, ignore_errors=True)
    return {"metrics": out.metrics, "device": out.device,
            "readings": readings(red), "trace": red,
            "engine_stats": out.diagnostics["engine_stats"],
            "window_compiles": out.diagnostics["window_compiles"]}


def main(argv=None) -> int:
    from chipbench import harness
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    cell = harness.find_cell(harness.load_benchmark(), args.workload)
    program = harness.import_program()
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(f"programtrace: {args.workload} needs {cell.chips} TPU "
              f"chip(s); JAX has {len(devices)} {devices[0].platform} "
              f"device(s)", file=sys.stderr)
        return 2
    out = profile_cell(cell, args.seed, args.seconds, T_PROCESS, program)
    print(json.dumps(out, default=str))
    return 0


if __name__ == "__main__":
    sys.exit(main())
