"""CPU tests of the trace reduction the readers take
(``chipbench/tracereduce.py``): synthetic traces with hand-checked
answers, the step-program and scheduler readers over them, and one tiny
run of the harness traced on the CPU.

    JAX_PLATFORMS=cpu python3 -m pytest -q chipbench/tests
"""
from __future__ import annotations

import json
import sys
import time
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(Path(__file__).resolve().parent))

from chipbench import harness  # noqa: E402
from chipbench import tracereduce as TR  # noqa: E402
from chipbench.readout import Run, Tick  # noqa: E402
from chipbench.references.dense_gqa import Work  # noqa: E402

import tiny  # noqa: E402

DEV = "/device:TPU:0"
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def _span(name, start, dur, **args):
    return TR.Event(name, float(start), float(dur), args)


def _ev(name, start, dur):
    return TR.Event(name, float(start), float(dur))


def _trace():
    """Window [100, 400). Three ticks: the first prefills then decodes,
    the second and third decode (the third runs past the window); the
    client waits between them."""
    modules = [_ev("jit_prefill_chunk(3)", 130, 40),
               _ev("jit_decode_block(4)", 175, 20),
               _ev("jit_decode_block(4)", 330, 20),
               _ev("jit_decode_block(4)", 390, 30)]  # ends after the window
    ops = [_ev("fusion.1", 130, 40), _ev("while.2", 175, 15),
           _ev("fusion.3", 190, 5), _ev("while.2", 330, 20),
           _ev("while.2", 390, 30)]
    host = [
        _span(TR.WINDOW, 100, 300),
        _span("serve.tick", 110, 100, step_num=1, prefill_lanes=1,
              decode_lanes=1, _r=1),
        _span("serve.admit", 110, 10),
        _span("serve.plan", 120, 5),
        _span("serve.dispatch.prefill", 125, 3, lanes=1, tokens=300,
              positions=2048),
        _span("serve.dispatch.decode", 160, 2, lanes=1, steps=1),
        _span("serve.harvest.wait", 162, 35, forced=1),
        _span("serve.harvest.apply", 197, 8),
        _span("serve.finish", 205, 5),
        _span("serve.tick", 300, 60, step_num=2, prefill_lanes=0,
              decode_lanes=2),
        _span("serve.dispatch.decode", 310, 2, lanes=2, steps=1),
        _span("serve.harvest.wait", 312, 40, forced=1),
        _span("serve.tick", 380, 45, step_num=3, prefill_lanes=0,
              decode_lanes=2),
        _span("serve.dispatch.decode", 385, 2, lanes=2, steps=1),
    ]
    return TR.TraceEvents({DEV: modules}, {DEV: ops}, host)


def test_programs_dispatches_and_counts():
    tr = TR.reduce(_trace())
    assert tr.window_s == pytest.approx(300e-9)
    assert tr.programs["jit_prefill_chunk"] == {
        "modules": 1, "device_s": pytest.approx(40e-9), "idle_s": 0.0}
    # three decode modules start in the window; the last is clipped at 400
    assert tr.programs["jit_decode_block"] == {
        "modules": 3, "device_s": pytest.approx(50e-9), "idle_s": 0.0}
    pre, dec = tr.spans["serve.dispatch.prefill"], tr.spans[
        "serve.dispatch.decode"]
    assert pre["count"] == 1 and pre["args"] == {"lanes": 1, "tokens": 300,
                                                 "positions": 2048}
    assert dec["count"] == 3 and dec["args"] == {"lanes": 5, "steps": 3}
    assert dec["seconds"] == pytest.approx(6e-9)
    # the tick span's numeric args are summed; "_"-keys are the profiler's
    assert tr.spans["serve.tick"]["count"] == 3
    assert tr.spans["serve.tick"]["args"] == {
        "step_num": 6, "prefill_lanes": 1, "decode_lanes": 5}
    assert tr.span_arg("serve.dispatch.prefill", "tokens") == 300
    assert tr.span_arg("serve.no_such_span", "tokens") == 0
    assert tr.covered_s == tr.window_s and tr.cut_s == 0
    # every op in the window, by name, the last clipped by nothing: ops keep
    # their whole duration
    assert tr.ops == {"fusion.1": pytest.approx(40e-9),
                      "while.2": pytest.approx(65e-9),
                      "fusion.3": pytest.approx(5e-9)}


def test_idle_goes_to_the_innermost_phase_and_sums_to_the_window_idle():
    tr = TR.reduce(_trace())
    # busy [130,170) [175,195) [330,350) [390,400): 90 of 300 ns
    assert tr.busy_s == pytest.approx(90e-9)
    assert tr.idle_s == pytest.approx(210e-9)
    idle = {name: s["idle_s"] for name, s in tr.spans.items()}
    assert idle["serve.admit"] == pytest.approx(10e-9)
    assert idle["serve.plan"] == pytest.approx(5e-9)
    assert idle["serve.dispatch.prefill"] == pytest.approx(3e-9)
    assert idle["serve.harvest.wait"] == pytest.approx((5 + 2 + 18 + 2) * 1e-9)
    assert idle["serve.harvest.apply"] == pytest.approx(8e-9)
    assert idle["serve.finish"] == pytest.approx(5e-9)
    assert idle["serve.dispatch.decode"] == pytest.approx(4e-9)
    # a tick holds its phases: its idle less theirs is its own, and with
    # the idle outside any tick it sums to the window's idle
    assert idle["serve.tick"] == pytest.approx(90e-9)
    phases = sum(v for name, v in idle.items() if name != "serve.tick")
    assert idle["serve.tick"] - phases == pytest.approx(
        (2 + 10 + 8 + 5 + 3) * 1e-9)
    outside = (10 + 90 + 20) * 1e-9
    assert idle["serve.tick"] + outside == pytest.approx(tr.idle_s)
    # every module's ops fill it: no idle inside a program
    assert all(p["idle_s"] == 0 for p in tr.programs.values())


def test_idle_between_the_ops_of_a_running_program_is_told_apart():
    ev = _trace()
    # the decode module [175, 195) ran two ops with a gap [180, 190)
    ev.ops[DEV][1] = _ev("while.2", 175, 5)
    tr = TR.reduce(ev)
    assert tr.idle_s == pytest.approx(220e-9)
    assert tr.programs["jit_decode_block"]["idle_s"] == pytest.approx(10e-9)
    assert tr.programs["jit_prefill_chunk"]["idle_s"] == 0
    assert tr.spans["serve.harvest.wait"]["idle_s"] == pytest.approx(
        (5 + 2 + 18 + 2 + 10) * 1e-9)


def _run(tr, ticks=(), counters=None):
    cfg = json.loads((ROOT / "chipbench/configs/phi4-mini-3.8b.json")
                     .read_text())
    return Run(t0=0.0, t1=300e-9, setup_s=1.0, loop="open", requests=[],
               ticks=list(ticks), work=Work(cfg, 1024), peaks=PEAKS,
               trace=tr, counters=dict(counters or {}))


def _read(name, run):
    return harness.load_reader(name)(run)


def test_readings_from_the_reduction():
    # the trace's spans, on the host clock of a window opening at 0
    ticks = [Tick(10e-9, 110e-9, 1, 1, [(0, 300, True)], 1, 1, [301]),
             Tick(200e-9, 260e-9, 0, 1, [], 1, 1, [302, 40]),
             Tick(280e-9, 325e-9, 0, 1, [], 1, 1, [303, 41])]
    run = _run(TR.reduce(_trace()), ticks,
               {"prefill_tokens": 300, "prefill_positions": 2048,
                "decode_steps": 3})
    assert _read("decode_program_ms", run) == pytest.approx(50e-9 * 1e3 / 3)
    assert _read("prefill_program_ms_per_ktok", run) == pytest.approx(
        40e-9 * 1e3 / 0.3)
    assert _read("prefill_padding_share", run) == pytest.approx(
        100 * (1 - 300 / 2048))
    assert _read("step_idle_share", run) == pytest.approx(100 * 90 / 300)
    w = run.work
    least = sum(w.decode_step_least_s(1, t.decode_keys, PEAKS)
                for t in ticks)
    # at one to two lanes the bytes set the least time: the weights once
    # per step and each lane's K/V rows
    keys = sum(k for t in ticks for k in t.decode_keys)
    assert least == pytest.approx(
        (3 * w.step_weight_bytes() + keys * w.kv_bytes_per_token()) / 819e9)
    assert _read("decode_roofline", run) == pytest.approx(
        100 * least / 50e-9)


def test_an_unnamed_program_reads_no_step_numbers():
    """An engine whose programs are all ``jit_step`` and that opens no
    ``serve.*`` span: the program is still keyed by its name, no step
    reader reads anything, and nothing raises."""
    ev = TR.TraceEvents({DEV: [_ev("jit_step(1)", 10, 5)]},
                        {DEV: [_ev("fusion", 10, 5)]},
                        [_span(TR.WINDOW, 0, 100)])
    tr = TR.reduce(ev)
    assert tr.programs == {"jit_step": {"modules": 1, "idle_s": 0.0,
                                        "device_s": pytest.approx(5e-9)}}
    assert tr.spans == {}
    assert tr.device_ops == [("jit_step:fusion", pytest.approx(5e-9))]
    run = _run(tr)
    for name in ("decode_program_ms", "prefill_program_ms_per_ktok",
                 "prefill_padding_share", "step_idle_share",
                 "decode_roofline"):
        assert _read(name, run) is None, name


def test_no_window_is_an_error():
    with pytest.raises(ValueError):
        TR.reduce(TR.TraceEvents({}, {}, []))


def test_a_trace_that_lost_its_tail_is_cut_where_the_device_events_end():
    """Dispatch spans go on after the last module event: the device
    trace was cut, and what follows must not read as idle under a span,
    nor pair the spans' steps with program time it lacks."""
    ev = _trace()
    ev.modules[DEV] = [m for m in ev.modules[DEV] if m.start < 300]
    ev.ops[DEV] = [o for o in ev.ops[DEV] if o.start < 300]
    tr = TR.reduce(ev)
    assert tr.cut_s == pytest.approx(205e-9)
    assert tr.covered_s == pytest.approx(95e-9)
    assert tr.programs["jit_decode_block"]["modules"] == \
        tr.spans["serve.dispatch.decode"]["count"] == 1
    assert tr.spans["serve.tick"]["count"] == 1
    # busy [130,170) [175,195) in [100,195)
    assert tr.idle_s == pytest.approx(35e-9)
    assert tr.spans["serve.tick"]["idle_s"] == pytest.approx(25e-9)
    # the whole window still counts for the device's busy share
    assert tr.window_s == pytest.approx(300e-9)
    assert tr.busy_s == pytest.approx(60e-9)
    run = _run(tr, counters={"decode_steps": 3})
    assert _read("decode_program_ms", run) == pytest.approx(20e-9 * 1e3)


@pytest.fixture(scope="module")
def tiny_arch():
    tiny.register_tiny_arch()


ROUTE_READER = '''"""Made-up per-layer metric: tokens routed per routing span, and the
engine's routed-token counter."""


def read(run):
    s = run.trace.spans.get("serve.route")
    if not s:
        return None
    return s["args"]["routed"] / s["count"] + 1000 * run.counters["routed"]
'''


def test_tiny_run_under_the_profiler_reads_the_engine_spans(
        tiny_arch, tmp_path, monkeypatch):
    """The harness's traced run on the CPU: the engine's spans and
    counters agree with the ticks the client saw, and a span and a
    counter the harness has never heard of reach a reader that is one new
    file."""
    import jax
    from repro.serve import engine as eng
    real_step = eng.ServeEngine.step

    def step(self, *a, **kw):
        self.stats["routed"] = self.stats.get("routed", 0) + 2
        with jax.profiler.TraceAnnotation("serve.route", routed=3):
            return real_step(self, *a, **kw)

    monkeypatch.setattr(eng.ServeEngine, "step", step)
    metrics = tmp_path / "metrics"
    metrics.mkdir()
    for f in (harness.METRICS).glob("*.py"):
        (metrics / f.name).write_text(f.read_text())
    (metrics / "route_made_up.py").write_text(ROUTE_READER)
    monkeypatch.setattr(harness, "METRICS", metrics)
    cell = tiny.tiny_cell("open")
    cell.per_layer = [{"name": n, "unit": "x"} for n in (
        "route_made_up", "prefill_padding_share", "decode_occupancy",
        "step_idle_share", "decode_program_ms")]
    out = harness.run_cell(cell, 2 ** 33 + 7, 2.0, True, time.perf_counter(),
                           check=False)
    run, tr = out.run, out.run.trace
    c = run.counters
    ticks = run.ticks
    assert tr.devices == 0 and tr.cut_s == 0
    assert tr.spans["serve.tick"]["count"] == len(ticks) == c["ticks"]
    # the prefill spans carry the real prompt tokens the ticks prefilled
    pre = tr.spans["serve.dispatch.prefill"]
    assert pre["count"] == sum(t.prefill_dispatches for t in ticks) > 0
    assert pre["args"]["tokens"] == sum(
        n for t in ticks for _s, n, _l in t.prefill_chunks) == \
        c["prefill_tokens"]
    assert pre["args"]["positions"] == c["prefill_positions"]
    dec = tr.spans["serve.dispatch.decode"]
    assert c["decode_steps"] == sum(t.decode_steps for t in ticks) == \
        dec["args"]["steps"] > 0
    assert c["decode_dispatches"] == dec["count"]
    # the CPU has no device plane: no program time, no device idle
    assert tr.programs == {} and tr.busy_s == 0
    assert c["routed"] == 2 * len(ticks)
    assert set(out.metrics) == {"route_made_up", "prefill_padding_share",
                                "decode_occupancy"}
    assert out.metrics["route_made_up"]["value"] == pytest.approx(
        3 + 1000 * 2 * len(ticks))
    assert 0 < out.metrics["prefill_padding_share"]["value"] < 100
