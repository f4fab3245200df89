"""CPU tests of the step-program reduction (``chipbench/programtrace.py``):
synthetic traces with hand-checked answers, and one tiny run of the
harness under the profiler.

    JAX_PLATFORMS=cpu python3 -m pytest -q chipbench/tests
"""
from __future__ import annotations

import sys
import time
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(Path(__file__).resolve().parent))

from chipbench import programtrace as PT  # noqa: E402
from chipbench import tracereduce as TR  # noqa: E402

import tiny  # noqa: E402

DEV = "/device:TPU:0"


def _span(name, start, dur, **args):
    return PT.Span(name, float(start), float(dur), args)


def _trace():
    """Window [100, 400). Three ticks: the first prefills then decodes,
    the second and third decode (the third runs past the window); the
    client waits between them."""
    modules = [TR.Event("jit_prefill_chunk(3)", 130, 40),
               TR.Event("jit_decode_block(4)", 175, 20),
               TR.Event("jit_decode_block(4)", 330, 20),
               TR.Event("jit_decode_block(4)", 390, 30)]  # ends after the window
    ops = [TR.Event("fusion.1", 130, 40), TR.Event("while.2", 175, 15),
           TR.Event("fusion.3", 190, 5), TR.Event("while.2", 330, 20),
           TR.Event("while.2", 390, 30)]
    spans = [
        _span("serve.tick", 110, 100, step_num=1, prefill_lanes=1,
              decode_lanes=1),
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
    return PT.ProgramEvents({DEV: modules}, {DEV: ops}, spans,
                            TR.Event(TR.WINDOW, 100, 300))


def test_programs_dispatches_and_counts():
    red = PT.reduce(_trace())
    assert red["window_s"] == pytest.approx(300e-9)
    assert red["programs"]["jit_prefill_chunk"] == {
        "modules": 1, "device_s": pytest.approx(40e-9)}
    # three decode modules start in the window; the last is clipped at 400
    assert red["programs"]["jit_decode_block"] == {
        "modules": 3, "device_s": pytest.approx(50e-9)}
    assert red["dispatches"] == {"prefill": 1, "prefill_tokens": 300,
                                 "prefill_positions": 2048, "decode": 3,
                                 "decode_steps": 3}
    assert red["ticks"] == 3
    assert red["covered_s"] == red["window_s"] and red["cut_s"] == 0


def test_idle_goes_to_the_innermost_phase_and_sums_to_the_window_idle():
    red = PT.reduce(_trace())
    # busy [130,170) [175,195) [330,350) [390,400): 90 of 300 ns
    assert red["idle_s"] == pytest.approx(210e-9)
    by = red["idle_by_phase"]
    assert sum(by.values()) == pytest.approx(red["idle_s"])
    assert by["outside_engine"] == pytest.approx((10 + 90 + 20) * 1e-9)
    assert by["serve.admit"] == pytest.approx(10e-9)
    assert by["serve.plan"] == pytest.approx(5e-9)
    assert by["serve.dispatch.prefill"] == pytest.approx(3e-9)
    # a tick's own time: between and around its phases
    assert by["serve.tick"] == pytest.approx((2 + 10 + 8 + 5 + 3) * 1e-9)
    assert by["serve.harvest.wait"] == pytest.approx((5 + 2 + 18 + 2) * 1e-9)
    assert by["serve.harvest.apply"] == pytest.approx(8e-9)
    assert by["serve.finish"] == pytest.approx(5e-9)
    assert by["serve.dispatch.decode"] == pytest.approx(4e-9)
    assert red["idle_in_tick_s"] == pytest.approx(90e-9)
    # every module's ops fill it: no idle inside a program
    assert sum(red["in_program_idle_by_phase"].values()) == 0


def test_idle_between_the_ops_of_a_running_program_is_told_apart():
    ev = _trace()
    # the decode module [175, 195) ran two ops with a gap [180, 190)
    ev.ops[DEV][1] = TR.Event("while.2", 175, 5)
    red = PT.reduce(ev)
    assert red["idle_s"] == pytest.approx(220e-9)
    assert red["in_program_idle_by_phase"] == {
        "serve.harvest.wait": pytest.approx(10e-9)}
    assert red["idle_by_phase"]["serve.harvest.wait"] == pytest.approx(
        (5 + 2 + 18 + 2 + 10) * 1e-9)


def test_readings_from_the_reduction():
    r = PT.readings(PT.reduce(_trace()))
    assert r["decode_program_ms"] == pytest.approx(50e-9 * 1e3 / 3)
    assert r["prefill_program_ms_per_ktok"] == pytest.approx(
        40e-9 * 1e3 / 0.3)
    assert r["prefill_padding_share"] == pytest.approx(
        100 * (1 - 300 / 2048))
    assert r["step_idle_share"] == pytest.approx(100 * 90 / 300)


def test_an_unnamed_program_reads_no_step_numbers():
    """An engine whose programs are all ``jit_step`` and that opens no
    ``serve.*`` span: tables stay empty, no reading, nothing raises."""
    ev = PT.ProgramEvents({DEV: [TR.Event("jit_step(1)", 10, 5)]},
                          {DEV: [TR.Event("fusion", 10, 5)]}, [],
                          TR.Event(TR.WINDOW, 0, 100))
    red = PT.reduce(ev)
    assert red["programs"] == {"jit_step": {"modules": 1,
                                            "device_s": pytest.approx(5e-9)}}
    assert red["idle_by_phase"] == {"outside_engine": pytest.approx(95e-9)}
    assert PT.readings(red) == {}


def test_no_window_is_an_error():
    with pytest.raises(ValueError):
        PT.reduce(PT.ProgramEvents({}, {}, [], None))


def test_a_trace_that_lost_its_tail_is_cut_where_the_device_events_end():
    """Dispatch spans go on after the last module event: the device
    trace was cut, and what follows must not read as idle."""
    ev = _trace()
    ev.modules[DEV] = [m for m in ev.modules[DEV] if m.start < 300]
    ev.ops[DEV] = [o for o in ev.ops[DEV] if o.start < 300]
    red = PT.reduce(ev)
    assert red["cut_s"] == pytest.approx(205e-9)
    assert red["covered_s"] == pytest.approx(95e-9)
    assert red["programs"]["jit_decode_block"]["modules"] == \
        red["dispatches"]["decode"] == 1
    assert red["ticks"] == 1
    # busy [130,170) [175,195) in [100,195)
    assert red["idle_s"] == pytest.approx(35e-9)
    assert sum(red["idle_by_phase"].values()) == pytest.approx(35e-9)


@pytest.fixture(scope="module")
def tiny_arch():
    tiny.register_tiny_arch()


def test_tiny_run_under_the_profiler_reads_the_engine_spans(tiny_arch):
    out = PT.profile_cell(tiny.tiny_cell("open"), 2 ** 33 + 7, 2.0,
                          time.perf_counter())
    red = out["trace"]
    d = red["dispatches"]
    assert red["ticks"] > 0 and d["decode"] > 0 and d["prefill"] > 0
    assert 0 < d["prefill_tokens"] < d["prefill_positions"]
    # the CPU has no device plane: no device numbers, only the padding
    assert red["devices"] == 0 and red["programs"] == {}
    assert set(out["readings"]) == {"prefill_padding_share"}
    assert set(out["metrics"]) >= {"output_tok_s", "itl_p99_ms"}
