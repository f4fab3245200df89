"""The engine's step programs and tick phases, as a profiler sees them.

* every jitted dispatch lowers to a module named after its program
  (``jit_decode_block``, ``jit_prefill_chunk``, ...), in both
  temperature branches, so a device trace tells the programs apart;
* a CPU ``jax.profiler`` trace of a few ticks holds one ``serve.tick``
  per tick, every ``serve.*`` phase inside its tick, and one dispatch
  span per dispatch the engine counted;
* ``stats["prefill_positions"]`` counts the positions of every packed
  ``[b, chunk]`` prefill dispatch (``b`` summed in
  ``stats["prefill_lanes_computed"]``), beside the real
  ``prefill_tokens``;
* with telemetry on, the trace ring holds the same phases;
* the profiler is a pure observer: token streams do not change.
"""
from __future__ import annotations

import json
import re
import zlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.config import A3Config
from repro.models import decoder as dec
from repro.serve import telemetry
from repro.serve.engine import CTRL_COLS, ServeEngine, make_serve_step

from test_serve_pipeline import TINY

MAX_LEN = 96
MAX_NEW = 6
PROMPT_LENS = (5, 12, 23, 9)
PHASES = ("serve.tick", "serve.admit", "serve.plan",
          "serve.dispatch.prefill", "serve.prefill.book",
          "serve.dispatch.decode", "serve.harvest.wait",
          "serve.harvest.apply", "serve.finish")


@pytest.fixture(scope="module")
def params():
    return dec.init_params(jax.random.PRNGKey(0), TINY)


def _prompts(seed=7):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, TINY.vocab_size, size=n) for n in PROMPT_LENS]


def _engine(params, **kw):
    kw = {"slots": 2, "max_len": MAX_LEN, "prefill_chunk": 8,
          "decode_block": 2, **kw}
    return ServeEngine(params, TINY, **kw)


def _serve(eng, prompts=None):
    uids = [eng.submit(p, max_new_tokens=MAX_NEW)
            for p in (prompts or _prompts())]
    eng.run_to_completion()
    return [eng.result(u) for u in uids]


def _module(lowered) -> str:
    return re.search(r"module @(\w+)", lowered.as_text()).group(1)


# ---------------------------------------------------------------------------
# program names
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("temperature", [0.0, 0.7])
@pytest.mark.parametrize("program, a3, want", [
    ("_decode_block", False, "jit_decode_block"),
    ("_decode_block_probe", True, "jit_decode_block_probe"),
    ("_prefill", False, "jit_prefill_chunk"),
    ("_prefill_nosort", True, "jit_prefill_chunk_nosort"),
])
def test_engine_programs_lower_to_named_modules(params, program, a3, want,
                                                temperature):
    eng = _engine(params, a3=A3Config.conservative() if a3 else A3Config(),
                  telemetry=a3, temperature=temperature)
    fn = getattr(eng, program)
    ctrl = jnp.zeros((2, CTRL_COLS), jnp.int32)
    tok = jnp.zeros((2,), jnp.int32)
    if program.startswith("_prefill"):
        args = (eng.params, eng.cache, jnp.zeros((2, 8), jnp.int32), ctrl)
    else:
        args = (eng.params, eng.cache, tok, tok, ctrl)
    if temperature > 0.0:
        args += (eng._sample_rng,)
    assert _module(fn.lower(*args)) == want


def test_serve_step_lowers_to_decode_step(params):
    cache = dec.init_cache(TINY, 2, MAX_LEN)
    tok = jnp.zeros((2,), jnp.int32)
    lowered = jax.jit(make_serve_step(TINY)).lower(params, cache, tok, tok)
    assert _module(lowered) == "jit_decode_step"


# ---------------------------------------------------------------------------
# tick phases under the profiler
# ---------------------------------------------------------------------------

def _host_spans(trace_dir):
    from jax.profiler import ProfileData
    path = next(trace_dir.glob("plugins/profile/*/*.xplane.pb"))
    spans = []
    for plane in ProfileData.from_file(str(path)).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                spans += [(e.name, e.start_ns, e.start_ns + e.duration_ns,
                           {n: v for n, v in e.stats})
                          for e in line.events
                          if e.name.startswith("serve.")]
    return sorted(spans, key=lambda s: (s[1], -s[2]))


@pytest.fixture(scope="module")
def profiled(params, tmp_path_factory):
    """A tiny engine served to the end under the profiler, and the same
    traffic on a second engine with the profiler off."""
    off = _serve(_engine(params))
    eng = _engine(params)
    trace_dir = tmp_path_factory.mktemp("profile")
    jax.profiler.start_trace(str(trace_dir))
    try:
        on = _serve(eng)
    finally:
        jax.profiler.stop_trace()
    return eng, _host_spans(trace_dir), on, off


def test_one_tick_span_per_tick_and_phases_nest_in_their_tick(profiled):
    eng, spans, _on, _off = profiled
    ticks = [s for s in spans if s[0] == "serve.tick"]
    assert len(ticks) == eng.stats["ticks"]
    assert [t[3]["step_num"] for t in ticks] == list(
        range(1, eng.stats["ticks"] + 1))
    assert {s[0] for s in spans} == set(PHASES)
    for name, start, end, _args in spans:
        if name == "serve.tick":
            continue
        assert any(t[1] <= start and end <= t[2] for t in ticks), name
    # the lane counts travel on the tick span
    assert sum(t[3]["prefill_lanes"] > 0 for t in ticks) == \
        eng.stats["prefill_dispatches"]
    assert sum(t[3]["decode_lanes"] > 0 for t in ticks) == \
        eng.stats["decode_dispatches"]


def test_dispatch_spans_match_the_engine_counters(profiled):
    eng, spans, _on, _off = profiled
    by = lambda n: [s[3] for s in spans if s[0] == n]
    pre, dec_ = by("serve.dispatch.prefill"), by("serve.dispatch.decode")
    assert len(pre) == eng.stats["prefill_dispatches"]
    assert len(dec_) == eng.stats["decode_dispatches"]
    assert sum(a["tokens"] for a in pre) == eng.stats["prefill_tokens"]
    assert sum(a["positions"] for a in pre) == \
        eng.stats["prefill_positions"]
    assert sum(a["width"] for a in pre) == \
        eng.stats["prefill_lanes_computed"]
    assert sum(a["steps"] for a in dec_) == eng.stats["decode_steps"]


def test_profiler_does_not_change_token_streams(profiled):
    _eng, _spans, on, off = profiled
    assert on == off
    assert all(len(r) == MAX_NEW for r in on)


# ---------------------------------------------------------------------------
# the padding counter
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("prefill_chunk, width, slots, n_prompts", [
    pytest.param(8, 8, 2, 4, id="8-8"),
    pytest.param(None, MAX_LEN, 2, 4, id="None-96"),
    pytest.param(8, 8, 4, 2, id="8-8-two-of-four-slots"),
])
def test_prefill_positions_count_the_padded_block(params, prefill_chunk,
                                                  width, slots, n_prompts):
    """Each prefill dispatch computes its prefilling lanes packed to the
    next power of two (at most ``slots``), ``width`` positions each."""
    eng = _engine(params, prefill_chunk=prefill_chunk, slots=slots,
                  telemetry=True, trace_events=1 << 16)
    _serve(eng, _prompts()[:n_prompts])
    st = eng.stats
    disp = [e[6] for e in eng.tm.tracer.events
            if e[2] == "serve.dispatch.prefill"]
    assert len(disp) == st["prefill_dispatches"]
    assert [d["width"] for d in disp] == [
        min(1 << (d["lanes"] - 1).bit_length(), slots) for d in disp]
    assert st["prefill_lanes_computed"] == sum(d["width"] for d in disp)
    assert st["prefill_positions"] == st["prefill_lanes_computed"] * width
    assert 0 < st["prefill_tokens"] <= st["prefill_positions"]
    assert st["prefill_tokens"] == sum(PROMPT_LENS[:n_prompts])
    if slots == 4:
        # two prompts prefill together in two of four lanes, then the
        # longer one alone in one
        assert [(d["lanes"], d["width"]) for d in disp] == [(2, 2), (1, 1)]


# ---------------------------------------------------------------------------
# the same phases in the telemetry ring
# ---------------------------------------------------------------------------

def test_telemetry_ring_holds_the_phase_spans(params):
    eng = _engine(params, telemetry=True, trace_events=1 << 16)
    _serve(eng)
    evs = [e for e in eng.tm.tracer.events if e[2].startswith("serve.")]
    assert {e[2] for e in evs} == set(PHASES)
    assert all(e[1] == "X" and e[4] == "engine" and e[5] >= 0 for e in evs)
    ticks = [e for e in evs if e[2] == "serve.tick"]
    assert len(ticks) == eng.stats["ticks"]
    assert all({"step_num", "prefill_lanes", "decode_lanes"} <= set(e[6])
               for e in ticks)
    # the Chrome-trace export carries them on the engine track
    names = {e["name"] for e in eng.tm.tracer.chrome_trace()["traceEvents"]
             if e["tid"] == "engine"}
    assert set(PHASES) <= names


def test_phase_is_inert_without_profiler_or_telemetry():
    with telemetry.phase("serve.x", None, a=1) as p:
        p.set(b=2)
    assert p.t0_ns == p.dur_ns == 0


def test_phase_records_span_with_late_args_in_the_ring():
    tm = telemetry.Telemetry(trace_events=8)
    with telemetry.phase("serve.tick", tm, step_num=3) as p:
        p.set(prefill_lanes=1)
    (ts, kind, name, uid, track, dur, args), = tm.tracer.events
    assert (kind, name, uid, track) == ("X", "serve.tick", -1, "engine")
    assert (ts, dur) == (p.t0_ns, p.dur_ns) and dur >= 0
    assert args == {"step_num": 3, "prefill_lanes": 1}


def test_restore_drops_stats_the_engine_no_longer_keeps(params, tmp_path):
    """A checkpoint written before the ``tick_ns_*`` stopwatch was
    removed restores with those keys dropped and the rest intact."""
    eng = _engine(params)
    for p in _prompts():
        eng.submit(p, max_new_tokens=MAX_NEW)
    for _ in range(4):
        eng.step()
    eng.checkpoint(str(tmp_path))
    path = tmp_path / "state.json"
    state = json.loads(path.read_bytes().split(b"\n", 1)[1])
    state["stats"]["tick_ns_host"] = 12345
    payload = json.dumps(state, sort_keys=True).encode()
    path.write_bytes(b"%d\n" % zlib.crc32(payload) + payload)
    eng2 = ServeEngine.restore(str(tmp_path), params, TINY)
    assert "tick_ns_host" not in eng2.stats
    assert set(eng2.stats) == set(eng.stats)
    assert eng2.stats["prefill_positions"] == eng.stats["prefill_positions"]
