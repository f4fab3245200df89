"""Telemetry-plane conformance suite.

The serving telemetry plane (``serve/telemetry.py``) promises to be a
pure observer: with ``telemetry=True`` the engine emits metrics,
request-lifecycle trace spans, and in-graph A^3 quality probes, and

* token streams are bit-identical to the untelemetered engine across
  every mixer kind it serves (attention, A^3, RG-LRU hybrid, xLSTM),
* the deterministic scheduling counters — including ``host_syncs``,
  the zero-new-syncs contract (probe arrays ride the already-landing
  deferred ring drain) — are identical,
* what the plane reports reconciles with the engine's own counters:
  TTFT observations match terminal counts, per-request attributed
  decode steps match ``decode_steps_advanced``, probed dispatches
  match ``ceil(decode_dispatches / telemetry_every)``,
* the Chrome-trace export round-trips through ``json`` and per-slot
  timelines are monotone,
* and histogram state survives the engine checkpoint/restore cycle.

Pure-host unit tests for the registry/histogram/tracer primitives run
first; they need no device dispatch at all.
"""
from __future__ import annotations

import json
import math

import numpy as np
import pytest

import jax

from repro.config import A3Config, ModelConfig, ServeConfig
from repro.models import decoder as dec
from repro.serve.engine import ServeEngine
from repro.serve.telemetry import (Histogram, MetricsRegistry, Tracer,
                                   _COUNT_BUCKET_BOUNDS)

from test_serve_pipeline import TINY, TINY_RG, TINY_XL, KINDS  # noqa: F401

MAX_LEN = 96
MAX_NEW = 6
PROMPT_LENS = (5, 12, 23, 9)

# Wall-clock-derived stats: these differ between ANY two runs (the
# harvest races the device's is_ready() against real time), telemetry
# or not, so the bit-identity comparisons exclude them. Everything else
# must match exactly.
WALL_STATS = ("host_sync_stalls",)


@pytest.fixture(scope="module")
def all_params():
    return {
        "tiny": dec.init_params(jax.random.PRNGKey(0), TINY),
        "tiny-rg": dec.init_params(jax.random.PRNGKey(1), TINY_RG),
        "tiny-xl": dec.init_params(jax.random.PRNGKey(2), TINY_XL),
    }


def _prompts(vocab, seed=7):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, vocab, size=n) for n in PROMPT_LENS]


def _det_stats(eng):
    return {k: v for k, v in eng.stats.items() if k not in WALL_STATS}


def _run(params, cfg, prompts, *, a3=A3Config(), telemetry=False,
         max_new=MAX_NEW, **kw):
    eng = ServeEngine(params, cfg, slots=2, max_len=MAX_LEN, a3=a3,
                      prefill_chunk=8, decode_block=2,
                      telemetry=telemetry, **kw)
    uids = [eng.submit(p, max_new_tokens=max_new) for p in prompts]
    eng.run_to_completion()
    return [eng.result(u) for u in uids], eng


# ---------------------------------------------------------------------------
# registry / histogram / tracer unit tests (no device work)
# ---------------------------------------------------------------------------

def test_histogram_buckets_total_sum_quantile():
    h = Histogram("h", (10.0, 100.0, 1000.0))
    for v in (1, 10, 11, 100, 5000):
        h.observe(v)
    # upper-inclusive edges + one overflow bucket
    assert list(h.counts) == [2, 2, 0, 1]
    assert h.total == 5 and h.sum == 5122.0
    assert h.quantile(0.5) == 100.0
    assert h.quantile(1.0) == float("inf")      # overflow bucket
    assert Histogram("e", (1.0,)).quantile(0.99) == 0.0


def test_histogram_snapshot_load_roundtrip():
    h = Histogram("h", _COUNT_BUCKET_BOUNDS)
    for v in (1, 7, 300, 10 ** 9):
        h.observe(v)
    snap = h.snapshot()
    # snapshot is JSON-clean (checkpoints serialize it verbatim)
    snap = json.loads(json.dumps(snap))
    h2 = Histogram("h", _COUNT_BUCKET_BOUNDS)
    h2.load(snap)
    assert h2.snapshot() == h.snapshot()
    # a bounds mismatch refuses the load instead of mis-bucketing
    h3 = Histogram("h", (1.0, 2.0))
    h3.load(snap)
    assert h3.total == 0


def test_registry_idempotent_handles_and_stats_view():
    r = MetricsRegistry()
    c = r.counter("reqs")
    assert r.counter("reqs") is c
    c.inc()
    c.inc(2.5)
    stats = {"ticks": 3}
    r.attach_stats("serve_", stats)
    stats["ticks"] = 7          # live reference, not a copy
    snap = r.snapshot()
    assert snap["counters"]["reqs"] == 3.5
    assert snap["counters"]["serve_ticks"] == 7.0
    assert snap["schema"] == "a3-serve-metrics/v1"


def test_prometheus_exposition_format():
    r = MetricsRegistry()
    r.counter("reqs").inc(2)
    r.gauge("depth").set(1.5)
    h = r.histogram("lat_ns{terminal=finished}", (10.0, 100.0))
    h.observe(5)
    h.observe(50)
    h.observe(5000)
    text = r.to_prometheus()
    lines = text.splitlines()
    assert "# TYPE reqs counter" in lines and "reqs 2" in lines
    assert "depth 1.5" in lines
    # labeled histogram: values quoted, le merged, buckets cumulative
    assert 'lat_ns_bucket{terminal="finished",le="10"} 1' in lines
    assert 'lat_ns_bucket{terminal="finished",le="100"} 2' in lines
    assert 'lat_ns_bucket{terminal="finished",le="+Inf"} 3' in lines
    assert 'lat_ns_count{terminal="finished"} 3' in lines


def test_tracer_ring_drops_oldest_and_counts():
    tr = Tracer(max_events=4)
    for i in range(7):
        tr.instant(f"e{i}", ts_ns=i)
    assert tr.dropped == 3
    ct = tr.chrome_trace()
    assert [e["name"] for e in ct["traceEvents"]] == ["e3", "e4", "e5", "e6"]
    assert ct["otherData"]["dropped_events"] == 3
    json.dumps(ct)              # export is always JSON-serializable


# ---------------------------------------------------------------------------
# pure-observer contract: telemetry on == off, bit for bit
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kind", sorted(KINDS))
def test_telemetry_off_bit_identity(kind, all_params):
    cfg, a3 = KINDS[kind]
    prompts = _prompts(cfg.vocab_size)
    toks_off, eng_off = _run(all_params[cfg.name], cfg, prompts, a3=a3)
    toks_on, eng_on = _run(all_params[cfg.name], cfg, prompts, a3=a3,
                           telemetry=True, telemetry_every=2)
    assert toks_on == toks_off
    assert _det_stats(eng_on) == _det_stats(eng_off)
    # the headline of the zero-overhead contract, stated explicitly:
    # probes and spans added not one blocking device read
    assert eng_on.stats["host_syncs"] == eng_off.stats["host_syncs"]
    assert eng_on.tm is not None and eng_off.tm is None


def test_telemetry_off_is_default_and_hookless(all_params):
    eng = ServeEngine(all_params["tiny"], TINY, slots=1, max_len=MAX_LEN)
    assert eng.tm is None
    assert eng._decode_block_probe is None


# ---------------------------------------------------------------------------
# reconciliation: reported metrics vs engine counters
# ---------------------------------------------------------------------------

def test_ttft_and_decode_step_reconciliation(all_params):
    # slots=1 serializes lanes, so per-request attributed decode steps
    # must equal decode_steps_advanced EXACTLY (no padding ambiguity)
    eng = ServeEngine(all_params["tiny"], TINY, slots=1, max_len=MAX_LEN,
                      prefill_chunk=8, decode_block=3, telemetry=True)
    attributed = {}
    orig = eng.tm.on_decode_steps

    def record(uid, steps):
        attributed[uid] = attributed.get(uid, 0) + steps
        orig(uid, steps)

    eng.tm.on_decode_steps = record
    prompts = _prompts(TINY.vocab_size)
    uids = [eng.submit(p, max_new_tokens=MAX_NEW) for p in prompts]
    eng.run_to_completion()
    assert all(eng.status(u) == "finished" for u in uids)
    assert sum(attributed.values()) == eng.stats["decode_steps_advanced"]

    snap = eng.tm.metrics_snapshot()
    h = snap["histograms"]
    ttft = h["serve_ttft_ns{terminal=finished}"]
    assert ttft["total"] == eng.stats["finished"] == len(prompts)
    # every finished request decoded at least one block -> one TPOT
    # observation each, and sojourn is keyed by the same terminal
    assert h["serve_tpot_ns"]["total"] == len(prompts)
    assert (h["serve_queue_sojourn_ns{terminal=finished}"]["total"]
            == len(prompts))
    # request tracking map drains with the requests (no leak)
    assert not eng.tm._reqs


def test_terminal_keyed_histograms_split_states(all_params):
    # a cancelled queued request lands in the cancelled sojourn/ttft
    # keys, not the finished ones
    eng = ServeEngine(all_params["tiny"], TINY, slots=1, max_len=MAX_LEN,
                      prefill_chunk=8, telemetry=True)
    u1 = eng.submit(np.arange(5, dtype=np.int32), max_new_tokens=2)
    u2 = eng.submit(np.arange(7, dtype=np.int32), max_new_tokens=2)
    eng.cancel(u2)
    eng.run_to_completion()
    assert eng.status(u1) == "finished" and eng.status(u2) == "cancelled"
    h = eng.tm.metrics_snapshot()["histograms"]
    assert h["serve_ttft_ns{terminal=finished}"]["total"] == 1
    # u2 never reached a slot: no admission -> no sojourn, no TTFT
    assert "serve_ttft_ns{terminal=cancelled}" not in h
    assert "serve_queue_sojourn_ns{terminal=cancelled}" not in h


@pytest.mark.parametrize("every", [1, 3])
def test_a3_probe_dispatch_reconciliation(every, all_params):
    prompts = _prompts(TINY.vocab_size)
    toks, eng = _run(all_params["tiny"], TINY, prompts,
                     a3=A3Config.conservative(), telemetry=True,
                     telemetry_every=every)
    snap = eng.tm.metrics_snapshot()
    nd = eng.stats["decode_dispatches"]
    assert nd > 0
    # the probe rides every telemetry_every-th dispatch, starting with
    # the first (counter % every == 0 pre-increment)
    assert (snap["counters"]["serve_a3_probe_dispatches"]
            == math.ceil(nd / every))
    # samples count (lane, step) pairs: every advanced step of every
    # live lane in a probed dispatch
    samples = snap["counters"]["serve_a3_probe_samples"]
    assert 0 < samples <= len(eng.slots) * eng.stats["decode_steps"]
    if every == 1:              # all dispatches probed: each advanced
        # step contributed at least one live lane
        assert samples >= eng.stats["decode_steps_advanced"]
    mass = snap["histograms"]["serve_a3_captured_mass"]
    cand = snap["histograms"]["serve_a3_candidates"]
    assert mass["total"] == cand["total"] > 0
    # captured-score-mass ratio is a fraction of the full softmax mass
    # measured from the same f32 scores: (0, 1] by construction
    assert 0.0 < mass["sum"] / mass["total"] <= 1.0
    assert cand["sum"] / cand["total"] >= 1.0


def test_probe_absent_without_a3(all_params):
    prompts = _prompts(TINY.vocab_size)
    _, eng = _run(all_params["tiny"], TINY, prompts, telemetry=True,
                  telemetry_every=1)
    assert eng._decode_block_probe is None
    snap = eng.tm.metrics_snapshot()
    assert snap["counters"]["serve_a3_probe_dispatches"] == 0
    assert snap["histograms"]["serve_a3_captured_mass"]["total"] == 0


# ---------------------------------------------------------------------------
# trace export
# ---------------------------------------------------------------------------

def test_trace_export_roundtrip_and_slot_monotonicity(all_params,
                                                      tmp_path):
    prompts = _prompts(TINY.vocab_size)
    _, eng = _run(all_params["tiny"], TINY, prompts,
                  a3=A3Config.conservative(), telemetry=True,
                  telemetry_every=2, page_size=8, cache_pages=16)
    path = tmp_path / "trace.json"
    eng.tm.write_trace(str(path))
    tr = json.loads(path.read_text())
    assert tr["otherData"]["schema"] == "a3-serve-trace/v1"
    evs = tr["traceEvents"]
    assert evs
    names = {e["name"] for e in evs}
    # the request lifecycle appears end to end
    for must in ("submit", "queued", "admit", "prefill", "first_token",
                 "decode_block", "terminal"):
        assert must in names, (must, sorted(names))
    # every span/instant carries a non-negative relative timestamp and
    # per-SLOT timelines are monotone in emission order (the harvest
    # lands tick-synchronously at depth 0, so a slot's spans replay in
    # dispatch order)
    by_slot = {}
    for e in evs:
        assert e["ts"] >= 0.0
        if isinstance(e["tid"], int):
            by_slot.setdefault(e["tid"], []).append(e["ts"])
    assert by_slot
    for tid, ts in by_slot.items():
        assert ts == sorted(ts), f"slot {tid} timeline not monotone"
    # lifecycle events carry their request uid
    assert all("uid" in e["args"] for e in evs
               if e["name"] in ("submit", "terminal"))


def test_trace_ring_bounded_under_pressure(all_params):
    prompts = _prompts(TINY.vocab_size) * 3
    _, eng = _run(all_params["tiny"], TINY, prompts, telemetry=True,
                  trace_events=16)
    assert len(eng.tm.tracer.events) == 16
    snap = eng.tm.metrics_snapshot()
    assert snap["counters"]["serve_trace_events_dropped"] > 0


# ---------------------------------------------------------------------------
# metrics through checkpoint/restore
# ---------------------------------------------------------------------------

def test_telemetry_checkpoint_roundtrip(all_params, tmp_path):
    eng = ServeEngine(all_params["tiny"], TINY, slots=2, max_len=MAX_LEN,
                      prefill_chunk=8, decode_block=2, telemetry=True,
                      a3=A3Config.conservative(), telemetry_every=2)
    prompts = _prompts(TINY.vocab_size)
    uids = [eng.submit(p, max_new_tokens=MAX_NEW) for p in prompts]
    for _ in range(6):          # park mid-flight state in the histograms
        eng.step()
    eng.checkpoint(str(tmp_path))
    before = eng.tm.metrics_snapshot()["histograms"]
    assert any(h["total"] > 0 for h in before.values())

    eng2 = ServeEngine.restore(str(tmp_path), all_params["tiny"], TINY,
                               a3=A3Config.conservative())
    assert eng2.tm is not None
    after = eng2.tm.metrics_snapshot()["histograms"]
    assert after == before      # bucket-exact across the round trip
    # the restored engine keeps observing into the SAME histograms.
    # Requests mid-flight at checkpoint time deliberately get no TTFT
    # (their monotonic-clock tracks died with the old process — the
    # tracer is a flight recorder, the histograms are the durable
    # record), but requests submitted after the restore are tracked
    # end to end on top of the restored counts.
    ttft_key = "serve_ttft_ns{terminal=finished}"
    ttft_before = before.get(ttft_key, {"total": 0})["total"]
    fresh = [eng2.submit(p, max_new_tokens=2)
             for p in _prompts(TINY.vocab_size, seed=11)[:2]]
    eng2.run_to_completion()
    final = eng2.tm.metrics_snapshot()["histograms"]
    assert final[ttft_key]["total"] == ttft_before + len(fresh)
    assert all(eng2.status(u) == "finished" for u in uids + fresh)


def test_old_checkpoint_without_telemetry_restores(all_params, tmp_path):
    # a checkpoint written by an untelemetered engine (or a pre-
    # telemetry version: no "telemetry" key) restores cleanly
    eng = ServeEngine(all_params["tiny"], TINY, slots=1, max_len=MAX_LEN,
                      prefill_chunk=8)
    eng.submit(np.arange(5, dtype=np.int32), max_new_tokens=3)
    eng.step()
    eng.checkpoint(str(tmp_path))
    eng2 = ServeEngine.restore(str(tmp_path), all_params["tiny"], TINY)
    assert eng2.tm is None
    eng2.run_to_completion()
    assert eng2.stats["finished"] == 1
