"""Pipelined tick-loop conformance: deferred harvest is a pure
scheduling change.

The engine's ``pipeline_depth`` knob defers the per-block ring harvest
behind the dispatch stream: tick N's ``[slots, 1+T]`` harvest array is
read back only after up to ``depth`` newer dispatches have issued, the
next block's input tokens ride the device-resident cross-block carry,
and host bookkeeping acts on the one-tick-delayed view (optimistic
``pos``/``budget`` advance at dispatch, uid-guarded finish/poison
accounting at harvest). None of that may change WHAT is generated:

* ``pipeline_depth=1`` (and 2) is token-for-token identical to the
  synchronous ``pipeline_depth=0`` engine across all four mixer kinds
  the engine serves (attention, A^3 attention, RG-LRU hybrid, pure
  xLSTM) and across admission orders,
* ``pipeline_depth=0`` is bit-identical — tokens AND scheduling
  counters — to the default-constructed engine (the knob is opt-in;
  the historical engine is the ``depth=0`` special case),
* the lifecycle edges that now act on the delayed view stay correct:
  deadline expiry, cancel, and chaos poison quarantine under
  ``pipeline_depth=1`` terminate exactly one victim and leave every
  other request's stream untouched,
* the conservation identity ``submitted == finished + rejected +
  cancelled + expired + failed + in_flight`` closes after EVERY tick
  with harvests in flight,
* crash/restore with a deferred harvest in flight resumes
  token-for-token (checkpoints drain pending harvests first),
* and the perf counters move the right way: strictly fewer blocking
  ``host_syncs`` at depth 1 on a decode-heavy workload, and the
  carry-returning decode block lowering on the 8-device CI mesh.
"""
from __future__ import annotations

import time

import numpy as np
import pytest

import jax

from helpers import check, run_with_devices

from repro.config import A3Config, AttentionKind, BlockKind, ModelConfig
from repro.models import decoder as dec
from repro.serve.chaos import ChaosConfig, ChaosInjector, EngineCrash
from repro.serve.engine import ServeEngine

TINY = ModelConfig("tiny", "dense", num_layers=2, d_model=64, num_heads=4,
                   num_kv_heads=2, d_ff=128, vocab_size=256, head_dim=16,
                   dtype="float32")
TINY_RG = ModelConfig("tiny-rg", "hybrid", num_layers=3, d_model=64,
                      num_heads=4, num_kv_heads=2, d_ff=128,
                      vocab_size=256, head_dim=16,
                      attention_kind=AttentionKind.SLIDING, window_size=24,
                      block_pattern=(BlockKind.RGLRU, BlockKind.RGLRU,
                                     BlockKind.ATTENTION),
                      act="gelu", dtype="float32")
TINY_XL = ModelConfig("tiny-xl", "ssm", num_layers=3, d_model=64,
                      num_heads=4, num_kv_heads=4, d_ff=0, vocab_size=256,
                      head_dim=16,
                      block_pattern=(BlockKind.MLSTM, BlockKind.MLSTM,
                                     BlockKind.SLSTM),
                      dtype="float32")
MAX_LEN = 96
MAX_NEW = 6
PROMPT_LENS = (5, 12, 23, 9)

KINDS = {"attention": (TINY, A3Config()),
         "a3": (TINY, A3Config.conservative()),
         "rglru": (TINY_RG, A3Config()),
         "xlstm": (TINY_XL, A3Config())}


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


def _check_conservation(eng):
    s = eng.stats
    assert s["submitted"] == (s["finished"] + s["rejected"]
                              + s["cancelled"] + s["expired"]
                              + s["failed"] + eng.in_flight), s


def _run(params, cfg, prompts, *, a3=A3Config(), order="upfront",
         depth=0, decode_block=2, max_new=MAX_NEW, chaos=None, **kw):
    eng = ServeEngine(params, cfg, slots=2, max_len=MAX_LEN, a3=a3,
                      prefill_chunk=8, decode_block=decode_block,
                      pipeline_depth=depth, chaos=chaos, **kw)
    uids = {}
    if order == "upfront":
        for i, p in enumerate(prompts):
            uids[i] = eng.submit(p, max_new_tokens=max_new)
        eng.run_to_completion()
    elif order == "staggered":
        pending = list(enumerate(prompts))
        while pending or eng._queue or any(s.active for s in eng.slots):
            if pending and eng.stats["ticks"] % 2 == 0:
                i, p = pending.pop(0)
                uids[i] = eng.submit(p, max_new_tokens=max_new)
            eng.step()
    else:
        raise ValueError(order)
    return {i: eng.result(u) for i, u in uids.items()}, eng, uids


# ---------------------------------------------------------------------------
# headline parity: deferred harvest never changes tokens
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("order", ["upfront", "staggered"])
@pytest.mark.parametrize("kind", ["attention", "a3", "rglru", "xlstm"])
def test_pipeline_depth_parity_all_kinds(all_params, kind, order):
    cfg, a3 = KINDS[kind]
    params = all_params[cfg.name]
    prompts = _prompts(cfg.vocab_size)
    ref, e0, _ = _run(params, cfg, prompts, a3=a3, order=order, depth=0)
    got, e1, _ = _run(params, cfg, prompts, a3=a3, order=order, depth=1)
    assert got == ref
    assert all(r is not None for r in ref.values())
    # scheduling MAY legitimately shift (a slot whose last ring is in
    # flight frees one tick later, delaying the next admission by a
    # tick), but every request finishes, per-lane A^3 resort counts are
    # pos-driven and schedule-independent, and deferral never ADDS
    # blocking syncs
    assert e1.stats["finished"] == e0.stats["finished"]
    assert e1.stats["resorts"] == e0.stats["resorts"]
    assert e1.stats["host_syncs"] <= e0.stats["host_syncs"]
    _check_conservation(e0)
    _check_conservation(e1)


def test_pipeline_depth_two_parity(all_params):
    params = all_params["tiny"]
    prompts = _prompts(TINY.vocab_size)
    ref, _, _ = _run(params, TINY, prompts, depth=0)
    got, eng, _ = _run(params, TINY, prompts, depth=2)
    assert got == ref
    _check_conservation(eng)


def test_pipeline_depth_parity_with_sampling(all_params):
    """temperature > 0: the (seed, uid, pos)-keyed in-graph sampler
    draws the same stream regardless of harvest depth."""
    params = all_params["tiny"]
    prompts = _prompts(TINY.vocab_size)
    kw = dict(temperature=0.8, sample_seed=5)
    ref, _, _ = _run(params, TINY, prompts, depth=0, **kw)
    got, _, _ = _run(params, TINY, prompts, depth=1, **kw)
    assert got == ref


def test_pipeline_depth_zero_pins_default_engine(all_params):
    """depth=0 IS the historical engine: token streams and every
    counter (modulo wall-clock timings) match a default-constructed
    engine bit-for-bit."""
    params = all_params["tiny"]
    prompts = _prompts(TINY.vocab_size)
    eng_default = ServeEngine(params, TINY, slots=2, max_len=MAX_LEN,
                              prefill_chunk=8, decode_block=2)
    uids = [eng_default.submit(p, max_new_tokens=MAX_NEW) for p in prompts]
    eng_default.run_to_completion()
    got, e0, u0 = _run(params, TINY, prompts, depth=0)
    assert [e0.result(u0[i]) for i in range(len(prompts))] == \
        [eng_default.result(u) for u in uids]
    # host_sync_stalls depends on whether the device finished before
    # the drain checked is_ready() — a race against real time, not part
    # of the deterministic contract
    strip = lambda st: {k: v for k, v in st.items()
                        if k != "host_sync_stalls"}
    assert strip(e0.stats) == strip(eng_default.stats)


def test_pipeline_rejects_negative_depth(all_params):
    with pytest.raises(ValueError, match="pipeline_depth"):
        ServeEngine(all_params["tiny"], TINY, slots=2, max_len=MAX_LEN,
                    pipeline_depth=-1)


# ---------------------------------------------------------------------------
# conservation closes every tick with harvests in flight
# ---------------------------------------------------------------------------

def test_pipeline_conservation_closes_every_tick(all_params):
    params = all_params["tiny"]
    prompts = _prompts(TINY.vocab_size)
    eng = ServeEngine(params, TINY, slots=2, max_len=MAX_LEN,
                      prefill_chunk=8, decode_block=2, pipeline_depth=1)
    uids = [eng.submit(p, max_new_tokens=MAX_NEW) for p in prompts]
    saw_pending = False
    for _ in range(200):
        if eng.in_flight == 0:
            break
        eng.step()
        saw_pending = saw_pending or len(eng._pending) > 0
        _check_conservation(eng)
    assert eng.in_flight == 0
    assert saw_pending, "depth=1 must actually defer harvests"
    for u in uids:
        assert eng.status(u) == "finished"


# ---------------------------------------------------------------------------
# lifecycle edges on the one-tick-delayed view
# ---------------------------------------------------------------------------

def test_pipeline_cancel_acts_on_delayed_view(all_params):
    """Cancelling a DECODING request whose latest ring is still in
    flight releases the slot immediately; the stale harvest rows are
    uid-dropped, every other stream is untouched."""
    params = all_params["tiny"]
    prompts = _prompts(TINY.vocab_size)
    ref, _, _ = _run(params, TINY, prompts, depth=0)

    for depth in (0, 1):
        eng = ServeEngine(params, TINY, slots=2, max_len=MAX_LEN,
                          prefill_chunk=8, decode_block=2,
                          pipeline_depth=depth)
        uids = [eng.submit(p, max_new_tokens=MAX_NEW) for p in prompts]
        # step until the first submitted request is decoding, then
        # cancel it (at depth 1 its last ring is typically pending)
        for _ in range(200):
            st = [s for s in eng.slots if s.uid == uids[0]]
            if st and st[0].decoding:
                break
            eng.step()
        assert eng.cancel(uids[0])
        eng.run_to_completion()
        assert eng.status(uids[0]) == "cancelled"
        assert eng.result(uids[0]) is None
        for i in (1, 2, 3):
            assert eng.status(uids[i]) == "finished"
            assert eng.result(uids[i]) == ref[i], (depth, i)
        _check_conservation(eng)


def test_pipeline_deadline_expiry_on_delayed_view(all_params):
    """Deadlines act on the optimistic host view: an expiry landing in
    the harvest gap terminates the request deterministically (the
    delayed view may legitimately expire a request whose final tokens
    were still in flight — one tick later than the synchronous engine
    would have finished it — but the decision is a pure function of
    the tick count, so identical runs agree exactly), and the books
    close either way."""
    params = all_params["tiny"]
    prompts = _prompts(TINY.vocab_size)
    outcomes = {}
    for depth, tag in ((0, "d0"), (1, "d1a"), (1, "d1b")):
        eng = ServeEngine(params, TINY, slots=2, max_len=MAX_LEN,
                          prefill_chunk=8, decode_block=2,
                          pipeline_depth=depth, deadline_ticks=4)
        uids = [eng.submit(p, max_new_tokens=32) for p in prompts]
        eng.run_to_completion()
        statuses = [eng.status(u) for u in uids]
        assert set(statuses) <= {"finished", "expired"}, tag
        assert "expired" in statuses, "deadline must bite"
        _check_conservation(eng)
        outcomes[tag] = (statuses, [eng.result(u) for u in uids])
    # pinned determinism: two depth-1 runs agree bit-for-bit
    assert outcomes["d1a"] == outcomes["d1b"]
    # requests that finish under BOTH views generated identical tokens
    for (s0, r0), (s1, r1) in [(outcomes["d0"], outcomes["d1a"])]:
        for i in range(len(prompts)):
            if s0[i] == "finished" and s1[i] == "finished":
                assert r0[i] == r1[i], i


def test_pipeline_poison_quarantine_on_delayed_harvest(all_params):
    """Chaos-corrupted lanes poison through the deferred ring: the
    victim fails (one request), the sentinel never reaches a result,
    and un-injected requests match the chaos-free synchronous run."""
    params = all_params["tiny"]
    prompts = _prompts(TINY.vocab_size)
    ref, _, _ = _run(params, TINY, prompts, depth=0)
    chaos = ChaosInjector(ChaosConfig(seed=0, rate=0.5,
                                      raise_mid_tick=False,
                                      fail_gather=False,
                                      max_injections=1))
    got, eng, uids = _run(params, TINY, prompts, depth=1, chaos=chaos)
    victims = chaos.injected_uids
    assert victims, "the pinned (seed, rate) schedule must inject"
    for i, u in uids.items():
        if u in victims:
            assert eng.status(u) == "failed"
            assert eng.result(u) is None
        else:
            assert eng.status(u) == "finished"
            assert eng.result(u) == ref[i]
    for r in got.values():
        assert r is None or dec.POISON not in r
    _check_conservation(eng)


# ---------------------------------------------------------------------------
# crash / restore with a harvest in flight
# ---------------------------------------------------------------------------

def test_pipeline_crash_restore_with_harvest_in_flight(all_params,
                                                       tmp_path):
    """EngineCrash with deferred harvests pending: the per-tick
    checkpoint drains them first (host-consistent snapshot), so
    restore + continue emits exactly the crash-free depth-0 tokens."""
    params = all_params["tiny"]
    prompts = _prompts(TINY.vocab_size)
    ref, _, _ = _run(params, TINY, prompts, depth=0)

    chaos = ChaosInjector(ChaosConfig(seed=3, rate=0.3,
                                      corrupt_logits=False,
                                      fail_gather=False,
                                      raise_mid_tick=False,
                                      crash_mid_tick=True))
    eng = ServeEngine(params, TINY, slots=2, max_len=MAX_LEN,
                      prefill_chunk=8, decode_block=2, pipeline_depth=1,
                      chaos=chaos)
    uids = [eng.submit(p, max_new_tokens=MAX_NEW) for p in prompts]
    ck = str(tmp_path / "ckpt")
    eng.checkpoint(ck)
    crashes, saw_pending = 0, False
    while eng.in_flight > 0:
        try:
            eng.step()
            saw_pending = saw_pending or len(eng._pending) > 0
            eng.checkpoint(ck)
            assert len(eng._pending) == 0  # checkpoint drained them
        except EngineCrash:
            crashes += 1
            eng = ServeEngine.restore(ck, params, TINY)
            assert eng.pipeline_depth == 1  # depth survives the trip
    assert crashes >= 1, "the pinned schedule must crash at least once"
    assert saw_pending, "a harvest must have been in flight pre-crash"
    for i, u in enumerate(uids):
        assert eng.status(u) == "finished"
        assert eng.result(u) == ref[i]
    _check_conservation(eng)


# ---------------------------------------------------------------------------
# perf counters: syncs fall, timings are sane
# ---------------------------------------------------------------------------

def test_pipeline_host_syncs_strictly_lower(all_params):
    """The acceptance criterion: on a decode-heavy workload the depth-1
    engine issues strictly fewer blocking host syncs than the
    synchronous engine at the same decode_block — for block=1 AND
    block=8."""
    params = all_params["tiny"]
    rng = np.random.default_rng(11)
    prompts = [rng.integers(0, TINY.vocab_size, size=8) for _ in range(2)]
    for block in (1, 8):
        ref, e0, _ = _run(params, TINY, prompts, depth=0,
                          decode_block=block, max_new=24)
        got, e1, _ = _run(params, TINY, prompts, depth=1,
                          decode_block=block, max_new=24)
        assert got == ref, block
        assert e1.stats["host_syncs"] < e0.stats["host_syncs"], (
            block, e1.stats["host_syncs"], e0.stats["host_syncs"])
        # stalls only count harvests that actually blocked
        assert 0 <= e1.stats["host_sync_stalls"] <= e1.stats["host_syncs"]


# ---------------------------------------------------------------------------
# sharded lowering of the carry-returning decode block (8-dev CI mesh)
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_pipeline_carry_decode_block_lowers_sharded():
    """The carry-returning decode block lowers under GSPMD on the
    8-device CI mesh: outputs are (ring [B, T], carry [B], cache) with
    the cache donated — the device-resident token chain the pipelined
    engine rides exists on the production mesh, not just on one CPU
    device."""
    out = check(run_with_devices("""
import jax
from repro.config import A3Config, ShapeConfig, ShapeKind, ShardingConfig, \\
    get_arch, smoke_variant
from repro.launch.mesh import make_mesh
from repro.launch.dryrun import lower_decode_block

cfg = smoke_variant(get_arch("phi4-mini-3.8b"))
dshape = ShapeConfig("decode_smoke", ShapeKind.DECODE, 256, 8)
mesh = make_mesh((2, 4), ("data", "model"))
scfg = ShardingConfig(remat="none")
with mesh:
    c = lower_decode_block(cfg, dshape, mesh, scfg, steps=8,
                           a3=A3Config.conservative(),
                           resort_every=64).compile()
assert c.memory_analysis().alias_size_in_bytes > 0   # donation held
print("OK")
""", devices=8, timeout=900))
    assert "OK" in out


# ---------------------------------------------------------------------------
# virtual-device emulation: the pipeline hides emulated completion latency
# ---------------------------------------------------------------------------

def test_pipeline_hides_virtual_device_latency(all_params):
    """Under ``virtual_device_latency_s`` — each decode block's ring
    readable only L after dispatch, a GIL-releasing readiness floor
    emulating an accelerator completing off-host — the synchronous
    engine serializes on L once per block (its drain reads the block
    it just dispatched, so the sleep intervals are disjoint by
    construction), while a primed pipeline keeps blocks in flight and
    amortizes L across the ticks it spends planning ahead. That makes
    the overlap a deterministic wall-clock win even on a single-core
    host, where real XLA compute timeshares the tick loop's core and
    raw overlap is otherwise invisible. The knob never changes
    tokens."""
    params = all_params["tiny"]
    prompts = _prompts(TINY.vocab_size)[:2]
    L = 0.004

    def timed(depth, lat):
        eng = ServeEngine(params, TINY, slots=2, max_len=MAX_LEN,
                          prefill_chunk=8, decode_block=1,
                          pipeline_depth=depth,
                          virtual_device_latency_s=lat)
        w = eng.submit(prompts[0], max_new_tokens=2)   # compile warmup
        eng.run_to_completion()
        assert eng.result(w) is not None
        eng.stats = {k: 0 for k in eng.stats}
        uids = [eng.submit(p, max_new_tokens=24) for p in prompts]
        eng.step()                                     # admission tick
        jax.block_until_ready(jax.tree.leaves(eng.cache)[0])
        t0 = time.perf_counter()
        eng.run_to_completion()
        wall = time.perf_counter() - t0
        return [eng.result(u) for u in uids], eng, wall

    base, _, _ = timed(0, 0.0)
    ref, e0, wall0 = timed(0, L)
    got, e2, wall2 = timed(2, L)
    assert ref == base              # emulation is scheduling only
    assert got == ref               # deferral is scheduling only
    # every synchronous drain stalls out the emulated latency; the
    # primed pipeline's forced reads find blocks past their readiness
    # floor after warmup
    assert e2.stats["host_sync_stalls"] < e0.stats["host_sync_stalls"]
    assert e2.stats["host_syncs"] < e0.stats["host_syncs"]
    # depth 0 pays >= decode_dispatches * L serially (disjoint
    # sleeps): wall0 has a hard floor no load can shrink. Depth 2
    # amortizes each L over 3 ticks of useful host work. 0.75 leaves
    # a wide margin for a loaded CI host.
    assert wall0 >= (e0.stats["decode_dispatches"] - 1) * L
    assert wall2 < 0.75 * wall0, (wall0, wall2, dict(e2.stats))
