"""Chaos-injection conformance: fault ISOLATION proven the same way
PRs 2-5 proved correctness — against a fault-free oracle run.

The headline property: running the engine under seeded injection
(corrupt a decoding lane's mixer state / fail a warm page gather /
abort ticks mid-phase), every UN-injected request's token stream is
token-for-token identical to the chaos-free run, every injected
request terminates FAILED (never hangs a slot), and ``host_syncs``
does not increase — poison detection rides the per-block ring harvest
the engine already pays for (``decoder.POISON`` sentinel), not an
extra device read.

The corrupt-site workload keeps requests <= slots so the schedule of
surviving lanes is pinned tick-for-tick: with no backlog, a victim's
early death cannot re-cohort the others, making "host_syncs does not
increase" an exact equality check rather than a statistical one.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import ModelConfig
from repro.models import decoder as dec
from repro.serve.chaos import ChaosConfig, ChaosError, ChaosInjector, \
    corrupt_cache_lane
from repro.serve.engine import ServeEngine

TINY = ModelConfig("tiny", "dense", num_layers=2, d_model=64, num_heads=4,
                   num_kv_heads=2, d_ff=128, vocab_size=256, head_dim=16,
                   dtype="float32")
MAX_LEN = 96
PROMPT_LENS = (5, 12, 23)


@pytest.fixture(scope="module")
def params():
    return dec.init_params(jax.random.PRNGKey(0), TINY)


@pytest.fixture(scope="module")
def prompts():
    rng = np.random.default_rng(0)
    return [rng.integers(0, TINY.vocab_size, size=n) for n in PROMPT_LENS]


def _run(params, prompts, *, chaos=None, slots=3, max_new=6,
         decode_block=4, cache_pages=0, max_ticks=10_000):
    eng = ServeEngine(params, TINY, slots=slots, max_len=MAX_LEN,
                      prefill_chunk=8, decode_block=decode_block,
                      page_size=8, cache_pages=cache_pages, chaos=chaos)
    uids = [eng.submit(p, max_new_tokens=max_new) for p in prompts]
    eng.run_to_completion(max_ticks=max_ticks)
    return eng, uids


def _check_conservation(eng):
    s = eng.stats
    assert s["submitted"] == (s["finished"] + s["rejected"]
                              + s["cancelled"] + s["expired"]
                              + s["failed"] + eng.in_flight), s


# ---------------------------------------------------------------------------
# the POISON sentinel at the decoder level
# ---------------------------------------------------------------------------

def test_chaos_poison_sentinel_rides_ring(params):
    """A NaN'd lane emits POISON exactly once on the existing token
    ring, then freezes; the healthy lane's ring row is bit-identical
    to the uncorrupted run — the quarantine select is lane-local."""
    rng = np.random.default_rng(1)
    toks = rng.integers(0, TINY.vocab_size, size=(2, 12))
    logits, cache = dec.prefill(params, TINY, jnp.asarray(toks),
                                max_len=32)
    first = jnp.argmax(logits, -1).astype(jnp.int32)
    pos = jnp.full((2,), 12, jnp.int32)
    left = jnp.full((2,), 4, jnp.int32)
    ring_ok, _, _ = dec.decode_block(params, TINY, cache, first, pos,
                                     left, steps=4)
    ring_bad, carry_bad, _ = dec.decode_block(params, TINY,
                                              corrupt_cache_lane(cache, 0),
                                              first, pos, left, steps=4)
    ring_ok, ring_bad = np.asarray(ring_ok), np.asarray(ring_bad)
    assert (ring_ok >= 0).all()
    assert ring_bad[0, 0] == dec.POISON          # poisoned once...
    assert (ring_bad[0, 1:] == -1).all()         # ...then frozen
    np.testing.assert_array_equal(ring_bad[1], ring_ok[1])
    # the poisoned lane's carry froze at its input token — feeding it
    # to a next block keeps the lane frozen (token != POISON guard is
    # on the INPUT token; its non-finite logits re-poison regardless)
    assert int(np.asarray(carry_bad)[0]) == int(first[0])


def test_chaos_corrupt_cache_lane_targets_one_lane(params):
    _, cache = dec.prefill(params, TINY,
                           jnp.zeros((3, 4), jnp.int32), max_len=16)
    bad = corrupt_cache_lane(cache, 1)
    for name, sc in bad.items():
        for key, leaf in sc.items():
            ref = cache[name][key]
            if jnp.issubdtype(np.asarray(leaf).dtype, np.floating):
                assert np.isnan(np.asarray(leaf)[:, 1]).all(), (name, key)
            np.testing.assert_array_equal(np.asarray(leaf)[:, 0],
                                          np.asarray(ref)[:, 0])
            np.testing.assert_array_equal(np.asarray(leaf)[:, 2],
                                          np.asarray(ref)[:, 2])


# ---------------------------------------------------------------------------
# headline conformance: corrupt injection
# ---------------------------------------------------------------------------

def test_chaos_conformance_corrupt_isolates_victim(params, prompts):
    free, fu = _run(params, prompts)
    chaos = ChaosInjector(ChaosConfig(seed=0, rate=0.5,
                                      raise_mid_tick=False,
                                      fail_gather=False,
                                      max_injections=1))
    eng, uids = _run(params, prompts, chaos=chaos)
    victims = chaos.injected_uids
    assert victims, "the pinned (seed, rate) schedule must inject"
    for u, f in zip(uids, fu):
        if u in victims:
            # injected -> FAILED, no result, slot was reclaimed (the
            # run completed without exhausting max_ticks)
            assert eng.status(u) == "failed"
            assert eng.result(u) is None
        else:
            # un-injected -> token-for-token identical to chaos-free
            assert eng.status(u) == "finished"
            assert eng.result(u) == free.result(f)
    assert eng.stats["failed"] == len(victims)
    # poison detection rides the existing per-block harvest: with no
    # backlog the surviving lanes' schedule is pinned, so syncs are
    # EQUAL, and in general must never increase
    assert eng.stats["host_syncs"] <= free.stats["host_syncs"]
    assert eng.stats["host_syncs"] <= (eng.stats["decode_dispatches"]
                                       + eng.stats["handoff_syncs"])
    _check_conservation(eng)
    # the victim's slot is genuinely reusable: new work completes on it
    u_next = eng.submit(prompts[0], max_new_tokens=4)
    eng.run_to_completion()
    assert eng.status(u_next) == "finished"
    assert eng.result(u_next) == free.result(fu[0])[:4]
    _check_conservation(eng)


def test_chaos_determinism_same_seed_same_faults(params, prompts):
    cfg = ChaosConfig(seed=0, rate=0.5, raise_mid_tick=False,
                      fail_gather=False, max_injections=1)
    ch1, ch2 = ChaosInjector(cfg), ChaosInjector(cfg)
    e1, u1 = _run(params, prompts, chaos=ch1)
    e2, u2 = _run(params, prompts, chaos=ch2)
    assert ch1.events == ch2.events
    assert [e1.status(u) for u in u1] == [e2.status(u) for u in u2]
    for a, b in zip(u1, u2):
        assert e1.result(a) == e2.result(b)


# ---------------------------------------------------------------------------
# gather-failure injection (prefix-cache admission)
# ---------------------------------------------------------------------------

def test_chaos_gather_failure_fails_request_not_engine(params, prompts):
    long_prompt = np.concatenate([prompts[2], prompts[1], prompts[2]])[:48]
    chaos = ChaosInjector(ChaosConfig(seed=1, rate=1.0,
                                      corrupt_logits=False,
                                      raise_mid_tick=False,
                                      max_injections=1))
    eng = ServeEngine(params, TINY, slots=2, max_len=MAX_LEN,
                      prefill_chunk=8, page_size=8, cache_pages=16,
                      chaos=chaos)
    # cold admission never gathers -> cannot be a gather victim
    u0 = eng.submit(long_prompt, max_new_tokens=4)
    eng.run_to_completion()
    assert eng.status(u0) == "finished"
    # warm admission: rate 1.0 -> the gather deterministically fails
    u1 = eng.submit(long_prompt, max_new_tokens=4)
    eng.run_to_completion()
    assert eng.status(u1) == "failed"
    assert ("gather_fail" in {k for k, _, _ in chaos.events})
    assert chaos.injected_uids == {u1}
    # max_injections exhausted: the retry reuses the cache and matches
    # the cold run token-for-token (no refs/pages were leaked by the
    # failed admission)
    u2 = eng.submit(long_prompt, max_new_tokens=4)
    eng.run_to_completion()
    assert eng.status(u2) == "finished"
    assert eng.result(u2) == eng.result(u0)
    assert eng.stats["prefix_hits"] >= 1
    assert eng._pc.referenced_nodes == 0
    _check_conservation(eng)


# ---------------------------------------------------------------------------
# mid-tick abort / delay injection
# ---------------------------------------------------------------------------

def test_chaos_mid_tick_aborts_change_nothing(params, prompts):
    """Raise-only chaos at tick phase boundaries: ticks abort and are
    retried, device-resident handoff tokens are flushed (not
    overwritten), and every request still finishes with exactly the
    chaos-free tokens."""
    free, fu = _run(params, prompts, slots=2, cache_pages=16)
    chaos = ChaosInjector(ChaosConfig(seed=3, rate=0.3,
                                      corrupt_logits=False,
                                      fail_gather=False,
                                      raise_mid_tick=True,
                                      delay_mid_tick=True))
    eng, uids = _run(params, prompts, chaos=chaos, slots=2,
                     cache_pages=16)
    aborts = [e for e in chaos.events if e[0] == "raise"]
    assert aborts, "the pinned (seed, rate) schedule must abort ticks"
    assert eng.stats["chaos_aborted_ticks"] == len(aborts)
    # delays are *virtual* stall ticks (no wall clock): every consumed
    # stall is counted, and none can exceed what the fired events accrued
    delays = [e for e in chaos.events if e[0] == "delay"]
    assert delays, "the pinned (seed, rate) schedule must fire delays"
    assert 0 < eng.stats["chaos_delayed_ticks"] \
        <= len(delays) * chaos.config.delay_ticks
    for u, f in zip(uids, fu):
        assert eng.status(u) == "finished"
        assert eng.result(u) == free.result(f)
    assert eng.stats["host_syncs"] <= (eng.stats["decode_dispatches"]
                                       + eng.stats["handoff_syncs"])
    assert eng._pc.referenced_nodes == 0
    _check_conservation(eng)


def test_chaos_step_propagates_chaos_error(params, prompts):
    """Callers driving step() by hand see the ChaosError; the engine
    is left consistent and the next step() simply resumes."""
    chaos = ChaosInjector(ChaosConfig(seed=3, rate=1.0,
                                      corrupt_logits=False,
                                      fail_gather=False,
                                      raise_mid_tick=True))
    eng = ServeEngine(params, TINY, slots=1, max_len=MAX_LEN,
                      prefill_chunk=8, chaos=chaos)
    u = eng.submit(prompts[0], max_new_tokens=2)
    with pytest.raises(ChaosError):
        eng.step()
    _check_conservation(eng)
    assert eng.status(u) in ("queued", "prefilling", "decoding")


# ---------------------------------------------------------------------------
# injector plumbing
# ---------------------------------------------------------------------------

def test_chaos_config_validation():
    with pytest.raises(ValueError):
        ChaosConfig(rate=1.5)
    with pytest.raises(ValueError):
        ChaosConfig(rate=-0.1)
    with pytest.raises(ValueError):
        ChaosConfig(delay_ticks=-1)
    with pytest.raises(ValueError):
        ChaosConfig(spill_pages=-1)
    with pytest.raises(ValueError):
        ChaosConfig(max_injections=-1)


def test_chaos_rate_zero_is_injection_free(params, prompts):
    free, fu = _run(params, prompts)
    chaos = ChaosInjector(ChaosConfig(seed=9, rate=0.0))
    eng, uids = _run(params, prompts, chaos=chaos)
    assert chaos.events == []
    # stats must match counter-for-counter; host_sync_stalls races
    # the device's is_ready() against real time and legitimately differs
    strip = lambda st: {k: v for k, v in st.items()
                        if k != "host_sync_stalls"}
    assert strip(eng.stats) == strip(free.stats)
    for u, f in zip(uids, fu):
        assert eng.result(u) == free.result(f)
