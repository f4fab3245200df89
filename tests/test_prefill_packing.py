"""Packed prefill: a dispatch computes only the lanes that prefill.

* the packed ``[b, chunk]`` program (``lane_slot`` given) leaves the
  cache and first tokens the full ``[slots, chunk]`` program leaves, for
  exact attention, A^3 (both sort variants) and an RG-LRU +
  sliding-window hybrid, with mixed cursors and a slot at ``pos 0``
  that resets its stale state in-graph; slots outside the packed lanes
  keep their rows bit-identical;
* the engine packs ``b`` = the next power of two of the prefilling
  lanes, at most ``slots``, and at full width dispatches the unpacked
  program;
* every width is compiled at the first prefill tick: traffic that packs
  1, 2 and then 4 lanes compiles nothing after it.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import A3Config
from repro.models import decoder as dec
from repro.serve import engine as E

from test_serve_pipeline import TINY, TINY_RG

SLOTS, MAX_LEN, CHUNK = 4, 32, 8


@pytest.fixture(scope="module")
def tiny_params():
    return dec.init_params(jax.random.PRNGKey(0), TINY)


@pytest.fixture(scope="module")
def rg_params():
    return dec.init_params(jax.random.PRNGKey(1), TINY_RG)


def _ctrl(rows):
    """[SLOTS, CTRL_COLS] with the prefill columns of ``rows``: slot ->
    (pos, length, final chunk)."""
    ctrl = np.zeros((SLOTS, E.CTRL_COLS), np.int32)
    ctrl[:, E.CTRL_D_POS] = -1
    for si, (pos, n, final) in rows.items():
        ctrl[si, E.CTRL_P_POS] = pos
        ctrl[si, E.CTRL_P_LEN] = n
        ctrl[si, E.CTRL_P_SORT] = int(final)
        ctrl[si, E.CTRL_P_SPOS] = pos + n - 1
        ctrl[si, E.CTRL_P_SIDS] = 100 + si
    return jnp.asarray(ctrl)


def _leaves(tree):
    return jax.tree_util.tree_flatten_with_path(tree)[0]


@pytest.mark.parametrize("arch, a3, update_sort", [
    ("exact", False, True),
    ("a3-sort", True, True),
    ("a3-nosort", True, False),
    ("rglru-swa", False, True),
])
def test_packed_dispatch_matches_the_full_block(tiny_params, rg_params,
                                                arch, a3, update_sort):
    cfg, params = ((TINY_RG, rg_params) if arch == "rglru-swa"
                   else (TINY, tiny_params))
    rng = np.random.default_rng(11)
    full = jax.jit(E.make_prefill_chunk_step(cfg, a3=a3,
                                             update_sort=update_sort))
    # every slot holds state: 13, 9, 6 and 8 prompt tokens prefilled
    cache = dec.init_cache(cfg, SLOTS, MAX_LEN, a3=a3)
    held = [13, 9, 6, 8]
    toks = np.zeros((SLOTS, 16), np.int32)
    for si, n in enumerate(held):
        toks[si, :n] = rng.integers(0, cfg.vocab_size, size=n)
    setup = jax.jit(E.make_prefill_chunk_step(cfg, a3=a3))
    _, cache = setup(params, cache, jnp.asarray(toks),
                     _ctrl({si: (0, n, True) for si, n in enumerate(held)}))
    # the plan: slot 3 continues at pos 8 with its final 5 tokens, slot 1
    # starts a new prompt at pos 0 over a finished request's state;
    # slots 0 and 2 do not prefill
    final = update_sort
    rows = {3: (8, 5, final), 1: (0, CHUNK, False)}
    lane_slot = [3, 1]
    chunk = {si: rng.integers(0, cfg.vocab_size, size=rows[si][1])
             for si in rows}
    block = np.zeros((SLOTS, CHUNK), np.int32)
    packed = np.zeros((len(lane_slot), CHUNK), np.int32)
    for j, si in enumerate(lane_slot):
        block[si, :rows[si][1]] = chunk[si]
        packed[j, :rows[si][1]] = chunk[si]
    ctrl = _ctrl(rows)
    tok_f, cache_f = full(params, cache, jnp.asarray(block), ctrl)
    tok_p, cache_p = full(params, cache, jnp.asarray(packed), ctrl,
                          lane_slot=jnp.asarray(lane_slot, jnp.int32))
    assert tok_p.shape == (SLOTS,)
    np.testing.assert_array_equal(np.asarray(tok_p)[lane_slot],
                                  np.asarray(tok_f)[lane_slot])
    for (path, a), (_, b), (_, old) in zip(_leaves(cache_p),
                                           _leaves(cache_f),
                                           _leaves(cache)):
        a, b, old = np.asarray(a), np.asarray(b), np.asarray(old)
        np.testing.assert_allclose(a[:, lane_slot], b[:, lane_slot],
                                   rtol=1e-6, atol=1e-6, err_msg=str(path))
        for si in (0, 2):
            np.testing.assert_array_equal(a[:, si], old[:, si],
                                          err_msg=str(path))
    # the pos-0 lane reset its ring in-graph: only the chunk's rows hold
    # keys, where the slot held 9 rows of the finished request
    if arch != "rglru-swa":
        k = np.asarray(cache_p["seg0"]["k"])[:, 1]        # [L, Hkv, w, D]
        assert np.abs(k[:, :, :CHUNK]).sum() > 0
        assert np.abs(np.asarray(cache["seg0"]["k"])[:, 1, :, CHUNK:9]
                      ).sum() > 0
        np.testing.assert_array_equal(k[:, :, CHUNK:], 0.0)


def _engine(params, **kw):
    kw = {"slots": SLOTS, "max_len": 64, "prefill_chunk": CHUNK,
          "decode_block": 2, **kw}
    return E.ServeEngine(params, TINY, **kw)


@pytest.mark.parametrize("lanes, width", [(1, 1), (2, 2), (3, 4), (4, 4)])
def test_plan_packs_to_the_next_power_of_two(tiny_params, lanes, width):
    eng = _engine(tiny_params)
    rng = np.random.default_rng(lanes)
    for _ in range(lanes):
        eng.submit(rng.integers(0, TINY.vocab_size, size=20))
    eng._admit()
    ctrl = np.zeros((SLOTS, E.CTRL_COLS), np.int32)
    plan = eng._plan_prefill(ctrl)
    assert plan["tokens"].shape == (width, CHUNK)
    if width == SLOTS:
        # full width: the unpacked program, lanes in slot order
        assert plan["lane_slot"] is None
    else:
        ls = np.asarray(plan["lane_slot"]).tolist()
        assert set(plan["pre"]) <= set(ls) and len(set(ls)) == width
        # padding lanes pass through at length 0
        pad = [si for si in ls if si not in plan["pre"]]
        assert (ctrl[pad, E.CTRL_P_LEN] == 0).all()
    assert sorted(plan["takes"]) == plan["pre"] == list(range(lanes))


@pytest.mark.parametrize("a3", [False, True], ids=["exact", "a3"])
def test_no_compile_after_the_first_prefill_tick(tiny_params, a3):
    """Traffic that prefills 1, then 2, then 3 (width 4) lanes at once
    traces and compiles every program at its first prefill tick, which
    also decodes, and none after it."""
    eng = _engine(tiny_params, telemetry=False,
                  a3=A3Config.conservative() if a3 else A3Config())
    rng = np.random.default_rng(5)
    prompt = lambda n: rng.integers(0, TINY.vocab_size, size=n)
    compiles, widths = [], []

    def on_duration(event, duration, fun_name=None, **_):
        # the harness's count: traces and backend compiles
        if event in ("/jax/core/compile/jaxpr_trace_duration",
                     "/jax/core/compile/backend_compile_duration"):
            compiles.append((event, fun_name))

    def tick():
        n = eng.stats["prefill_lanes_computed"]
        eng.step()
        widths.append(eng.stats["prefill_lanes_computed"] - n)

    eng.submit(prompt(5), max_new_tokens=24)
    tick()
    assert eng.stats["decode_dispatches"] == 1
    jax.monitoring.register_event_duration_secs_listener(on_duration)
    try:
        eng.submit(prompt(20), max_new_tokens=4)
        eng.submit(prompt(20), max_new_tokens=4)
        tick()
        for _ in range(3):
            eng.submit(prompt(12), max_new_tokens=4)
        while eng.in_flight:
            tick()
    finally:
        jax.monitoring.unregister_event_duration_listener(on_duration)
    assert widths[:3] == [1, 2, 4]
    assert compiles == []
