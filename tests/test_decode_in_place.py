"""The decode layer scan updates the cache in place: a blocked decode
writes exactly each advancing lane's new ring rows, leaves every other
row and every ride-along lane's whole slot bit-identical, and its logits
match a whole-prompt prefill of the same tokens."""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import A3Config, A3Mode, AttentionKind, BlockKind, \
    ModelConfig
from repro.models import decoder as dec

_TINY = dict(num_layers=2, d_model=64, num_heads=4, num_kv_heads=2,
             d_ff=128, vocab_size=256, head_dim=16, dtype="float32")
DENSE = ModelConfig("tiny", "dense", **_TINY)
# an 8-row ring: both advancing lanes write past its end during the block
SLIDING = dataclasses.replace(DENSE, name="tiny-swa",
                              attention_kind=AttentionKind.SLIDING,
                              window_size=8)
RGLRU = ModelConfig("tiny-rg", "hybrid", **{**_TINY, "num_layers": 3},
                    attention_kind=AttentionKind.SLIDING, window_size=8,
                    block_pattern=(BlockKind.RGLRU, BlockKind.RGLRU,
                                   BlockKind.ATTENTION), act="gelu")
# post-scoring keeps every score within 46 nats of the best: nothing
# whose weight float32 can hold is pruned
A3_KEEP_ALL = dataclasses.replace(A3Config.conservative(),
                                  threshold_pct=1e-18)

CASES = {
    "dense": (DENSE, A3Config(), 1e-5),
    "sliding_wrapped": (SLIDING, A3Config(), 1e-5),
    "a3_probe": (DENSE, A3_KEEP_ALL, 1e-5),
    "rglru": (RGLRU, A3Config(), 3e-5),
}
MAX_LEN = 32
STEPS = 3
PROMPT_LENS = (13, 6, 9)    # lane 2 rides along at pos = -1


def _stacked(caches):
    return jax.tree.map(lambda *xs: jnp.concatenate(xs, axis=1), *caches)


@pytest.fixture(scope="module", params=sorted(CASES))
def blocked(request):
    cfg, a3, tol = CASES[request.param]
    use_a3 = a3.mode != A3Mode.OFF
    params = dec.init_params(jax.random.PRNGKey(7), cfg)
    rng = np.random.default_rng(11)
    prompts = [rng.integers(0, cfg.vocab_size, size=n) for n in PROMPT_LENS]
    caches, first = [], []
    for p in prompts:
        lg, c = dec.prefill(params, cfg, jnp.asarray(p, jnp.int32)[None],
                            max_len=MAX_LEN, a3=use_a3)
        caches.append(c)
        first.append(int(jnp.argmax(lg[0])))
    cache = _stacked(caches)
    if use_a3:
        # a ring never folded into its sorted keys: every row is fresh,
        # so A^3 selects each, and the logits can match prefill
        cache["seg0"]["sorted_upto"] = jnp.zeros_like(
            cache["seg0"]["sorted_upto"])
    before = jax.tree.map(np.asarray, cache)
    pos = jnp.asarray([PROMPT_LENS[0], PROMPT_LENS[1], -1], jnp.int32)
    out = dec.decode_block(params, cfg, cache,
                           jnp.asarray(first, jnp.int32), pos,
                           jnp.asarray([STEPS, STEPS, 0], jnp.int32),
                           steps=STEPS, a3=a3, probe=use_a3)
    ring, carry, after = out[:3]
    if use_a3:
        # the probe sampled each advanced step of each advancing lane
        np.testing.assert_array_equal(np.asarray(out[3])[:, 0],
                                      [STEPS, STEPS, 0])
    return dict(cfg=cfg, a3=a3, tol=tol, params=params, prompts=prompts,
                first=first, before=before, ring=np.asarray(ring),
                carry=np.asarray(carry),
                after=jax.tree.map(np.asarray, after))


def test_only_advancing_lanes_rows_are_written(blocked):
    ring = blocked["ring"]
    assert (ring[:2] >= 0).all() and (ring[2] == -1).all()
    for seg, seg_after in blocked["after"].items():
        for name, new in seg_after.items():
            old = blocked["before"][seg][name]
            assert new.shape == old.shape and new.dtype == old.dtype
            if name in ("sk_vals", "sk_rows", "sorted_upto"):
                np.testing.assert_array_equal(new, old, err_msg=name)
            if name not in ("k", "v"):
                continue
            w = old.shape[3]
            written = np.zeros(old.shape[1:4:2], bool)          # [B, w]
            for lane in (0, 1):
                for t in range(STEPS):
                    written[lane, (PROMPT_LENS[lane] + t) % w] = True
            keep = ~written
            np.testing.assert_array_equal(
                new.transpose(1, 3, 0, 2, 4)[keep],
                old.transpose(1, 3, 0, 2, 4)[keep], err_msg=f"{seg}/{name}")
            # the written rows did change: the block wrote its tokens
            assert (new.transpose(1, 3, 0, 2, 4)[written]
                    != old.transpose(1, 3, 0, 2, 4)[written]).any()


def test_ride_along_lane_slot_unchanged(blocked):
    for seg, seg_after in blocked["after"].items():
        for name, new in seg_after.items():
            old = blocked["before"][seg][name]
            np.testing.assert_array_equal(new[:, 2], old[:, 2],
                                          err_msg=f"{seg}/{name}")


def test_blocked_decode_logits_match_prefill(blocked):
    """Decoding one more step from the block's cache gives the logits a
    whole-prompt prefill of prompt + handed-off token + the block's
    tokens gives."""
    cfg, params, tol = blocked["cfg"], blocked["params"], blocked["tol"]
    pos = jnp.asarray([PROMPT_LENS[0] + STEPS, PROMPT_LENS[1] + STEPS, -1],
                      jnp.int32)
    logits, _ = dec.decode_step(params, cfg, blocked["after"],
                                jnp.asarray(blocked["carry"]), pos,
                                a3=blocked["a3"])
    for lane in (0, 1):
        seq = np.concatenate([blocked["prompts"][lane],
                              [blocked["first"][lane]],
                              blocked["ring"][lane]])
        ref, _ = dec.prefill(params, cfg, jnp.asarray(seq, jnp.int32)[None],
                             max_len=MAX_LEN)
        np.testing.assert_allclose(np.asarray(logits[lane]),
                                   np.asarray(ref[0]), rtol=tol, atol=tol)
