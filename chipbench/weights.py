"""Seeded random weights, made by the benchmark and not by the program.

What every model kind shares: the key of a seed, the normal draw, the key
of one layer, and the embedding, output head and final norm. A kind's
module (``references/<kind>.py``) defines its layers' weights once, from
``layer_key``; the program's tree is those layers stacked, made on the
device in one jitted call, and the reference draws the same layers again
one at a time, so it never takes an array the program has held.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

_EMBED, _HEAD, _LAYER0 = 0, 1, 1000


def base_key(seed: int) -> jax.Array:
    """PRNG key of a seed of any size up to 64 bits."""
    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def normal(key, shape, scale) -> jax.Array:
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(
        jnp.bfloat16)


def layer_key(key: jax.Array, layer) -> jax.Array:
    """Key of one layer's weights; ``layer`` may be traced."""
    return jax.random.fold_in(key, _LAYER0 + layer)


def embed_weights(cfg: dict, key: jax.Array) -> jax.Array:
    """N(0, 1/hidden): after the program's sqrt(hidden) embedding scale a
    token enters the residual stream at unit RMS, the scale of what each
    layer adds, so attention moves the logits as it does in a trained
    model (a unit-variance table would let the embedding drown it)."""
    d = cfg["hidden_size"]
    return normal(jax.random.fold_in(key, _EMBED), (cfg["vocab_size"], d),
                  d ** -0.5)


def head_weights(cfg: dict, key: jax.Array) -> jax.Array:
    d = cfg["hidden_size"]
    return normal(jax.random.fold_in(key, _HEAD), (d, cfg["vocab_size"]),
                  d ** -0.5)


def program_tree(cfg: dict, key: jax.Array, segments: dict) -> dict:
    """The program's whole tree around a kind's ``seg<i>`` segments."""
    params = {"embed": embed_weights(cfg, key),
              "final_norm": {"scale": jnp.ones((cfg["hidden_size"],),
                                               jnp.bfloat16)},
              **segments}
    if not cfg["tie_word_embeddings"]:
        params["lm_head"] = head_weights(cfg, key)
    return params
