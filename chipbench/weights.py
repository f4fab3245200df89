"""Seeded random weights, made by the benchmark and not by the program.

``layer_weights(cfg, seed, layer)`` is the one definition of every weight:
the program's parameter tree is these layers stacked (``program_params``,
one jitted call on the device), and the reference draws the same layers
again one at a time, so it never takes an array the program has held.
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

_LEAF_IDS = {"wq": 1, "wk": 2, "wv": 3, "wo": 4,
             "w_gate": 5, "w_up": 6, "w_down": 7}
_EMBED, _HEAD, _LAYER0 = 0, 1, 1000


def base_key(seed: int) -> jax.Array:
    """PRNG key of a seed of any size up to 64 bits."""
    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def _normal(key, shape, scale) -> jax.Array:
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(
        jnp.bfloat16)


def layer_shapes(cfg: dict) -> Dict[str, tuple]:
    d, hd, f = cfg["hidden_size"], cfg["head_dim"], cfg["intermediate_size"]
    hq, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return {"wq": (d, hq * hd), "wk": (d, hkv * hd), "wv": (d, hkv * hd),
            "wo": (hq * hd, d), "w_gate": (d, f), "w_up": (d, f),
            "w_down": (f, d)}


def layer_weights(cfg: dict, key: jax.Array, layer) -> Dict[str, jax.Array]:
    """bf16 weights of one layer; ``layer`` may be traced."""
    lkey = jax.random.fold_in(key, _LAYER0 + layer)
    return {name: _normal(jax.random.fold_in(lkey, _LEAF_IDS[name]), shape,
                          shape[0] ** -0.5)
            for name, shape in layer_shapes(cfg).items()}


def embed_weights(cfg: dict, key: jax.Array) -> jax.Array:
    """N(0, 1/hidden): after the program's sqrt(hidden) embedding scale a
    token enters the residual stream at unit RMS, the scale of what each
    layer adds, so attention moves the logits as it does in a trained
    model (a unit-variance table would let the embedding drown it)."""
    d = cfg["hidden_size"]
    return _normal(jax.random.fold_in(key, _EMBED), (cfg["vocab_size"], d),
                   d ** -0.5)


def head_weights(cfg: dict, key: jax.Array) -> jax.Array:
    d = cfg["hidden_size"]
    return _normal(jax.random.fold_in(key, _HEAD), (d, cfg["vocab_size"]),
                   d ** -0.5)


def _program_tree(cfg: dict, key: jax.Array) -> dict:
    d, n = cfg["hidden_size"], cfg["num_hidden_layers"]
    layers = jax.lax.map(lambda i: layer_weights(cfg, key, i),
                         jnp.arange(n, dtype=jnp.int32))
    ones = jnp.ones((n, d), jnp.bfloat16)
    seg = {"ln1": {"scale": ones},
           "attn": {k: layers[k] for k in ("wq", "wk", "wv", "wo")},
           "ln2": {"scale": ones},
           "ffn": {k: layers[k] for k in ("w_gate", "w_up", "w_down")}}
    params = {"embed": embed_weights(cfg, key),
              "final_norm": {"scale": jnp.ones((d,), jnp.bfloat16)},
              "seg0": seg}
    if not cfg["tie_word_embeddings"]:
        params["lm_head"] = head_weights(cfg, key)
    return params


def program_params(cfg: dict, seed: int) -> dict:
    """The program's parameter tree (one segment of dense attention
    layers, as ``repro.models.decoder`` lays it out), made on the device
    in one jitted call."""
    fn = jax.jit(lambda k: _program_tree(cfg, k))
    return fn(base_key(seed))
