"""Dense GQA decoder layer: grouped-query attention with RoPE and exact
causal attention, then a SwiGLU feed-forward, pre-norm RMSNorm on both,
every layer alike in one segment ``seg0`` of the program's tree."""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Dict

import jax
import jax.numpy as jnp

from chipbench import reference as R
from chipbench import weights as W
from chipbench.reference import Q_CHUNK
from chipbench.workcount import BF16

_LEAF_IDS = {"wq": 1, "wk": 2, "wv": 3, "wo": 4,
             "w_gate": 5, "w_up": 6, "w_down": 7}


def layer_shapes(cfg: dict) -> Dict[str, tuple]:
    d, hd, f = cfg["hidden_size"], cfg["head_dim"], cfg["intermediate_size"]
    hq, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return {"wq": (d, hq * hd), "wk": (d, hkv * hd), "wv": (d, hkv * hd),
            "wo": (hq * hd, d), "w_gate": (d, f), "w_up": (d, f),
            "w_down": (f, d)}


def layer_weights(cfg: dict, key: jax.Array, layer) -> Dict[str, jax.Array]:
    """bf16 weights of one layer; ``layer`` may be traced."""
    lkey = W.layer_key(key, layer)
    return {name: W.normal(jax.random.fold_in(lkey, _LEAF_IDS[name]), shape,
                           shape[0] ** -0.5)
            for name, shape in layer_shapes(cfg).items()}


def _program_tree(cfg: dict, key: jax.Array) -> dict:
    d, n = cfg["hidden_size"], cfg["num_hidden_layers"]
    layers = jax.lax.map(lambda i: layer_weights(cfg, key, i),
                         jnp.arange(n, dtype=jnp.int32))
    ones = jnp.ones((n, d), jnp.bfloat16)
    seg = {"ln1": {"scale": ones},
           "attn": {k: layers[k] for k in ("wq", "wk", "wv", "wo")},
           "ln2": {"scale": ones},
           "ffn": {k: layers[k] for k in ("w_gate", "w_up", "w_down")}}
    return W.program_tree(cfg, key, {"seg0": seg})


def program_params(cfg: dict, seed: int) -> dict:
    """The program's parameter tree (one segment of dense attention
    layers, as ``repro.models.decoder`` lays it out), made on the device
    in one jitted call."""
    fn = jax.jit(lambda k: _program_tree(cfg, k))
    return fn(W.base_key(seed))


class Reference(R.Reference):
    """Exact causal attention at every position, a dense FFN in every
    layer."""

    def __init__(self, cfg: dict, ring: int, seed: int):
        super().__init__(cfg, ring, seed)
        self.hq = cfg["num_attention_heads"]
        self.hkv = cfg["num_key_value_heads"]
        self.hd = cfg["head_dim"]

    def layer_weights(self, layer, group) -> dict:
        return layer_weights(self.cfg, self.key, layer)

    def layer_forward(self, layer: int, w: dict, h, control: bool):
        q, k, v = self._qkv(w, h, control)
        return self._finish_layer(w, h, self._attend_exact(q, k, v), control)

    def _rope(self, x, pos):
        hd = x.shape[-1]
        rot = int(hd * self.cfg["partial_rotary_factor"]) // 2 * 2
        half = rot // 2
        freqs = 1.0 / (self.cfg["rope_theta"]
                       ** (jnp.arange(half, dtype=jnp.float32) / half))
        ang = pos[:, None, None].astype(jnp.float32) * freqs
        x1, x2, rest = x[..., :half], x[..., half:rot], x[..., rot:]
        c, s = jnp.cos(ang), jnp.sin(ang)
        return jnp.concatenate([x1 * c - x2 * s, x1 * s + x2 * c, rest], -1)

    @partial(jax.jit, static_argnums=(0, 3))
    def _qkv(self, w, h, control: bool):
        s = h.shape[0]
        pos = jnp.arange(s, dtype=jnp.int32)
        hn = self._norm(h)
        mm = lambda name: self._mm(hn, w[name], control)
        q = self._rope(mm("wq").reshape(s, self.hq, self.hd), pos)
        k = self._rope(mm("wk").reshape(s, self.hkv, self.hd), pos)
        v = mm("wv").reshape(s, self.hkv, self.hd)
        return q, k, v

    @partial(jax.jit, static_argnums=(0,))
    def _attend_exact(self, q, k, v):
        s = q.shape[0]
        g = self.hq // self.hkv
        scale = self.hd ** -0.5
        cols = jnp.arange(s)

        def chunk(i):
            qc = jax.lax.dynamic_slice_in_dim(q, i * Q_CHUNK, Q_CHUNK, 0)
            qc = qc.reshape(Q_CHUNK, self.hkv, g, self.hd) * scale
            sc = jnp.einsum("chgd,khd->hgck", qc, k)
            rows = i * Q_CHUNK + jnp.arange(Q_CHUNK)
            sc = jnp.where(cols[None, :] <= rows[:, None], sc, -jnp.inf)
            p = jax.nn.softmax(sc, axis=-1)
            o = jnp.einsum("hgck,khd->chgd", p, v)
            return o.reshape(Q_CHUNK, self.hq, self.hd)

        return jax.lax.map(chunk, jnp.arange(s // Q_CHUNK)).reshape(
            s, self.hq, self.hd)

    @partial(jax.jit, static_argnums=(0, 4))
    def _finish_layer(self, w, h, o, control: bool):
        mm = lambda x, name: self._mm(x, w[name], control)
        h = h + mm(o.reshape(h.shape[0], -1), "wo")
        hn = self._norm(h)
        return h + mm(jax.nn.silu(mm(hn, "w_gate")) * mm(hn, "w_up"), "w_down")


@dataclasses.dataclass(frozen=True)
class Work:
    """Counts from the configuration's shapes alone (never from the
    compiled program), lower bounds on the work of exact attention: a
    decode step reads every weight but the embedding table once, plus the
    valid K/V rows of each lane."""
    cfg: dict
    ring: int

    @property
    def d(self) -> int:
        return self.cfg["hidden_size"]

    @property
    def hd(self) -> int:
        return self.cfg["head_dim"]

    @property
    def layers(self) -> int:
        return self.cfg["num_hidden_layers"]

    @property
    def hq(self) -> int:
        return self.cfg["num_attention_heads"]

    @property
    def hkv(self) -> int:
        return self.cfg["num_key_value_heads"]

    @property
    def vocab(self) -> int:
        return self.cfg["vocab_size"]

    def layer_params(self) -> int:
        d, hd = self.d, self.hd
        attn = d * self.hq * hd * 2 + d * self.hkv * hd * 2
        ffn = 3 * d * self.cfg["intermediate_size"]
        return attn + ffn + 2 * d

    def params(self) -> int:
        embed = self.vocab * self.d
        head = 0 if self.cfg["tie_word_embeddings"] else self.vocab * self.d
        return self.layers * self.layer_params() + embed + head + self.d

    def weight_bytes(self) -> int:
        return self.params() * BF16

    def step_weight_bytes(self) -> int:
        """Weights one decode step reads: all but the embedding table."""
        embed = 0 if self.cfg["tie_word_embeddings"] else self.vocab * self.d
        return (self.params() - embed) * BF16

    def kv_bytes_per_token(self) -> int:
        return self.layers * 2 * self.hkv * self.hd * BF16

    # -- decode ----------------------------------------------------------------
    def decode_lane_bytes(self, keys: int) -> int:
        """Cache bytes one lane's decode step needs over ``keys`` positions."""
        return keys * self.kv_bytes_per_token()

    def decode_lane_flops(self, keys: int) -> int:
        """Model operations of one decoded token over ``keys`` positions."""
        dense = 2 * (self.layers * self.layer_params()
                     + self.vocab * self.d)
        return dense + self.layers * 4 * self.hq * self.hd * keys

    def decode_step_least_s(self, steps: int, lane_keys, peaks: dict) -> float:
        """Least time of ``steps`` decode steps that decoded tokens with the
        given key counts: the larger of the FLOP and the byte bound."""
        flops = sum(self.decode_lane_flops(k) for k in lane_keys)
        bytes_ = (steps * self.step_weight_bytes()
                  + sum(self.decode_lane_bytes(k) for k in lane_keys))
        return max(flops / peaks["bf16_flops_per_s"],
                   bytes_ / peaks["hbm_bytes_per_s"])

    # -- prefill ---------------------------------------------------------------
    def prefill_flops(self, start: int, length: int, last: bool) -> int:
        """Model operations of prompt positions start .. start+length-1
        (causal exact attention); ``last`` adds the unembedding of the
        prompt's final position."""
        dense = 2 * self.layers * self.layer_params() * length
        # sum over positions p of the p+1 keys each attends
        keys = length * start + length * (length + 1) // 2
        attn = self.layers * 4 * self.hq * self.hd * keys
        head = 2 * self.vocab * self.d if last else 0
        return dense + attn + head
