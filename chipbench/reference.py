"""Plain reference of the dense GQA decoder the configurations describe.

Float32 at the highest matmul precision, one request and one layer at a
time, with the weights drawn again from the seed (``weights.py``), and
exact causal attention at every position: the full forward pass over
each prompt with its served tokens.

It imports nothing of the program. ``control=True`` computes the same
model in fp8 (e4m3), the precision below the configuration's bfloat16:
every weight and every matmul input rounded to fp8, with one scale per
output channel of a weight and per row of an input, accumulated in
float32. That is the check's control.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import List

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import weights as W

Q_CHUNK = 512
GAP_ROWS = 64


@dataclasses.dataclass
class Sequence:
    """One served request: its prompt and the tokens the program served."""
    prompt: np.ndarray     # int32 [L]
    served: np.ndarray     # int32 [n]


FP8_MAX = 448.0            # largest finite float8_e4m3fn


def _fp8(x: jax.Array, axis: int) -> jax.Array:
    """fp8 (e4m3) round trip, scaled so each slice's largest magnitude
    along ``axis`` maps to the format's largest finite value."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.maximum(amax, 1e-30) / FP8_MAX
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


class Reference:
    def __init__(self, cfg: dict, ring: int, seed: int):
        if cfg.get("a3"):
            raise ValueError("the reference computes exact attention only")
        self.cfg, self.ring = cfg, ring
        self.key = W.base_key(seed)
        # room for a GAP_ROWS slice that starts at the ring's last row
        self.s_pad = -(-(ring + GAP_ROWS) // Q_CHUNK) * Q_CHUNK
        self.hq = cfg["num_attention_heads"]
        self.hkv = cfg["num_key_value_heads"]
        self.hd = cfg["head_dim"]
        self.eps = float(cfg["rms_norm_eps"])

    # -- weights ---------------------------------------------------------------
    @partial(jax.jit, static_argnums=(0, 2))
    def _layer(self, layer, control: bool):
        w = W.layer_weights(self.cfg, self.key, layer)
        w = {k: v.astype(jnp.float32) for k, v in w.items()}
        if control:
            w = {k: _fp8(v, 0) for k, v in w.items()}
        return w

    @partial(jax.jit, static_argnums=(0, 1))
    def _embed_head(self, control: bool):
        emb = W.embed_weights(self.cfg, self.key).astype(jnp.float32)
        head = (emb.T if self.cfg["tie_word_embeddings"]
                else W.head_weights(self.cfg, self.key).astype(jnp.float32))
        if control:
            emb, head = _fp8(emb, 1), _fp8(head, 0)
        return emb, head

    # -- pieces of a layer -------------------------------------------------------
    def _norm(self, x):
        return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + self.eps)

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

    @staticmethod
    def _mm(x, w, control: bool):
        return (_fp8(x, -1) if control else x) @ w

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

    @partial(jax.jit, static_argnums=(0,))
    def _embed(self, emb, tokens):
        return emb[tokens] * self.cfg["embedding_multiplier"]

    @partial(jax.jit, static_argnums=(0,))
    def _gap_rows(self, head, h, start, tok):
        """Gaps of ``tok`` [GAP_ROWS] at the rows start .. start+GAP_ROWS-1
        of ``h``, and the reference's logits' best there."""
        hc = jax.lax.dynamic_slice_in_dim(h, start, GAP_ROWS, 0)
        lg = self._norm(hc) @ head
        best = jnp.max(lg, -1)
        return best - jnp.take_along_axis(lg, tok[:, None], -1)[:, 0]

    @partial(jax.jit, static_argnums=(0,))
    def _control_gap_rows(self, head, chead, h, hc, start):
        """Gaps, in the reference's logits, of the tokens the control's
        logits put first."""
        sl = lambda x: jax.lax.dynamic_slice_in_dim(x, start, GAP_ROWS, 0)
        lg = self._norm(sl(h)) @ head
        tok = jnp.argmax(self._mm(self._norm(sl(hc)), chead, True), -1)
        return jnp.max(lg, -1) - jnp.take_along_axis(lg, tok[:, None], -1)[:, 0]

    # -- running the model ---------------------------------------------------------
    def final_hidden(self, seqs: List[Sequence], control: bool = False):
        """Final hidden states [s_pad, d] of each request; rows L-1 ..
        L+n-2 hold the positions whose logits chose its served tokens."""
        emb, _ = self._embed_head(control)
        toks = []
        for sq in seqs:
            full = np.concatenate([sq.prompt, sq.served[:-1]]).astype(np.int32)
            if len(full) > self.ring:
                raise ValueError("sequence longer than the ring")
            pad = np.zeros((self.s_pad,), np.int32)
            pad[:len(full)] = full
            toks.append(jnp.asarray(pad))
        hs = [self._embed(emb, t) for t in toks]
        del emb
        for layer in range(self.cfg["num_hidden_layers"]):
            w = self._layer(layer, control)
            for r, sq in enumerate(seqs):
                q, k, v = self._qkv(w, hs[r], control)
                o = self._attend_exact(q, k, v)
                hs[r] = self._finish_layer(w, hs[r], o, control)
            del w
        return hs

    def gaps(self, seqs: List[Sequence], control: bool = False):
        with jax.default_matmul_precision("highest"):
            return self._gaps(seqs, control)

    def _gaps(self, seqs: List[Sequence], control: bool):
        """Per request: the gaps (reference's best logit minus the
        reference's logit of the served token) at each served position, and
        with ``control`` the same gaps for the tokens the fp8 control puts
        first."""
        ref_h = self.final_hidden(seqs)
        ctl_h = self.final_hidden(seqs, control=True) if control else None
        _, head = self._embed_head(False)
        chead = self._embed_head(True)[1] if control else None
        served, ctl = [], []
        for r, sq in enumerate(seqs):
            L, n = len(sq.prompt), len(sq.served)
            g_s, g_c = [], []
            for c0 in range(0, n, GAP_ROWS):
                tok = np.zeros((GAP_ROWS,), np.int32)
                part = sq.served[c0:c0 + GAP_ROWS]
                tok[:len(part)] = part
                start = L - 1 + c0
                g_s.append(np.asarray(self._gap_rows(
                    head, ref_h[r], start, jnp.asarray(tok)))[:len(part)])
                if control:
                    g_c.append(np.asarray(self._control_gap_rows(
                        head, chead, ref_h[r], ctl_h[r], start))[:len(part)])
            served.append(np.concatenate(g_s))
            if control:
                ctl.append(np.concatenate(g_c))
        return served, (ctl if control else None)
