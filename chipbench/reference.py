"""Plain reference of a decoder, shared by every model kind: float32 at
the highest matmul precision, one request and one layer at a time, with
the weights drawn again from the seed, and the full forward pass over
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
from typing import Hashable, List

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
    """A kind (``references/<kind>.py``) subclasses it with its layer:
    ``layer_weights(layer, group)`` draws one layer's weights (``layer``
    traced, ``group`` static), ``layer_forward(layer, w, h, control)`` runs
    it over one request's hidden states [s_pad, d]. A kind whose layers
    differ in their weights' shapes (dense layers before expert layers, or
    another head geometry) names each layer's group in ``layer_group``."""

    def __init__(self, cfg: dict, ring: int, seed: int):
        if cfg.get("a3"):
            raise ValueError("the reference computes exact attention only")
        self.cfg, self.ring = cfg, ring
        self.key = W.base_key(seed)
        # room for a GAP_ROWS slice that starts at the ring's last row
        self.s_pad = -(-(ring + GAP_ROWS) // Q_CHUNK) * Q_CHUNK
        self.eps = float(cfg["rms_norm_eps"])

    # -- weights ---------------------------------------------------------------
    def layer_group(self, layer: int) -> Hashable:
        """The group of ``layer``: the layers of one group have weights of
        the same shapes, drawn by one compiled program. One group unless a
        kind says otherwise."""
        return 0

    @partial(jax.jit, static_argnums=(0, 2, 3))
    def _layer(self, layer, group, control: bool):
        w = self.layer_weights(layer, group)
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

    def _norm(self, x):
        return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + self.eps)

    @staticmethod
    def _mm(x, w, control: bool):
        return (_fp8(x, -1) if control else x) @ w

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
            w = self._layer(layer, self.layer_group(layer), control)
            for r in range(len(seqs)):
                hs[r] = self.layer_forward(layer, w, hs[r], control)
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
