"""Unified decoder stack covering all assigned architectures.

A model is a sequence of *segments*: maximal runs of layers sharing the
same (block kind, ffn kind, attention window) signature. Each segment's
layer parameters are stacked on a leading ``layers`` axis and executed
with ``lax.scan`` (compact HLO for the 512-device dry-run; remat applies
per layer). Examples:

  phi4-mini        -> 1 segment  (attention + dense FFN, full window)
  deepseek-moe     -> 2 segments (1 dense-FFN layer, 27 MoE layers)
  gemma3           -> 12 segments (5 local / 1 global alternating)
  recurrentgemma   -> 17 segments (rglru pairs / attention, 1:2)
  xlstm            -> alternating mLSTM / sLSTM segments

Every segment kind implements the per-segment **mixer-state interface**
(:mod:`repro.models.mixer`): ``init_state / forward / prefill_full /
prefill_chunk / decode_step``. The four execution paths here — train
forward, whole-prompt prefill, chunked ragged admission prefill, and
ragged decode — are each ONE kind-agnostic loop over segments; per-kind
behavior (KV ring buffers + A^3 sorted columns, conv tail + LRU hidden
state, mLSTM matrix memory, sLSTM cell state) lives entirely behind the
mixer registry, with uniform ragged pad-lane masking. Chunked admission
therefore covers every architecture, including recurrent/hybrid stacks
(the mid-prompt recurrent carry is part of each mixer's
``prefill_chunk``).

KV caches are **ring buffers** sized ``min(max_len, window)`` per
segment — sliding-window layers at 500k context keep an O(window) cache,
which is what makes ``long_500k`` runnable for SWA/hybrid archs.

Approximation (the paper's technique) is applied at inference only
(paper SSVI-B); ``decode_step`` takes an ``A3Config`` and routes windowless
attention layers through ``a3_decode_attention``.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.config import A3Config, A3Mode, BlockKind, ModelConfig
from repro.models import xlstm as xl
from repro.models.common import (
    Params,
    shard_act,
    attention_init,
    cross_entropy_loss,
    dense_init,
    embed_init,
    ffn_apply,
    ffn_init,
    rmsnorm,
    rmsnorm_init,
    softcap,
)
# FULL_WINDOW and cache_len_for are re-exported: they are decoder's
# long-standing public cache-geometry API (ring sizing), now owned by
# the mixer module alongside the segment machinery.
from repro.models.mixer import (  # noqa: F401
    FULL_WINDOW,
    MIXERS,
    SegmentSpec,
    build_segments,
    cache_len_for,
)
from repro.models.moe import moe_apply, moe_init
from repro.models.rglru import rglru_init


def padded_vocab(v: int) -> int:
    """Pad vocab to a multiple of 128 (MXU lane + mesh divisibility)."""
    return ((v + 127) // 128) * 128


# ---------------------------------------------------------------------------
# parameter init
# ---------------------------------------------------------------------------

def _layer_init(key, cfg: ModelConfig, seg: SegmentSpec) -> Params:
    dtype = jnp.dtype(cfg.dtype)
    d, hd = cfg.d_model, cfg.resolved_head_dim
    ks = jax.random.split(key, 4)
    p: Params = {"ln1": rmsnorm_init(d, dtype)}
    if seg.kind == BlockKind.ATTENTION:
        p["attn"] = attention_init(ks[0], d, cfg.num_heads, cfg.num_kv_heads,
                                   hd, dtype)
    elif seg.kind == BlockKind.RGLRU:
        p["rnn"] = rglru_init(ks[0], d, cfg.num_heads * hd, dtype)
    elif seg.kind == BlockKind.MLSTM:
        p["mlstm"] = xl.mlstm_init(ks[0], d, cfg.num_heads, hd, dtype)
    elif seg.kind == BlockKind.SLSTM:
        p["slstm"] = xl.slstm_init(ks[0], d, cfg.num_heads, dtype)
    if seg.ffn != "none":
        p["ln2"] = rmsnorm_init(d, dtype)
    if seg.ffn == "dense":
        p["ffn"] = ffn_init(ks[1], d, cfg.d_ff, dtype, act=cfg.act)
    elif seg.ffn == "moe":
        moe_cfg = cfg.moe
        if (moe_cfg.d_expert or 0) == 0:
            moe_cfg = dataclasses.replace(moe_cfg, d_expert=cfg.d_ff)
        p["moe"] = moe_init(ks[1], d, moe_cfg, dtype)
    return p


def init_params(key, cfg: ModelConfig) -> Params:
    dtype = jnp.dtype(cfg.dtype)
    vp = padded_vocab(cfg.vocab_size)
    segs = build_segments(cfg)
    n_keys = 2 + len(segs)
    keys = jax.random.split(key, n_keys)
    params: Params = {
        "embed": embed_init(keys[0], vp, cfg.d_model, dtype),
        "final_norm": rmsnorm_init(cfg.d_model, dtype),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = dense_init(keys[1], cfg.d_model, vp, dtype)
    for si, seg in enumerate(segs):
        lkeys = jax.random.split(keys[2 + si], seg.count)
        stacked = jax.vmap(lambda k: _layer_init(k, cfg, seg))(lkeys)
        params[f"seg{si}"] = stacked
    return params


def init_params_shape(cfg: ModelConfig) -> Params:
    """ShapeDtypeStruct pytree of the params (no allocation; dry-run)."""
    return jax.eval_shape(lambda k: init_params(k, cfg), jax.random.PRNGKey(0))


# ---------------------------------------------------------------------------
# forward (train / prefill)
# ---------------------------------------------------------------------------

def _moe_cfg(cfg: ModelConfig):
    m = cfg.moe
    if m is not None and (m.d_expert or 0) == 0:
        m = dataclasses.replace(m, d_expert=cfg.d_ff)
    return m


@jax.named_scope("ffn")
def _ffn_block(lp: Params, h: jax.Array, cfg: ModelConfig,
               seg: SegmentSpec) -> Tuple[jax.Array, jax.Array]:
    """Kind-independent FFN half of a block. Returns (h, moe_aux_loss)."""
    aux = jnp.zeros((), jnp.float32)
    if seg.ffn == "dense":
        hn = rmsnorm(lp["ln2"], h, cfg.norm_eps)
        h = h + ffn_apply(lp["ffn"], hn, act=cfg.act)
    elif seg.ffn == "moe":
        hn = rmsnorm(lp["ln2"], h, cfg.norm_eps)
        o, moe_aux = moe_apply(lp["moe"], hn, _moe_cfg(cfg))
        h = h + o
        aux = aux + moe_aux["moe_aux_loss"]
    return h, aux


def _block_forward(lp: Params, h: jax.Array, positions: jax.Array,
                   cfg: ModelConfig, seg: SegmentSpec,
                   attn_chunk: int) -> Tuple[jax.Array, jax.Array]:
    """One layer forward (full sequence). Returns (h, moe_aux_loss)."""
    h = shard_act(h, "hidden")
    hn = rmsnorm(lp["ln1"], h, cfg.norm_eps)
    h = h + MIXERS[seg.kind].forward(lp, hn, cfg=cfg, seg=seg,
                                     positions=positions,
                                     attn_chunk=attn_chunk)
    return _ffn_block(lp, h, cfg, seg)


def _run_segment(params_seg: Params, h: jax.Array, positions: jax.Array,
                 cfg: ModelConfig, seg: SegmentSpec, remat: str,
                 attn_chunk: int) -> Tuple[jax.Array, jax.Array]:
    def body(carry, lp):
        out, aux = _block_forward(lp, carry, positions, cfg, seg, attn_chunk)
        return out, aux

    if remat == "full":
        body = jax.checkpoint(body, prevent_cse=False)
    elif remat == "dots":
        body = jax.checkpoint(
            body, prevent_cse=False,
            policy=jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims)
    h, auxs = jax.lax.scan(body, h, params_seg)
    return h, jnp.sum(auxs)


def embed_tokens(params: Params, cfg: ModelConfig, tokens: jax.Array
                 ) -> jax.Array:
    h = params["embed"][tokens]
    return h * jnp.asarray(math.sqrt(cfg.d_model), h.dtype)


@jax.named_scope("lm_head")
def unembed(params: Params, cfg: ModelConfig, h: jax.Array) -> jax.Array:
    h = rmsnorm(params["final_norm"], h, cfg.norm_eps)
    if cfg.tie_embeddings:
        logits = h @ params["embed"].T
    else:
        logits = h @ params["lm_head"]
    logits = softcap(logits, cfg.logit_softcap)
    vp = logits.shape[-1]
    if vp != cfg.vocab_size:       # mask the vocab-padding columns
        pad_mask = jnp.arange(vp) >= cfg.vocab_size
        logits = jnp.where(pad_mask, jnp.asarray(-1e30, logits.dtype),
                           logits)
    return logits


def forward_hidden(
    params: Params,
    cfg: ModelConfig,
    tokens: Optional[jax.Array] = None,        # [B, S] int32
    inputs_embeds: Optional[jax.Array] = None,  # [B, S, D] (frontend stubs)
    *,
    positions: Optional[jax.Array] = None,
    remat: str = "none",
    attn_chunk: int = 1024,
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Full-sequence forward up to (not including) the unembed.
    Returns (hidden [B, S, D], aux)."""
    if inputs_embeds is not None:
        h = inputs_embeds.astype(jnp.dtype(cfg.dtype))
        b, s, _ = h.shape
    else:
        b, s = tokens.shape
        h = embed_tokens(params, cfg, tokens)
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    aux_total = jnp.zeros((), jnp.float32)
    for si, seg in enumerate(build_segments(cfg)):
        h, aux = _run_segment(params[f"seg{si}"], h, positions, cfg, seg,
                              remat, attn_chunk)
        aux_total = aux_total + aux
    return h, {"moe_aux_loss": aux_total}


def forward(
    params: Params,
    cfg: ModelConfig,
    tokens: Optional[jax.Array] = None,
    inputs_embeds: Optional[jax.Array] = None,
    *,
    positions: Optional[jax.Array] = None,
    remat: str = "none",
    attn_chunk: int = 1024,
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Full-sequence forward -> (logits [B, S, Vp], aux)."""
    h, aux = forward_hidden(params, cfg, tokens, inputs_embeds,
                            positions=positions, remat=remat,
                            attn_chunk=attn_chunk)
    return unembed(params, cfg, h), aux


def chunked_ce(params: Params, cfg: ModelConfig, h: jax.Array,
               labels: jax.Array, ce_chunk: int = 512) -> jax.Array:
    """Cross-entropy without materializing [B, S, Vp] logits.

    The unembed + log-softmax runs per sequence-chunk under a
    ``lax.scan`` with ``jax.checkpoint``: peak logits memory drops from
    O(S x Vp) to O(ce_chunk x Vp) (e.g. 90 GiB -> 350 MiB per device on
    internlm2 train_4k), and the backward recomputes each chunk's logits
    instead of keeping them. This is a production-LM-framework standard;
    the dry-run memory analysis in EXPERIMENTS.md quantifies it.
    """
    b, s, _ = h.shape
    c = min(ce_chunk, s)
    if s % c != 0:
        c = s                                # fallback: single chunk
    n = s // c

    def chunk_nll(hc, lc):
        hc = shard_act(hc, "hidden")
        logits = unembed(params, cfg, hc)              # [B, c, Vp]
        lf = logits.astype(jnp.float32)
        m = jnp.max(lf, axis=-1, keepdims=True)
        lse = jnp.log(jnp.sum(jnp.exp(lf - m), axis=-1)) + m[..., 0]
        safe = jnp.maximum(lc, 0)
        gold = jnp.take_along_axis(lf, safe[..., None], axis=-1)[..., 0]
        valid = (lc != -1).astype(jnp.float32)
        return jnp.sum((lse - gold) * valid), jnp.sum(valid)

    chunk_nll = jax.checkpoint(chunk_nll, prevent_cse=False)

    if n == 1:
        nll, cnt = chunk_nll(h, labels)
        return nll / jnp.maximum(cnt, 1.0)

    hc = jnp.moveaxis(h.reshape(b, n, c, h.shape[-1]), 1, 0)
    lc = jnp.moveaxis(labels.reshape(b, n, c), 1, 0)

    def body(carry, xs):
        nll, cnt = chunk_nll(*xs)
        return (carry[0] + nll, carry[1] + cnt), None

    (nll, cnt), _ = jax.lax.scan(
        body, (jnp.zeros(()), jnp.zeros(())), (hc, lc))
    return nll / jnp.maximum(cnt, 1.0)


def lm_loss(params: Params, cfg: ModelConfig, tokens: jax.Array,
            labels: jax.Array, *, inputs_embeds: Optional[jax.Array] = None,
            remat: str = "none", attn_chunk: int = 1024,
            ce_chunk: int = 512) -> Tuple[jax.Array, Dict]:
    h, aux = forward_hidden(params, cfg, tokens, inputs_embeds, remat=remat,
                            attn_chunk=attn_chunk)
    loss = chunked_ce(params, cfg, h, labels, ce_chunk)
    total = loss + aux["moe_aux_loss"]
    return total, {"lm_loss": loss, **aux}


# ---------------------------------------------------------------------------
# KV / recurrent caches
# ---------------------------------------------------------------------------

def init_cache(cfg: ModelConfig, batch: int, max_len: int,
               dtype=None, a3: bool = False) -> Dict[str, Any]:
    """Per-segment decode state via the mixer interface. Attention:
    ring-buffer K/V sized min(max_len, window). Recurrent: carried
    states.

    ``a3=True`` additionally allocates the *sorted key matrix* for
    global-attention segments (the paper's comprehension-time
    preprocessing, kept alongside the cache exactly like the ASIC's
    40KB sorted-key SRAM next to the 20KB key SRAM) plus the
    ``sorted_upto`` watermark for the exact fresh-tail policy."""
    dtype = dtype or jnp.dtype(cfg.dtype)
    return {f"seg{si}": MIXERS[seg.kind].init_state(cfg, seg, batch,
                                                    max_len, dtype, a3)
            for si, seg in enumerate(build_segments(cfg))}


def init_page_pool(cfg: ModelConfig, pages: int, page_size: int,
                   dtype=None, a3: bool = False,
                   kv_quant: str = "none") -> Dict[str, Any]:
    """Paged prefix-cache pool: the page-axis view of the decode cache.

    Where :func:`init_cache` allocates per-*slot* state (a [L, B, ...]
    leaf per segment), this allocates the per-*page* store the serving
    prefix cache (:mod:`repro.serve.prefix_cache`) copies admitted
    prompts into: a logical page spans ``page_size`` token positions
    across every segment at once, so one page id indexes each attention
    segment's [L, pages, Hkv, page_size, hd] K/V arrays. Segments whose
    per-token state is a fixed-size carry (recurrent kinds) contribute
    no pool arrays — their state is snapshotted at page boundaries by
    the trie, not paged. ``a3`` is accepted for signature symmetry with
    ``init_cache``; sorted-key state is a whole-ring property restored
    at gather time, never paged. With ``kv_quant="int8"`` attention
    pool pages are stored as int8 with per-page fp32 scale leaves
    (``k_scale``/``v_scale``, [L, pages, Hkv, 1, 1]); the gather hook
    dequantizes back to the slot-cache dtype inside the one-dispatch
    warm gather."""
    dtype = dtype or jnp.dtype(cfg.dtype)
    pool: Dict[str, Any] = {}
    for si, seg in enumerate(build_segments(cfg)):
        seg_pages = MIXERS[seg.kind].init_pages(cfg, seg, pages,
                                                page_size, dtype, a3,
                                                kv_quant=kv_quant)
        if seg_pages is not None:
            pool[f"seg{si}"] = seg_pages
    return pool


# ---------------------------------------------------------------------------
# decode step
# ---------------------------------------------------------------------------

def _decode_block(lp: Params, cache_slice: Dict[str, jax.Array],
                  h: jax.Array, pos: jax.Array, cfg: ModelConfig,
                  seg: SegmentSpec, a3: A3Config, use_kernel: bool,
                  probe: bool = False):
    h = shard_act(h, "hidden")
    hn = rmsnorm(lp["ln1"], h, cfg.norm_eps)
    o, new_slice = MIXERS[seg.kind].decode_step(
        lp, cache_slice, hn, cfg=cfg, seg=seg, pos=pos, a3=a3,
        use_kernel=use_kernel, probe=probe)
    h = h + o
    h, aux = _ffn_block(lp, h, cfg, seg)
    return h, new_slice, aux


def decode_step(
    params: Params,
    cfg: ModelConfig,
    cache: Dict[str, Any],
    token: Optional[jax.Array] = None,          # [B] int32
    pos: jax.Array = None,                      # int32 position: scalar or [B]
    *,
    input_embed: Optional[jax.Array] = None,    # [B, D]
    a3: A3Config = A3Config(),
    use_kernel: bool = False,
    probe: bool = False,
) -> Tuple[jax.Array, Dict[str, Any]]:
    """One autoregressive step -> (logits [B, Vp], new cache).

    ``pos`` may be a scalar (all sequences at the same position) or a
    per-sequence vector [B] (*ragged* decode): each sequence writes its
    token at its own ring slot and masks its own valid window, so a
    continuous-batching engine can advance slots at arbitrary position
    skew in a single dispatch.

    ``probe=True`` (A^3 global-attention segments only) additionally
    returns ``(logits, cache, (probe_sum [B, 2], n_probed_layers))``:
    the per-layer (candidate count, captured-score-mass ratio) leaves
    summed over every probed layer, for telemetry sampling. The logits
    and cache are computed by the identical ops either way.
    """
    if input_embed is not None:
        h = input_embed[:, None, :].astype(jnp.dtype(cfg.dtype))
    else:
        h = embed_tokens(params, cfg, token[:, None])
    pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (h.shape[0],))
    new_cache: Dict[str, Any] = {}
    probe_sum, probe_layers = None, 0
    _RO = ("sk_vals", "sk_rows", "sorted_upto")
    for si, seg in enumerate(build_segments(cfg)):
        seg_cache = cache[f"seg{si}"]
        ro = {k: v for k, v in seg_cache.items() if k in _RO}
        mut = {k: v for k, v in seg_cache.items() if k not in _RO}

        def body(carry, xs):
            lp, cs, ro_s = xs
            out, ns, aux = _decode_block(lp, {**cs, **ro_s}, carry, pos,
                                         cfg, seg, a3, use_kernel,
                                         probe=probe)
            return out, ns

        h, new_seg = jax.lax.scan(body, h, (params[f"seg{si}"], mut, ro))
        if probe and "_probe" in new_seg:
            pr = new_seg.pop("_probe")           # [L_seg, B, 2]
            probe_sum = probe_sum + pr.sum(axis=0) if probe_sum is not None \
                else pr.sum(axis=0)
            probe_layers += pr.shape[0]
        new_cache[f"seg{si}"] = {**new_seg, **ro}
    logits = unembed(params, cfg, h)[:, 0]
    if probe:
        if probe_sum is None:
            probe_sum = jnp.zeros((h.shape[0], 2), jnp.float32)
        return logits, new_cache, (probe_sum, probe_layers)
    return logits, new_cache


# ---------------------------------------------------------------------------
# multi-step scanned decode: T steps per dispatch, sampling in-graph
# ---------------------------------------------------------------------------

@jax.named_scope("a3_resort")
def resort_sorted_keys(cache: Dict[str, Any], pos: jax.Array,
                       resort_every: int) -> Dict[str, Any]:
    """In-graph A^3 re-sort: fold each lane's ring into its sorted key
    columns when the exact tail outgrew ``resort_every``.

    The serving-time analogue of the paper's comprehension-time
    preprocessing (SSIV-C), previously scheduled by a host-side read of
    the ``sorted_upto`` watermarks every tick. Here the watermark check
    is part of the dispatch: for each global-attention segment a lane is
    *due* when ``pos - sorted_upto >= resort_every``; a ``lax.cond``
    skips the O(w log w) sort entirely on steps where no lane is due,
    and due lanes select the fresh sort via ``jnp.where`` (others keep
    their matrices and watermark bit-identically). Lanes riding along at
    ``pos < 0`` are never due.

    ``pos`` is the per-lane position about to be written — the sort runs
    *before* the step's ring write, so it sees exactly the ring the
    host-side re-sort used to see between dispatches.
    """
    from repro.core.candidate_selection import sort_key_columns
    new_cache: Dict[str, Any] = {}
    pos = jnp.asarray(pos, jnp.int32)
    for name, sc in cache.items():
        if not isinstance(sc, dict) or "sk_vals" not in sc:
            new_cache[name] = sc
            continue
        due = (pos >= 0) & (pos - sc["sorted_upto"][0] >= resort_every)

        def _fold(op, due=due):
            k, skv, skr, upto = op
            sk = jax.vmap(jax.vmap(jax.vmap(sort_key_columns)))(k)
            d5 = due[None, :, None, None, None]
            return (jnp.where(d5, sk.values, skv),
                    jnp.where(d5, sk.rows, skr),
                    jnp.where(due[None, :], pos[None, :], upto))

        def _keep(op):
            _, skv, skr, upto = op
            return skv, skr, upto

        skv, skr, upto = jax.lax.cond(
            jnp.any(due), _fold, _keep,
            (sc["k"], sc["sk_vals"], sc["sk_rows"], sc["sorted_upto"]))
        new_cache[name] = {**sc, "sk_vals": skv, "sk_rows": skr,
                           "sorted_upto": upto}
    return new_cache


# Poison-quarantine sentinel for the decode token ring: emitted (once)
# by a lane whose logits went non-finite (NaN/Inf), then the lane
# freezes exactly like an exhausted ride-along. Distinct from -1
# (inactive lane) so the per-block harvest can tell "no token" from
# "poisoned lane" without any extra device read — the flag rides the
# ring the host already syncs once per block.
POISON = -2


@jax.named_scope("sample")
def sample_logits(logits: jax.Array, *, temperature: float = 0.0,
                  rng: Optional[jax.Array] = None,
                  pos: Optional[jax.Array] = None,
                  ids: Optional[jax.Array] = None) -> jax.Array:
    """In-graph next-token sampling -> token ids [B].

    ``temperature == 0`` (or no ``rng``) is greedy argmax — identical to
    the host-side ``argmax`` the engine used to run after a device
    round-trip. With ``temperature > 0`` each lane draws from the
    tempered softmax with a key folded from (``ids``, ``pos``): the
    per-lane request id decorrelates concurrent and successive requests
    (identical prompts do not share a key stream), while folding the
    absolute position — not the step index — keeps a lane's draw at
    position p independent of how decode steps are blocked into
    dispatches or which engine slot the request occupies.
    """
    if temperature <= 0.0 or rng is None:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    if pos is None:
        pos = jnp.zeros((logits.shape[0],), jnp.int32)
    if ids is None:
        ids = jnp.zeros((logits.shape[0],), jnp.int32)
    keys = jax.vmap(lambda u, p: jax.random.fold_in(
        jax.random.fold_in(rng, u), p))(ids, pos)
    draw = lambda k, lg: jax.random.categorical(
        k, lg.astype(jnp.float32) / temperature)
    return jax.vmap(draw)(keys, logits).astype(jnp.int32)


def decode_block(
    params: Params,
    cfg: ModelConfig,
    cache: Dict[str, Any],
    token: jax.Array,                 # [B] int32 last emitted token per lane
    pos: jax.Array,                   # [B] int32 next position; -1 = ride-along
    steps_left: jax.Array,            # [B] int32 steps this lane may advance
    *,
    steps: int,
    a3: A3Config = A3Config(),
    use_kernel: bool = False,
    resort_every: int = 0,
    temperature: float = 0.0,
    rng: Optional[jax.Array] = None,
    sample_ids: Optional[jax.Array] = None,   # [B] per-request sample keys
    probe: bool = False,
) -> Tuple[jax.Array, jax.Array, Dict[str, Any]]:
    """Run ``steps`` autoregressive decode steps in ONE dispatch via
    ``lax.scan`` -> (token ring [B, steps] int32, token carry [B] int32,
    new cache).

    The *carry* is the scan's final per-lane token — exactly the value a
    caller would feed as ``token`` to the next block. Returning it as a
    device array lets a serving loop chain blocks without ever
    harvesting the ring on the critical path: the next dispatch consumes
    the carry directly and the ring read becomes deferrable
    bookkeeping. Frozen lanes (budget spent, ``pos = -1`` ride-alongs,
    poisoned) pass their input token through unchanged, so the carry is
    valid for every lane that was valid on entry.

    The whole inner loop is device-resident: each scan step (a) re-sorts
    due lanes' A^3 key columns in-graph (:func:`resort_sorted_keys` —
    no host watermark read), (b) runs :func:`decode_step`, and (c)
    samples the next token in-graph (:func:`sample_logits`), feeding it
    to the following step. The host syncs once per block to harvest the
    emitted-token ring instead of once (or three times) per token.

    Lanes are masked per step: a lane is *active* while ``pos >= 0`` and
    its ``steps_left`` budget is unspent. Inactive lanes ride along at
    ``pos = -1`` — their ring writes scatter out of bounds and are
    dropped (the ragged-decode machinery), recurrent segments reselect
    their carried state bit-identically (the mixer interface's uniform
    pad-lane masking), their ring entries read -1, and their carried
    token/pos freeze — so lanes that exhaust budget or hit ``max_len``
    mid-block leave ALL cache state untouched, for every segment kind.
    A lane whose logits go non-finite (NaN/Inf — e.g. a corrupted mixer
    state) emits the :data:`POISON` sentinel once and freezes the same
    way; the host reads the sentinel off the ring it already harvests,
    so poison detection costs no extra sync and healthy lanes stay
    bit-identical. With ``steps=1`` this is exactly one
    :func:`decode_step` plus in-graph sampling.

    ``probe=True`` (A^3 telemetry) returns a 4-tuple ``(ring, carry,
    cache, probe [B, 3])`` where the probe accumulates, over the
    block's *advanced* steps only, ``(samples, sum of per-step mean
    candidate count, sum of per-step captured-score-mass ratio)`` per
    lane — in-graph state that lands with the same ring harvest the
    host already performs. The token/cache path runs the identical ops.
    """
    b = token.shape[0]
    pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (b,))
    steps_left = jnp.broadcast_to(jnp.asarray(steps_left, jnp.int32), (b,))
    do_resort = resort_every > 0 and a3.mode != A3Mode.OFF

    def one_step(carry, _):
        if probe:
            token, pos, remaining, cache, acc = carry
        else:
            token, pos, remaining, cache = carry
        active = (pos >= 0) & (remaining > 0)
        eff_pos = jnp.where(active, pos, -1)
        if do_resort:
            cache = resort_sorted_keys(cache, eff_pos, resort_every)
        if probe:
            logits, cache, (psum, players) = decode_step(
                params, cfg, cache, token, eff_pos, a3=a3,
                use_kernel=use_kernel, probe=True)
        else:
            logits, cache = decode_step(params, cfg, cache, token, eff_pos,
                                        a3=a3, use_kernel=use_kernel)
        nxt = sample_logits(logits, temperature=temperature, rng=rng,
                            pos=eff_pos, ids=sample_ids)
        # poison quarantine: a lane whose logits went non-finite — or
        # whose handoff token already carried the POISON mark — emits
        # POISON once and freezes like an exhausted ride-along. Healthy
        # lanes take the identical select, so their tokens and cache
        # state are bit-for-bit unchanged by this check.
        ok = jnp.all(jnp.isfinite(logits), axis=-1) & (token != POISON)
        advance = active & ok
        poisoned = active & ~ok
        emit = jnp.where(advance, nxt,
                         jnp.where(poisoned, POISON, -1))
        token = jnp.where(advance, nxt, token)
        pos = jnp.where(advance, pos + 1, pos)
        remaining = jnp.where(poisoned, 0,
                              jnp.where(advance, remaining - 1, remaining))
        if probe:
            nl = max(players, 1)
            step_row = jnp.stack(
                [jnp.ones((b,), jnp.float32),
                 psum[:, 0] / nl,
                 jnp.clip(psum[:, 1] / nl, 0.0, 1.0)], axis=1)
            acc = acc + jnp.where(advance[:, None], step_row, 0.0)
            return (token, pos, remaining, cache, acc), emit
        return (token, pos, remaining, cache), emit

    init = (token.astype(jnp.int32), pos, steps_left, cache)
    if probe:
        init = init + (jnp.zeros((b, 3), jnp.float32),)
        (tok_f, _, _, cache, acc), ring = jax.lax.scan(
            one_step, init, None, length=steps)
        return jnp.moveaxis(ring, 0, 1), tok_f, cache, acc
    (tok_f, _, _, cache), ring = jax.lax.scan(
        one_step, init, None, length=steps)
    return jnp.moveaxis(ring, 0, 1), tok_f, cache


# ---------------------------------------------------------------------------
# prefill: full-sequence forward that also fills the decode caches
# ---------------------------------------------------------------------------

def prefill(
    params: Params,
    cfg: ModelConfig,
    tokens: Optional[jax.Array] = None,
    inputs_embeds: Optional[jax.Array] = None,
    *,
    max_len: Optional[int] = None,
    attn_chunk: int = 1024,
    a3: bool = False,
    select_shards: int = 1,
) -> Tuple[jax.Array, Dict[str, Any]]:
    """Process a prompt, return (last-token logits [B, Vp], filled cache).
    ``a3=True`` also builds the sorted-key matrices for global-attention
    segments (comprehension-time preprocessing, paper SSIV-C).

    Only the final position's logits are computed (serving needs just
    the next-token distribution; a full [B, S, Vp] logits tensor at 32k
    prompt x 262k vocab would be ~0.5 TB)."""
    if inputs_embeds is not None:
        h = inputs_embeds.astype(jnp.dtype(cfg.dtype))
        b, s, _ = h.shape
    else:
        b, s = tokens.shape
        h = embed_tokens(params, cfg, tokens)
    max_len = max_len or s
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    cache: Dict[str, Any] = {}

    for si, seg in enumerate(build_segments(cfg)):
        def body(carry, lp, seg=seg):
            hh = shard_act(carry, "hidden")
            hn = rmsnorm(lp["ln1"], hh, cfg.norm_eps)
            o, ns = MIXERS[seg.kind].prefill_full(
                lp, hn, cfg=cfg, seg=seg, positions=positions,
                attn_chunk=attn_chunk, max_len=max_len, a3=a3,
                select_shards=select_shards)
            hh = hh + o
            hh, _ = _ffn_block(lp, hh, cfg, seg)
            return hh, ns

        h, seg_cache = jax.lax.scan(body, h, params[f"seg{si}"])
        cache[f"seg{si}"] = seg_cache

    logits = unembed(params, cfg, h[:, -1:])[:, 0]
    return logits, cache


# ---------------------------------------------------------------------------
# chunked / ragged admission prefill: extend per-slot caches in place
# ---------------------------------------------------------------------------

def prefill_chunk(
    params: Params,
    cfg: ModelConfig,
    cache: Dict[str, Any],
    tokens: jax.Array,                # [B, C] int32 (ragged, zero-padded)
    pos: jax.Array,                   # [B] int32 per-slot chunk start
    length: jax.Array,                # [B] int32 valid tokens; 0 = skip lane
    *,
    a3: bool = False,
    sort_lanes: Optional[jax.Array] = None,   # [B] bool; default: length > 0
    update_sort: bool = True,                 # static: False = sk leaves RO
    lane_slot: Optional[jax.Array] = None,    # [B] int32 distinct slots
) -> Tuple[jax.Array, Dict[str, Any]]:
    """Extend per-slot decode caches with one ragged batch of prompt chunks.

    Every lane processes ``length[b]`` tokens of its prompt starting at
    absolute position ``pos[b]`` — a single dispatch serves slots at
    arbitrary prompt cursors (ragged admission prefill). Without
    ``lane_slot`` lane ``b`` is cache slot ``b`` (``B`` = slots). With
    it, the ``B`` lanes are a packed subset of the slots: lane ``b``
    reads and writes slot ``lane_slot[b]`` (distinct slots), each
    segment gathers those slots' state on the slot axis, runs the same
    chunk maths on ``B`` lanes, and scatters the new state back into
    the cache; the other slots' rows are not touched. Works for every
    segment kind through the mixer-state interface: attention segments
    extend their KV rings, recurrent segments (RG-LRU conv tail + LRU
    hidden, mLSTM matrix memory, sLSTM cell state) carry their
    mid-prompt state across chunk boundaries, with pad positions masked
    out of the state update per lane. Lanes with ``length == 0`` are
    passed through untouched (their cache rows are bit-identical on
    output), so decoding slots can share the dispatch batch with
    prefilling ones. A lane at ``pos == 0`` first resets its state
    in-graph (a reused slot may hold a finished request's keys or
    recurrent state).

    With ``a3=True``, lanes in ``sort_lanes`` fold the updated ring into
    the per-column sorted-key matrices and advance ``sorted_upto`` to
    ``pos + length``. The engine passes only lanes on their *final*
    chunk (one sort per admitted prompt); the default sorts every
    active lane's chunk, which is correct but does the sort work
    per-chunk instead of per-prompt. ``update_sort=False`` (a *static*
    flag — a separate jit specialization) additionally keeps the sorted
    leaves out of the layer scan entirely, so non-final chunk ticks do
    not pay a per-layer copy of the sorted-key cache (the same
    read-only-leaf treatment ``decode_step`` applies).

    Chunking is output-invariant: a query's attention set (positions
    ``<= q``, within the segment window) does not depend on where chunk
    boundaries fall, so running a prompt through any chunk split yields
    the same cache rows and logits as :func:`prefill` up to fp
    summation order. With ``a3=True`` the chunk's keys are folded into
    the per-column sorted-key matrices (incremental comprehension-time
    preprocessing) and ``sorted_upto`` advances to ``pos + length``.

    Returns (logits [B, Vp] at each lane's last valid position, cache).
    """
    if lane_slot is not None:
        lane_slot = jnp.asarray(lane_slot, jnp.int32)
    b, c = tokens.shape
    h = embed_tokens(params, cfg, tokens)
    pos = jnp.asarray(pos, jnp.int32)
    length = jnp.asarray(length, jnp.int32)
    if sort_lanes is None:
        sort_lanes = length > 0
    sort_lanes = jnp.asarray(sort_lanes, bool)
    offs = jnp.arange(c, dtype=jnp.int32)
    positions = pos[:, None] + offs[None, :]               # [B, C]
    valid_tok = offs[None, :] < length[:, None]            # [B, C]
    new_cache: Dict[str, Any] = {}
    _RO = ("sk_vals", "sk_rows", "sorted_upto")
    for si, seg in enumerate(build_segments(cfg)):
        seg_cache = cache[f"seg{si}"]
        ro = {} if update_sort else \
            {k: v for k, v in seg_cache.items() if k in _RO}
        mut = seg_cache if update_sort else \
            {k: v for k, v in seg_cache.items() if k not in _RO}

        def body(carry, xs, seg=seg):
            lp, cs = xs
            hh = shard_act(carry, "hidden")
            hn = rmsnorm(lp["ln1"], hh, cfg.norm_eps)
            o, ns = MIXERS[seg.kind].prefill_chunk(
                lp, cs, hn, cfg=cfg, seg=seg, positions=positions,
                valid_tok=valid_tok, pos=pos, length=length,
                sort_lanes=sort_lanes, a3=a3)
            hh = hh + o
            hh, _ = _ffn_block(lp, hh, cfg, seg)
            return hh, ns

        lanes = mut if lane_slot is None else \
            jax.tree.map(lambda x: x[:, lane_slot], mut)
        h, new_seg = jax.lax.scan(body, h, (params[f"seg{si}"], lanes))
        if lane_slot is not None:
            new_seg = jax.tree.map(
                lambda x, y: x.at[:, lane_slot].set(y, unique_indices=True),
                mut, new_seg)
        new_cache[f"seg{si}"] = {**new_seg, **ro}
    bidx = jnp.arange(b, dtype=jnp.int32)
    last = jnp.clip(length - 1, 0, c - 1)
    logits = unembed(params, cfg, h[bidx, last][:, None])[:, 0]
    return logits, new_cache
