"""Unified per-segment mixer-state interface.

A model is a sequence of *segments* (maximal runs of layers sharing a
(block kind, ffn kind, attention window) signature — see
:class:`SegmentSpec`). Every segment kind carries decode-time state:

  ATTENTION -> KV ring buffers (+ the A^3 sorted key columns and the
               ``sorted_upto`` watermark on global-window layers)
  RGLRU     -> causal-conv tail + LRU hidden state
  MLSTM     -> (C, n, m) matrix-memory state
  SLSTM     -> (c, n, m, h) cell state

This module makes that state flow through ONE interface per kind — a
:class:`SegmentMixer` with five entry points — so the decoder's four
execution paths (train forward, whole-prompt prefill, chunked ragged
admission prefill, ragged decode) are each a single kind-agnostic loop
instead of three near-duplicate per-kind branches:

  ``init_state``     allocate the per-layer-stacked state pytree
  ``forward``        full-sequence mixer output (train; no state)
  ``prefill_full``   full-sequence output + end-of-prompt state
  ``prefill_chunk``  ragged mid-prompt chunk with carried state
  ``decode_step``    one ragged autoregressive step

Ragged pad-lane masking is uniform: in ``prefill_chunk`` a lane with
``length == 0`` and in ``decode_step`` a lane with ``pos < 0`` returns
its state **bit-identically** (attention: out-of-bounds scatter drop;
recurrent kinds: an explicit per-lane reselect), so idle / prefilling /
budget-exhausted engine slots can ride along in any dispatch without
their state advancing on garbage. A lane starting a fresh prompt
(``pos == 0, length > 0``) resets its state in-graph inside the chunk
dispatch — the slot may hold a finished request's state.

Each mixer consumes the post-``ln1`` normalized hidden ``hn`` and
returns the residual *delta* (the caller owns norm, residual add, and
the FFN half of the block, which is kind-independent).

The interface also carries the **paged prefix-cache hooks**
(:mod:`repro.serve.prefix_cache`): ``init_pages`` allocates a segment's
share of the page pool (attention: per-page K/V rows; recurrent kinds:
no per-token pages — their decode state is a fixed-size carry),
``write_page`` / ``gather_pages`` copy ring rows pool-ward /
slot-ward, and ``snapshot_state`` / ``restore_state`` capture / replay
the per-lane mixer state at a page boundary (the chunked-prefill carry
*is* the snapshot: for recurrent kinds it is the whole state; for
attention everything per-token lives in pages, so the snapshot is
empty and restore is the page gather).
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.config import A3Config, A3Mode, AttentionKind, BlockKind, \
    ModelConfig
from repro.kernels.decode_attention.ops import a3_decode_attention
from repro.models import xlstm as xl
from repro.models.common import (
    Params,
    attention_out,
    attention_qkv,
    attention_xla_flash,
    shard_act,
)
from repro.models.rglru import (
    CONV_WIDTH,
    rglru_apply_scan,
    rglru_chunk_step,
    rglru_decode_step,
)

FULL_WINDOW = 1 << 30


# ---------------------------------------------------------------------------
# segments
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class SegmentSpec:
    kind: BlockKind
    ffn: str                 # "dense" | "moe" | "none"
    window: int              # FULL_WINDOW for global attention
    layers: Tuple[int, ...]  # absolute layer indices

    @property
    def count(self) -> int:
        return len(self.layers)


def _layer_signature(cfg: ModelConfig, i: int) -> Tuple:
    kind = cfg.block_kind(i)
    if kind in (BlockKind.MLSTM, BlockKind.SLSTM):
        ffn = "dense" if cfg.d_ff else "none"
    elif cfg.moe is not None and i >= cfg.moe.num_dense_layers:
        ffn = "moe"
    else:
        ffn = "dense"
    window = FULL_WINDOW
    if kind == BlockKind.ATTENTION:
        if cfg.attention_kind == AttentionKind.SLIDING:
            window = cfg.window_size
        elif cfg.attention_kind == AttentionKind.LOCAL_GLOBAL:
            window = FULL_WINDOW if cfg.layer_is_global(i) else cfg.window_size
    return (kind, ffn, window)


def build_segments(cfg: ModelConfig) -> List[SegmentSpec]:
    segs: List[SegmentSpec] = []
    cur: List[int] = []
    cur_sig = None
    for i in range(cfg.num_layers):
        sig = _layer_signature(cfg, i)
        if sig != cur_sig and cur:
            segs.append(SegmentSpec(cur_sig[0], cur_sig[1], cur_sig[2],
                                    tuple(cur)))
            cur = []
        cur_sig = sig
        cur.append(i)
    if cur:
        segs.append(SegmentSpec(cur_sig[0], cur_sig[1], cur_sig[2], tuple(cur)))
    return segs


def cache_len_for(seg: SegmentSpec, max_len: int) -> int:
    if seg.kind != BlockKind.ATTENTION:
        return 0
    return min(max_len, seg.window)


# ---------------------------------------------------------------------------
# ring-buffer geometry (attention)
# ---------------------------------------------------------------------------

def _ring_slot_positions(w: int, pos: jax.Array) -> jax.Array:
    """Position held by each ring slot after writing position ``pos``.

    Slot s holds position p(s) = largest p' <= pos with p' % w == s.
    ``pos`` may be a scalar (-> [w]) or a per-batch vector [B] (-> [B, w]).
    """
    slots = jnp.arange(w, dtype=jnp.int32)
    pos = jnp.asarray(pos, jnp.int32)[..., None]
    return pos - jnp.mod(pos - slots, w)


def _ring_valid_mask(w: int, pos: jax.Array, window: int) -> jax.Array:
    """Validity of ring slots after writing position ``pos`` at pos % w.

    Valid iff p(s) >= 0 (written) and p(s) > pos - window. ``pos`` may be
    scalar or per-batch [B] (ragged decode); the mask gains a matching
    leading batch dim.
    """
    slot_pos = _ring_slot_positions(w, pos)
    pos = jnp.asarray(pos, jnp.int32)[..., None]
    return (slot_pos >= 0) & (slot_pos > pos - window)


def _lane_select(new: jax.Array, old: jax.Array,
                 active: jax.Array) -> jax.Array:
    """Per-lane state select: inactive lanes keep ``old`` bit-identically.
    ``active`` is [B]; leaves are [B, ...]."""
    shape = (old.shape[0],) + (1,) * (old.ndim - 1)
    return jnp.where(active.reshape(shape), new, old)


# ---------------------------------------------------------------------------
# ATTENTION mixer
# ---------------------------------------------------------------------------

def _attn_init_state(cfg: ModelConfig, seg: SegmentSpec, batch: int,
                     max_len: int, dtype, a3: bool) -> Dict[str, jax.Array]:
    L, hd = seg.count, cfg.resolved_head_dim
    w = cache_len_for(seg, max_len)
    state = {
        "k": jnp.zeros((L, batch, cfg.num_kv_heads, w, hd), dtype),
        "v": jnp.zeros((L, batch, cfg.num_kv_heads, w, hd), dtype),
    }
    if a3 and seg.window >= FULL_WINDOW:
        state["sk_vals"] = jnp.zeros((L, batch, cfg.num_kv_heads, w, hd),
                                     dtype)
        state["sk_rows"] = jnp.zeros((L, batch, cfg.num_kv_heads, w, hd),
                                     jnp.int32)
        state["sorted_upto"] = jnp.zeros((L, batch), jnp.int32)
    return state


def _attn_forward(lp: Params, hn: jax.Array, *, cfg: ModelConfig,
                  seg: SegmentSpec, positions: jax.Array,
                  attn_chunk: int, **_) -> jax.Array:
    q, k, v = attention_qkv(lp["attn"], hn, positions, cfg.num_heads,
                            cfg.num_kv_heads, cfg.resolved_head_dim,
                            cfg.rope_theta)
    q = shard_act(q, "q")
    k = shard_act(k, "kv")
    v = shard_act(v, "kv")
    window = None if seg.window >= FULL_WINDOW else jnp.int32(seg.window)
    o = attention_xla_flash(q, k, v, causal=True, window=window,
                            chunk=attn_chunk)
    return attention_out(lp["attn"], o)


def _attn_prefill_full(lp: Params, hn: jax.Array, *, cfg: ModelConfig,
                       seg: SegmentSpec, positions: jax.Array,
                       attn_chunk: int, max_len: int, a3: bool,
                       select_shards: int, **_
                       ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    b, s, _ = hn.shape
    hd = cfg.resolved_head_dim
    w = cache_len_for(seg, max_len)
    q, k, v = attention_qkv(lp["attn"], hn, positions, cfg.num_heads,
                            cfg.num_kv_heads, hd, cfg.rope_theta)
    q = shard_act(q, "q")
    k = shard_act(k, "kv")
    v = shard_act(v, "kv")
    window = None if seg.window >= FULL_WINDOW else jnp.int32(seg.window)
    o = attention_xla_flash(q, k, v, causal=True, window=window,
                            chunk=attn_chunk)
    # ring-write the last min(s, w) positions
    kc = jnp.zeros((k.shape[0], k.shape[1], w, hd), k.dtype)
    vc = jnp.zeros_like(kc)
    take = min(s, w)
    pos_tail = jnp.arange(s - take, s, dtype=jnp.int32)  # positions s-take..s-1
    slots = jnp.mod(pos_tail, w)
    kc = kc.at[:, :, slots].set(k[:, :, s - take:])
    vc = vc.at[:, :, slots].set(v[:, :, s - take:])
    state = {"k": kc, "v": vc}
    if a3 and seg.window >= FULL_WINDOW:
        from repro.core.candidate_selection import sort_key_columns
        ns = select_shards if w % max(select_shards, 1) == 0 else 1
        kb = kc.reshape(kc.shape[0], kc.shape[1], ns, w // ns, hd)
        sk = jax.vmap(jax.vmap(jax.vmap(sort_key_columns)))(kb)
        state["sk_vals"] = sk.values.reshape(kc.shape)
        state["sk_rows"] = sk.rows.reshape(kc.shape)       # block-local
        state["sorted_upto"] = jnp.full((kc.shape[0],), s, jnp.int32)
    return attention_out(lp["attn"], o), state


@jax.named_scope("attn_prefill")
def _attn_prefill_chunk(lp: Params, state: Dict[str, jax.Array],
                        hn: jax.Array, *, cfg: ModelConfig,
                        seg: SegmentSpec, positions: jax.Array,
                        valid_tok: jax.Array, pos: jax.Array,
                        length: jax.Array, sort_lanes: jax.Array,
                        a3: bool, **_
                        ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    b, c, _ = hn.shape
    hd = cfg.resolved_head_dim
    hkv, group = cfg.num_kv_heads, cfg.num_heads // cfg.num_kv_heads
    q, k, v = attention_qkv(lp["attn"], hn, positions, cfg.num_heads,
                            hkv, hd, cfg.rope_theta)           # [B, H, C, D]
    q = shard_act(q, "q")
    k = shard_act(k, "kv")
    v = shard_act(v, "kv")
    ck, cv = state["k"], state["v"]
    # A lane starting a new prompt (pos 0) zeroes its ring rows inside
    # the donated dispatch — the slot may hold a finished request's rows,
    # and whole-prompt-parity (incl. the A3 sort over the full ring)
    # needs unwritten rows to read as zeros. Fused here, this costs no
    # extra HBM sweep, unlike a host-side reset copy per admission.
    fresh = ((pos == 0) & (length > 0))[:, None, None, None]
    zero = jnp.asarray(0, ck.dtype)
    ck = jnp.where(fresh, zero, ck)
    cv = jnp.where(fresh, zero, cv)
    w = ck.shape[2]
    window = seg.window

    # Attention BEFORE the ring write: chunk queries see (a) the ring as
    # it stood before this chunk and (b) in-chunk keys, so a wrapping
    # write can never clobber a position an earlier query still needs.
    scale = hd ** -0.5
    qf = (q.astype(jnp.float32) * scale).reshape(b, hkv, group, c, hd)
    offs = jnp.arange(c, dtype=jnp.int32)
    slots = jnp.arange(w, dtype=jnp.int32)
    last_prev = pos - 1                                        # [B]
    slot_pos = last_prev[:, None] - jnp.mod(
        last_prev[:, None] - slots[None, :], w)                # [B, w]
    ring_mask = (slot_pos[:, None, :] >= 0) & \
        (slot_pos[:, None, :] > positions[:, :, None] - window)  # [B, C, w]
    chunk_mask = (offs[None, :, None] >= offs[None, None, :]) & \
        (offs[None, :, None] - offs[None, None, :] < window) & \
        valid_tok[:, None, :]                                  # [B, C, C]
    mask = jnp.concatenate([ring_mask, chunk_mask], -1)        # [B, C, w+C]

    s_ring = jnp.einsum("bhgqd,bhkd->bhgqk", qf,
                        ck.astype(jnp.float32))                # [B,Hkv,G,C,w]
    s_chunk = jnp.einsum("bhgqd,bhkd->bhgqk", qf,
                         k.astype(jnp.float32))                # [B,Hkv,G,C,C]
    s = jnp.concatenate([s_ring, s_chunk], -1)
    mb = mask[:, None, None]
    s = jnp.where(mb, s, -1e30)
    m = jnp.max(s, -1, keepdims=True)
    p = jnp.where(mb, jnp.exp(s - m), 0.0)
    l = jnp.sum(p, -1, keepdims=True)
    vcat = jnp.concatenate([cv, v], 2).astype(jnp.float32)     # [B,Hkv,w+C,D]
    acc = jnp.einsum("bhgqk,bhkd->bhgqd", p, vcat)
    o = jnp.where(l == 0.0, 0.0, acc / jnp.where(l == 0.0, 1.0, l))
    o = o.reshape(b, cfg.num_heads, c, hd).astype(hn.dtype)

    # Ragged ring write: pad slots and inactive lanes scatter to index w
    # (out of bounds -> dropped), leaving other slots' rows untouched.
    # When the chunk exceeds the ring (sliding windows) only the last w
    # chunk positions land, as in whole-prompt prefill.
    writable = valid_tok & (positions > (pos + length - 1)[:, None] - w)
    tgt = jnp.where(writable, jnp.mod(positions, w), w)        # [B, C]
    b2 = jnp.broadcast_to(jnp.arange(b, dtype=jnp.int32)[:, None], (b, c))
    kc = ck.at[b2, :, tgt].set(jnp.swapaxes(k, 1, 2), mode="drop")
    vc = cv.at[b2, :, tgt].set(jnp.swapaxes(v, 1, 2), mode="drop")
    new_state = {"k": kc, "v": vc}

    if a3 and "sk_vals" in state:
        # incremental comprehension-time preprocessing: fold the chunk's
        # keys into the per-column sort for lanes in ``sort_lanes``
        # (whole-ring sort; other lanes keep their sorted state +
        # watermark). The engine only sets sort_lanes on a prompt's
        # final chunk — nothing reads a PREFILLING slot's sort — so the
        # O(w log w) sort runs once per admitted prompt, as in
        # whole-prompt prefill; lax.cond skips it entirely on ticks
        # where no lane finishes.
        from repro.core.candidate_selection import sort_key_columns

        def _fold(_):
            sk = jax.vmap(jax.vmap(sort_key_columns))(kc)
            l4 = sort_lanes[:, None, None, None]
            return (jnp.where(l4, sk.values, state["sk_vals"]),
                    jnp.where(l4, sk.rows, state["sk_rows"]),
                    jnp.where(sort_lanes, pos + length,
                              state["sorted_upto"]))

        def _keep(_):
            return (state["sk_vals"], state["sk_rows"],
                    state["sorted_upto"])

        sk_vals, sk_rows, upto = jax.lax.cond(jnp.any(sort_lanes),
                                              _fold, _keep, None)
        new_state["sk_vals"] = sk_vals
        new_state["sk_rows"] = sk_rows
        new_state["sorted_upto"] = upto
    return attention_out(lp["attn"], o), new_state


@jax.named_scope("attn_decode")
def _attn_decode_step(lp: Params, state: Dict[str, jax.Array],
                      hn: jax.Array, *, cfg: ModelConfig, seg: SegmentSpec,
                      pos: jax.Array, a3: A3Config, use_kernel: bool,
                      probe: bool = False, **_
                      ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    b = hn.shape[0]
    hd = cfg.resolved_head_dim
    positions = pos[:, None]                                   # [B, 1]
    q, k, v = attention_qkv(lp["attn"], hn, positions, cfg.num_heads,
                            cfg.num_kv_heads, hd, cfg.rope_theta)
    q = shard_act(q, "q")
    w = state["k"].shape[2]
    # per-slot ring write: each sequence writes its own token at its own
    # ring slot (ragged continuous batching — one dispatch serves slots
    # at arbitrary position skew). Lanes with pos < 0 (idle/prefilling
    # engine slots riding along in the batch) scatter out of bounds and
    # are dropped, so mid-prefill cache rows are never clobbered.
    slot = jnp.where(pos >= 0, jnp.mod(pos, w), w)             # [B]
    bidx = jnp.arange(b, dtype=jnp.int32)
    kc = state["k"].at[bidx, :, slot].set(k[:, :, 0], mode="drop")
    vc = state["v"].at[bidx, :, slot].set(v[:, :, 0], mode="drop")
    kc = shard_act(kc, "kv_cache")
    vc = shard_act(vc, "kv_cache")
    valid = _ring_valid_mask(w, pos, seg.window)               # [B, w]
    # A^3 approximate decode only on global-attention layers: windowed
    # layers already bound the search (DESIGN.md SS5).
    use_a3 = a3.mode != A3Mode.OFF and seg.window >= FULL_WINDOW
    # NOTE: read-only leaves (sk_*, sorted_upto) are NOT returned — the
    # caller keeps them out of the scan ys (passing them through forced
    # a full copy of the sorted-key cache per layer iteration). The
    # returned k/v ride the caller's scan carry and are written back into
    # the donated ring in place (``decoder.decode_step``).
    new_state = {"k": kc, "v": vc}
    if use_a3 and "sk_vals" in state:
        # comprehension-time sorted keys cached at prefill (paper SSIV-C);
        # rows written since the last re-sort get exact treatment.
        from repro.core.candidate_selection import SortedKeys
        from repro.kernels.decode_attention.ops import \
            a3_decode_attention_compact
        slot_pos = _ring_slot_positions(w, pos)                 # [B, w]
        fresh = slot_pos >= state["sorted_upto"][:, None]       # [B, w]
        sk = SortedKeys(values=shard_act(state["sk_vals"], "kv_cache"),
                        rows=shard_act(state["sk_rows"], "kv_cache"))
        if probe:
            # A^3 quality probe (telemetry): captured-score-mass and
            # candidate-count leaves are not cache state, so they ride
            # the scan ys (not the carry) and land with the ring
            # harvest — zero extra host syncs. The attention output ops
            # are identical with or without the probe.
            o, pr = a3_decode_attention_compact(
                q[:, :, 0], kc, vc, valid, a3, sk, fresh_mask=fresh,
                return_probe=True)
            new_state["_probe"] = pr
        else:
            o = a3_decode_attention_compact(
                q[:, :, 0], kc, vc, valid, a3, sk, fresh_mask=fresh)
    elif use_a3:
        from repro.core.candidate_selection import sort_key_columns
        # no cached sort available: build inline (single-shot use)
        sorted_keys = jax.vmap(jax.vmap(sort_key_columns))(kc)
        o = a3_decode_attention(q[:, :, 0], kc, vc, valid, a3,
                                sorted_keys=sorted_keys,
                                use_kernel=use_kernel)
    else:
        o = a3_decode_attention(q[:, :, 0], kc, vc, valid, A3Config(),
                                use_kernel=use_kernel)
    return attention_out(lp["attn"], o[:, :, None, :]), new_state


def _attn_init_pages(cfg: ModelConfig, seg: SegmentSpec, pages: int,
                     page_size: int, dtype, a3: bool,
                     kv_quant: str = "none") -> Dict[str, jax.Array]:
    """Attention's share of the paged prefix-cache pool: per-page K/V
    rows. A *logical* page spans ``page_size`` token positions across
    every segment at once; sorted-key state is not paged (it is a
    whole-ring property, restored at gather time).

    ``kv_quant="int8"`` stores the pages as int8 with one fp32 amax
    scale per (layer, page, kv head) — ~4x more pages resident at equal
    HBM, and the warm gather moves 1 byte/element instead of 4.
    ``write_page`` quantizes on record and ``gather_pages`` dequantizes
    inside the same one-dispatch copy (the presence of the scale leaves
    is what routes them)."""
    L, hd = seg.count, cfg.resolved_head_dim
    if kv_quant == "int8":
        shp = (L, pages, cfg.num_kv_heads, page_size, hd)
        return {
            "k": jnp.zeros(shp, jnp.int8),
            "v": jnp.zeros(shp, jnp.int8),
            "k_scale": jnp.zeros((L, pages, cfg.num_kv_heads, 1, 1),
                                 jnp.float32),
            "v_scale": jnp.zeros((L, pages, cfg.num_kv_heads, 1, 1),
                                 jnp.float32),
        }
    return {
        "k": jnp.zeros((L, pages, cfg.num_kv_heads, page_size, hd), dtype),
        "v": jnp.zeros((L, pages, cfg.num_kv_heads, page_size, hd), dtype),
    }


def _attn_write_page(pool_seg: Dict[str, jax.Array],
                     state: Dict[str, jax.Array], si: jax.Array,
                     page_id: jax.Array, rows: jax.Array,
                     valid: jax.Array) -> Dict[str, jax.Array]:
    """Copy one page of slot ``si``'s ring into the pool at ``page_id``.

    ``rows`` [ps] maps page offsets to ring rows (``pos % w``); offsets
    whose position fell out of the ring (``valid`` False — a page wider
    than a sliding window) store zeros, matching what an unwritten ring
    row reads as at restore time.

    On an int8 pool (``k_scale`` present) the copy quantizes in the same
    dispatch: one fp32 amax scale per (layer, head) for this page."""
    v4 = valid[None, None, :, None]

    def put(pages, leaf):
        src = leaf[:, si][:, :, rows]                  # [L, H, ps, hd]
        src = jnp.where(v4, src, jnp.zeros((), leaf.dtype))
        return pages.at[:, page_id].set(src)

    if "k_scale" not in pool_seg:
        return {"k": put(pool_seg["k"], state["k"]),
                "v": put(pool_seg["v"], state["v"])}

    from repro.core.quantization import quantize_int8_block

    def put_q(pages, scales, leaf):
        src = leaf[:, si][:, :, rows]                  # [L, H, ps, hd]
        src = jnp.where(v4, src, jnp.zeros((), leaf.dtype))
        q, scale = quantize_int8_block(src, axes=(2, 3))   # [L, H, 1, 1]
        return (pages.at[:, page_id].set(q),
                scales.at[:, page_id].set(scale))

    k, ks = put_q(pool_seg["k"], pool_seg["k_scale"], state["k"])
    v, vs = put_q(pool_seg["v"], pool_seg["v_scale"], state["v"])
    return {"k": k, "v": v, "k_scale": ks, "v_scale": vs}


def _attn_gather_pages(state: Dict[str, jax.Array],
                       pool_seg: Dict[str, jax.Array], si: jax.Array,
                       t: jax.Array, page_idx: jax.Array,
                       row_off: jax.Array, valid: jax.Array, *,
                       a3: bool, sk_snap=None) -> Dict[str, jax.Array]:
    """Restore slot ``si``'s ring for a matched prefix of length ``t``
    from pool pages — the warm-admission copy.

    ``page_idx`` / ``row_off`` [w] give each ring row's source
    (pool page, in-page offset); rows with ``valid`` False (unwritten at
    position ``t``) are zeroed, so the slot's ring is bit-identical to a
    cold chunked prefill of the same prefix. With ``a3`` the sorted key
    columns are restored too: sliced out of a donor prompt's leaf
    snapshot via :func:`~repro.core.candidate_selection.slice_sorted_keys`
    when one exists (``sk_snap``), else re-derived by a comprehension
    sort of the gathered ring — either way ``sorted_upto`` comes back as
    ``t``, so admission triggers no A^3 re-sort.

    An int8 pool (``k_scale`` present) dequantizes inside this same
    dispatch — per-page fp32 scales broadcast over the gathered rows, so
    the slot ring comes back in its serving dtype and the wire/HBM
    traffic of the gather stays 1 byte/element. Int8 sorted-key
    snapshots (``sk_snap["scale"]``) dequantize per sorted column before
    the boundary slice."""
    v4 = valid[None, None, :, None]
    quant = "k_scale" in pool_seg
    out_dtype = state["k"].dtype

    def take(pages, scales=None):
        g = pages[:, page_idx, :, row_off]             # [w, L, H, hd]
        g = jnp.moveaxis(g, 0, 2)                      # [L, H, w, hd]
        if scales is not None:
            sc = scales[:, page_idx, :, 0, 0]          # [w, L, H]
            sc = jnp.moveaxis(sc, 0, 2)[..., None]     # [L, H, w, 1]
            g = (g.astype(jnp.float32) * sc).astype(out_dtype)
        return jnp.where(v4, g, jnp.zeros((), g.dtype))

    k_slot = take(pool_seg["k"], pool_seg.get("k_scale"))
    new = {"k": state["k"].at[:, si].set(k_slot),
           "v": state["v"].at[:, si].set(
               take(pool_seg["v"], pool_seg.get("v_scale")))}
    if a3 and "sk_vals" in state:
        from repro.core.candidate_selection import SortedKeys, \
            slice_sorted_keys, sort_key_columns
        from repro.core.quantization import dequantize_int8_block
        if sk_snap is not None:
            sk_vals = sk_snap["vals"]
            if "scale" in sk_snap:
                sk_vals = dequantize_int8_block(sk_vals, sk_snap["scale"],
                                                dtype=out_dtype)
            sliced = jax.vmap(jax.vmap(
                lambda v_, r_: slice_sorted_keys(SortedKeys(v_, r_),
                                                 valid)))(
                sk_vals, sk_snap["rows"])
        else:
            sliced = jax.vmap(jax.vmap(sort_key_columns))(k_slot)
        new["sk_vals"] = state["sk_vals"].at[:, si].set(sliced.values)
        new["sk_rows"] = state["sk_rows"].at[:, si].set(sliced.rows)
        new["sorted_upto"] = state["sorted_upto"].at[:, si].set(
            jnp.asarray(t, jnp.int32))
    return {**state, **new}


def _attn_snapshot(state: Dict[str, jax.Array], si: jax.Array
                   ) -> Dict[str, jax.Array]:
    """Attention's per-token decode state lives entirely in pages; the
    boundary snapshot is empty (sorted-key leaf snapshots are captured
    separately by the prefix cache, once per recorded prompt)."""
    return {}


def _attn_restore(state: Dict[str, jax.Array], snap: Dict[str, jax.Array],
                  si: jax.Array) -> Dict[str, jax.Array]:
    return state                                    # pages carry it all


# ---------------------------------------------------------------------------
# RG-LRU mixer
# ---------------------------------------------------------------------------

def _rglru_init_state(cfg: ModelConfig, seg: SegmentSpec, batch: int,
                      max_len: int, dtype, a3: bool) -> Dict[str, jax.Array]:
    L = seg.count
    d_rnn = cfg.num_heads * cfg.resolved_head_dim
    return {
        "h": jnp.zeros((L, batch, d_rnn), jnp.float32),
        "conv": jnp.zeros((L, batch, CONV_WIDTH - 1, d_rnn), dtype),
    }


def _rglru_forward(lp: Params, hn: jax.Array, **_) -> jax.Array:
    return rglru_apply_scan(lp["rnn"], hn)[0]


def _rglru_prefill_full(lp: Params, hn: jax.Array, **_):
    o, h_last, conv = rglru_apply_scan(lp["rnn"], hn)
    return o, {"h": h_last, "conv": conv}


def _rglru_prefill_chunk(lp: Params, state: Dict[str, jax.Array],
                         hn: jax.Array, *, pos: jax.Array,
                         length: jax.Array, valid_tok: jax.Array, **_):
    fresh = (pos == 0) & (length > 0)
    h0 = jnp.where(fresh[:, None], 0.0, state["h"])
    conv = _lane_select(jnp.zeros_like(state["conv"]), state["conv"], fresh)
    o, h_last, new_conv = rglru_chunk_step(lp["rnn"], hn, h0, conv,
                                           valid_tok)
    act = length > 0
    return o, {"h": _lane_select(h_last, state["h"], act),
               "conv": _lane_select(new_conv, state["conv"], act)}


def _rglru_decode_step(lp: Params, state: Dict[str, jax.Array],
                       hn: jax.Array, *, pos: jax.Array, **_):
    o, h_new, conv_new = rglru_decode_step(lp["rnn"], hn, state["h"],
                                           state["conv"])
    act = pos >= 0
    return o, {"h": _lane_select(h_new, state["h"], act),
               "conv": _lane_select(conv_new, state["conv"], act)}


# ---------------------------------------------------------------------------
# mLSTM mixer
# ---------------------------------------------------------------------------

def _mlstm_init_state(cfg: ModelConfig, seg: SegmentSpec, batch: int,
                      max_len: int, dtype, a3: bool) -> Dict[str, jax.Array]:
    L, hd = seg.count, cfg.resolved_head_dim
    return {
        "C": jnp.zeros((L, batch, cfg.num_heads, hd, hd), jnp.float32),
        "n": jnp.zeros((L, batch, cfg.num_heads, hd), jnp.float32),
        "m": jnp.full((L, batch, cfg.num_heads), -1e30, jnp.float32),
    }


def _mlstm_state_tuple(state: Dict[str, jax.Array]):
    return (state["C"], state["n"], state["m"])


def _mlstm_forward(lp: Params, hn: jax.Array, *, cfg: ModelConfig, **_):
    return xl.mlstm_parallel(lp["mlstm"], hn, cfg.num_heads,
                             cfg.resolved_head_dim)


def _mlstm_prefill_full(lp: Params, hn: jax.Array, *, cfg: ModelConfig, **_):
    o, (C, n, m) = xl.mlstm_chunkwise(lp["mlstm"], hn, cfg.num_heads,
                                      cfg.resolved_head_dim)
    return o, {"C": C, "n": n, "m": m}


def _mlstm_prefill_chunk(lp: Params, state: Dict[str, jax.Array],
                         hn: jax.Array, *, cfg: ModelConfig,
                         pos: jax.Array, length: jax.Array,
                         valid_tok: jax.Array, **_):
    fresh = (pos == 0) & (length > 0)
    st = (
        _lane_select(jnp.zeros_like(state["C"]), state["C"], fresh),
        _lane_select(jnp.zeros_like(state["n"]), state["n"], fresh),
        _lane_select(jnp.full_like(state["m"], -1e30), state["m"], fresh),
    )
    o, (C, n, m) = xl.mlstm_chunkwise(lp["mlstm"], hn, cfg.num_heads,
                                      cfg.resolved_head_dim, state=st,
                                      valid=valid_tok)
    act = length > 0
    return o, {"C": _lane_select(C, state["C"], act),
               "n": _lane_select(n, state["n"], act),
               "m": _lane_select(m, state["m"], act)}


def _mlstm_decode_step(lp: Params, state: Dict[str, jax.Array],
                       hn: jax.Array, *, cfg: ModelConfig,
                       pos: jax.Array, **_):
    o, (C, n, m) = xl.mlstm_decode_step(lp["mlstm"], hn,
                                        _mlstm_state_tuple(state),
                                        cfg.num_heads,
                                        cfg.resolved_head_dim)
    act = pos >= 0
    return o, {"C": _lane_select(C, state["C"], act),
               "n": _lane_select(n, state["n"], act),
               "m": _lane_select(m, state["m"], act)}


# ---------------------------------------------------------------------------
# sLSTM mixer
# ---------------------------------------------------------------------------

def _slstm_init_state(cfg: ModelConfig, seg: SegmentSpec, batch: int,
                      max_len: int, dtype, a3: bool) -> Dict[str, jax.Array]:
    L, d = seg.count, cfg.d_model
    # distinct buffers per leaf: the engine's donated dispatches would
    # otherwise donate one aliased buffer several times
    zeros = lambda: jnp.zeros((L, batch, d), jnp.float32)  # noqa: E731
    return {"c": zeros(), "n": zeros(),
            "m": jnp.full((L, batch, d), -1e30, jnp.float32), "h": zeros()}


def _slstm_state_tuple(state: Dict[str, jax.Array]):
    return (state["c"], state["n"], state["m"], state["h"])


def _slstm_forward(lp: Params, hn: jax.Array, *, cfg: ModelConfig, **_):
    return xl.slstm_apply_scan(lp["slstm"], hn, cfg.num_heads)[0]


def _slstm_prefill_full(lp: Params, hn: jax.Array, *, cfg: ModelConfig, **_):
    o, (c, n, m, h) = xl.slstm_apply_scan(lp["slstm"], hn, cfg.num_heads)
    return o, {"c": c, "n": n, "m": m, "h": h}


def _slstm_prefill_chunk(lp: Params, state: Dict[str, jax.Array],
                         hn: jax.Array, *, cfg: ModelConfig,
                         pos: jax.Array, length: jax.Array,
                         valid_tok: jax.Array, **_):
    fresh = (pos == 0) & (length > 0)
    st = (
        _lane_select(jnp.zeros_like(state["c"]), state["c"], fresh),
        _lane_select(jnp.zeros_like(state["n"]), state["n"], fresh),
        _lane_select(jnp.full_like(state["m"], -1e30), state["m"], fresh),
        _lane_select(jnp.zeros_like(state["h"]), state["h"], fresh),
    )
    # pad positions reselect the carried state inside the scan, so a
    # zero-length lane is bit-identical by construction
    o, (c, n, m, h) = xl.slstm_apply_scan(lp["slstm"], hn, cfg.num_heads,
                                          state=st, valid=valid_tok)
    act = length > 0
    return o, {"c": _lane_select(c, state["c"], act),
               "n": _lane_select(n, state["n"], act),
               "m": _lane_select(m, state["m"], act),
               "h": _lane_select(h, state["h"], act)}


def _slstm_decode_step(lp: Params, state: Dict[str, jax.Array],
                       hn: jax.Array, *, cfg: ModelConfig,
                       pos: jax.Array, **_):
    o, (c, n, m, h) = xl.slstm_decode_step(lp["slstm"], hn,
                                           _slstm_state_tuple(state),
                                           cfg.num_heads)
    act = pos >= 0
    return o, {"c": _lane_select(c, state["c"], act),
               "n": _lane_select(n, state["n"], act),
               "m": _lane_select(m, state["m"], act),
               "h": _lane_select(h, state["h"], act)}


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------

def _no_pages(cfg: ModelConfig, seg: SegmentSpec, pages: int,
              page_size: int, dtype, a3: bool,
              kv_quant: str = "none") -> None:
    """Recurrent kinds keep no per-token pages: their decode state is a
    fixed-size carry, snapshotted per page boundary instead."""
    return None


def _carry_snapshot(state: Dict[str, jax.Array], si: jax.Array
                    ) -> Dict[str, jax.Array]:
    """Per-lane boundary snapshot: the chunked-prefill carry itself.
    Every recurrent state leaf is [L, B, ...]; slice lane ``si``."""
    return {k: jax.lax.dynamic_slice_in_dim(v, si, 1, axis=1)
            for k, v in state.items()}


def _carry_restore(state: Dict[str, jax.Array],
                   snap: Dict[str, jax.Array], si: jax.Array
                   ) -> Dict[str, jax.Array]:
    """Replay a boundary snapshot into lane ``si`` (warm admission)."""
    return {k: v.at[:, si].set(snap[k][:, 0]) for k, v in state.items()}


def _snapshot_dump(snap: Dict[str, jax.Array]) -> Dict[str, np.ndarray]:
    """Serialize a boundary snapshot to host numpy for the durable page
    store / engine checkpoint (dtype- and bit-exact: float leaves round-
    trip unchanged, so a promoted or restored carry replays the same
    tokens). Per-kind mixers with non-array snapshot state override
    this pair."""
    return {k: np.asarray(v) for k, v in snap.items()}


def _snapshot_load(host: Dict[str, np.ndarray]) -> Dict[str, jax.Array]:
    """Rehydrate a dumped snapshot to device arrays (L2 promotion /
    checkpoint restore)."""
    return {k: jnp.asarray(v) for k, v in host.items()}


@dataclasses.dataclass(frozen=True)
class SegmentMixer:
    """The per-kind mixer-state interface (see module docstring)."""
    init_state: Callable[..., Dict[str, jax.Array]]
    forward: Callable[..., jax.Array]
    prefill_full: Callable[..., Tuple[jax.Array, Dict[str, jax.Array]]]
    prefill_chunk: Callable[..., Tuple[jax.Array, Dict[str, jax.Array]]]
    decode_step: Callable[..., Tuple[jax.Array, Dict[str, jax.Array]]]
    # paged prefix-cache hooks (repro.serve.prefix_cache)
    init_pages: Callable[..., Optional[Dict[str, jax.Array]]] = _no_pages
    write_page: Optional[Callable[..., Dict[str, jax.Array]]] = None
    gather_pages: Optional[Callable[..., Dict[str, jax.Array]]] = None
    snapshot_state: Callable[..., Dict[str, jax.Array]] = _carry_snapshot
    restore_state: Callable[..., Dict[str, jax.Array]] = _carry_restore
    # durable-state hooks (repro.serve.page_store): snapshot <-> host
    # bytes for the L2 tier and the engine checkpoint
    dump_snapshot: Callable[..., Dict[str, np.ndarray]] = _snapshot_dump
    load_snapshot: Callable[..., Dict[str, jax.Array]] = _snapshot_load


MIXERS: Dict[BlockKind, SegmentMixer] = {
    BlockKind.ATTENTION: SegmentMixer(
        _attn_init_state, _attn_forward, _attn_prefill_full,
        _attn_prefill_chunk, _attn_decode_step,
        init_pages=_attn_init_pages, write_page=_attn_write_page,
        gather_pages=_attn_gather_pages, snapshot_state=_attn_snapshot,
        restore_state=_attn_restore),
    BlockKind.RGLRU: SegmentMixer(
        _rglru_init_state, _rglru_forward, _rglru_prefill_full,
        _rglru_prefill_chunk, _rglru_decode_step),
    BlockKind.MLSTM: SegmentMixer(
        _mlstm_init_state, _mlstm_forward, _mlstm_prefill_full,
        _mlstm_prefill_chunk, _mlstm_decode_step),
    BlockKind.SLSTM: SegmentMixer(
        _slstm_init_state, _slstm_forward, _slstm_prefill_full,
        _slstm_prefill_chunk, _slstm_decode_step),
}
