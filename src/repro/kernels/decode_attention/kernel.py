"""Single-token decode attention Pallas TPU kernel with A³ masking.

Decode is the accelerator's home turf: one query vector against an n-row
KV memory — exactly the paper's Figure 1 unit op. On TPU the op is
HBM-bandwidth-bound (the KV cache streams through VMEM once), so the
MXU-friendly layout puts the GQA *query-head group* in the sublane
dimension: each grid step computes a [G, bk] score tile with one
[G, D]·[D, bk] matmul. q, mask and output are viewed as
``[B, Hkv, G, ·]`` so every block's last two dims are either the full
array dims (G, D) or lane-aligned (bk) — the TPU compiler's tiling rule;
blocking G rows out of a ``[B, Hq, ·]`` array is refused for G < 8.

A³ enters as a per-position candidate mask (row-granular — decode is
bandwidth- not MXU-bound, so row granularity costs nothing here) plus the
post-scoring threshold of §IV-D.

The default path is a **fused single-pass** kernel: a flash-style online
softmax streams K/V through VMEM exactly once, carrying running
max/sum/accumulator scratch with rescaling. Because decode is
bandwidth-bound, halving the K reads (the old two-pass structure read K
once for the row max and again for the weighted sum) directly cuts
per-token latency.

Post-scoring in the fused pass tests scores against the *running* max —
a documented superset relaxation of the paper's exact two-pass rule: the
running max only grows, so ``s >= running_max - t`` is implied by
``s >= final_max - t``; no entry the exact pass keeps is ever dropped.
Entries admitted early that the exact rule would drop each carry softmax
weight < exp(-t) relative to the max, so the output delta is bounded (and
tested) by ~n·exp(-t) in total variation of the attention weights.
``exact_two_pass=True`` keeps the literal ASIC pipeline (pass 1 =
dot-product + max modules, pass 2 = exponent + output modules) for
bit-faithful §IV-D semantics.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _rowmax_kernel(q_ref, k_ref, mask_ref, m_out, m_scr, *, scale):
    ik = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)

    q = q_ref[0, 0].astype(jnp.float32)                  # [G, D]
    k = k_ref[0, 0].astype(jnp.float32)                  # [bk, D]
    mask = mask_ref[0, 0]                                # [G, bk]
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    s = jnp.where(mask, s, NEG_INF)
    m_scr[...] = jnp.maximum(m_scr[...], jnp.max(s, -1, keepdims=True))

    @pl.when(ik == nk - 1)
    def _emit():
        m_out[0, 0] = m_scr[...]


def _attend_kernel(q_ref, k_ref, v_ref, mask_ref, rm_ref, o_ref,
                   l_scr, acc_scr, *, scale, threshold):
    ik = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ik == 0)
    def _init():
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0].astype(jnp.float32)                  # [G, D]
    k = k_ref[0, 0].astype(jnp.float32)                  # [bk, D]
    v = v_ref[0, 0].astype(jnp.float32)                  # [bk, Dv]
    mask = mask_ref[0, 0]                                # [G, bk]
    rm = rm_ref[0, 0]                                    # [G, 1]
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    if threshold is not None:
        mask &= s >= rm - threshold
    p = jnp.where(mask, jnp.exp(s - rm), 0.0)
    l_scr[...] += jnp.sum(p, -1, keepdims=True)
    acc_scr[...] += jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(ik == nk - 1)
    def _emit():
        l = l_scr[...]
        safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = jnp.where(l == 0.0, 0.0, acc_scr[...] / safe
                                ).astype(o_ref.dtype)


def _fused_kernel(q_ref, k_ref, v_ref, mask_ref, o_ref,
                  m_scr, l_scr, acc_scr, *, scale, threshold):
    """Single-pass online-softmax decode: one K/V stream, running
    max/sum/acc scratch with rescaling. Threshold (if any) is applied
    against the running max — see the module docstring for the bound."""
    ik = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0].astype(jnp.float32)                  # [G, D]
    k = k_ref[0, 0].astype(jnp.float32)                  # [bk, D]
    v = v_ref[0, 0].astype(jnp.float32)                  # [bk, Dv]
    mask = mask_ref[0, 0]                                # [G, bk]
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    s = jnp.where(mask, s, NEG_INF)
    m_prev = m_scr[...]                                  # [G, 1]
    m_cur = jnp.maximum(m_prev, jnp.max(s, -1, keepdims=True))
    keep = mask
    if threshold is not None:
        keep &= s >= m_cur - threshold
    p = jnp.where(keep, jnp.exp(s - m_cur), 0.0)
    alpha = jnp.exp(m_prev - m_cur)                      # rescale factor
    l_scr[...] = l_scr[...] * alpha + jnp.sum(p, -1, keepdims=True)
    acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_scr[...] = m_cur

    @pl.when(ik == nk - 1)
    def _emit():
        l = l_scr[...]
        safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = jnp.where(l == 0.0, 0.0, acc_scr[...] / safe
                                ).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("threshold", "scale", "block_k", "interpret",
                     "exact_two_pass"))
def decode_attention(
    q: jax.Array,                   # [B, Hq, D] one new token per sequence
    k: jax.Array,                   # [B, Hkv, S, D]
    v: jax.Array,                   # [B, Hkv, S, Dv]
    mask: jax.Array,                # [B, Hq, S] candidates & cache validity
    *,
    threshold: Optional[float] = None,
    scale: Optional[float] = None,
    block_k: int = 512,
    interpret: bool = False,
    exact_two_pass: bool = False,
) -> jax.Array:
    b, hq, d = q.shape
    _, hkv, s, dv = v.shape
    group = hq // hkv
    if scale is None:
        scale = d ** -0.5
    bk = min(block_k, s)
    assert s % bk == 0

    grid = (b, hkv, s // bk)
    qg = q.reshape(b, hkv, group, d)
    maskg = mask.reshape(b, hkv, group, s)

    q_spec = pl.BlockSpec((1, 1, group, d),
                          lambda b_, h, ik: (b_, h, 0, 0))
    kv_spec = pl.BlockSpec((1, 1, bk, d), lambda b_, h, ik: (b_, h, ik, 0))
    vv_spec = pl.BlockSpec((1, 1, bk, dv), lambda b_, h, ik: (b_, h, ik, 0))
    mask_spec = pl.BlockSpec((1, 1, group, bk),
                             lambda b_, h, ik: (b_, h, 0, ik))
    o_spec = pl.BlockSpec((1, 1, group, dv),
                          lambda b_, h, ik: (b_, h, 0, 0))
    o_shape = jax.ShapeDtypeStruct((b, hkv, group, dv), q.dtype)

    if not exact_two_pass:
        out = pl.pallas_call(
            functools.partial(_fused_kernel, scale=scale,
                              threshold=threshold),
            grid=grid,
            in_specs=[q_spec, kv_spec, vv_spec, mask_spec],
            out_specs=o_spec,
            out_shape=o_shape,
            scratch_shapes=[
                pltpu.VMEM((group, 1), jnp.float32),
                pltpu.VMEM((group, 1), jnp.float32),
                pltpu.VMEM((group, dv), jnp.float32),
            ],
            interpret=interpret,
        )(qg, k, v, maskg)
        return out.reshape(b, hq, dv)

    rm_spec = pl.BlockSpec((1, 1, group, 1),
                           lambda b_, h, ik: (b_, h, 0, 0))

    rowmax = pl.pallas_call(
        functools.partial(_rowmax_kernel, scale=scale),
        grid=grid,
        in_specs=[q_spec, kv_spec, mask_spec],
        out_specs=rm_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, group, 1), jnp.float32),
        scratch_shapes=[pltpu.VMEM((group, 1), jnp.float32)],
        interpret=interpret,
    )(qg, k, maskg)

    out = pl.pallas_call(
        functools.partial(_attend_kernel, scale=scale, threshold=threshold),
        grid=grid,
        in_specs=[q_spec, kv_spec, vv_spec, mask_spec, rm_spec],
        out_specs=o_spec,
        out_shape=o_shape,
        scratch_shapes=[
            pltpu.VMEM((group, 1), jnp.float32),
            pltpu.VMEM((group, dv), jnp.float32),
        ],
        interpret=interpret,
    )(qg, k, v, maskg, rowmax)
    return out.reshape(b, hq, dv)
