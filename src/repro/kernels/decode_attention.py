"""Pallas TPU kernel: fused decode attention (SAL-PIM C3 adaptation).

One token attends to an S-entry KV cache. SAL-PIM's mapping for MHA:

  * Q x K^T and S x V use *two accumulation directions* over the same
    (H, S, D) K/V layout — no transpose is ever materialized. Here both
    contractions happen inside one kernel over the same streamed K/V tile.
  * The S-ALU `max` op feeding the exp LUT becomes the online-softmax
    running max; exp optionally goes through the same 64-section LUT
    table as the paper.
  * Bank-sequential K/V concatenation becomes the cache append — dense
    per-slot arenas here, or page-granular through the block-table pool
    (serving/kvcache.py + kernels/paged_attention.py); this kernel just
    reads a dense cache up to `length`.
  * The C-ALU merge of per-bank partials becomes the (m, l, acc) merge
    across seq blocks — and, for sequence-parallel long-context decode,
    the same algebra merges per-chip partials
    (distributed/collectives.py `merge_partial_softmax`).

Grid: (B * Hkv, S_blocks); q block (group, D) where group = H // Hkv (GQA
groups share one K/V stream — one HBM read serves `group` query heads).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.lut import LutTable
from repro.kernels.lut_interp import SMEM_SPEC, lut_eval, lut_operand

NEG_INF = -1e30
# Window operand value meaning "attend globally": every kernel takes the
# sliding window as a scalar-prefetched int32 (a per-layer value traced
# through the model's layer scan), and `pos >= length - GLOBAL_WINDOW`
# holds for every real position.
GLOBAL_WINDOW = 1 << 30


def window_operand(window) -> jax.Array:
    """(1,) int32 scalar-prefetch operand for a static, traced or absent
    (None = global) sliding window."""
    if window is None:
        window = GLOBAL_WINDOW
    return jnp.asarray(window, jnp.int32).reshape(1)


def online_softmax_update(scores, mask, v, m_ref, l_ref, acc_ref, *,
                          use_lut, expwb_ref, lut, v_scale=None):
    """One page/block of the online softmax shared by the attention
    kernels: S-ALU max, exp (64-section LUT or exact), running (m, l,
    acc) rescale — the C-ALU merge of per-bank partials. `scores` are
    already NEG_INF where `mask` is False; `v` is the f32 (block, D)
    value tile, and `v_scale` (1, block) its per-position dequant scales
    (int8/int4 pools), folded into the probabilities before the second
    contraction so the page itself is never rescaled."""
    m_prev = m_ref[...]
    m_cur = jnp.max(scores, axis=-1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    if use_lut:
        # exp of (x - m) <= 0: the LUT's calibrated negative domain.
        p = lut_eval(scores - m_new, expwb_ref, **lut)
        corr = lut_eval(jnp.maximum(m_prev - m_new, lut["lo"]), expwb_ref,
                        **lut)
    else:
        p = jnp.exp(scores - m_new)
        corr = jnp.exp(m_prev - m_new)
    p = jnp.where(mask, p, 0.0)
    l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
    # Direction 2: contract seq (S x V) over the same V tile.
    pv = p if v_scale is None else p * v_scale
    acc_ref[...] = acc_ref[...] * corr + jnp.dot(
        pv, v, preferred_element_type=jnp.float32)
    m_ref[...] = m_new


def _decode_attn_kernel(
    len_ref,  # scalar prefetch: (B*Hkv,) int32 valid lengths
    win_ref,  # scalar prefetch: (1,) int32 sliding window
    q_ref, k_ref, v_ref, expwb_ref, o_ref,
    m_ref, l_ref, acc_ref,
    *, n_s, block_s, scale, use_lut, lut, softcap,
):
    s_idx = pl.program_id(1)

    @pl.when(s_idx == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    bh = pl.program_id(0)
    length = len_ref[bh]

    q = q_ref[0].astype(jnp.float32)             # (g, D)
    k = k_ref[0].astype(jnp.float32)             # (block_s, D)
    # Direction 1: contract head_dim (Q x K^T).
    scores = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
    if softcap is not None:
        scores = softcap * jnp.tanh(scores / softcap)

    pos = s_idx * block_s + jax.lax.broadcasted_iota(jnp.int32, scores.shape, 1)
    mask = jnp.logical_and(pos < length, pos >= length - win_ref[0])
    scores = jnp.where(mask, scores, NEG_INF)
    online_softmax_update(scores, mask, v_ref[0].astype(jnp.float32),
                          m_ref, l_ref, acc_ref, use_lut=use_lut,
                          expwb_ref=expwb_ref, lut=lut)

    @pl.when(s_idx == n_s - 1)
    def _writeback():
        l = jnp.maximum(l_ref[...], 1e-9)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


def decode_attention(
    q: jax.Array,            # (B, H, D)
    k: jax.Array,            # (B, Hkv, S, D)
    v: jax.Array,            # (B, Hkv, S, D)
    length: jax.Array,       # (B,) int32 valid cache lengths
    *,
    scale: float | None = None,
    exp_table: LutTable | None = None,
    softcap: float | None = None,
    window=None,             # int, traced int32 scalar, or None (global)
    block_s: int = 256,
    interpret: bool = False,
) -> jax.Array:
    B, H, D = q.shape
    Hkv, S = k.shape[1], k.shape[2]
    g = H // Hkv
    scale = scale if scale is not None else 1.0 / (D**0.5)
    block_s = min(block_s, S)
    if S % block_s:
        raise ValueError(f"cache length {S} does not tile by {block_s}")
    n_s = S // block_s
    wb, lut = lut_operand(exp_table)

    qg = q.reshape(B * Hkv, g, D)
    kf = k.reshape(B * Hkv, S, D)
    vf = v.reshape(B * Hkv, S, D)
    lens = jnp.repeat(length.astype(jnp.int32), Hkv)  # (B*Hkv,)

    kernel = functools.partial(
        _decode_attn_kernel, n_s=n_s, block_s=block_s, scale=scale,
        use_lut=exp_table is not None, lut=lut, softcap=softcap,
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B * Hkv, n_s),
        in_specs=[
            pl.BlockSpec((1, g, D), lambda bh, s, *_: (bh, 0, 0)),
            pl.BlockSpec((1, block_s, D), lambda bh, s, *_: (bh, s, 0)),
            pl.BlockSpec((1, block_s, D), lambda bh, s, *_: (bh, s, 0)),
            SMEM_SPEC,
        ],
        out_specs=pl.BlockSpec((1, g, D), lambda bh, s, *_: (bh, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, D), jnp.float32),
        ],
    )

    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B * Hkv, g, D), q.dtype),
        interpret=interpret,
    )(lens, window_operand(window), qg, kf, vf, wb)
    return out.reshape(B, H, D)
