"""Pallas TPU kernel: chunked paged *prefill* attention over a block-table
KV pool.

The prefill sibling of `kernels/paged_attention.py`: a chunk of Sq query
tokens per sequence (prompt positions start .. start+Sq-1) attends to K/V
that live in the shared page pool — earlier chunks' KV is read back
through the scalar-prefetched block table, exactly like decode, and the
chunk's own KV has already been written into its pages by the caller
(`serving/kvcache.append_chunk_kv_pages`). This is SAL-PIM's parallel
summarization stage run on the same bank-sequential placement the
generation stage uses: no dense per-slot prefill arena, no scatter pass.

  * per-sequence lengths + chunk starts + the sliding-window scalar +
    block table are `num_scalar_prefetch` inputs, so the BlockSpec index
    map computes each physical page's DMA address before the body runs;
  * the body is the decode kernel's online-softmax (m, l, acc) merge
    across pages — the C-ALU merge of per-bank partials — widened to
    Sq*g query rows, with a causal mask at absolute positions
    (key <= start + row//g) on top of the length mask;
  * exp optionally routes through the same 64-section LUT;
  * int8 pools (`k_scales`/`v_scales` given) dequantize in VMEM right
    after the page DMA, folding each (page, head) scale row into the
    score and probability tiles — the page body is
    `kernels/paged_attention.attend_page`, shared with decode; the
    chunk's own K/V was already amax-quantized at write time by the
    caller; int4 pools (payload axis Dh/2, detected structurally)
    additionally nibble-unpack in VMEM first.

Grid: (B, Hkv, n_pages); q block (Sq*g, D) where g = H // Hkv (GQA
groups share one K/V page stream; row r is query r//g, group r%g).

Under mesh-sharded serving (`models/attention.py`'s shard_map wrapper)
the kernel runs unchanged on per-shard slices — local Hkv, local pool
shard — exactly as described in `kernels/paged_attention.py`: the grid
and index maps never cross the Hkv axis, so sharding it only shrinks
the grid, and the (kv_head, group) q-head ordering keeps each shard's
q block aligned with its KV heads.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.lut import LutTable
from repro.kernels.decode_attention import NEG_INF, window_operand
from repro.kernels.lut_interp import SMEM_SPEC, lut_operand
from repro.kernels.paged_attention import (attend_page, check_scale_rows,
                                           split_refs)


def _paged_prefill_kernel(
    len_ref,    # scalar prefetch: (B,) int32 valid KV lengths (incl. chunk)
    start_ref,  # scalar prefetch: (B,) int32 absolute first query position
    win_ref,    # scalar prefetch: (1,) int32 sliding window
    tbl_ref,    # scalar prefetch: (B, n_pages) int32 physical page ids
    *refs,      # q, k, v, [ksc, vsc,] expwb, o, then m/l/acc scratch
    n_pages, page_size, g, quantized, **page_kw,
):
    (q_ref, k_ref, v_ref, ksc_ref, vsc_ref, expwb_ref, (o_ref,),
     (m_ref, l_ref, acc_ref)) = split_refs(refs, quantized, 1)
    b = pl.program_id(0)
    s_idx = pl.program_id(2)

    @pl.when(s_idx == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    length = len_ref[b]
    start = start_ref[b]

    def mask_fn(shape):
        k_pos = (s_idx * page_size
                 + jax.lax.broadcasted_iota(jnp.int32, shape, 1))
        row = jax.lax.broadcasted_iota(jnp.int32, shape, 0)
        q_pos = start + row // g                 # absolute query positions
        causal = jnp.logical_and(k_pos < length, k_pos <= q_pos)
        return jnp.logical_and(causal, k_pos > q_pos - win_ref[0])

    attend_page(q_ref[0, 0].astype(jnp.float32), k_ref, v_ref, ksc_ref,
                vsc_ref, expwb_ref, m_ref, l_ref, acc_ref, mask_fn=mask_fn,
                **page_kw)

    @pl.when(s_idx == n_pages - 1)
    def _writeback():
        l = jnp.maximum(l_ref[...], 1e-9)
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)


def paged_prefill_attention(
    q: jax.Array,             # (B, Sq, H, D) one prompt chunk per sequence
    k_pages: jax.Array,       # (P, Hkv, page_size, D) shared pool
    v_pages: jax.Array,       # (P, Hkv, page_size, D)
    block_tables: jax.Array,  # (B, n_pages) int32 physical page ids
    length: jax.Array,        # (B,) int32 valid KV lengths (start + Sq)
    start: jax.Array,         # (B,) int32 absolute position of query 0
    k_scales: jax.Array | None = None,  # (P, Hkv, 1, page_size) int8/int4
    v_scales: jax.Array | None = None,
    *,
    scale: float | None = None,
    exp_table: LutTable | None = None,
    softcap: float | None = None,
    window=None,              # int, traced int32 scalar, or None (global)
    interpret: bool = False,
) -> jax.Array:
    B, Sq, H, D = q.shape
    Hkv, page_size = k_pages.shape[1], k_pages.shape[2]
    n_pages = block_tables.shape[1]
    g = H // Hkv
    scale = scale if scale is not None else 1.0 / (D**0.5)
    wb, lut = lut_operand(exp_table)

    # (B, Sq, H, D) -> (B, Hkv, Sq*g, D): row r is query r//g, group r%g.
    qg = (q.reshape(B, Sq, Hkv, g, D)
          .transpose(0, 2, 1, 3, 4)
          .reshape(B, Hkv, Sq * g, D))
    quantized = check_scale_rows(k_pages, k_scales, v_scales)
    packed = 2 * k_pages.shape[-1] == D    # nibble-packed int4 payload
    Dp = k_pages.shape[-1]                 # payload axis (D, or D/2 packed)
    if packed and not quantized:
        raise ValueError("packed int4 pools require scale rows")

    kernel = functools.partial(
        _paged_prefill_kernel, n_pages=n_pages, page_size=page_size, g=g,
        quantized=quantized, scale=scale, softcap=softcap, packed=packed,
        use_lut=exp_table is not None, lut=lut)
    # Physical page addresses come from the prefetched block table.
    page_spec = pl.BlockSpec((1, 1, page_size, Dp),
                             lambda b, h, s, lens_ref, start_ref, win_ref,
                             tbl_ref: (tbl_ref[b, s], h, 0, 0))
    scale_spec = pl.BlockSpec((1, 1, 1, page_size),
                              lambda b, h, s, lens_ref, start_ref, win_ref,
                              tbl_ref: (tbl_ref[b, s], h, 0, 0))
    in_specs = [
        pl.BlockSpec((1, 1, Sq * g, D), lambda b, h, s, *_: (b, h, 0, 0)),
        page_spec,
        page_spec,
    ]
    inputs = [qg, k_pages, v_pages]
    if quantized:
        # Scale rows stream in their storage dtype (f32 or bf16) — the
        # bf16 mode's bandwidth saving depends on NOT widening them
        # host-side; the kernel widens after the DMA.
        in_specs += [scale_spec, scale_spec]
        inputs += [k_scales, v_scales]
    in_specs.append(SMEM_SPEC)
    inputs.append(wb)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(B, Hkv, n_pages),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, Sq * g, D),
                               lambda b, h, s, *_: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((Sq * g, 1), jnp.float32),
            pltpu.VMEM((Sq * g, 1), jnp.float32),
            pltpu.VMEM((Sq * g, D), jnp.float32),
        ],
    )

    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, Sq * g, D), q.dtype),
        interpret=interpret,
    )(length.astype(jnp.int32), start.astype(jnp.int32),
      window_operand(window), block_tables.astype(jnp.int32), *inputs)
    return (out.reshape(B, Hkv, Sq, g, D)
            .transpose(0, 2, 1, 3, 4)
            .reshape(B, Sq, H, D))
