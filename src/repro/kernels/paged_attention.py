"""Pallas TPU kernel: paged decode attention over a block-table KV pool.

The paged sibling of `kernels/decode_attention.py`: one query token per
sequence attends to K/V that live in a *shared page pool* rather than a
dense per-sequence arena. Each sequence's pages are found through a
scalar-prefetched block table — SAL-PIM's bank-sequential K/V placement
(`serving/kvcache.py`) read back bank-by-bank:

  * the block table is the last `num_scalar_prefetch` input (after the
    lengths and the sliding-window scalar), so the BlockSpec index map
    can compute the physical page DMA address *before* the kernel body
    runs (the C3 hierarchy mapping: logical page -> bank);
  * inside the body the math is identical to the dense kernel — the
    online-softmax (m, l, acc) merge across pages is the C-ALU merge of
    per-bank partials, and exp optionally routes through the same
    64-section LUT (`decode_attention.online_softmax_update`).

int8 pools (`k_scales`/`v_scales` given): the page DMA moves int8
payload plus one f32 scale row per (page, head) — (Dh + 4) bytes per
vector instead of 2*Dh for bf16. Scale pools are laid out
(P, Hkv, 1, page_size), so each (page, head) scale row is its own
(1, page_size) block — a block shape Mosaic accepts, and exactly the
bytes `kv_vector_bytes` counts. The kernel dequantizes *in VMEM* by
folding the scale row into the contraction it feeds: K scales multiply
the (g, page) score tile column-wise, V scales the probability tile, so
neither page is rescaled elementwise and the ~2x HBM traffic cut is real.

int4 pools pack two values per byte, so the page payload block is
(page_size, Dh/2) and the DMA moves (Dh/2 + 2) bytes per vector (bf16
scale rows). Packing is detected structurally (payload axis is half the
query head_dim) and the kernel unpacks in VMEM with two arithmetic
shifts on the int32-widened bytes plus a halves concat
(`serving/quantize.unpack_int4`'s convention).

Grid: (B, Hkv, n_pages); q block (group, D) where group = H // Hkv (GQA
groups share one K/V page stream). Unmapped table entries point at the
trash page (physical page 0); their positions are masked by `length`.

KV-split (flash-decode) mode (`kv_splits` > 1): the page walk becomes a
4D grid (B, Hkv, kv_splits, pages_per_split). Each split runs the same
online-softmax over only its contiguous run of block-table pages and
writes *partials* — (m, l, un-normalized acc) — and a single combine
pass outside the kernel merges them with
`distributed.collectives.merge_partial_softmax_stacked` (the same
log-sum-exp algebra as the mesh-axis C-ALU merge). Splitting breaks
the sequential page-walk dependency chain so long contexts expose
parallelism across the grid; `effective_kv_splits` auto-disables it
below `KV_SPLIT_MIN_CONTEXT` resident tokens where the partials
traffic would dominate.

Under mesh-sharded serving (`models/attention.py`'s shard_map wrapper)
the kernel runs unchanged on *per-shard* slices: Hkv here is the local
KV-head count (n_kv_heads / tp) and the pools are the local pool shard.
That works because the grid and every index map are head-separable —
no kernel instance ever reads across the Hkv axis — so sharding that
axis just shrinks the grid. The q slice keeps group = H // Hkv because
GQA orders q heads as (kv_head, group): a contiguous H-block lines up
exactly with its KV-head block.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.lut import LutTable
from repro.kernels.decode_attention import (NEG_INF, online_softmax_update,
                                           window_operand)
from repro.kernels.lut_interp import SMEM_SPEC, lut_operand

# Below this many resident tokens the split path's partials traffic
# outweighs the parallelism win; effective_kv_splits disables it.
KV_SPLIT_MIN_CONTEXT = 1024


def effective_kv_splits(kv_splits: int | None, n_pages: int,
                        page_size: int) -> int | None:
    """Resolve the autotune knob to an actual split count, or None.

    Static (trace-time) decision: splitting engages only when asked
    (kv_splits > 1) and the table's worth of context is at least
    KV_SPLIT_MIN_CONTEXT tokens; the count is clamped to n_pages so
    every split owns at least one page.
    """
    if kv_splits is None or kv_splits <= 1:
        return None
    if n_pages * page_size < KV_SPLIT_MIN_CONTEXT:
        return None
    return min(kv_splits, n_pages)


def kv_vector_bytes(head_dim: int, kv_dtype: str = "model",
                    kv_scale_dtype: str = "float32",
                    payload_dtype="float32") -> int:
    """HBM bytes one (token, head) K-or-V vector costs this kernel's DMA.

    This is the byte contract of the page BlockSpecs above — what one
    row of a page payload block (plus its scale-row element, when the
    pool is quantized) actually moves per vector:

        fp pools:   Dh * itemsize(payload_dtype)
        int8 pools: Dh + itemsize(scale)      (4 f32 / 2 bf16 scales)
        int4 pools: Dh/2 + itemsize(scale)    (nibble-packed payload)

    `serving/kvcache.page_kv_bytes` (pool sizing / admission budgets)
    and `serving/costmodel` (the roofline model) both derive from this
    single definition, so modeled traffic can never drift from what the
    kernels DMA.
    """
    if kv_dtype == "int8":
        return head_dim + jnp.dtype(kv_scale_dtype).itemsize
    if kv_dtype == "int4":
        return head_dim // 2 + jnp.dtype(kv_scale_dtype).itemsize
    return head_dim * jnp.dtype(payload_dtype).itemsize


def _page_tile(x_ref, packed):
    """One page payload block -> f32 (page_size, D). int4 pools unpack
    in VMEM: the bytes widen to int32 first (Mosaic has no 8-bit
    shifts), then arithmetic shifts sign-extend the low and high nibbles
    and a halves concat restores head_dim order."""
    x = x_ref[0, 0]
    if packed:
        x = x.astype(jnp.int32)
        x = jnp.concatenate(
            [jnp.right_shift(jnp.left_shift(x, 28), 28),
             jnp.right_shift(x, 4)], axis=-1)
    return x.astype(jnp.float32)


def _scale_row(sc_ref):
    """The (1, page_size) f32 dequant scales of this (page, head), or
    None for fp pools; DMA'd in storage dtype, widened in VMEM."""
    return None if sc_ref is None else sc_ref[0, 0].astype(jnp.float32)


def attend_page(q, k_ref, v_ref, ksc_ref, vsc_ref, expwb_ref,
                m_ref, l_ref, acc_ref, *, mask_fn, scale, softcap,
                packed, use_lut, lut):
    """Fold one K/V page into the running (m, l, acc) of `q` (rows, D):
    the body shared by the paged decode (single-walk and split) and
    paged prefill kernels, which differ only in `mask_fn(shape)` — the
    validity mask of the (rows, page_size) score tile."""
    # Direction 1: contract head_dim (Q x K^T) — same layout, no transpose.
    scores = jnp.dot(q, _page_tile(k_ref, packed).T,
                     preferred_element_type=jnp.float32) * scale
    k_scale = _scale_row(ksc_ref)
    if k_scale is not None:
        scores = scores * k_scale
    if softcap is not None:
        scores = softcap * jnp.tanh(scores / softcap)
    mask = mask_fn(scores.shape)
    scores = jnp.where(mask, scores, NEG_INF)
    online_softmax_update(scores, mask, _page_tile(v_ref, packed),
                          m_ref, l_ref, acc_ref, use_lut=use_lut,
                          expwb_ref=expwb_ref, lut=lut,
                          v_scale=_scale_row(vsc_ref))


def split_refs(refs, quantized: bool, n_out: int):
    """(q, k, v, ksc, vsc, expwb, outs, scratch) from a kernel's refs;
    ksc/vsc are None for fp pools."""
    if quantized:
        q_ref, k_ref, v_ref, ksc_ref, vsc_ref, expwb_ref, *rest = refs
    else:
        q_ref, k_ref, v_ref, expwb_ref, *rest = refs
        ksc_ref = vsc_ref = None
    return (q_ref, k_ref, v_ref, ksc_ref, vsc_ref, expwb_ref,
            rest[:n_out], rest[n_out:])


def _paged_attn_kernel(
    len_ref,   # scalar prefetch: (B,) int32 valid lengths
    win_ref,   # scalar prefetch: (1,) int32 sliding window
    tbl_ref,   # scalar prefetch: (B, n_pages) int32 physical page ids
    *refs,     # q, k, v, [ksc, vsc,] expwb, o, then m/l/acc scratch
    n_pages, page_size, quantized, **page_kw,
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

    def mask_fn(shape):
        pos = s_idx * page_size + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
        return jnp.logical_and(pos < length, pos >= length - win_ref[0])

    attend_page(q_ref[0, 0].astype(jnp.float32), k_ref, v_ref, ksc_ref,
                vsc_ref, expwb_ref, m_ref, l_ref, acc_ref, mask_fn=mask_fn,
                **page_kw)

    @pl.when(s_idx == n_pages - 1)
    def _writeback():
        l = jnp.maximum(l_ref[...], 1e-9)
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)


def _paged_attn_split_kernel(
    len_ref,   # scalar prefetch: (B,) int32 valid lengths
    win_ref,   # scalar prefetch: (1,) int32 sliding window
    tbl_ref,   # scalar prefetch: (B, kv_splits * pps) int32, trash-padded
    *refs,     # q, k, v, [ksc, vsc,] expwb, m_out, l_out, acc_out,
               # then m/l/acc scratch
    pps, page_size, quantized, **page_kw,
):
    """KV-split body: identical page math as `_paged_attn_kernel`, but
    the page walk covers only this split's `pps` pages and the writeback
    emits raw partials (m, l, un-normalized acc) for the host-side
    `merge_partial_softmax_stacked` combine. A split whose pages are all
    past `length` (trash-padded tail) emits the empty partial
    (m=NEG_INF, l=0, acc=0), which the merge's finite guard absorbs."""
    (q_ref, k_ref, v_ref, ksc_ref, vsc_ref, expwb_ref,
     (mo_ref, lo_ref, ao_ref), (m_ref, l_ref, acc_ref)) = split_refs(
        refs, quantized, 3)
    b = pl.program_id(0)
    sp_idx = pl.program_id(2)
    p_idx = pl.program_id(3)

    @pl.when(p_idx == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    length = len_ref[b]

    def mask_fn(shape):
        # Absolute position: this split's run starts sp_idx * pps pages in.
        pos = ((sp_idx * pps + p_idx) * page_size
               + jax.lax.broadcasted_iota(jnp.int32, shape, 1))
        return jnp.logical_and(pos < length, pos >= length - win_ref[0])

    attend_page(q_ref[0, 0].astype(jnp.float32), k_ref, v_ref, ksc_ref,
                vsc_ref, expwb_ref, m_ref, l_ref, acc_ref, mask_fn=mask_fn,
                **page_kw)

    @pl.when(p_idx == pps - 1)
    def _writeback():
        mo_ref[0, 0, 0] = m_ref[...]
        lo_ref[0, 0, 0] = l_ref[...]
        ao_ref[0, 0, 0] = acc_ref[...]


def check_scale_rows(k_pages, k_scales, v_scales):
    """Validate the optional scale pools of one layer's page pool:
    both or neither, laid out (P, Hkv, 1, page_size). True = quantized."""
    if (k_scales is None) != (v_scales is None):
        raise ValueError("pass both k_scales and v_scales or neither")
    if k_scales is None:
        return False
    want = k_pages.shape[:2] + (1, k_pages.shape[2])
    if k_scales.shape != want or v_scales.shape != want:
        raise ValueError(
            f"scale rows must be laid out (P, Hkv, 1, page_size) = {want}, "
            f"got {k_scales.shape} / {v_scales.shape}")
    return True


def paged_attention(
    q: jax.Array,             # (B, H, D)
    k_pages: jax.Array,       # (P, Hkv, page_size, D) shared pool
    v_pages: jax.Array,       # (P, Hkv, page_size, D)
    block_tables: jax.Array,  # (B, n_pages) int32 physical page ids
    length: jax.Array,        # (B,) int32 valid cache lengths
    k_scales: jax.Array | None = None,  # (P, Hkv, 1, page_size) int8/int4
    v_scales: jax.Array | None = None,
    *,
    scale: float | None = None,
    exp_table: LutTable | None = None,
    softcap: float | None = None,
    window=None,              # int, traced int32 scalar, or None (global)
    kv_splits: int | None = None,
    interpret: bool = False,
) -> jax.Array:
    B, H, D = q.shape
    Hkv, page_size = k_pages.shape[1], k_pages.shape[2]
    n_pages = block_tables.shape[1]
    g = H // Hkv
    scale = scale if scale is not None else 1.0 / (D**0.5)

    wb, lut = lut_operand(exp_table)
    qg = q.reshape(B, Hkv, g, D)
    lens = length.astype(jnp.int32)
    tables = block_tables.astype(jnp.int32)
    quantized = check_scale_rows(k_pages, k_scales, v_scales)
    packed = 2 * k_pages.shape[-1] == D    # nibble-packed int4 payload
    Dp = k_pages.shape[-1]                 # payload axis (D, or D/2 packed)
    if packed and not quantized:
        raise ValueError("packed int4 pools require scale rows")
    page_kw = dict(page_size=page_size, quantized=quantized, scale=scale,
                   softcap=softcap, packed=packed,
                   use_lut=exp_table is not None, lut=lut)
    scalars = (lens, window_operand(window), tables)

    splits = effective_kv_splits(kv_splits, n_pages, page_size)
    if splits is not None:
        return _paged_attention_split(
            qg, k_pages, v_pages, scalars, k_scales, v_scales, wb,
            splits=splits, page_kw=page_kw, interpret=interpret,
            out_dtype=q.dtype)

    kernel = functools.partial(_paged_attn_kernel, n_pages=n_pages,
                               **page_kw)
    # Physical page addresses come from the prefetched block table.
    page_spec = pl.BlockSpec((1, 1, page_size, Dp),
                             lambda b, h, s, lens_ref, win_ref, tbl_ref:
                             (tbl_ref[b, s], h, 0, 0))
    scale_spec = pl.BlockSpec((1, 1, 1, page_size),
                              lambda b, h, s, lens_ref, win_ref, tbl_ref:
                              (tbl_ref[b, s], h, 0, 0))
    in_specs = [
        pl.BlockSpec((1, 1, g, D), lambda b, h, s, *_: (b, h, 0, 0)),
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
        num_scalar_prefetch=3,
        grid=(B, Hkv, n_pages),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, g, D), lambda b, h, s, *_: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, D), jnp.float32),
        ],
    )

    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, g, D), q.dtype),
        interpret=interpret,
    )(*scalars, *inputs)
    return out.reshape(B, H, D)


def _paged_attention_split(
    qg, k_pages, v_pages, scalars, k_scales, v_scales, wb, *,
    splits, page_kw, interpret, out_dtype,
):
    """KV-split pallas_call: 4D grid + host-side partials combine."""
    from repro.distributed.collectives import merge_partial_softmax_stacked

    B, Hkv, g, D = qg.shape
    page_size, Dp = k_pages.shape[2], k_pages.shape[-1]
    lens, win, tables = scalars
    n_pages = tables.shape[1]
    pps = -(-n_pages // splits)            # pages per split
    pad = pps * splits - n_pages
    if pad:
        # Trash-page padding: positions land >= length, so masked out.
        tables = jnp.pad(tables, ((0, 0), (0, pad)))

    kernel = functools.partial(_paged_attn_split_kernel, pps=pps, **page_kw)
    page_spec = pl.BlockSpec((1, 1, page_size, Dp),
                             lambda b, h, sp, p, lens_ref, win_ref, tbl_ref:
                             (tbl_ref[b, sp * pps + p], h, 0, 0))
    scale_spec = pl.BlockSpec((1, 1, 1, page_size),
                              lambda b, h, sp, p, lens_ref, win_ref, tbl_ref:
                              (tbl_ref[b, sp * pps + p], h, 0, 0))
    in_specs = [
        pl.BlockSpec((1, 1, g, D), lambda b, h, sp, p, *_: (b, h, 0, 0)),
        page_spec,
        page_spec,
    ]
    inputs = [qg, k_pages, v_pages]
    if page_kw["quantized"]:
        in_specs += [scale_spec, scale_spec]
        inputs += [k_scales, v_scales]
    in_specs.append(SMEM_SPEC)
    inputs.append(wb)

    part_spec = pl.BlockSpec((1, 1, 1, g, 1),
                             lambda b, h, sp, p, *_: (b, h, sp, 0, 0))
    acc_spec = pl.BlockSpec((1, 1, 1, g, D),
                            lambda b, h, sp, p, *_: (b, h, sp, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, Hkv, splits, pps),
        in_specs=in_specs,
        out_specs=[part_spec, part_spec, acc_spec],
        scratch_shapes=[
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, D), jnp.float32),
        ],
    )

    m, l, acc = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((B, Hkv, splits, g, 1), jnp.float32),
            jax.ShapeDtypeStruct((B, Hkv, splits, g, 1), jnp.float32),
            jax.ShapeDtypeStruct((B, Hkv, splits, g, D), jnp.float32),
        ],
        interpret=interpret,
    )(lens, win, tables, *inputs)
    out = merge_partial_softmax_stacked(m, l, acc, axis=2)
    return out.reshape(B, Hkv * g, D).astype(out_dtype)
