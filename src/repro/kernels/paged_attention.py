"""Pallas TPU kernel: paged decode attention over a block-table KV pool.

The paged sibling of `kernels/decode_attention.py`: one query token per
sequence attends to K/V that live in a *shared page pool* rather than a
dense per-sequence arena. Each sequence's pages are found through a
scalar-prefetched block table — SAL-PIM's bank-sequential K/V placement
(`serving/kvcache.py`) read back bank-by-bank:

  * the block table is the last `num_scalar_prefetch` input (after the
    lengths and the sliding-window scalar), so page addresses are
    scalar reads (the C3 hierarchy mapping: logical page -> bank);
  * the math is the dense kernel's — the online-softmax (m, l, acc)
    merge across pages is the C-ALU merge of per-bank partials, and exp
    optionally routes through the same 64-section LUT
    (`decode_attention.online_softmax_update`).

Grid: (B,), one step per row. The step walks only that row's live
pages, in blocks of `walk_block_pages` pages (the fewest that fill a
128-lane score row): from the page holding its first in-window
position to the one holding position length - 1, a `fori_loop` whose
trip count comes from the row's length and the window operand. Each
page arrives by one `make_async_copy` that moves every KV head at
once, double-buffered: block i + 1's DMAs start before block i's math.
The kernel reads and DMAs no table entry outside that range
(`walk_pages` counts the pages it does). The pools are passed in HBM
(`pl.ANY`) as a (P, page, Hkv * Dp) view — one token per row, its heads
side by side in the lanes — because Mosaic cannot slice a DMA out of
an array whose lane axis is narrower than 128 (head_dim 64). The view
is a relayout of the whole pool, built outside the kernel on every
call: its cost grows with the pool's pages P, live or not, and is not
part of the kernel's time. A block-diagonal query (row h * g + r holds
query head h * g + r's dims at KV head h's lanes, zeros elsewhere)
turns each block into one (Hkv * g, block) score tile for every head:
one LUT and softmax update per block, not per head.

int8 pools (`k_scales`/`v_scales` given) hold int8 payload plus one
scale row per (page, head) — the (Dh + 4) bytes per vector that
`kv_vector_bytes` counts, against 2*Dh for bf16. The scale pools,
laid out (P, Hkv, 1, page_size), reach the walk in their storage
dtype as one slab a page holding every head's row (`_scale_view`;
lane-padded to 128k, so a page whose Hkv * page_size scales fill
fewer lanes moves the padding too); the kernel widens them and spreads
each head's row over its g score rows in VMEM. The split path's
BlockSpecs take each (1, page_size) row as it is. The kernel
dequantizes *in VMEM* by folding the scale rows into the contraction
they feed: K scales multiply the score tile column-wise, V scales the
probability tile, so neither page is rescaled elementwise.

int4 pools pack two values per byte, so the page payload is
(page_size, Dh/2) and the pool holds (Dh/2 + 2) bytes per vector (bf16
scale rows). Packing is detected structurally (payload axis is half the
query head_dim) and the kernel unpacks in VMEM with two arithmetic
shifts on the int32-widened bytes plus a halves concat
(`serving/quantize.unpack_int4`'s convention). Unmapped table entries
point at the trash page (physical page 0); positions past `length` in
a walked page are masked.

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
That works because the kernels are head-separable — the walk's
block-diagonal query never mixes heads, and no split-grid index map
reads across the Hkv axis — so sharding that axis just narrows each
page. The q slice keeps group = H // Hkv because GQA orders q heads as
(kv_head, group): a contiguous H-block lines up exactly with its
KV-head block.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.lut import LutTable
from repro.kernels.decode_attention import (NEG_INF, online_softmax_update,
                                           window_operand)
from repro.kernels.lut_interp import LANE, SMEM_SPEC, lut_operand

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

    This is the byte contract of the pools the kernels read — one row
    of a page payload (plus its scale-row element, when the pool is
    quantized) per vector:

        fp pools:   Dh * itemsize(payload_dtype)
        int8 pools: Dh + itemsize(scale)      (4 f32 / 2 bf16 scales)
        int4 pools: Dh/2 + itemsize(scale)    (nibble-packed payload)

    `serving/kvcache.page_kv_bytes` (pool sizing / admission budgets)
    and `serving/costmodel` (the roofline model) both derive from this
    single definition. It is what the pools hold and the least a walk
    over them must move. The decode walk moves more where its views pad
    lanes: payload rows narrower than 128 lanes across all heads, and
    scale slabs whose Hkv * page_size entries fill fewer than 128k lanes
    (2 KV heads of 16-token pages: 128 f32 lanes for 32 scales). The
    views themselves are relayout copies of the whole pool per call
    (module docstring), which no per-vector figure counts.
    """
    if kv_dtype == "int8":
        return head_dim + jnp.dtype(kv_scale_dtype).itemsize
    if kv_dtype == "int4":
        return head_dim // 2 + jnp.dtype(kv_scale_dtype).itemsize
    return head_dim * jnp.dtype(payload_dtype).itemsize


def widen_payload(x, packed):
    """Page payload rows (..., Dp) -> f32 (..., D). int4 pools unpack
    in VMEM: the bytes widen to int32 first (Mosaic has no 8-bit
    shifts), then arithmetic shifts sign-extend the low and high nibbles
    and a halves concat puts the high ones after the low (one head's
    row: head_dim order)."""
    if packed:
        x = x.astype(jnp.int32)
        x = jnp.concatenate(
            [jnp.right_shift(jnp.left_shift(x, 28), 28),
             jnp.right_shift(x, 4)], axis=-1)
    return x.astype(jnp.float32)


def attend_block(q, k, v, k_scale, v_scale, mask, expwb_ref, m_ref, l_ref,
                 acc_ref, *, scale, softcap, use_lut, lut, n_live=None):
    """Fold one block of keys into the running (m, l, acc) of `q` (rows,
    D): the body shared by the KV-split decode and chunk prefill
    kernels, which differ only in `mask`, the validity of each score of
    the (rows, block) tile. k and v are the f32 (block, D) page rows,
    k_scale and v_scale their (1, block) f32 dequant scales (int8/int4
    pools) or None. Only the block's first `n_live` positions hold
    fetched pages (None: all): the others' V rows and V scales are
    zeroed, so stale buffer rows never reach p . V."""
    # Direction 1: contract head_dim (Q x K^T) — same layout, no transpose.
    scores = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
    if k_scale is not None:
        scores = scores * k_scale
    if softcap is not None:
        scores = softcap * jnp.tanh(scores / softcap)
    scores = jnp.where(mask, scores, NEG_INF)
    if n_live is not None:
        def live(shape, axis):
            return jax.lax.broadcasted_iota(jnp.int32, shape, axis) < n_live

        bt = k.shape[0]
        v = jnp.where(live((bt, 1), 0), v, 0.0)
        if v_scale is not None:
            v_scale = jnp.where(live((1, bt), 1), v_scale, 0.0)
    online_softmax_update(scores, mask, v, m_ref, l_ref, acc_ref,
                          use_lut=use_lut, expwb_ref=expwb_ref, lut=lut,
                          v_scale=v_scale)


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


def _walk_kernel(
    len_ref,   # scalar prefetch: (B,) int32 valid lengths
    win_ref,   # scalar prefetch: (1,) int32 sliding window
    tbl_ref,   # scalar prefetch: (B, n_pages) int32 physical page ids
    q_ref,     # (1, Hkv*g, W) this row's block-diagonal query
    *refs,     # k, v, [ksc, vsc,] page views (HBM), expwb, o, then the
               # double-buffered page blocks, DMA semaphores, m/l/acc
    ppb, hkv, page_size, quantized, packed, scale, softcap, use_lut, lut,
):
    """One row: walk its live page blocks, every KV head in each.

    Block i holds pages lo + i*ppb .. lo + i*ppb + ppb - 1 of the row's
    table, where lo is the page of the first in-window position; the
    walk stops at hi, the page after the one holding position
    length - 1 (`walk_pages`), so no table entry outside [lo, hi) is
    read or DMA'd. A last block that runs past hi keeps stale buffer
    rows there: their scores are masked and their V rows and V scales
    zeroed, so p . V never meets them. Block i + 1's DMAs start before
    block i's math. Row h*g + r of the scores is query row r of KV head
    h: the block-diagonal query picks that head's lanes out of each
    token's (Hkv * D) row, so one online-softmax update covers every
    head."""
    n = 4 if quantized else 2
    views, (expwb_ref, o_ref) = refs[:n], refs[n:n + 2]
    bufs = refs[n + 2:2 * n + 2]
    sem, m_ref, l_ref, acc_ref = refs[2 * n + 2:]
    rows, bt = q_ref.shape[1], ppb * page_size
    b = pl.program_id(0)
    length, window = len_ref[b], win_ref[0]
    lo = jnp.maximum(length - window, 0) // page_size
    hi = (length + page_size - 1) // page_size
    n_blocks = (hi - lo + ppb - 1) // ppb

    def each_copy(i, slot, act):  # act(copy) on block i's pages below hi
        for j in range(ppb):
            t = lo + i * ppb + j

            @pl.when(t < hi)
            def _():
                page = tbl_ref[b, t]
                for src, buf in zip(views, bufs):
                    act(pltpu.make_async_copy(src.at[page], buf.at[slot, j],
                                              sem.at[slot]))

    def kv_tile(buf, slot):     # (ppb, page, lanes) -> f32 (bt, D lanes)
        return widen_payload(buf[slot], packed).reshape(bt, -1)

    def scale_tile(buf, slot):  # (ppb, R, C) -> f32 (rows, bt)
        x = buf[slot].astype(jnp.float32)
        cols = buf.shape[-1]
        heads = []
        for h in range(hkv):
            r, c = divmod(h * page_size, cols)
            row = jnp.concatenate([x[j, r:r + 1, c:c + page_size]
                                   for j in range(ppb)], axis=-1)
            heads.append(jnp.broadcast_to(row, (rows // hkv, bt)))
        return jnp.concatenate(heads, axis=0)

    def block(i, carry):
        slot = i % 2

        @pl.when(i + 1 < n_blocks)
        def _prefetch():
            each_copy(i + 1, 1 - slot, lambda c: c.start())

        each_copy(i, slot, lambda c: c.wait())
        start = (lo + i * ppb) * page_size
        pos = start + jax.lax.broadcasted_iota(jnp.int32, (rows, bt), 1)
        mask = jnp.logical_and(pos < length, pos >= length - window)
        fetched = (start + jax.lax.broadcasted_iota(jnp.int32, (bt, 1), 0)
                   < hi * page_size)
        scores = jnp.dot(q_ref[0].astype(jnp.float32),
                         kv_tile(bufs[0], slot).T,
                         preferred_element_type=jnp.float32) * scale
        v_scale = None
        if quantized:
            scores = scores * scale_tile(bufs[2], slot)
            v_scale = jnp.where(mask, scale_tile(bufs[3], slot), 0.0)
        if softcap is not None:
            scores = softcap * jnp.tanh(scores / softcap)
        scores = jnp.where(mask, scores, NEG_INF)
        v = jnp.where(fetched, kv_tile(bufs[1], slot), 0.0)
        online_softmax_update(scores, mask, v, m_ref, l_ref, acc_ref,
                              use_lut=use_lut, expwb_ref=expwb_ref, lut=lut,
                              v_scale=v_scale)
        return carry

    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)

    each_copy(0, 0, lambda c: c.start())
    jax.lax.fori_loop(0, n_blocks, block, 0)
    l = jnp.maximum(l_ref[...], 1e-9)
    o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


def walk_block_pages(n_pages: int, page_size: int) -> int:
    """Pages per block of the decode walk: the fewest whose positions
    fill a 128-lane score row, capped at the table's width."""
    return max(1, min(n_pages, -(-LANE // page_size)))


def walk_pages(lengths, window, page_size: int) -> int:
    """Pages the decode walk DMAs for rows of kernel lengths `lengths`
    (host integers) under sliding window `window` (None: global): each
    row's pages from the one holding its first in-window position
    through the one holding position length - 1, each page one DMA of
    every KV head per pool."""
    lengths = np.asarray(lengths, np.int64)
    lo = 0 if window is None else np.maximum(lengths - window, 0) // page_size
    return int((-(-lengths // page_size) - lo).sum())


def _pad_lanes(x):
    """Zero-pad the last axis to a multiple of 128: Mosaic slices an HBM
    array for a DMA only along leading axes of a lane-aligned array."""
    pad = -x.shape[-1] % LANE
    return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)]) if pad else x


def _page_view(pool):
    """(P, Hkv, page, Dp) pool -> (P, page, W): page p's K or V of every
    head as one DMA, a token per row and its heads side by side in the
    lanes (W = Hkv * Dp, lane-padded)."""
    P, Hkv, page, Dp = pool.shape
    return _pad_lanes(pool.transpose(0, 2, 1, 3).reshape(P, page, Hkv * Dp))


def _scale_view(scales):
    """(P, Hkv, 1, page) scale rows -> (P, R, C) in their storage dtype:
    page p's scales of every head, head h's at flat offset h * page, in
    R rows (the dtype's sublane packing: 1 for f32, 2 for bf16) of
    C lanes, a multiple of 128 — the smallest slab a DMA can take out of
    the pool. The walk widens them and spreads each head's row over its
    g score rows in VMEM."""
    P, Hkv, _, page = scales.shape
    R = max(1, 4 // scales.dtype.itemsize)
    flat = scales.reshape(P, Hkv * page)
    flat = jnp.pad(flat, [(0, 0), (0, -flat.shape[1] % (R * LANE))])
    return flat.reshape(P, R, -1)


def _diagonal_query(qg, Dp, W):
    """(B, Hkv, g, D) -> (B, Hkv*g, F*W), F = D // Dp: query row (h, r)
    holds its D dims at the lanes head h's payload widens to — dims
    [f*Dp, (f+1)*Dp) at f*W + h*Dp (int4: the low then the high
    nibbles) — and zeros at every other head's."""
    B, Hkv, g, D = qg.shape
    F = D // Dp
    eye = jnp.eye(Hkv, dtype=qg.dtype)[:, None, None, :, None]
    qd = qg.reshape(B, Hkv, g, F, 1, Dp) * eye
    return _pad_lanes(qd.reshape(B, Hkv * g, F, Hkv * Dp)).reshape(
        B, Hkv * g, F * W)


def _diagonal_heads(out, Hkv, Dp, W):
    """Inverse of `_diagonal_query` on the kernel's (B, Hkv*g, F*W)
    output: each head's own lanes, as (B, Hkv*g*D)."""
    B, rows, FW = out.shape
    F, g = FW // W, rows // Hkv
    o = out.reshape(B, Hkv, g, F, W)[..., :Hkv * Dp]
    o = jnp.diagonal(o.reshape(B, Hkv, g, F, Hkv, Dp), axis1=1, axis2=4)
    return jnp.moveaxis(o, -1, 1).reshape(B, -1)


def _paged_attn_split_kernel(
    len_ref,   # scalar prefetch: (B,) int32 valid lengths
    win_ref,   # scalar prefetch: (1,) int32 sliding window
    tbl_ref,   # scalar prefetch: (B, kv_splits * pps) int32, trash-padded
    *refs,     # q, k, v, [ksc, vsc,] expwb, m_out, l_out, acc_out,
               # then m/l/acc scratch
    pps, page_size, quantized, packed, **block_kw,
):
    """KV-split body: `attend_block`'s math over one head and only
    this split's `pps` pages of the table; the writeback emits raw
    partials (m, l, un-normalized acc) for the host-side
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
    q = q_ref[0, 0].astype(jnp.float32)
    # Absolute position: this split's run starts sp_idx * pps pages in.
    pos = ((sp_idx * pps + p_idx) * page_size
           + jax.lax.broadcasted_iota(jnp.int32, (q.shape[0], page_size), 1))
    mask = jnp.logical_and(pos < length, pos >= length - win_ref[0])

    def scales(ref):  # (1, page_size) row, DMA'd in its storage dtype
        return None if ref is None else ref[0, 0].astype(jnp.float32)

    attend_block(q, widen_payload(k_ref[0, 0], packed),
                 widen_payload(v_ref[0, 0], packed), scales(ksc_ref),
                 scales(vsc_ref), mask, expwb_ref, m_ref, l_ref, acc_ref,
                 **block_kw)

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

    ppb = walk_block_pages(n_pages, page_size)
    kernel = functools.partial(_walk_kernel, ppb=ppb, hkv=Hkv,
                               **page_kw)
    views = [_page_view(k_pages), _page_view(v_pages)]
    if quantized:
        views += [_scale_view(k_scales), _scale_view(v_scales)]
    W = views[0].shape[-1]
    qd = _diagonal_query(qg, Dp, W)
    rows, lanes = qd.shape[1:]
    row_spec = pl.BlockSpec((1, rows, lanes), lambda b, *_: (b, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B,),
        in_specs=([row_spec]
                  + [pl.BlockSpec(memory_space=pl.ANY)] * len(views)
                  + [SMEM_SPEC]),
        out_specs=row_spec,
        scratch_shapes=(
            [pltpu.VMEM((2, ppb) + x.shape[1:], x.dtype) for x in views]
            + [pltpu.SemaphoreType.DMA((2,)),
               pltpu.VMEM((rows, 1), jnp.float32),
               pltpu.VMEM((rows, 1), jnp.float32),
               pltpu.VMEM((rows, lanes), jnp.float32)]),
    )

    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, rows, lanes), q.dtype),
        interpret=pltpu.InterpretParams() if interpret else False,
    )(*scalars, qd, *views, wb)
    return _diagonal_heads(out, Hkv, Dp, W).reshape(B, H, D)


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
