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

Grid: (B, Hkv, row tiles). The query block of one (sequence, KV head)
is (Sq*g, D), g = H // Hkv (GQA groups share one K/V page stream; row r
is query r//g, group r%g), cut into tiles of `prefill_row_tile` rows.
Each step walks only its tile's live table entries, [lo, hi): from the
page of its first query's first in-window key to the page after its
causal frontier (its last query's position, or length - 1 if that is
less), bounds taken from the prefetched lengths, starts and window, so
no entry outside them is read or DMA'd (`prefill_walk_pages` counts a
chunk's). Two routes, chosen from the pool's shape:

  * DMA route (fp payloads a multiple of 128 lanes wide: head_dim 128):
    a `fori_loop` over blocks of `prefill_walk_block_pages` pages, as
    the decode walk does. Each page of the step's KV head arrives by
    one `make_async_copy` out of the pool as it is stored, (P, Hkv,
    page, D) in HBM (`pl.ANY`), double-buffered: block i + 1's DMAs
    start before block i's math. No view or copy of the pool is made.
  * BlockSpec route (head_dim 64; int8/int4 pools with their (1, page)
    scale rows): Mosaic refuses a DMA sliced out of an HBM array whose
    lane axis is narrower than 128, so a fourth grid axis takes one
    table entry a step, and its index map clamps the entry into
    [lo, hi): the steps outside it repeat a block index (no DMA) and
    skip the math.

The body is `paged_attention.attend_block`, the KV-split decode
kernel's too: the online-softmax (m, l, acc) merge — the C-ALU merge
of per-bank partials — once per block, with a causal mask at absolute
positions (key <= start + row//g) on top of the length and window
masks; exp optionally routes through the same 64-section LUT. int8
pools dequantize in VMEM right after the DMA, folding each page's
scale row into the score and probability tiles; int4 pools (payload
axis Dh/2, detected structurally) nibble-unpack in VMEM first.

Under mesh-sharded serving (`models/attention.py`'s shard_map wrapper)
the kernel runs unchanged on per-shard slices — local Hkv, local pool
shard — exactly as described in `kernels/paged_attention.py`: the grid
and the DMAs never cross the Hkv axis, so sharding it only shrinks
the grid, and the (kv_head, group) q-head ordering keeps each shard's
q block aligned with its KV heads.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.lut import LutTable
from repro.kernels.decode_attention import NEG_INF, window_operand
from repro.kernels.lut_interp import LANE, SMEM_SPEC, lut_operand
from repro.kernels.paged_attention import (attend_block, check_scale_rows,
                                           widen_payload)

# Query rows one grid step holds, so that the (rows, D) accumulator and
# the (rows, block) score tiles fit VMEM whatever the chunk.
MAX_TILE_ROWS = 4096
# f32 score-tile bytes a block may fill, and the most keys it may span.
# Wider tiles run faster (one v5e, Qwen2-1.5B's 512-token chunk at 7,680
# keys: 0.34 ms a call with one 3,072-row tile and 512-key blocks, 1.03
# with 1,024-row tiles and 192-key blocks, 6.29 a grid step per entry).
SCORE_TILE_BYTES = 8 * 2**20
MAX_BLOCK_KEYS = 512
VMEM_CAP = 100 * 2**20


def prefill_row_tile(rows: int) -> int:
    """Query rows per grid step: all `rows` of a (sequence, KV head) if
    they fit MAX_TILE_ROWS, else the fewest equal tiles that do, rounded
    up to a 16-row multiple (bf16's sublane tile; a last tile past the
    rows is partial)."""
    if rows <= MAX_TILE_ROWS:
        return rows
    n = -(-rows // MAX_TILE_ROWS)
    return -(-rows // (16 * n)) * 16


def prefill_walk_block_pages(page_size: int, head_dim: int, rows: int,
                             n_pages: int) -> int:
    """Pages per block of the chunk walk: as many as keep the (rows,
    block) f32 score tile and the block's widened K and V within
    SCORE_TILE_BYTES, at least the fewest that fill a 128-lane score
    row, at most MAX_BLOCK_KEYS positions, capped at the table's
    width."""
    keys = SCORE_TILE_BYTES // (4 * (rows + 2 * head_dim))
    keys = min(max(keys, LANE), MAX_BLOCK_KEYS)
    return max(1, min(n_pages, max(keys // page_size,
                                   -(-LANE // page_size))))


def prefill_walk_pages(length, start, window, page_size: int) -> int:
    """Table entries the chunk walk reads for chunks of kernel lengths
    `length` whose first queries sit at `start` (host integers), under
    sliding window `window` (None: global): each chunk's pages from the
    one holding its first query's first in-window key through the one
    holding position length - 1. Every KV head DMAs each once per row
    tile it covers."""
    length = np.asarray(length, np.int64)
    start = np.asarray(start, np.int64)
    lo = (0 if window is None
          else np.maximum(start - window + 1, 0) // page_size)
    return int(np.maximum(-(-length // page_size) - lo, 0).sum())


def _fetch(pool, page, head, buf, sem):
    """The DMA of one table entry's page of one KV head, out of a pool
    stored (P, Hkv, page, D), into one VMEM slot."""
    return pltpu.make_async_copy(pool.at[page, head], buf, sem)


def _bounds(t, rows, g, page_size, length, start, window):
    """[lo, hi): the table entries row tile t's walk reads — from the
    page of its first query's first in-window key to the page after its
    causal frontier (its last query's position, or length - 1 if that
    is less)."""
    first = start + (t * rows) // g
    last = jnp.minimum(start + (t * rows + rows - 1) // g, length - 1)
    lo = jnp.maximum(first - window + 1, 0) // page_size
    return lo, last // page_size + 1


def _pipeline_entry(s, lo, hi, n_pages):
    """The table entry grid step s of the BlockSpec route fetches: s
    itself inside [lo, hi), else the nearest end of that range (a
    repeated block index, which the pipeline does not DMA again)."""
    return jnp.clip(jnp.minimum(s, hi - 1), lo, n_pages - 1)


def _chunk_mask(rows, bt, base, row0, g, start, length, window):
    """Validity of the (rows, bt) score tile of query rows row0 .. and
    keys base .. at absolute positions: causal (key <= start + row//g),
    below the length, inside the window. With k = key - start, the
    first two read row >= k*g and the window row < (k + window)*g, so
    no vector divides; a window of `length` or more already reaches
    position 0, so it is clipped there before the multiply."""
    k = base - start + jax.lax.broadcasted_iota(jnp.int32, (rows, bt), 1)
    row = row0 + jax.lax.broadcasted_iota(jnp.int32, (rows, bt), 0)
    window = jnp.minimum(window, length)
    return jnp.logical_and(
        jnp.logical_and(k < length - start, row >= k * g),
        row < (k + window) * g)


def _init(m_ref, l_ref, acc_ref):
    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)


def _writeback(o_ref, l_ref, acc_ref):
    l = jnp.maximum(l_ref[...], 1e-9)
    o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)


def _walk_kernel(
    len_ref,    # scalar prefetch: (B,) int32 valid KV lengths (incl. chunk)
    start_ref,  # scalar prefetch: (B,) int32 absolute first query position
    win_ref,    # scalar prefetch: (1,) int32 sliding window
    tbl_ref,    # scalar prefetch: (B, n_pages) int32 physical page ids
    q_ref,      # (1, 1, rows, D) this tile's queries
    k_hbm, v_hbm,          # (P, Hkv, page, D) pools, left in HBM
    expwb_ref, o_ref,
    k_buf, v_buf,          # (2, ppb, page, D) double-buffered page blocks
    sem, m_ref, l_ref, acc_ref,
    *, ppb, page_size, g, **block_kw,
):
    """DMA route, one (sequence, KV head, row tile): walk the tile's
    live page blocks. Block i holds table entries lo + i*ppb .. lo +
    i*ppb + ppb - 1; a last block that runs past hi DMAs nothing there
    and keeps stale buffer rows, which `attend_block` zeroes."""
    rows, bt = q_ref.shape[2], ppb * page_size
    b, h, t = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    length, start, window = len_ref[b], start_ref[b], win_ref[0]
    lo, hi = _bounds(t, rows, g, page_size, length, start, window)
    n_blocks = jnp.maximum(hi - lo + ppb - 1, 0) // ppb

    def each_copy(i, slot, act):  # act(copy) on block i's pages below hi
        for j in range(ppb):
            e = lo + i * ppb + j

            @pl.when(e < hi)
            def _():
                page = tbl_ref[b, e]
                for pool, buf in ((k_hbm, k_buf), (v_hbm, v_buf)):
                    act(_fetch(pool, page, h, buf.at[slot, j], sem.at[slot]))

    def block(i, carry):
        slot = i % 2

        @pl.when(i + 1 < n_blocks)
        def _prefetch():
            each_copy(i + 1, 1 - slot, lambda c: c.start())

        each_copy(i, slot, lambda c: c.wait())
        base = (lo + i * ppb) * page_size
        attend_block(q_ref[0, 0].astype(jnp.float32),
                     k_buf[slot].astype(jnp.float32).reshape(bt, -1),
                     v_buf[slot].astype(jnp.float32).reshape(bt, -1), None,
                     None, _chunk_mask(rows, bt, base, t * rows, g, start,
                                       length, window),
                     expwb_ref, m_ref, l_ref, acc_ref,
                     n_live=hi * page_size - base, **block_kw)
        return carry

    _init(m_ref, l_ref, acc_ref)

    @pl.when(n_blocks > 0)
    def _first():
        each_copy(0, 0, lambda c: c.start())

    jax.lax.fori_loop(0, n_blocks, block, 0)
    _writeback(o_ref, l_ref, acc_ref)


def _pipeline_kernel(
    len_ref, start_ref, win_ref, tbl_ref,   # scalar prefetch, as above
    q_ref,      # (1, 1, rows, D) this tile's queries
    *refs,      # k, v, [ksc, vsc,] pages, expwb, o, then m/l/acc scratch
    n_pages, page_size, g, quantized, packed, **block_kw,
):
    """BlockSpec route, one (sequence, KV head, row tile, table entry):
    the index maps clamp every entry into the tile's [lo, hi), so the
    steps outside it repeat a block index (no DMA) and skip the math."""
    k_ref, v_ref = refs[:2]
    ksc_ref, vsc_ref = refs[2:4] if quantized else (None, None)
    expwb_ref, o_ref, m_ref, l_ref, acc_ref = refs[-5:]
    rows = q_ref.shape[2]
    b, t, s = pl.program_id(0), pl.program_id(2), pl.program_id(3)
    length, start, window = len_ref[b], start_ref[b], win_ref[0]
    lo, hi = _bounds(t, rows, g, page_size, length, start, window)

    @pl.when(s == 0)
    def _():
        _init(m_ref, l_ref, acc_ref)

    @pl.when(jnp.logical_and(s >= lo, s < hi))
    def _():
        def scales(ref):  # (1, page_size) row, DMA'd in its storage dtype
            return None if ref is None else ref[0, 0].astype(jnp.float32)

        attend_block(q_ref[0, 0].astype(jnp.float32),
                     widen_payload(k_ref[0, 0], packed),
                     widen_payload(v_ref[0, 0], packed), scales(ksc_ref),
                     scales(vsc_ref),
                     _chunk_mask(rows, page_size, s * page_size, t * rows, g,
                                 start, length, window),
                     expwb_ref, m_ref, l_ref, acc_ref, **block_kw)

    @pl.when(s == n_pages - 1)
    def _():
        _writeback(o_ref, l_ref, acc_ref)


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
    R = Sq * g
    qg = (q.reshape(B, Sq, Hkv, g, D)
          .transpose(0, 2, 1, 3, 4)
          .reshape(B, Hkv, R, D))
    quantized = check_scale_rows(k_pages, k_scales, v_scales)
    packed = 2 * k_pages.shape[-1] == D    # nibble-packed int4 payload
    if packed and not quantized:
        raise ValueError("packed int4 pools require scale rows")

    rows = prefill_row_tile(R)
    block_kw = dict(scale=scale, softcap=softcap,
                     use_lut=exp_table is not None, lut=lut)
    scalars = (length.astype(jnp.int32), start.astype(jnp.int32),
               window_operand(window), block_tables.astype(jnp.int32))
    pools = [k_pages, v_pages]
    if quantized:
        # Scale rows stream in their storage dtype (f32 or bf16) — the
        # bf16 mode's bandwidth saving depends on NOT widening them
        # host-side; the kernel widens after the DMA.
        pools += [k_scales, v_scales]
    stats = [pltpu.VMEM((rows, 1), jnp.float32),
             pltpu.VMEM((rows, 1), jnp.float32),
             pltpu.VMEM((rows, D), jnp.float32)]
    if not quantized and k_pages.shape[-1] % LANE == 0:
        # DMA route: Mosaic slices a DMA out of an HBM array only where
        # its lane axis is a multiple of 128 (fp payloads at head_dim
        # 128k); everything else takes the BlockSpec route below.
        ppb = prefill_walk_block_pages(page_size, D, rows, n_pages)
        bt = ppb * page_size
        kernel = functools.partial(_walk_kernel, ppb=ppb,
                                   page_size=page_size, g=g, **block_kw)
        row_spec = pl.BlockSpec((1, 1, rows, D),
                                lambda b, h, t, *_: (b, h, t, 0))
        grid = (B, Hkv, pl.cdiv(R, rows))
        in_specs = ([row_spec] + [pl.BlockSpec(memory_space=pl.ANY)] * 2
                    + [SMEM_SPEC])
        scratch = ([pltpu.VMEM((2, ppb, page_size, D), x.dtype)
                    for x in pools]
                   + [pltpu.SemaphoreType.DMA((2,))] + stats)
        # Only the DMA route needs the TPU interpreter's model of DMAs
        # and semaphores; the plain one runs a BlockSpec pipeline faster.
        interpret = interpret and pltpu.InterpretParams()
    else:
        bt = page_size
        kernel = functools.partial(
            _pipeline_kernel, n_pages=n_pages, page_size=page_size, g=g,
            quantized=quantized, packed=packed, **block_kw)

        def page_map(b, h, t, s, len_ref, start_ref, win_ref, tbl_ref):
            lo, hi = _bounds(t, rows, g, page_size, len_ref[b],
                             start_ref[b], win_ref[0])
            return tbl_ref[b, _pipeline_entry(s, lo, hi, n_pages)], h, 0, 0

        row_spec = pl.BlockSpec((1, 1, rows, D),
                                lambda b, h, t, s, *_: (b, h, t, 0))
        grid = (B, Hkv, pl.cdiv(R, rows), n_pages)
        in_specs = ([row_spec]
                    + [pl.BlockSpec((1, 1) + x.shape[2:], page_map)
                       for x in pools]
                    + [SMEM_SPEC])
        scratch = stats
    vmem = (4 * rows * D * q.dtype.itemsize       # q and o, double-buffered
            + 4 * rows * (D + 2 * LANE)           # acc, m, l
            + 8 * 4 * rows * bt                   # score-tile temporaries
            + 4 * 4 * bt * D)                     # K and V blocks
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4, grid=grid, in_specs=in_specs,
            out_specs=row_spec, scratch_shapes=scratch),
        out_shape=jax.ShapeDtypeStruct((B, Hkv, R, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=min(vmem + 16 * 2**20, VMEM_CAP)),
        interpret=interpret,
    )(*scalars, qg, *pools, wb)
    return (out.reshape(B, Hkv, Sq, g, D)
            .transpose(0, 2, 1, 3, 4)
            .reshape(B, Sq, H, D))
