"""Paged KV cache: block-granular cache memory for continuous batching.

SAL-PIM appends K/V bank-sequentially — generation writes land in the
next free bank-row rather than a pre-reserved per-sequence arena. The
software analogue is a *paged* cache: a shared pool of fixed-size KV
pages plus a per-sequence block table, so a slot only holds the pages
its sequence actually filled. Mixed prompt/output lengths then share
one pool instead of each reserving `max_len` slots.

Two halves:

  * `BlockAllocator` — host-side free-list over physical page ids with
    watermark admission: a request is admitted only if its *worst-case*
    page count (see `worst_case_tokens`) can be reserved, so decode
    can never run out of pages mid-sequence (preemption-free). Pages
    are physically allocated lazily — prompt pages at admit, one page
    per decode-step boundary after that — from the reservation.
  * `PagedCache` — the device pytree: page pools (L, P, Hkv, page, Dh),
    per-slot block tables, per-slot lengths. Physical page 0 is a trash
    page that is never allocated; unmapped table entries point at it so
    writes from empty slots land harmlessly.

Prefix sharing (refcount + copy-on-write):

  Every allocated page carries a refcount. Full (page-aligned) prompt
  pages are registered in a prefix cache keyed by a hash *chain* over
  page-sized token chunks — chunk i's key folds in chunk i-1's key, so
  a key identifies the entire token prefix through that page, not just
  the chunk's own content. `admit_tokens` walks the chain and maps the
  longest cached run of full pages into the new sequence's block table
  (refcount += 1 per shared page); the engine then prefills only the
  remaining suffix. Watermark admission reserves the worst case *net of
  shared pages* (plus one page when the prompt is fully covered and the
  recomputed last token's KV write needs a private copy).

  A write that would land in a page with refcount > 1 must first fork
  it: `fork_page` moves the owner to a fresh physical page (COW), the
  engine copies the page contents (`copy_page`) and repoints the block
  table, and only then is the write issued. Cache entries live exactly
  as long as their page: when a release drops a refcount to zero the
  page returns to the free list and its prefix-cache entry is removed.

Chunked paged prefill:

  Prompts are prefilled in chunks written *directly* into pool pages
  (`append_chunk_kv_pages`) — there is no dense per-slot prefill arena
  and no scatter pass. Each chunk's queries read earlier chunks' K/V
  back through the block table, so a prompt mid-prefill occupies only
  its own pages and the engine can interleave decode steps between
  chunks. A chunk that would write into a refcount>1 page COW-forks it
  first, exactly like decode appends.

int8 page pools (kv_dtype="int8"):

  Generation is memory-bandwidth-bound — every decode step streams the
  whole resident KV history — so halving KV bytes per token is worth as
  much as doubling internal bandwidth. The pool can store K/V as int8
  with per-(token, head) *scale rows* kept page-indexed beside the
  payload pools (`k_scale`/`v_scale`, one (page_size,) row per physical
  page per head per layer). Quantization is symmetric amax at write
  time (`serving/quantize.quantize_vec`) in both append paths; the
  paged kernels dequantize in VMEM after the int8 page DMA, so HBM
  traffic per decode step genuinely drops ~2x (Dh + 4 bytes per vector
  vs 2*Dh for bf16). `kv_scale_dtype="bfloat16"` stores the scale rows
  in bf16 — (Dh + 2) bytes per vector — trading ~3 bits of scale
  mantissa for another ~3% of bandwidth. COW forks copy the scale rows
  alongside the pages — a fork must never alias its donor's scales.

int4 page pools (kv_dtype="int4"):

  The same scale-row plumbing carried one step further: payload pools
  pack two 4-bit values per byte (`serving/quantize.quantize_vec_int4`,
  halves convention — byte i holds element i low-nibble and element
  i + Dh/2 high-nibble), so the pool's last axis is Dh/2 and a KV
  vector costs (Dh/2 + 2) bytes with the mandatory bf16 scales — half
  of int8's bytes again. Everything downstream detects packing
  structurally: a pool whose last axis is half the model head_dim is
  int4 (`2 * pool.shape[-1] == Dh`), so the appends pack at write time
  and the kernels/oracles unpack+dequantize after the page DMA with no
  extra dtype flag threaded through the stack. COW forks, swap blobs,
  rewinds, and the prefix cache treat packed payloads as opaque int8
  bytes and need no changes.

Speculative rollback (draft-verify serving):

  The speculative decoding subsystem (`serving/speculative.py`) writes
  k+1 candidate tokens' KV into a slot's pages in one verify pass, then
  keeps only the accepted prefix. Rollback is *in-pool*: `rewind_slot`
  rewinds the slot's device length and re-trashes table entries past
  the kept pages, and `BlockAllocator.rewind` returns now-empty tail
  pages to the free list *and the sequence's reservation* (the exact
  inverse of `extend`, so watermark math is unchanged). This is safe
  because decode-generated pages are never shared: only full *prompt*
  pages enter the prefix cache, so a rewound page always has
  refcount 1 (asserted). Data past the rewound length inside a kept
  page is dead — reads are length-masked and decode appends overwrite
  it (and, in int8 mode, its scale-row entries) position by position.

Tiered page store (preempt-and-swap scheduling):

  The device pool is tier 0 of a two-tier store. `swap_out_slot`
  gathers a slot's page payloads — K/V and, in int8 mode, their scale
  rows, bit-exact — into a host-RAM `SwappedKV` blob (`HostSwapTier`
  keys blobs by uid with byte accounting) and clears the slot; the
  allocator then releases the device pages. `swap_in_slot` restores the
  blob into freshly allocated pages (`BlockAllocator.admit_restored`)
  and reinstates the block-table row and device length, so a preempted
  sequence continues bit-identically to one that was never swapped.
  Restored pages are private (refcount 1, never prefix-registered).

  Two admission modes support the scheduler split
  (`serving/scheduler.py`): `reserve=True` (default) is the historical
  watermark — worst-case pages promised up front, decode can never run
  dry, no preemption. `reserve=False` is *optimistic*: only the pages
  needed now must be free, nothing is promised, and `extend`/`fork_page`
  draw straight from the free list — the engine must keep enough pages
  free (preempting victims when the pool runs dry) before every write
  round. `pin_budget_pages > 0` additionally lets up to that many
  prefix-cache pages survive refcount 0 ("pinned": out of the free
  list, still content-addressable); a later admission revives a pinned
  page at refcount 1, and `reclaim_pinned` evicts oldest-first when the
  pool needs the bytes back.

The Pallas kernels that read this layout through a scalar-prefetched
block table are `kernels/paged_attention.py` (decode) and
`kernels/paged_prefill.py` (chunked prefill).
"""
from __future__ import annotations

import dataclasses
import hashlib
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import paged_attention as paged_k
from repro.serving.quantize import quantize_vec, quantize_vec_int4
from repro.serving.telemetry import NULL_TELEMETRY

Array = jax.Array

TRASH_PAGE = 0  # physical page 0: scribble target for unmapped writes


@dataclasses.dataclass
class PagedCache:
    """Decode-time paged KV state (dense/moe attention families).

    lengths:      (B,) int32           valid tokens per slot
    block_tables: (B, max_pages) int32 physical page per logical page
    k_pages:      (L, P, Hkv, page_size, Dh) shared K pool
    v_pages:      (L, P, Hkv, page_size, Dh) shared V pool
    k_scale:      (L, P, Hkv, 1, page_size) int8/int4 dequant scales
    v_scale:      (L, P, Hkv, 1, page_size) (f32 or bf16; None in fp
                  mode) — the unit axis makes each (page, head) scale row
                  its own (1, page_size) kernel block
    """

    lengths: Array
    block_tables: Array
    k_pages: Array
    v_pages: Array
    k_scale: Optional[Array] = None
    v_scale: Optional[Array] = None

    @property
    def page_size(self) -> int:
        return self.k_pages.shape[3]

    @property
    def quantized(self) -> bool:
        return self.k_scale is not None


jax.tree_util.register_pytree_node(
    PagedCache,
    lambda c: ((c.lengths, c.block_tables, c.k_pages, c.v_pages,
                c.k_scale, c.v_scale), None),
    lambda _, ch: PagedCache(*ch),
)


_SCALE_DTYPES = ("float32", "bfloat16")


def page_kv_bytes(cfg, page_size: int, kv_dtype: str = "model",
                  kv_scale_dtype: str = "float32") -> int:
    """HBM bytes one physical page costs (K + V, all layers, incl. the
    int8/int4 modes' scale rows). The allocator hands out pages by
    *count*; this is the count -> bytes conversion admission byte
    budgets, the benchmarks, and the roofline cost model use.

    The per-vector math lives with the kernel whose DMA it describes
    (`kernels/paged_attention.kv_vector_bytes`): fp pools move
    Dh * itemsize(cdtype) bytes per (token, head) vector, int8 pools
    (Dh + scale) and int4 pools (Dh/2 + scale); the factor 2 is K + V.
    """
    unit = cfg.n_layers * cfg.n_kv_heads * page_size
    return 2 * unit * paged_k.kv_vector_bytes(
        cfg.head_dim, kv_dtype, kv_scale_dtype, payload_dtype=cfg.cdtype)


def init_paged_cache(cfg, batch: int, num_pages: int, page_size: int,
                     max_pages: int, dtype=None, kv_dtype: str = "model",
                     kv_scale_dtype: str = "float32") -> PagedCache:
    """Empty pool + all-trash block tables for `batch` decode slots.

    kv_dtype "model" stores pages in `dtype` (default cfg.cdtype);
    "int8" stores int8 payload pools plus scale-row pools in
    `kv_scale_dtype` ("float32" default; "bfloat16" halves the scale
    overhead to (Dh + 2) B/vector); "int4" packs two values per byte —
    payload pools of last axis Dh/2 — plus the same scale rows
    ((Dh/2 + 2) B/vector with bf16 scales).
    """
    dtype = dtype or cfg.cdtype
    L, Hkv, Dh = cfg.n_layers, cfg.n_kv_heads, cfg.head_dim
    shape = (L, num_pages, Hkv, page_size, Dh)
    lengths = jnp.zeros((batch,), jnp.int32)
    tables = jnp.full((batch, max_pages), TRASH_PAGE, jnp.int32)
    if kv_scale_dtype not in _SCALE_DTYPES:
        raise ValueError(f"unknown kv_scale_dtype {kv_scale_dtype!r}")
    if kv_dtype in ("int8", "int4"):
        sdt = jnp.dtype(kv_scale_dtype)
        if kv_dtype == "int4":
            if Dh % 2:
                raise ValueError("int4 KV pools need an even head_dim")
            shape = shape[:-1] + (Dh // 2,)
        return PagedCache(
            lengths=lengths,
            block_tables=tables,
            k_pages=jnp.zeros(shape, jnp.int8),
            v_pages=jnp.zeros(shape, jnp.int8),
            k_scale=jnp.zeros(shape[:3] + (1, page_size), sdt),
            v_scale=jnp.zeros(shape[:3] + (1, page_size), sdt),
        )
    if kv_dtype != "model":
        raise ValueError(f"unknown kv_dtype {kv_dtype!r}")
    return PagedCache(
        lengths=lengths,
        block_tables=tables,
        k_pages=jnp.zeros(shape, dtype),
        v_pages=jnp.zeros(shape, dtype),
    )


def shard_cache(cache: PagedCache, mesh, rules=None) -> PagedCache:
    """Place a PagedCache on a mesh: payload/scale pools sharded over
    their KV-head axis (the logical "model" axis, via
    `distributed.sharding.paged_pool_pspecs`), lengths and block tables
    replicated on every mesh device.

    Idempotent and cheap when already placed — each leaf is moved only
    if its sharding differs — so the engine also calls this as a safety
    net after host-side pool surgery (swap-in restores), keeping the
    sharding invariant without forking any of those paths.
    """
    if mesh is None:
        return cache
    from jax.sharding import NamedSharding
    from repro.distributed.sharding import paged_pool_pspecs
    specs = paged_pool_pspecs(mesh, quantized=cache.quantized, rules=rules)

    def put(x, spec):
        if x is None:
            return None
        target = NamedSharding(mesh, spec)
        # is_equivalent_to, not ==: jit outputs normalize trailing Nones
        # off the PartitionSpec, which == treats as a different sharding.
        have = getattr(x, "sharding", None)
        if have is not None and have.is_equivalent_to(target, x.ndim):
            return x
        return jax.device_put(x, target)

    return PagedCache(
        lengths=put(cache.lengths, specs["lengths"]),
        block_tables=put(cache.block_tables, specs["block_tables"]),
        k_pages=put(cache.k_pages, specs["pools"]),
        v_pages=put(cache.v_pages, specs["pools"]),
        k_scale=put(cache.k_scale, specs["scales"]),
        v_scale=put(cache.v_scale, specs["scales"]),
    )


def append_kv_pages(k_pages: Array, v_pages: Array, block_tables: Array,
                    lengths: Array, k_new: Array, v_new: Array,
                    k_scale: Array | None = None,
                    v_scale: Array | None = None):
    """Append one token's K/V at each slot's current length (traced).

    k_pages/v_pages: (P, Hkv, page, Dh) one layer's pool;
    k_new/v_new: (B, Hkv, Dh). Slots whose logical page is unmapped hit
    the trash page (block tables default to 0 there).

    With scale pools (k_scale/v_scale (P, Hkv, 1, page), int8/int4 mode)
    the new vectors are amax-quantized here — at write time — and the
    narrow payload plus its scale land in the same (page, offset);
    returns (k_pages, v_pages, k_scale, v_scale). Without, returns the
    2-tuple. A pool whose last axis is half the incoming head_dim is
    int4: the write packs two nibbles per byte.
    """
    page = k_pages.shape[2]
    logical = lengths // page
    phys = jnp.take_along_axis(block_tables, logical[:, None], axis=1)[:, 0]
    off = lengths % page
    if k_scale is not None:
        quant = (quantize_vec_int4
                 if 2 * k_pages.shape[-1] == k_new.shape[-1]
                 else quantize_vec)
        k_q, k_sc = quant(k_new, scale_dtype=k_scale.dtype)
        v_q, v_sc = quant(v_new, scale_dtype=v_scale.dtype)
        k_pages = k_pages.at[phys, :, off].set(k_q)
        v_pages = v_pages.at[phys, :, off].set(v_q)
        k_scale = k_scale.at[phys, :, 0, off].set(k_sc)
        v_scale = v_scale.at[phys, :, 0, off].set(v_sc)
        return k_pages, v_pages, k_scale, v_scale
    k_pages = k_pages.at[phys, :, off].set(k_new.astype(k_pages.dtype))
    v_pages = v_pages.at[phys, :, off].set(v_new.astype(v_pages.dtype))
    return k_pages, v_pages


def write_prompt_pages(cache: PagedCache, slot: int, page_ids: list[int],
                       k_dense: Array, v_dense: Array, length: int
                       ) -> PagedCache:
    """Scatter a slot's prefill KV (L, Hkv, S, Dh) into its pages.

    `page_ids` are the physical pages the allocator handed this slot;
    they must cover ceil(length / page_size) logical pages. fp pools
    only — int8 prompts quantize through `append_chunk_kv_pages`.
    """
    assert cache.k_scale is None, "write_prompt_pages is fp-only"
    L, Hkv, S, Dh = k_dense.shape
    bs = cache.page_size
    n0 = len(page_ids)
    assert n0 * bs >= length, (n0, bs, length)
    pad = n0 * bs - S
    if pad > 0:
        spec = ((0, 0), (0, 0), (0, pad), (0, 0))
        k_dense = jnp.pad(k_dense, spec)
        v_dense = jnp.pad(v_dense, spec)
    else:
        k_dense = k_dense[:, :, :n0 * bs]
        v_dense = v_dense[:, :, :n0 * bs]
    # (L, Hkv, n0, bs, Dh) -> (L, n0, Hkv, bs, Dh): pool page layout.
    ck = jnp.moveaxis(k_dense.reshape(L, Hkv, n0, bs, Dh), 2, 1)
    cv = jnp.moveaxis(v_dense.reshape(L, Hkv, n0, bs, Dh), 2, 1)
    ids = jnp.asarray(page_ids, jnp.int32)
    table_row = jnp.full((cache.block_tables.shape[1],), TRASH_PAGE,
                         jnp.int32).at[:n0].set(ids)
    return PagedCache(
        lengths=cache.lengths.at[slot].set(length),
        block_tables=cache.block_tables.at[slot].set(table_row),
        k_pages=cache.k_pages.at[:, ids].set(ck.astype(cache.k_pages.dtype)),
        v_pages=cache.v_pages.at[:, ids].set(cv.astype(cache.v_pages.dtype)),
    )


def copy_page(cache: PagedCache, src: int, dst: int) -> PagedCache:
    """COW fork: duplicate physical page `src` into `dst` on every layer.

    int8 mode copies the scale rows with the payload — the fork owns
    private scales from the first write, so releasing the donor's page
    (which recycles its scale row) can never corrupt the fork's reads.
    """
    return PagedCache(
        lengths=cache.lengths,
        block_tables=cache.block_tables,
        k_pages=cache.k_pages.at[:, dst].set(cache.k_pages[:, src]),
        v_pages=cache.v_pages.at[:, dst].set(cache.v_pages[:, src]),
        k_scale=(None if cache.k_scale is None
                 else cache.k_scale.at[:, dst].set(cache.k_scale[:, src])),
        v_scale=(None if cache.v_scale is None
                 else cache.v_scale.at[:, dst].set(cache.v_scale[:, src])),
    )


def append_chunk_kv_pages(k_pages: Array, v_pages: Array,
                          block_tables: Array, start: Array,
                          k_new: Array, v_new: Array,
                          k_scale: Array | None = None,
                          v_scale: Array | None = None):
    """Write one prefill chunk's K/V at positions start..start+S-1 (traced).

    k_pages/v_pages: (P, Hkv, page, Dh) one layer's pool; k_new/v_new:
    (B, S, Hkv, Dh) chunk K/V in projection layout; start: (B,) int32
    absolute position of the chunk's first token. Every page the chunk
    touches must already be mapped (and COW-forked out of any sharing)
    in `block_tables` — rows whose table entries are trash scribble into
    the trash page harmlessly, like `append_kv_pages`.

    With scale pools (int8/int4 mode) the chunk is amax-quantized per
    (token, head) vector at write time; payload (nibble-packed when the
    pool's last axis is half the chunk head_dim) and scales land at the
    same (page, offset) and the 4-tuple is returned.
    """
    page = k_pages.shape[2]
    S = k_new.shape[1]
    pos = start[:, None] + jnp.arange(S)[None, :]            # (B, S)
    logical = pos // page
    phys = jnp.take_along_axis(block_tables, logical, axis=1)
    off = pos % page
    # Advanced indices (B, S) around the Hkv slice: result dims lead, so
    # the update payload is chunk-major (B, S, Hkv, Dh) — no transpose.
    if k_scale is not None:
        quant = (quantize_vec_int4
                 if 2 * k_pages.shape[-1] == k_new.shape[-1]
                 else quantize_vec)
        k_q, k_sc = quant(k_new, scale_dtype=k_scale.dtype)
        v_q, v_sc = quant(v_new, scale_dtype=v_scale.dtype)
        k_pages = k_pages.at[phys, :, off].set(k_q)
        v_pages = v_pages.at[phys, :, off].set(v_q)
        k_scale = k_scale.at[phys, :, 0, off].set(k_sc)
        v_scale = v_scale.at[phys, :, 0, off].set(v_sc)
        return k_pages, v_pages, k_scale, v_scale
    k_pages = k_pages.at[phys, :, off].set(k_new.astype(k_pages.dtype))
    v_pages = v_pages.at[phys, :, off].set(v_new.astype(v_pages.dtype))
    return k_pages, v_pages


def clear_slot(cache: PagedCache, slot: int) -> PagedCache:
    """Point a released slot back at the trash page."""
    return PagedCache(
        lengths=cache.lengths.at[slot].set(0),
        block_tables=cache.block_tables.at[slot].set(TRASH_PAGE),
        k_pages=cache.k_pages,
        v_pages=cache.v_pages,
        k_scale=cache.k_scale,
        v_scale=cache.v_scale,
    )


def rewind_slot(cache: PagedCache, slot: int, new_len: int,
                keep_pages: int) -> PagedCache:
    """Roll back a slot after speculative rejection: device length back
    to `new_len`, table entries past the first `keep_pages` re-trashed
    (the allocator freed those physical pages via `rewind`). The pools
    are untouched — rejected K/V (and, in int8 mode, its scale-row
    entries) past `new_len` inside a kept page is dead data: reads are
    length-masked and the next appends at positions new_len.. overwrite
    payload and scales alike, so the kept prefix's scale rows survive
    rollback bit-for-bit."""
    n = cache.block_tables.shape[1]
    keep = jnp.arange(n) < keep_pages
    row = jnp.where(keep, cache.block_tables[slot], TRASH_PAGE)
    return PagedCache(
        lengths=cache.lengths.at[slot].set(new_len),
        block_tables=cache.block_tables.at[slot].set(row),
        k_pages=cache.k_pages,
        v_pages=cache.v_pages,
        k_scale=cache.k_scale,
        v_scale=cache.v_scale,
    )


@dataclasses.dataclass
class SwappedKV:
    """One preempted slot's KV payload, gathered to host RAM.

    The swap tier's unit: page-major copies of the device pools
    restricted to the slot's pages — K/V payloads and, in int8 mode,
    their scale rows, bit-exact — so a restored slot continues exactly
    as if it had never left the device.

    n_tokens: valid tokens the pages held at swap-out
    k, v:     (L, n_pages, Hkv, page_size, Dh) numpy, pool dtype
    k_scale, v_scale: (L, n_pages, Hkv, 1, page_size) or None (fp mode)
    """

    n_tokens: int
    k: np.ndarray
    v: np.ndarray
    k_scale: Optional[np.ndarray] = None
    v_scale: Optional[np.ndarray] = None

    @property
    def n_pages(self) -> int:
        return self.k.shape[1]

    @property
    def nbytes(self) -> int:
        n = self.k.nbytes + self.v.nbytes
        if self.k_scale is not None:
            n += self.k_scale.nbytes + self.v_scale.nbytes
        return n


class HostSwapTier:
    """Host-RAM tier of the page store: swapped-out slots' `SwappedKV`
    blobs keyed by request uid, with byte accounting for gauges."""

    def __init__(self):
        self._blobs: dict[int, SwappedKV] = {}
        self.bytes_peak = 0

    def __len__(self) -> int:
        return len(self._blobs)

    @property
    def bytes_used(self) -> int:
        return sum(b.nbytes for b in self._blobs.values())

    def put(self, uid: int, blob: SwappedKV) -> None:
        assert uid not in self._blobs, f"uid {uid} already swapped"
        self._blobs[uid] = blob
        self.bytes_peak = max(self.bytes_peak, self.bytes_used)

    def pop(self, uid: int) -> SwappedKV:
        return self._blobs.pop(uid)


def swap_out_slot(cache: PagedCache, slot: int, page_ids: list[int],
                  n_tokens: int) -> tuple[PagedCache, SwappedKV]:
    """Gather `page_ids`' payloads (and scale rows) to host and clear
    the slot: returns (cache', blob). The caller releases the device
    pages afterwards — the blob is an exact bit-copy, so `swap_in_slot`
    into any fresh pages resumes the sequence bit-identically. Host
    transfer + full-pool gather: this is the slow tier, by design."""
    ids = np.asarray(page_ids, np.int32)
    if cache.quantized:
        k, v, ks, vs = jax.device_get((
            cache.k_pages[:, ids], cache.v_pages[:, ids],
            cache.k_scale[:, ids], cache.v_scale[:, ids]))
    else:
        k, v = jax.device_get((cache.k_pages[:, ids], cache.v_pages[:, ids]))
        ks = vs = None
    blob = SwappedKV(n_tokens=n_tokens, k=np.asarray(k), v=np.asarray(v),
                     k_scale=None if ks is None else np.asarray(ks),
                     v_scale=None if vs is None else np.asarray(vs))
    return clear_slot(cache, slot), blob


def swap_in_slot(cache: PagedCache, slot: int, page_ids: list[int],
                 blob: SwappedKV) -> PagedCache:
    """Restore a swapped slot: scatter the blob's payloads into freshly
    allocated `page_ids` and reinstate the block-table row and device
    length. Inverse of `swap_out_slot` up to physical page numbering."""
    assert len(page_ids) == blob.n_pages, (len(page_ids), blob.n_pages)
    ids = jnp.asarray(page_ids, jnp.int32)
    row = jnp.full((cache.block_tables.shape[1],), TRASH_PAGE,
                   jnp.int32).at[:len(page_ids)].set(ids)
    return PagedCache(
        lengths=cache.lengths.at[slot].set(blob.n_tokens),
        block_tables=cache.block_tables.at[slot].set(row),
        k_pages=cache.k_pages.at[:, ids].set(
            jnp.asarray(blob.k, cache.k_pages.dtype)),
        v_pages=cache.v_pages.at[:, ids].set(
            jnp.asarray(blob.v, cache.v_pages.dtype)),
        k_scale=(None if cache.k_scale is None
                 else cache.k_scale.at[:, ids].set(
                     jnp.asarray(blob.k_scale, cache.k_scale.dtype))),
        v_scale=(None if cache.v_scale is None
                 else cache.v_scale.at[:, ids].set(
                     jnp.asarray(blob.v_scale, cache.v_scale.dtype))),
    )


_PREFIX_ROOT = b"salpim-prefix-root"


def _chain_key(prev: bytes, chunk: np.ndarray) -> bytes:
    """Hash-chain key for one page-aligned token chunk: folds the parent
    key in, so equal keys imply equal *prefixes*, not just equal chunks."""
    h = hashlib.sha256(prev)
    h.update(np.ascontiguousarray(chunk, np.int64).tobytes())
    return h.digest()


class BlockAllocator:
    """Free-list page allocator with watermark (reserve-ahead) admission,
    per-page refcounts, and content-addressed prefix sharing.

    Physical page 0 is never handed out (trash page). `admit` /
    `admit_tokens` reserve a sequence's worst-case page count up front
    and allocate only the prompt's pages; `extend` draws one page from
    the reservation at a decode-step boundary; `release` returns
    everything. Because admission is gated on `free - reserved`, an
    admitted sequence can always extend — no preemption, no mid-decode
    OOM.

    With `prefix_sharing=True`, `admit_tokens` first walks the prefix
    cache (hash chain over full page-sized token chunks) and maps the
    longest cached run of pages instead of allocating them: those pages
    get refcount += 1 and the watermark only reserves the worst case
    net of shared pages. A shared page must be `fork_page`d (COW) before
    any write lands in it.

    `telemetry` (serving/telemetry.py, optional) receives page-economy
    counters: pages allocated/freed/rewound, COW forks, prefix-cache
    page hits/misses (full prompt pages only — the unit the cache
    shares at), and watermark refusals. All no-ops when the telemetry
    is disabled or absent.
    """

    def __init__(self, num_pages: int, page_size: int,
                 prefix_sharing: bool = False, telemetry=None,
                 pin_budget_pages: int = 0):
        assert num_pages >= 2, "need at least trash + 1 usable page"
        assert page_size >= 1
        self.num_pages = num_pages
        self.page_size = page_size
        self.prefix_sharing = prefix_sharing
        self.pin_budget_pages = pin_budget_pages
        self._tel = telemetry if telemetry is not None else NULL_TELEMETRY
        self._free = list(range(num_pages - 1, TRASH_PAGE, -1))
        self._reserved = 0
        self._pages: dict[int, list[int]] = {}
        self._quota: dict[int, int] = {}     # worst-case *new* pages per uid
        self._owned: dict[int, int] = {}     # pages uid drew from the free list
        self._reserve_mode: dict[int, bool] = {}   # uid -> watermark-reserved?
        self._ref: dict[int, int] = {}       # physical page -> refcount
        self._prefix_cache: dict[bytes, int] = {}  # chain key -> phys page
        self._page_key: dict[int, bytes] = {}      # phys page -> chain key
        self._pinned: dict[int, None] = {}   # refcount-0 cached pages (FIFO)

    # -- accounting ---------------------------------------------------------
    @property
    def free_pages(self) -> int:
        return len(self._free)

    @property
    def available_pages(self) -> int:
        """Pages not yet promised to any admitted sequence."""
        return len(self._free) - self._reserved

    @property
    def used_pages(self) -> int:
        return (self.num_pages - 1) - len(self._free)

    def pages_for(self, tokens: int) -> int:
        return -(-max(tokens, 1) // self.page_size)

    @staticmethod
    def worst_case_tokens(prompt_tokens: int, max_new_tokens: int) -> int:
        """Cache positions a request can ever occupy: the prompt plus one
        KV append per generated token except the last — the slot is
        released at the sampling step, before that token's decode."""
        return prompt_tokens + max(max_new_tokens, 1) - 1

    def pages_of(self, uid: int) -> list[int]:
        return list(self._pages[uid])

    def refcount(self, page: int) -> int:
        return self._ref.get(page, 0)

    @property
    def cached_pages(self) -> int:
        """Pages currently addressable through the prefix cache."""
        return len(self._prefix_cache)

    @property
    def pinned_pages(self) -> int:
        """Prefix-cache pages held alive at refcount 0 (out of the free
        list, still content-addressable)."""
        return len(self._pinned)

    # -- internal helpers ---------------------------------------------------
    def _alloc(self) -> int:
        page = self._free.pop()
        self._ref[page] = 1
        self._tel.count("pool.pages_allocated")
        return page

    def _decref(self, page: int) -> None:
        self._ref[page] -= 1
        if self._ref[page] == 0:
            del self._ref[page]
            if (page in self._page_key
                    and len(self._pinned) < self.pin_budget_pages):
                # Pin: the page keeps its bytes and prefix-cache entry at
                # refcount 0 — a future admission hit revives it.
                self._pinned[page] = None
                self._tel.count("sched.pin")
                return
            key = self._page_key.pop(page, None)
            if key is not None:
                self._prefix_cache.pop(key, None)
            self._free.append(page)
            self._tel.count("pool.pages_freed")

    def reclaim_pinned(self, n: int, protect=()) -> int:
        """Evict up to `n` pinned pages (oldest pin first, skipping
        `protect`) back to the free list, dropping their prefix-cache
        entries. Returns the number actually reclaimed."""
        freed = 0
        for page in list(self._pinned):
            if freed >= n:
                break
            if page in protect:
                continue
            del self._pinned[page]
            key = self._page_key.pop(page, None)
            if key is not None:
                self._prefix_cache.pop(key, None)
            self._free.append(page)
            self._tel.count("pool.pages_freed")
            self._tel.count("sched.pin_evict")
            freed += 1
        return freed

    def _walk_hits(self, tokens) -> tuple[list[bytes], list[int]]:
        """Hash-chain walk over `tokens`' full pages: (chain keys, the
        longest cached run of pages). Pure lookup, no refcount changes."""
        ps = self.page_size
        n_full = int(tokens.shape[0]) // ps
        keys: list[bytes] = []
        if self.prefix_sharing:
            key = _PREFIX_ROOT
            for i in range(n_full):
                key = _chain_key(key, tokens[i * ps:(i + 1) * ps])
                keys.append(key)
        hits: list[int] = []
        for key in keys:
            page = self._prefix_cache.get(key)
            if page is None:
                break
            hits.append(page)
        return keys, hits

    def _register(self, key: bytes, page: int) -> None:
        if key not in self._prefix_cache and page not in self._page_key:
            self._prefix_cache[key] = page
            self._page_key[page] = key

    # -- lifecycle ----------------------------------------------------------
    def can_admit(self, prompt_tokens: int, max_new_tokens: int) -> bool:
        worst = self.pages_for(
            self.worst_case_tokens(prompt_tokens, max_new_tokens))
        return self.available_pages >= worst

    def admit(self, uid: int, prompt_tokens: int,
              max_new_tokens: int) -> Optional[list[int]]:
        """Reserve worst case, allocate prompt pages. None if over watermark.

        Content-free form: no prefix-cache lookup or registration. Use
        `admit_tokens` to share cached prefix pages.
        """
        assert uid not in self._pages, f"uid {uid} already admitted"
        worst = self.pages_for(
            self.worst_case_tokens(prompt_tokens, max_new_tokens))
        if self.available_pages < worst:
            self._tel.count("pool.watermark_refusals")
            return None
        n0 = self.pages_for(prompt_tokens)
        pages = [self._alloc() for _ in range(n0)]
        self._pages[uid] = pages
        self._quota[uid] = worst
        self._owned[uid] = n0
        self._reserve_mode[uid] = True
        self._reserved += worst - n0
        return list(pages)

    def admit_tokens(self, uid: int, tokens, max_new_tokens: int,
                     reserve: bool = True
                     ) -> Optional[tuple[list[int], int]]:
        """Admit with prefix reuse: returns (prompt pages, shared tokens).

        Walks the hash chain over `tokens`' full page-sized chunks; the
        longest cached run is mapped into this sequence (refcount += 1,
        reviving pinned pages), the rest allocated fresh, and the fresh
        *full* pages registered for future admissions. With
        `reserve=True` (watermark mode) the worst case net of shared
        pages is reserved up front — plus one fork page when the prompt
        is fully covered, since the engine then recomputes the last
        prompt token and its KV write must COW the final shared page.
        With `reserve=False` (optimistic mode) only the pages written
        during prefill must be free now; later extends draw from the
        live free list, so the caller must be prepared to preempt.
        Pinned pages not hit by this prompt are reclaimed automatically
        to cover a shortage. None when the pool cannot cover the
        request."""
        assert uid not in self._pages, f"uid {uid} already admitted"
        tokens = np.asarray(tokens)
        n_tok = int(tokens.shape[0])
        ps = self.page_size
        n_full = n_tok // ps
        keys, hits = self._walk_hits(tokens)
        n_shared = len(hits)
        shared_tokens = n_shared * ps
        total = self.pages_for(self.worst_case_tokens(n_tok, max_new_tokens))
        fork = shared_tokens >= n_tok        # fully covered prompt
        worst_new = total - n_shared + (1 if fork else 0)
        n0 = self.pages_for(n_tok)
        need_now = worst_new if reserve else (n0 - n_shared) + (1 if fork else 0)
        shortage = (need_now - self.available_pages if reserve
                    else need_now - len(self._free))
        if shortage > 0:
            # Pinned pages this prompt does not hit are reclaimable.
            self.reclaim_pinned(shortage, protect=frozenset(hits))
            shortage = (need_now - self.available_pages if reserve
                        else need_now - len(self._free))
        if shortage > 0:
            self._tel.count("pool.watermark_refusals" if reserve
                            else "pool.admit_refusals")
            return None
        # Hit/miss accounting over *full* prompt pages — the unit the
        # prefix cache shares at (partial tail pages are never cached).
        self._tel.count("prefix_cache.page_hits", n_shared)
        self._tel.count("prefix_cache.page_misses", n_full - n_shared)
        fresh = [self._alloc() for _ in range(n0 - n_shared)]
        for p in hits:
            if p in self._pinned:        # revive: back to refcount 1
                del self._pinned[p]
                self._ref[p] = 1
                self._tel.count("sched.pin_hits")
            else:
                self._ref[p] += 1
        pages = hits + fresh
        for i in range(n_shared, len(keys)):
            self._register(keys[i], pages[i])
        self._pages[uid] = pages
        self._quota[uid] = worst_new
        self._owned[uid] = len(fresh)
        self._reserve_mode[uid] = reserve
        if reserve:
            self._reserved += worst_new - len(fresh)
        return list(pages), shared_tokens

    def admission_probe(self, tokens, max_new_tokens: int,
                        reserve: bool = True) -> tuple[int, int]:
        """Non-mutating admission check: (need_now, reclaimable_pins).

        `need_now` is exactly the free-list draw `admit_tokens` would
        make for this prompt right now (hit-aware: cached prefix pages
        cost nothing); `reclaimable_pins` is how many pinned pages a
        shortage could evict for it — pins the prompt *hits* excluded,
        since those revive in place and are protected from reclaim.
        Preemptive schedulers use the pair to decide whether a candidate
        can ever fit before evicting victims for it (futile evictions
        would livelock: the same infeasible candidate re-evicts its
        victims every step)."""
        tokens = np.asarray(tokens)
        n_tok = int(tokens.shape[0])
        hits = self._walk_hits(tokens)[1]
        n_shared = len(hits)
        fork = n_shared * self.page_size >= n_tok
        if reserve:
            need = (self.pages_for(self.worst_case_tokens(
                n_tok, max_new_tokens)) - n_shared + (1 if fork else 0))
        else:
            need = (self.pages_for(n_tok) - n_shared) + (1 if fork else 0)
        hit_set = frozenset(hits)
        reclaimable = sum(1 for p in self._pinned if p not in hit_set)
        return need, reclaimable

    def needs_extend(self, uid: int, next_token_pos: int) -> bool:
        """True when the write at `next_token_pos` falls off mapped pages."""
        return self.pages_for(next_token_pos + 1) > len(self._pages[uid])

    def extend(self, uid: int) -> int:
        """One more page for uid (decode-step boundary): drawn from its
        reservation in watermark mode, straight from the free list in
        optimistic mode (the engine must have ensured capacity)."""
        pages = self._pages[uid]
        assert self._owned[uid] < self._quota[uid], "quota exhausted"
        if self._reserve_mode.get(uid, True):
            self._reserved -= 1
        else:
            assert self._free, "optimistic extend on a dry pool"
        self._owned[uid] += 1
        page = self._alloc()
        pages.append(page)
        return page

    def fork_page(self, uid: int, logical_idx: int) -> tuple[int, int]:
        """COW fork: move uid's `logical_idx` page to a private physical
        page (from its reservation in watermark mode, the free list in
        optimistic mode). Returns (old, new); the caller must copy the
        device page (`copy_page`) and repoint the block table before
        writing."""
        pages = self._pages[uid]
        old = pages[logical_idx]
        assert self._ref[old] > 1, f"fork of unshared page {old}"
        assert self._owned[uid] < self._quota[uid], "quota exhausted"
        if self._reserve_mode.get(uid, True):
            self._reserved -= 1
        else:
            assert self._free, "optimistic fork on a dry pool"
        self._owned[uid] += 1
        new = self._alloc()
        self._decref(old)
        pages[logical_idx] = new
        self._tel.count("pool.cow_forks")
        return old, new

    def rewind(self, uid: int, n_tokens: int) -> list[int]:
        """Speculative rollback: unmap uid's pages past those needed to
        hold `n_tokens`, returning each to the free list *and* to uid's
        reservation — the exact inverse of `extend`, so the watermark
        (`available_pages`) is unchanged by a draft-verify round
        regardless of how many drafts were rejected.

        Only decode-frontier pages are ever rewound, and those are never
        shared (the prefix cache registers full *prompt* pages only) nor
        registered — both asserted, because rewinding a shared or cached
        page would free KV another sequence still reads. Returns the
        dropped physical pages (for tests; the caller re-trashes the
        device block-table row via `rewind_slot`)."""
        pages = self._pages[uid]
        keep = self.pages_for(n_tokens)
        reserved = self._reserve_mode.get(uid, True)
        dropped: list[int] = []
        while len(pages) > keep:
            p = pages.pop()
            assert self._ref[p] == 1, f"rewind of shared page {p}"
            assert p not in self._page_key, f"rewind of cached page {p}"
            del self._ref[p]
            self._free.append(p)
            self._owned[uid] -= 1
            if reserved:
                self._reserved += 1
            dropped.append(p)
        self._tel.count("pool.pages_rewound", len(dropped))
        return dropped

    def admit_restored(self, uid: int, n_pages: int, worst_pages: int,
                       reserve: bool = True) -> Optional[list[int]]:
        """Re-admit a swapped-out sequence: allocate `n_pages` fresh
        pages (the caller restores their payloads from the host tier via
        `swap_in_slot`) under a `worst_pages` lifetime quota. No
        prefix-cache lookup or registration — restored pages are
        private. Reclaims pins to cover a shortage; None when the pool
        cannot cover the request."""
        assert uid not in self._pages, f"uid {uid} already admitted"
        assert n_pages <= worst_pages, (n_pages, worst_pages)
        need_now = worst_pages if reserve else n_pages
        shortage = (need_now - self.available_pages if reserve
                    else need_now - len(self._free))
        if shortage > 0:
            self.reclaim_pinned(shortage)
            shortage = (need_now - self.available_pages if reserve
                        else need_now - len(self._free))
        if shortage > 0:
            self._tel.count("pool.watermark_refusals" if reserve
                            else "pool.admit_refusals")
            return None
        pages = [self._alloc() for _ in range(n_pages)]
        self._pages[uid] = pages
        self._quota[uid] = worst_pages
        self._owned[uid] = n_pages
        self._reserve_mode[uid] = reserve
        if reserve:
            self._reserved += worst_pages - n_pages
        return list(pages)

    def unregister(self, uid: int, from_logical: int = 0) -> None:
        """Drop prefix-cache entries held by uid's pages at logical index
        >= `from_logical`. A preempt-aborted mid-prefill sequence calls
        this before release: pages it registered at admission but never
        finished writing must not be served from the cache (or pinned)
        with incomplete payloads."""
        for p in self._pages[uid][from_logical:]:
            key = self._page_key.pop(p, None)
            if key is not None:
                self._prefix_cache.pop(key, None)

    def release(self, uid: int) -> None:
        pages = self._pages.pop(uid)
        quota, owned = self._quota.pop(uid), self._owned.pop(uid)
        if self._reserve_mode.pop(uid, True):
            self._reserved -= quota - owned
        for p in pages:
            self._decref(p)
