"""Shared cases for the paged decode and chunk walks' tests: row lengths
and chunks at every edge of their block walks, both benchmark cells'
head shapes, and pools whose pages outside each row's walk hold NaN."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

PAGE, N_PAGES = 16, 16
# With 16-token pages the walk reads 8-page (128-position) blocks. One
# batch holds an empty slot (cache length 0, so kernel length 1), a row
# ending on a page boundary, one a position short of a full block, and
# a full table.
LENGTHS = [1, PAGE, 8 * PAGE - 1, N_PAGES * PAGE]
SHAPES = {"mha16x64": (16, 16, 64), "gqa12:2x128": (12, 2, 128)}
WINDOW = 40      # starts mid-page in every row longer than it


def pools(H, Hkv, D, key=jax.random.PRNGKey(0)):
    """(q, k_pages, v_pages, tables, lengths): f32 pools behind a
    shuffled block table, one row per LENGTHS entry."""
    B = len(LENGTHS)
    P = 1 + B * N_PAGES
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (B, H, D), jnp.float32)
    kp = jax.random.normal(ks[1], (P, Hkv, PAGE, D), jnp.float32)
    vp = jax.random.normal(ks[2], (P, Hkv, PAGE, D), jnp.float32)
    tables = np.random.RandomState(0).permutation(np.arange(1, P))
    return (q, kp, vp, jnp.asarray(tables.reshape(B, N_PAGES), jnp.int32),
            jnp.asarray(LENGTHS, jnp.int32))


def poison(pool, tables, window=None, lengths=LENGTHS, starts=None):
    """`pool` (pages first) with NaN in every page a row's table maps
    outside the pages its walk reads: past the page holding its last
    position, and before the page holding its first query's first
    in-window key. A decode row's one query sits at length - 1; a
    chunk's first at its start."""
    out = np.array(pool)
    if starts is None:
        starts = [n - 1 for n in lengths]
    for row, n, s in zip(np.asarray(tables), lengths, starts):
        first = max(s - window + 1, 0) // PAGE if window is not None else 0
        last = -(-n // PAGE)
        for i, page in enumerate(row):
            if not first <= i < last:
                out[page] = np.nan
    return jnp.asarray(out)


# Chunks, one per row of a 64-entry table (the chunk walk's multi-page
# blocks, fewer than the table): a first chunk, one at start 512 (34 of
# 64 entries live), one that starts and ends mid-page, and a one-token
# chunk (speculative verify, the recomputed last prompt token). Each
# case is (Sq, starts); lengths are starts + Sq.
CHUNK_PAGES = 64
CHUNKS = {
    "first": (24, [0, 0]),
    "wide-table": (32, [512, 480]),
    "mid-page": (24, [21, 517]),
    "one-token": (1, [40, 1000]),
}
CHUNK_WINDOW = 40    # lo > 0 at start 512; mid-page in every long row


def chunk_pools(H, Hkv, D, Sq, starts, key=jax.random.PRNGKey(1)):
    """(q, k_pages, v_pages, tables, lengths, starts): f32 pools behind a
    shuffled CHUNK_PAGES-entry table, a chunk of Sq queries per row."""
    B = len(starts)
    P = 1 + B * CHUNK_PAGES
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (B, Sq, H, D), jnp.float32)
    kp = jax.random.normal(ks[1], (P, Hkv, PAGE, D), jnp.float32)
    vp = jax.random.normal(ks[2], (P, Hkv, PAGE, D), jnp.float32)
    tables = np.random.RandomState(1).permutation(np.arange(1, P))
    starts = np.asarray(starts, np.int32)
    return (q, kp, vp,
            jnp.asarray(tables.reshape(B, CHUNK_PAGES), jnp.int32),
            jnp.asarray(starts + Sq), jnp.asarray(starts))
