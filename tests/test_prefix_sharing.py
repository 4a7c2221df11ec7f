"""Copy-on-write prefix sharing for the paged KV cache: allocator
refcounts + hash-chain prefix cache, COW page forks, watermark accounting
net of shared pages, and bit-identical greedy serving with sharing on vs
off. (The suffix-prefill device ops this file once covered were subsumed
by chunked paged prefill — see tests/test_chunked_prefill.py.)"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.core.salpim import SalPimConfig, SalPimEngine
from repro.models import api
from repro.serving import kvcache as kv
from repro.serving.engine import EngineConfig, GenConfig, ServingEngine

ENGINE = SalPimEngine.create(SalPimConfig())
KEY = jax.random.PRNGKey(0)


def _setup(arch="gpt2_medium"):
    cfg = get_config(arch, smoke=True)
    params = api.init_params(KEY, cfg)
    return cfg, params


# ---------------------------------------------------------------------------
# Allocator: refcounts, prefix cache, fork
# ---------------------------------------------------------------------------

def test_admit_tokens_shares_full_prefix_pages():
    a = kv.BlockAllocator(num_pages=16, page_size=4, prefix_sharing=True)
    toks = np.arange(100, 110)                      # 10 tokens, 2 full pages
    pages1, shared1 = a.admit_tokens(1, toks, max_new_tokens=4)
    assert shared1 == 0 and len(pages1) == 3
    assert a.cached_pages == 2                      # full pages registered
    # Same prefix, different tail: first two pages shared.
    toks2 = np.concatenate([toks[:8], [7, 8, 9]])
    pages2, shared2 = a.admit_tokens(2, toks2, max_new_tokens=4)
    assert shared2 == 8
    assert pages2[:2] == pages1[:2]                 # physical sharing
    assert pages2[2] != pages1[2]
    assert a.refcount(pages1[0]) == 2 and a.refcount(pages1[1]) == 2
    assert a.refcount(pages1[2]) == 1               # partial page is private
    a.release(1)
    assert a.refcount(pages1[0]) == 1               # uid 2 still holds them
    a.release(2)
    assert a.refcount(pages1[0]) == 0
    assert a.used_pages == 0 and a.cached_pages == 0


def test_prefix_cache_is_a_chain_not_per_chunk():
    """Chunk keys fold in the parent key: an identical *chunk* after a
    different first page must not hit the cache."""
    a = kv.BlockAllocator(num_pages=16, page_size=4, prefix_sharing=True)
    common = np.asarray([5, 6, 7, 8])
    a.admit_tokens(1, np.concatenate([[1, 1, 1, 1], common]), 4)
    pages2, shared2 = a.admit_tokens(
        2, np.concatenate([[2, 2, 2, 2], common]), 4)
    assert shared2 == 0                             # page 2 content matches,
    assert a.refcount(pages2[0]) == 1               # but the prefix differs


def test_fork_page_moves_owner_to_private_copy():
    a = kv.BlockAllocator(num_pages=16, page_size=4, prefix_sharing=True)
    toks = np.arange(50, 58)                        # 8 tokens, 2 full pages
    pages1, _ = a.admit_tokens(1, toks, max_new_tokens=4)
    pages2, shared2 = a.admit_tokens(2, toks.copy(), max_new_tokens=4)
    assert shared2 == 8 and pages2 == pages1
    old, new = a.fork_page(2, 1)
    assert old == pages1[1] and new not in pages1
    assert a.pages_of(2) == [pages1[0], new]
    assert a.refcount(old) == 1 and a.refcount(new) == 1
    assert a.refcount(pages1[0]) == 2               # page 0 still shared
    a.release(1)
    a.release(2)
    assert a.used_pages == 0 and a.cached_pages == 0


def test_watermark_reserves_net_of_shared_pages():
    """A request that only fits because its prefix is shared must be
    admitted: worst case is charged net of shared pages."""
    a = kv.BlockAllocator(num_pages=7, page_size=4, prefix_sharing=True)
    toks = np.arange(30, 42)                        # 12 tokens, 3 full pages
    # uid 1: worst = ceil((12+5-1)/4) = 4 pages -> 2 usable left.
    assert a.admit_tokens(1, toks, max_new_tokens=5) is not None
    assert a.available_pages == 2
    # Same worst case without sharing would need 4 pages > 2 available...
    assert not a.can_admit(prompt_tokens=12, max_new_tokens=5)
    # ...but 3 of them are shared, so only 1 new page is reserved.
    res = a.admit_tokens(2, toks.copy(), max_new_tokens=4)
    assert res is not None
    pages2, shared2 = res
    assert shared2 == 12
    assert a.available_pages == 2 - 2   # fork page + 1 decode page reserved
    a.release(1)
    a.release(2)
    assert a.available_pages == 6


def test_fully_covered_prompt_reserves_fork_page():
    """Full-cover admission needs one extra physical page (the COW fork
    for the recomputed last token); at exactly that margin admission
    must succeed, below it must fail."""
    a = kv.BlockAllocator(num_pages=4, page_size=4, prefix_sharing=True)
    toks = np.arange(10, 18)                        # 2 full pages
    assert a.admit_tokens(1, toks, max_new_tokens=1) is not None
    assert a.available_pages == 1
    # uid 2 shares both pages, worst = 2 - 2 + 1 (fork) = 1 page: fits.
    res = a.admit_tokens(2, toks.copy(), max_new_tokens=1)
    assert res is not None and res[1] == 8
    assert a.available_pages == 0
    # uid 3 would also need a fork page; pool is exhausted.
    assert a.admit_tokens(3, toks.copy(), max_new_tokens=1) is None


# ---------------------------------------------------------------------------
# Device ops: copy_page
# ---------------------------------------------------------------------------

def test_copy_page_duplicates_all_layers():
    cfg, _ = _setup()
    cache = kv.init_paged_cache(cfg, batch=1, num_pages=5, page_size=4,
                                max_pages=4)
    filled = jax.random.normal(KEY, cache.k_pages[:, 1].shape)
    cache = kv.PagedCache(cache.lengths, cache.block_tables,
                          cache.k_pages.at[:, 1].set(filled),
                          cache.v_pages.at[:, 1].set(2 * filled))
    out = kv.copy_page(cache, 1, 3)
    np.testing.assert_allclose(np.asarray(out.k_pages[:, 3]),
                               np.asarray(filled), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(out.v_pages[:, 3]),
                               np.asarray(2 * filled), rtol=1e-6)
    assert float(jnp.abs(out.k_pages[:, 2]).sum()) == 0.0


# ---------------------------------------------------------------------------
# Serving engine end-to-end
# ---------------------------------------------------------------------------

def _drain_outputs(params, cfg, prompts, new_tokens, *, sharing, slots=2,
                   page_size=4, max_len=32):
    gen = GenConfig(temperature=0.0, stop_on_eos=False)
    eng = ServingEngine(params, cfg, ENGINE, EngineConfig(
        slots=slots, max_len=max_len, gen=gen, page_size=page_size,
        prefix_sharing=sharing))
    uids = [eng.submit(p.copy(), max_new_tokens=n)
            for p, n in zip(prompts, new_tokens)]
    done = eng.run(max_steps=400)
    assert sorted(r.uid for r in done) == sorted(uids)
    by = {r.uid: r for r in done}
    return [by[u].generated for u in uids], eng


def test_shared_prefix_serving_bit_identical_and_saves_prefill():
    """Greedy outputs with prefix sharing on == off, with strictly fewer
    prefilled tokens and a lower page high-water mark."""
    cfg, params = _setup()
    prefix = np.asarray(jax.random.randint(KEY, (8,), 2, cfg.vocab))
    prompts = [np.concatenate([prefix, t]) for t in
               ([11, 12, 13], [21], [31, 32])]
    new = [6, 8, 5]
    out_off, eng_off = _drain_outputs(params, cfg, prompts, new,
                                      sharing=False)
    out_on, eng_on = _drain_outputs(params, cfg, prompts, new, sharing=True)
    assert out_on == out_off
    assert eng_on.prefill_tokens < eng_off.prefill_tokens
    assert eng_on.prefill_tokens_saved > 0
    assert eng_off.prefill_tokens_saved == 0
    assert eng_on.peak_pages < eng_off.peak_pages
    assert eng_on.allocator.used_pages == 0


def test_cow_fork_no_cross_contamination():
    """A fully-covered identical prompt triggers the admit-time COW fork;
    the donor's pages must stay intact (its continuation unchanged) and
    the forked request must produce the reference output. Requests then
    diverge down their own suffix pages with no cross-talk."""
    cfg, params = _setup()
    prompt = np.asarray(jax.random.randint(KEY, (8,), 2, cfg.vocab))
    # Reference: each request alone, sharing off.
    ref_a, _ = _drain_outputs(params, cfg, [prompt], [12], sharing=False,
                              slots=1)
    ref_b, _ = _drain_outputs(params, cfg, [prompt], [3], sharing=False,
                              slots=1)
    # Together with sharing: B's prompt (page-aligned, identical) is fully
    # covered while A still holds the pages -> fork of the last page.
    outs, eng = _drain_outputs(params, cfg, [prompt, prompt], [12, 3],
                               sharing=True)
    assert outs[0] == ref_a[0]
    assert outs[1] == ref_b[0]
    assert eng.prefill_tokens_saved == 7    # 8 shared, last token recomputed
    assert eng.allocator.used_pages == 0    # refcounts back to zero


def test_decode_boundary_cow_fork():
    """If a decode append would land in a still-shared page, the engine
    must fork it first. Unreachable through normal admission (shared
    pages are always full), so force the state: hand the decode slot a
    block table pointing at a refcount-2 page mid-fill."""
    cfg, params = _setup()
    gen = GenConfig(temperature=0.0, stop_on_eos=False)
    eng = ServingEngine(params, cfg, ENGINE, EngineConfig(
        slots=1, max_len=32, gen=gen, page_size=4, prefix_sharing=True))
    prompt = np.asarray(jax.random.randint(KEY, (6,), 2, cfg.vocab))
    eng.submit(prompt, max_new_tokens=4)
    eng.step()                              # admit + first decode
    req = eng.active[0]
    # Simulate a shared partial page: bump the refcount of the page the
    # next append will hit.
    pos = int(eng._host_len[0])
    page = eng.allocator.pages_of(req.uid)[pos // 4]
    eng.allocator._ref[page] += 1
    eng.allocator._quota[req.uid] += 1  # a real sharer would have reserved
    eng.allocator._reserved += 1        # the fork page at its admission
    before = np.asarray(eng.cache.k_pages[:, page]).copy()
    eng.step()                              # decode must fork, not write
    assert eng.allocator.pages_of(req.uid)[pos // 4] != page
    np.testing.assert_array_equal(np.asarray(eng.cache.k_pages[:, page]),
                                  before)   # original page untouched
    eng.allocator._decref(page)             # undo the simulated sharer
    done = eng.run(max_steps=100)
    assert len(done[0].generated) == 4


def test_sharing_disabled_never_shares():
    cfg, params = _setup()
    prompt = np.asarray(jax.random.randint(KEY, (8,), 2, cfg.vocab))
    _, eng = _drain_outputs(params, cfg, [prompt, prompt.copy()], [4, 4],
                            sharing=False)
    assert eng.prefill_tokens_saved == 0
    assert eng.allocator.cached_pages == 0
