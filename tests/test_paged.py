"""Paged KV cache subsystem: allocator invariants, paged attention vs the
dense oracle (ragged lengths / GQA / page boundaries), paged decode_step
equivalence, and paged continuous batching end-to-end."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import lut as L
from repro.core.salpim import SalPimConfig, SalPimEngine
from repro.kernels import ops, ref as ref_k
from repro.models import api
from repro.serving import kvcache as kv
from repro.serving.engine import (EngineConfig, GenConfig, ServingEngine,
                                  generate)

import paged_cases

ENGINE = SalPimEngine.create(SalPimConfig())
KEY = jax.random.PRNGKey(0)


# ---------------------------------------------------------------------------
# BlockAllocator
# ---------------------------------------------------------------------------

def test_allocator_never_hands_out_trash_page():
    a = kv.BlockAllocator(num_pages=8, page_size=4)
    # worst = 24 + 5 - 1 = 28 tokens -> all 7 usable pages.
    pages = a.admit(uid=1, prompt_tokens=24, max_new_tokens=5)
    assert pages is not None and len(pages) == 6
    while len(a.pages_of(1)) < 7:
        pages.append(a.extend(1))
    assert kv.TRASH_PAGE not in pages           # full pool, page 0 untouched
    assert sorted(pages) == list(range(1, 8))
    assert a.free_pages == 0


def test_allocator_admit_extend_release_roundtrip():
    a = kv.BlockAllocator(num_pages=9, page_size=4)   # 8 usable
    pages = a.admit(uid=1, prompt_tokens=6, max_new_tokens=5)
    # prompt needs 2 pages now; worst case ceil((6+5-1)/4)=3 reserved.
    assert len(pages) == 2
    assert a.used_pages == 2
    assert a.available_pages == 8 - 3
    # Token positions 6, 7 fit page 2; position 8 needs a third page.
    assert not a.needs_extend(1, 6)
    assert not a.needs_extend(1, 7)
    assert a.needs_extend(1, 8)
    p = a.extend(1)
    assert p not in pages and p != kv.TRASH_PAGE
    assert a.used_pages == 3
    a.release(1)
    assert a.used_pages == 0
    assert a.available_pages == 8


def test_allocator_watermark_blocks_admission():
    a = kv.BlockAllocator(num_pages=5, page_size=4)   # 4 usable
    # First request reserves worst case 3 pages (8+3-1 = 10 tokens).
    assert a.admit(uid=1, prompt_tokens=8, max_new_tokens=3) is not None
    # Second wants 2 pages worst case but only 1 is unreserved.
    assert not a.can_admit(prompt_tokens=4, max_new_tokens=2)
    assert a.admit(uid=2, prompt_tokens=4, max_new_tokens=2) is None
    a.release(1)
    assert a.admit(uid=2, prompt_tokens=4, max_new_tokens=2) is not None


def test_allocator_exhausts_exactly_at_capacity():
    a = kv.BlockAllocator(num_pages=4, page_size=2)   # 3 usable
    # worst = 2 + 5 - 1 = 6 tokens -> all 3 usable pages reserved.
    assert a.admit(uid=1, prompt_tokens=2, max_new_tokens=5) is not None
    assert a.available_pages == 0
    assert a.admit(uid=2, prompt_tokens=1, max_new_tokens=1) is None


def test_worst_case_excludes_final_unwritten_token():
    """The last generated token's KV is never written (slot releases at
    its sampling step), so a request with prompt+max_new-1 == capacity
    must be admittable."""
    a = kv.BlockAllocator(num_pages=3, page_size=4)   # 2 usable, 8 tokens
    assert a.admit(uid=1, prompt_tokens=4, max_new_tokens=5) is not None


# ---------------------------------------------------------------------------
# Paged attention vs dense oracle
# ---------------------------------------------------------------------------

def _paged_setup(B, H, Hkv, D, page, n_pages_per_seq, lengths, key=KEY,
                 pool_pages=None):
    """Random dense KV + a shuffled page layout holding the same values."""
    ks = jax.random.split(key, 3)
    S = n_pages_per_seq * page
    q = jax.random.normal(ks[0], (B, H, D), jnp.float32)
    k = jax.random.normal(ks[1], (B, Hkv, S, D), jnp.float32)
    v = jax.random.normal(ks[2], (B, Hkv, S, D), jnp.float32)
    P = pool_pages or (1 + B * n_pages_per_seq)
    rng = np.random.RandomState(0)
    phys = rng.permutation(np.arange(1, B * n_pages_per_seq + 1))
    tables = phys.reshape(B, n_pages_per_seq).astype(np.int32)
    k_pages = np.zeros((P, Hkv, page, D), np.float32)
    v_pages = np.zeros((P, Hkv, page, D), np.float32)
    for b in range(B):
        for i in range(n_pages_per_seq):
            sl = slice(i * page, (i + 1) * page)
            k_pages[tables[b, i]] = np.asarray(k[b, :, sl])
            v_pages[tables[b, i]] = np.asarray(v[b, :, sl])
    return (q, k, v, jnp.asarray(k_pages), jnp.asarray(v_pages),
            jnp.asarray(tables), jnp.asarray(lengths, jnp.int32))


@pytest.mark.parametrize("H,Hkv", [(4, 4), (8, 2), (8, 1)])
@pytest.mark.parametrize("lengths", [[5, 13], [16, 1], [32, 17]])
def test_paged_ref_matches_dense_ref(H, Hkv, lengths):
    """Gathering pages via the block table == dense attention, across
    ragged lengths, GQA group sizes, and exact page-boundary lengths."""
    q, k, v, kp, vp, tbl, lens = _paged_setup(
        B=2, H=H, Hkv=Hkv, D=16, page=8, n_pages_per_seq=4, lengths=lengths)
    want = ref_k.decode_attention_ref(q, k, v, lens)
    got = ref_k.paged_attention_ref(q, kp, vp, tbl, lens)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("H,Hkv", [(4, 4), (8, 2)])
@pytest.mark.parametrize("lengths", [[5, 13], [16, 32]])
def test_paged_kernel_matches_ref(H, Hkv, lengths):
    q, k, v, kp, vp, tbl, lens = _paged_setup(
        B=2, H=H, Hkv=Hkv, D=128, page=16, n_pages_per_seq=2,
        lengths=lengths)
    want = ops.pim_paged_attention(q, kp, vp, tbl, lens, impl="reference")
    got = ops.pim_paged_attention(q, kp, vp, tbl, lens, impl="interpret")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


def test_paged_kernel_softcap_window_and_lut():
    bank = L.LutBank.create(64)
    q, k, v, kp, vp, tbl, lens = _paged_setup(
        B=2, H=4, Hkv=2, D=128, page=16, n_pages_per_seq=2,
        lengths=[23, 32])
    for kw in ({"softcap": 30.0}, {"window": 9},
               {"exp_table": bank.exp}):
        want = ops.pim_paged_attention(q, kp, vp, tbl, lens,
                                       impl="reference", **kw)
        got = ops.pim_paged_attention(q, kp, vp, tbl, lens,
                                      impl="interpret", **kw)
        # LUT mode: the kernel's online-softmax correction goes through
        # the LUT too, so it matches the oracle at the same 3e-3 the
        # dense decode kernel is held to; exact-exp paths stay at 1e-4.
        tol = 3e-3 if "exp_table" in kw else 1e-4
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=tol, atol=tol, err_msg=str(kw))


def test_unmapped_pages_are_masked():
    """Entries past `length` may point at the trash page; they must not
    contribute. Compare against a table with real (garbage) pages there."""
    q, k, v, kp, vp, tbl, lens = _paged_setup(
        B=2, H=4, Hkv=2, D=16, page=8, n_pages_per_seq=4,
        lengths=[9, 10])
    want = ref_k.paged_attention_ref(q, kp, vp, tbl, lens)
    trashed = jnp.where(
        jnp.arange(4)[None, :] < 2, tbl, kv.TRASH_PAGE)  # pages >= 2 unmapped
    got = ref_k.paged_attention_ref(q, kp, vp, trashed, lens)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# The decode walk: each row's live pages, every head per block
# ---------------------------------------------------------------------------

def test_walk_block_fills_a_score_row():
    from repro.kernels.paged_attention import walk_block_pages
    assert walk_block_pages(n_pages=24, page_size=16) == 8
    assert walk_block_pages(n_pages=544, page_size=16) == 8
    assert walk_block_pages(n_pages=3, page_size=16) == 3      # capped
    assert walk_block_pages(n_pages=9, page_size=128) == 1
    assert walk_block_pages(n_pages=9, page_size=48) == 3


@pytest.mark.parametrize("window", [None, paged_cases.WINDOW, 1])
def test_walk_pages_counts_the_pages_it_reads(window):
    """`walk_pages` (the engine's `decode.pages_walked`) equals the
    table entries the poison case leaves readable: per row, the pages
    from its first in-window position through position length - 1."""
    from repro.kernels.paged_attention import walk_pages
    n = len(paged_cases.LENGTHS)
    tbl = np.arange(1, 1 + n * paged_cases.N_PAGES).reshape(n, -1)
    pool = paged_cases.poison(np.zeros((1 + tbl.size, 1)), tbl, window)
    readable = int(np.isfinite(np.asarray(pool)[tbl]).sum())
    assert walk_pages(paged_cases.LENGTHS, window,
                      paged_cases.PAGE) == readable
    if window is None:
        assert readable == sum(-(-n // paged_cases.PAGE)
                               for n in paged_cases.LENGTHS)


@pytest.mark.parametrize("shape", paged_cases.SHAPES)
@pytest.mark.parametrize("mode", ["exact", "lut", "window"])
def test_walk_matches_ref_at_block_edges(shape, mode):
    """Empty slot, page boundary, one short of a block and a full table
    in one batch, at both cells' head shapes; the window starts
    mid-page."""
    q, kp, vp, tbl, lens = paged_cases.pools(*paged_cases.SHAPES[shape])
    kw = {"exact": {}, "lut": {"exp_table": L.LutBank.create(64).exp},
          "window": {"window": paged_cases.WINDOW}}[mode]
    want = ops.pim_paged_attention(q, kp, vp, tbl, lens, impl="reference",
                                   **kw)
    got = ops.pim_paged_attention(q, kp, vp, tbl, lens, impl="interpret",
                                  **kw)
    tol = 3e-3 if mode == "lut" else 1e-4
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("shape", paged_cases.SHAPES)
@pytest.mark.parametrize("window", [None, paged_cases.WINDOW])
def test_walk_reads_no_page_outside_it(shape, window):
    """Poison: NaN in every page the table maps past a row's length or
    before its window. A walk that read one would carry the NaN through
    p.V (0 * NaN); the live-page walk never loads it."""
    q, kp, vp, tbl, lens = paged_cases.pools(*paged_cases.SHAPES[shape])
    want = ref_k.paged_attention_ref(q, kp, vp, tbl, lens, window=window)
    got = ops.pim_paged_attention(
        q, paged_cases.poison(kp, tbl, window),
        paged_cases.poison(vp, tbl, window), tbl, lens, window=window,
        impl="interpret")
    assert bool(jnp.all(jnp.isfinite(got)))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# append / prompt-write helpers
# ---------------------------------------------------------------------------

def test_append_kv_pages_lands_at_length():
    page, Hkv, D = 4, 2, 8
    kp = jnp.zeros((5, Hkv, page, D))
    vp = jnp.zeros((5, Hkv, page, D))
    tbl = jnp.asarray([[1, 2], [3, 4]], jnp.int32)
    lens = jnp.asarray([3, 4], jnp.int32)     # slot 1 lands on page boundary
    k_new = jnp.ones((2, Hkv, D))
    v_new = 2 * jnp.ones((2, Hkv, D))
    nk, nv = kv.append_kv_pages(kp, vp, tbl, lens, k_new, v_new)
    np.testing.assert_allclose(np.asarray(nk[1, :, 3]), 1.0)  # page 1 off 3
    np.testing.assert_allclose(np.asarray(nk[4, :, 0]), 1.0)  # page 4 off 0
    np.testing.assert_allclose(np.asarray(nv[4, :, 0]), 2.0)
    assert float(jnp.abs(nk[2]).sum()) == 0.0  # slot 0 page 2 untouched


def test_write_prompt_pages_roundtrip():
    cfg = get_config("gpt2_medium", smoke=True)
    page = 4
    cache = kv.init_paged_cache(cfg, batch=2, num_pages=9, page_size=page,
                                max_pages=4)
    L_, Hkv, Dh = cfg.n_layers, cfg.n_kv_heads, cfg.head_dim
    length = 7
    kd = jax.random.normal(KEY, (L_, Hkv, 12, Dh))
    vd = jax.random.normal(jax.random.PRNGKey(1), (L_, Hkv, 12, Dh))
    cache = kv.write_prompt_pages(cache, 1, [3, 5], kd, vd, length)
    assert int(cache.lengths[1]) == length
    tbl = np.asarray(cache.block_tables)
    assert list(tbl[1]) == [3, 5, 0, 0] and (tbl[0] == 0).all()
    got = np.asarray(cache.k_pages)[:, tbl[1, :2]]       # (L, 2, Hkv, page, Dh)
    got = np.moveaxis(got, 2, 1).reshape(L_, Hkv, 2 * page, Dh)
    np.testing.assert_allclose(got[:, :, :length],
                               np.asarray(kd, got.dtype)[:, :, :length],
                               rtol=1e-6, atol=1e-6)


# ---------------------------------------------------------------------------
# Paged decode_step == dense decode_step
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("arch", ["gpt2_medium", "qwen2_1_5b"])
def test_paged_decode_matches_dense_decode(arch):
    """Greedy decode over enough steps to cross a page boundary must track
    the dense cache path step for step."""
    cfg = get_config(arch, smoke=True)
    params = api.init_params(KEY, cfg)
    B, S, page, steps = 2, 6, 4, 7    # crosses boundaries at 8 and 12
    prompts = jax.random.randint(KEY, (B, S), 2, cfg.vocab)
    max_len = S + steps + 1

    logits_d, dense = api.prefill(params, {"tokens": prompts}, cfg, ENGINE,
                                  max_len=max_len)
    max_pages = -(-max_len // page)
    paged = api.init_paged_cache(cfg, B, num_pages=B * max_pages + 1,
                                 page_size=page, max_pages=max_pages)
    next_page = 1
    for b in range(B):
        n0 = -(-S // page)
        ids = list(range(next_page, next_page + n0))
        next_page += n0
        paged = kv.write_prompt_pages(paged, b, ids, dense.k[:, b],
                                      dense.v[:, b], S)
    logits_p = logits_d

    mapped = {b: -(-S // page) for b in range(B)}
    for t in range(steps):
        tok_d = jnp.argmax(logits_d, -1).astype(jnp.int32)
        tok_p = jnp.argmax(logits_p, -1).astype(jnp.int32)
        np.testing.assert_array_equal(np.asarray(tok_d), np.asarray(tok_p),
                                      err_msg=f"step {t}")
        cur = S + t
        if (cur + 1) > mapped[0] * page:   # same length for all seqs here
            for b in range(B):
                paged = kv.PagedCache(
                    lengths=paged.lengths,
                    block_tables=paged.block_tables.at[b, mapped[b]].set(
                        next_page),
                    k_pages=paged.k_pages, v_pages=paged.v_pages)
                mapped[b] += 1
                next_page += 1
        logits_d, dense = api.decode_step(params, tok_d, dense, cfg, ENGINE)
        logits_p, paged = api.decode_step(params, tok_p, paged, cfg, ENGINE)
        np.testing.assert_allclose(np.asarray(logits_p),
                                   np.asarray(logits_d),
                                   rtol=1e-4, atol=1e-4,
                                   err_msg=f"step {t}")


# ---------------------------------------------------------------------------
# Paged serving engine end-to-end
# ---------------------------------------------------------------------------

def test_paged_continuous_batching_matches_batch_generate():
    """Paged slot engine output == whole-batch greedy generate."""
    cfg = get_config("gpt2_medium", smoke=True)
    params = api.init_params(KEY, cfg)
    prompts = np.asarray(jax.random.randint(KEY, (3, 8), 2, cfg.vocab))
    gen = GenConfig(max_new_tokens=5, temperature=0.0, stop_on_eos=False)
    ref, _ = generate(params, jnp.asarray(prompts), cfg, ENGINE, gen)

    eng = ServingEngine(params, cfg, ENGINE, EngineConfig(
        slots=2, max_len=32, gen=gen, page_size=4))
    uids = [eng.submit(prompts[i], max_new_tokens=5) for i in range(3)]
    done = eng.run(max_steps=200)
    assert len(done) == 3
    by_uid = {r.uid: r for r in done}
    for i, uid in enumerate(uids):
        np.testing.assert_array_equal(
            np.asarray(by_uid[uid].generated), np.asarray(ref[i]),
            err_msg=f"request {i}")
    # All pages returned to the pool after drain.
    assert eng.allocator.used_pages == 0


def test_paged_engine_under_page_pressure():
    """A pool too small for all requests at once still drains correctly —
    watermark admission delays, never corrupts."""
    cfg = get_config("gpt2_medium", smoke=True)
    params = api.init_params(KEY, cfg)
    gen = GenConfig(max_new_tokens=4, temperature=0.0, stop_on_eos=False)
    ref, _ = generate(
        params, jax.random.randint(KEY, (4, 8), 2, cfg.vocab), cfg, ENGINE,
        gen)
    prompts = np.asarray(jax.random.randint(KEY, (4, 8), 2, cfg.vocab))
    # Enough pages for ~1.3 worst-case requests -> strictly serialized.
    eng = ServingEngine(params, cfg, ENGINE, EngineConfig(
        slots=2, max_len=32, gen=gen, page_size=4, num_pages=6))
    uids = [eng.submit(prompts[i], max_new_tokens=4) for i in range(4)]
    done = eng.run(max_steps=400)
    assert sorted(r.uid for r in done) == sorted(uids)
    assert eng.allocator.used_pages == 0
    ref2, _ = generate(params, jnp.asarray(prompts), cfg, ENGINE, gen)
    by_uid = {r.uid: r for r in done}
    for i, uid in enumerate(uids):
        np.testing.assert_array_equal(
            np.asarray(by_uid[uid].generated), np.asarray(ref2[i]),
            err_msg=f"request {i}")


def test_oversized_request_raises_instead_of_spinning():
    """A request whose gross worst-case page count can never fit the pool
    is rejected at submit() — before it is queued, long before any pages
    are reserved — instead of blocking the FIFO head forever."""
    cfg = get_config("gpt2_medium", smoke=True)
    params = api.init_params(KEY, cfg)
    eng = ServingEngine(params, cfg, ENGINE, EngineConfig(
        slots=2, max_len=32, page_size=4, num_pages=4))  # 3 usable
    # Fits max_len (10 + 10 - 1 = 19 <= 32) but needs 5 pages > pool.
    with pytest.raises(ValueError, match="pages"):
        eng.submit(np.arange(2, 12), max_new_tokens=10)
    assert not eng.queue
    assert eng.allocator.available_pages == 3   # nothing reserved


def test_exact_fit_request_is_served():
    """prompt + max_new - 1 == max_len must be admitted and complete
    (the old +1 worst-case bound rejected it)."""
    cfg = get_config("gpt2_medium", smoke=True)
    params = api.init_params(KEY, cfg)
    gen = GenConfig(max_new_tokens=7, temperature=0.0, stop_on_eos=False)
    eng = ServingEngine(params, cfg, ENGINE, EngineConfig(
        slots=1, max_len=16, gen=gen, page_size=4))
    eng.submit(np.arange(2, 12), max_new_tokens=7)  # worst 16 == max_len
    (req,) = eng.run(max_steps=100)
    assert len(req.generated) == 7


def test_submit_rejects_requests_past_max_len():
    """Writes past max_len would be silently dropped (the block table is
    sized for max_len) — reject at submit."""
    cfg = get_config("gpt2_medium", smoke=True)
    params = api.init_params(KEY, cfg)
    eng = ServingEngine(params, cfg, ENGINE, EngineConfig(
        slots=2, max_len=16, page_size=4))
    with pytest.raises(ValueError, match="max_len"):
        eng.submit(np.arange(2, 12), max_new_tokens=10)  # 21 > 16


def test_run_returns_requests_admitted_before_call():
    """Requests admitted into slots before run() must still be returned
    (regression: run() used to snapshot only the pending queue)."""
    cfg = get_config("gpt2_medium", smoke=True)
    params = api.init_params(KEY, cfg)
    gen = GenConfig(max_new_tokens=3, temperature=0.0, stop_on_eos=False)
    eng = ServingEngine(params, cfg, ENGINE, EngineConfig(
        slots=2, max_len=32, gen=gen))
    u1 = eng.submit(np.arange(2, 8), max_new_tokens=3)
    eng.step()          # admits u1 into a slot, decodes once
    u2 = eng.submit(np.arange(2, 6), max_new_tokens=3)
    done = eng.run(max_steps=100)
    assert sorted(r.uid for r in done) == sorted([u1, u2])


def test_sampling_key_advances_between_steps():
    """temperature>0 must not reuse one PRNGKey every step (regression)."""
    cfg = get_config("gpt2_medium", smoke=True)
    params = api.init_params(KEY, cfg)
    gen = GenConfig(max_new_tokens=24, temperature=1.5, top_k=0,
                    stop_on_eos=False)
    eng = ServingEngine(params, cfg, ENGINE, EngineConfig(
        slots=1, max_len=64, gen=gen))
    eng.submit(np.arange(2, 10), max_new_tokens=24)
    (req,) = eng.run(max_steps=100)
    # With a frozen key the chain tok->logits->tok collapses to a cycle of
    # identical draws whenever logits repeat; with a stepping key 24 draws
    # from a near-uniform smoke model should not all coincide.
    assert len(set(req.generated)) > 1
