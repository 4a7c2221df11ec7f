"""Chunked paged prefill: the prefill-attention kernel vs its oracle,
direct-to-page chunk writes, chunked prefill == dense prefill at the
model level, and continuous batching — greedy outputs bit-identical
across dense prefill, one-shot paged prefill, and chunked prefill at
several chunk sizes, with prefix sharing on and off, while long prompts
no longer stall resident decodes."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import lut as L
from repro.core.salpim import SalPimConfig, SalPimEngine
from repro.kernels import ops, paged_prefill as pf_k, ref as ref_k
from repro.kernels.decode_attention import GLOBAL_WINDOW
from repro.models import api
from repro.serving import kvcache as kv
from repro.serving.engine import GenConfig, ServingEngine
import paged_cases
from repro.serving.quantize import quantize_vec, quantize_vec_int4
from tolerances import f32_reorder_tol

ENGINE = SalPimEngine.create(SalPimConfig())
KEY = jax.random.PRNGKey(0)


def _setup(arch="gpt2_medium"):
    cfg = get_config(arch, smoke=True)
    params = api.init_params(KEY, cfg)
    return cfg, params


# ---------------------------------------------------------------------------
# Prefill kernel vs oracle
# ---------------------------------------------------------------------------

def _chunk_setup(B, H, Hkv, D, page, n_pages_per_seq, Sq, starts, lengths,
                 key=KEY):
    """Random KV pool behind a shuffled block table + a query chunk."""
    ks = jax.random.split(key, 3)
    P = 1 + B * n_pages_per_seq
    rng = np.random.RandomState(0)
    phys = rng.permutation(np.arange(1, P))
    tables = phys.reshape(B, n_pages_per_seq).astype(np.int32)
    k_pages = jax.random.normal(ks[0], (P, Hkv, page, D), jnp.float32)
    v_pages = jax.random.normal(ks[1], (P, Hkv, page, D), jnp.float32)
    q = jax.random.normal(ks[2], (B, Sq, H, D), jnp.float32)
    return (q, k_pages, v_pages, jnp.asarray(tables),
            jnp.asarray(lengths, jnp.int32), jnp.asarray(starts, jnp.int32))


def test_chunk_ref_matches_dense_masked_attention():
    """Gathering pages and attending causally at offset `start` must equal
    dense attention over the same KV with an explicit causal mask."""
    B, H, Hkv, D, page, npg = 2, 4, 2, 16, 4, 4
    Sq, starts, lengths = 3, [2, 5], [5, 8]
    q, kp, vp, tbl, lens, st = _chunk_setup(B, H, Hkv, D, page, npg, Sq,
                                            starts, lengths)
    got = ref_k.paged_prefill_attention_ref(q, kp, vp, tbl, lens, st)
    # Dense reference: gather, then per-sequence softmax with the same
    # causal+length mask.
    k = jnp.moveaxis(kp[tbl], 2, 1).reshape(B, Hkv, npg * page, D)
    v = jnp.moveaxis(vp[tbl], 2, 1).reshape(B, Hkv, npg * page, D)
    g = H // Hkv
    S = npg * page
    scale = D ** -0.5
    for b in range(B):
        qb = np.asarray(q[b], np.float32).reshape(Sq, Hkv, g, D)
        kb = np.asarray(k[b], np.float32)
        scores = np.einsum("qhgd,hsd->hgqs", qb, kb) * scale
        q_pos = starts[b] + np.arange(Sq)
        mask = (np.arange(S)[None, :] <= q_pos[:, None]) & (
            np.arange(S)[None, :] < lengths[b])
        scores = np.where(mask[None, None], scores, -np.inf)
        m = scores.max(-1, keepdims=True)
        e = np.where(mask[None, None], np.exp(scores - m), 0.0)
        probs = e / e.sum(-1, keepdims=True)
        out = np.einsum("hgqs,hsd->qhgd", probs, np.asarray(v[b], np.float32))
        np.testing.assert_allclose(np.asarray(got[b]),
                                   out.reshape(Sq, H, D),
                                   rtol=1e-5, atol=1e-5, err_msg=f"b={b}")


@pytest.mark.parametrize("H,Hkv", [(4, 4), (8, 2), (8, 1)])
@pytest.mark.parametrize("Sq,starts,lengths", [
    (8, [0, 5], [8, 13]),       # first chunk / mid-page start
    (4, [16, 27], [20, 31]),    # page-aligned / odd start, later chunks
    (1, [40, 21], [41, 22]),    # single-token chunk (recompute case)
])
def test_chunk_kernel_matches_ref(H, Hkv, Sq, starts, lengths):
    q, kp, vp, tbl, lens, st = _chunk_setup(
        B=2, H=H, Hkv=Hkv, D=128, page=16, n_pages_per_seq=3, Sq=Sq,
        starts=starts, lengths=lengths)
    want = ops.pim_paged_prefill_attention(q, kp, vp, tbl, lens, st,
                                           impl="reference")
    got = ops.pim_paged_prefill_attention(q, kp, vp, tbl, lens, st,
                                          impl="interpret")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


def test_chunk_kernel_softcap_window_and_lut():
    bank = L.LutBank.create(64)
    q, kp, vp, tbl, lens, st = _chunk_setup(
        B=2, H=4, Hkv=2, D=128, page=16, n_pages_per_seq=2, Sq=6,
        starts=[10, 17], lengths=[16, 23])
    for kw in ({"softcap": 30.0}, {"window": 9}, {"exp_table": bank.exp}):
        want = ops.pim_paged_prefill_attention(q, kp, vp, tbl, lens, st,
                                               impl="reference", **kw)
        got = ops.pim_paged_prefill_attention(q, kp, vp, tbl, lens, st,
                                              impl="interpret", **kw)
        tol = 3e-3 if "exp_table" in kw else 1e-4
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=tol, atol=tol, err_msg=str(kw))


def test_single_query_chunk_matches_decode_oracle():
    """A 1-token chunk at position length-1 is exactly a decode-attention
    read (the masks coincide), tying the two kernels together."""
    lengths = [9, 14]
    q, kp, vp, tbl, lens, st = _chunk_setup(
        B=2, H=4, Hkv=2, D=16, page=4, n_pages_per_seq=4, Sq=1,
        starts=[x - 1 for x in lengths], lengths=lengths)
    got = ref_k.paged_prefill_attention_ref(q, kp, vp, tbl, lens, st)
    want = ref_k.paged_attention_ref(q[:, 0], kp, vp, tbl, lens)
    np.testing.assert_allclose(np.asarray(got[:, 0]), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# The chunk walk: each row tile's live pages, in multi-page blocks
# ---------------------------------------------------------------------------

def test_prefill_walk_block_pages_fill_a_score_row():
    """At least a 128-lane score row, at most MAX_BLOCK_KEYS positions,
    the (rows, block) f32 tile within SCORE_TILE_BYTES, capped at the
    table's width."""
    bp = pf_k.prefill_walk_block_pages
    assert bp(page_size=16, head_dim=128, rows=3072, n_pages=544) == 32
    assert bp(page_size=16, head_dim=128, rows=4096, n_pages=544) == 30
    assert bp(page_size=16, head_dim=128, rows=256, n_pages=96) == 32
    assert bp(page_size=16, head_dim=128, rows=32768, n_pages=544) == 8
    assert bp(page_size=16, head_dim=64, rows=64, n_pages=24) == 24
    assert bp(page_size=128, head_dim=128, rows=4096, n_pages=9) == 3
    for rows in (1, 6, 192, 1024, 3072, 4096):
        ppb = bp(16, 128, rows, 544)
        assert 128 <= ppb * 16 <= pf_k.MAX_BLOCK_KEYS
        assert ppb * 16 == 128 or (
            4 * (rows + 256) * ppb * 16 <= pf_k.SCORE_TILE_BYTES)


def test_prefill_row_tile_splits_only_past_the_cap():
    assert pf_k.prefill_row_tile(3072) == 3072   # Qwen2's 512-token chunk
    assert pf_k.prefill_row_tile(256) == 256     # OLMoE's
    assert pf_k.prefill_row_tile(4097) == 2064   # two tiles, 16-row multiple
    assert pf_k.prefill_row_tile(12288) == 4096  # a 2,048-token chunk
    assert pf_k.prefill_row_tile(7) == 7


@pytest.mark.parametrize("window", [None, paged_cases.CHUNK_WINDOW, 1])
def test_prefill_walk_pages_counts_the_pages_it_reads(window):
    """`prefill_walk_pages` (the engine's `prefill.pages_walked`) equals
    the table entries the chunk poison case leaves readable: per chunk,
    the pages from its first query's first in-window key through
    position length - 1."""
    starts = [s for _, st in paged_cases.CHUNKS.values() for s in st]
    lengths = [s + sq for sq, st in paged_cases.CHUNKS.values() for s in st]
    n = len(starts)
    tbl = np.arange(1, 1 + n * paged_cases.CHUNK_PAGES).reshape(n, -1)
    pool = paged_cases.poison(np.zeros((1 + tbl.size, 1)), tbl, window,
                              lengths, starts)
    readable = int(np.isfinite(np.asarray(pool)[tbl]).sum())
    assert pf_k.prefill_walk_pages(lengths, starts, window,
                                   paged_cases.PAGE) == readable
    if window is None:
        assert readable == sum(-(-x // paged_cases.PAGE) for x in lengths)


def test_pipeline_route_maps_no_page_outside_the_walk():
    """The BlockSpec route (head_dim 64, quantized pools) clamps each
    grid step's table entry into its row tile's [lo, hi): the steps
    outside it repeat a block index, which the pipeline does not DMA."""
    page, n_pages = paged_cases.PAGE, paged_cases.CHUNK_PAGES
    for rows, g in ((24, 1), (144, 6), (48, 6)):
        for start in (0, 21, 512):
            for window in (GLOBAL_WINDOW, paged_cases.CHUNK_WINDOW, 1):
                length = start + 24
                for t in range(-(-24 * g // rows)):
                    lo, hi = pf_k._bounds(t, rows, g, page, length, start,
                                          window)
                    seen = {int(pf_k._pipeline_entry(s, lo, hi, n_pages))
                            for s in range(n_pages)}
                    assert seen == set(range(int(lo), int(hi)))


def _walk_case(shape, case, window=None):
    H, Hkv, D = paged_cases.SHAPES[shape]
    Sq, starts = paged_cases.CHUNKS[case]
    return paged_cases.chunk_pools(H, Hkv, D, Sq, starts)


@pytest.mark.parametrize("shape", paged_cases.SHAPES)
@pytest.mark.parametrize("case", paged_cases.CHUNKS)
@pytest.mark.parametrize("mode", ["exact", "window", "softcap-lut"])
def test_chunk_walk_matches_ref_and_reads_no_page_outside_it(shape, case,
                                                             mode):
    """Both cells' head shapes over a table far wider than the live
    range, with NaN in every page the table maps outside each chunk's
    walk (a walk that read one would carry it through p.V). Chunks start
    at 0, at 512 and mid-page; the window makes lo > 0 mid-page."""
    q, kp, vp, tbl, lens, st = _walk_case(shape, case)
    window = paged_cases.CHUNK_WINDOW if mode == "window" else None
    kw = ({"softcap": 30.0, "exp_table": L.LutBank.create(64).exp}
          if mode == "softcap-lut" else {"window": window})
    want = ops.pim_paged_prefill_attention(q, kp, vp, tbl, lens, st,
                                           impl="reference", **kw)
    poison = functools.partial(paged_cases.poison, tables=tbl,
                               window=window, lengths=np.asarray(lens),
                               starts=np.asarray(st))
    got = ops.pim_paged_prefill_attention(q, poison(kp), poison(vp), tbl,
                                          lens, st, impl="interpret", **kw)
    assert bool(jnp.all(jnp.isfinite(got)))
    # The LUT exp's interpolation error (1e-3 relative) against exact
    # exp in the reference; f32 reordering otherwise.
    tol = 3e-3 if mode == "softcap-lut" else 1e-4
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("case", ["first", "mid-page", "wide-table"])
def test_chunk_walk_row_tiles(case, monkeypatch):
    """Row tiles of 32 of the GQA shape's 6 * Sq rows, each walking to
    its own causal frontier; 144 rows leave a partial last tile."""
    monkeypatch.setattr(pf_k, "MAX_TILE_ROWS", 32)
    q, kp, vp, tbl, lens, st = _walk_case("gqa12:2x128", case)
    want = ops.pim_paged_prefill_attention(q, kp, vp, tbl, lens, st,
                                           impl="reference")
    got = pf_k.paged_prefill_attention(
        q, paged_cases.poison(kp, tbl, None, np.asarray(lens)),
        paged_cases.poison(vp, tbl, None, np.asarray(lens)), tbl, lens, st,
        interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("shape", paged_cases.SHAPES)
@pytest.mark.parametrize("kv", ["int8", "int4"])
def test_quantized_chunk_walk_reads_no_scale_outside_it(shape, kv):
    """int8 (f32 scale rows) and int4 (bf16 scale rows, nibble-packed)
    pools, a chunk at 512 in a 64-entry table, with NaN in every scale
    row the table maps outside the walk."""
    q, kp, vp, tbl, lens, st = _walk_case(shape, "wide-table")
    if kv == "int8":
        (kq, ksc), (vq, vsc) = quantize_vec(kp), quantize_vec(vp)
    else:
        (kq, ksc), (vq, vsc) = (
            quantize_vec_int4(x, scale_dtype=jnp.bfloat16) for x in (kp, vp))
    ksc, vsc = ksc[:, :, None], vsc[:, :, None]
    want = ref_k.paged_prefill_attention_ref(q, kq, vq, tbl, lens, st,
                                             ksc, vsc)
    got = ops.pim_paged_prefill_attention(
        q, kq, vq, tbl, lens, st,
        paged_cases.poison(ksc, tbl, None, np.asarray(lens)),
        paged_cases.poison(vsc, tbl, None, np.asarray(lens)),
        impl="interpret")
    assert bool(jnp.all(jnp.isfinite(got)))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=0, atol=1e-4)


# ---------------------------------------------------------------------------
# Direct-to-page chunk writes
# ---------------------------------------------------------------------------

def test_append_chunk_kv_pages_mid_page_and_across_boundary():
    page, Hkv, D = 4, 2, 8
    kp = jnp.zeros((6, Hkv, page, D))
    vp = jnp.zeros((6, Hkv, page, D))
    tbl = jnp.asarray([[1, 2, 3], [4, 5, 0]], jnp.int32)
    start = jnp.asarray([3, 4], jnp.int32)     # mid-page / page-aligned
    S = 5
    k_new = jnp.arange(1, 2 * S * Hkv * D + 1, dtype=jnp.float32).reshape(
        2, S, Hkv, D)
    nk, nv = kv.append_chunk_kv_pages(kp, vp, tbl, start, k_new, 2 * k_new)
    # Slot 0 tokens land at positions 3..7 -> page 1 off 3, page 2 off 0..3.
    np.testing.assert_allclose(np.asarray(nk[1, :, 3]),
                               np.asarray(k_new[0, 0]))
    for i in range(4):
        np.testing.assert_allclose(np.asarray(nk[2, :, i]),
                                   np.asarray(k_new[0, 1 + i]))
    # Slot 1 tokens land at positions 4..8 -> page 5 fully, page 0 (trash).
    for i in range(4):
        np.testing.assert_allclose(np.asarray(nv[5, :, i]),
                                   np.asarray(2 * k_new[1, i]))
    # Untouched pages stay zero; the boundary write scribbled only trash.
    assert float(jnp.abs(nk[3]).sum()) == 0.0
    np.testing.assert_allclose(np.asarray(nk[0, :, 0]),
                               np.asarray(k_new[1, 4]))  # trash page soak


# ---------------------------------------------------------------------------
# prefill_chunk == dense prefill (model level)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("arch", ["gpt2_medium", "qwen2_1_5b"])
@pytest.mark.parametrize("splits", [
    [(0, 13)],                         # one-shot
    [(0, 4), (4, 8), (8, 12), (12, 13)],   # page-size chunks
    [(0, 5), (5, 10), (10, 13)],       # odd non-divisor chunks
    [(0, 8), (8, 13)],                 # 2-page chunk then tail
])
def test_prefill_chunk_matches_dense_prefill(arch, splits):
    """Running a prompt through prefill_chunk in any split must reproduce
    the dense prefill's last-position logits and leave exactly the dense
    cache's K/V in the pool — for learned positions (gpt2) and RoPE
    (qwen2) alike."""
    cfg, params = _setup(arch)
    S, page = 13, 4
    prompt = jax.random.randint(KEY, (1, S), 2, cfg.vocab)
    logits_d, cache_d = api.prefill(params, {"tokens": prompt}, cfg, ENGINE,
                                    max_len=16)
    cache = api.init_paged_cache(cfg, 1, num_pages=6, page_size=page,
                                 max_pages=4)
    pages = jnp.asarray([1, 2, 3, 4], jnp.int32)
    row = pages[None]
    kp, vp = cache.k_pages, cache.v_pages
    for (a, b) in splits:
        logits_c, kp, vp = api.prefill_chunk(
            params, prompt[:, a:b], row, jnp.asarray([a], jnp.int32),
            kp, vp, cfg, ENGINE)
    # Chunks of 4-13 rows and the 13-row dense prefill sum their f32
    # contractions in shape-dependent orders (tests/tolerances.py).
    tol = f32_reorder_tol(cfg)
    np.testing.assert_allclose(np.asarray(logits_c), np.asarray(logits_d),
                               rtol=tol, atol=tol)
    # The pool K/V go through the same reordered contractions: at 1e-6
    # one element in 1664 missed by 1.18e-6 (gpt2_medium smoke, splits1,
    # CPU, xdist -n 2), so these checks take the derived bound too.
    gk = jnp.moveaxis(kp[:, pages], 1, 2).reshape(
        cfg.n_layers, cfg.n_kv_heads, -1, cfg.head_dim)[:, :, :S]
    gv = jnp.moveaxis(vp[:, pages], 1, 2).reshape(
        cfg.n_layers, cfg.n_kv_heads, -1, cfg.head_dim)[:, :, :S]
    np.testing.assert_allclose(np.asarray(gk),
                               np.asarray(cache_d.k[:, 0, :, :S]),
                               rtol=tol, atol=tol)
    np.testing.assert_allclose(np.asarray(gv),
                               np.asarray(cache_d.v[:, 0, :, :S]),
                               rtol=tol, atol=tol)


# ---------------------------------------------------------------------------
# Serving: bit-identical across backends, chunk sizes, and sharing
# ---------------------------------------------------------------------------

def _workload(cfg):
    rng = np.random.RandomState(3)
    prefix = rng.randint(2, cfg.vocab, size=8)
    prompts = [np.concatenate([prefix, rng.randint(2, cfg.vocab, size=n)])
               for n in (3, 1, 9)]
    prompts.append(rng.randint(2, cfg.vocab, size=17))   # long, unshared
    new = [6, 8, 5, 4]
    return prompts, new


def _drain_outputs(params, cfg, prompts, new, **kw):
    gen = GenConfig(temperature=0.0, stop_on_eos=False)
    eng = ServingEngine(params, cfg, ENGINE, slots=2, max_len=32, gen=gen,
                        **kw)
    uids = [eng.submit(p.copy(), max_new_tokens=n)
            for p, n in zip(prompts, new)]
    done = eng.run(max_steps=600)
    assert sorted(r.uid for r in done) == sorted(uids)
    by = {r.uid: r.generated for r in done}
    if eng.paged:
        assert eng.allocator.used_pages == 0
    return [by[u] for u in uids], eng


@pytest.fixture(scope="module")
def serving_env():
    cfg, params = _setup()
    prompts, new = _workload(cfg)
    ref, _ = _drain_outputs(params, cfg, prompts, new)       # dense
    return cfg, params, prompts, new, ref


@pytest.mark.parametrize("sharing", [True, False])
@pytest.mark.parametrize("chunk", [None, 4, 8, 5])
def test_serving_bit_identical_dense_oneshot_chunked(serving_env, sharing,
                                                     chunk):
    """Acceptance: greedy outputs bit-identical across dense prefill,
    one-shot paged prefill (chunk=None), and chunked prefill at chunk
    sizes {page, 2*page, odd non-divisor}, with prefix sharing on/off."""
    cfg, params, prompts, new, ref = serving_env
    out, eng = _drain_outputs(params, cfg, prompts, new, paged=True,
                              page_size=4, prefix_sharing=sharing,
                              prefill_chunk_tokens=chunk)
    assert out == ref
    if sharing:
        assert eng.prefill_tokens_saved > 0
        assert eng.prefill_tokens < sum(len(p) for p in prompts)
    else:
        assert eng.prefill_tokens_saved == 0
        assert eng.prefill_tokens == sum(len(p) for p in prompts)


def test_long_prompt_does_not_stall_resident_decode():
    """While a long prompt prefills chunk-by-chunk, a resident decode
    must emit one token per engine step — continuous batching — and both
    requests must still match their solo greedy outputs."""
    cfg, params = _setup()
    gen = GenConfig(temperature=0.0, stop_on_eos=False)
    rng = np.random.RandomState(5)
    res_prompt = rng.randint(2, cfg.vocab, size=4)
    long_prompt = rng.randint(2, cfg.vocab, size=16)
    chunk = 4

    eng = ServingEngine(params, cfg, ENGINE, slots=2, max_len=32, gen=gen,
                        paged=True, page_size=4, prefill_chunk_tokens=chunk)
    u_res = eng.submit(res_prompt.copy(), max_new_tokens=12)
    eng.step()                       # resident admitted + first token
    res = next(r for r in eng.active if r is not None and r.uid == u_res)
    assert len(res.generated) == 1
    u_long = eng.submit(long_prompt.copy(), max_new_tokens=2)
    prefill_steps = 0
    while True:
        long_req = next((r for r in eng.active
                         if r is not None and r.uid == u_long), None)
        if long_req is not None and not long_req.prefilling:
            break
        before = len(res.generated)
        eng.step()
        prefill_steps += 1
        # The resident decode advanced during the long prompt's prefill.
        assert len(res.generated) == before + 1, "resident decode stalled"
        assert prefill_steps <= 16 // chunk + 1, "prefill never finished"
    assert prefill_steps == 16 // chunk     # one chunk per step, no more
    done = eng.run(max_steps=200)
    by = {r.uid: r.generated for r in done}

    solo = {}
    for p, n, u in [(res_prompt, 12, u_res), (long_prompt, 2, u_long)]:
        e2 = ServingEngine(params, cfg, ENGINE, slots=1, max_len=32,
                           gen=gen, paged=True, page_size=4)
        e2.submit(p.copy(), max_new_tokens=n)
        (r2,) = e2.run(max_steps=200)
        solo[u] = r2.generated
    assert by[u_res] == solo[u_res]
    assert by[u_long] == solo[u_long]


def test_sharer_admitted_during_donor_prefill_is_correct():
    """A request admitted while its prefix donor is still mid-prefill
    maps pages whose contents arrive later; uid-ordered prefill ticks
    guarantee the donor writes them first. Outputs must match the
    sharing-off run bit-for-bit."""
    cfg, params = _setup()
    rng = np.random.RandomState(7)
    prefix = rng.randint(2, cfg.vocab, size=12)
    prompts = [np.concatenate([prefix, rng.randint(2, cfg.vocab, size=2)]),
               np.concatenate([prefix, rng.randint(2, cfg.vocab, size=3)])]
    new = [5, 6]
    kw = dict(paged=True, page_size=4, prefill_chunk_tokens=4)
    out_off, _ = _drain_outputs(params, cfg, prompts, new,
                                prefix_sharing=False, **kw)
    out_on, eng = _drain_outputs(params, cfg, prompts, new,
                                 prefix_sharing=True, **kw)
    assert out_on == out_off
    assert eng.prefill_tokens_saved == 12    # 3 full prefix pages shared


# ---------------------------------------------------------------------------
# Admission-control regressions
# ---------------------------------------------------------------------------

def test_chunk_budget_requires_paged_backend():
    """The dense backend cannot honor a chunk budget; silently ignoring
    it would fake a latency bound that is not enforced."""
    cfg, params = _setup()
    with pytest.raises(ValueError, match="paged"):
        ServingEngine(params, cfg, ENGINE, slots=1, max_len=32,
                      prefill_chunk_tokens=8)


def test_oversized_submit_leaves_engine_unscathed():
    """An oversized submit must be rejected before queueing or reserving
    anything; requests around it are unaffected."""
    cfg, params = _setup()
    gen = GenConfig(temperature=0.0, stop_on_eos=False)
    eng = ServingEngine(params, cfg, ENGINE, slots=2, max_len=32, gen=gen,
                        paged=True, page_size=4, num_pages=7)  # 6 usable
    u1 = eng.submit(np.arange(2, 8), max_new_tokens=3)
    with pytest.raises(ValueError, match="pages"):
        # 20 + 10 - 1 = 29 <= max_len but 8 pages > 6 usable.
        eng.submit(np.arange(2, 22), max_new_tokens=10)
    assert [r.uid for r in eng.queue] == [u1]
    assert eng.allocator.available_pages == 6
    u2 = eng.submit(np.arange(2, 9), max_new_tokens=3)
    done = eng.run(max_steps=200)
    assert sorted(r.uid for r in done) == sorted([u1, u2])
    assert eng.allocator.used_pages == 0


def test_waiting_queue_head_reserves_nothing():
    """A request waiting at the FIFO head for pages must not hold any
    reservation while it waits (regression: leaked reservations would
    shrink the pool for the resident request and deadlock the drain)."""
    cfg, params = _setup()
    gen = GenConfig(temperature=0.0, stop_on_eos=False)
    eng = ServingEngine(params, cfg, ENGINE, slots=2, max_len=32, gen=gen,
                        paged=True, page_size=4, num_pages=7)  # 6 usable
    u1 = eng.submit(np.arange(2, 10), max_new_tokens=9)   # worst 4 pages
    u2 = eng.submit(np.arange(20, 28), max_new_tokens=9)  # no shared prefix
    eng.step()
    assert eng.active[0] is not None and eng.active[0].uid == u1
    assert [r.uid for r in eng.queue] == [u2]
    avail_while_waiting = eng.allocator.available_pages
    eng.step()
    # Waiting changed nothing: u2 holds no pages, no reservation.
    assert eng.allocator.available_pages == avail_while_waiting
    assert eng.allocator._reserved + eng.allocator.used_pages \
        == eng.allocator._quota[u1]
    done = eng.run(max_steps=300)
    assert sorted(r.uid for r in done) == sorted([u1, u2])
    assert eng.allocator.used_pages == 0
    assert eng.allocator.available_pages == 6
