"""Chunked paged prefill: the prefill-attention kernel vs its oracle,
direct-to-page chunk writes, chunked prefill == dense prefill at the
model level, and continuous batching — greedy outputs bit-identical
across dense prefill, one-shot paged prefill, and chunked prefill at
several chunk sizes, with prefix sharing on and off, while long prompts
no longer stall resident decodes."""
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
from repro.serving.engine import GenConfig, ServingEngine
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
