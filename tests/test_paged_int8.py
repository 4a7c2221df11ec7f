"""int8 KV pages for the paged cache: write-time quantization, in-kernel
dequant vs the quantize->dequantize oracle, COW scale-row copies,
byte-budget pool sizing / watermark capacity, and end-to-end serving
equivalence (greedy exact-match vs the fp engine on the test prompts)."""
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
from repro.serving.engine import EngineConfig, GenConfig, ServingEngine
from repro.serving.quantize import dequantize_vec, quantize_vec

import paged_cases

ENGINE = SalPimEngine.create(SalPimConfig())
KEY = jax.random.PRNGKey(0)


# ---------------------------------------------------------------------------
# Quantization roundtrip + oracles
# ---------------------------------------------------------------------------

def test_quantize_vec_roundtrip_error_bound():
    x = jax.random.normal(KEY, (4, 3, 32)) * 2.0
    q, scale = quantize_vec(x)
    assert q.dtype == jnp.int8 and scale.shape == (4, 3)
    deq = dequantize_vec(q, scale, jnp.float32)
    # Symmetric amax: error per element is at most half a quantization
    # step of that vector (= amax/127), plus float rounding.
    bound = np.asarray(jnp.max(jnp.abs(x), -1) / 127.0) * 0.5 + 1e-6
    err = np.asarray(jnp.max(jnp.abs(deq - x), -1))
    assert (err <= bound).all(), (err.max(), bound.max())


def _rows(sc):
    """(P, Hkv, page) per-vector scales -> the pools' (P, Hkv, 1, page)
    scale-row layout."""
    return sc[:, :, None]


def _paged_int8_setup(B, H, Hkv, D, page, npg, lengths, key=KEY):
    """fp pools + their quantized twins behind one shuffled block table."""
    ks = jax.random.split(key, 3)
    P = 1 + B * npg
    rng = np.random.RandomState(0)
    tables = rng.permutation(np.arange(1, P)).reshape(B, npg).astype(np.int32)
    q = jax.random.normal(ks[0], (B, H, D), jnp.float32)
    kp = jax.random.normal(ks[1], (P, Hkv, page, D), jnp.float32)
    vp = jax.random.normal(ks[2], (P, Hkv, page, D), jnp.float32)
    kq, ksc = quantize_vec(kp)
    vq, vsc = quantize_vec(vp)
    return (q, kp, vp, kq, _rows(ksc), vq, _rows(vsc), jnp.asarray(tables),
            jnp.asarray(lengths, jnp.int32))


def test_int8_ref_equals_fp_ref_on_roundtripped_kv():
    """The int8 oracle is *exactly* the fp oracle run on the
    quantize->dequantize roundtrip of the pools — the documented error
    envelope is quantization alone, not a second approximation."""
    q, kp, vp, kq, ksc, vq, vsc, tbl, lens = _paged_int8_setup(
        B=2, H=4, Hkv=2, D=16, page=8, npg=4, lengths=[9, 26])
    got = ref_k.paged_attention_ref(q, kq, vq, tbl, lens, ksc, vsc)
    want = ref_k.paged_attention_ref(
        q, ref_k.kv_roundtrip_ref(kp), ref_k.kv_roundtrip_ref(vp), tbl, lens)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_int8_quant_error_vs_fp_is_bounded():
    q, kp, vp, kq, ksc, vq, vsc, tbl, lens = _paged_int8_setup(
        B=2, H=8, Hkv=2, D=64, page=8, npg=4, lengths=[17, 32])
    got = ref_k.paged_attention_ref(q, kq, vq, tbl, lens, ksc, vsc)
    fp = ref_k.paged_attention_ref(q, kp, vp, tbl, lens)
    # Attention outputs are convex combinations of dequantized V rows
    # perturbed by K-side score noise: a loose 5% of the output scale
    # bounds the ~1/127-per-vector quantization noise with margin.
    tol = 0.05 * float(jnp.std(fp))
    assert float(jnp.max(jnp.abs(got - fp))) < tol


# ---------------------------------------------------------------------------
# Kernels vs oracle (interpret mode)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("H,Hkv", [(4, 4), (8, 2)])
@pytest.mark.parametrize("lengths", [[5, 13], [16, 32]])
def test_int8_decode_kernel_matches_ref(H, Hkv, lengths):
    q, kp, vp, kq, ksc, vq, vsc, tbl, lens = _paged_int8_setup(
        B=2, H=H, Hkv=Hkv, D=128, page=16, npg=2, lengths=lengths)
    want = ops.pim_paged_attention(q, kq, vq, tbl, lens, ksc, vsc,
                                   impl="reference")
    got = ops.pim_paged_attention(q, kq, vq, tbl, lens, ksc, vsc,
                                  impl="interpret")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


def test_int8_decode_kernel_softcap_window_and_lut():
    bank = L.LutBank.create(64)
    q, kp, vp, kq, ksc, vq, vsc, tbl, lens = _paged_int8_setup(
        B=2, H=4, Hkv=2, D=128, page=16, npg=2, lengths=[23, 32])
    for kw in ({"softcap": 30.0}, {"window": 9}, {"exp_table": bank.exp}):
        want = ops.pim_paged_attention(q, kq, vq, tbl, lens, ksc, vsc,
                                       impl="reference", **kw)
        got = ops.pim_paged_attention(q, kq, vq, tbl, lens, ksc, vsc,
                                      impl="interpret", **kw)
        tol = 3e-3 if "exp_table" in kw else 1e-4
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=tol, atol=tol, err_msg=str(kw))


@pytest.mark.parametrize("shape", paged_cases.SHAPES)
@pytest.mark.parametrize("window", [None, paged_cases.WINDOW])
def test_int8_walk_at_block_edges_reads_no_page_outside_it(shape, window):
    """The decode walk over int8 pools at its block edges (empty slot,
    page boundary, one short of a block, full table), with NaN in every
    scale row the table maps outside a row's walk."""
    q, kp, vp, tbl, lens = paged_cases.pools(*paged_cases.SHAPES[shape])
    (kq, ksc), (vq, vsc) = quantize_vec(kp), quantize_vec(vp)
    ksc, vsc = _rows(ksc), _rows(vsc)
    want = ref_k.paged_attention_ref(q, kq, vq, tbl, lens, ksc, vsc,
                                     window=window)
    got = ops.pim_paged_attention(
        q, kq, vq, tbl, lens, paged_cases.poison(ksc, tbl, window),
        paged_cases.poison(vsc, tbl, window), window=window,
        impl="interpret")
    assert bool(jnp.all(jnp.isfinite(got)))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("Sq,starts,lengths", [
    (8, [0, 5], [8, 13]),
    (4, [16, 27], [20, 31]),
    (1, [40, 21], [41, 22]),
])
def test_int8_prefill_kernel_matches_ref(Sq, starts, lengths):
    ks = jax.random.split(KEY, 3)
    B, H, Hkv, D, page, npg = 2, 8, 2, 128, 16, 3
    P = 1 + B * npg
    rng = np.random.RandomState(0)
    tbl = jnp.asarray(
        rng.permutation(np.arange(1, P)).reshape(B, npg).astype(np.int32))
    kq, ksc = quantize_vec(jax.random.normal(ks[0], (P, Hkv, page, D)))
    vq, vsc = quantize_vec(jax.random.normal(ks[1], (P, Hkv, page, D)))
    ksc, vsc = _rows(ksc), _rows(vsc)
    q = jax.random.normal(ks[2], (B, Sq, H, D), jnp.float32)
    st = jnp.asarray(starts, jnp.int32)
    lens = jnp.asarray(lengths, jnp.int32)
    want = ops.pim_paged_prefill_attention(q, kq, vq, tbl, lens, st,
                                           ksc, vsc, impl="reference")
    got = ops.pim_paged_prefill_attention(q, kq, vq, tbl, lens, st,
                                          ksc, vsc, impl="interpret")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# Write-time quantization in the append paths
# ---------------------------------------------------------------------------

def test_append_kv_pages_quantizes_at_write():
    page, Hkv, D = 4, 2, 8
    kp = jnp.zeros((5, Hkv, page, D), jnp.int8)
    vp = jnp.zeros((5, Hkv, page, D), jnp.int8)
    ksc = jnp.zeros((5, Hkv, 1, page))
    vsc = jnp.zeros((5, Hkv, 1, page))
    tbl = jnp.asarray([[1, 2], [3, 4]], jnp.int32)
    lens = jnp.asarray([3, 4], jnp.int32)
    k_new = jax.random.normal(KEY, (2, Hkv, D))
    v_new = 2.0 * k_new
    nk, nv, nks, nvs = kv.append_kv_pages(kp, vp, tbl, lens, k_new, v_new,
                                          ksc, vsc)
    assert nk.dtype == jnp.int8
    # Slot 0 landed at page 1 offset 3; slot 1 at page 4 offset 0.
    for slot, (pg, off) in enumerate([(1, 3), (4, 0)]):
        deq = dequantize_vec(nk[pg, :, off], nks[pg, :, 0, off],
                             jnp.float32)
        np.testing.assert_allclose(np.asarray(deq),
                                   np.asarray(k_new[slot]),
                                   rtol=0, atol=2e-2)
        deq_v = dequantize_vec(nv[pg, :, off], nvs[pg, :, 0, off],
                               jnp.float32)
        np.testing.assert_allclose(np.asarray(deq_v),
                                   np.asarray(v_new[slot]),
                                   rtol=0, atol=4e-2)
    assert float(jnp.abs(nks[2]).sum()) == 0.0  # untouched page, no scale


def test_append_chunk_kv_pages_quantizes_at_write():
    page, Hkv, D, S = 4, 2, 8, 5
    kp = jnp.zeros((6, Hkv, page, D), jnp.int8)
    vp = jnp.zeros((6, Hkv, page, D), jnp.int8)
    ksc = jnp.zeros((6, Hkv, 1, page))
    vsc = jnp.zeros((6, Hkv, 1, page))
    tbl = jnp.asarray([[1, 2, 3]], jnp.int32)
    start = jnp.asarray([3], jnp.int32)
    k_new = jax.random.normal(KEY, (1, S, Hkv, D))
    nk, nv, nks, nvs = kv.append_chunk_kv_pages(
        kp, vp, tbl, start, k_new, 0.5 * k_new, ksc, vsc)
    # Tokens land at positions 3..7 -> page 1 off 3, page 2 off 0..3.
    for i, (pg, off) in enumerate([(1, 3), (2, 0), (2, 1), (2, 2), (2, 3)]):
        deq = dequantize_vec(nk[pg, :, off], nks[pg, :, 0, off],
                             jnp.float32)
        np.testing.assert_allclose(np.asarray(deq),
                                   np.asarray(k_new[0, i]),
                                   rtol=0, atol=2e-2, err_msg=f"token {i}")


def test_copy_page_copies_scale_rows():
    """COW forks must duplicate the scale rows with the payload: after a
    fork, rewriting the donor page's scales cannot change the fork."""
    cfg = get_config("gpt2_medium", smoke=True)
    cache = kv.init_paged_cache(cfg, batch=1, num_pages=4, page_size=4,
                                max_pages=2, kv_dtype="int8")
    assert cache.quantized
    cache = kv.PagedCache(
        cache.lengths, cache.block_tables,
        cache.k_pages.at[:, 1].set(7), cache.v_pages.at[:, 1].set(-7),
        cache.k_scale.at[:, 1].set(0.25), cache.v_scale.at[:, 1].set(0.5))
    cache = kv.copy_page(cache, src=1, dst=2)
    np.testing.assert_allclose(np.asarray(cache.k_scale[:, 2]), 0.25)
    np.testing.assert_allclose(np.asarray(cache.v_scale[:, 2]), 0.5)
    np.testing.assert_array_equal(np.asarray(cache.k_pages[:, 2]), 7)
    # Donor page recycled (its scale row overwritten by a new sequence):
    # the fork's row must be untouched — scales are copied, not aliased.
    cache = kv.PagedCache(
        cache.lengths, cache.block_tables, cache.k_pages, cache.v_pages,
        cache.k_scale.at[:, 1].set(99.0), cache.v_scale.at[:, 1].set(99.0))
    np.testing.assert_allclose(np.asarray(cache.k_scale[:, 2]), 0.25)


# ---------------------------------------------------------------------------
# Pool sizing + watermark capacity at the halved per-page byte cost
# ---------------------------------------------------------------------------

def test_page_kv_bytes_int8_at_least_halves_bf16_pages():
    import dataclasses
    # The tight regime is bf16 (2 B/elem) with production head dims: at
    # Dh=64 the ratio is 2*64/(64+4) = 1.88; smoke configs are f32 and
    # would pass trivially at 4*Dh/(Dh+4).
    cfg = dataclasses.replace(get_config("qwen2_1_5b", smoke=True),
                              compute_dtype="bfloat16", head_dim=64)
    fp = kv.page_kv_bytes(cfg, 16, "model")
    q8 = kv.page_kv_bytes(cfg, 16, "int8")
    unit = cfg.n_layers * cfg.n_kv_heads * 16
    assert fp == 2 * unit * cfg.head_dim * 2
    assert q8 == 2 * unit * (cfg.head_dim + 4)   # payload + f32 scale
    assert fp / q8 >= 1.8, (fp, q8)
    with pytest.raises(ValueError, match="kv_dtype"):
        kv.init_paged_cache(cfg, 1, 4, 4, 2, kv_dtype="fp4")


def test_int8_pools_without_scales_fail_fast():
    """Regression for the deleted 'int8 unsupported' guard: int8 pools
    reaching the fp write branch would astype float K/V to int8 —
    silent garbage. Both paged entry points must raise instead."""
    cfg = get_config("gpt2_medium", smoke=True)
    params = api.init_params(KEY, cfg)
    cache = kv.init_paged_cache(cfg, 1, 4, 4, 2, kv_dtype="int8")
    stripped = kv.PagedCache(cache.lengths, cache.block_tables,
                             cache.k_pages, cache.v_pages)
    with pytest.raises(ValueError, match="scale"):
        api.decode_step(params, jnp.zeros((1,), jnp.int32), stripped,
                        cfg, ENGINE)
    with pytest.raises(ValueError, match="scale"):
        api.prefill_chunk(params, jnp.zeros((1, 4), jnp.int32),
                          stripped.block_tables,
                          jnp.zeros((1,), jnp.int32),
                          stripped.k_pages, stripped.v_pages, cfg, ENGINE)


def test_int8_default_pool_doubles_capacity_at_fixed_bytes():
    """num_pages=None keeps the fp cache's byte budget: the int8 pool
    must hold ~2x+ the pages and the watermark must admit ~2x+ the
    worst-case reservations before refusing — and still refuse then."""
    cfg = get_config("gpt2_medium", smoke=True)
    params = api.init_params(KEY, cfg)
    engf = ServingEngine(params, cfg, ENGINE, EngineConfig(
        slots=4, max_len=32, page_size=4))
    eng8 = ServingEngine(params, cfg, ENGINE, EngineConfig(
        slots=4, max_len=32, page_size=4, kv_cache_dtype="int8"))
    usable_f = engf.allocator.num_pages - 1
    usable_8 = eng8.allocator.num_pages - 1
    assert usable_8 >= 1.8 * usable_f, (usable_8, usable_f)
    # Same HBM budget (trash page excluded on both sides).
    assert usable_8 * eng8.page_bytes <= usable_f * engf.page_bytes

    def admissions(alloc):
        n = 0
        while alloc.admit(uid=n + 1, prompt_tokens=8, max_new_tokens=9):
            n += 1          # worst case 16 tokens = 4 pages each
        return n

    n_f = admissions(engf.allocator)
    n_8 = admissions(eng8.allocator)
    assert n_f == usable_f // 4
    assert n_8 == usable_8 // 4
    assert n_8 >= 1.8 * n_f
    # Watermark accounting is still exact at the larger capacity: every
    # page is either handed out or reserved, and one release frees
    # exactly one more admission.
    a = eng8.allocator
    assert a.used_pages + a._reserved == n_8 * 4
    assert not a.can_admit(prompt_tokens=8, max_new_tokens=9)
    a.release(1)
    assert a.can_admit(prompt_tokens=8, max_new_tokens=9)


def test_kv_cache_dtype_validation():
    cfg = get_config("gpt2_medium", smoke=True)
    params = api.init_params(KEY, cfg)
    with pytest.raises(ValueError, match="kv_cache_dtype"):
        ServingEngine(params, cfg, ENGINE, EngineConfig(
            slots=1, max_len=16, kv_cache_dtype="fp4"))


# ---------------------------------------------------------------------------
# Serving end-to-end: int8 greedy outputs match the fp engine exactly
# ---------------------------------------------------------------------------

def _workload(cfg):
    rng = np.random.RandomState(3)
    prefix = rng.randint(2, cfg.vocab, size=8)
    prompts = [np.concatenate([prefix, rng.randint(2, cfg.vocab, size=n)])
               for n in (3, 1, 9)]
    prompts.append(rng.randint(2, cfg.vocab, size=17))
    new = [6, 8, 5, 4]
    return prompts, new


def _drain_outputs(params, cfg, prompts, new, **kw):
    gen = GenConfig(temperature=0.0, stop_on_eos=False)
    eng = ServingEngine(params, cfg, ENGINE, EngineConfig(
        slots=2, max_len=32, gen=gen, **kw))
    uids = [eng.submit(p.copy(), max_new_tokens=n)
            for p, n in zip(prompts, new)]
    done = eng.run(max_steps=600)
    assert sorted(r.uid for r in done) == sorted(uids)
    assert eng.allocator.used_pages == 0
    by = {r.uid: r.generated for r in done}
    return [by[u] for u in uids], eng


@pytest.mark.parametrize("arch", ["gpt2_medium", "qwen2_1_5b"])
def test_int8_serving_greedy_exact_match(arch):
    """Acceptance: greedy decode with kv_cache_dtype=int8 must reproduce
    the fp paged engine's outputs exactly on the serving test prompts
    (quantization noise stays below every argmax margin here), with the
    int8 pools actually in use."""
    cfg = get_config(arch, smoke=True)
    params = api.init_params(KEY, cfg)
    prompts, new = _workload(cfg)
    ref, _ = _drain_outputs(params, cfg, prompts, new, page_size=4)
    out, eng = _drain_outputs(params, cfg, prompts, new,
                              page_size=4, kv_cache_dtype="int8")
    assert eng.cache.k_pages.dtype == jnp.int8 and eng.cache.quantized
    assert out == ref


@pytest.mark.parametrize("sharing", [True, False])
@pytest.mark.parametrize("chunk", [None, 4, 5])
def test_int8_serving_invariants_hold(sharing, chunk):
    """Prefix sharing and chunked prefill stay output-invariant under
    int8 pools (all runs quantize identically, so COW forks and chunk
    splits must still be bit-identical to one-shot no-sharing int8)."""
    cfg = get_config("gpt2_medium", smoke=True)
    params = api.init_params(KEY, cfg)
    prompts, new = _workload(cfg)
    base, _ = _drain_outputs(params, cfg, prompts, new,
                             page_size=4, prefix_sharing=False,
                             kv_cache_dtype="int8")
    out, eng = _drain_outputs(params, cfg, prompts, new,
                              page_size=4, prefix_sharing=sharing,
                              prefill_chunk_tokens=chunk,
                              kv_cache_dtype="int8")
    assert out == base
    if sharing:
        assert eng.prefill_tokens_saved > 0


def test_int8_fork_survives_donor_release_and_page_reuse():
    """The release-while-shared edge the int8 path stresses: a fully
    covered prompt COW-forks the donor's last prefix page (payload *and*
    scale row); the donor then finishes, its pages — and scale rows —
    are recycled by a fresh unrelated request, and the forked request
    must keep decoding off its private copies, matching its solo run."""
    cfg = get_config("gpt2_medium", smoke=True)
    params = api.init_params(KEY, cfg)
    gen = GenConfig(temperature=0.0, stop_on_eos=False)
    rng = np.random.RandomState(11)
    prefix = rng.randint(2, cfg.vocab, size=8)       # exactly 2 pages
    other = rng.randint(2, cfg.vocab, size=9)
    kw = dict(slots=2, max_len=32, gen=gen, page_size=4,
              kv_cache_dtype="int8")

    eng = ServingEngine(params, cfg, ENGINE, EngineConfig(**kw))
    u_donor = eng.submit(prefix.copy(), max_new_tokens=2)
    u_fork = eng.submit(prefix.copy(), max_new_tokens=12)  # fully covered
    eng.step()
    fork_req = next(r for r in eng.active
                    if r is not None and r.uid == u_fork)
    assert fork_req.shared_prompt_tokens == 8        # mapped both pages
    done = eng.run(max_steps=100)
    assert sorted(r.uid for r in done) == sorted([u_donor, u_fork])
    # Donor released mid-run; submit page-reusing traffic, drain it too.
    u_new = eng.submit(other.copy(), max_new_tokens=4)
    (r_new,) = eng.run(max_steps=100)
    assert r_new.uid == u_new

    by = {r.uid: r.generated for r in done}
    solo = {}
    for p, n, u in [(prefix, 2, u_donor), (prefix, 12, u_fork)]:
        e2 = ServingEngine(params, cfg, ENGINE, EngineConfig(**kw))
        e2.submit(p.copy(), max_new_tokens=n)
        (r2,) = e2.run(max_steps=100)
        solo[u] = r2.generated
    assert by[u_donor] == solo[u_donor]
    assert by[u_fork] == solo[u_fork]


# ---------------------------------------------------------------------------
# bf16 scale rows: (Dh + 2) B/vector instead of (Dh + 4)
# ---------------------------------------------------------------------------

def test_quantize_vec_bf16_scale_roundtrip_bound():
    """bf16 scale storage adds the scale's own rounding (<= 2^-9
    relative, so <= 127 * 2^-9 ~ 0.25 steps on the largest payload) to
    the half-step quantization error — still bounded per vector."""
    x = jax.random.normal(KEY, (4, 3, 32)) * 2.0
    q, scale = quantize_vec(x, scale_dtype=jnp.bfloat16)
    assert scale.dtype == jnp.bfloat16
    deq = dequantize_vec(q, scale, jnp.float32)
    step = np.asarray(jnp.max(jnp.abs(x), -1) / 127.0)
    bound = step * (0.5 + 127 * 2.0**-9) + 1e-6
    err = np.asarray(jnp.max(jnp.abs(deq - x), -1))
    assert (err <= bound).all(), (err.max(), bound.max())


def test_bf16_scale_ref_equals_fp_ref_on_bf16_roundtrip():
    """The bf16-scale oracle is exactly the fp oracle on the
    bf16-roundtripped pools — same elementwise-identity contract the
    f32 scale rows are held to, with scale rounding inside the
    envelope, not a second approximation."""
    ks = jax.random.split(KEY, 3)
    B, H, Hkv, D, page, npg = 2, 4, 2, 16, 8, 4
    P = 1 + B * npg
    rng = np.random.RandomState(0)
    tbl = jnp.asarray(
        rng.permutation(np.arange(1, P)).reshape(B, npg).astype(np.int32))
    kp = jax.random.normal(ks[0], (P, Hkv, page, D), jnp.float32)
    vp = jax.random.normal(ks[1], (P, Hkv, page, D), jnp.float32)
    q = jax.random.normal(ks[2], (B, H, D), jnp.float32)
    kq, ksc = quantize_vec(kp, scale_dtype=jnp.bfloat16)
    vq, vsc = quantize_vec(vp, scale_dtype=jnp.bfloat16)
    ksc, vsc = _rows(ksc), _rows(vsc)
    lens = jnp.asarray([9, 26], jnp.int32)
    got = ref_k.paged_attention_ref(q, kq, vq, tbl, lens, ksc, vsc)
    want = ref_k.paged_attention_ref(
        q, ref_k.kv_roundtrip_ref(kp, scale_dtype=jnp.bfloat16),
        ref_k.kv_roundtrip_ref(vp, scale_dtype=jnp.bfloat16), tbl, lens)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("entry", ["decode", "prefill"])
def test_bf16_scale_kernels_match_ref(entry):
    """Both Pallas kernels must accept bf16 scale rows (DMA'd in storage
    dtype, widened in VMEM) and still match the reference oracle."""
    q, kp, vp, _, _, _, _, tbl, lens = _paged_int8_setup(
        B=2, H=4, Hkv=2, D=128, page=16, npg=2, lengths=[13, 32])
    kq, ksc = quantize_vec(kp, scale_dtype=jnp.bfloat16)
    vq, vsc = quantize_vec(vp, scale_dtype=jnp.bfloat16)
    ksc, vsc = _rows(ksc), _rows(vsc)
    if entry == "decode":
        want = ops.pim_paged_attention(q, kq, vq, tbl, lens, ksc, vsc,
                                       impl="reference")
        got = ops.pim_paged_attention(q, kq, vq, tbl, lens, ksc, vsc,
                                      impl="interpret")
    else:
        qs = jax.random.normal(KEY, (2, 4, 4, 128), jnp.float32)
        st = jnp.asarray([9, 28], jnp.int32)
        want = ops.pim_paged_prefill_attention(
            qs, kq, vq, tbl, lens, st, ksc, vsc, impl="reference")
        got = ops.pim_paged_prefill_attention(
            qs, kq, vq, tbl, lens, st, ksc, vsc, impl="interpret")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


def test_page_kv_bytes_bf16_scale_rows():
    import dataclasses
    cfg = dataclasses.replace(get_config("qwen2_1_5b", smoke=True),
                              compute_dtype="bfloat16", head_dim=64)
    unit = cfg.n_layers * cfg.n_kv_heads * 16
    q8f = kv.page_kv_bytes(cfg, 16, "int8")
    q8b = kv.page_kv_bytes(cfg, 16, "int8", "bfloat16")
    assert q8f == 2 * unit * (cfg.head_dim + 4)
    assert q8b == 2 * unit * (cfg.head_dim + 2)      # payload + bf16 scale
    # bf16 scales never change fp pool sizing.
    assert kv.page_kv_bytes(cfg, 16, "model", "bfloat16") == \
        kv.page_kv_bytes(cfg, 16, "model")


def test_init_paged_cache_bf16_scale_pools_and_appends():
    """kv_scale_dtype=bfloat16 must build bf16 scale pools and both
    append paths must write scales in the pool's dtype."""
    cfg = get_config("gpt2_medium", smoke=True)
    cache = kv.init_paged_cache(cfg, 1, 4, 4, 2, kv_dtype="int8",
                                kv_scale_dtype="bfloat16")
    assert cache.k_scale.dtype == jnp.bfloat16
    assert cache.v_scale.dtype == jnp.bfloat16
    with pytest.raises(ValueError, match="kv_scale_dtype"):
        kv.init_paged_cache(cfg, 1, 4, 4, 2, kv_dtype="int8",
                            kv_scale_dtype="float16")
    Hkv, D = cfg.n_kv_heads, cfg.head_dim
    k_new = jax.random.normal(KEY, (1, Hkv, D))
    tables = jnp.array([[1, kv.TRASH_PAGE]], jnp.int32)
    kp, vp, ksc, vsc = kv.append_kv_pages(
        cache.k_pages[0], cache.v_pages[0], tables,
        jnp.zeros((1,), jnp.int32), k_new, k_new,
        cache.k_scale[0], cache.v_scale[0])
    assert ksc.dtype == jnp.bfloat16
    _, want_sc = quantize_vec(k_new, scale_dtype=jnp.bfloat16)
    np.testing.assert_array_equal(np.asarray(ksc[1, :, 0, 0]),
                                  np.asarray(want_sc[0]))
    kp2, vp2, ksc2, _ = kv.append_chunk_kv_pages(
        cache.k_pages[0], cache.v_pages[0], tables,
        jnp.zeros((1,), jnp.int32), k_new[:, None], k_new[:, None],
        cache.k_scale[0], cache.v_scale[0])
    assert ksc2.dtype == jnp.bfloat16
    np.testing.assert_array_equal(np.asarray(ksc2[1, :, 0, 0]),
                                  np.asarray(want_sc[0]))


def test_kv_scale_dtype_engine_validation():
    cfg = get_config("gpt2_medium", smoke=True)
    params = api.init_params(KEY, cfg)
    with pytest.raises(ValueError, match="scale"):
        ServingEngine(params, cfg, ENGINE, EngineConfig(
            slots=1, max_len=16, kv_scale_dtype="bfloat16"))  # fp pools
    eng = ServingEngine(params, cfg, ENGINE, EngineConfig(
        slots=1, max_len=16, kv_cache_dtype="int8", kv_scale_dtype="bfloat16"))
    assert eng.cache.k_scale.dtype == jnp.bfloat16
    # Byte-budget sizing sees the smaller pages: more of them fit the
    # same fp budget than with f32 scale rows.
    engf = ServingEngine(params, cfg, ENGINE, EngineConfig(
        slots=1, max_len=16, kv_cache_dtype="int8"))
    assert eng.allocator.num_pages >= engf.allocator.num_pages


def test_bf16_scale_serving_greedy_exact_match():
    """End-to-end: int8 pools with bf16 scale rows reproduce the fp
    engine's greedy outputs exactly on the serving test prompts (the
    added scale rounding stays below every argmax margin here)."""
    cfg = get_config("gpt2_medium", smoke=True)
    params = api.init_params(KEY, cfg)
    prompts, new = _workload(cfg)
    ref, _ = _drain_outputs(params, cfg, prompts, new, page_size=4)
    out, eng = _drain_outputs(params, cfg, prompts, new,
                              page_size=4, kv_cache_dtype="int8",
                              kv_scale_dtype="bfloat16")
    assert eng.cache.k_scale.dtype == jnp.bfloat16
    assert out == ref
