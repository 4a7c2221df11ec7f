"""int4 page pools + KV-split (flash-decode) paged attention.

Two features that share the scale-row plumbing and the partial-softmax
merge respectively:

  * int4 pools: `quantize_vec_int4` packs two nibbles per byte (halves
    convention), both append paths pack at write time, kernels/oracles
    unpack+dequantize after the page DMA. Contract mirrors the int8
    suite (tests/test_paged_int8.py): kernel == fp oracle on
    roundtripped K/V *elementwise*, engine greedy outputs exact-match
    fp on the smoke workload, pool bytes >= 3.5x below fp.

  * KV-split: the block-table walk splits into K online-softmax
    partials merged by `merge_partial_softmax_stacked`. Property: the
    merge is permutation-invariant and matches the unsplit oracle
    within float tolerance, including empty (length-0 tail) splits.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.salpim import SalPimConfig, SalPimEngine
from repro.distributed.collectives import merge_partial_softmax_stacked
from repro.kernels import ops, ref as ref_k
from repro.kernels import paged_attention as paged_k
from repro.models import api
from repro.serving import kvcache as kv
from repro.serving.config import EngineConfig
from repro.serving.engine import GenConfig, ServingEngine
from repro.serving.quantize import (dequantize_vec_int4, pack_int4,
                                    quantize_vec_int4, unpack_int4)

import paged_cases

KEY = jax.random.PRNGKey(0)
ENGINE = SalPimEngine.create()


# ---------------------------------------------------------------------------
# int4 primitives
# ---------------------------------------------------------------------------

def test_pack_unpack_int4_roundtrip_and_convention():
    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.randint(-8, 8, (3, 5, 16)), jnp.int8)
    p = pack_int4(q)
    assert p.shape == (3, 5, 8) and p.dtype == jnp.int8
    np.testing.assert_array_equal(np.asarray(unpack_int4(p)), np.asarray(q))
    # Halves convention: byte i = (elem[i + D/2] << 4) | (elem[i] & 0xF).
    lo = np.asarray(q)[..., :8].astype(np.uint8) & 0x0F
    hi = np.asarray(q)[..., 8:].astype(np.uint8) & 0x0F
    np.testing.assert_array_equal(np.asarray(p).astype(np.uint8),
                                  (hi << 4) | lo)


def test_quantize_vec_int4_roundtrip_error_bound():
    x = jax.random.normal(KEY, (6, 4, 64), jnp.float32) * 3.0
    p, scale = quantize_vec_int4(x)
    assert p.shape == (6, 4, 32) and p.dtype == jnp.int8
    assert scale.shape == (6, 4) and scale.dtype == jnp.float32
    deq = dequantize_vec_int4(p, scale, jnp.float32)
    # Round-to-nearest at amax/7 steps: error <= half a step per element.
    err = jnp.abs(deq - x)
    bound = scale[..., None] * 0.5 + 1e-6
    assert bool(jnp.all(err <= bound))
    # Nibble range is the symmetric [-7, 7].
    u = unpack_int4(p)
    assert int(jnp.max(u)) <= 7 and int(jnp.min(u)) >= -7


def _paged_int4_setup(B, H, Hkv, D, page, npg, lengths, key=KEY):
    """fp pools plus their int4-quantized twins behind shuffled tables."""
    P = B * npg + 1
    ks = jax.random.split(key, 3)
    kp = jax.random.normal(ks[0], (P, Hkv, page, D), jnp.float32)
    vp = jax.random.normal(ks[1], (P, Hkv, page, D), jnp.float32)
    q = jax.random.normal(ks[2], (B, H, D), jnp.float32)
    rng = np.random.RandomState(0)
    tbl = jnp.asarray(rng.permutation(np.arange(1, P))[:B * npg]
                      .reshape(B, npg).astype(np.int32))
    kq, ksc = quantize_vec_int4(kp, scale_dtype=jnp.bfloat16)
    vq, vsc = quantize_vec_int4(vp, scale_dtype=jnp.bfloat16)
    lens = jnp.asarray(lengths, jnp.int32)
    # Scale rows in the pools' (P, Hkv, 1, page) layout.
    return q, kp, vp, kq, vq, ksc[:, :, None], vsc[:, :, None], tbl, lens


def test_int4_ref_equals_fp_ref_on_roundtripped_kv():
    """The int4 oracle on packed pools must be *elementwise identical*
    to the fp oracle on roundtripped (quantize->unpack->dequant) K/V —
    same math, same rounding, no extra tolerance."""
    q, kp, vp, kq, vq, ksc, vsc, tbl, lens = _paged_int4_setup(
        2, 8, 4, 32, 8, 5, [37, 12])
    out_q = ref_k.paged_attention_ref(q, kq, vq, tbl, lens, ksc, vsc)
    kr = ref_k.kv_roundtrip_int4_ref(kp, scale_dtype=jnp.bfloat16)
    vr = ref_k.kv_roundtrip_int4_ref(vp, scale_dtype=jnp.bfloat16)
    out_fp = ref_k.paged_attention_ref(q, kr, vr, tbl, lens)
    np.testing.assert_array_equal(np.asarray(out_q), np.asarray(out_fp))


@pytest.mark.parametrize("lengths", [[37, 12], [40, 0]])
def test_int4_decode_kernel_matches_ref(lengths):
    q, kp, vp, kq, vq, ksc, vsc, tbl, lens = _paged_int4_setup(
        2, 8, 4, 64, 8, 5, lengths)
    ref = ops.pim_paged_attention(q, kq, vq, tbl, lens, ksc, vsc,
                                  impl="reference")
    out = ops.pim_paged_attention(q, kq, vq, tbl, lens, ksc, vsc,
                                  impl="interpret")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=0, atol=1e-4)


@pytest.mark.parametrize("shape", paged_cases.SHAPES)
@pytest.mark.parametrize("window", [None, paged_cases.WINDOW])
def test_int4_walk_at_block_edges_reads_no_page_outside_it(shape, window):
    """The decode walk over nibble-packed pools with bf16 scale rows at
    its block edges, with NaN in every scale row the table maps outside
    a row's walk."""
    q, kp, vp, tbl, lens = paged_cases.pools(*paged_cases.SHAPES[shape])
    kq, ksc = quantize_vec_int4(kp, scale_dtype=jnp.bfloat16)
    vq, vsc = quantize_vec_int4(vp, scale_dtype=jnp.bfloat16)
    ksc, vsc = ksc[:, :, None], vsc[:, :, None]
    want = ref_k.paged_attention_ref(q, kq, vq, tbl, lens, ksc, vsc,
                                     window=window)
    got = ops.pim_paged_attention(
        q, kq, vq, tbl, lens, paged_cases.poison(ksc, tbl, window),
        paged_cases.poison(vsc, tbl, window), window=window,
        impl="interpret")
    assert bool(jnp.all(jnp.isfinite(got)))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=0, atol=1e-4)


def test_int4_prefill_kernel_matches_ref():
    """Chunked-prefill attention (interpret) over int4 pools: the kernel
    nibble-unpacks after the page DMA and must match the oracle."""
    from repro.kernels.paged_prefill import paged_prefill_attention
    B, Sq, H, Hkv, D, page, npg = 2, 3, 8, 4, 64, 8, 5
    _q, kp, vp, kq, vq, ksc, vsc, tbl, lens = _paged_int4_setup(
        B, H, Hkv, D, page, npg, [37, 12])
    qc = jax.random.normal(jax.random.PRNGKey(3), (B, Sq, H, D),
                           jnp.float32)
    start = lens - Sq
    ref = ref_k.paged_prefill_attention_ref(qc, kq, vq, tbl, lens, start,
                                            ksc, vsc)
    out = paged_prefill_attention(qc, kq, vq, tbl, lens, start, ksc, vsc,
                                  interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=0, atol=1e-4)


def test_int4_append_kv_pages_packs_at_write():
    cfg = get_config("gpt2_medium", smoke=True)
    assert cfg.head_dim % 2 == 0
    cache = kv.init_paged_cache(cfg, batch=2, num_pages=6, page_size=4,
                                max_pages=3, kv_dtype="int4",
                                kv_scale_dtype="bfloat16")
    assert cache.k_pages.shape[-1] == cfg.head_dim // 2
    assert cache.k_scale.dtype == jnp.bfloat16
    tables = jnp.asarray([[2, 1, 3], [4, 5, 0]], jnp.int32)
    lengths = jnp.asarray([7, 4], jnp.int32)   # page 1 off 3; page 5 off 0
    Hkv, Dh = cfg.n_kv_heads, cfg.head_dim
    k_new = jax.random.normal(KEY, (2, Hkv, Dh), jnp.float32)
    v_new = -k_new
    kp, vp, ksc, vsc = kv.append_kv_pages(
        cache.k_pages[0], cache.v_pages[0], tables, lengths, k_new, v_new,
        cache.k_scale[0], cache.v_scale[0])
    exp_k, exp_ks = quantize_vec_int4(k_new, scale_dtype=jnp.bfloat16)
    np.testing.assert_array_equal(np.asarray(kp[1, :, 3]),
                                  np.asarray(exp_k[0]))
    np.testing.assert_array_equal(np.asarray(kp[5, :, 0]),
                                  np.asarray(exp_k[1]))
    np.testing.assert_array_equal(np.asarray(ksc[1, :, 0, 3]),
                                  np.asarray(exp_ks[0]))
    exp_v, exp_vs = quantize_vec_int4(v_new, scale_dtype=jnp.bfloat16)
    np.testing.assert_array_equal(np.asarray(vp[5, :, 0]),
                                  np.asarray(exp_v[1]))
    np.testing.assert_array_equal(np.asarray(vsc[5, :, 0, 0]),
                                  np.asarray(exp_vs[1]))


def test_int4_append_chunk_packs_at_write():
    cfg = get_config("gpt2_medium", smoke=True)
    cache = kv.init_paged_cache(cfg, batch=1, num_pages=5, page_size=4,
                                max_pages=3, kv_dtype="int4",
                                kv_scale_dtype="bfloat16")
    tables = jnp.asarray([[2, 3, 1]], jnp.int32)
    Hkv, Dh = cfg.n_kv_heads, cfg.head_dim
    k_new = jax.random.normal(KEY, (1, 6, Hkv, Dh), jnp.float32)
    start = jnp.asarray([2], jnp.int32)        # spans pages 2 and 3
    kp, vp, ksc, vsc = kv.append_chunk_kv_pages(
        cache.k_pages[0], cache.v_pages[0], tables, start, k_new, -k_new,
        cache.k_scale[0], cache.v_scale[0])
    exp_k, exp_ks = quantize_vec_int4(k_new, scale_dtype=jnp.bfloat16)
    # token 0 -> pos 2 = page idx 0 (phys 2) off 2; token 3 -> pos 5 =
    # page idx 1 (phys 3) off 1.
    np.testing.assert_array_equal(np.asarray(kp[2, :, 2]),
                                  np.asarray(exp_k[0, 0]))
    np.testing.assert_array_equal(np.asarray(kp[3, :, 1]),
                                  np.asarray(exp_k[0, 3]))
    np.testing.assert_array_equal(np.asarray(ksc[3, :, 0, 1]),
                                  np.asarray(exp_ks[0, 3]))


def test_page_kv_bytes_int4_at_least_3_5x_below_fp():
    # bf16 fp pools at Dh=64: 2*64 / (64/2 + 2) = 128/34 = 3.76x; the
    # f32 smoke configs are 256/34 = 7.5x. Both clear the 3.5x gate.
    cfg = dataclasses.replace(get_config("qwen2_1_5b", smoke=True),
                              compute_dtype="bfloat16", head_dim=64)
    fp = kv.page_kv_bytes(cfg, 16, "model")
    q4 = kv.page_kv_bytes(cfg, 16, "int4", "bfloat16")
    unit = cfg.n_layers * cfg.n_kv_heads * 16
    assert q4 == 2 * unit * (cfg.head_dim // 2 + 2)
    assert fp / q4 >= 3.5, (fp, q4)
    q8 = kv.page_kv_bytes(cfg, 16, "int8", "bfloat16")
    assert q8 / q4 >= 1.9, (q8, q4)   # half of int8's bytes again


def test_int4_validation_rules():
    cfg = get_config("gpt2_medium", smoke=True)
    params = api.init_params(KEY, cfg)
    # int4 without bf16 scales is refused (f32 scales would spend the
    # bytes the packing saved).
    with pytest.raises(ValueError, match="bfloat16"):
        ServingEngine(params, cfg, ENGINE, config=EngineConfig(
            slots=1, max_len=16, paged=True, kv_cache_dtype="int4"))
    # Odd head_dim cannot nibble-pack.
    odd = dataclasses.replace(cfg, head_dim=cfg.head_dim + 1)
    with pytest.raises(ValueError, match="even head_dim"):
        EngineConfig(slots=1, max_len=16, paged=True,
                     kv_cache_dtype="int4",
                     kv_scale_dtype="bfloat16").validate(odd)
    with pytest.raises(ValueError, match="even head_dim"):
        kv.init_paged_cache(odd, 1, 4, 4, 2, kv_dtype="int4",
                            kv_scale_dtype="bfloat16")


def test_int4_default_pool_at_least_3_5x_capacity():
    """num_pages=None keeps the fp byte budget: the int4 pool must hold
    >= 3.5x the pages (f32 smoke configs give 6.4x at Dh=16)."""
    cfg = get_config("gpt2_medium", smoke=True)
    params = api.init_params(KEY, cfg)
    engf = ServingEngine(params, cfg, ENGINE, config=EngineConfig(
        slots=4, max_len=32, paged=True, page_size=4))
    eng4 = ServingEngine(params, cfg, ENGINE, config=EngineConfig(
        slots=4, max_len=32, paged=True, page_size=4,
        kv_cache_dtype="int4", kv_scale_dtype="bfloat16"))
    usable_f = engf.allocator.num_pages - 1
    usable_4 = eng4.allocator.num_pages - 1
    assert usable_4 >= 3.5 * usable_f, (usable_4, usable_f)
    assert usable_4 * eng4.page_bytes <= usable_f * engf.page_bytes


def _int4_workload(cfg):
    """The int4 smoke workload (also bench part 9's): independent random
    prompts with per-request output budgets, fixed seed."""
    rng = np.random.RandomState(9)
    prompts = [rng.randint(2, cfg.vocab, size=s) for s in (6, 4, 17, 11)]
    return prompts, [4, 3, 4, 3]


def _drain_outputs(params, cfg, prompts, new, **kw):
    gen = GenConfig(temperature=0.0, stop_on_eos=False)
    eng = ServingEngine(params, cfg, ENGINE, config=EngineConfig(
        slots=2, max_len=32, gen=gen, **kw))
    uids = [eng.submit(p.copy(), max_new_tokens=n)
            for p, n in zip(prompts, new)]
    done = eng.run(max_steps=600)
    assert sorted(r.uid for r in done) == sorted(uids)
    assert eng.allocator.used_pages == 0
    by = {r.uid: r.generated for r in done}
    return [by[u] for u in uids], eng


def _paged_logits(params, cfg, prompt, forced, chunk, kv_dtype):
    """Teacher-forced logits through the serving path's paged programs:
    prefill `prompt` in `chunk`-token chunks (None = one chunk) into a
    4-token-page pool, then decode the `forced` tokens. Returns
    (1 + len(forced), V): the first generated position, then each step."""
    page = 4
    n = -(-(len(prompt) + len(forced)) // page)
    cache = api.init_paged_cache(
        cfg, 1, n + 1, page, n, kv_dtype=kv_dtype,
        kv_scale_dtype="bfloat16" if kv_dtype == "int4" else "float32")
    table = jnp.arange(1, n + 1, dtype=jnp.int32)[None]
    kp, vp, ks, vs = cache.k_pages, cache.v_pages, cache.k_scale, cache.v_scale
    step = chunk or len(prompt)
    for st in range(0, len(prompt), step):
        logits, kp, vp, *scales = api.prefill_chunk(
            params, jnp.asarray(prompt[st:st + step])[None], table,
            jnp.asarray([st], jnp.int32), kp, vp, cfg, ENGINE, ks, vs)
        ks, vs = scales or (None, None)
    cache = kv.PagedCache(jnp.asarray([len(prompt)], jnp.int32), table,
                          kp, vp, ks, vs)
    out = [logits[0]]
    for t in forced:
        logits, cache = api.decode_step(
            params, jnp.asarray([t], jnp.int32), cache, cfg, ENGINE)
        out.append(logits[0])
    return jnp.stack(out)


@pytest.mark.parametrize("chunk", [None, 4, 5])
def test_int4_serving_greedy_exact_match(chunk):
    """Acceptance: the int4 engine drains the smoke workload with packed
    pools in use at any prefill chunking, and its logits stay within a
    stated band of the fp paged path's.

    The logits are compared teacher-forced on the fp engine's greedy
    tokens, not as sampled tokens: a lossy pool under random weights can
    flip a near-tied argmax, after which the two token streams (and
    everything compared after them) diverge for no fault of the kernels.

    Band: relative L2 error <= 0.25 per request, over the first
    generated position and every forced decode step. int4 stores each
    K/V vector to amax/7 — 127/7 ~ 18x int8's step — and measures
    0.10-0.13 here; the same model on an unrelated
    context (the prompt reversed) differs by 0.63-0.81, so a wrong
    nibble, page or scale row fails the band by 2.5x.
    """
    cfg = get_config("gpt2_medium", smoke=True)
    params = api.init_params(KEY, cfg)
    prompts, new = _int4_workload(cfg)
    ref, _ = _drain_outputs(params, cfg, prompts, new, paged=True,
                            page_size=4)
    out, eng = _drain_outputs(params, cfg, prompts, new, paged=True,
                              page_size=4, prefill_chunk_tokens=chunk,
                              kv_cache_dtype="int4",
                              kv_scale_dtype="bfloat16")
    assert eng.cache.k_pages.dtype == jnp.int8
    assert eng.cache.k_pages.shape[-1] == cfg.head_dim // 2
    assert [len(t) for t in out] == new
    for prompt, forced in zip(prompts, ref):
        fp = _paged_logits(params, cfg, prompt, forced[:-1], chunk, "model")
        q4 = _paged_logits(params, cfg, prompt, forced[:-1], chunk, "int4")
        # The fp paged path reproduces the engine's greedy tokens.
        assert [int(t) for t in jnp.argmax(fp, -1)] == forced
        rel = float(jnp.linalg.norm(q4 - fp) / jnp.linalg.norm(fp))
        assert rel <= 0.25, (len(prompt), rel)


# ---------------------------------------------------------------------------
# merge_partial_softmax_stacked: the KV-split combine property
# ---------------------------------------------------------------------------

def _partials_from_chunks(scores, values, bounds):
    """Online-softmax partials (m, l, acc) for each [lo, hi) chunk of a
    dense (G, S) score matrix — what one KV split computes."""
    parts = []
    for lo, hi in bounds:
        s = scores[:, lo:hi]
        if s.shape[1] == 0 or bool(jnp.all(s <= -1e30)):
            g = scores.shape[0]
            parts.append((jnp.full((g, 1), -1e30), jnp.zeros((g, 1)),
                          jnp.zeros((g, values.shape[1]))))
            continue
        m = jnp.max(s, axis=-1, keepdims=True)
        e = jnp.where(s <= -1e30, 0.0, jnp.exp(s - m))
        l = jnp.sum(e, axis=-1, keepdims=True)
        acc = e @ values[lo:hi]
        parts.append((m, l, acc))
    return parts


@pytest.mark.parametrize("n_chunks", [2, 5, 9])
def test_merge_partial_softmax_stacked_permutation_invariant(n_chunks):
    """Merging K partial (m, l, acc) triples gives the same result for
    every ordering of the splits, and matches softmax(V) computed
    without splitting — including an empty (fully masked) split."""
    rng = np.random.RandomState(7)
    G, S, D = 4, 40, 16
    scores = jnp.asarray(rng.randn(G, S) * 3, jnp.float32)
    values = jnp.asarray(rng.randn(S, D), jnp.float32)
    # Unsplit oracle: plain softmax(scores) @ values.
    e = jnp.exp(scores - jnp.max(scores, axis=-1, keepdims=True))
    expect = (e / jnp.sum(e, axis=-1, keepdims=True)) @ values

    cuts = sorted(rng.choice(S - 1, size=n_chunks - 1, replace=False) + 1)
    bounds = list(zip([0] + cuts, cuts + [S]))
    bounds.append((S, S))                       # empty length-0 tail split
    parts = _partials_from_chunks(scores, values, bounds)
    for perm in [list(range(len(parts))),
                 list(reversed(range(len(parts)))),
                 list(rng.permutation(len(parts)))]:
        m = jnp.stack([parts[i][0] for i in perm])
        l = jnp.stack([parts[i][1] for i in perm])
        acc = jnp.stack([parts[i][2] for i in perm])
        got = merge_partial_softmax_stacked(m, l, acc, axis=0)
        np.testing.assert_allclose(np.asarray(got), np.asarray(expect),
                                   rtol=0, atol=1e-5)


def test_merge_all_empty_splits_is_zero_not_nan():
    g, d = 3, 8
    m = jnp.full((4, g, 1), -1e30)
    l = jnp.zeros((4, g, 1))
    acc = jnp.zeros((4, g, d))
    out = merge_partial_softmax_stacked(m, l, acc, axis=0)
    assert bool(jnp.all(out == 0.0)) and bool(jnp.all(jnp.isfinite(out)))


# ---------------------------------------------------------------------------
# KV-split paged attention: ref and kernel vs the unsplit oracle
# ---------------------------------------------------------------------------

def test_effective_kv_splits_gating():
    # Engages only when asked, above the context threshold, clamped.
    assert paged_k.effective_kv_splits(None, 128, 16) is None
    assert paged_k.effective_kv_splits(1, 128, 16) is None
    assert paged_k.effective_kv_splits(8, 16, 16) is None      # 256 tokens
    assert paged_k.effective_kv_splits(8, 64, 16) == 8         # 1024 tokens
    assert paged_k.effective_kv_splits(999, 64, 16) == 64      # clamp
    assert paged_k.KV_SPLIT_MIN_CONTEXT == 1024


@pytest.mark.parametrize("kv_splits", [2, 5, 64])
@pytest.mark.parametrize("lengths", [[157, 43], [160, 0], [1, 160]])
def test_split_ref_matches_unsplit_oracle(kv_splits, lengths):
    q, kp, vp, _kq, _vq, _ks, _vs, tbl, lens = _paged_int4_setup(
        2, 8, 4, 32, 8, 20, lengths)
    ref = ref_k.paged_attention_ref(q, kp, vp, tbl, lens)
    out = ref_k.paged_attention_split_ref(q, kp, vp, tbl, lens,
                                          kv_splits=kv_splits)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=0, atol=1e-5)


def test_split_ref_softcap_window_and_int4_pool():
    q, kp, vp, kq, vq, ksc, vsc, tbl, lens = _paged_int4_setup(
        2, 8, 4, 32, 8, 20, [155, 80])
    ref = ref_k.paged_attention_ref(q, kp, vp, tbl, lens,
                                    softcap=30.0, window=100)
    out = ref_k.paged_attention_split_ref(q, kp, vp, tbl, lens,
                                          kv_splits=7, softcap=30.0,
                                          window=100)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=0, atol=1e-5)
    ref4 = ref_k.paged_attention_ref(q, kq, vq, tbl, lens, ksc, vsc)
    out4 = ref_k.paged_attention_split_ref(q, kq, vq, tbl, lens, ksc, vsc,
                                           kv_splits=7)
    np.testing.assert_allclose(np.asarray(out4), np.asarray(ref4),
                               rtol=0, atol=1e-5)


@pytest.mark.parametrize("quantized", [False, True])
@pytest.mark.parametrize("kv_splits", [8, 7])   # even and trash-padded
def test_split_kernel_matches_ref_interpret(quantized, kv_splits):
    """The 4D-grid Pallas kernel (interpret mode) through the partials
    combine must match the unsplit oracle at a context long enough to
    engage splitting (72 pages * 16 = 1152 >= KV_SPLIT_MIN_CONTEXT)."""
    q, kp, vp, kq, vq, ksc, vsc, tbl, lens = _paged_int4_setup(
        2, 4, 2, 32, 16, 72, [1147, 900])
    if quantized:
        kp_t, vp_t, sc = kq, vq, (ksc, vsc)
    else:
        kp_t, vp_t, sc = kp, vp, (None, None)
    ref = ref_k.paged_attention_ref(q, kp_t, vp_t, tbl, lens, *sc)
    out = paged_k.paged_attention(q, kp_t, vp_t, tbl, lens, *sc,
                                  kv_splits=kv_splits, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=0, atol=1e-4)


def test_ops_dispatch_kv_splits_reference():
    q, kp, vp, _kq, _vq, _ks, _vs, tbl, lens = _paged_int4_setup(
        2, 4, 2, 32, 16, 72, [1100, 512])
    ref = ops.pim_paged_attention(q, kp, vp, tbl, lens, impl="reference")
    out = ops.pim_paged_attention(q, kp, vp, tbl, lens, kv_splits=16,
                                  impl="reference")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=0, atol=1e-5)
    # Below the threshold the knob is a no-op: bit-identical single walk.
    q2, kp2, vp2, _kq2, _vq2, _ks2, _vs2, tbl2, lens2 = _paged_int4_setup(
        2, 4, 2, 32, 8, 5, [37, 12])
    a = ops.pim_paged_attention(q2, kp2, vp2, tbl2, lens2, impl="reference")
    b = ops.pim_paged_attention(q2, kp2, vp2, tbl2, lens2, kv_splits=16,
                                impl="reference")
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_salpim_config_kv_splits_dispatch():
    """SalPimConfig.kv_splits routes paged_decode_attention through the
    split reference at long context — same result, split math."""
    q, kp, vp, _kq, _vq, _ks, _vs, tbl, lens = _paged_int4_setup(
        2, 4, 2, 32, 16, 72, [1100, 512])
    plain = SalPimEngine.create()
    split = SalPimEngine.create(SalPimConfig(kv_splits=8))
    a = plain.paged_decode_attention(q, kp, vp, tbl, lens)
    b = split.paged_decode_attention(q, kp, vp, tbl, lens)
    np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                               rtol=0, atol=1e-5)


def test_kv_splits_validation_and_engine_threading():
    cfg = get_config("gpt2_medium", smoke=True)
    params = api.init_params(KEY, cfg)
    with pytest.raises(ValueError, match="kv_splits"):
        EngineConfig(slots=1, max_len=16, paged=True,
                     kv_splits=0).validate(cfg)
    with pytest.raises(ValueError, match="paged"):
        EngineConfig(slots=1, max_len=16, kv_splits=4).validate(cfg)
    eng = ServingEngine(params, cfg, ENGINE, config=EngineConfig(
        slots=1, max_len=16, paged=True, kv_splits=4))
    assert eng.engine.config.kv_splits == 4


def test_kv_splits_engine_drain_matches_baseline():
    """EngineConfig(kv_splits=...) must not change greedy outputs (the
    smoke context sits below KV_SPLIT_MIN_CONTEXT, so the knob resolves
    to the identical single walk — the safe-autotune contract)."""
    cfg = get_config("gpt2_medium", smoke=True)
    params = api.init_params(KEY, cfg)
    prompts, new = _int4_workload(cfg)
    base, _ = _drain_outputs(params, cfg, prompts, new, paged=True,
                             page_size=4)
    out, eng = _drain_outputs(params, cfg, prompts, new, paged=True,
                              page_size=4, kv_splits=8)
    assert eng.engine.config.kv_splits == 8
    assert out == base
