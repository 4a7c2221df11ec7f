"""Speculative decoding: drafters, verify pass, greedy acceptance,
in-pool rollback, and bit-identical serving with speculation on/off."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.salpim import SalPimConfig, SalPimEngine
from repro.kernels import ref as ref_k
from repro.models import api
from repro.serving.engine import EngineConfig, GenConfig, ServingEngine
from repro.serving.kvcache import TRASH_PAGE, BlockAllocator
from repro.serving.speculative import (DraftModelDrafter, NgramDrafter,
                                       SpecConfig, greedy_accept)
from tolerances import f32_reorder_tol

ENGINE = SalPimEngine.create(SalPimConfig())
KEY = jax.random.PRNGKey(0)


def _setup(arch="qwen2-1.5b"):
    cfg = get_config(arch, smoke=True)
    params = api.init_params(KEY, cfg)
    return cfg, params


def _self_draft(cfg, params, k=4):
    """Drafting with the target model itself: every proposal is the
    target's own greedy continuation, so acceptance is total — the
    deterministic upper bound that pins the acceptance machinery."""
    return SpecConfig(mode="draft-model", k=k, draft_cfg=cfg,
                      draft_params=params)


class WrongDrafter:
    """Adversarial drafter: proposes tokens guaranteed to be rejected
    (vocab - 1 - greedy is never the argmax). Exercises the rollback
    path on every round."""

    def __init__(self, vocab):
        self.vocab = vocab

    def propose(self, slot, context, k):
        return np.full((k,), -1, np.int64) % self.vocab  # vocab-1 garbage

    def release(self, slot):
        pass


def _drain(params, cfg, prompts, new, **kw):
    gen = kw.pop("gen", GenConfig(temperature=0.0, stop_on_eos=False))
    eng = ServingEngine(params, cfg, ENGINE, EngineConfig(
        slots=2, max_len=32, gen=gen, **kw))
    uids = [eng.submit(p.copy(), max_new_tokens=n)
            for p, n in zip(prompts, new)]
    done = eng.run(max_steps=600)
    assert sorted(r.uid for r in done) == sorted(uids)
    by = {r.uid: r.generated for r in done}
    assert eng.allocator.used_pages == 0, "leaked pages after drain"
    assert eng.allocator._reserved == 0, "leaked reservations"
    assert eng.allocator.free_pages == eng.allocator.num_pages - 1
    return [by[u] for u in uids], eng


def _workload(cfg, seed=0, n=4):
    rng = np.random.RandomState(seed)
    prompts = [rng.randint(2, cfg.vocab, size=rng.randint(4, 11))
               for _ in range(n)]
    new = [int(rng.randint(6, 14)) for _ in range(n)]
    return prompts, new


# ---------------------------------------------------------------------------
# Drafters
# ---------------------------------------------------------------------------

def test_ngram_drafter_proposes_continuation_of_most_recent_match():
    d = NgramDrafter(ngram_max=3, ngram_min=1)
    ctx = np.array([7, 8, 9, 4, 7, 8, 5, 7, 8])
    # Suffix [7, 8] occurs earlier at index 4 (-> 5) and index 0 (-> 9);
    # the most recent occurrence wins, so the proposal is [5, 7].
    np.testing.assert_array_equal(d.propose(0, ctx, 2), [5, 7])


def test_ngram_drafter_prefers_longest_ngram():
    d = NgramDrafter(ngram_max=3, ngram_min=1)
    ctx = np.array([1, 2, 3, 9, 5, 2, 3, 6, 2, 3])
    # The 3-gram suffix [6, 2, 3] has no earlier occurrence, so the
    # 2-gram [2, 3] decides — most recent match at index 5 -> [6, 2, 3].
    np.testing.assert_array_equal(d.propose(0, ctx, 3), [6, 2, 3])


def test_ngram_drafter_no_match_returns_empty():
    d = NgramDrafter(ngram_max=3, ngram_min=2)
    assert len(d.propose(0, np.array([1, 2, 3, 4]), 4)) == 0
    # Too-short context can't match (needs an *earlier* occurrence).
    assert len(d.propose(0, np.array([5]), 4)) == 0


def test_ngram_drafter_clamps_to_k():
    d = NgramDrafter(ngram_max=2, ngram_min=1)
    ctx = np.array([4, 1, 2, 3, 4, 5, 6, 1, 2, 3, 4])
    got = d.propose(0, ctx, 2)
    assert len(got) <= 2
    np.testing.assert_array_equal(got, [5, 6])


def test_draft_model_drafter_matches_target_greedy():
    """Self-draft: the drafter's proposals from a given context must be
    the target model's own greedy continuation of that context."""
    cfg, params = _setup()
    d = DraftModelDrafter(params, cfg, ENGINE, max_len=32, headroom=5)
    rng = np.random.RandomState(1)
    ctx = rng.randint(2, cfg.vocab, size=6)
    got = d.propose(0, ctx, 4)

    from repro.serving.engine import generate
    want, _ = generate(params, jnp.asarray(ctx[None]), cfg, ENGINE,
                       GenConfig(max_new_tokens=4, temperature=0.0,
                                 stop_on_eos=False))
    np.testing.assert_array_equal(got, np.asarray(want)[0])
    # Incremental catch-up: extend the context by the first two drafted
    # tokens — the continuation must still match the from-scratch run.
    ctx2 = np.concatenate([ctx, got[:2]])
    got2 = d.propose(0, ctx2, 2)
    np.testing.assert_array_equal(got2, got[2:4])
    d.release(0)
    assert 0 not in d._state


def test_draft_model_drafter_resets_on_context_change():
    cfg, params = _setup()
    d = DraftModelDrafter(params, cfg, ENGINE, max_len=32, headroom=5)
    rng = np.random.RandomState(2)
    a = rng.randint(2, cfg.vocab, size=6)
    b = rng.randint(2, cfg.vocab, size=6)
    first = d.propose(0, a, 3)
    del first
    got = d.propose(0, b, 3)       # slot reused by a different request
    d2 = DraftModelDrafter(params, cfg, ENGINE, max_len=32, headroom=5)
    np.testing.assert_array_equal(got, d2.propose(0, b, 3))


# ---------------------------------------------------------------------------
# Acceptance rule
# ---------------------------------------------------------------------------

def test_greedy_accept_longest_prefix_and_eos():
    g = np.array([5, 6, 7, 8])
    assert greedy_accept(np.array([5, 6, 9]), g, eos_id=0,
                         stop_on_eos=True) == (2, False)
    assert greedy_accept(np.array([5, 6, 7]), g, eos_id=0,
                         stop_on_eos=True) == (3, False)
    assert greedy_accept(np.array([4]), g, eos_id=0,
                         stop_on_eos=True) == (0, False)
    # Accepted EOS ends the request mid-round...
    g2 = np.array([5, 0, 7, 8])
    assert greedy_accept(np.array([5, 0, 7]), g2, eos_id=0,
                         stop_on_eos=True) == (2, True)
    # ...but only when EOS stops generation.
    assert greedy_accept(np.array([5, 0, 7]), g2, eos_id=0,
                         stop_on_eos=False) == (3, False)


def test_engine_acceptance_matches_ref_oracle():
    """The engine's in-loop acceptance must agree with the standalone
    kernels/ref oracle on the same verify logits."""
    rng = np.random.RandomState(3)
    logits = jnp.asarray(rng.randn(5, 11).astype(np.float32))
    greedy = np.asarray(jnp.argmax(logits, -1))
    for _ in range(20):
        drafts = rng.randint(0, 11, size=rng.randint(0, 5))
        a, _ = greedy_accept(drafts, greedy, eos_id=0, stop_on_eos=False)
        assert a == ref_k.greedy_accept_len_ref(drafts, logits)


# ---------------------------------------------------------------------------
# Serving equivalence: bit-identical greedy outputs, spec on/off
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def spec_env():
    cfg, params = _setup()
    prompts, new = _workload(cfg)
    ref, _ = _drain(params, cfg, prompts, new, page_size=4)
    return cfg, params, prompts, new, ref


@pytest.mark.parametrize("sharing", [True, False])
@pytest.mark.parametrize("kv_dtype", ["model", "int8"])
def test_spec_outputs_bit_identical(spec_env, sharing, kv_dtype):
    """Acceptance: greedy outputs bit-identical with speculation on/off,
    across {fp, int8} pools x {sharing on, off}."""
    cfg, params, prompts, new, ref = spec_env
    base_kw = dict(page_size=4, prefix_sharing=sharing,
                   kv_cache_dtype=kv_dtype)
    off, _ = _drain(params, cfg, prompts, new, **base_kw)
    on, eng = _drain(params, cfg, prompts, new,
                     speculative=SpecConfig(mode="ngram", k=4), **base_kw)
    assert on == off
    if kv_dtype == "model" and sharing:
        assert off == ref   # and the whole family matches plain paged


def test_spec_self_draft_accepts_everything(spec_env):
    """Draft-model speculation with the target as its own draft: every
    proposal is the target's greedy choice, so acceptance is 100% and
    rounds commit k+1 tokens whenever budget allows — while outputs
    stay bit-identical."""
    cfg, params, prompts, new, ref = spec_env
    out, eng = _drain(params, cfg, prompts, new, page_size=4,
                      speculative=_self_draft(cfg, params, k=3))
    assert out == ref
    st = eng.stats()
    assert st["proposed"] > 0
    assert st["accepted"] == st["proposed"]
    assert st["acceptance_rate"] == 1.0
    assert st["verify_passes"] < st["tokens"]
    assert st["verify_per_token"] < 1.0


def test_spec_all_rejected_still_bit_identical(spec_env):
    """An adversarial drafter (always wrong) degrades speculation to one
    token per verify pass — rollback every round — without changing a
    single output token or leaking a page."""
    cfg, params, prompts, new, ref = spec_env
    out, eng = _drain(params, cfg, prompts, new, page_size=4,
                      speculative=SpecConfig(mode="ngram", k=4))
    del out
    eng2 = ServingEngine(params, cfg, ENGINE, EngineConfig(
        slots=2, max_len=32, gen=GenConfig(temperature=0.0, stop_on_eos=False),
        page_size=4, speculative=SpecConfig(mode="ngram", k=4)))
    eng2.drafter = WrongDrafter(cfg.vocab)
    uids = [eng2.submit(p.copy(), max_new_tokens=n)
            for p, n in zip(prompts, new)]
    done = eng2.run(max_steps=600)
    by = {r.uid: r.generated for r in done}
    assert [by[u] for u in uids] == ref
    st = eng2.stats()
    assert st["accepted"] == 0
    assert st["proposed"] > 0
    assert eng2.allocator.used_pages == 0
    assert eng2.allocator._reserved == 0


def test_spec_with_chunked_prefill_and_sharing(spec_env):
    """Speculation composes with chunked prefill (mid-prefill slots never
    speculate — they are outside the decode batch) and prefix sharing."""
    cfg, params, prompts, new, ref = spec_env
    shared = [np.concatenate([prompts[0][:8], p]) for p in prompts]
    off, _ = _drain(params, cfg, shared, new, page_size=4,
                    prefill_chunk_tokens=4, prefix_sharing=True)
    on, eng = _drain(params, cfg, shared, new, page_size=4,
                     prefill_chunk_tokens=4, prefix_sharing=True,
                     speculative=SpecConfig(mode="ngram", k=4))
    assert on == off
    assert eng.prefill_tokens_saved > 0


def test_spec_stops_on_eos_inside_accepted_drafts():
    """An accepted draft equal to eos must end the request exactly as a
    sampled eos would — same generated list as the spec-off engine."""
    cfg, params = _setup()
    gen = GenConfig(temperature=0.0, stop_on_eos=True, eos_id=0)
    rng = np.random.RandomState(11)
    prompts = [rng.randint(2, cfg.vocab, size=6) for _ in range(3)]
    new = [12, 12, 12]
    off, _ = _drain(params, cfg, prompts, new, gen=gen, page_size=4)
    on, _ = _drain(params, cfg, prompts, new, gen=gen,
                   page_size=4, speculative=_self_draft(cfg, params, k=4))
    assert on == off


# ---------------------------------------------------------------------------
# In-pool rollback: page accounting
# ---------------------------------------------------------------------------

def test_allocator_rewind_is_inverse_of_extend():
    a = BlockAllocator(num_pages=16, page_size=4)
    pages = a.admit(1, prompt_tokens=4, max_new_tokens=12)
    assert pages is not None
    avail0 = a.available_pages
    used0 = a.used_pages
    got = [a.extend(1) for _ in range(3)]         # positions 4..15
    assert a.used_pages == used0 + 3
    assert a.available_pages == avail0            # drawn from reservation
    dropped = a.rewind(1, 5)                      # keep 2 pages (5 tokens)
    assert sorted(dropped) == sorted(got[1:])
    assert a.used_pages == used0 + 1
    assert a.available_pages == avail0            # watermark unchanged
    assert a.pages_of(1) == pages + got[:1]
    # Reuse after rewind: extend hands pages back out of the free list.
    again = [a.extend(1) for _ in range(2)]
    assert set(again) <= set(dropped) | set(range(1, 16))
    a.release(1)
    assert a.used_pages == 0
    assert a.available_pages == a.free_pages == 15


def test_allocator_rewind_refuses_shared_and_cached_pages():
    a = BlockAllocator(num_pages=16, page_size=2, prefix_sharing=True)
    toks = np.arange(4)
    res = a.admit_tokens(1, toks, max_new_tokens=4)
    assert res is not None
    # Both prompt pages are full -> registered in the prefix cache.
    with pytest.raises(AssertionError):
        a.rewind(1, 2)                 # would drop a cached prompt page
    res2 = a.admit_tokens(2, toks, max_new_tokens=4)  # shares both pages
    assert res2 is not None and res2[1] == 4
    with pytest.raises(AssertionError):
        a.rewind(2, 2)                 # would drop a shared page


def test_rewind_then_reuse_no_leak_no_double_free():
    """Accounting invariant across many extend/rewind cycles: pages in
    use + free always covers the pool, reservations never go negative,
    and a full release restores the empty-pool state."""
    a = BlockAllocator(num_pages=12, page_size=2)
    a.admit(7, prompt_tokens=2, max_new_tokens=16)
    for _ in range(5):
        grown = [a.extend(7) for _ in range(3)]
        del grown
        assert a.used_pages + a.free_pages == a.num_pages - 1
        a.rewind(7, 3)                # back to 2 pages
        assert a.used_pages + a.free_pages == a.num_pages - 1
        assert a._reserved >= 0
        assert len(set(a._free)) == len(a._free), "double-freed page"
    a.release(7)
    assert a.used_pages == 0 and a._reserved == 0
    assert sorted(a._free) == list(range(1, 12))


def test_engine_rewind_unmaps_device_tail_pages():
    """After a round with rejected drafts the slot's device block table
    must hold trash past the kept pages and its length must equal the
    accepted frontier."""
    cfg, params = _setup()
    gen = GenConfig(temperature=0.0, stop_on_eos=False)
    eng = ServingEngine(params, cfg, ENGINE, EngineConfig(
        slots=1, max_len=32, gen=gen, page_size=2,
        speculative=SpecConfig(mode="ngram", k=4)))
    eng.drafter = WrongDrafter(cfg.vocab)
    rng = np.random.RandomState(13)
    eng.submit(rng.randint(2, cfg.vocab, size=5), max_new_tokens=10)
    eng.step()                         # admit + prefill + first round
    eng.step()
    req = eng.active[0]
    assert req is not None
    n_mapped = len(eng.allocator.pages_of(req.uid))
    table = np.asarray(eng.cache.block_tables[0])
    assert (table[n_mapped:] == TRASH_PAGE).all()
    assert (table[:n_mapped] != TRASH_PAGE).all()
    assert int(eng.cache.lengths[0]) == int(eng._host_len[0])
    # Every rejected round rewound: with all drafts wrong, length grows
    # by exactly 1 per round past the prompt.
    assert int(eng.cache.lengths[0]) == 5 + len(req.generated) - 1 + 1


def test_spec_watermark_admission_unchanged():
    """Speculative rounds draw and return reservation pages; admission
    capacity (the watermark) must match the spec-off engine at every
    admission decision — same request stream admitted, same refusals."""
    cfg, params = _setup()
    gen = GenConfig(temperature=0.0, stop_on_eos=False)
    rng = np.random.RandomState(17)
    prompts = [rng.randint(2, cfg.vocab, size=8) for _ in range(4)]
    # A pool just big enough for ~2 concurrent requests.
    kw = dict(page_size=4, num_pages=13)
    outs = {}
    for label, spec in [("off", None),
                        ("on", _self_draft(cfg, params, k=3))]:
        eng = ServingEngine(params, cfg, ENGINE, EngineConfig(
            slots=2, max_len=24, gen=gen, speculative=spec, **kw))
        uids = [eng.submit(p.copy(), max_new_tokens=8) for p in prompts]
        done = eng.run(max_steps=400)
        assert sorted(r.uid for r in done) == sorted(uids)
        by = {r.uid: r.generated for r in done}
        outs[label] = [by[u] for u in uids]
        assert eng.allocator.used_pages == 0
        assert eng.allocator._reserved == 0
    assert outs["on"] == outs["off"]


# ---------------------------------------------------------------------------
# Config validation
# ---------------------------------------------------------------------------

def test_speculative_requires_greedy():
    cfg, params = _setup()
    with pytest.raises(ValueError, match="greedy"):
        ServingEngine(params, cfg, ENGINE, EngineConfig(
            slots=1, max_len=16, gen=GenConfig(temperature=1.0),
            speculative=SpecConfig()))


def test_spec_config_validation():
    with pytest.raises(ValueError, match="mode"):
        SpecConfig(mode="oracle").validate()
    with pytest.raises(ValueError, match="k"):
        SpecConfig(k=0).validate()
    with pytest.raises(ValueError, match="ngram"):
        SpecConfig(ngram_min=3, ngram_max=2).validate()
    with pytest.raises(ValueError, match="draft"):
        SpecConfig(mode="draft-model").validate()


def test_verify_tokens_rejects_encdec():
    cfg = get_config("whisper-large-v3", smoke=True)
    with pytest.raises(ValueError, match="encdec"):
        api.verify_tokens({}, None, None, None, None, None, cfg, ENGINE)


# ---------------------------------------------------------------------------
# verify_tokens: per-position logits equal the sequential decode logits
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("arch", ["qwen2-1.5b", "gpt2-medium"])
def test_verify_logits_match_sequential_decode(arch):
    """Row j of the verify logits must equal (to fp tolerance) the
    logits a decode step at that position would produce with the same
    resident KV — the foundation of exact greedy acceptance."""
    cfg, params = _setup(arch)
    page_size, max_pages = 4, 8
    B = 2
    cache = api.init_paged_cache(cfg, B, 32, page_size, max_pages)
    rng = np.random.RandomState(5)
    prompt = rng.randint(2, cfg.vocab, size=(B, 6))
    tables = np.full((B, max_pages), TRASH_PAGE, np.int32)
    tables[0, :3] = [1, 2, 3]
    tables[1, :3] = [4, 5, 6]
    bt = jnp.asarray(tables)
    logits, nk, nv = api.prefill_chunk(
        params, jnp.asarray(prompt), bt, jnp.zeros((B,), jnp.int32),
        cache.k_pages, cache.v_pages, cfg, ENGINE)

    from repro.serving.kvcache import PagedCache
    pc = PagedCache(jnp.full((B,), 6, jnp.int32), bt, nk, nv)
    # Sequential decode: 3 tokens, recording logits after each.
    toks, seq_logits = [], []
    la, pca = logits, pc
    for _ in range(3):
        t = jnp.argmax(la, -1).astype(jnp.int32)
        toks.append(np.asarray(t))
        la, pca = api.decode_step(params, t, pca, cfg, ENGINE)
        seq_logits.append(np.asarray(la))
    # One verify pass over the same 3 tokens from the same state.
    chunk = jnp.asarray(np.stack(toks, 1))
    vlog, vk, vv = api.verify_tokens(
        params, chunk, pc.block_tables, jnp.full((B,), 6, jnp.int32),
        pc.k_pages, pc.v_pages, cfg, ENGINE)
    vlog = np.asarray(vlog)
    # The 3-row verify pass and the 1-row decode steps sum their f32
    # contractions in shape-dependent orders (tests/tolerances.py).
    tol = f32_reorder_tol(cfg)
    for j in range(3):
        np.testing.assert_allclose(vlog[:, j], seq_logits[j],
                                   rtol=tol, atol=tol, err_msg=f"j={j}")
        np.testing.assert_array_equal(vlog[:, j].argmax(-1),
                                      seq_logits[j].argmax(-1))
    # And the KV the verify pass wrote equals the decode-written KV, to
    # the same bound: at 1e-6 one element missed by 1.25e-6 (qwen2 and
    # gpt2 smoke, CPU, xdist -n 2).
    np.testing.assert_allclose(np.asarray(vk), np.asarray(pca.k_pages),
                               rtol=tol, atol=tol)
    np.testing.assert_allclose(np.asarray(vv), np.asarray(pca.v_pages),
                               rtol=tol, atol=tol)


def test_spec_per_request_counters(spec_env):
    """Request.proposed/accepted sum to the engine aggregates and the
    acceptance report is consistent."""
    cfg, params, prompts, new, ref = spec_env
    out, eng = _drain(params, cfg, prompts, new, page_size=4,
                      speculative=SpecConfig(mode="ngram", k=4))
    del out
    reqs = eng.finished
    assert sum(r.proposed for r in reqs) == eng.spec_proposed
    assert sum(r.accepted for r in reqs) == eng.spec_accepted
    assert all(0 <= r.accepted <= r.proposed for r in reqs)
    st = eng.stats()
    assert st["proposed"] == eng.spec_proposed
    assert st["accepted"] == eng.spec_accepted
