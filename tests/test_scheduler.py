"""Scheduler layer: FIFO bit-identity, SLO preempt-and-swap, and the
tiered page store (optimistic admission, host swap tier, prefix
pinning, swap roundtrip exactness)."""
from __future__ import annotations

import dataclasses

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.salpim import SalPimConfig, SalPimEngine
from repro.models import api
from repro.serving import kvcache as kv
from repro.serving.engine import EngineConfig, GenConfig, ServingEngine
from repro.serving.kvcache import TRASH_PAGE, BlockAllocator
from repro.serving.scheduler import FifoScheduler, Scheduler, SloScheduler
from repro.serving.speculative import SpecConfig
from repro.serving.telemetry import Telemetry

ENGINE = SalPimEngine.create(SalPimConfig())
KEY = jax.random.PRNGKey(0)


def _setup(arch="qwen2-1.5b"):
    cfg = get_config(arch, smoke=True)
    params = api.init_params(KEY, cfg)
    return cfg, params


def _workload(cfg, seed=0, n=4):
    rng = np.random.RandomState(seed)
    prompts = [rng.randint(2, cfg.vocab, size=rng.randint(6, 11))
               for _ in range(n)]
    new = [int(rng.randint(8, 13)) for _ in range(n)]
    return prompts, new


def _drain(params, cfg, prompts, new, priorities=None, **kw):
    gen = kw.pop("gen", GenConfig(temperature=0.0, stop_on_eos=False))
    eng = ServingEngine(params, cfg, ENGINE, EngineConfig(
        max_len=32, gen=gen, page_size=4, **kw))
    prios = priorities or [0] * len(prompts)
    uids = [eng.submit(p.copy(), max_new_tokens=n, priority=pr)
            for p, n, pr in zip(prompts, new, prios)]
    done = eng.run(max_steps=800)
    assert sorted(r.uid for r in done) == sorted(uids)
    by = {r.uid: r.generated for r in done}
    assert eng.allocator.used_pages == 0, "leaked pages after drain"
    assert eng.allocator._reserved == 0, "leaked reservations"
    assert len(eng.swap_tier) == 0, "leaked swap blobs"
    return [by[u] for u in uids], eng


# ---------------------------------------------------------------------------
# Protocol
# ---------------------------------------------------------------------------

def test_schedulers_satisfy_protocol():
    assert isinstance(FifoScheduler(), Scheduler)
    assert isinstance(SloScheduler(), Scheduler)
    assert FifoScheduler().reserve and not FifoScheduler().preemptive
    assert SloScheduler().preemptive and not SloScheduler().reserve


# ---------------------------------------------------------------------------
# Allocator: optimistic admission mode
# ---------------------------------------------------------------------------

def test_optimistic_admission_reserves_nothing():
    a = BlockAllocator(num_pages=16, page_size=4)
    res = a.admit_tokens(1, np.arange(6), max_new_tokens=20, reserve=False)
    assert res is not None and len(res[0]) == 2
    assert a._reserved == 0                 # nothing reserved ahead
    assert a.free_pages == 13
    assert a.available_pages == 13          # watermark == free list
    p = a.extend(1)                         # draws from the live free list
    assert p not in res[0] and a.free_pages == 12 and a._reserved == 0
    a.release(1)
    assert a.used_pages == 0 and a.free_pages == 15


def test_optimistic_admits_what_watermark_refuses():
    # Worst case (6 pages) exceeds the pool's watermark, but the prompt
    # itself (2 pages) fits now — optimistic admission takes the bet.
    a = BlockAllocator(num_pages=4, page_size=4)
    assert a.admit_tokens(1, np.arange(8), max_new_tokens=16) is None
    res = a.admit_tokens(1, np.arange(8), max_new_tokens=16, reserve=False)
    assert res is not None and len(res[0]) == 2
    a.release(1)


def test_optimistic_extend_asserts_on_dry_pool():
    a = BlockAllocator(num_pages=3, page_size=4)
    res = a.admit_tokens(1, np.arange(8), max_new_tokens=4, reserve=False)
    assert res is not None and a.free_pages == 0
    with pytest.raises(AssertionError, match="dry pool"):
        a.extend(1)                         # the engine must preempt first
    a.release(1)


def test_release_mixed_modes_restores_pool():
    a = BlockAllocator(num_pages=16, page_size=4)
    assert a.admit_tokens(1, np.arange(6), max_new_tokens=8) is not None
    assert a.admit_tokens(2, np.arange(6), max_new_tokens=8,
                          reserve=False) is not None
    a.extend(2)
    a.release(1)
    a.release(2)
    assert a.used_pages == 0 and a._reserved == 0 and a.free_pages == 15


# ---------------------------------------------------------------------------
# Allocator: prefix pinning
# ---------------------------------------------------------------------------

def test_pin_budget_zero_frees_like_before():
    a = BlockAllocator(num_pages=16, page_size=2, prefix_sharing=True)
    a.admit_tokens(1, np.arange(6), max_new_tokens=2)
    a.release(1)
    assert a.pinned_pages == 0 and a.used_pages == 0
    assert a.free_pages == 15               # historical behavior intact


def test_pin_lifecycle_and_revival():
    a = BlockAllocator(num_pages=16, page_size=2, prefix_sharing=True,
                       pin_budget_pages=2)
    toks = np.arange(6)                     # 3 full (registered) pages
    res = a.admit_tokens(1, toks, max_new_tokens=2)
    pages = res[0]
    a.release(1)
    # Budget 2: first two pins land, the third page frees (cache entry
    # dropped with it).
    assert a.pinned_pages == 2
    assert a.free_pages == 15 - 2
    assert all(a.refcount(p) == 0 for p in pages[:2])
    # A matching admission revives the pinned pages in place.
    res2 = a.admit_tokens(2, toks, max_new_tokens=2)
    assert res2[1] == 4                     # only 2 pages survived pinning
    assert res2[0][:2] == pages[:2]
    assert a.pinned_pages == 0
    assert all(a.refcount(p) == 1 for p in pages[:2])
    a.release(2)


def test_reclaim_pinned_oldest_first_with_protect():
    a = BlockAllocator(num_pages=16, page_size=2, prefix_sharing=True,
                       pin_budget_pages=8)
    a.admit_tokens(1, np.arange(6), max_new_tokens=2)
    p0, p1, p2 = a.pages_of(1)
    a.release(1)
    assert a.pinned_pages == 3
    assert a.reclaim_pinned(1) == 1
    assert p0 not in a._pinned              # oldest pin evicted first
    assert a.reclaim_pinned(1, protect=frozenset((p1,))) == 1
    assert p1 in a._pinned and p2 not in a._pinned
    # Evicted pins are gone from the cache: re-admission shares nothing
    # past the protected page... which is page index 1, so no hit chain.
    res = a.admit_tokens(2, np.arange(6), max_new_tokens=2)
    assert res[1] == 0
    a.release(2)


def test_pins_auto_reclaimed_on_admission_shortage():
    a = BlockAllocator(num_pages=6, page_size=2, prefix_sharing=True,
                       pin_budget_pages=8)
    a.admit_tokens(1, np.arange(6), max_new_tokens=2)
    a.release(1)
    assert a.pinned_pages == 3 and a.free_pages == 2
    # A disjoint prompt needing 4 pages forces reclaim of 2 pins.
    res = a.admit_tokens(2, np.arange(100, 108), max_new_tokens=0)
    assert res is not None and res[1] == 0
    assert a.pinned_pages == 1
    a.release(2)


# ---------------------------------------------------------------------------
# Allocator: admission probe (the feasibility guard's oracle)
# ---------------------------------------------------------------------------

def test_admission_probe_matches_admit_and_does_not_mutate():
    a = BlockAllocator(num_pages=16, page_size=2, prefix_sharing=True)
    donor = np.arange(8)
    a.admit_tokens(1, donor, max_new_tokens=2)      # 4 cached pages, live
    cases = [(np.concatenate([donor, [99, 98]]), 4),   # partial prefix hit
             (donor[:5], 2),                           # hit + partial tail
             (np.arange(50, 60), 4)]                   # disjoint
    for toks, new in cases:
        for reserve in (True, False):
            before = (list(a._free), dict(a._ref))
            need, _ = a.admission_probe(toks, new, reserve=reserve)
            assert (list(a._free), dict(a._ref)) == before   # pure lookup
            avail0, free0 = a.available_pages, a.free_pages
            res = a.admit_tokens(9, toks, new, reserve=reserve)
            assert res is not None
            # The probe's need is exactly what admission charges: the
            # watermark drop in reserve mode, the free-list draw in
            # optimistic mode (no fully-covered prompts here — their +1
            # COW page is checked, not drawn; see the fork test).
            charged = (avail0 - a.available_pages if reserve
                       else free0 - a.free_pages)
            assert charged == need, (toks[:4], new, reserve)
            a.release(9)


def test_admission_probe_fully_covered_needs_fork_page():
    a = BlockAllocator(num_pages=16, page_size=2, prefix_sharing=True)
    donor = np.arange(8)
    a.admit_tokens(1, donor, max_new_tokens=2)
    # A fully covered prompt maps only hits but must still find one free
    # page: the recomputed last token COW-forks the final shared page.
    need, _ = a.admission_probe(donor, 4, reserve=False)
    assert need == 1
    need_w, _ = a.admission_probe(donor, 4, reserve=True)
    assert need_w == a.pages_for(a.worst_case_tokens(8, 4)) - 4 + 1


def test_admission_probe_counts_pinned_hits_as_free():
    a = BlockAllocator(num_pages=8, page_size=2, prefix_sharing=True,
                       pin_budget_pages=4)
    toks = np.arange(8)
    a.admit_tokens(1, toks, max_new_tokens=0)
    a.release(1)                            # all 4 pages pinned
    need, reclaimable = a.admission_probe(toks, 0, reserve=False)
    assert need == 1                        # revivals + the COW fork page
    assert reclaimable == 0                 # every pin is a hit: protected
    need2, reclaimable2 = a.admission_probe(np.arange(50, 58), 0,
                                            reserve=False)
    assert need2 == 4 and reclaimable2 == 4


# ---------------------------------------------------------------------------
# Allocator: restore-side admission + unregister
# ---------------------------------------------------------------------------

def test_admit_restored_private_pages():
    a = BlockAllocator(num_pages=16, page_size=4, prefix_sharing=True)
    pages = a.admit_restored(5, n_pages=3, worst_pages=6, reserve=False)
    assert pages is not None and len(pages) == 3
    assert a._reserved == 0 and a.free_pages == 12
    assert all(a.refcount(p) == 1 for p in pages)
    assert all(p not in a._page_key for p in pages)   # never cache-served
    a.extend(5)
    a.release(5)
    assert a.used_pages == 0 and a.free_pages == 15


def test_admit_restored_watermark_mode_and_refusal():
    a = BlockAllocator(num_pages=8, page_size=4)
    pages = a.admit_restored(5, n_pages=2, worst_pages=5)
    assert pages is not None and a._reserved == 3
    assert a.admit_restored(6, n_pages=3, worst_pages=3) is None
    a.release(5)
    assert a.admit_restored(6, n_pages=9, worst_pages=9) is None
    assert a.used_pages == 0 and a._reserved == 0


def test_unregister_drops_cache_entries():
    a = BlockAllocator(num_pages=16, page_size=2, prefix_sharing=True)
    toks = np.arange(8)
    a.admit_tokens(1, toks, max_new_tokens=2)
    a.unregister(1, from_logical=2)         # first 2 pages stay cached
    res = a.admit_tokens(2, toks, max_new_tokens=2)
    assert res[1] == 4                      # hits stop at the unregistered
    a.release(1)
    a.release(2)


# ---------------------------------------------------------------------------
# Tiered page store: swap roundtrip exactness
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kv_dtype", ["model", "int8"])
def test_swap_roundtrip_bit_exact(kv_dtype):
    cfg, _ = _setup()
    cache = api.init_paged_cache(cfg, 2, num_pages=8, page_size=4,
                                 max_pages=4, kv_dtype=kv_dtype)
    rng = np.random.RandomState(7)

    def _fill(arr):
        if arr.dtype == np.int8:
            return rng.randint(-128, 128, arr.shape).astype(np.int8)
        return rng.randn(*arr.shape).astype(arr.dtype)

    cache = dataclasses.replace(
        cache,
        k_pages=jax.numpy.asarray(_fill(np.asarray(cache.k_pages))),
        v_pages=jax.numpy.asarray(_fill(np.asarray(cache.v_pages))),
        k_scale=(None if cache.k_scale is None else
                 jax.numpy.asarray(_fill(np.asarray(cache.k_scale)))),
        v_scale=(None if cache.v_scale is None else
                 jax.numpy.asarray(_fill(np.asarray(cache.v_scale)))),
        lengths=jax.numpy.asarray([10, 0], jax.numpy.int32),
        block_tables=jax.numpy.asarray([[2, 5, 3, TRASH_PAGE],
                                        [TRASH_PAGE] * 4], jax.numpy.int32))
    want_k = np.asarray(cache.k_pages)[:, [2, 5, 3]].copy()
    cache2, blob = kv.swap_out_slot(cache, 0, [2, 5, 3], 10)
    assert blob.n_tokens == 10 and blob.n_pages == 3
    np.testing.assert_array_equal(blob.k, want_k)
    np.testing.assert_array_equal(
        blob.v, np.asarray(cache.v_pages)[:, [2, 5, 3]])
    if kv_dtype == "int8":
        np.testing.assert_array_equal(
            blob.k_scale, np.asarray(cache.k_scale)[:, [2, 5, 3]])
        np.testing.assert_array_equal(
            blob.v_scale, np.asarray(cache.v_scale)[:, [2, 5, 3]])
    assert int(cache2.lengths[0]) == 0
    assert (np.asarray(cache2.block_tables[0]) == TRASH_PAGE).all()
    # Restore into *different* physical pages on the other slot.
    cache3 = kv.swap_in_slot(cache2, 1, [6, 1, 4], blob)
    assert int(cache3.lengths[1]) == 10
    np.testing.assert_array_equal(np.asarray(cache3.block_tables[1]),
                                  [6, 1, 4, TRASH_PAGE])
    np.testing.assert_array_equal(
        np.asarray(cache3.k_pages)[:, [6, 1, 4]], blob.k)
    np.testing.assert_array_equal(
        np.asarray(cache3.v_pages)[:, [6, 1, 4]], blob.v)
    if kv_dtype == "int8":
        np.testing.assert_array_equal(
            np.asarray(cache3.k_scale)[:, [6, 1, 4]], blob.k_scale)


def test_host_swap_tier_accounting():
    tier = kv.HostSwapTier()
    blob = kv.SwappedKV(n_tokens=4, k=np.zeros((1, 2, 1, 4, 8)),
                        v=np.zeros((1, 2, 1, 4, 8)))
    tier.put(3, blob)
    assert len(tier) == 1 and tier.bytes_used == 2 * blob.k.nbytes
    with pytest.raises(AssertionError):
        tier.put(3, blob)
    assert tier.pop(3) is blob
    assert len(tier) == 0 and tier.bytes_used == 0
    assert tier.bytes_peak == 2 * blob.k.nbytes


# ---------------------------------------------------------------------------
# Engine: FIFO vs SLO equivalence and preempt-and-swap bit-identity
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def sched_env():
    cfg, params = _setup()
    prompts, new = _workload(cfg)
    ref, _ = _drain(params, cfg, prompts, new, slots=2, num_pages=64)
    return cfg, params, prompts, new, ref


def test_slo_without_pressure_matches_fifo(sched_env):
    """With pages and slots to spare, the SLO policy never preempts and
    its greedy outputs are bit-identical to FIFO's."""
    cfg, params, prompts, new, ref = sched_env
    out, eng = _drain(params, cfg, prompts, new, slots=2, num_pages=64,
                      scheduler=SloScheduler())
    assert out == ref
    assert eng.preemptions == 0 and eng.swap_outs == 0
    st = eng.stats()
    assert st["scheduler"] == "slo" and st["preemptions"] == 0


@pytest.mark.parametrize("sharing", [True, False])
@pytest.mark.parametrize("kv_dtype", ["model", "int8"])
def test_slo_preempt_swap_bit_identical(sched_env, sharing, kv_dtype):
    """Acceptance: an oversubscribed pool forces preempt-and-swap, and
    swap-restored slots continue bit-identically — across {fp, int8}
    pools x {prefix sharing on, off}."""
    cfg, params, prompts, new, ref = sched_env
    out, eng = _drain(params, cfg, prompts, new, slots=3, num_pages=12,
                      scheduler=SloScheduler(), prefix_sharing=sharing,
                      kv_cache_dtype=kv_dtype)
    if kv_dtype == "model":
        assert out == ref
    else:
        # int8 engines differ from fp engines but must agree with an
        # unpressured int8 engine: swap changed nothing.
        calm, _ = _drain(params, cfg, prompts, new, slots=2, num_pages=64,
                         prefix_sharing=sharing, kv_cache_dtype=kv_dtype)
        assert out == calm
    assert eng.preemptions > 0, "workload failed to force preemption"
    assert eng.swap_ins > 0, "no slot went through the swap tier"
    st = eng.stats()
    assert st["swap_bytes_peak"] > 0
    assert st["swapped"] == 0


def test_slo_priority_admission_preempts_lower_class():
    """An urgent submission finds every slot held by background work:
    the scheduler swaps a background victim out for it, and the victim
    still completes (restored from the swap tier) with correct output."""
    cfg, params = _setup()
    gen = GenConfig(temperature=0.0, stop_on_eos=False)
    eng = ServingEngine(params, cfg, ENGINE, EngineConfig(
        slots=2, max_len=32, gen=gen, page_size=4, num_pages=64,
        scheduler=SloScheduler()))
    rng = np.random.RandomState(23)
    bg_prompts = [rng.randint(2, cfg.vocab, size=6) for _ in range(2)]
    bg = [eng.submit(p.copy(), max_new_tokens=16, priority=2)
          for p in bg_prompts]
    for _ in range(4):
        eng.step()                          # both slots decoding background
    urgent_prompt = rng.randint(2, cfg.vocab, size=6)
    hi = eng.submit(urgent_prompt.copy(), max_new_tokens=4, priority=0)
    done = eng.run(max_steps=600)
    assert sorted(r.uid for r in done) == sorted(bg + [hi])
    assert eng.preemptions >= 1 and eng.swap_ins >= 1
    order = [r.uid for r in eng.finished]
    assert order.index(hi) < max(order.index(u) for u in bg)
    by = {r.uid: r for r in done}
    assert by[hi].preemptions == 0          # the urgent class never waits
    # Each request's output matches an unpressured solo run.
    for uid, prompt, n in [(hi, urgent_prompt, 4),
                           (bg[0], bg_prompts[0], 16),
                           (bg[1], bg_prompts[1], 16)]:
        solo, _ = _drain(params, cfg, [prompt], [n], slots=1, num_pages=64)
        assert by[uid].generated == solo[0]


def test_slo_same_class_never_preempts_for_admission():
    """Admission preemption claims strictly-lower-priority victims only:
    an all-one-class workload with ample pages must drain with zero
    preemptions even when requests queue for slots."""
    cfg, params = _setup()
    prompts, new = _workload(cfg, seed=3, n=5)
    out, eng = _drain(params, cfg, prompts, new, slots=2, num_pages=64,
                      scheduler=SloScheduler(),
                      priorities=[1, 1, 1, 1, 1])
    assert eng.preemptions == 0


def test_slo_with_speculation_preempt_bit_identical(sched_env):
    """Speculative decoding composes with preempt-and-swap: preempted
    slots drop drafter state, restored slots re-contact the drafter,
    outputs stay bit-identical."""
    cfg, params, prompts, new, ref = sched_env
    out, eng = _drain(params, cfg, prompts, new, slots=3, num_pages=12,
                      scheduler=SloScheduler(),
                      speculative=SpecConfig(mode="ngram", k=4))
    assert out == ref
    assert eng.preemptions > 0


def test_slo_infeasible_candidate_never_evicts():
    """The feasibility guard: a candidate whose resident need exceeds
    the free list plus everything eviction could free (an urgent tenant
    is untouchable) must not preempt the small evictable tenant it
    cannot profit from — it waits; nobody thrashes."""
    cfg, params = _setup()
    gen = GenConfig(temperature=0.0, stop_on_eos=False)
    eng = ServingEngine(params, cfg, ENGINE, EngineConfig(
        slots=2, max_len=32, gen=gen, page_size=4, num_pages=7,
        scheduler=SloScheduler()))
    rng = np.random.RandomState(31)
    # Urgent long-runner (2 prompt pages, grows to 5) + tiny background
    # tenant (1 page): both slots busy, 3 pages free.
    hi = eng.submit(rng.randint(2, cfg.vocab, size=8), max_new_tokens=12,
                    priority=0)
    lo = eng.submit(rng.randint(2, cfg.vocab, size=4), max_new_tokens=2,
                    priority=2)
    for _ in range(3):
        eng.step()
    # Mid-priority candidate needing 5 pages now: even evicting the
    # background tenant attains only 4 — infeasible until the urgent
    # tenant finishes, so preempting anyone would be futile thrash.
    mid = eng.submit(rng.randint(2, cfg.vocab, size=20), max_new_tokens=2,
                     priority=1)
    eng.run(max_steps=400)
    # (eng.finished, not run()'s return: lo may finish during the manual
    # warmup steps above, and run() only reports its own window.)
    assert sorted(r.uid for r in eng.finished) == sorted([hi, lo, mid])
    by = {r.uid: r for r in eng.finished}
    assert by[lo].preemptions == 0          # never futilely evicted
    assert eng.preemptions == 0


def test_pinning_keeps_hot_prefix_across_requests():
    """End-to-end pinning: a system-prompt page outlives its refcount-0
    gap under the pin budget and the next request revives it, skipping
    prefill work — visible in sched.pin/pin_hits and tokens saved."""
    cfg, params = _setup()
    tel = Telemetry(enabled=True)
    gen = GenConfig(temperature=0.0, stop_on_eos=False)
    eng = ServingEngine(params, cfg, ENGINE, EngineConfig(
        slots=2, max_len=32, gen=gen, page_size=4, num_pages=64,
        scheduler=SloScheduler(pin_budget_pages=4), telemetry=tel))
    rng = np.random.RandomState(29)
    system = rng.randint(2, cfg.vocab, size=8)        # 2 full pages
    p1 = np.concatenate([system, rng.randint(2, cfg.vocab, size=2)])
    p2 = np.concatenate([system, rng.randint(2, cfg.vocab, size=3)])
    eng.submit(p1.copy(), max_new_tokens=4)
    eng.run(max_steps=200)
    # The only pages still off the free list are the pins themselves.
    assert eng.allocator.pinned_pages == 2            # survived refcount 0
    assert eng.allocator.used_pages == 2
    assert eng.prefill_tokens_saved == 0
    u2 = eng.submit(p2.copy(), max_new_tokens=4)
    done = eng.run(max_steps=200)
    assert eng.prefill_tokens_saved == 8              # revived, not recomputed
    sched = tel.snapshot()["scheduler"]
    assert sched["pin"] >= 2 and sched["pin_hits"] == 2
    # Output matches a fresh, pinless engine.
    solo, _ = _drain(params, cfg, [p2], [4], slots=1, num_pages=64)
    assert next(r for r in done if r.uid == u2).generated == solo[0]


def test_swapped_requests_counted_in_stats_and_step_return():
    """A parked (swapped) request keeps the engine's step() return and
    stats() honest: it is outstanding work, not a finished drain."""
    cfg, params = _setup()
    gen = GenConfig(temperature=0.0, stop_on_eos=False)
    eng = ServingEngine(params, cfg, ENGINE, EngineConfig(
        slots=1, max_len=32, gen=gen, page_size=4, num_pages=64,
        scheduler=SloScheduler()))
    rng = np.random.RandomState(41)
    eng.submit(rng.randint(2, cfg.vocab, size=6), max_new_tokens=16,
               priority=2)
    for _ in range(3):
        eng.step()
    eng.submit(rng.randint(2, cfg.vocab, size=6), max_new_tokens=4,
               priority=0)
    n = eng.step()                          # preempts the background slot
    assert eng.stats()["swapped"] == 1
    assert n >= 2                           # active + parked both counted
    eng.run(max_steps=400)
    assert eng.stats()["swapped"] == 0 and len(eng.finished) == 2
