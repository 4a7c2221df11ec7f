"""Serving engine: scan-generation vs manual loop, continuous batching
equivalence, throughput stats."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.core.salpim import SalPimConfig, SalPimEngine
from repro.models import api
from repro.serving.engine import (EngineConfig, GenConfig, ServingEngine,
                                  generate)
from repro.serving.sampling import sample

ENGINE = SalPimEngine.create(SalPimConfig())
KEY = jax.random.PRNGKey(0)


def _setup(arch="gpt2_medium"):
    cfg = get_config(arch, smoke=True)
    params = api.init_params(KEY, cfg)
    return cfg, params


def test_greedy_generate_matches_manual_loop():
    cfg, params = _setup()
    prompts = jax.random.randint(KEY, (2, 8), 2, cfg.vocab)
    gen = GenConfig(max_new_tokens=6, temperature=0.0, stop_on_eos=False)
    toks, stats = generate(params, prompts, cfg, ENGINE, gen)

    # manual reference loop
    logits, cache = api.prefill(params, {"tokens": prompts}, cfg, ENGINE,
                                max_len=8 + 7)
    out = []
    for _ in range(6):
        t = jnp.argmax(logits, -1).astype(jnp.int32)
        out.append(t)
        logits, cache = api.decode_step(params, t, cache, cfg, ENGINE)
    manual = jnp.stack(out, 1)
    np.testing.assert_array_equal(np.asarray(toks), np.asarray(manual))
    assert stats["tokens"] == 12


def test_generate_stops_on_eos():
    cfg, params = _setup()
    prompts = jax.random.randint(KEY, (1, 4), 2, cfg.vocab)
    gen = GenConfig(max_new_tokens=8, temperature=0.0, eos_id=0,
                    stop_on_eos=True)
    toks, _ = generate(params, prompts, cfg, ENGINE, gen)
    arr = np.asarray(toks)[0]
    if (arr == 0).any():
        first = int(np.argmax(arr == 0))
        assert (arr[first:] == 0).all()


def test_sampling_modes():
    logits = jnp.array([[0.0, 5.0, 1.0, -2.0]])
    assert int(sample(logits, KEY, temperature=0.0)[0]) == 1
    t = sample(jnp.tile(logits, (64, 1)), KEY, temperature=1.0, top_k=2)
    assert set(np.asarray(t)) <= {1, 2}


def test_continuous_batching_matches_batch_generate():
    """Slot engine output == whole-batch greedy generate per request."""
    cfg, params = _setup()
    prompts = np.asarray(jax.random.randint(KEY, (3, 8), 2, cfg.vocab))
    gen = GenConfig(max_new_tokens=5, temperature=0.0, stop_on_eos=False)
    # reference via batch generate
    ref, _ = generate(params, jnp.asarray(prompts), cfg, ENGINE, gen)

    eng = ServingEngine(params, cfg, ENGINE, EngineConfig(
        slots=2, max_len=32, gen=gen))
    uids = [eng.submit(prompts[i], max_new_tokens=5) for i in range(3)]
    done = eng.run(max_steps=200)
    assert len(done) == 3
    by_uid = {r.uid: r for r in done}
    for i, uid in enumerate(uids):
        np.testing.assert_array_equal(
            np.asarray(by_uid[uid].generated), np.asarray(ref[i]),
            err_msg=f"request {i}")


def test_generate_stats_count_only_pre_eos_tokens():
    """tokens/sec must not be inflated by post-EOS padding: `tokens`
    counts through each row's first EOS, not B * max_new_tokens."""
    cfg, params = _setup()
    prompts = jax.random.randint(KEY, (2, 6), 2, cfg.vocab)
    gen = GenConfig(max_new_tokens=8, temperature=0.0, eos_id=0,
                    stop_on_eos=True)
    toks, stats = generate(params, prompts, cfg, ENGINE, gen)
    arr = np.asarray(toks)
    is_eos = arr == 0
    want = int(np.where(is_eos.any(1), is_eos.argmax(1) + 1,
                        arr.shape[1]).sum())
    assert stats["tokens"] == want
    assert stats["tokens_budget"] == 16
    assert stats["tokens"] <= stats["tokens_budget"]
    assert stats["sec_per_token"] > 0
    # Without early stop, the full budget is generated work.
    _, stats2 = generate(params, prompts, cfg, ENGINE,
                         GenConfig(max_new_tokens=8, temperature=0.0,
                                   stop_on_eos=False))
    assert stats2["tokens"] == 16


def test_released_slot_lengths_stay_frozen():
    """Regression: decode_step advanced every slot's length uncondition-
    ally, so released/empty slots crept without bound (and idle slots
    scattered garbage K/V over trash pages each step). After a release
    the slot must park at length 0 — while the survivor's output is
    unchanged."""
    cfg, params = _setup()
    gen = GenConfig(temperature=0.0, stop_on_eos=False)
    eng = ServingEngine(params, cfg, ENGINE, EngineConfig(
        slots=2, max_len=32, gen=gen, page_size=4))
    prompts = np.asarray(jax.random.randint(KEY, (2, 6), 2, cfg.vocab))
    u_short = eng.submit(prompts[0], max_new_tokens=2)
    u_long = eng.submit(prompts[1], max_new_tokens=10)
    slot_of = {}
    eng.step()
    slot_of = {r.uid: i for i, r in enumerate(eng.active) if r is not None}
    done = eng.run(max_steps=100)
    assert sorted(r.uid for r in done) == sorted([u_short, u_long])
    s = slot_of[u_short]
    assert int(eng.cache.lengths[s]) == 0
    assert int(eng._host_len[s]) == 0
    # The survivor matches a solo run (idle slots did not perturb it).
    eng2 = ServingEngine(params, cfg, ENGINE, EngineConfig(
        slots=2, max_len=32, gen=gen, page_size=4))
    eng2.submit(prompts[1], max_new_tokens=10)
    (ref,) = eng2.run(max_steps=100)
    long_req = next(r for r in done if r.uid == u_long)
    assert long_req.generated == ref.generated


def test_serving_with_lut_engine():
    cfg, params = _setup()
    lut = SalPimEngine.create(SalPimConfig(nonlinear_mode="lut"))
    prompts = jax.random.randint(KEY, (2, 6), 2, cfg.vocab)
    gen = GenConfig(max_new_tokens=4, temperature=0.0, stop_on_eos=False)
    toks, stats = generate(params, prompts, cfg, lut, gen)
    assert toks.shape == (2, 4)
    assert stats["sec_per_token"] > 0


def test_engine_stats_fields():
    """ServingEngine.stats(): token accounting mirrors generate()'s
    fields (tokens, tokens_budget, sec_per_token) and the speculative
    counters are zero when speculation is off."""
    cfg, params = _setup()
    gen = GenConfig(temperature=0.0, stop_on_eos=False)
    prompts = np.asarray(jax.random.randint(KEY, (3, 6), 2, cfg.vocab))
    eng = ServingEngine(params, cfg, ENGINE, EngineConfig(
        slots=2, max_len=32, gen=gen, page_size=4))
    for i in range(3):
        eng.submit(prompts[i], max_new_tokens=5)
    eng.run(max_steps=200)
    st = eng.stats()
    assert st["tokens"] == 15
    assert st["tokens_budget"] == 15
    assert st["sec_per_token"] > 0
    assert st["prefill_tokens"] == eng.prefill_tokens
    assert st["proposed"] == st["accepted"] == st["verify_passes"] == 0
    assert st["acceptance_rate"] == 0.0


def test_engine_stats_counts_unfinished_budget():
    """tokens_budget covers admitted-but-unfinished requests too, and
    tokens counts their partial output (honest mid-flight reporting)."""
    cfg, params = _setup()
    gen = GenConfig(temperature=0.0, stop_on_eos=False)
    prompts = np.asarray(jax.random.randint(KEY, (2, 5), 2, cfg.vocab))
    eng = ServingEngine(params, cfg, ENGINE, EngineConfig(
        slots=2, max_len=32, gen=gen))
    eng.submit(prompts[0], max_new_tokens=8)
    eng.submit(prompts[1], max_new_tokens=8)
    for _ in range(3):
        eng.step()
    st = eng.stats()
    assert st["tokens_budget"] == 16
    assert 0 < st["tokens"] < 16


def test_engine_stats_under_speculative_run():
    """Speculative engine stats: tokens/tokens_budget/sec_per_token stay
    honest, acceptance aggregates match the per-request counters, and
    tokens_per_pass > 1 when the drafter is the target model itself."""
    from repro.serving.speculative import SpecConfig
    cfg, params = _setup()
    gen = GenConfig(temperature=0.0, stop_on_eos=False)
    prompts = np.asarray(jax.random.randint(KEY, (3, 6), 2, cfg.vocab))
    eng = ServingEngine(params, cfg, ENGINE, EngineConfig(
        slots=2, max_len=32, gen=gen, page_size=4,
        speculative=SpecConfig(mode="draft-model", k=3, draft_cfg=cfg,
                               draft_params=params)))
    for i in range(3):
        eng.submit(prompts[i], max_new_tokens=8)
    eng.run(max_steps=200)
    st = eng.stats()
    assert st["tokens"] == 24
    assert st["tokens_budget"] == 24
    assert st["sec_per_token"] > 0
    assert st["proposed"] == sum(r.proposed for r in eng.finished)
    assert st["accepted"] == sum(r.accepted for r in eng.finished)
    assert st["acceptance_rate"] == 1.0       # self-draft accepts all
    assert 0 < st["verify_passes"] < st["tokens"]
    assert st["verify_per_token"] < 1.0
    assert st["tokens_per_pass"] > 1.0


def test_engine_stats_zero_token_drain_returns_zero_ratios():
    """Satellite regression: every ratio field must report 0.0 — not
    raise, not NaN — when nothing was generated (empty engine, and again
    after construction with speculation on)."""
    from repro.serving.speculative import SpecConfig
    cfg, params = _setup()
    for spec in (None, SpecConfig(mode="ngram", k=4)):
        eng = ServingEngine(params, cfg, ENGINE, EngineConfig(
            slots=2, max_len=32,
            gen=GenConfig(temperature=0.0, stop_on_eos=False),
            page_size=4, speculative=spec))
        st = eng.stats()
        for field in ("sec_per_token", "model_sec_per_token",
                      "acceptance_rate", "verify_per_token",
                      "tokens_per_pass"):
            assert st[field] == 0.0, field
        assert st["tokens"] == 0 and st["tokens_budget"] == 0
