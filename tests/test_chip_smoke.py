"""CPU rehearsal of `chip_smoke.py`: its drain, run at smoke configs.

The chip run serves gpt2_medium at full width through the compiled
Pallas kernels; here the same `build_engine` + `drain` (imported, not
run through an option of the script) serve a smoke config with the
kernels in interpret mode, and must give the greedy tokens of the jnp
oracles. gemma2's smoke config alternates windowed and global layers, so
the traced per-layer window operand reaches every kernel.
"""
from __future__ import annotations

import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.salpim import SalPimConfig, SalPimEngine
from repro.models import api

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _chip_smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(REPO, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


CS = _chip_smoke()


def _engine(impl, nonlinear_mode="lut"):
    return SalPimEngine.create(
        SalPimConfig(nonlinear_mode=nonlinear_mode, impl=impl))


@pytest.mark.parametrize("arch", ["gpt2_medium", "gemma2_2b"])
def test_drain_interpret_matches_reference(arch):
    cfg = get_config(arch, smoke=True)
    params = api.init_params(jax.random.PRNGKey(0), cfg)
    # 70 tokens = a 64-token chunk plus a 6-token one; both exceed
    # gemma2's 16-token window.
    prompts = CS.make_prompts(cfg.vocab, (20, 70), 3, seed=0)
    got = {}
    for impl in ("interpret", "reference"):
        eng = CS.build_engine(params, cfg, _engine(impl))
        toks, steps, _ = CS.drain(eng, prompts, max_new_tokens=3)
        assert eng.allocator.used_pages == 0 and steps > 0
        got[impl] = toks
    assert got["interpret"] == got["reference"]


def test_prompt_logits_interpret_matches_reference():
    cfg = get_config("gpt2_medium", smoke=True)
    params = api.init_params(jax.random.PRNGKey(0), cfg)
    prompt = CS.make_prompts(cfg.vocab, (70,), 1, seed=1)[0]
    forced = [5, 6, 7]
    got = CS.forced_logits(params, cfg, _engine("interpret", "exact"),
                           prompt, forced)
    want = CS.forced_logits(params, cfg, _engine("reference", "exact"),
                            prompt, forced)
    assert got.shape == (4, cfg.vocab) and bool(jnp.all(jnp.isfinite(got)))
    # f32 smoke model, exact exp: the two paths differ only in f32
    # summation order (per-page online softmax vs one softmax, blocked
    # GEMV accumulation). With the LUT exp they differ by the table's
    # interpolation error instead, since LUT(a) * LUT(b) != LUT(a + b) in
    # the per-page rescale; the drains above cover that mode.
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("kv_dtype", [None, "int8"])
def test_compare_forced_passes_kernels_and_catches_planted_fault(kv_dtype):
    """The chip run's teacher-forced check, at the smoke config: the
    interpret-mode kernels sit inside the band, and the planted
    wrong-page fault falls outside it."""
    cfg = get_config("gpt2_medium", smoke=True)
    params = api.init_params(jax.random.PRNGKey(0), cfg)
    prompts = CS.make_prompts(cfg.vocab, (70,), 1, seed=1)
    ref = _engine("reference")
    toks, _, _ = CS.drain(CS.build_engine(params, cfg, ref), prompts, 4)
    fault = CS.compare_forced("smoke", params, cfg, _engine("interpret"),
                              ref, prompts[0], toks[0], kv_dtype,
                              plant_fault=True)
    assert fault > 4 * CS.LOGIT_BAND
    with pytest.raises(CS.SmokeFailure, match="below the reference"):
        CS.compare_forced("smoke", params, cfg, ref, ref, prompts[0],
                          [toks[0][0], (toks[0][1] + 1) % cfg.vocab],
                          kv_dtype)


def test_refuses_to_run_without_a_tpu(monkeypatch, capsys):
    """On the CPU backend the script exits non-zero before any phase and
    prints no result line — it never falls back to the CPU."""
    monkeypatch.setattr("sys.argv", ["chip_smoke.py"])
    assert CS.main() != 0
    out = capsys.readouterr()
    assert '"ok"' not in out.out and "needs a TPU" in out.err
