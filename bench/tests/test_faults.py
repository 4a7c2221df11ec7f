"""A run whose timed path is broken underneath must come out not correct.

Each test drives a whole smoke-size run on the CPU (the harness's look
for a chip skipped), with one fault planted in the engine after its
set-up drain, and requires `correct` to be false:

  state_unchanged  the decode step hands back the KV pools it was given,
                   so no decoded token's keys and values are kept
  half_batch       the decode step leaves the upper half of the slots'
                   logits as they were, so that half is not computed
  token_altered    the sampled token is changed where it is produced,
                   on every fourth step

A cell on one chip has no exchange between chips to leave out.
"""
from __future__ import annotations

import time

import jax.numpy as jnp
import pytest

from bench import run
from bench.tests import smoke
from repro.serving import engine as engine_mod
from repro.serving.kvcache import PagedCache


def state_unchanged(s, monkeypatch):
    real = s.eng._decode

    def decode(p, tok, cache):
        k, v = jnp.copy(cache.k_pages), jnp.copy(cache.v_pages)
        logits, new = real(p, tok, cache)
        return logits, PagedCache(new.lengths, new.block_tables, k, v,
                                  new.k_scale, new.v_scale)
    s.eng._decode = decode


def half_batch(s, monkeypatch):
    eng = s.eng
    real = eng._decode

    def decode(p, tok, cache):
        prev = eng.last_logits
        logits, new = real(p, tok, cache)
        rows = logits.shape[0]
        left_out = (jnp.arange(rows) >= rows // 2)[:, None]
        return jnp.where(left_out, prev, logits), new
    eng._decode = decode


def token_altered(s, monkeypatch):
    real = engine_mod.sample
    calls = [0]

    def sample(logits, key, **kw):
        calls[0] += 1
        tok = real(logits, key, **kw)
        if calls[0] % 4 == 0:
            tok = (tok + 1) % logits.shape[-1]
        return tok
    monkeypatch.setattr(engine_mod, "sample", sample)


FAULTS = {"state_unchanged": state_unchanged, "half_batch": half_batch,
          "token_altered": token_altered}
CASES = [(cell, f) for cell in ("gpt2m.paper.poisson",
                                "qwen2-1.5b.longdoc.closed16")
         for f in FAULTS]


@pytest.mark.parametrize("name,fault", CASES)
def test_planted_fault_is_not_correct(name, fault, monkeypatch):
    drain = run.Serve.compile_drain

    def drain_then_break(self):
        drain(self)
        FAULTS[fault](self, monkeypatch)

    monkeypatch.setattr(run.Serve, "compile_drain", drain_then_break)
    c = smoke.cell(name, tokens=200)
    out = run.execute(c, 11, 3.0, False, time.perf_counter(),
                      require_chip=False, limits=smoke.limits(c))
    assert not out["correct"], out["checks"]
