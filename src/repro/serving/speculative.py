"""Speculative decoding: draft-verify serving over the paged KV cache.

SAL-PIM's generation stage is memory-bound because every decode
iteration streams the whole model (and the resident KV history) to emit
a single token. Speculative decoding amortizes that stream across k
tokens per *verification* pass: a cheap drafter proposes k candidate
continuations, and the target model scores all of them in one
prefill-chunk-shaped forward (`models/api.verify_tokens` — the same
block/attention path and paged-prefill kernel dispatch as chunked
prefill). With greedy decoding, acceptance is exact-match: the longest
prefix of drafts where each token equals the target's argmax at that
position is committed, so outputs are bit-identical to non-speculative
greedy decoding — speculation only changes how many target forwards it
takes to emit them.

Per engine round (EngineConfig(speculative=SpecConfig(...))):

  1. t0 = argmax(last_logits) — free, no model call (greedy);
  2. the drafter proposes d1..dk continuing after t0;
  3. one verify pass scores [t0, d1..dk]: each candidate's KV is
     written into the slot's pool pages (append_chunk_kv_pages) and
     logits come back at all k+1 positions;
  4. greedy acceptance commits t0 plus the longest matching draft
     prefix; the rejected tail is rolled back *in-pool* — the slot's
     lengths rewound and now-empty tail pages returned to the
     allocator's free list and the slot's reservation
     (BlockAllocator.rewind / kvcache.rewind_slot), so watermark math
     is unchanged;
  5. last_logits := the verify logits after the last accepted token —
     the next round's t0 comes from there, exactly as a decode step
     would have produced it.

Every round emits >= 1 token per live slot, so verify passes per
generated token is <= 1 by construction and < 1 whenever anything is
accepted.

Two drafters behind one protocol:

  * `NgramDrafter` — model-free prompt-lookup: match the longest recent
    n-gram of the request's own token history against an earlier
    occurrence and propose the tokens that followed it. Free to run,
    surprisingly effective on repetitive/extractive workloads (and on
    greedy decoding's own loops).
  * `DraftModelDrafter` — a small second model (its own ModelConfig +
    params) running on its own *dense* KV cache, greedy-decoding k
    tokens ahead. Draft-side rollback is trivial on the dense cache:
    lengths are rewound and stale tail KV is overwritten by the next
    append. Pointing it at the target model itself ("self-draft") gives
    a deterministic 100%-acceptance drafter, used by tests to pin the
    acceptance machinery.

Preemption (scheduler layer): a preempted slot drops its drafter state
— the engine calls `Drafter.release(slot)` from `_preempt`, because the
slot id is about to be reused by a different request. For `NgramDrafter`
release is a no-op (it is stateless; proposals derive from the request's
own token history, which travels with the swapped request). For
`DraftModelDrafter` the per-slot dense cache is discarded; on
re-admission the first `propose` finds no state and `_catch_up`
re-prefills the draft cache from the handed-in context, so drafting
after a swap-in resumes exactly (and target-side acceptance keeps
outputs bit-identical regardless).
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Protocol

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.salpim import SalPimEngine
from repro.models import api as model_api
from repro.models.config import ModelConfig
from repro.serving.telemetry import NULL_TELEMETRY

Array = jax.Array


@dataclasses.dataclass(frozen=True)
class SpecConfig:
    """Speculative serving knobs.

    mode:       "ngram" (prompt lookup, model-free) | "draft-model"
    k:          drafted tokens per verify pass (the pass scores k+1)
    ngram_max:  longest history suffix the ngram drafter tries to match
    ngram_min:  shortest match it will draft from
    draft_cfg / draft_params: the small model for "draft-model" mode
                (pass the target's own cfg/params for self-draft)
    """

    mode: str = "ngram"
    k: int = 4
    ngram_max: int = 3
    ngram_min: int = 1
    draft_cfg: Optional[ModelConfig] = None
    draft_params: Optional[dict] = None

    def validate(self) -> None:
        if self.mode not in ("ngram", "draft-model"):
            raise ValueError(f"unknown speculative mode {self.mode!r}")
        if self.k < 1:
            raise ValueError(f"spec k must be >= 1, got {self.k}")
        if not 1 <= self.ngram_min <= self.ngram_max:
            raise ValueError(
                f"need 1 <= ngram_min <= ngram_max, got "
                f"{self.ngram_min}..{self.ngram_max}")
        if self.mode == "draft-model" and (
                self.draft_cfg is None or self.draft_params is None):
            raise ValueError("draft-model mode needs draft_cfg and "
                             "draft_params")


class Drafter(Protocol):
    """One drafter instance serves every slot of one ServingEngine."""

    def propose(self, slot: int, context: np.ndarray, k: int) -> np.ndarray:
        """Up to k draft tokens continuing `context` (the request's full
        committed history: prompt + generated, t0 included). May return
        fewer (or none) when it has nothing confident to say."""
        ...

    def release(self, slot: int) -> None:
        """The request in `slot` finished; drop any per-slot state."""
        ...


class NgramDrafter:
    """Prompt-lookup drafting: propose the continuation of the most
    recent earlier occurrence of the history's own suffix n-gram.

    For n from ngram_max down to ngram_min, take the last n tokens of
    the context and scan for the latest earlier position where the same
    n-gram occurs; on a hit, propose the (up to k) tokens that followed
    it. Recency-first matching follows the prompt-lookup/PLD heuristic:
    the most recent occurrence is likeliest to predict the local
    continuation (copying, templated output, greedy loops).
    """

    def __init__(self, ngram_max: int = 3, ngram_min: int = 1):
        assert 1 <= ngram_min <= ngram_max
        self.ngram_max = ngram_max
        self.ngram_min = ngram_min

    def propose(self, slot: int, context: np.ndarray, k: int) -> np.ndarray:
        del slot
        ctx = np.asarray(context)
        n_ctx = len(ctx)
        for n in range(min(self.ngram_max, n_ctx - 1), self.ngram_min - 1,
                       -1):
            pattern = ctx[n_ctx - n:]
            # Latest i with ctx[i:i+n] == pattern and a continuation
            # strictly before the suffix itself (i + n < n_ctx).
            for i in range(n_ctx - n - 1, -1, -1):
                if np.array_equal(ctx[i:i + n], pattern):
                    return ctx[i + n:i + n + k].copy()
        return np.zeros((0,), ctx.dtype)

    def release(self, slot: int) -> None:
        del slot


class DraftModelDrafter:
    """Small-model drafting on a per-slot dense KV cache.

    Each slot keeps (fed tokens, dense Cache, last logits). A propose()
    call first catches the cache up to the request's committed history
    (prefill on first contact or a context change, decode steps for the
    per-round delta — accepted tokens the target already committed),
    then greedy-decodes k tokens ahead. The drafting decode steps write
    speculative KV into the dense cache; rollback is a length rewind —
    stale tail KV is never read (length-masked) and the next catch-up
    append overwrites it, mirroring the target pool's in-place rollback.
    """

    def __init__(self, params: dict, cfg: ModelConfig,
                 engine: SalPimEngine, max_len: int, headroom: int,
                 telemetry=None):
        if cfg.family == "encdec":
            raise ValueError("draft-model drafting unsupported for encdec")
        self.params = params
        self.cfg = cfg
        # Draft-model streams are real work the target's verify pass
        # amortizes; count them so telemetry can price a round honestly.
        self._tel = telemetry if telemetry is not None else NULL_TELEMETRY
        # Drafting runs k tokens past the longest committed context.
        self.max_len = max_len + headroom
        self._decode = jax.jit(
            lambda p, tok, cache: model_api.decode_step(
                p, tok, cache, cfg, engine),
            donate_argnums=(2,))
        self._prefill = jax.jit(
            lambda p, toks: model_api.prefill(
                p, {"tokens": toks}, cfg, engine, max_len=self.max_len))
        # slot -> [fed tokens (np), Cache, last logits (1, V)]
        self._state: dict[int, list] = {}

    def _catch_up(self, slot: int, context: np.ndarray):
        st = self._state.get(slot)
        fed = None if st is None else st[0]
        if (fed is None or len(fed) > len(context)
                or not np.array_equal(fed, context[:len(fed)])):
            logits, cache = self._prefill(
                self.params, jnp.asarray(context[None], jnp.int32))
            st = [context.copy(), cache, logits]
            self._tel.count("spec.draft_prefills")
        else:
            _, cache, logits = st
            for t in context[len(fed):]:
                logits, cache = self._decode(
                    self.params, jnp.asarray([t], jnp.int32), cache)
            self._tel.count("spec.draft_decode_steps",
                            len(context) - len(fed))
            st = [context.copy(), cache, logits]
        self._state[slot] = st
        return st

    def propose(self, slot: int, context: np.ndarray, k: int) -> np.ndarray:
        context = np.asarray(context)
        st = self._catch_up(slot, context)
        fed, cache, logits = st
        drafts = np.zeros((k,), np.int64)
        for j in range(k):
            drafts[j] = int(jnp.argmax(logits[0]))
            if j == k - 1:
                break          # the k-th draft needs no follow-up forward
            logits, cache = self._decode(
                self.params, jnp.asarray([drafts[j]], jnp.int32), cache)
        self._tel.count("spec.draft_decode_steps", max(k - 1, 0))
        # Draft-side rollback: rewind to the committed context. The
        # drafted tokens' KV stays as dead data past `lengths` until the
        # next catch-up overwrites it position by position. st[2] keeps
        # the logits-after-context recorded by _catch_up.
        cache.lengths = jnp.full_like(cache.lengths, len(fed))
        st[1] = cache
        return drafts

    def release(self, slot: int) -> None:
        self._state.pop(slot, None)


def make_drafter(spec: SpecConfig, engine: SalPimEngine,
                 max_len: int, telemetry=None) -> Drafter:
    """Build the drafter a ServingEngine's SpecConfig asks for."""
    spec.validate()
    if spec.mode == "ngram":
        return NgramDrafter(ngram_max=spec.ngram_max,
                            ngram_min=spec.ngram_min)
    return DraftModelDrafter(spec.draft_params, spec.draft_cfg, engine,
                             max_len=max_len, headroom=spec.k + 1,
                             telemetry=telemetry)


def greedy_accept(drafts: np.ndarray, greedy_tokens: np.ndarray,
                  *, eos_id: int, stop_on_eos: bool) -> tuple[int, bool]:
    """Greedy acceptance rule: (accepted count, hit_eos).

    `greedy_tokens[j]` is the target's argmax after verify-chunk token j
    (j=0 is after t0). Draft j+1 is accepted iff it equals
    greedy_tokens[j] — i.e. it is exactly the token non-speculative
    greedy decoding would have emitted — and acceptance stops *after* an
    accepted EOS (which ends the request, like a sampled EOS would).
    Cross-checked against `kernels/ref.greedy_accept_len_ref` in tests.
    """
    a = 0
    hit_eos = False
    while a < len(drafts) and int(drafts[a]) == int(greedy_tokens[a]):
        a += 1
        if stop_on_eos and int(drafts[a - 1]) == eos_id:
            hit_eos = True
            break
    return a, hit_eos
