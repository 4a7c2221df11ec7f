#!/usr/bin/env python
"""Paged continuous batching, prefix sharing, chunked prefill
decode-latency jitter, int8/int4 KV pages, and KV-split flash-decode
attention.

Part 1 — mixed lengths: the engine serves a workload of mixed
prompt/output lengths from one shared page pool; short requests release
their pages the moment they finish. It reports tokens/s and the pool's
KV bytes.

Part 2 — shared prefixes: requests that repeat a system-prompt-style
prefix are served twice on the paged engine, with prefix sharing off
and on. Sharing maps the cached prefix pages into each new slot and
prefills only the remainder, so it must show fewer prefill tokens and a
lower page high-water mark — with bit-identical greedy outputs.

Part 3 — decode-latency jitter: resident short requests are decoding
when a long prompt arrives mid-flight. With one-shot ("stall the
world") prefill, the whole prompt runs inside a single engine step and
every resident's inter-token time spikes; chunked prefill bounds the
per-step prefill work, so the residents' p99 inter-token latency stays
near p50. Both runs produce bit-identical tokens — chunking only moves
the work. `--smoke` asserts p99(chunked) < p99(stall).

Part 4 — int8 KV pages: the same paged workload served from fp pools
and from int8 pools (per-(token, head) scale rows, write-time amax
quantization, in-kernel dequant). Greedy outputs must match the fp run
exactly on these prompts and every per-step logit must stay within the
documented tolerance (0.25 x the fp logit std, the same envelope the
dense int8 KV path is held to); peak KV bytes drop ~2x at the same peak
page count, and at a fixed HBM budget the int8 pool holds ~2x the pages
(double resident capacity). Mean decode-step wall time is reported for
both.

Part 5 — speculative decoding: a *repetitive* workload (looping prompts
whose greedy continuations the n-gram drafter can look up) drained with
speculation off and on. Greedy outputs must be bit-identical — the
acceptance rule only commits drafts equal to the target's argmax — and
under --smoke the spec-on engine must spend < 1 verify pass per
generated token (the whole point: each pass streams the model once but
commits 1 + accepted tokens). Acceptance rate, verify passes per token,
and decode ms/token for both engines go to the JSON artifact.

Part 6 — serving telemetry: a *bursty* mixed workload (half the
requests up front, the rest arriving mid-flight) drained with telemetry
off and on. Outputs must be bit-identical — the observability layer
records at step boundaries only, never inside jit — and under --smoke
the telemetry-enabled decode ms/step must stay within 5% of disabled
(the overhead regression gate, min-over-interleaved-trials so host
noise cancels). The enabled run exports the metrics snapshot (pool
occupancy timeline, prefix-cache hit rate, admission rejections,
per-request inter-token p50/p99) and a Chrome `trace_event` file
viewable at https://ui.perfetto.dev — the baselines the SLO-scheduler
work will regress against.

Part 7 — SLO scheduling under oversubscription: a two-class workload
(a prio-1 batch backlog submitted up front plus a trickle of prio-0
interactive requests) drained on a deliberately undersized pool
(~1.75x one worst-case request) under `FifoScheduler` and under
`SloScheduler`. FIFO's watermark admission serializes the backlog and
head-of-line-blocks the interactive class; SLO admits optimistically,
preempts-and-swaps the lowest class when an interactive request
arrives, and swaps it back in afterwards. Latency is *step-indexed*
(gaps between engine steps that emitted a token, the first gap being
queueing + TTFT), so the comparison is deterministic. Both drains must
produce bit-identical greedy outputs; under --smoke the SLO run must
actually preempt and swap back in, beat FIFO's interactive p99
step-gap, and not lose goodput (fraction of interactive requests whose
TTFT meets the deadline). Scheduler counters (sched.preempt/swap_out/
swap_in/...), per-class latency, and goodput go to `--sched-out`.

Part 8 — multi-chip paged serving: the same mixed workload drained on a
single device and on a `jax.sharding.Mesh` over 2-8 (fake CPU) devices
with the page pools PartitionSpec-sharded over their KV-head axis
(tensor parallel; block tables, lengths, and weights replicated).
Greedy outputs must be bit-identical to the single-device engine — each
shard attends its own head block against its local pool shard and the
merge is a pure head concatenation, never a float reduction. Reports
decode ms/step and, per mesh width, the per-device resident pool bytes
(measured from the actual device shards) and the resident-capacity
scaling at a fixed per-device HBM budget; under --smoke the per-device
pool bytes must shrink >= 1.8x at mesh width 2. Requires
`XLA_FLAGS=--xla_force_host_platform_device_count=8` (or real devices);
with fewer devices than the requested width the part records a skip
note instead of failing, so single-device CI legs stay green.

Part 9 — KV-split attention + int4 pages: (a) one decode-attention
call over an 8k-token resident context, single serial page walk vs
`kv_splits=32` flash-decode partials merged by the log-sum-exp combine
(`distributed.collectives.merge_partial_softmax_stacked`). The split
path must be faster at long context — under --smoke its median call
time must be <= 0.6x the single walk's, with outputs allclose. (b) The
int4 engine (nibble-packed pools, bf16 scale rows) drains the pinned
smoke workload in lockstep with fp: greedy outputs exact-match under
--smoke, peak KV bytes >= 3.5x below fp always.

Part 10 — roofline cost model vs measured structure: the analytical
per-phase byte/FLOP model (`repro.serving.costmodel`) is held to the
engine's actual pools. Modeled fp/int8 and fp/int4 KV-byte ratios must
match the measured peak-KV ratios within 5% (both sides derive from
the kernel DMA contract `kv_vector_bytes`, so a fail means allocator,
kernel, or model drifted); a telemetry-on drain must classify decode
as memory-bound with achieved GB/s > 0; and `kv_splits` must change
wall time but never modeled bytes. `--roofline-out` exports the
per-phase achieved-bandwidth record.

Reports, per engine: decode steps to drain, wall time (first step
excluded as compile warmup), generated tokens/sec, KV bytes
provisioned, prefill tokens, and peak pages. `--json PATH` (default
bench_smoke.json under --smoke) exports the headline numbers for the
perf-trajectory record, stamped with schema version, git SHA, jax
version, and device kind (`repro.serving.telemetry.bench_metadata`);
under --smoke the same stamped summary is also written to
`BENCH_smoke.json` at the repo root — the tracked cross-PR trajectory
record. `--parts` selects which parts run (e.g. `--parts 1,2,4` skips
the slow jitter study); `--kv-cache-dtype int8` (or `int4`, which
implies bf16 scale rows) serves parts 1-3, 5, and 6's paged engines
from quantized pools.

    PYTHONPATH=src python benchmarks/paged_serving.py
    PYTHONPATH=src python benchmarks/paged_serving.py --requests 16 --slots 4
    PYTHONPATH=src python benchmarks/paged_serving.py --requests 4 --smoke
    PYTHONPATH=src python benchmarks/paged_serving.py --smoke \
        --kv-cache-dtype int8 --parts 1,2,5
    PYTHONPATH=src python benchmarks/paged_serving.py --smoke --parts 6 \
        --trace-out trace.json --metrics-out telemetry.json
    PYTHONPATH=src python benchmarks/paged_serving.py --smoke --parts 7 \
        --sched-out sched.json
    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        PYTHONPATH=src python benchmarks/paged_serving.py --smoke \
        --parts 8 --mesh 2 --json mesh_smoke.json
"""
from __future__ import annotations

import argparse
import json
import os
import time

import jax
import numpy as np

from repro.configs import get_config
from repro.core.salpim import SalPimConfig, SalPimEngine
from repro.models import api
from repro.runtime.compile_cache import enable_compile_cache
from repro.serving import (EngineConfig, FifoScheduler, GenConfig,
                           ServingEngine, SloScheduler, SpecConfig,
                           Telemetry)
from repro.serving.telemetry import bench_metadata

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _kv_opts(kv_cache_dtype):
    """EngineConfig kwargs for a pool dtype. int4 pools require bf16
    scale rows (f32 rows would spend the bytes the packing saved), so
    the choice travels with the dtype everywhere a part builds one."""
    if kv_cache_dtype == "int4":
        return {"kv_cache_dtype": "int4", "kv_scale_dtype": "bfloat16"}
    return {"kv_cache_dtype": kv_cache_dtype}


def _mixed_workload(rng, vocab, n, max_len):
    """Mixed lengths: short chat-y requests + a few long summarizations.
    Every request is clamped to fit: prompt + max_new - 1 <= max_len."""
    assert max_len >= 4, max_len
    reqs = []
    for i in range(n):
        if i % 4 == 3:   # long prompt, short output
            p_len = rng.randint(max_len // 2, 3 * max_len // 4)
            new = rng.randint(4, 8)
        else:            # short prompt, modest output
            p_len = rng.randint(4, 12)
            new = rng.randint(6, 16)
        p_len = min(p_len, max_len - 2)
        new = max(1, min(new, max_len - p_len + 1))
        reqs.append((rng.randint(2, vocab, size=p_len), int(new)))
    return reqs


def _shared_prefix_workload(rng, vocab, n, max_len, prefix_len):
    """System-prompt style: every request starts with the same prefix
    (few-shot template / system prompt) followed by a short unique tail."""
    prefix = rng.randint(2, vocab, size=prefix_len)
    reqs = []
    for _ in range(n):
        tail = rng.randint(2, vocab, size=rng.randint(1, 5))
        prompt = np.concatenate([prefix, tail])
        budget = max_len - len(prompt) + 1
        new = int(max(1, min(rng.randint(4, 10), budget)))
        reqs.append((prompt, new))
    return reqs


def _repetitive_workload(rng, vocab, n, max_len):
    """Looping prompts: a short random block tiled to ~half of max_len.
    Greedy decoding falls into local loops on such contexts, which is
    exactly the structure prompt-lookup (n-gram) drafting predicts —
    the benchmark's stand-in for extractive / templated serving traffic
    where speculative decoding earns its keep."""
    reqs = []
    for _ in range(n):
        block = rng.randint(2, vocab, size=rng.randint(2, 5))
        reps = -(-(max_len // 2) // len(block))
        prompt = np.tile(block, reps)[:max_len // 2]
        budget = max_len - len(prompt) + 1
        new = int(max(4, min(budget, max_len // 2)))
        reqs.append((prompt, new))
    return reqs


def _engine_state_dump(eng):
    """Engine state attached to drain-timeout errors, so a wedged CI run
    is diagnosable from the log alone: per-slot request progress, the
    waiting queue, pool occupancy, and (when enabled) the telemetry
    snapshot's counters and admission view."""
    slots = []
    for i, r in enumerate(eng.active):
        if r is None:
            slots.append({"slot": i, "empty": True})
            continue
        slots.append({"slot": i, "uid": r.uid,
                      "prompt_tokens": len(r.prompt),
                      "prefill_cursor": r.prefill_cursor,
                      "generated": len(r.generated),
                      "max_new_tokens": r.max_new_tokens})
    a = eng.allocator
    dump = {
        "queue": [{"uid": r.uid, "prompt_tokens": len(r.prompt),
                   "max_new_tokens": r.max_new_tokens} for r in eng.queue],
        "slots": slots,
        "pool": {"num_pages": a.num_pages, "used_pages": a.used_pages,
                 "free_pages": a.free_pages,
                 "available_pages": a.available_pages},
    }
    if eng.telemetry.enabled:
        snap = eng.telemetry.snapshot()
        dump["telemetry"] = {"counters": snap["counters"],
                             "admission": snap["admission"]}
    return dump


def _not_drained(eng, max_steps):
    return RuntimeError(
        f"engine not drained after {max_steps} steps; state:\n"
        + json.dumps(_engine_state_dump(eng), indent=2, default=str))


def _drain(eng, reqs, max_steps=10_000):
    for prompt, new in reqs:
        eng.submit(prompt, max_new_tokens=new)

    def drained():
        return not eng.queue and all(a is None for a in eng.active)

    def tok_count():
        return (sum(len(r.generated) for r in eng.finished)
                + sum(len(r.generated) for r in eng.active
                      if r is not None))

    eng.step()       # warmup: first step pays prefill/decode compile
    warm_toks = tok_count()
    steps = 0        # timed steps; the warmup step is in neither rate
    t0 = time.perf_counter()
    while not drained():
        if steps >= max_steps:
            raise _not_drained(eng, max_steps)
        eng.step()
        steps += 1
    dt = time.perf_counter() - t0
    toks = sum(len(r.generated) for r in eng.finished)
    assert len(eng.finished) == len(reqs), (len(eng.finished), len(reqs))
    return {"steps": steps, "sec": dt, "tokens": toks,
            "tok_per_sec": (toks - warm_toks) / max(dt, 1e-9)}


def _kv_bytes(eng):
    # page_bytes includes the int8 mode's scale rows.
    return eng.page_bytes * eng.allocator.num_pages


def _report(mode, eng, stats):
    print(f"{mode:>14}: {stats['steps']} steps, {stats['sec']:.2f}s, "
          f"{stats['tokens']} tokens, {stats['tok_per_sec']:.1f} tok/s, "
          f"KV {stats['kv_bytes'] / 1e6:.2f} MB, "
          f"prefill {eng.prefill_tokens} tok "
          f"(saved {eng.prefill_tokens_saved}), "
          f"peak pages {eng.peak_pages}")


def _jitter_trial(eng, res_prompts, res_new, long_prompt, long_new,
                  max_steps):
    """Resident decodes + a long prompt arriving mid-flight: returns
    (per-step [(seconds, resident tokens emitted)], outputs in submit
    order). The engine is deterministic, so repeated trials execute the
    identical step sequence — callers can align steps by index."""
    res_uids = [eng.submit(p.copy(), max_new_tokens=n)
                for p, n in zip(res_prompts, res_new)]
    res_reqs = [r for r in eng.queue if r.uid in set(res_uids)]
    for _ in range(3):
        eng.step()                    # residents admitted and decoding
    long_uid = eng.submit(long_prompt.copy(), max_new_tokens=long_new)
    prev = {r.uid: len(r.generated) for r in res_reqs}
    steps = []
    # Python's cyclic GC fires mid-loop (30-50 ms pauses, dwarfing a
    # decode step on smoke models) — park it while timing.
    import gc
    gc.collect()
    gc.disable()
    try:
        while eng.queue or any(a is not None for a in eng.active):
            if len(steps) >= max_steps:
                raise _not_drained(eng, max_steps)
            t0 = time.perf_counter()
            eng.step()
            dt = time.perf_counter() - t0
            emitted = 0
            for r in res_reqs:
                if len(r.generated) > prev[r.uid]:
                    emitted += 1
                    prev[r.uid] = len(r.generated)
            steps.append((dt, emitted))
    finally:
        gc.enable()
    by = {r.uid: list(r.generated) for r in eng.finished}
    outs = [by[u] for u in res_uids + [long_uid]]
    return steps, outs


def _part4(params, cfg, engine, gen, *, slots, max_len, requests,
           page_size, seed, max_steps, smoke):
    """int8 KV pages vs fp pages on the same paged workload.

    Drives both engines in lockstep over an identical request stream and
    asserts: (1) greedy outputs exactly match, (2) every per-step logit
    stays within 0.25 x the fp logit std (the same envelope the dense
    int8 KV path is held to in tests/test_perf_features.py), (3) peak KV
    bytes drop ~2x at equal peak page count, and (4) at the fp pool's
    byte budget the int8 pool holds ~1.8x+ the pages (the resident-
    capacity doubling). Mean decode-step wall time is reported for both.
    """
    rng = np.random.RandomState(seed + 2)
    reqs = _mixed_workload(rng, cfg.vocab, requests, max_len)
    stats = {}
    outs = {}
    hists = {}
    for label, kv_dtype in [("paged-fp", "model"), ("paged-int8", "int8")]:
        eng = ServingEngine(params, cfg, engine, EngineConfig(
            slots=slots, max_len=max_len, gen=gen,
            page_size=page_size, kv_cache_dtype=kv_dtype))
        for p, n in reqs:
            eng.submit(p.copy(), max_new_tokens=n)
        eng.step()                        # compile warmup (untimed)
        hist = [np.asarray(eng.last_logits)]
        steps = 0
        dt = 0.0
        while eng.queue or any(a is not None for a in eng.active):
            if steps >= max_steps:
                raise _not_drained(eng, max_steps)
            # Clock only the engine step; the logit snapshot below is
            # bench instrumentation (device->host copy) and would
            # otherwise pad both engines' step_ms toward parity.
            t0 = time.perf_counter()
            eng.step()
            dt += time.perf_counter() - t0
            hist.append(np.asarray(eng.last_logits))
            steps += 1
        hists[label] = hist
        outs[label] = {r.uid: list(r.generated) for r in eng.finished}
        stats[label] = {
            "steps": steps,
            "step_ms": dt / max(steps, 1) * 1e3,
            "peak_pages": eng.peak_pages,
            "peak_kv_bytes": eng.peak_pages * eng.page_bytes,
            "pool_pages": eng.allocator.num_pages - 1,
        }
        print(f"{label:>14}: {steps} steps, "
              f"{stats[label]['step_ms']:.2f} ms/step, peak "
              f"{eng.peak_pages} pages = "
              f"{stats[label]['peak_kv_bytes'] / 1e6:.3f} MB KV, pool "
              f"{stats[label]['pool_pages']} pages at the fp byte budget")

    fp, q8 = stats["paged-fp"], stats["paged-int8"]
    # Structural invariants hold at any scale: with stop_on_eos=False and
    # fixed per-request budgets the engine schedule (admissions, steps,
    # page trajectory) is independent of the token *values*, and the fp
    # default pool is slot-limited (slots * max_pages pages), so both
    # engines execute the identical step sequence.
    assert len(hists["paged-fp"]) == len(hists["paged-int8"]), \
        "schedules diverged"
    assert q8["peak_pages"] == fp["peak_pages"], "schedules diverged"
    byte_ratio = fp["peak_kv_bytes"] / max(q8["peak_kv_bytes"], 1)
    assert byte_ratio >= 1.8, f"peak KV bytes only dropped {byte_ratio:.2f}x"
    cap_ratio = q8["pool_pages"] / max(fp["pool_pages"], 1)
    assert cap_ratio >= 1.8, f"capacity only grew {cap_ratio:.2f}x"

    uids = sorted(outs["paged-fp"])
    n_match = sum(outs["paged-int8"][u] == outs["paged-fp"][u]
                  for u in uids)
    fp_all = np.stack(hists["paged-fp"])
    logit_diff = float(np.max(np.abs(fp_all - np.stack(hists["paged-int8"]))))
    logit_tol = 0.25 * float(np.std(fp_all))
    if smoke:
        # Content-sensitive accuracy gates run on the smoke prompts the
        # repo validates (tests/test_paged_int8.py holds the same bar).
        # At larger scales a single near-tied argmax can legitimately
        # flip — and once one token differs the remaining logits compare
        # different *contexts* — so full runs report instead of gating.
        assert n_match == len(uids), \
            "int8 KV pages changed greedy outputs on the smoke prompts"
        assert logit_diff < logit_tol, (logit_diff, logit_tol)
    print(f"int8 KV pages: peak KV bytes {byte_ratio:.1f}x lower "
          f"({fp['peak_kv_bytes'] / 1e6:.3f} -> "
          f"{q8['peak_kv_bytes'] / 1e6:.3f} MB), {cap_ratio:.1f}x pages at "
          f"fixed HBM, {n_match}/{len(uids)} outputs exact-match, "
          f"max logit diff {logit_diff:.4f} "
          f"(tol {logit_tol:.4f}; diffs past a flipped token compare "
          "different contexts)")
    return {"step_ms_fp": fp["step_ms"], "step_ms_int8": q8["step_ms"],
            "peak_kv_bytes_fp": fp["peak_kv_bytes"],
            "peak_kv_bytes_int8": q8["peak_kv_bytes"],
            "pool_pages_ratio": cap_ratio,
            "exact_match": n_match, "exact_match_of": len(uids),
            "logit_maxdiff": logit_diff, "logit_tol": logit_tol}


def _part5(params, cfg, engine, gen, *, slots, max_len, requests,
           page_size, seed, max_steps, smoke, spec_k=4,
           kv_cache_dtype="model"):
    """Speculative decoding: spec-off vs spec-on (n-gram drafting) on a
    repetitive workload, same request stream on both engines.

    Each engine drains the workload twice: an untimed warmup drain pays
    every jit compile (the verify forward compiles shapes the spec-off
    engine never sees — clocking it made historical spec-on ms/token
    read ~7x *worse* than spec-off, a pure artifact), then a timed
    drain whose wall seconds / generated tokens is the reported
    ms/token — the same end-to-end unit for both engines, directly
    comparable. Host-side draft time (argmaxes + n-gram lookups) is
    reported as its own share of spec-on step time instead of being
    buried in the average.

    Asserts greedy outputs bit-identical (always — the acceptance rule
    only ever commits the target's own argmax choices) and, under
    --smoke, that the spec-on engine spends < 1 verify round per
    generated token *with real acceptance behind it*: verify rounds are
    counted per slot (one full model stream each, the same unit as a
    decode step), a zero-acceptance run needs exactly tokens - requests
    rounds (each request's final token is a free argmax), so the assert
    demands strictly fewer — at least one accepted draft saved a whole
    model stream. All gates and reported numbers cover the timed drain
    only (stat deltas across it, not engine-lifetime cumulatives).
    Acceptance rate, both ms/token figures, and the draft share go to
    the JSON artifact.
    """
    rng = np.random.RandomState(seed + 3)
    reqs = _repetitive_workload(rng, cfg.vocab, requests, max_len)
    stats = {}
    outs = {}
    for label, spec in [
        ("spec-off", None),
        ("spec-on", SpecConfig(mode="ngram", k=spec_k)),
    ]:
        eng = ServingEngine(params, cfg, engine, EngineConfig(
            slots=slots, max_len=max_len, gen=gen,
            page_size=page_size, speculative=spec,
            **_kv_opts(kv_cache_dtype)))
        # Warmup drain: every compile lands here. Its outputs feed the
        # bit-identicality assert — the engine is deterministic, so the
        # timed drain below replays the same tokens.
        _drain(eng, [(p.copy(), n) for p, n in reqs],
               max_steps=max_steps)
        outs[label] = {r.uid: list(r.generated) for r in eng.finished}
        es0 = eng.stats()
        for p, n in reqs:
            eng.submit(p.copy(), max_new_tokens=n)
        steps = 0
        t0 = time.perf_counter()
        while eng.queue or any(a is not None for a in eng.active):
            if steps >= max_steps:
                raise _not_drained(eng, max_steps)
            eng.step()
            steps += 1
        dt = time.perf_counter() - t0
        es1 = eng.stats()
        # Everything below is a delta over the timed drain alone.
        st = {"steps": steps, "sec": dt,
              "tokens": es1["tokens"] - es0["tokens"]}
        st["ms_per_token"] = st["sec"] / max(st["tokens"], 1) * 1e3
        st["spec_rounds"] = es1["spec_rounds"] - es0["spec_rounds"]
        st["proposed"] = es1["proposed"] - es0["proposed"]
        st["accepted"] = es1["accepted"] - es0["accepted"]
        st["acceptance_rate"] = st["accepted"] / max(st["proposed"], 1)
        st["verify_per_token"] = st["spec_rounds"] / max(st["tokens"], 1)
        st["tokens_per_pass"] = (st["tokens"] / st["spec_rounds"]
                                 if st["spec_rounds"] else 0.0)
        st["draft_time_share"] = (
            (es1["draft_sec"] - es0["draft_sec"])
            / max(es1["step_sec"] - es0["step_sec"], 1e-12))
        stats[label] = st
        print(f"{label:>14}: {st['steps']} steps, {st['tokens']} tokens, "
              f"{st['ms_per_token']:.2f} ms/token, "
              f"accept {st['accepted']}/{st['proposed']} "
              f"({st['acceptance_rate']:.0%}), "
              f"{st['spec_rounds']} verify rounds "
              f"({st['verify_per_token']:.2f}/token)")

    assert outs["spec-on"] == outs["spec-off"], \
        "speculative decoding changed greedy outputs"
    on = stats["spec-on"]
    vpt = on["verify_per_token"]
    print(f"speculative decoding: outputs bit-identical, "
          f"{vpt:.2f} verify rounds per generated token "
          f"({on['tokens_per_pass']:.2f} tokens/round at "
          f"{on['acceptance_rate']:.0%} acceptance), decode "
          f"{stats['spec-off']['ms_per_token']:.2f} -> "
          f"{on['ms_per_token']:.2f} ms/token warmed "
          f"(draft share {on['draft_time_share']:.0%} of spec-on "
          "step time)")
    if smoke:
        assert vpt < 1.0, (vpt, on)
        # The teeth: strictly fewer model streams than a zero-acceptance
        # run would need (tokens - requests: each request's final token
        # is a free argmax in both engines).
        no_accept_rounds = on["tokens"] - len(reqs)
        assert on["spec_rounds"] < no_accept_rounds, (
            "speculation accepted nothing on the repetitive workload: "
            f"{on['spec_rounds']} verify rounds for {on['tokens']} tokens "
            f"({no_accept_rounds} = zero-acceptance cost)")
    return {"acceptance_rate": on["acceptance_rate"],
            "verify_per_token": vpt,
            "tokens_per_pass": on["tokens_per_pass"],
            "ms_per_token_off": stats["spec-off"]["ms_per_token"],
            "ms_per_token_on": on["ms_per_token"],
            "draft_time_share": on["draft_time_share"]}


def _bursty_arrivals(rng, vocab, n, max_len, prefix_len=None):
    """Part 6's arrival schedule: half the requests land at step 0, the
    rest in a burst a few steps in — oversubscription that exercises
    queueing, watermark blocking, and the pool-occupancy swings the
    telemetry timeline is there to capture. With `prefix_len`, a third
    wave of shared-prefix requests lands later still, so the snapshot's
    prefix-cache hit rate reflects real hits instead of the structural
    0.0 a purely mixed workload produces (the historical export showed
    exactly that — a dead gauge nobody could regress against). Returns
    a sorted list of (step_index, [(prompt, max_new), ...])."""
    reqs = _mixed_workload(rng, vocab, n, max_len)
    split = max(1, n // 2)
    waves = [(0, reqs[:split]), (3, reqs[split:])]
    if prefix_len:
        shared = _shared_prefix_workload(rng, vocab, max(2, n // 2),
                                         max_len, prefix_len)
        waves.append((6, shared))
    return waves


def _drain_bursty(eng, arrivals, max_steps):
    """Submit per the arrival schedule, step until drained. Returns
    steps, wall seconds, and outputs in submit order. Every step is
    timed — part 6 warms each engine with one untimed drain first, so
    compiles never land inside a measured trial."""
    uids = []
    pending = list(arrivals)
    step = 0
    t0 = time.perf_counter()
    while pending or eng.queue or any(a is not None for a in eng.active):
        while pending and pending[0][0] <= step:
            _, batch = pending.pop(0)
            uids += [eng.submit(p.copy(), max_new_tokens=n)
                     for p, n in batch]
        if step >= max_steps:
            raise _not_drained(eng, max_steps)
        eng.step()
        step += 1
    dt = time.perf_counter() - t0
    by = {r.uid: list(r.generated) for r in eng.finished}
    return {"steps": step, "sec": dt, "outputs": [by[u] for u in uids]}


def _part6(params, cfg, engine, gen, *, slots, max_len, requests,
           page_size, seed, max_steps, smoke, kv_cache_dtype="model",
           trace_out=None, metrics_out=None, trials=3):
    """Serving telemetry on a bursty mixed workload: zero-cost-when-off
    gate plus the observability exports.

    Two identical chunked-prefill engines drain the same arrival
    schedule, telemetry off and on. Asserts: (1) greedy outputs are
    bit-identical — telemetry records at step boundaries only, never
    inside jit; (2) the disabled engine's registry stays empty (the
    no-op is real, not just cheap); (3) counters are exact — the window
    records precisely trials x the workload's token/request totals; (4)
    under --smoke, enabled ms/step stays within 5% of disabled
    (min over interleaved trials, so both engines sample the same host
    weather and additive noise cancels). The enabled run then exports
    the metrics snapshot and a Chrome trace_event file — the occupancy
    timeline + inter-token histogram baselines for the SLO-scheduler
    work.
    """
    rng = np.random.RandomState(seed + 4)
    # Prefix must tile whole pages for the cache to map it; same
    # rounding run() uses for part 2's shared-prefix workload.
    prefix_len = max(page_size, (max_len // 2 // page_size) * page_size)
    arrivals = _bursty_arrivals(rng, cfg.vocab, requests, max_len,
                                prefix_len=prefix_len)
    n_reqs = sum(len(batch) for _, batch in arrivals)
    n_new = sum(n for _, batch in arrivals for _, n in batch)
    chunk = max(4, max_len // 4)
    tel = Telemetry(enabled=True)
    engines = {}
    for label, t in [("telemetry-off", None), ("telemetry-on", tel)]:
        engines[label] = ServingEngine(params, cfg, engine, EngineConfig(
            slots=slots, max_len=max_len, gen=gen,
            page_size=page_size, prefix_sharing=True,
            prefill_chunk_tokens=chunk, telemetry=t,
            **_kv_opts(kv_cache_dtype)))

    # Warmup drain per engine pays every jit compile; its outputs feed
    # the bit-identicality assert (the engine is deterministic, so the
    # timed drains below replay the same tokens).
    outs = {label: _drain_bursty(eng, arrivals, max_steps)["outputs"]
            for label, eng in engines.items()}
    assert outs["telemetry-on"] == outs["telemetry-off"], \
        "telemetry changed greedy outputs"
    assert engines["telemetry-off"].telemetry.registry.empty, \
        "disabled telemetry populated its metrics registry"

    tel.reset()                       # measured window: the timed trials
    times = {label: [] for label in engines}
    import gc
    gc.collect()
    gc.disable()
    try:
        for _ in range(trials):
            for label, eng in engines.items():
                st = _drain_bursty(eng, arrivals, max_steps)
                times[label].append(st["sec"] / max(st["steps"], 1))
    finally:
        gc.enable()
    off_ms = min(times["telemetry-off"]) * 1e3
    on_ms = min(times["telemetry-on"]) * 1e3
    ratio = on_ms / max(off_ms, 1e-12)

    snap = tel.snapshot()
    counters = snap["counters"]
    assert counters["tokens.generated"] == trials * n_new, \
        (counters["tokens.generated"], trials, n_new)
    assert counters["requests.finished"] == trials * n_reqs
    # The SLO-scheduler baselines the snapshot must carry:
    assert len(snap["pool"]["occupancy_timeline"]) == snap["steps"]["count"]
    assert 0.0 <= snap["prefix_cache"]["hit_rate"] <= 1.0
    if smoke:
        # The shared-prefix wave must register actual cache hits — a
        # 0.0 here means the gauge is dead, not that the workload is
        # uncacheable (the warmup drain already seeded the prefix).
        assert snap["prefix_cache"]["hit_rate"] > 0.0, \
            "prefix-cache hit rate stayed 0.0 despite shared-prefix wave"
    assert "rejected" in snap["admission"]
    per_req = snap["requests"]["per_request"]
    assert per_req and all("inter_token_p50_sec" in r and
                           "inter_token_p99_sec" in r for r in per_req)

    if metrics_out:
        tel.export_json(metrics_out)
        print(f"wrote {metrics_out}")
    n_events = None
    if trace_out:
        n_events = tel.export_chrome_trace(trace_out)
        with open(trace_out) as f:
            events = json.load(f)["traceEvents"]
        open_spans = {}
        for e in events:
            if e["ph"] == "B":
                open_spans[e["tid"]] = open_spans.get(e["tid"], 0) + 1
            elif e["ph"] == "E":
                open_spans[e["tid"]] = open_spans.get(e["tid"], 0) - 1
        assert all(v == 0 for v in open_spans.values()), \
            f"unbalanced B/E spans in {trace_out}: {open_spans}"
        print(f"wrote {trace_out} ({n_events} events, "
              "load at https://ui.perfetto.dev)")

    print(f"{'telemetry':>14}: {off_ms:.3f} -> {on_ms:.3f} ms/step "
          f"({ratio:.3f}x) over {trials} interleaved trials, outputs "
          f"bit-identical, {counters['tokens.generated']} tokens and "
          f"{snap['steps']['count']} steps recorded, prefix-cache hit "
          f"rate {snap['prefix_cache']['hit_rate']:.0%}")
    if smoke:
        assert ratio <= 1.05, (
            f"telemetry overhead {ratio:.3f}x exceeds the 5% budget "
            f"({off_ms:.3f} -> {on_ms:.3f} ms/step)")
    return {"step_ms_off": off_ms, "step_ms_on": on_ms,
            "overhead_ratio": ratio,
            "prefix_cache_hit_rate": snap["prefix_cache"]["hit_rate"],
            "tokens_recorded": counters["tokens.generated"],
            "trace_events": n_events}


def _slo_arrivals(rng, vocab, n, max_len):
    """Part 7's oversubscribed mixed-priority schedule: a backlog of
    long batch requests (priority 1) lands at step 0; short interactive
    requests (priority 0) trickle in afterwards, one every three steps.
    Returns [(step, priority, prompt, max_new), ...]."""
    n_batch = max(3, n // 2)
    n_int = max(3, n - n_batch)
    arrivals = []
    for _ in range(n_batch):
        plen = int(rng.randint(max_len // 4, max_len // 2 + 1))
        new = min(int(rng.randint(max_len // 4, max_len // 2 + 1)),
                  max_len - plen)
        arrivals.append((0, 1, rng.randint(2, vocab, size=plen), new))
    for i in range(n_int):
        plen = int(rng.randint(3, max(4, max_len // 8) + 1))
        arrivals.append((2 + 3 * i, 0, rng.randint(2, vocab, size=plen),
                         max(2, max_len // 8)))
    return arrivals


def _drain_stepwise(eng, arrivals, max_steps):
    """Submit per the arrival schedule and record, per request, the step
    index of every token emission. All latency numbers downstream are
    *step-indexed* — deterministic scheduling quality, independent of
    host wall-clock noise, so the smoke gate cannot flake. Returns
    {uid: {"prio", "submit_step", "emits", "tokens"}} in submit order."""
    info = {}
    reqs = {}
    pending = sorted(arrivals, key=lambda a: a[0])
    step = 0
    while (pending or eng.queue or eng.swapped
           or any(a is not None for a in eng.active)):
        while pending and pending[0][0] <= step:
            _, prio, p, n = pending.pop(0)
            uid = eng.submit(p.copy(), max_new_tokens=n, priority=prio)
            info[uid] = {"prio": prio, "submit_step": step, "emits": []}
            reqs[uid] = eng.queue[-1]
        if step >= max_steps:
            raise _not_drained(eng, max_steps)
        eng.step()
        step += 1
        for uid, r in reqs.items():
            while len(info[uid]["emits"]) < len(r.generated):
                info[uid]["emits"].append(step)
    for uid, r in reqs.items():
        info[uid]["tokens"] = list(r.generated)
    return info


def _gap_stats(info, prio, deadline_steps):
    """Per-class step-gap percentiles + goodput. Gaps are diffs over
    [submit_step, emit steps...]: the first gap is time-to-first-token
    (where queueing and preemption policy actually show up), the rest
    are inter-token stalls."""
    gaps, ttfts = [], []
    for rec in info.values():
        if rec["prio"] != prio or not rec["emits"]:
            continue
        seq = [rec["submit_step"]] + rec["emits"]
        gaps += [b - a for a, b in zip(seq, seq[1:])]
        ttfts.append(rec["emits"][0] - rec["submit_step"])
    p50, p99 = np.percentile(np.asarray(gaps), [50, 99], method="higher")
    good = sum(1 for t in ttfts if t <= deadline_steps)
    return {"p50_gap_steps": int(p50), "p99_gap_steps": int(p99),
            "ttft_steps": ttfts, "goodput": good / max(len(ttfts), 1)}


def _part7(params, cfg, engine, gen, *, slots, max_len, requests,
           page_size, seed, max_steps, smoke, kv_cache_dtype="model",
           sched_out=None):
    """SLO scheduling under oversubscription: FIFO watermark admission
    vs preempt-and-swap, identical arrivals.

    The pool is sized to ~1.75x one worst-case request, so the batch
    backlog oversubscribes it: FIFO's worst-case reservations serialize
    the batch class and head-of-line-block every interactive request
    behind it, while the SLO policy admits optimistically, skips blocked
    candidates, and preempts/swaps batch slots when an interactive
    request lands. Latency is measured in *steps* (deterministic — see
    `_drain_stepwise`); the headline is the interactive class's p99
    step gap and its goodput (TTFT within a deadline) under each
    policy. Asserts (always) that per-request greedy outputs are
    bit-identical across policies — scheduling moves work, never
    changes tokens — and under --smoke that the SLO policy actually
    preempted-and-swapped, beat FIFO's interactive p99, and matched or
    beat its goodput. The SLO engine's scheduler-decision counters
    (sched.preempt/swap_out/swap_in/...) are exported to `sched_out`."""
    rng = np.random.RandomState(seed + 7)
    arrivals = _slo_arrivals(rng, cfg.vocab, max(requests, 6), max_len)
    # 1.75x one worst-case request (max_len tokens), plus the trash page:
    # any single request fits alone, the backlog cannot all fit at once.
    num_pages = 1 + int(1.75 * -(-max_len // page_size))
    deadline = max(4, max_len // 4)
    tel = Telemetry(enabled=True)
    results, infos, engines = {}, {}, {}
    for label, sched, t in [("fifo", None, None),
                            ("slo", SloScheduler(), tel)]:
        eng = ServingEngine(params, cfg, engine, EngineConfig(
            slots=slots, max_len=max_len, gen=gen,
            page_size=page_size, num_pages=num_pages,
            prefix_sharing=True, scheduler=sched, telemetry=t,
            **_kv_opts(kv_cache_dtype)))
        infos[label] = _drain_stepwise(eng, arrivals, max_steps)
        results[label] = _gap_stats(infos[label], prio=0,
                                    deadline_steps=deadline)
        engines[label] = eng
        st = _gap_stats(infos[label], prio=1, deadline_steps=deadline)
        print(f"{label:>14}: interactive p50/p99 gap "
              f"{results[label]['p50_gap_steps']}/"
              f"{results[label]['p99_gap_steps']} steps, goodput "
              f"{results[label]['goodput']:.0%} (TTFT <= {deadline} "
              f"steps); batch p99 gap {st['p99_gap_steps']} steps")
    assert ([infos["fifo"][u]["tokens"] for u in sorted(infos["fifo"])]
            == [infos["slo"][u]["tokens"] for u in sorted(infos["slo"])]), \
        "scheduling policy changed greedy outputs"
    slo_eng = engines["slo"]
    sched_counters = tel.snapshot().get("scheduler", {})
    print(f"{'slo decisions':>14}: {slo_eng.preemptions} preemptions, "
          f"{slo_eng.swap_outs} swap-outs / {slo_eng.swap_ins} swap-ins, "
          f"swap tier peak {slo_eng.swap_tier.bytes_peak / 1e6:.2f} MB, "
          f"pool {num_pages - 1} usable pages")
    if sched_out:
        payload = {
            "scheduler_counters": sched_counters,
            "interactive": {label: {k: v for k, v in r.items()
                                    if k != "ttft_steps"}
                            for label, r in results.items()},
            "deadline_steps": deadline,
            "preemptions": slo_eng.preemptions,
            "swap_outs": slo_eng.swap_outs,
            "swap_ins": slo_eng.swap_ins,
            "swap_bytes_peak": slo_eng.swap_tier.bytes_peak,
            "meta": bench_metadata(),
        }
        with open(sched_out, "w") as f:
            json.dump(payload, f, indent=2, sort_keys=True)
            f.write("\n")
        print(f"wrote {sched_out}")
    if smoke:
        assert slo_eng.preemptions > 0 and slo_eng.swap_ins > 0, \
            "part 7 workload failed to force preempt-and-swap"
        assert (results["slo"]["p99_gap_steps"]
                < results["fifo"]["p99_gap_steps"]), (
            f"SLO p99 gap {results['slo']['p99_gap_steps']} steps did not "
            f"beat FIFO {results['fifo']['p99_gap_steps']}")
        assert results["slo"]["goodput"] >= results["fifo"]["goodput"], (
            results["slo"]["goodput"], results["fifo"]["goodput"])
    return {"p99_gap_steps_fifo": results["fifo"]["p99_gap_steps"],
            "p99_gap_steps_slo": results["slo"]["p99_gap_steps"],
            "goodput_fifo": results["fifo"]["goodput"],
            "goodput_slo": results["slo"]["goodput"],
            "preemptions": slo_eng.preemptions,
            "swap_ins": slo_eng.swap_ins}


def _part3(cfg, engine, gen, *, max_len, page_size, seed, max_steps, smoke,
           kv_cache_dtype="model"):
    """Decode-latency jitter, one-shot ("stall") vs chunked prefill.

    Runs on its own fixed workload shape (cfg is widened and max_len
    floored below) — parts 1/2's --slots/--requests sizing does not
    apply here.
    """
    import dataclasses

    # The jitter contrast needs prefill *compute* to dwarf a decode step
    # and the per-call dispatch constants. The smoke models are so small
    # that a 100-token one-shot prefill costs about the same as an
    # 8-token chunk — so part 3 runs on its own horizon (independent of
    # the --smoke-shrunk part-1/2 sizes): a short but *wide* stack, where
    # prefill GEMMs scale with d_model^2 while the per-step decode floor
    # (block-table reads) scales only with d_model. On that shape the
    # one-shot prefill of the long prompt costs many decode steps and the
    # stall spike is unambiguous even on a noisy CI host.
    max_len = max(max_len, 256)
    # Exactly one resident + one slot for the long prompt: more slots
    # inflate the per-step block-table read floor and drown the contrast.
    slots = 2
    cfg = dataclasses.replace(
        cfg, n_layers=4, d_model=512, n_heads=8, n_kv_heads=8,
        head_dim=64, d_ff=2048, max_seq=max(cfg.max_seq, max_len))
    params = api.init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.RandomState(seed + 1)
    n_res = slots - 1                  # one slot stays free for the long one
    res_prompts = [rng.randint(2, cfg.vocab, size=5) for _ in range(n_res)]
    res_new = [48] * n_res
    long_prompt = rng.randint(2, cfg.vocab, size=3 * max_len // 4)
    chunk = len(long_prompt) // 3

    modes = [("stall", None), ("chunked", chunk)]
    engines = {}
    trials = {label: [] for label, _ in modes}
    outs = {}
    for label, chunk_tokens in modes:
        engines[label] = ServingEngine(params, cfg, engine, EngineConfig(
            slots=slots, max_len=max_len, gen=gen,
            page_size=page_size, prefill_chunk_tokens=chunk_tokens,
            **_kv_opts(kv_cache_dtype)))
        # Warm every jit shape (prefill chunks, decode) on this engine.
        _jitter_trial(engines[label], res_prompts, res_new, long_prompt, 4,
                      max_steps)
    # The engine is deterministic, so repeated trials execute the
    # identical step sequence; the per-step-index MIN across trials
    # strips additive host noise and leaves each structural step's cost
    # — the stall spike and the chunk steps both survive, one-off jitter
    # does not. Trials of the two modes are interleaved so both sample
    # the same machine weather.
    for _ in range(4):
        for label, _ in modes:
            steps, outs[label] = _jitter_trial(
                engines[label], res_prompts, res_new, long_prompt, 4,
                max_steps)
            trials[label].append(steps)
    stats = {}
    for label, chunk_tokens in modes:
        runs = trials[label]
        assert len({len(t) for t in runs}) == 1, "trials diverged"
        inter = []
        for i in range(len(runs[0])):
            dt = min(t[i][0] for t in runs)
            inter.extend([dt] * runs[0][i][1])
        # method="higher": the p99 is an actual observed step, so a
        # single structural spike (the stall) is not interpolated away.
        p50, p99 = np.percentile(np.asarray(inter), [50, 99],
                                 method="higher")
        stats[label] = {"p50": float(p50), "p99": float(p99),
                        "samples": len(inter)}
        print(f"{label:>14}: resident inter-token p50 "
              f"{stats[label]['p50'] * 1e3:.2f} ms, p99 "
              f"{stats[label]['p99'] * 1e3:.2f} ms over {len(inter)} tokens "
              f"x4 trials (long prompt {len(long_prompt)} tok, "
              f"chunk {chunk_tokens or 'whole prompt'})")

    assert outs["chunked"] == outs["stall"], \
        "chunked prefill changed greedy outputs"
    ratio = stats["stall"]["p99"] / max(stats["chunked"]["p99"], 1e-12)
    print(f"chunked prefill p99 inter-token: {stats['chunked']['p99'] * 1e3:.2f} ms "
          f"vs stall-the-world {stats['stall']['p99'] * 1e3:.2f} ms "
          f"({ratio:.1f}x)")
    if smoke:
        assert stats["chunked"]["p99"] < stats["stall"]["p99"], (
            "chunked prefill did not lower p99 inter-token latency: "
            f"{stats['chunked']['p99']:.6f}s vs {stats['stall']['p99']:.6f}s")
    return stats


def _per_device_pool_bytes(eng):
    """Resident KV pool bytes on one device, measured from the actual
    shards (`addressable_shards[0]`) — with a sharded pool this is the
    global pool bytes divided by the mesh's 'model' axis extent, with a
    replicated (or single-device) pool it is the full pool."""
    total = 0
    for leaf in (eng.cache.k_pages, eng.cache.v_pages,
                 eng.cache.k_scale, eng.cache.v_scale):
        if leaf is not None:
            total += leaf.addressable_shards[0].data.nbytes
    return total


def _part8(params, cfg, engine, gen, *, slots, max_len, requests,
           page_size, seed, max_steps, smoke, kv_cache_dtype, mesh_width):
    """Multi-chip paged serving: single-device vs mesh-sharded pools.

    Drains the same mixed workload on a single-device paged engine and
    on engines whose page pools are sharded over a ("model",) mesh of
    2-8 fake CPU devices, asserting bit-identical greedy outputs, and
    measures decode ms/step, per-device resident pool bytes, and the
    resident-capacity scaling at a fixed per-device HBM budget. Returns
    the per-width rows plus a skip note when the host exposes too few
    devices (or the width doesn't divide n_kv_heads).
    """
    from jax.sharding import Mesh

    n_dev = len(jax.devices())
    want = [mesh_width] if mesh_width else [2, 4, 8]
    widths, skipped = [], []
    for w in want:
        if w > n_dev:
            skipped.append((w, f"{n_dev} device(s) visible"))
        elif cfg.n_kv_heads % w != 0:
            skipped.append((w, f"does not divide n_kv_heads={cfg.n_kv_heads}"))
        else:
            widths.append(w)
    for w, why in skipped:
        print(f"part 8: mesh width {w} skipped ({why})")
    out = {"devices": n_dev, "widths": widths,
           "skipped": [f"{w}: {why}" for w, why in skipped]}
    if not widths:
        print("part 8: no feasible mesh width; run under "
              "XLA_FLAGS=--xla_force_host_platform_device_count=8")
        return out

    rng = np.random.RandomState(seed + 8)
    reqs = _mixed_workload(rng, cfg.vocab, requests, max_len)

    def build_and_drain(mesh):
        eng = ServingEngine(params, cfg, engine, EngineConfig(
            slots=slots, max_len=max_len, gen=gen,
            page_size=page_size, mesh=mesh, **_kv_opts(kv_cache_dtype)))
        stats = _drain(eng, [(p.copy(), n) for p, n in reqs],
                       max_steps=max_steps)
        stats["step_ms"] = stats["sec"] / max(stats["steps"], 1) * 1e3
        stats["pool_bytes_per_device"] = _per_device_pool_bytes(eng)
        return eng, stats

    single_eng, single = build_and_drain(None)
    single_out = {r.uid: list(r.generated) for r in single_eng.finished}
    pool_pages = single_eng.allocator.num_pages
    budget = single["pool_bytes_per_device"]   # one device's pool bytes
    print(f"  single-device: {single['step_ms']:.2f} ms/step, "
          f"{budget / 1e6:.2f} MB/device pool, {pool_pages} pages")
    out["step_ms_single"] = single["step_ms"]
    out["pool_bytes_per_device_single"] = budget
    out["per_width"] = {}

    for w in widths:
        mesh = Mesh(np.array(jax.devices()[:w]), ("model",))
        eng, stats = build_and_drain(mesh)
        outs = {r.uid: list(r.generated) for r in eng.finished}
        assert outs == single_out, \
            f"mesh={w} outputs diverged from single-device"
        shrink = budget / stats["pool_bytes_per_device"]
        # Same per-device HBM budget, w-way sharded pages: the pool that
        # fits is `shrink`x larger, i.e. resident capacity scales with
        # the mesh width.
        pages_at_budget = int(pool_pages * shrink)
        print(f"  mesh={w}: {stats['step_ms']:.2f} ms/step, "
              f"{stats['pool_bytes_per_device'] / 1e6:.2f} MB/device pool "
              f"({shrink:.2f}x shrink), {pages_at_budget} pages at the "
              f"single-device budget, outputs bit-identical")
        out["per_width"][str(w)] = {
            "step_ms": stats["step_ms"],
            "pool_bytes_per_device": stats["pool_bytes_per_device"],
            "pool_shrink_x": shrink,
            "pages_at_budget": pages_at_budget,
        }
        if smoke and w >= 2:
            assert shrink >= 1.8, \
                f"mesh={w}: per-device pool bytes shrank only {shrink:.2f}x"
    return out


def _part9(params, cfg, engine, gen, *, smoke, seed):
    """KV-split flash-decode attention + int4 page pools.

    (a) Kernel-level split study: one decode-attention call over an 8k-
    token resident context served from an fp page pool, single page walk
    vs kv_splits=32 partials (`ops.pim_paged_attention`, reference
    impl, both jit-compiled). The split path parallelizes the KV walk
    that the single grid walks serially, so at long context it must be
    *faster*: median of >= 20 timed calls, gate (under --smoke)
    split <= 0.6x single-walk, outputs allclose always. Quantized pools
    are deliberately not gated — XLA fuses their dequant into the walk
    well enough that splitting does not pay there.

    (b) int4 pools end-to-end: the fp and int4 engines drain the pinned
    int4 smoke workload (tests/test_paged_int4_split.py serves the same
    one) in lockstep. Gates: greedy outputs exact-match under --smoke,
    and peak KV bytes >= 3.5x below fp always (structural: nibble
    payload + bf16 scale rows vs full-width vectors).
    """
    from repro.kernels import ops

    # -- (a) the split study: 8k context, page 16, fp pool ------------------
    B = 2 if smoke else 4
    Hq, Hkv, D, page, ctx = 8, 8, 128, 16, 8192
    npg, splits = ctx // page, 32
    key = jax.random.PRNGKey(seed + 9)
    ks = jax.random.split(key, 3)
    kp = jax.random.normal(ks[0], (B * npg + 1, Hkv, page, D),
                           dtype=jax.numpy.float32)
    vp = jax.random.normal(ks[1], kp.shape, dtype=jax.numpy.float32)
    q = jax.random.normal(ks[2], (B, Hq, D), dtype=jax.numpy.float32)
    tbl = jax.numpy.asarray(
        np.random.RandomState(seed).permutation(B * npg).reshape(B, npg)
        + 1, jax.numpy.int32)
    lens = jax.numpy.full((B,), ctx, jax.numpy.int32)

    def median_ms(fn, iters=20):
        fn().block_until_ready()              # compile warmup (untimed)
        ts = []
        for _ in range(iters):
            t0 = time.perf_counter()
            fn().block_until_ready()
            ts.append(time.perf_counter() - t0)
        return float(np.median(ts)) * 1e3

    def single():
        return ops.pim_paged_attention(q, kp, vp, tbl, lens,
                                       impl="reference")

    def split():
        return ops.pim_paged_attention(q, kp, vp, tbl, lens,
                                       impl="reference", kv_splits=splits)
    diff = float(jax.numpy.max(jax.numpy.abs(single() - split())))
    assert diff < 1e-4, f"split path diverged: max diff {diff}"
    ms_single = median_ms(single)
    ms_split = median_ms(split)
    ratio = ms_split / ms_single
    print(f"kv-split decode attention (B={B}, ctx {ctx}, {splits} splits): "
          f"{ms_single:.2f} -> {ms_split:.2f} ms/call ({ratio:.2f}x), "
          f"max output diff {diff:.2e}")
    if smoke:
        assert ratio <= 0.6, \
            f"kv_splits={splits} only reached {ratio:.2f}x at {ctx} ctx"

    # -- (b) int4 pools vs fp on the pinned exact-match workload ------------
    rng = np.random.RandomState(9)
    reqs = [(rng.randint(2, cfg.vocab, size=s), n)
            for s, n in zip((6, 4, 17, 11), (4, 3, 4, 3))]
    stats, outs = {}, {}
    for label, kv_dtype in [("paged-fp", "model"), ("paged-int4", "int4")]:
        eng = ServingEngine(params, cfg, engine, EngineConfig(
            slots=2, max_len=32, gen=gen, page_size=4,
            **_kv_opts(kv_dtype)))
        st = _drain(eng, [(p.copy(), n) for p, n in reqs],
                    max_steps=2_000)
        outs[label] = {r.uid: list(r.generated) for r in eng.finished}
        stats[label] = {
            "step_ms": st["sec"] / max(st["steps"], 1) * 1e3,
            "peak_kv_bytes": eng.peak_pages * eng.page_bytes,
            "peak_pages": eng.peak_pages,
        }
        print(f"{label:>14}: {st['steps']} steps, "
              f"{stats[label]['peak_kv_bytes'] / 1e6:.3f} MB peak KV")

    fp, q4 = stats["paged-fp"], stats["paged-int4"]
    assert q4["peak_pages"] == fp["peak_pages"], "schedules diverged"
    byte_ratio = fp["peak_kv_bytes"] / max(q4["peak_kv_bytes"], 1)
    assert byte_ratio >= 3.5, \
        f"int4 peak KV bytes only dropped {byte_ratio:.2f}x"
    uids = sorted(outs["paged-fp"])
    n_match = sum(outs["paged-int4"][u] == outs["paged-fp"][u] for u in uids)
    if smoke:
        assert n_match == len(uids), \
            "int4 KV pages changed greedy outputs on the pinned prompts"
    print(f"int4 KV pages: peak KV bytes {byte_ratio:.1f}x lower, "
          f"{n_match}/{len(uids)} outputs exact-match")
    return {"kvsplit_ms_single": ms_single, "kvsplit_ms_split": ms_split,
            "kvsplit_ratio": ratio, "kvsplit_maxdiff": diff,
            "kvsplit_context": ctx, "kvsplit_splits": splits,
            "peak_kv_bytes_fp": fp["peak_kv_bytes"],
            "peak_kv_bytes_int4": q4["peak_kv_bytes"],
            "int4_byte_ratio": byte_ratio,
            "int4_exact_match": n_match, "int4_exact_match_of": len(uids)}


def _part10(params, cfg, engine, gen, *, slots, max_len, requests,
            page_size, seed, max_steps, smoke, summary=None,
            roofline_out=None):
    """Roofline cost model vs measured structure.

    The analytical cost model (`repro.serving.costmodel`) predicts what
    every phase *should* move; this part holds it to what the engine
    actually does, three ways:

    (a) Byte-model tripwire: for each KV pool dtype (fp, int8, int4)
    the model's page bytes must equal the engine pool's `page_bytes`
    exactly, and the modeled fp/int8 and fp/int4 KV-byte ratios must
    match the measured peak-KV ratios from real drains within 5%. Both
    sides derive from `kernels.paged_attention.kv_vector_bytes`, so a
    pass means the kernel DMA contract, the pool allocator, and the
    cost model still agree — a fail means one of them drifted. When
    parts 4/9 already ran, their `peak_kv_bytes_*` summary numbers are
    cross-checked against the same modeled ratios.

    (b) Achieved bandwidth: a telemetry-on drain must report decode
    `achieved_gbps > 0` with `bound == "memory"` — decode streams every
    weight and resident KV byte for one token of math, intensity ~1
    FLOP/byte against ridges of 10-300, so any other classification
    means the bytes or FLOPs model is wrong, on every spec in
    `HARDWARE_SPECS`. The engine's `stats()["roofline"]` view must
    agree with the telemetry snapshot's.

    (c) KV-split invariance: `kv_splits` repartitions the decode page
    walk — it changes wall time, never traffic. Engines either side of
    the knob must agree on modeled bytes to the byte (and on outputs).

    `roofline_out` exports the snapshot's roofline section plus the
    model description and the ratio table as JSON — the per-phase
    achieved-GB/s trajectory record CI uploads next to the trace.
    """
    rng = np.random.RandomState(seed + 10)
    reqs = _mixed_workload(rng, cfg.vocab, requests, max_len)

    # -- (a) modeled vs measured KV bytes across pool dtypes ----------------
    measured, modeled = {}, {}
    for label, kv_dtype in [("fp", "model"), ("int8", "int8"),
                            ("int4", "int4")]:
        eng = ServingEngine(params, cfg, engine, EngineConfig(
            slots=slots, max_len=max_len, gen=gen,
            page_size=page_size, **_kv_opts(kv_dtype)))
        assert eng.cost_model.page_bytes == eng.page_bytes, (
            f"cost model and pool disagree on {label} page bytes: "
            f"{eng.cost_model.page_bytes} vs {eng.page_bytes}")
        _drain(eng, [(p.copy(), n) for p, n in reqs], max_steps=max_steps)
        measured[label] = eng.peak_pages * eng.page_bytes
        modeled[label] = eng.cost_model.page_bytes
    ratios = {}
    for q in ("int8", "int4"):
        m_ratio = measured["fp"] / max(measured[q], 1)
        c_ratio = modeled["fp"] / max(modeled[q], 1)
        rel = abs(m_ratio / c_ratio - 1.0)
        assert rel < 0.05, (
            f"modeled fp/{q} KV-byte ratio {c_ratio:.3f} vs measured "
            f"{m_ratio:.3f} ({rel:.1%} apart)")
        ratios[q] = {"modeled": c_ratio, "measured": m_ratio}
        print(f"{'kv fp/' + q:>14}: modeled {c_ratio:.2f}x, measured "
              f"{m_ratio:.2f}x peak-KV ratio")
    if summary:
        # Parts 4/9 measured the same pool dtypes on their own
        # workloads; the model must explain their within-part ratios
        # too (same 5% band). Part 4 exports fp and int8 peaks from one
        # drain; part 9's fp/int4 ratio is already a single number.
        # Cross-part byte ratios are NOT comparable (different
        # workloads and page sizes), so only within-part pairs gate.
        if {"peak_kv_bytes_fp", "peak_kv_bytes_int8"} <= set(summary):
            m = (summary["peak_kv_bytes_fp"]
                 / max(summary["peak_kv_bytes_int8"], 1))
            c = ratios["int8"]["modeled"]
            assert abs(m / c - 1.0) < 0.05, (
                f"part 4 measured fp/int8 ratio {m:.3f} vs "
                f"modeled {c:.3f}")
        if "int4_byte_ratio" in summary:
            m = summary["int4_byte_ratio"]
            c = ratios["int4"]["modeled"]
            assert abs(m / c - 1.0) < 0.05, (
                f"part 9 measured fp/int4 ratio {m:.3f} vs "
                f"modeled {c:.3f}")

    # -- (b) achieved bandwidth + boundedness from a telemetry drain --------
    tel = Telemetry(enabled=True)
    eng = ServingEngine(params, cfg, engine, EngineConfig(
        slots=slots, max_len=max_len, gen=gen,
        page_size=page_size, telemetry=tel))
    _drain(eng, [(p.copy(), n) for p, n in reqs], max_steps=max_steps)
    roof = tel.snapshot()["roofline"]
    dec = roof["phases"].get("decode")
    assert dec is not None, f"no decode phase in roofline: {roof['phases']}"
    assert dec["achieved_gbps"] > 0.0, dec
    assert dec["arithmetic_intensity"] > 0.0, dec
    assert dec["bound"] == "memory", (
        "decode classified compute-bound — the byte or FLOP model is "
        f"off by orders of magnitude: {dec}")
    es = eng.stats()["roofline"]["decode"]
    assert abs(es["modeled_bytes"] - dec["bytes"]) < 1.0, (es, dec)
    print(f"{'roofline':>14}: decode {dec['achieved_gbps']:.3f} GB/s "
          f"achieved on {roof['hardware']['name']} "
          f"(intensity {dec['arithmetic_intensity']:.2f} FLOP/B vs "
          f"ridge {roof['hardware']['ridge_flops_per_byte']:.0f} -> "
          f"{dec['bound']}-bound)")

    # -- (c) kv_splits moves time, never modeled bytes ----------------------
    mods, outs = {}, {}
    for label, splits in [("nosplit", None), ("split", 4)]:
        eng2 = ServingEngine(params, cfg, engine, EngineConfig(
            slots=slots, max_len=max_len, gen=gen,
            page_size=page_size, kv_splits=splits))
        _drain(eng2, [(p.copy(), n) for p, n in reqs],
               max_steps=max_steps)
        outs[label] = {r.uid: list(r.generated) for r in eng2.finished}
        mods[label] = {p: v["modeled_bytes"]
                       for p, v in eng2.stats()["roofline"].items()}
    assert outs["split"] == outs["nosplit"], \
        "kv_splits changed greedy outputs"
    assert mods["split"] == mods["nosplit"], (
        "kv_splits changed modeled traffic — the cost model must be "
        f"split-blind: {mods}")
    print(f"{'kv-split':>14}: modeled bytes identical across "
          f"kv_splits=None/4 ({sum(mods['split'].values()) / 1e6:.2f} MB "
          "total), outputs bit-identical")

    out = {"kv_ratio_int8_modeled": ratios["int8"]["modeled"],
           "kv_ratio_int8_measured": ratios["int8"]["measured"],
           "kv_ratio_int4_modeled": ratios["int4"]["modeled"],
           "kv_ratio_int4_measured": ratios["int4"]["measured"],
           "decode_gbps": dec["achieved_gbps"],
           "decode_intensity": dec["arithmetic_intensity"],
           "decode_bound": dec["bound"],
           "hardware": roof["hardware"]["name"]}
    if roofline_out:
        with open(roofline_out, "w") as f:
            json.dump({"roofline": roof,
                       "model": eng.cost_model.describe(),
                       "kv_byte_ratios": ratios,
                       "meta": bench_metadata()},
                      f, indent=2, sort_keys=True)
            f.write("\n")
        print(f"wrote {roofline_out}")
    return out


def run(arch="gpt2_medium", slots=4, max_len=64, requests=12,
        page_size=16, seed=0, max_steps=10_000, smoke=False,
        json_path=None, kv_cache_dtype="model",
        parts=(1, 2, 3, 4, 5, 6, 7, 8, 9, 10), trace_out=None,
        metrics_out=None, sched_out=None, mesh=0, roofline_out=None):
    cfg = get_config(arch, smoke=True)
    engine = SalPimEngine.create(SalPimConfig())
    params = api.init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.RandomState(seed)
    gen = GenConfig(temperature=0.0, stop_on_eos=False)
    parts = set(parts)
    rows = []
    summary = {"arch": arch, "requests": requests,
               "kv_cache_dtype": kv_cache_dtype}

    # Workloads are drawn up front, in a fixed order, so running a parts
    # subset serves the exact same prompts each part always served.
    reqs = _mixed_workload(rng, cfg.vocab, requests, max_len)
    prefix_len = max(page_size, (max_len // 2 // page_size) * page_size)
    shared_reqs = _shared_prefix_workload(rng, cfg.vocab, requests, max_len,
                                          prefix_len)

    # -- part 1: mixed lengths ----------------------------------------------
    if 1 in parts:
        eng = ServingEngine(params, cfg, engine, EngineConfig(
            slots=slots, max_len=max_len, gen=gen, page_size=page_size,
            **_kv_opts(kv_cache_dtype)))
        stats = _drain(eng, [(p.copy(), n) for p, n in reqs],
                       max_steps=max_steps)
        stats["kv_bytes"] = _kv_bytes(eng)
        rows.append(("paged", stats))
        _report("paged", eng, stats)
        summary["tokens_per_sec"] = stats["tok_per_sec"]

    # -- part 2: prefix sharing on a shared-prefix workload -----------------
    if 2 in parts:
        outs = {}
        p2 = {}
        for mode, sharing in [("paged-noshare", False),
                              ("paged-share", True)]:
            eng = ServingEngine(params, cfg, engine, EngineConfig(
                slots=slots, max_len=max_len, gen=gen,
                page_size=page_size, prefix_sharing=sharing,
                **_kv_opts(kv_cache_dtype)))
            stats = _drain(eng, [(p.copy(), n) for p, n in shared_reqs],
                           max_steps=max_steps)
            stats["kv_bytes"] = _kv_bytes(eng)
            stats["prefill_tokens"] = eng.prefill_tokens
            stats["peak_pages"] = eng.peak_pages
            outs[mode] = {r.uid: list(r.generated) for r in eng.finished}
            rows.append((mode, stats))
            p2[mode] = stats
            _report(mode, eng, stats)

        base, share = p2["paged-noshare"], p2["paged-share"]
        assert outs["paged-share"] == outs["paged-noshare"], \
            "prefix sharing changed greedy outputs"
        assert share["prefill_tokens"] < base["prefill_tokens"], \
            (share["prefill_tokens"], base["prefill_tokens"])
        assert share["peak_pages"] < base["peak_pages"], \
            (share["peak_pages"], base["peak_pages"])
        saved = base["prefill_tokens"] - share["prefill_tokens"]
        print(f"prefix sharing: {saved} prefill tokens saved "
              f"({saved / base['prefill_tokens']:.0%}), peak pages "
              f"{base['peak_pages']} -> {share['peak_pages']}, "
              "outputs bit-identical")
        summary["prefill_tokens_saved"] = saved
        summary["peak_pages"] = share["peak_pages"]

    # -- part 3: decode-latency jitter, stall-the-world vs chunked ----------
    # The smoke assert compares wall-clock percentiles; one retry absorbs
    # the rare run where host jitter survives the min-over-trials
    # estimator (a genuine regression fails both attempts).
    if 3 in parts:
        try:
            jitter = _part3(cfg, engine, gen, max_len=max_len,
                            page_size=page_size, seed=seed,
                            max_steps=max_steps, smoke=smoke,
                            kv_cache_dtype=kv_cache_dtype)
        except AssertionError as e:
            print(f"part 3 retry (noisy host?): {e}")
            jitter = _part3(cfg, engine, gen, max_len=max_len,
                            page_size=page_size, seed=seed,
                            max_steps=max_steps, smoke=smoke,
                            kv_cache_dtype=kv_cache_dtype)
        summary.update({
            "p50_inter_token_stall_sec": jitter["stall"]["p50"],
            "p99_inter_token_stall_sec": jitter["stall"]["p99"],
            "p50_inter_token_chunked_sec": jitter["chunked"]["p50"],
            "p99_inter_token_chunked_sec": jitter["chunked"]["p99"],
        })

    # -- part 4: int8 KV pages vs fp pages ----------------------------------
    if 4 in parts:
        int8 = _part4(params, cfg, engine, gen, slots=slots,
                      max_len=max_len, requests=requests,
                      page_size=page_size, seed=seed, max_steps=max_steps,
                      smoke=smoke)
        summary.update({
            "decode_step_ms_fp": int8["step_ms_fp"],
            "decode_step_ms_int8": int8["step_ms_int8"],
            "peak_kv_bytes_fp": int8["peak_kv_bytes_fp"],
            "peak_kv_bytes_int8": int8["peak_kv_bytes_int8"],
            "int8_pool_pages_ratio": int8["pool_pages_ratio"],
            "int8_exact_match": int8["exact_match"],
            "int8_exact_match_of": int8["exact_match_of"],
            "int8_logit_maxdiff": int8["logit_maxdiff"],
            "int8_logit_tol": int8["logit_tol"],
        })

    # -- part 5: speculative decoding (draft-verify) ------------------------
    if 5 in parts:
        spec = _part5(params, cfg, engine, gen, slots=slots,
                      max_len=max_len, requests=requests,
                      page_size=page_size, seed=seed, max_steps=max_steps,
                      smoke=smoke, kv_cache_dtype=kv_cache_dtype)
        summary.update({
            "spec_acceptance_rate": spec["acceptance_rate"],
            "spec_verify_per_token": spec["verify_per_token"],
            "spec_tokens_per_pass": spec["tokens_per_pass"],
            "decode_ms_per_token_spec_off": spec["ms_per_token_off"],
            "decode_ms_per_token_spec_on": spec["ms_per_token_on"],
            "spec_draft_time_share": spec["draft_time_share"],
        })

    # -- part 6: serving telemetry (overhead gate + exports) ----------------
    # Like part 3, the smoke assert is a wall-clock comparison; one retry
    # absorbs the rare run where host jitter survives the min-over-
    # interleaved-trials estimator (a genuine regression fails both).
    if 6 in parts:
        kw = dict(slots=slots, max_len=max_len, requests=requests,
                  page_size=page_size, seed=seed, max_steps=max_steps,
                  smoke=smoke, kv_cache_dtype=kv_cache_dtype,
                  trace_out=trace_out, metrics_out=metrics_out)
        try:
            t6 = _part6(params, cfg, engine, gen, **kw)
        except AssertionError as e:
            print(f"part 6 retry (noisy host?): {e}")
            t6 = _part6(params, cfg, engine, gen, **kw)
        summary.update({
            "telemetry_step_ms_off": t6["step_ms_off"],
            "telemetry_step_ms_on": t6["step_ms_on"],
            "telemetry_overhead_ratio": t6["overhead_ratio"],
            "telemetry_prefix_cache_hit_rate": t6["prefix_cache_hit_rate"],
            "telemetry_trace_events": t6["trace_events"],
        })

    # -- part 7: SLO scheduling under oversubscription ----------------------
    # Step-indexed (not wall-clock) latency: deterministic, no retry
    # needed — a failed gate is a real scheduling regression.
    if 7 in parts:
        t7 = _part7(params, cfg, engine, gen, slots=slots, max_len=max_len,
                    requests=requests, page_size=page_size, seed=seed,
                    max_steps=max_steps, smoke=smoke,
                    kv_cache_dtype=kv_cache_dtype, sched_out=sched_out)
        summary.update({
            "sched_p99_gap_steps_fifo": t7["p99_gap_steps_fifo"],
            "sched_p99_gap_steps_slo": t7["p99_gap_steps_slo"],
            "sched_goodput_fifo": t7["goodput_fifo"],
            "sched_goodput_slo": t7["goodput_slo"],
            "sched_preemptions": t7["preemptions"],
            "sched_swap_ins": t7["swap_ins"],
        })

    # -- part 8: multi-chip paged serving (mesh-sharded page pools) ---------
    if 8 in parts:
        t8 = _part8(params, cfg, engine, gen, slots=slots, max_len=max_len,
                    requests=requests, page_size=page_size, seed=seed,
                    max_steps=max_steps, smoke=smoke,
                    kv_cache_dtype=kv_cache_dtype, mesh_width=mesh)
        summary["mesh_devices"] = t8["devices"]
        summary["mesh_widths"] = t8["widths"]
        summary["mesh_skipped"] = t8["skipped"]
        if t8["widths"]:
            summary["mesh_step_ms_single"] = t8["step_ms_single"]
            summary["mesh_pool_bytes_per_device_single"] = \
                t8["pool_bytes_per_device_single"]
            summary["mesh_per_width"] = t8["per_width"]
            summary["mesh_bit_identical"] = True

    # -- part 9: KV-split flash-decode attention + int4 page pools ----------
    if 9 in parts:
        t9 = _part9(params, cfg, engine, gen, smoke=smoke, seed=seed)
        summary.update({
            "kvsplit_ms_single": t9["kvsplit_ms_single"],
            "kvsplit_ms_split": t9["kvsplit_ms_split"],
            "kvsplit_ratio": t9["kvsplit_ratio"],
            "kvsplit_context": t9["kvsplit_context"],
            "kvsplit_splits": t9["kvsplit_splits"],
            "peak_kv_bytes_int4": t9["peak_kv_bytes_int4"],
            "int4_byte_ratio": t9["int4_byte_ratio"],
            "int4_exact_match": t9["int4_exact_match"],
            "int4_exact_match_of": t9["int4_exact_match_of"],
        })

    # -- part 10: roofline cost model vs measured structure -----------------
    if 10 in parts:
        t10 = _part10(params, cfg, engine, gen, slots=slots,
                      max_len=max_len, requests=requests,
                      page_size=page_size, seed=seed, max_steps=max_steps,
                      smoke=smoke, summary=summary,
                      roofline_out=roofline_out)
        summary.update({
            "roofline_kv_ratio_int8_modeled": t10["kv_ratio_int8_modeled"],
            "roofline_kv_ratio_int8_measured": t10["kv_ratio_int8_measured"],
            "roofline_kv_ratio_int4_modeled": t10["kv_ratio_int4_modeled"],
            "roofline_kv_ratio_int4_measured": t10["kv_ratio_int4_measured"],
            "roofline_decode_gbps": t10["decode_gbps"],
            "roofline_decode_intensity": t10["decode_intensity"],
            "roofline_decode_bound": t10["decode_bound"],
            "roofline_hardware": t10["hardware"],
        })

    # Every export carries its provenance: schema version, git SHA, jax
    # version, device kind — cross-PR trajectory comparisons need to know
    # what produced each number.
    summary["meta"] = bench_metadata()
    if json_path:
        with open(json_path, "w") as f:
            json.dump(summary, f, indent=2, sort_keys=True)
            f.write("\n")
        print(f"wrote {json_path}")
        if smoke:
            # The tracked cross-PR record at the repo root.
            root_json = os.path.join(REPO_ROOT, "BENCH_smoke.json")
            with open(root_json, "w") as f:
                json.dump(summary, f, indent=2, sort_keys=True)
                f.write("\n")
            print(f"wrote {root_json}")
    return rows, summary


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="gpt2_medium")
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=64)
    ap.add_argument("--requests", type=int, default=12)
    ap.add_argument("--page-size", type=int, default=16)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--max-steps", type=int, default=10_000,
                    help="hard cap on decode steps per drain (an engine "
                         "regression raises instead of hanging)")
    ap.add_argument("--smoke", action="store_true",
                    help="small fast configuration for CI: few requests, "
                         "short sequences, small pages; asserts the "
                         "chunked-prefill p99 win and writes --json")
    ap.add_argument("--kv-cache-dtype", default="model",
                    choices=["model", "int8", "int4"],
                    help="KV pool storage for parts 1-3, 5, and 6's paged "
                         "engines (part 4 always compares model vs int8; "
                         "part 9 always compares model vs int4; int4 "
                         "implies bf16 scale rows)")
    ap.add_argument("--parts", default="1,2,3,4,5,6,7,8,9,10",
                    help="comma-separated parts to run (e.g. 1,2,4 skips "
                         "the slow decode-jitter study and the "
                         "speculative, telemetry, scheduler, mesh, and "
                         "roofline comparisons)")
    ap.add_argument("--mesh", type=int, default=0,
                    help="part 8's mesh width (devices on the tensor-"
                         "parallel 'model' axis); 0 sweeps every feasible "
                         "width in 2,4,8. Widths beyond the visible device "
                         "count are skipped with a note, so part 8 is a "
                         "no-op on single-device hosts")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="write the headline numbers (tokens/s, prefill "
                         "tokens saved, peak pages, inter-token p50/p99, "
                         "int8 KV memory/latency, telemetry overhead) as "
                         "JSON (default under --smoke: bench_smoke.json)")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="part 6's Chrome trace_event export (default "
                         "trace_smoke.json under --smoke, else "
                         "trace_part6.json; open at ui.perfetto.dev)")
    ap.add_argument("--metrics-out", default=None, metavar="PATH",
                    help="part 6's metrics-snapshot JSON export (default "
                         "telemetry_smoke.json under --smoke, else "
                         "telemetry_part6.json)")
    ap.add_argument("--sched-out", default=None, metavar="PATH",
                    help="part 7's scheduler-counters JSON export "
                         "(sched.* counters, per-class latency, goodput; "
                         "default sched_smoke.json under --smoke, else "
                         "sched_part7.json)")
    ap.add_argument("--roofline-out", default=None, metavar="PATH",
                    help="part 10's roofline JSON export (per-phase "
                         "achieved GB/s, memory/compute-bound "
                         "classification, modeled-vs-measured KV byte "
                         "ratios; default roofline_smoke.json under "
                         "--smoke, else roofline_part10.json)")
    args = ap.parse_args()
    if args.smoke:
        args.requests = min(args.requests, 4)
        args.max_len = min(args.max_len, 32)
        args.page_size = min(args.page_size, 8)
        args.slots = min(args.slots, 2)
        args.max_steps = min(args.max_steps, 2_000)
        if args.json is None:
            args.json = "bench_smoke.json"
    if args.trace_out is None:
        args.trace_out = ("trace_smoke.json" if args.smoke
                          else "trace_part6.json")
    if args.metrics_out is None:
        args.metrics_out = ("telemetry_smoke.json" if args.smoke
                            else "telemetry_part6.json")
    if args.sched_out is None:
        args.sched_out = ("sched_smoke.json" if args.smoke
                          else "sched_part7.json")
    if args.roofline_out is None:
        args.roofline_out = ("roofline_smoke.json" if args.smoke
                             else "roofline_part10.json")
    parts = tuple(int(p) for p in args.parts.split(",") if p)
    enable_compile_cache()
    run(arch=args.arch, slots=args.slots, max_len=args.max_len,
        requests=args.requests, page_size=args.page_size, seed=args.seed,
        max_steps=args.max_steps, smoke=args.smoke, json_path=args.json,
        kv_cache_dtype=args.kv_cache_dtype, parts=parts,
        trace_out=args.trace_out, metrics_out=args.metrics_out,
        sched_out=args.sched_out, mesh=args.mesh,
        roofline_out=args.roofline_out)


if __name__ == "__main__":
    main()
