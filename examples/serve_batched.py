#!/usr/bin/env python
"""Continuous-batching serving demo: N requests stream through B decode
slots (slot-based admission, per-request lengths, EOS release).

    PYTHONPATH=src python examples/serve_batched.py
    PYTHONPATH=src python examples/serve_batched.py --page-size 8
    PYTHONPATH=src python examples/serve_batched.py \
        --telemetry --trace-out trace.json
    PYTHONPATH=src python examples/serve_batched.py \
        --scheduler slo --priority --num-pages 12
    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        PYTHONPATH=src python examples/serve_batched.py --mesh 2
"""
import argparse
import time

import numpy as np
import jax

from repro.configs import get_config
from repro.core.salpim import SalPimConfig, SalPimEngine
from repro.models import api
from repro.runtime.compile_cache import enable_compile_cache
from repro.serving import (EngineConfig, FifoScheduler, GenConfig,
                           ServingEngine, SloScheduler, Telemetry)


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--page-size", type=int, default=16,
                    help="tokens per KV page")
    ap.add_argument("--num-pages", type=int, default=None,
                    help="page pool size (default: every slot at max_len)")
    ap.add_argument("--no-prefix-sharing", action="store_true",
                    help="disable copy-on-write prompt prefix sharing "
                         "(shared by default)")
    ap.add_argument("--shared-prefix", type=int, default=0,
                    help="prepend a common prefix of this many tokens to "
                         "every prompt (exercises prefix sharing)")
    ap.add_argument("--prefill-chunk-tokens", type=int, default=None,
                    help="chunked prefill budget per engine step "
                         "(default: whole prompt in one chunk)")
    ap.add_argument("--kv-cache-dtype", default=None,
                    choices=["model", "int8", "int4"],
                    help="page pool storage: int8 stores pages as int8 "
                         "+ per-(token, head) scale rows (write-time amax "
                         "quantization, in-kernel dequant) — ~2x KV bytes "
                         "saved, ~2x pages at the same HBM budget; int4 "
                         "packs two elements per byte (implies bf16 "
                         "scale rows) for ~4x fewer KV bytes")
    ap.add_argument("--kv-scale-dtype", default="float32",
                    choices=["float32", "bfloat16"],
                    help="int8 mode's scale-row storage: bfloat16 halves "
                         "the scale overhead to (Dh + 2) B per vector "
                         "(int4 requires bf16 and selects it itself)")
    ap.add_argument("--kv-splits", type=int, default=None,
                    help="flash-decode KV-split factor: split each "
                         "slot's page walk into this many online-softmax "
                         "partials merged by one combine pass "
                         "(engages above 1024-token contexts)")
    ap.add_argument("--speculative", default="off",
                    choices=["off", "ngram", "draft-model"],
                    help="speculative decoding (greedy only): a "
                         "drafter proposes --spec-k tokens, one verify "
                         "pass scores them all, rejected tails roll back "
                         "in-pool — greedy outputs are bit-identical, "
                         "but each verify pass can commit up to k+1 "
                         "tokens. 'ngram' looks continuations up in the "
                         "request's own history (model-free); "
                         "'draft-model' greedy-decodes a 1-layer shrink "
                         "of the serving model on its own dense cache")
    ap.add_argument("--spec-k", type=int, default=4,
                    help="drafted tokens per verify pass")
    ap.add_argument("--scheduler", default="fifo", choices=["fifo", "slo"],
                    help="admission/preemption policy: 'fifo' is the "
                         "historical strict-FIFO watermark admission "
                         "(never preempts); 'slo' admits "
                         "optimistically, serves higher-priority classes "
                         "first, and preempts-and-swaps lower classes to "
                         "host RAM under page pressure — greedy outputs "
                         "stay bit-identical either way")
    ap.add_argument("--priority", action="store_true",
                    help="mixed-class demo workload: every third request "
                         "is interactive (class 0), the rest are batch "
                         "(class 1); implies --telemetry and prints "
                         "per-class inter-token p50/p99 after the drain")
    ap.add_argument("--telemetry", action="store_true",
                    help="enable the serving telemetry layer: metric "
                         "counters/gauges/histograms, per-request "
                         "lifecycle traces, per-step phase timings; a "
                         "snapshot summary is printed after the drain")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="write a Chrome trace_event timeline of the run "
                         "(implies --telemetry; open at ui.perfetto.dev)")
    ap.add_argument("--mesh", type=int, default=0,
                    help="shard the page pools over this many devices "
                         "(tensor-parallel 'model' axis; must divide "
                         "the model's KV heads). Run "
                         "under XLA_FLAGS=--xla_force_host_platform_"
                         "device_count=8 to demo on a CPU-only host; "
                         "greedy outputs stay bit-identical")
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=64)
    ap.add_argument("--requests", type=int, default=10)
    args = ap.parse_args()
    if args.trace_out:
        args.telemetry = True
    if args.priority:
        args.telemetry = True

    enable_compile_cache()
    cfg = get_config("qwen2-1.5b", smoke=True)
    engine = SalPimEngine.create(SalPimConfig(nonlinear_mode="lut"))
    params = api.init_params(jax.random.PRNGKey(0), cfg)

    speculative = None
    if args.speculative != "off":
        from repro.serving.speculative import SpecConfig
        if args.speculative == "draft-model":
            # A 1-layer shrink of the serving model as the cheap draft
            # (its own params — in production this is a distilled small
            # model; here it demonstrates the machinery).
            import dataclasses
            draft_cfg = dataclasses.replace(cfg, n_layers=1)
            draft_params = api.init_params(jax.random.PRNGKey(1), draft_cfg)
            speculative = SpecConfig(mode="draft-model", k=args.spec_k,
                                     draft_cfg=draft_cfg,
                                     draft_params=draft_params)
        else:
            speculative = SpecConfig(mode="ngram", k=args.spec_k)

    telemetry = Telemetry(enabled=True) if args.telemetry else None
    scheduler = (SloScheduler() if args.scheduler == "slo"
                 else FifoScheduler())
    mesh = None
    if args.mesh:
        from jax.sharding import Mesh
        if args.mesh > len(jax.devices()):
            raise SystemExit(
                f"--mesh {args.mesh} but only {len(jax.devices())} "
                "device(s) visible; set XLA_FLAGS="
                "--xla_force_host_platform_device_count=8")
        mesh = Mesh(np.array(jax.devices()[:args.mesh]), ("model",))
    eng = ServingEngine(params, cfg, engine, EngineConfig(
        slots=args.slots, max_len=args.max_len,
        gen=GenConfig(temperature=0.0, stop_on_eos=False),
        page_size=args.page_size,
        num_pages=args.num_pages,
        prefix_sharing=not args.no_prefix_sharing,
        prefill_chunk_tokens=args.prefill_chunk_tokens,
        kv_cache_dtype=args.kv_cache_dtype,
        kv_scale_dtype=("bfloat16" if args.kv_cache_dtype == "int4"
                        else args.kv_scale_dtype),
        kv_splits=args.kv_splits,
        speculative=speculative,
        scheduler=scheduler,
        telemetry=telemetry,
        mesh=mesh))
    rng = np.random.RandomState(0)
    shared = rng.randint(2, cfg.vocab, size=args.shared_prefix)
    uids = []
    for i in range(args.requests):
        prompt = rng.randint(2, cfg.vocab, size=rng.randint(4, 12))
        prompt = np.concatenate([shared, prompt])
        prio = (0 if i % 3 == 0 else 1) if args.priority else 0
        uids.append(eng.submit(prompt, max_new_tokens=int(rng.randint(5, 15)),
                               priority=prio))
    mode = (f"page_size={args.page_size}, {eng.allocator.num_pages} pages, "
            f"kv {eng.kv_cache_dtype}, scheduler {args.scheduler}")
    if mesh is not None:
        mode += f", mesh model={args.mesh}"
    if speculative is not None:
        mode += f", speculative {args.speculative} k={args.spec_k}"
    print(f"submitted {len(uids)} requests into {args.slots} slots [{mode}]")

    t0 = time.perf_counter()
    steps = 0
    while True:
        n = eng.step()
        steps += 1
        if (n == 0 and not eng.queue and not eng.swapped
                and all(a is None for a in eng.active)):
            break
    dt = time.perf_counter() - t0
    toks = sum(len(r.generated) for r in eng.finished)
    print(f"drained in {steps} decode steps, {dt:.2f}s "
          f"({steps/dt:.1f} steps/s on {jax.devices()[0].device_kind}, "
          "compiles included)")
    print(f"finished {len(eng.finished)}/{len(uids)} requests, "
          f"{toks} tokens generated")
    a = eng.allocator
    print(f"page pool: {a.used_pages} in use / {a.num_pages - 1} usable "
          f"(all should be free after drain: {a.free_pages}), "
          f"peak {eng.peak_pages} pages")
    print(f"prefill: {eng.prefill_tokens} tokens computed, "
          f"{eng.prefill_tokens_saved} skipped via shared prefix pages")
    if speculative is not None:
        st = eng.stats()
        print(f"speculative: {st['accepted']}/{st['proposed']} drafts "
              f"accepted ({st['acceptance_rate']:.0%}), "
              f"{st['spec_rounds']} verify rounds for {st['tokens']} "
              f"tokens ({st['verify_per_token']:.2f} rounds/token, "
              f"{st['tokens_per_pass']:.2f} tokens/round)")
    if args.scheduler == "slo":
        st = eng.stats()
        print(f"scheduler: {st['preemptions']} preemptions, "
              f"{st['swap_outs']} swap-outs / {st['swap_ins']} swap-ins, "
              f"swap tier peak {st['swap_bytes_peak'] / 1e6:.2f} MB, "
              f"{st['pinned_pages']} pages pinned after drain")
    if telemetry is not None:
        snap = telemetry.snapshot()
        phases = snap["steps"]["phase_sec"]
        busy = {p: s for p, s in phases.items() if s > 0}
        per_req = snap["requests"]["per_request"]
        ttfts = sorted(r["ttft_sec"] for r in per_req
                       if r["ttft_sec"] is not None)
        print(f"telemetry: {snap['steps']['count']} steps, phase split "
              + ", ".join(f"{p} {s * 1e3:.1f} ms" for p, s in busy.items()))
        if ttfts:
            print(f"telemetry: ttft median {ttfts[len(ttfts) // 2] * 1e3:.1f}"
                  f" ms over {len(ttfts)} requests, prefix-cache hit rate "
                  f"{snap['prefix_cache']['hit_rate']:.0%}")
        if args.priority:
            # Per-class latency straight off the snapshot: the tracer
            # feeds one histogram per scheduling class
            # (latency.inter_token_sec.class{p}).
            hists = snap["histograms"]
            prefix = "latency.inter_token_sec.class"
            for key in sorted(k for k in hists if k.startswith(prefix)):
                h = hists[key]
                label = {"0": "interactive", "1": "batch"}.get(
                    key[len(prefix):], f"class {key[len(prefix):]}")
                print(f"telemetry: {label:<11} inter-token "
                      f"p50 {h['p50'] * 1e3:.1f} ms / "
                      f"p99 {h['p99'] * 1e3:.1f} ms "
                      f"({h['total']} gaps)")
        # Roofline: modeled traffic against measured phase time — where
        # each phase sits relative to the hardware's memory/compute
        # roofs (docs/observability.md).
        roof = snap["roofline"]
        for phase, r in roof["phases"].items():
            if r["sec"] <= 0:
                continue
            print(f"roofline: {phase:<13} {r['achieved_gbps']:.3f} GB/s "
                  f"achieved on {roof['hardware']['name']} "
                  f"(intensity {r['arithmetic_intensity']:.2f} FLOP/B, "
                  f"{r['bound']}-bound)")
        if args.trace_out:
            n = telemetry.export_chrome_trace(args.trace_out)
            print(f"telemetry: wrote {args.trace_out} ({n} trace events, "
                  "open at https://ui.perfetto.dev)")


if __name__ == "__main__":
    main()
