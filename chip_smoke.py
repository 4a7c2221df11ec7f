#!/usr/bin/env python
"""Chip smoke: serve GPT-2 medium at full width through the Pallas kernels.

    python chip_smoke.py            # one TPU chip
    python chip_smoke.py --chips 4  # mesh-sharded pools on four chips

One process, TPU required. GPT-2 medium at its published widths (24L,
d1024, 16H, head_dim 64, vocab 50257, bf16) with random weights from
SEED; the engine is built as `examples/serve_batched.py` builds it
(LUT nonlinearities, paged pool of 16-token pages, 4 slots, 64-token
prefill chunks). The one-chip run:

  1. checks the kernel implementation resolved to "pallas" and that the
     compiled decode and chunk-prefill programs hold Mosaic kernels;
  2. drains 8 greedy requests through submit()/step() — prompts of 32,
     64 and 128 tokens (the paper's input sizes), 32 new tokens each;
  3. drains 2 more with int8 KV pages;
  4. teacher-forces one bf16 and one int8 request with their drained
     tokens and compares the logits at every generated position (chunked
     prefill, then the paged decode steps) with the same model run
     through the jnp oracles on the chip; each drained token must be
     near-optimal under the oracle's logits;
  5. plants a fault (decode reads a wrong page) and requires the logit
     band to catch it.

`--chips 4` runs only the mesh drain on Mesh(devices[:4], ("model",))
(16 KV heads, 4 per chip) and the same requests on one device: greedy
tokens must be identical, pool shards must sit on 4 devices and the
weights must be replicated.

Lines before the last are smoke figures, not benchmark metrics. The
last line is one JSON object: {"ok": true, "device": {...}}. Any failed
check exits non-zero without it.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

SEED = 0                      # weights and prompts
PROMPT_LENS = (32, 64, 128)   # the paper's input sizes
NEW_TOKENS = 32
N_REQUESTS = 8
N_INT8_REQUESTS = 2
PAGE_SIZE = 16
CHUNK = 64
# Band on max |logit(pallas) - logit(reference)| at a request's
# generated positions, in units of the reference logits' std (~1.0 for
# these random weights). Both paths are bf16 pipelines over 24 layers
# that round at different points (the GEMV kernel adds bias and applies
# the LUT activation in f32 before one bf16 cast; the kernels' online
# softmax rescales per page) and their matmuls may use different f32
# precisions. On CPU the same model in bf16 vs f32 differs by at most
# 0.05 std at prompt 32 and 128, and on a TPU v5e the two paths differ
# by 0.055 std at the 128-token prompt's prefill; 0.25 leaves about 5x
# that. The one-chip run plants a wrong-page fault in the decode and
# requires it to land outside the band.
LOGIT_BAND = 0.25


class SmokeFailure(AssertionError):
    pass


def check(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def make_prompts(vocab: int, lens, n: int, seed: int) -> list:
    rng = np.random.RandomState(seed)
    return [rng.randint(1, vocab, size=lens[i % len(lens)]).astype(np.int32)
            for i in range(n)]


def build_engine(params, cfg, engine, **engine_kw):
    """The engine `examples/serve_batched.py` builds, greedy."""
    from repro.serving import EngineConfig, GenConfig, ServingEngine
    return ServingEngine(params, cfg, engine, EngineConfig(
        page_size=PAGE_SIZE, slots=4, max_len=256,
        prefill_chunk_tokens=CHUNK,
        gen=GenConfig(temperature=0.0, stop_on_eos=False), **engine_kw))


def drain(eng, prompts, max_new_tokens: int, max_steps: int = 10_000):
    """Submit `prompts`, step until drained, and check every request
    finished with `max_new_tokens` tokens and the pool is empty again.
    Returns (greedy tokens per prompt, steps, wall seconds)."""
    uids = [eng.submit(p, max_new_tokens=max_new_tokens) for p in prompts]
    t0 = time.perf_counter()
    steps = 0
    while True:
        n = eng.step()
        steps += 1
        if (n == 0 and not eng.queue and not eng.swapped
                and all(a is None for a in eng.active)):
            break
        check(steps < max_steps, f"not drained after {steps} steps")
    jax.block_until_ready(eng.last_logits)
    wall = time.perf_counter() - t0
    done = {r.uid: r for r in eng.finished}
    check(all(u in done for u in uids),
          f"{sum(u in done for u in uids)}/{len(uids)} requests finished")
    check(all(len(done[u].generated) == max_new_tokens for u in uids),
          "a request stopped short of its token budget")
    check(eng.allocator.used_pages == 0,
          f"pool ends with {eng.allocator.used_pages} pages in use, not 0")
    check(bool(jnp.all(jnp.isfinite(eng.last_logits))),
          "non-finite logits after the drain")
    return [list(done[u].generated) for u in uids], steps, wall


def forced_logits(params, cfg, engine, prompt, forced=(), kv_dtype=None,
                  plant_fault=False):
    """f32 logits (1 + len(forced), vocab) of one request, teacher-forced:
    row 0 at its first generated position after the engine's chunked
    paged prefill (CHUNK-token chunks into a fresh pool), then one row
    per paged decode step fed the next token of `forced`.

    With `plant_fault` it also returns the decode rows rerun from the
    same prefilled pool with logical page 1 mapped to page 0's K/V, as a
    kernel that walked the block table wrongly would read them."""
    from repro.models import api
    from repro.serving.kvcache import PagedCache
    n_pages = -(-(len(prompt) + len(forced)) // PAGE_SIZE)
    cache = api.init_paged_cache(cfg, 1, n_pages + 1, PAGE_SIZE, n_pages,
                                 kv_dtype=kv_dtype)
    table = jnp.arange(1, n_pages + 1, dtype=jnp.int32)[None]
    chunk = jax.jit(lambda p, t, st, *pools: api.prefill_chunk(
        p, t, table, st, *pools[:2], cfg, engine, *pools[2:]))
    step = jax.jit(lambda p, t, c: api.decode_step(p, t, c, cfg, engine))
    pools = [cache.k_pages, cache.v_pages]
    if cache.quantized:
        pools += [cache.k_scale, cache.v_scale]
    for start in range(0, len(prompt), CHUNK):
        toks = jnp.asarray(prompt[start:start + CHUNK])[None]
        logits, *pools = chunk(params, toks, jnp.asarray([start], jnp.int32),
                               *pools)

    def decode(tab):
        c = PagedCache(jnp.asarray([len(prompt)], jnp.int32), tab, *pools)
        rows = []
        for tok in forced:
            lg, c = step(params, jnp.asarray([tok], jnp.int32), c)
            rows.append(lg[0])
        return rows

    rows = jnp.stack([logits[0], *decode(table)]).astype(jnp.float32)
    if not plant_fault:
        return rows
    bad = jnp.stack(decode(table.at[0, 1].set(table[0, 0])))
    return rows, bad.astype(jnp.float32)


def compare_forced(label, params, cfg, engine, ref_engine, prompt, toks,
                   kv_dtype=None, plant_fault=False):
    """Teacher-force `prompt` + its drained greedy tokens `toks` through
    `engine` and `ref_engine`; require every position's logits within
    LOGIT_BAND std of the reference and each drained token within
    2 * LOGIT_BAND std of the reference's best logit (the engine's
    argmax of logits within the band can fall no further). Returns the
    planted fault's max |diff| in std when `plant_fault`."""
    forced = toks[:-1]
    got = forced_logits(params, cfg, engine, prompt, forced, kv_dtype,
                        plant_fault)
    if plant_fault:
        got, bad = got
    want = forced_logits(params, cfg, ref_engine, prompt, forced, kv_dtype)
    check(bool(jnp.all(jnp.isfinite(got))), f"{label}: non-finite logits")
    std = float(jnp.std(want))
    diff = jnp.max(jnp.abs(got - want), axis=-1) / std
    gap = (jnp.max(want, axis=-1)
           - want[jnp.arange(len(toks)), jnp.asarray(toks)]) / std
    print(f"logits vs reference, {label} (prompt {len(prompt)}, "
          f"{len(toks)} positions): max |diff| {float(diff[0]):.6f} std at "
          f"prefill, {float(jnp.max(diff[1:])):.6f} std over decode (band "
          f"{LOGIT_BAND} std); drained tokens' shortfall from the "
          f"reference's best: max {float(jnp.max(gap)):.6f} std, "
          f"{int(jnp.sum(gap == 0))}/{len(toks)} its argmax")
    check(float(jnp.max(diff)) <= LOGIT_BAND,
          f"{label}: logits differ from the reference by "
          f"{float(jnp.max(diff)):.4f} std")
    check(float(jnp.max(gap)) <= 2 * LOGIT_BAND,
          f"{label}: a drained token is {float(jnp.max(gap)):.4f} std "
          f"below the reference's best")
    if plant_fault:
        return float(jnp.max(jnp.abs(bad - want[1:]))) / std
    return None


def check_compiled_kernels(eng) -> float:
    """Compile the engine's decode and chunk-prefill programs (every
    chunk shape the drain uses) and require Mosaic kernels in each.
    Returns the compile seconds."""
    c = eng.cache
    t0 = time.perf_counter()
    programs = {"decode": eng._decode.lower(
        eng.params, jnp.zeros((eng.slots,), jnp.int32), c)}
    row = c.block_tables[:1]
    for s in sorted({min(n, CHUNK) for n in PROMPT_LENS}):
        programs[f"chunk{s}"] = eng._prefill_chunk.lower(
            eng.params, jnp.zeros((1, s), jnp.int32), row,
            jnp.zeros((1,), jnp.int32), c.k_pages, c.v_pages,
            c.k_scale, c.v_scale)
    for name, lowered in programs.items():
        check("tpu_custom_call" in lowered.compile().as_text(),
              f"the compiled {name} program holds no Pallas kernel")
    return time.perf_counter() - t0


def one_chip(params, cfg) -> None:
    from repro.core.salpim import SalPimConfig, SalPimEngine
    engine = SalPimEngine.create(SalPimConfig(nonlinear_mode="lut"))
    check(engine.config.impl == "pallas",
          f"kernel impl resolved to {engine.config.impl!r} on a TPU")

    eng = build_engine(params, cfg, engine)
    compile_sec = check_compiled_kernels(eng)
    print(f"compile: decode + chunk programs {compile_sec:.3f} s")

    prompts = make_prompts(cfg.vocab, PROMPT_LENS, N_REQUESTS, SEED)
    toks, steps, wall = drain(eng, prompts, NEW_TOKENS)
    n_tok = sum(len(t) for t in toks)
    print(f"drain bf16: {len(prompts)} requests, {steps} steps, {n_tok} "
          f"tokens, {wall:.3f} s wall (first-call compiles included)")

    eng8 = build_engine(params, cfg, engine, kv_cache_dtype="int8")
    toks8, steps8, wall8 = drain(eng8, prompts[:N_INT8_REQUESTS], NEW_TOKENS)
    print(f"drain int8 KV: {N_INT8_REQUESTS} requests, {steps8} steps, "
          f"{sum(len(t) for t in toks8)} tokens, {wall8:.3f} s wall")

    # The 128-token request: two chunks, the second reading the first
    # chunk's K/V back through the block table, then 31 decode steps.
    # The int8 request has a 64-token prompt: one chunk.
    ref_engine = SalPimEngine.create(
        SalPimConfig(nonlinear_mode="lut", impl="reference"))
    i = PROMPT_LENS.index(max(PROMPT_LENS))
    fault = compare_forced("bf16", params, cfg, engine, ref_engine,
                           prompts[i], toks[i], plant_fault=True)
    compare_forced("int8 KV", params, cfg, engine, ref_engine, prompts[1],
                   toks8[1], kv_dtype="int8")
    print(f"planted fault (decode reads page 0 as logical page 1): max "
          f"|diff| {fault:.6f} std (band {LOGIT_BAND} std)")
    check(fault > LOGIT_BAND, "the logit band does not catch a decode "
          "that reads a wrong page")


def four_chips(params, cfg) -> None:
    from jax.sharding import Mesh
    from repro.core.salpim import SalPimConfig, SalPimEngine
    devices = jax.devices()
    check(len(devices) >= 4, f"--chips 4 but {len(devices)} devices")
    engine = SalPimEngine.create(SalPimConfig(nonlinear_mode="lut"))
    prompts = make_prompts(cfg.vocab, PROMPT_LENS, N_REQUESTS, SEED)

    mesh = Mesh(np.array(devices[:4]), ("model",))
    eng_mesh = build_engine(params, cfg, engine, mesh=mesh)
    toks_mesh, steps, wall = drain(eng_mesh, prompts, NEW_TOKENS)
    print(f"drain mesh model=4: {len(prompts)} requests, {steps} steps, "
          f"{wall:.3f} s wall (first-call compiles included)")
    shards = eng_mesh.cache.k_pages.addressable_shards
    check(len({s.device for s in shards}) == 4,
          "pool shards are not on 4 distinct devices")
    check(all(s.data.shape[2] == cfg.n_kv_heads // 4 for s in shards),
          "a pool shard does not hold n_kv_heads / 4 heads")
    check(all(leaf.sharding.is_fully_replicated
              for leaf in jax.tree.leaves(eng_mesh.params)),
          "weights are not replicated over the mesh")
    per_dev = {str(s.device): s.data.nbytes * 2 for s in shards}  # K + V
    print("pool bytes per device (K+V): " + ", ".join(
        f"{d} {b}" for d, b in sorted(per_dev.items())))

    eng_one = build_engine(params, cfg, engine)
    toks_one, steps1, wall1 = drain(eng_one, prompts, NEW_TOKENS)
    print(f"drain one device: {steps1} steps, {wall1:.3f} s wall")
    same = sum(a == b for a, b in zip(toks_mesh, toks_one))
    print(f"greedy tokens identical: {same}/{len(prompts)} requests")
    check(same == len(prompts),
          "mesh greedy tokens differ from the one-device drain")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__,
                                 formatter_class=argparse.RawTextHelpFormatter)
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the mesh-sharded drain and its "
                         "one-device comparison")
    args = ap.parse_args()

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke needs a TPU; JAX found {dev.platform} "
              f"({dev.device_kind})", file=sys.stderr)
        return 2
    from repro.configs import get_config
    from repro.models import api
    from repro.runtime.compile_cache import enable_compile_cache
    print(f"compile cache: {enable_compile_cache()}")
    print(f"device: {dev.platform} {dev.device_kind} x{len(devices)}")

    cfg = get_config("gpt2-medium")
    params = api.init_params(jax.random.PRNGKey(SEED), cfg)
    if args.chips == 4:
        four_chips(params, cfg)
    else:
        one_chip(params, cfg)
    peak = dev.memory_stats().get("peak_bytes_in_use")
    print(f"peak_bytes_in_use (device 0): {peak}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
