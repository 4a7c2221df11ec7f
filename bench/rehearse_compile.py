"""Compile one cell's serving programs for a described TPU v5e, no chip.

    JAX_PLATFORMS=cpu python3 bench/rehearse_compile.py --workload <name>

Builds the decode program and every prefill-chunk program the cell's
traffic uses, at the cell's sizes, exactly as `ServingEngine` jits them
(kernels forced to the compiled Pallas path), from shapes alone, and
compiles each for one chip of a described v5e:2x2. Prints each
program's compile seconds, its memory analysis, and whether it holds a
Mosaic kernel (`tpu_custom_call`). What the chip's compiler refuses is
found here at no chip time. Nothing runs, so nothing is timed.
"""
from __future__ import annotations

import argparse
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "src"))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    args = ap.parse_args()
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    from bench import spec
    from repro.models import api

    jax.config.update("jax_enable_compilation_cache", False)
    cell = spec.load_cell(args.workload)
    cfg, engine = spec.program_config(cell, impl="pallas")
    eng_cfg = cell.traffic["engine"]
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])

    def on_chip(tree):
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one),
            tree)

    slots, page = eng_cfg["slots"], eng_cfg["page_size"]
    max_pages = -(-eng_cfg["max_len"] // page)
    params = on_chip(jax.eval_shape(
        lambda: api.init_params(jax.random.PRNGKey(0), cfg)))
    cache = on_chip(jax.eval_shape(lambda: api.init_paged_cache(
        cfg, slots, slots * max_pages + 1, page, max_pages)))
    i32 = jnp.int32
    decode = jax.jit(lambda p, tok, c: api.decode_step(p, tok, c, cfg,
                                                       engine),
                     donate_argnums=(2,))
    chunk = jax.jit(
        lambda p, toks, bt, st, kp, vp, ksc, vsc: api.prefill_chunk(
            p, toks, bt, st, kp, vp, cfg, engine, ksc, vsc),
        donate_argnums=(4, 5, 6, 7))
    programs = {"decode": (decode, (
        params, jax.ShapeDtypeStruct((slots,), i32, sharding=one), cache))}
    for s in spec.chunk_shapes(cell):
        programs[f"chunk{s}"] = (chunk, (
            params, jax.ShapeDtypeStruct((1, s), i32, sharding=one),
            jax.ShapeDtypeStruct((1, max_pages), i32, sharding=one),
            jax.ShapeDtypeStruct((1,), i32, sharding=one),
            cache.k_pages, cache.v_pages, None, None))
    ok = True
    for name, (fn, a) in programs.items():
        t0 = time.perf_counter()
        compiled = fn.lower(*a).compile()
        sec = time.perf_counter() - t0
        mosaic = "tpu_custom_call" in compiled.as_text()
        ok &= mosaic
        print(f"{name}: compiled in {sec:.1f} s, Mosaic kernel: {mosaic}, "
              f"{compiled.memory_analysis()}", flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
