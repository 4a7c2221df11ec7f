#!/usr/bin/env python
"""Lower the serving engine's decode and chunk-prefill programs on the CPU
for each benchmark cell's engine settings, at the smoke widths of the
cell's model, and print one sha256 per program.

Two checkouts whose printed hashes agree run the same programs: a
refactor of the engine's host code can be checked to leave what the
device runs untouched without a chip. The StableHLO texts are written to
OUT_DIR for a diff.

    PYTHONPATH=src python scripts/lower_engine_programs.py OUT_DIR
"""
from __future__ import annotations

import hashlib
import json
import os
import sys

import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.core.salpim import SalPimConfig, SalPimEngine
from repro.models import api
from repro.serving import EngineConfig, GenConfig, ServingEngine
from repro.serving.telemetry import Telemetry

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load(*path):
    with open(os.path.join(ROOT, *path)) as fh:
        return json.load(fh)


def main(out_dir: str) -> None:
    os.makedirs(out_dir, exist_ok=True)
    bench = _load("BENCHMARK.json")
    configs = {c["name"]: c for c in bench["configs"]}
    for work in bench["workloads"]:
        mode = _load(configs[work["config"]]["file"])["bench"]["nonlinear_mode"]
        t = _load("bench", "traffic", work["traffic"] + ".json")
        cfg = get_config(work["config"], smoke=True)
        engine = SalPimEngine.create(SalPimConfig(nonlinear_mode=mode))
        params = api.init_params(jax.random.PRNGKey(0), cfg)
        # The benchmark's own call form (bench/run.py).
        eng = ServingEngine(params, cfg, engine, EngineConfig(
            paged=True, gen=GenConfig(temperature=0.0, stop_on_eos=False),
            telemetry=Telemetry(enabled=True, annotate=True), **t["engine"]))
        c = eng.cache
        n = t["engine"]["prefill_chunk_tokens"]
        texts = {
            "decode": eng._decode.lower(
                eng.params, jnp.zeros((eng.slots,), jnp.int32), c).as_text(),
            "chunk": eng._prefill_chunk.lower(
                eng.params, jnp.zeros((1, n), jnp.int32),
                jnp.zeros((1, c.block_tables.shape[1]), jnp.int32),
                jnp.zeros((1,), jnp.int32), c.k_pages, c.v_pages,
                c.k_scale, c.v_scale).as_text(),
        }
        for prog, txt in texts.items():
            name = f"{work['name']}.{prog}"
            with open(os.path.join(out_dir, name + ".mlir"), "w") as fh:
                fh.write(txt)
            print(name, hashlib.sha256(txt.encode()).hexdigest())


if __name__ == "__main__":
    if len(sys.argv) != 2:
        raise SystemExit(__doc__)
    main(sys.argv[1])
