"""EngineConfig: the one `ServingEngine` configuration object, its
single-place validation, the benchmark's call form, and the
`repro.serving` facade exports."""
from __future__ import annotations

import dataclasses
import json
import os

import jax
import numpy as np
import pytest

import repro.serving as serving
from repro.configs import get_config
from repro.core.salpim import SalPimConfig, SalPimEngine
from repro.models import api
from repro.serving import EngineConfig, GenConfig, ServingEngine
from repro.serving import config as config_mod
from repro.serving import engine as engine_mod
from repro.serving.scheduler import SloScheduler
from repro.serving.speculative import SpecConfig
from repro.serving.telemetry import Telemetry

ENGINE = SalPimEngine.create(SalPimConfig())
KEY = jax.random.PRNGKey(0)
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _setup(arch="gpt2_medium"):
    cfg = get_config(arch, smoke=True)
    return cfg, api.init_params(KEY, cfg)


def _workload(cfg, seed=0, n=3):
    rng = np.random.RandomState(seed)
    prompts = [rng.randint(2, cfg.vocab, size=rng.randint(4, 9))
               for _ in range(n)]
    new = [int(rng.randint(4, 8)) for _ in range(n)]
    return prompts, new


def _drain(eng, prompts, new):
    uids = [eng.submit(p.copy(), max_new_tokens=n)
            for p, n in zip(prompts, new)]
    done = eng.run(max_steps=800)
    by = {r.uid: list(r.generated) for r in done}
    return [by[u] for u in uids]


# ---------------------------------------------------------------------------
# The dataclass itself
# ---------------------------------------------------------------------------

def test_config_is_frozen_value_type():
    cfg = EngineConfig(slots=2, max_len=32, page_size=8)
    assert cfg == EngineConfig(slots=2, max_len=32, page_size=8)
    with pytest.raises(dataclasses.FrozenInstanceError):
        cfg.slots = 4
    # replace() is the supported way to derive variants.
    small = dataclasses.replace(cfg, page_size=4)
    assert small.page_size == 4 and small.slots == 2 and cfg.page_size == 8


def test_resolved_kv_dtype_defers_to_model_config():
    cfg, _ = _setup()
    assert EngineConfig(slots=1, max_len=16).resolved_kv_dtype(cfg) \
        == cfg.kv_dtype
    assert EngineConfig(slots=1, max_len=16,
                        kv_cache_dtype="int8").resolved_kv_dtype(cfg) \
        == "int8"


# ---------------------------------------------------------------------------
# Validation and construction
# ---------------------------------------------------------------------------

@pytest.mark.skipif(len(jax.devices()) < 8, reason="needs 8 devices")
def test_validate_mesh_width_must_divide_kv_heads():
    cfg, params = _setup()     # smoke gpt2_medium: n_kv_heads = 4
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:8]), ("model",))
    with pytest.raises(ValueError, match="must divide"):
        ServingEngine(params, cfg, ENGINE, EngineConfig(
            slots=1, max_len=16, mesh=mesh))


def test_config_requires_slots_and_max_len():
    with pytest.raises(TypeError):
        EngineConfig()
    with pytest.raises(TypeError):
        EngineConfig(slots=2)


def test_paged_false_raises():
    """The dense per-slot arena is gone; the inert flag refuses False
    when the config is built and is not stored."""
    with pytest.raises(ValueError, match="dense per-slot KV arena"):
        EngineConfig(slots=1, max_len=16, paged=False)
    ec = EngineConfig(slots=1, max_len=16, paged=True)
    assert ec == EngineConfig(slots=1, max_len=16)
    assert "paged" not in {f.name for f in dataclasses.fields(ec)}


def test_engine_takes_only_a_config():
    """One constructor: (params, model, engine, config) and nothing else."""
    cfg, params = _setup()
    with pytest.raises(TypeError):
        ServingEngine(params, cfg, ENGINE, slots=1, max_len=16)
    with pytest.raises(TypeError):
        ServingEngine(params, cfg, ENGINE)
    with pytest.raises(TypeError):
        ServingEngine(params, cfg, ENGINE,
                      EngineConfig(slots=1, max_len=16), slots=1)


@pytest.mark.parametrize("features", [
    {"scheduler": SloScheduler()},
    {"prefill_chunk_tokens": 4},
    {"kv_splits": 2},
    {"speculative": SpecConfig(mode="ngram", k=3)},
    {"kv_cache_dtype": "int8"},
    {"kv_cache_dtype": "int4", "kv_scale_dtype": "bfloat16"},
], ids=["slo", "chunked", "kv_splits", "ngram_spec", "int8", "int4"])
def test_features_serve_from_the_default_config(features):
    """Every feature that once needed a backend switch builds from the
    bare config and drains to completion with the pool emptied."""
    cfg, params = _setup()
    eng = ServingEngine(params, cfg, ENGINE, EngineConfig(
        slots=2, max_len=32,
        gen=GenConfig(temperature=0.0, stop_on_eos=False), **features))
    prompts, new = _workload(cfg, n=2)
    outs = _drain(eng, prompts, new)
    assert [len(o) for o in outs] == new
    assert eng.allocator.used_pages == 0


def _bench_cell(mix):
    with open(os.path.join(REPO, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    work = next(w for w in bench["workloads"] if w["traffic"] == mix)
    conf = next(c for c in bench["configs"] if c["name"] == work["config"])
    with open(os.path.join(REPO, conf["file"])) as fh:
        model = json.load(fh)
    with open(os.path.join(REPO, "bench", "traffic", mix + ".json")) as fh:
        traffic = json.load(fh)
    return work["config"], model["bench"]["nonlinear_mode"], traffic


@pytest.mark.parametrize("mix", ["paper_poisson", "longdoc_closed16",
                                 "chat_closed32"])
def test_bench_call_form_builds_and_drains(mix):
    """The benchmark builds its engine with exactly this call (bench/run.py
    `Serve.__init__`): the paged flag, greedy no-EOS generation, annotated
    telemetry and the traffic file's engine block, at the cell's published
    max_len. Here the model is its family's smoke config and one short
    request drains."""
    name, mode, t = _bench_cell(mix)
    cfg = get_config(name, smoke=True)
    params = api.init_params(KEY, cfg)
    engine = SalPimEngine.create(SalPimConfig(nonlinear_mode=mode))
    eng = ServingEngine(params, cfg, engine, EngineConfig(
        paged=True, gen=GenConfig(temperature=0.0, stop_on_eos=False),
        telemetry=Telemetry(enabled=True, annotate=True), **t["engine"]))
    eng.submit(np.arange(2, 10), max_new_tokens=3)
    done = eng.run(max_steps=50)
    assert len(done) == 1 and len(done[0].generated) == 3
    stats = eng.stats()
    assert stats["prefill_tokens"] == 8 and stats["tokens"] == 3
    assert stats["peak_pages"] >= 1 and eng.allocator.used_pages == 0
    assert not eng.queue and all(r is None for r in eng.active)
    assert eng.last_logits.shape == (t["engine"]["slots"], cfg.vocab)


def test_engine_exposes_its_config():
    cfg, params = _setup()
    ec = EngineConfig(slots=2, max_len=32, page_size=8)
    eng = ServingEngine(params, cfg, ENGINE, ec)
    assert eng.config is ec
    assert eng.mesh is None


# ---------------------------------------------------------------------------
# Facade
# ---------------------------------------------------------------------------

def test_facade_exports_resolve():
    for name in serving.__all__:
        assert getattr(serving, name) is not None, name


def test_facade_names_are_the_canonical_objects():
    assert serving.GenConfig is engine_mod.GenConfig
    assert serving.GenConfig is config_mod.GenConfig
    assert serving.EngineConfig is config_mod.EngineConfig
    assert serving.ServingEngine is engine_mod.ServingEngine
