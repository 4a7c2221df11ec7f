"""Multi-chip paged serving: mesh registry helpers on in-process fake
devices (the suite runs under 8 fake CPU devices — see conftest), pool
sharding invariants, and mesh-vs-single-device bit-identity across the
serving feature matrix (chunked prefill, prefix sharing/COW, int8 +
bf16 scale rows, speculation, preempt-and-swap)."""
from __future__ import annotations

import jax
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs import get_config
from repro.core.salpim import SalPimConfig, SalPimEngine
from repro.distributed import api as dist_api
from repro.distributed.sharding import paged_pool_pspecs
from repro.models import api
from repro.serving import (EngineConfig, GenConfig, ServingEngine,
                           SloScheduler, SpecConfig)
from repro.serving.kvcache import shard_cache

ENGINE = SalPimEngine.create(SalPimConfig())
KEY = jax.random.PRNGKey(0)

needs2 = pytest.mark.skipif(len(jax.devices()) < 2,
                            reason="needs >= 2 devices")
needs4 = pytest.mark.skipif(len(jax.devices()) < 4,
                            reason="needs >= 4 devices")


def _mesh(width, axis="model"):
    return Mesh(np.array(jax.devices()[:width]), (axis,))


def _setup(arch="gpt2_medium"):
    cfg = get_config(arch, smoke=True)
    return cfg, api.init_params(KEY, cfg)


def _workload(cfg, seed=0, n=4, shared_prefix=0):
    rng = np.random.RandomState(seed)
    prefix = rng.randint(2, cfg.vocab, size=shared_prefix)
    prompts = [np.concatenate(
                   [prefix, rng.randint(2, cfg.vocab,
                                        size=rng.randint(4, 9))])
               for _ in range(n)]
    new = [int(rng.randint(4, 9)) for _ in range(n)]
    return prompts, new


def _drain(params, cfg, prompts, new, priorities=None, engine=ENGINE, **kw):
    kw.setdefault("gen", GenConfig(temperature=0.0, stop_on_eos=False))
    kw.setdefault("slots", 2)
    kw.setdefault("max_len", 32)
    kw.setdefault("page_size", 8)
    eng = ServingEngine(params, cfg, engine, EngineConfig(**kw))
    prios = priorities or [0] * len(prompts)
    uids = [eng.submit(p.copy(), max_new_tokens=n, priority=pr)
            for p, n, pr in zip(prompts, new, prios)]
    done = eng.run(max_steps=800)
    assert sorted(r.uid for r in done) == sorted(uids)
    by = {r.uid: list(r.generated) for r in done}
    return [by[u] for u in uids], eng


# ---------------------------------------------------------------------------
# Mesh registry helpers, in-process (no subprocess machinery)
# ---------------------------------------------------------------------------

@needs4
def test_resolve_spec_on_fake_devices():
    mesh = Mesh(np.asarray(jax.devices()[:4]).reshape(2, 2),
                ("data", "model"))
    assert dist_api.resolve_spec(("batch", None, "model"), mesh) \
        == P("data", None, "model")
    # Unknown logical names and absent physical axes resolve to None.
    assert dist_api.resolve_spec(("nonsense", "model"), mesh) \
        == P(None, "model")
    data_only = Mesh(np.asarray(jax.devices()[:4]), ("data",))
    assert dist_api.resolve_spec(("model",), data_only) == P(None)
    # A physical axis is never used twice in one spec.
    assert dist_api.resolve_spec(("model", "seq_tp"), mesh) \
        == P("model", None)


@needs2
def test_use_mesh_scopes_and_restores():
    assert dist_api.current_mesh() is None
    mesh = _mesh(2)
    with dist_api.use_mesh(mesh, rules={"model": "model"}):
        assert dist_api.current_mesh() is mesh
        assert dist_api.current_rules()["model"] == "model"
        with dist_api.use_mesh(None):
            assert dist_api.current_mesh() is None
        assert dist_api.current_mesh() is mesh
    assert dist_api.current_mesh() is None
    assert dist_api.current_rules() is dist_api.DEFAULT_RULES


@needs4
def test_axis_size():
    assert dist_api.axis_size(None, "model") == 1
    assert dist_api.axis_size(_mesh(4), "model") == 4
    assert dist_api.axis_size(_mesh(4, axis="data"), "model") == 1
    assert dist_api.axis_size(
        Mesh(np.asarray(jax.devices()[:4]).reshape(2, 2),
             ("data", "model")), "batch") == 2


@needs2
def test_paged_pool_pspecs_shard_kv_head_axis():
    mesh = _mesh(2)
    specs = paged_pool_pspecs(mesh)
    assert specs["pools"] == P(None, None, "model", None, None)
    assert specs["lengths"] == P() and specs["block_tables"] == P()
    assert specs["scales"] is None
    # Scale rows (L, P, Hkv, 1, page) shard exactly like their pools.
    assert paged_pool_pspecs(mesh, quantized=True)["scales"] \
        == P(None, None, "model", None, None)


# ---------------------------------------------------------------------------
# Pool placement
# ---------------------------------------------------------------------------

@needs2
def test_shard_cache_places_pools_and_is_idempotent():
    cfg, _ = _setup()
    mesh = _mesh(2)
    cache = api.init_paged_cache(cfg, batch=2, num_pages=8, page_size=4,
                                 max_pages=8, mesh=mesh)
    want = NamedSharding(mesh, P(None, None, "model", None, None))
    assert cache.k_pages.sharding == want
    assert cache.v_pages.sharding == want
    assert cache.lengths.sharding == NamedSharding(mesh, P())
    assert cache.block_tables.sharding == NamedSharding(mesh, P())
    # One device holds 1/2 of the pool payload.
    assert cache.k_pages.addressable_shards[0].data.nbytes \
        == cache.k_pages.nbytes // 2
    # Re-sharding an already-placed cache is a no-op (same buffers).
    again = shard_cache(cache, mesh)
    assert again.k_pages is cache.k_pages


@needs2
def test_int8_scale_rows_shard_with_their_pools():
    cfg, _ = _setup()
    mesh = _mesh(2)
    cache = api.init_paged_cache(cfg, batch=2, num_pages=8, page_size=4,
                                 max_pages=8, kv_dtype="int8",
                                 kv_scale_dtype="bfloat16", mesh=mesh)
    want = NamedSharding(mesh, P(None, None, "model", None, None))
    assert cache.k_scale.sharding == want
    assert cache.v_scale.sharding == want


@needs2
def test_engine_pools_stay_sharded_after_drain():
    cfg, params = _setup()
    prompts, new = _workload(cfg)
    mesh = _mesh(2)
    _, eng = _drain(params, cfg, prompts, new, mesh=mesh)
    want = NamedSharding(mesh, P(None, None, "model", None, None))
    # is_equivalent_to: jit normalizes trailing Nones off the spec.
    assert eng.cache.k_pages.sharding.is_equivalent_to(want, 5)
    assert eng.cache.v_pages.sharding.is_equivalent_to(want, 5)


# ---------------------------------------------------------------------------
# Bit-identity: the mesh engine is an implementation detail, not a model
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def mesh_env():
    cfg, params = _setup()
    prompts, new = _workload(cfg)
    ref, _ = _drain(params, cfg, prompts, new)
    return cfg, params, prompts, new, ref


@needs2
@pytest.mark.parametrize("width", [2, 4])
def test_mesh_decode_bit_identical(mesh_env, width):
    if len(jax.devices()) < width:
        pytest.skip(f"needs >= {width} devices")
    cfg, params, prompts, new, ref = mesh_env
    out, _ = _drain(params, cfg, prompts, new, mesh=_mesh(width))
    assert out == ref


@needs2
def test_mesh_kernels_bit_identical():
    """Through the Pallas kernels (interpret mode here, compiled on a
    TPU): the paged attention runs in the pools' shard_map and the GEMV
    in a replicated one of its own (Mosaic kernels cannot be partitioned
    automatically), with the same greedy tokens as one device."""
    cfg, params = _setup()
    prompts, new = _workload(cfg, seed=7, n=3)
    engine = SalPimEngine.create(SalPimConfig(nonlinear_mode="lut",
                                              impl="interpret"))
    ref, _ = _drain(params, cfg, prompts, new, engine=engine,
                    prefill_chunk_tokens=5)
    out, eng = _drain(params, cfg, prompts, new, engine=engine,
                      prefill_chunk_tokens=5, mesh=_mesh(2))
    assert out == ref
    assert eng.allocator.used_pages == 0


@needs2
def test_mesh_chunked_prefill_and_prefix_sharing_bit_identical():
    cfg, params = _setup()
    prompts, new = _workload(cfg, seed=3, shared_prefix=8)
    ref, _ = _drain(params, cfg, prompts, new, prefix_sharing=True,
                    prefill_chunk_tokens=5)
    out, eng = _drain(params, cfg, prompts, new, prefix_sharing=True,
                      prefill_chunk_tokens=5, mesh=_mesh(2))
    assert out == ref
    assert eng.prefill_tokens_saved > 0    # COW sharing engaged under mesh


@needs2
def test_mesh_int8_pools_bit_identical():
    cfg, params = _setup()
    prompts, new = _workload(cfg, seed=4)
    ref, _ = _drain(params, cfg, prompts, new, kv_cache_dtype="int8",
                    kv_scale_dtype="bfloat16")
    out, _ = _drain(params, cfg, prompts, new, kv_cache_dtype="int8",
                    kv_scale_dtype="bfloat16", mesh=_mesh(2))
    assert out == ref


@needs2
def test_mesh_speculative_bit_identical():
    cfg, params = _setup()
    rng = np.random.RandomState(5)
    block = rng.randint(2, cfg.vocab, size=3)
    prompts = [np.tile(block, 4) for _ in range(3)]
    new = [8, 8, 8]
    spec = SpecConfig(mode="ngram", k=3)
    ref, _ = _drain(params, cfg, prompts, new, speculative=spec)
    out, _ = _drain(params, cfg, prompts, new, speculative=spec,
                    mesh=_mesh(2))
    assert out == ref


@needs2
def test_mesh_gqa_model_bit_identical():
    """Grouped-query attention: the q-head shard must line up with its
    KV-head shard (smoke qwen2: 4 q heads over 2 kv heads)."""
    cfg, params = _setup("qwen2-1.5b")
    prompts, new = _workload(cfg, seed=6)
    ref, _ = _drain(params, cfg, prompts, new)
    out, _ = _drain(params, cfg, prompts, new, mesh=_mesh(2))
    assert out == ref


@needs2
def test_mesh_preempt_swap_roundtrip_bit_identical():
    """Preempt-and-swap moves pool pages through host RAM and back; the
    swap-in scatter must land the pages back *sharded* so the shard_map
    decode keeps seeing its local slice."""
    cfg, params = _setup("qwen2-1.5b")
    rng = np.random.RandomState(7)
    prompts = [rng.randint(2, cfg.vocab, size=rng.randint(6, 11))
               for _ in range(4)]
    new = [int(rng.randint(8, 13)) for _ in range(4)]
    kw = dict(slots=3, max_len=32, page_size=4, num_pages=12,
              scheduler=SloScheduler())
    ref, ref_eng = _drain(params, cfg, prompts, new, **kw)
    out, eng = _drain(params, cfg, prompts, new, mesh=_mesh(2), **kw)
    assert out == ref
    assert ref_eng.preemptions > 0, "workload failed to force preemption"
    assert eng.preemptions == ref_eng.preemptions
    assert eng.swap_ins == ref_eng.swap_ins and eng.swap_ins > 0
    # Counters surface identically through stats() (single update path).
    st = eng.stats()
    assert st["preemptions"] == eng.preemptions
    assert st["swap_outs"] == eng.swap_outs
    assert st["swap_ins"] == eng.swap_ins
    want = NamedSharding(_mesh(2), P(None, None, "model", None, None))
    assert eng.cache.k_pages.sharding.is_equivalent_to(want, 5)


def test_width_one_mesh_falls_back_to_replicated():
    """A degenerate 1-device mesh is accepted and serves identically —
    the attention path falls back to the single-device kernels."""
    cfg, params = _setup()
    prompts, new = _workload(cfg, seed=8)
    ref, _ = _drain(params, cfg, prompts, new)
    out, _ = _drain(params, cfg, prompts, new, mesh=_mesh(1))
    assert out == ref


@needs2
def test_nondividing_width_rejected_up_front():
    cfg, params = _setup("qwen2-1.5b")   # n_kv_heads = 2
    if len(jax.devices()) < 4:
        pytest.skip("needs >= 4 devices")
    with pytest.raises(ValueError, match="must divide"):
        ServingEngine(params, cfg, ENGINE, EngineConfig(
            slots=1, max_len=16, mesh=_mesh(4)))
