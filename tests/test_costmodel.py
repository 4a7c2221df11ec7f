"""Roofline cost model: the byte math against the kernel DMA contract
and the pool allocator, page rounding, mesh per-device division,
memory/compute-bound classification, engine integration (modeled
traffic accumulates telemetry-on AND -off), and KV-split invariance."""
from __future__ import annotations

import jax
import pytest

from repro.configs import get_config
from repro.core.salpim import SalPimConfig, SalPimEngine
from repro.kernels.paged_attention import kv_vector_bytes
from repro.models import api
from repro.serving import (CostModel, EngineConfig, GenConfig,
                           ServingEngine, Telemetry)
from repro.serving.costmodel import (HARDWARE_SPECS, HardwareSpec,
                                     PhaseCost, StepShape, detect_hardware)
from repro.serving.kvcache import page_kv_bytes

ENGINE = SalPimEngine.create(SalPimConfig())
KEY = jax.random.PRNGKey(0)


def _setup(arch="gpt2_medium"):
    cfg = get_config(arch, smoke=True)
    params = api.init_params(KEY, cfg)
    return cfg, params


def _model(cfg, **kw):
    kw.setdefault("hardware", HARDWARE_SPECS["hbm2"])
    return CostModel(cfg, **kw)


def _drain(eng, reqs):
    for p, n in reqs:
        eng.submit(p, max_new_tokens=n)
    steps = 0
    while eng.queue or any(a is not None for a in eng.active):
        eng.step()
        steps += 1
        assert steps < 500
    return {r.uid: list(r.generated) for r in eng.finished}


def _reqs(cfg, n=2, plen=6, new=4):
    import numpy as np
    rng = np.random.RandomState(0)
    return [(rng.randint(2, cfg.vocab, size=plen), new) for _ in range(n)]


# -- byte math: one source of truth ----------------------------------------

@pytest.mark.parametrize("kv_dtype,scale", [
    ("model", "float32"), ("int8", "float32"), ("int4", "bfloat16")])
def test_page_bytes_match_pool_contract(kv_dtype, scale):
    cfg, _ = _setup()
    for page_size in (1, 4, 16):
        cm = _model(cfg, page_size=page_size, kv_dtype=kv_dtype,
                    kv_scale_dtype=scale)
        assert cm.page_bytes == page_kv_bytes(cfg, page_size, kv_dtype,
                                              scale)
        assert cm.kv_token_bytes == \
            2 * cfg.n_layers * cfg.n_kv_heads * cm.vec_bytes
        assert cm.vec_bytes == kv_vector_bytes(
            cfg.head_dim, kv_dtype, scale, payload_dtype=cfg.cdtype)


def test_kv_byte_ratios_quantized():
    cfg, _ = _setup()
    fp = _model(cfg, kv_dtype="model")
    q8 = _model(cfg, kv_dtype="int8")
    q4 = _model(cfg, kv_dtype="int4", kv_scale_dtype="bfloat16")
    # fp: Dh * 4 bytes; int8: Dh + 4; int4: Dh/2 + 2 per vector.
    d = cfg.head_dim
    assert fp.vec_bytes / q8.vec_bytes == pytest.approx(4 * d / (d + 4))
    assert fp.vec_bytes / q4.vec_bytes == \
        pytest.approx(4 * d / (d / 2 + 2))
    assert fp.page_bytes > q8.page_bytes > q4.page_bytes


def test_kv_read_bytes_page_rounded():
    cfg, _ = _setup()
    cm = _model(cfg, page_size=16)
    assert cm.kv_read_bytes(0) == 0.0
    one_page = 16 * cm.kv_token_bytes
    assert cm.kv_read_bytes(1) == one_page
    assert cm.kv_read_bytes(16) == one_page
    assert cm.kv_read_bytes(17) == 2 * one_page


# -- phase shapes ----------------------------------------------------------

def test_decode_streams_weights_once_per_launch():
    cfg, _ = _setup()
    cm = _model(cfg, page_size=4)
    c1 = cm.decode([8])
    c2 = cm.decode([8, 8, 8])
    assert c1.weight_bytes == c2.weight_bytes == cm.weight_stream_bytes
    assert c2.kv_bytes == pytest.approx(3 * c1.kv_bytes)
    assert c2.linear_flops == pytest.approx(3 * c1.linear_flops)
    # Decode intensity is tiny — the textbook memory-bound shape.
    assert c1.intensity < 5.0


def test_chunk_prefill_attention_grows_with_offset():
    cfg, _ = _setup()
    cm = _model(cfg, page_size=4)
    early = cm.chunk_prefill(0, 8)
    late = cm.chunk_prefill(64, 8)
    assert late.attn_flops > early.attn_flops      # reads back the prefix
    assert late.weight_bytes == early.weight_bytes
    # Causal within-chunk: n*start + n(n+1)/2 pairs.
    assert early.attn_flops == cm._attn_flops(8 * 9 / 2)
    assert late.attn_flops == cm._attn_flops(8 * 64 + 8 * 9 / 2)


def test_verify_is_batched_chunk_rows():
    cfg, _ = _setup()
    cm = _model(cfg, page_size=4)
    v = cm.verify([(10, 3), (20, 3)])
    r1, r2 = cm.chunk_prefill(10, 3), cm.chunk_prefill(20, 3)
    assert v.weight_bytes == cm.weight_stream_bytes   # one launch
    assert v.kv_bytes == pytest.approx(r1.kv_bytes + r2.kv_bytes)
    assert v.attn_flops == pytest.approx(r1.attn_flops + r2.attn_flops)


def test_step_costs_keys_follow_shape():
    cfg, _ = _setup()
    cm = _model(cfg, page_size=4)
    assert cm.step_costs(StepShape()) == {}
    costs = cm.step_costs(StepShape(decode_lens=[4, 4], decode_ran=True,
                                    chunk=(0, 8)))
    assert set(costs) == {"decode", "chunk_prefill"}
    # A decode launch over all-dead rows still streams the weights.
    dead = cm.step_costs(StepShape(decode_ran=True))
    assert dead["decode"].weight_bytes == cm.weight_stream_bytes
    assert dead["decode"].kv_bytes == 0.0


# -- classification --------------------------------------------------------

def test_hardware_classification_and_ridge():
    hw = HardwareSpec("x", peak_flops=100e12, peak_bytes_per_sec=1e12)
    assert hw.ridge == pytest.approx(100.0)
    assert hw.classify(1.0) == "memory"
    assert hw.classify(500.0) == "compute"
    for spec in HARDWARE_SPECS.values():
        assert spec.ridge > 0
    # SAL-PIM's whole point: internal bandwidth moves the ridge left.
    assert HARDWARE_SPECS["salpim-hbm2"].ridge < HARDWARE_SPECS["hbm2"].ridge
    assert detect_hardware().name in HARDWARE_SPECS


def test_hardware_specs_keyed_by_device_kind_with_sources():
    for key, spec in HARDWARE_SPECS.items():
        assert spec.name == key and spec.source, key
    v5e = HARDWARE_SPECS["TPU v5 lite"]      # v5e's jax device_kind
    assert (v5e.peak_flops, v5e.peak_bytes_per_sec) == (197e12, 819e9)


@pytest.mark.parametrize("platform,kind,want", [
    ("tpu", "TPU v5 lite", "TPU v5 lite"),
    ("tpu", "TPU v4", "TPU v4"),
    ("cpu", "Intel Xeon", "cpu"),
    ("tpu", "TPU v99", None),
    ("gpu", "NVIDIA H100 80GB HBM3", None),
])
def test_detect_hardware_by_device_kind(monkeypatch, platform, kind, want):
    """An accelerator kind without a table entry raises; the nominal
    CPU roof is only for the CPU platform."""
    dev = type("Dev", (), {"platform": platform, "device_kind": kind})()
    monkeypatch.setattr(jax, "devices", lambda *a, **k: [dev])
    if want is None:
        with pytest.raises(ValueError, match="no roofline peaks"):
            detect_hardware()
    else:
        assert detect_hardware().name == want


def test_engine_config_hardware_validation():
    cfg, _ = _setup()
    with pytest.raises(ValueError, match="unknown hardware"):
        EngineConfig(slots=2, max_len=32, hardware="hbm9").validate(cfg)
    EngineConfig(slots=2, max_len=32, hardware="salpim-hbm2").validate(cfg)


def test_from_configs_resolves_hardware_and_dtype():
    cfg, _ = _setup()
    ec = EngineConfig(slots=2, max_len=32, page_size=8,
                      kv_cache_dtype="int8", hardware="salpim-hbm2")
    cm = CostModel.from_configs(cfg, ec)
    assert cm.hardware.name == "salpim-hbm2"
    assert cm.kv_dtype == "int8"
    assert cm.page_size == 8


# -- mesh ------------------------------------------------------------------

def test_per_device_shards_kv_not_weights():
    cfg, _ = _setup()
    assert cfg.n_kv_heads % 2 == 0, "test assumes tp=2 divides kv heads"
    cm1 = _model(cfg, page_size=4, tensor_parallel=1)
    cm2 = _model(cfg, page_size=4, tensor_parallel=2)
    costs = cm2.step_costs(StepShape(decode_lens=[16, 16],
                                     decode_ran=True))
    dev = cm2.per_device(costs)["decode"]
    full = cm1.step_costs(StepShape(decode_lens=[16, 16],
                                    decode_ran=True))["decode"]
    assert dev.kv_bytes == pytest.approx(full.kv_bytes / 2)
    assert dev.weight_bytes == full.weight_bytes          # replicated
    assert dev.attn_flops == pytest.approx(full.attn_flops / 2)
    assert dev.linear_flops == full.linear_flops
    # gather_heads receive traffic rides on act_bytes, per scored token.
    n_tokens = full.act_bytes / cm2.logits_row_bytes
    assert dev.act_bytes == pytest.approx(
        full.act_bytes + cm2.gather_bytes_per_token * n_tokens)
    # tp=1 is the identity.
    same = cm1.per_device(cm1.step_costs(StepShape(decode_lens=[4],
                                                   decode_ran=True)))
    assert same["decode"].kv_bytes == \
        cm1.decode([4]).kv_bytes


# -- engine integration ----------------------------------------------------

def test_engine_accumulates_costs_telemetry_off():
    cfg, params = _setup()
    eng = ServingEngine(params, cfg, ENGINE, EngineConfig(
        slots=2, max_len=32, gen=GenConfig(stop_on_eos=False),
        page_size=8))
    assert eng.cost_model.page_bytes == eng.page_bytes
    _drain(eng, _reqs(cfg))
    # Costs accumulate with telemetry disabled (always-on, so the
    # part-6 overhead gate compares equal work) — but the registry
    # stays empty: the zero-cost contract is about observability state.
    roof = eng.stats()["roofline"]
    assert roof["decode"]["modeled_bytes"] > 0
    assert roof["decode"]["bound"] in ("memory", "compute")
    assert eng.telemetry.registry.empty


def test_engine_snapshot_roofline_phases():
    cfg, params = _setup()
    tel = Telemetry(enabled=True)
    eng = ServingEngine(params, cfg, ENGINE, EngineConfig(
        slots=2, max_len=32, gen=GenConfig(stop_on_eos=False),
        page_size=8, prefill_chunk_tokens=8, telemetry=tel))
    _drain(eng, _reqs(cfg))
    roof = tel.snapshot()["roofline"]
    assert roof["hardware"]["name"] in HARDWARE_SPECS
    assert roof["model"]["page_bytes"] == eng.page_bytes
    dec = roof["phases"]["decode"]
    assert dec["bytes"] > 0 and dec["sec"] > 0
    assert dec["achieved_gbps"] > 0
    assert dec["bound"] == "memory"
    assert "chunk_prefill" in roof["phases"]
    # Engine-side and telemetry-side accumulations agree.
    assert eng.stats()["roofline"]["decode"]["modeled_bytes"] == \
        pytest.approx(dec["bytes"])


def test_kv_splits_change_time_not_modeled_bytes():
    cfg, params = _setup()
    mods, outs = {}, {}
    for splits in (None, 4):
        eng = ServingEngine(params, cfg, ENGINE, EngineConfig(
            slots=2, max_len=32, gen=GenConfig(stop_on_eos=False),
            page_size=8, kv_splits=splits))
        outs[splits] = _drain(eng, _reqs(cfg))
        mods[splits] = {p: v["modeled_bytes"]
                        for p, v in eng.stats()["roofline"].items()}
    assert outs[4] == outs[None]
    assert mods[4] == mods[None]


def test_phasecost_add_and_dict():
    a = PhaseCost(weight_bytes=10, kv_bytes=5, linear_flops=30)
    b = PhaseCost(kv_bytes=5, attn_flops=20)
    c = a.add(b)
    assert c.bytes == 20 and c.flops == 50
    assert c.intensity == pytest.approx(2.5)
    d = c.to_dict()
    assert d["bytes"] == 20 and d["arithmetic_intensity"] == 2.5
    assert PhaseCost().intensity == 0.0
