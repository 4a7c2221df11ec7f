"""Serving: prefill/decode engine, continuous batching, paged KV cache,
speculative draft-verify decoding, sampling, scheduling, telemetry.

The public surface — import from here, not from the submodules:

    from repro.serving import EngineConfig, ServingEngine

    eng = ServingEngine(params, cfg, engine, EngineConfig(
        slots=4, max_len=64))

Submodules stay importable for the internals (kvcache allocators,
drafters, sampling), but engine construction, configuration, policy
and observability all have their canonical names here.
"""
from repro.serving.config import EngineConfig, GenConfig
from repro.serving.costmodel import CostModel, HardwareSpec
from repro.serving.engine import Request, ServingEngine, generate
from repro.serving.scheduler import FifoScheduler, Scheduler, SloScheduler
from repro.serving.speculative import SpecConfig
from repro.serving.telemetry import Telemetry

__all__ = [
    "CostModel",
    "EngineConfig",
    "FifoScheduler",
    "GenConfig",
    "HardwareSpec",
    "Request",
    "Scheduler",
    "ServingEngine",
    "SloScheduler",
    "SpecConfig",
    "Telemetry",
    "generate",
]
