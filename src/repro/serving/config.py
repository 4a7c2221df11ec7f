"""Engine configuration: one frozen, validated dataclass.

`EngineConfig` collects every serving option — page pool, prefix
sharing, chunked prefill, int8/int4 pages, speculation, scheduling,
telemetry, mesh sharding — in one object:

    from repro.serving import EngineConfig, ServingEngine
    eng = ServingEngine(params, cfg, engine, EngineConfig(
        slots=4, max_len=64, page_size=16))

Validation lives in one place (`EngineConfig.validate`): speculation
is greedy only, scale-row dtypes are int8/int4-only, int4 needs bf16
scale rows, and mesh sharding must divide the KV-head axis.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax

from repro.distributed import api as dist_api
from repro.serving.scheduler import Scheduler
from repro.serving.speculative import SpecConfig
from repro.serving.telemetry import Telemetry


@dataclasses.dataclass(frozen=True)
class GenConfig:
    """Per-request generation settings (shared by `generate()` and the
    serving engine)."""
    max_new_tokens: int = 64
    temperature: float = 0.0
    top_k: int = 0
    eos_id: int = 0
    stop_on_eos: bool = True


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    """Everything `ServingEngine` needs beyond (params, model, engine).

    `slots` and `max_len` are required. `mesh` (a `jax.sharding.Mesh`
    with a tensor-parallel axis mapped by the logical name "model")
    shards the KV page pools over KV heads; admission and scheduling
    stay host-side and global.
    """
    slots: int
    max_len: int
    gen: GenConfig = GenConfig()
    # Inert: kept only for the benchmark's call site, which passes True.
    paged: dataclasses.InitVar[bool] = True
    page_size: int = 16
    num_pages: Optional[int] = None
    prefix_sharing: bool = True
    prefill_chunk_tokens: Optional[int] = None
    kv_cache_dtype: Optional[str] = None
    kv_scale_dtype: str = "float32"
    speculative: Optional[SpecConfig] = None
    scheduler: Optional[Scheduler] = None
    telemetry: Optional[Telemetry] = None
    seed: int = 0
    mesh: Optional[jax.sharding.Mesh] = None
    # KV-split (flash-decode) autotune knob: split paged decode
    # attention's page walk into this many online-softmax partials,
    # merged by merge_partial_softmax_stacked. None/1 = single walk;
    # the kernel layer auto-disables splitting below
    # KV_SPLIT_MIN_CONTEXT resident tokens regardless of the knob.
    kv_splits: Optional[int] = None
    # Roofline target for the cost model's memory/compute-bound
    # classification: a key of costmodel.HARDWARE_SPECS ("hbm2",
    # "salpim-hbm2", "TPU v4", ...). None = the running device's
    # device_kind. Purely observational — never changes what runs.
    hardware: Optional[str] = None

    def resolved_kv_dtype(self, model_cfg) -> str:
        """The pool storage dtype: kv_cache_dtype, deferring to the
        model config's kv_dtype when unset."""
        return (self.kv_cache_dtype if self.kv_cache_dtype is not None
                else model_cfg.kv_dtype)

    def tensor_parallel(self) -> int:
        """Extent of the mesh axis behind the logical "model" axis
        (1 when no mesh / no such axis) — the pool shard count."""
        return dist_api.axis_size(self.mesh, "model")

    def __post_init__(self, paged: bool) -> None:
        if not paged:
            raise ValueError(
                "paged=False is not supported: the dense per-slot KV "
                "arena was removed; every engine serves from the page pool")

    def validate(self, model_cfg) -> None:
        """Every feature-interaction rule in one place."""
        if self.page_size < 1:
            raise ValueError(f"page_size must be >= 1, got {self.page_size}")
        if self.prefill_chunk_tokens is not None \
                and self.prefill_chunk_tokens < 1:
            raise ValueError("prefill_chunk_tokens must be >= 1, got "
                             f"{self.prefill_chunk_tokens}")
        resolved_kv = self.resolved_kv_dtype(model_cfg)
        if resolved_kv not in ("model", "int8", "int4"):
            raise ValueError(f"unknown kv_cache_dtype {resolved_kv!r}")
        if self.kv_scale_dtype != "float32" \
                and resolved_kv not in ("int8", "int4"):
            raise ValueError(
                "kv_scale_dtype selects the int8/int4 pools' scale-row "
                "storage; fp pools have no scale rows")
        if resolved_kv == "int4":
            if model_cfg.head_dim % 2:
                raise ValueError(
                    "kv_cache_dtype='int4' packs two values per byte and "
                    f"needs an even head_dim, got {model_cfg.head_dim}")
            if self.kv_scale_dtype != "bfloat16":
                raise ValueError(
                    "kv_cache_dtype='int4' requires "
                    "kv_scale_dtype='bfloat16': f32 scale rows would "
                    "spend the bytes the nibble packing just saved")
        if self.hardware is not None:
            from repro.serving.costmodel import HARDWARE_SPECS
            if self.hardware not in HARDWARE_SPECS:
                raise ValueError(
                    f"unknown hardware {self.hardware!r}; known roofline "
                    f"specs: {sorted(HARDWARE_SPECS)}")
        if self.kv_splits is not None and self.kv_splits < 1:
            raise ValueError(f"kv_splits must be >= 1, got {self.kv_splits}")
        if self.speculative is not None:
            self.speculative.validate()
            if self.gen.temperature > 0.0:
                raise ValueError(
                    "speculative decoding is greedy-only: acceptance "
                    "compares drafts against argmax, which is exact "
                    "only at temperature 0")
        tp = self.tensor_parallel()
        if tp > 1 and model_cfg.n_kv_heads % tp:
            raise ValueError(
                f"mesh 'model' axis size {tp} must divide "
                f"n_kv_heads ({model_cfg.n_kv_heads}) to shard the "
                "KV-head axis of the page pools")
