"""Engine configuration: one frozen dataclass instead of ~14 kwargs.

`ServingEngine` historically grew one keyword argument per feature PR
(paged pools, prefix sharing, chunked prefill, int8 pages, speculation,
scheduling, telemetry, and now mesh sharding). `EngineConfig` collects
them in one validated object:

    from repro.serving import EngineConfig, ServingEngine
    eng = ServingEngine(params, cfg, engine, EngineConfig(
        slots=4, max_len=64, paged=True, page_size=16))

Validation lives in one place (`EngineConfig.validate`) so every
feature-interaction rule — preemptive scheduling requires paged pools,
speculation is paged + greedy only, scale-row dtypes are int8-only,
mesh sharding is paged-only and must divide the KV-head axis — is
checked identically no matter how the engine was constructed.

The legacy kwarg call sites keep working through a deprecation shim in
`ServingEngine.__init__`: the kwargs are folded into an `EngineConfig`
and a `DeprecationWarning` is emitted once per process (not once per
engine — benches construct dozens).
"""
from __future__ import annotations

import dataclasses
import warnings
from typing import Any, Optional

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


# The engine kwargs EngineConfig replaces, with their historical
# defaults — the shim uses this to tell "not passed" from "passed".
_LEGACY_DEFAULTS: dict[str, Any] = {
    "slots": None, "max_len": None, "gen": None, "paged": False,
    "page_size": 16, "num_pages": None, "prefix_sharing": True,
    "prefill_chunk_tokens": None, "kv_cache_dtype": None,
    "kv_scale_dtype": "float32", "speculative": None, "scheduler": None,
    "telemetry": None, "seed": 0, "mesh": None,
}

_SENTINEL = object()
_legacy_warned = False


def warn_legacy_kwargs_once() -> None:
    """Emit the kwargs-deprecation warning exactly once per process."""
    global _legacy_warned
    if _legacy_warned:
        return
    _legacy_warned = True
    warnings.warn(
        "ServingEngine(slots=..., paged=..., ...) keyword arguments are "
        "deprecated; pass ServingEngine(params, cfg, engine, "
        "EngineConfig(...)) instead (repro.serving.EngineConfig)",
        DeprecationWarning, stacklevel=4)


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    """Everything `ServingEngine` needs beyond (params, model, engine).

    `slots` and `max_len` are required; every other field keeps the
    historical kwarg default. `mesh` (a `jax.sharding.Mesh` with a
    tensor-parallel axis mapped by the logical name "model") shards the
    paged KV pools over KV heads; admission and scheduling stay
    host-side and global.
    """
    slots: int
    max_len: int
    gen: GenConfig = GenConfig()
    paged: bool = False
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

    @classmethod
    def from_legacy_kwargs(cls, **kwargs) -> "EngineConfig":
        """Build a config from the historical `ServingEngine` kwargs
        (each either its value or the `None` placeholder the shim passes
        for "not given"). Emits the deprecation warning once."""
        warn_legacy_kwargs_once()
        if kwargs.get("slots") is None or kwargs.get("max_len") is None:
            raise TypeError(
                "ServingEngine requires slots= and max_len= (or an "
                "EngineConfig carrying them)")
        resolved = {}
        for name, default in _LEGACY_DEFAULTS.items():
            val = kwargs.get(name)
            resolved[name] = default if val is None else val
        if resolved["gen"] is None:
            resolved["gen"] = GenConfig()
        return cls(**resolved)

    def resolved_kv_dtype(self, model_cfg) -> str:
        """The pool storage dtype: kv_cache_dtype, deferring to the
        model config's kv_dtype when unset."""
        return (self.kv_cache_dtype if self.kv_cache_dtype is not None
                else model_cfg.kv_dtype)

    def tensor_parallel(self) -> int:
        """Extent of the mesh axis behind the logical "model" axis
        (1 when no mesh / no such axis) — the pool shard count."""
        return dist_api.axis_size(self.mesh, "model")

    def validate(self, model_cfg) -> None:
        """Every feature-interaction rule in one place. Messages are
        kept verbatim from the historical per-kwarg checks so existing
        error-handling call sites and tests keep matching."""
        scheduler = self.scheduler
        if scheduler is not None and scheduler.preemptive and not self.paged:
            raise ValueError(
                "preemptive scheduling requires paged=True: preemption "
                "swaps pool pages to the host tier, which the dense "
                "backend does not have")
        if self.prefill_chunk_tokens is not None:
            if self.prefill_chunk_tokens < 1:
                raise ValueError("prefill_chunk_tokens must be >= 1, got "
                                 f"{self.prefill_chunk_tokens}")
            if not self.paged:
                raise ValueError(
                    "prefill_chunk_tokens requires paged=True: the dense "
                    "backend prefills whole prompts into per-slot arenas "
                    "and would silently ignore the chunk budget")
        resolved_kv = self.resolved_kv_dtype(model_cfg)
        if resolved_kv not in ("model", "int8", "int4"):
            raise ValueError(f"unknown kv_cache_dtype {resolved_kv!r}")
        if self.kv_cache_dtype is not None and not self.paged \
                and self.kv_cache_dtype != model_cfg.kv_dtype:
            raise ValueError(
                "kv_cache_dtype selects the paged pool storage; the dense "
                "backend's arena dtype comes from cfg.kv_dtype")
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
        if self.kv_splits is not None:
            if self.kv_splits < 1:
                raise ValueError(
                    f"kv_splits must be >= 1, got {self.kv_splits}")
            if self.kv_splits > 1 and not self.paged:
                raise ValueError(
                    "kv_splits requires paged=True: the KV-split path "
                    "partitions the block-table page walk; the dense "
                    "backend has no pages to split")
        if self.speculative is not None:
            self.speculative.validate()
            if not self.paged:
                raise ValueError(
                    "speculative decoding requires paged=True: rollback "
                    "is in-pool (rewind lengths + unmap tail pages)")
            if self.gen.temperature > 0.0:
                raise ValueError(
                    "speculative decoding is greedy-only: acceptance "
                    "compares drafts against argmax, which is exact "
                    "only at temperature 0")
        if self.paged and self.page_size < 1:
            raise ValueError(f"page_size must be >= 1, got {self.page_size}")
        if self.mesh is not None:
            if not self.paged:
                raise ValueError(
                    "mesh sharding requires paged=True: only the paged "
                    "KV pools are PartitionSpec-sharded; the dense "
                    "backend's per-slot arenas are single-device")
            tp = self.tensor_parallel()
            if tp > 1 and model_cfg.n_kv_heads % tp:
                raise ValueError(
                    f"mesh 'model' axis size {tp} must divide "
                    f"n_kv_heads ({model_cfg.n_kv_heads}) to shard the "
                    "KV-head axis of the page pools")
