"""The chip: what JAX reports of it, its peaks, its compile cache.

PEAKS holds each device kind's published peaks, with their source. A
device that is not in the table is an error, not a default.
"""
from __future__ import annotations

import os

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# Google Cloud documentation, "TPU v5e" (system architecture): per chip
# 197 TFLOP/s bf16, 394 TOP/s int8, 16 GiB HBM2 at 819 GB/s.
PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9,
                    "source": "cloud.google.com/tpu/docs/v5e"},
}


class NoChip(RuntimeError):
    pass


def describe(chips: int, require_chip: bool = True) -> dict:
    """platform, kind and count of the devices JAX found. Raises NoChip
    unless they are TPUs, at least `chips` of them, of a kind in PEAKS."""
    import jax
    devs = jax.devices()
    d = devs[0]
    if require_chip:
        if d.platform != "tpu":
            raise NoChip(f"needs a TPU; JAX found {d.platform} "
                         f"({d.device_kind})")
        if len(devs) < chips:
            raise NoChip(f"the cell needs {chips} chips; JAX found "
                         f"{len(devs)}")
        if d.device_kind not in PEAKS:
            raise NoChip(f"no peaks known for device kind "
                         f"{d.device_kind!r}")
    return {"platform": d.platform, "kind": d.device_kind,
            "count": min(len(devs), chips)}


def peaks(kind: str) -> dict:
    if kind not in PEAKS:
        raise KeyError(f"no peaks known for device kind {kind!r}")
    return PEAKS[kind]


def peak_bytes() -> int:
    """Peak bytes in use on the fullest chip, as the runtime reports."""
    import jax
    return max(int(d.memory_stats()["peak_bytes_in_use"])
               for d in jax.local_devices())


def enable_compile_cache() -> str:
    """JAX's persistent compilation cache: where JAX_COMPILATION_CACHE_DIR
    says, or else `<checkout>/.jax_cache`, a fixed path (the path is part
    of the cache key). Every program is cached, small ones too, so a
    second run of a cell compiles nothing."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        ROOT, ".jax_cache")
    os.makedirs(path, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileCounter:
    """Counts programs lowered (traced to MLIR) since it was made: any
    program JAX had not yet built in this process, whether its binary
    then came from the persistent cache or the compiler."""

    EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"

    def __init__(self):
        import jax
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, name, dur, **kw):
        if name == self.EVENT:
            self.n += 1
