"""Parameter/cache/batch sharding rules — SAL-PIM's mapping scheme (C3)
projected onto the (pod, data, model) mesh.

The paper's rule set:
  * channels get *independent* weights (no accumulation across them)
    -> `model` axis carries heads / ffn columns / vocab / experts,
  * banks parallelize with cheap merges (C-ALU)
    -> `data` axis carries batch (+ FSDP shards, merged by all-gather;
       + KV sequence for long-context decode, merged by softmax algebra),
  * subarrays stream tiles -> kernel grid, no mesh axis.

Rules are path-regex -> logical spec; divisibility is checked per tensor
so one rule set serves every arch (qwen2's 12 heads, gemma2's 8, etc.).
"""
from __future__ import annotations

import re
from typing import Any, Optional

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.models.config import ModelConfig

# (path regex, logical spec applied to the *trailing* dims of the tensor).
# Stacked layer dims (leading L on scanned blocks) are padded with None.
_RULES: list[tuple[str, tuple]] = [
    (r"(^|/)embed$", (None, "dshard")),     # gather table: d on data, always
    (r"pos_embed$|enc_pos$", (None, "dshard")),
    (r"lm_head$", ("model", "fsdp")),
    (r"router$", ("model", None)),
    (r"w[qkv]$", ("model", "fsdp")),
    (r"b[qkv]$", ("model",)),
    (r"wo$", ("fsdp", "model")),
    (r"moe/.*w_(up|gate)$", ("expert", None, "fsdp")),   # EP first
    (r"moe/.*w_down$", ("expert", "fsdp", None)),
    (r"w_(up|gate)$", ("model", "fsdp")),
    (r"w_down$", ("fsdp", "model")),
    (r"in_proj$", ("model", "fsdp")),
    (r"out_proj$", ("fsdp", "model")),
    (r"conv_w$", (None, "model")),
    (r"conv_b$", ("model",)),
    (r"A_log$|^D$|/D$|dt_bias$", ("model",)),
    (r"norm_g$", ("model",)),
    (r".*", ()),                                # norms, scalars: replicate
]

LOGICAL_TO_PHYS = {
    "model": ("model",),
    "expert": ("model",),
    "fsdp": ("data",),
    "dshard": ("data",),    # like fsdp but applied regardless of the flag
    "batch": ("pod", "data"),
    "seq_shard": ("data",),
}


def _phys_axes(logical: Optional[str], mesh: Mesh) -> Optional[Any]:
    if logical is None:
        return None
    axes = tuple(a for a in LOGICAL_TO_PHYS.get(logical, ())
                 if a in mesh.axis_names)
    if not axes:
        return None
    return axes[0] if len(axes) == 1 else axes


def _fit_spec(shape: tuple[int, ...], logical: tuple, mesh: Mesh,
              *, fsdp: bool) -> P:
    """Right-align the logical spec onto `shape`, dropping invalid axes."""
    spec: list = [None] * len(shape)
    offset = len(shape) - len(logical)
    if offset < 0:
        logical = logical[-len(shape):]
        offset = 0
    used: set[str] = set()
    for i, name in enumerate(logical):
        if name is None:
            continue
        if name == "fsdp" and not fsdp:
            continue
        phys = _phys_axes(name, mesh)
        if phys is None:
            continue
        names = (phys,) if isinstance(phys, str) else tuple(phys)
        names = tuple(n for n in names if n not in used)
        if not names:
            continue
        extent = 1
        for n in names:
            extent *= mesh.shape[n]
        if shape[offset + i] % extent != 0:
            continue
        used.update(names)
        spec[offset + i] = names[0] if len(names) == 1 else names
    return P(*spec)


def _path_str(path) -> str:
    parts = []
    for p in path:
        if hasattr(p, "key"):
            parts.append(str(p.key))
        elif hasattr(p, "idx"):
            parts.append(str(p.idx))
        else:
            parts.append(str(p))
    return "/".join(parts)


def param_pspecs(params_shape: Any, mesh: Mesh, *, fsdp: bool = False) -> Any:
    """PartitionSpec pytree for a params pytree (of arrays or SDS)."""

    def one(path, leaf):
        ps = _path_str(path)
        # Quantized weights (QTensor): the int8 payload shards like its
        # parent weight; the per-row scale inherits the row axis.
        scale_of = None
        if ps.endswith("/w_i8"):
            ps = ps[: -len("/w_i8")]
        elif ps.endswith("/scale"):
            ps = ps[: -len("/scale")]
            scale_of = True
        for pat, logical in _RULES:
            if re.search(pat, ps):
                if scale_of:
                    row_axis = logical[0] if logical else None
                    logical = (None,) * max(leaf.ndim - 1, 0) + (row_axis,) \
                        if row_axis else ()
                    return _fit_spec(leaf.shape, logical, mesh, fsdp=fsdp)
                return _fit_spec(leaf.shape, logical, mesh, fsdp=fsdp)
        return P()

    return jax.tree_util.tree_map_with_path(one, params_shape)


def param_shardings(params_shape: Any, mesh: Mesh, *, fsdp: bool = False) -> Any:
    return jax.tree.map(lambda s: NamedSharding(mesh, s),
                        param_pspecs(params_shape, mesh, fsdp=fsdp))


def batch_pspecs(batch_shape: dict, mesh: Mesh) -> dict:
    """Train/prefill inputs: batch dim over (pod, data)."""
    dp = _phys_axes("batch", mesh)

    def one(leaf):
        if leaf.ndim == 0:
            return P()
        names = (dp,) if isinstance(dp, str) else tuple(dp or ())
        extent = 1
        for n in names:
            extent *= mesh.shape[n]
        if dp is not None and leaf.shape[0] % extent == 0:
            return P(dp, *([None] * (leaf.ndim - 1)))
        return P(*([None] * leaf.ndim))

    return jax.tree.map(one, batch_shape)


def cache_pspecs(cache_shape: Any, mesh: Mesh, *, seq_shard: bool = False) -> Any:
    """Decode cache: batch on data; long-context (B=1): KV seq on data.

    Cache tensors: k/v (L, B, Hkv, S, D); ssm (L, B, H, N, P);
    conv (L, B, K-1, C); shared_k/v (A, B, Hkv, S, D); cross similar;
    lengths (B,).
    """
    dp = _phys_axes("batch", mesh)
    model = _phys_axes("model", mesh)

    def extent(ax):
        names = (ax,) if isinstance(ax, str) else tuple(ax or ())
        e = 1
        for n in names:
            e *= mesh.shape[n]
        return e

    def _combine_axes(a, b):
        an = (a,) if isinstance(a, str) else tuple(a)
        bn = (b,) if isinstance(b, str) else tuple(b)
        return an + bn

    def one(leaf):
        if leaf.ndim == 1:   # lengths
            return P(None)
        if leaf.ndim == 5:   # KV: (L/A, B, Hkv, S, D)
            spec = [None] * 5
            if dp is not None and leaf.shape[1] % extent(dp) == 0:
                spec[1] = dp
            elif seq_shard and dp is not None and leaf.shape[3] % extent(dp) == 0:
                spec[3] = dp
            # model axis: heads if they divide, else the sequence dim —
            # the sequence-sharded case is the C-ALU-style distributed
            # flash-decode (partial softmax merged by collectives). With
            # B=1 (long-context) the seq dim takes BOTH axes: heads would
            # idle 16/256 of the machine otherwise.
            if spec[3] is not None and model is not None \
                    and leaf.shape[3] % (extent(dp) * extent(model)) == 0:
                spec[3] = _combine_axes(spec[3], model)
            elif model is not None and leaf.shape[2] % extent(model) == 0:
                spec[2] = model
            elif (model is not None and spec[3] is None
                    and leaf.shape[3] % extent(model) == 0):
                spec[3] = model
            return P(*spec)
        if leaf.ndim == 4:   # KV dequant scales (L, B, Hkv, S) — follow KV
            spec = [None] * 4
            if dp is not None and leaf.shape[1] % extent(dp) == 0:
                spec[1] = dp
            elif seq_shard and dp is not None and leaf.shape[3] % extent(dp) == 0:
                spec[3] = dp
            if model is not None and leaf.shape[2] % extent(model) == 0:
                spec[2] = model
            elif (model is not None and spec[3] is None
                    and leaf.shape[3] % extent(model) == 0):
                spec[3] = model
            return P(*spec)
        if leaf.ndim >= 2:   # ssm/conv: (L, B, ...)
            spec = [None] * leaf.ndim
            if dp is not None and leaf.shape[1] % extent(dp) == 0:
                spec[1] = dp
            if leaf.ndim == 5 and model is not None \
                    and leaf.shape[2] % extent(model) == 0:
                spec[2] = model
            return P(*spec)
        return P()

    return jax.tree.map(one, cache_shape)


def paged_pool_pspecs(mesh: Mesh, *, quantized: bool = False,
                      rules: Optional[dict] = None) -> dict:
    """PartitionSpecs for the paged serving cache (`kvcache.PagedCache`).

    Payload pools (L, P, Hkv, page, Dh) and scale pools (L, P, Hkv, 1,
    page) shard their KV-head axis over the mesh axis behind the
    logical "model" name (tensor parallel within a replica), so each
    device holds 1/tp of every page — decode streams the pool from
    aggregate HBM bandwidth. Bookkeeping (per-slot lengths, block
    tables) stays replicated: admission, scheduling, COW forks, rewind
    and swap are host-side and global, exactly as on one device.

    Resolution goes through `distributed.api.resolve_spec`, so custom
    logical->physical rules (e.g. {"model": "tp"}) apply here too.
    Returns {"pools", "scales", "lengths", "block_tables"} specs; use
    `to_shardings` to turn them into NamedShardings.
    """
    from repro.distributed import api as dist_api
    pool = dist_api.resolve_spec((None, None, "model", None, None),
                                 mesh, rules)
    specs = {
        "pools": pool,
        "lengths": P(),
        "block_tables": P(),
    }
    specs["scales"] = pool if quantized else None
    return specs


def to_shardings(pspecs: Any, mesh: Mesh) -> Any:
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s) if isinstance(s, P) else s, pspecs,
        is_leaf=lambda s: isinstance(s, P))


def should_fsdp(cfg: ModelConfig, threshold: float = 8e9) -> bool:
    """ZeRO-3 param+optimizer sharding for models past ~8B params."""
    return cfg.param_count() > threshold
