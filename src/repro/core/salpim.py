"""The SAL-PIM engine: the paper's technique as one composable module.

`SalPimEngine` bundles the three contributions behind a single object the
models and the serving path consume:

  C1 — bandwidth-optimal linear/GEMV (float / int8-MXU / int16-Q paths),
  C2 — LUT nonlinearities (the `Nonlinear` policy + tables),
  C3 — hierarchy mapping: heads/columns -> `model` axis (channels),
       batch/FSDP/seq -> `data` axis (banks), VMEM tiles (subarrays);
       cross-shard merges via psum (the C-ALU).

The engine is pure configuration + functions (no state); it is safe to
close over inside jit. `quant` selects the decode-path weight datapath.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.nonlinear import Nonlinear
from repro.core import quant as quant_lib
from repro.distributed import api as dist_api
from repro.kernels import ops

Array = jax.Array


def _replicated_on_mesh(fn, *args):
    """`fn(*args)`, run whole on every device of the active mesh.

    XLA cannot partition a Mosaic kernel itself, so under a mesh
    (`EngineConfig(mesh=...)` shards only the page pools) a kernel
    outside the paged-attention shard_map runs in a shard_map of its own
    with every operand replicated: each device computes the full result
    from its replicated weights, as the one-device engine would."""
    mesh = dist_api.current_mesh()
    if mesh is None:
        return fn(*args)
    from jax.sharding import PartitionSpec as P
    return jax.shard_map(fn, mesh=mesh, in_specs=(P(),) * len(args),
                         out_specs=P(), check_vma=False)(*args)


@dataclasses.dataclass(frozen=True)
class SalPimConfig:
    """Technique knobs (paper Table 2 defaults)."""

    nonlinear_mode: str = "exact"   # "exact" | "lut"
    lut_sections: int = 64          # paper: 64; >=32 keeps accuracy
    quant: str = "none"             # "none" | "int8" | "fixed16" (decode path)
    fixed_frac_w: int = 12          # Q-format fraction bits (weights)
    fixed_frac_x: int = 10          # Q-format fraction bits (activations)
    use_fused_attention: bool = True
    # Kernel implementation (kernels/ops.py). None = derived from the
    # backend by SalPimEngine.create ("pallas" on TPU, the jnp oracles
    # elsewhere); "interpret"/"reference" are for tests.
    impl: Optional[str] = None
    # KV-split (flash-decode) autotune knob for paged decode attention:
    # None/1 = single page walk; K > 1 = K online-softmax partials merged
    # by merge_partial_softmax_stacked, engaged only above
    # kernels.paged_attention.KV_SPLIT_MIN_CONTEXT resident tokens.
    kv_splits: Optional[int] = None


@dataclasses.dataclass(frozen=True)
class SalPimEngine:
    config: SalPimConfig
    nl: Nonlinear

    @classmethod
    def create(cls, config: SalPimConfig | None = None) -> "SalPimEngine":
        config = config or SalPimConfig()
        if config.impl is None:
            config = dataclasses.replace(config, impl=ops.default_impl())
        if config.impl not in ops.IMPLS:
            raise ValueError(f"unknown kernel impl {config.impl!r}; "
                             f"expected one of {ops.IMPLS}")
        nl = Nonlinear.create(config.nonlinear_mode, config.lut_sections)
        return cls(config=config, nl=nl)

    # -- C1: linear ----------------------------------------------------------
    def linear(self, x: Array, w: Array, b: Array | None = None,
               *, act: str | None = None) -> Array:
        """y = x @ w^T (+b) (+activation). x: (..., C), w: (R, C).

        The decode serving path routes through the quantized kernels; the
        training path stays in float (straight-through estimation of the
        LUT is handled by the tables being piecewise-linear — gradients
        are the section slopes).
        """
        lead = x.shape[:-1]
        cfg = self.config
        # Pre-quantized serving weights (serving/quantize.py QTensor):
        # native s8 x s8 -> s32 dot, per-row rescale, bias/act epilogue.
        if type(w).__name__ == "QTensor":
            from repro.serving.quantize import qtensor_linear
            out = qtensor_linear(x, w, b)
            if act is not None:
                out = self.nl.activation(act)(out)
            return out
        if cfg.quant == "none" and cfg.impl == "reference":
            # Fast path: stay in the caller's trace (no nested jit), keep
            # the leading dims so XLA sees one big contraction.
            out = jnp.einsum(
                "...c,rc->...r", x, w.astype(x.dtype),
                preferred_element_type=jnp.float32,
            ).astype(x.dtype)
            if b is not None:
                out = out + b.astype(x.dtype)
            if act is not None:
                out = self.nl.activation(act)(out)
            return out
        x2 = x.reshape(-1, x.shape[-1])
        if cfg.quant == "int8":
            x_absmax = jnp.max(jnp.abs(x2), axis=-1)
            x_scale = jnp.maximum(x_absmax, 1e-8) / 127.0
            x_i8 = jnp.clip(jnp.round(x2 / x_scale[:, None]), -127, 127).astype(jnp.int8)
            w_i8, w_scale = quant_lib.quantize_int8_rowwise(w)
            out = ops.pim_linear_int8(x_i8, x_scale, w_i8, w_scale, impl=cfg.impl)
            if b is not None:
                out = out + b
            out = out.astype(x.dtype)
        elif cfg.quant == "fixed16":
            w_fmt = quant_lib.QFormat(cfg.fixed_frac_w)
            x_fmt = quant_lib.QFormat(cfg.fixed_frac_x)
            w_q = w_fmt.quantize(w)
            x_q = x_fmt.quantize(x2)
            acc_frac = cfg.fixed_frac_w + cfg.fixed_frac_x
            out_q = ops.pim_linear_fixed(
                x_q, w_q, shift=acc_frac - cfg.fixed_frac_x, impl=cfg.impl)
            out = x_fmt.dequantize(out_q).astype(x.dtype)
            if b is not None:
                out = out + b.astype(x.dtype)
        else:
            act_table = None
            if act is not None and self.nl.mode == "lut" and cfg.impl != "reference":
                act_table = getattr(self.nl.bank, act, None)
            out = _replicated_on_mesh(
                lambda x2, w, *b: ops.pim_linear(
                    x2, w, *b, act_table=act_table, impl=cfg.impl),
                x2, w, *([] if b is None else [b]))
            if act_table is not None:
                return out.reshape(*lead, -1)
        out = out.reshape(*lead, -1)
        if act is not None:
            out = self.nl.activation(act)(out)
        return out

    def expert_gemv(self, x: Array, w: Array, offs: Array, sizes: Array,
                    w_up: Array | None = None, layer: Array | None = None,
                    *, max_rows: int, act: str = "silu") -> Array:
        """Grouped expert GEMV over rows sorted by expert (see
        `ops.pim_expert_gemv`): expert e's `sizes[e]` rows start at row
        `offs[e]`; w (E, R, C), or with `layer` a stack (L, E, R, C) read
        in place; with `w_up`, act(x w^T) * (x w_up^T) fused, the
        activation through the LUT in "lut" mode."""
        act_table = (getattr(self.nl.bank, act) if w_up is not None
                     and self.nl.mode == "lut" else None)
        given = {k: v for k, v in (("w_up", w_up), ("layer", layer))
                 if v is not None}
        return _replicated_on_mesh(
            lambda x, w, offs, sizes, *rest: ops.pim_expert_gemv(
                x, w, offs, sizes, **dict(zip(given, rest)),
                max_rows=max_rows, act=act, act_table=act_table,
                impl=self.config.impl),
            x, w, offs, sizes, *given.values())

    # -- C3: fused decode attention -------------------------------------------
    @property
    def _exp_table(self):
        """The attention kernels' exp LUT (None = exact exp)."""
        return self.nl.bank.exp if self.nl.mode == "lut" else None

    def decode_attention(self, q: Array, k: Array, v: Array, length: Array,
                         *, scale: Optional[float] = None,
                         softcap: Optional[float] = None,
                         window=None) -> Array:
        return ops.pim_decode_attention(
            q, k, v, length, scale=scale, exp_table=self._exp_table,
            softcap=softcap, window=window, impl=self.config.impl)

    def paged_decode_attention(self, q: Array, k_pages: Array,
                               v_pages: Array, block_tables: Array,
                               length: Array,
                               k_scales: Optional[Array] = None,
                               v_scales: Optional[Array] = None, *,
                               scale: Optional[float] = None,
                               softcap: Optional[float] = None,
                               window=None) -> Array:
        """Decode attention reading K/V through a block table
        (serving/kvcache.py pool layout). int8/int4 pools pass their
        scale rows; the kernel dequantizes (int4: unpacks) in VMEM.
        `config.kv_splits` > 1 engages the KV-split (flash-decode) path
        above KV_SPLIT_MIN_CONTEXT resident tokens."""
        return ops.pim_paged_attention(
            q, k_pages, v_pages, block_tables, length, k_scales, v_scales,
            scale=scale, exp_table=self._exp_table, softcap=softcap,
            window=window, kv_splits=self.config.kv_splits,
            impl=self.config.impl)

    def paged_prefill_attention(self, q: Array, k_pages: Array,
                                v_pages: Array, block_tables: Array,
                                length: Array, start: Array,
                                k_scales: Optional[Array] = None,
                                v_scales: Optional[Array] = None, *,
                                scale: Optional[float] = None,
                                softcap: Optional[float] = None,
                                window=None) -> Array:
        """Chunked prefill attention reading earlier chunks' K/V back
        through the block table (kernels/paged_prefill.py). q holds one
        prompt chunk per sequence at absolute positions start..start+Sq-1;
        the chunk's own K/V must already be resident in the pool (int8
        mode: quantized, with its scale rows written)."""
        return ops.pim_paged_prefill_attention(
            q, k_pages, v_pages, block_tables, length, start,
            k_scales, v_scales, scale=scale, exp_table=self._exp_table,
            softcap=softcap, window=window, impl=self.config.impl)

    # -- C2: norms -------------------------------------------------------------
    def layernorm(self, x: Array, gamma: Array, beta: Array | None,
                  eps: float = 1e-5) -> Array:
        return self.nl.layernorm(x, gamma, beta, eps)

    def rmsnorm(self, x: Array, gamma: Array, eps: float = 1e-6,
                *, plus_one: bool = False) -> Array:
        return self.nl.rmsnorm(x, gamma, eps, plus_one=plus_one)

    def softmax(self, x: Array, axis: int = -1, where: Array | None = None) -> Array:
        return self.nl.softmax(x, axis=axis, where=where)
