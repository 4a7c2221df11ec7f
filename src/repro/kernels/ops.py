"""Public jit'd wrappers for the Pallas kernels, with impl dispatch.

impl (required — `SalPimEngine.create` resolves it once from the backend
via `default_impl()`, so the serving path never picks it by hand):
  "pallas"    — compiled TPU kernel (the only choice on a TPU backend),
  "reference" — pure-jnp oracle (`kernels/ref.py`): the CPU backend,
                where pallas_call does not lower, and tests,
  "interpret" — Pallas interpret mode (CPU correctness checks in tests).

A shape a kernel cannot take raises; no op falls back to the oracle.
The sliding window is a traced operand (the layer scan carries one per
layer), never a static argument.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.lut import LutTable
from repro.kernels import ref as ref_k
from repro.kernels import lut_interp as lut_k
from repro.kernels import gemv_pim as gemv_k
from repro.kernels import decode_attention as attn_k
from repro.kernels import paged_attention as paged_k
from repro.kernels import paged_prefill as paged_pf_k
from repro.kernels import layernorm_lut as ln_k
from repro.kernels import softmax_lut as sm_k

LANE = 128


IMPLS = ("pallas", "reference", "interpret")


def default_impl() -> str:
    """The kernel implementation for the running backend."""
    return "pallas" if jax.default_backend() == "tpu" else "reference"


def _pad_to_2d(x: jax.Array) -> tuple[jax.Array, tuple, int]:
    """Flatten x to (M, 128k) padding the tail; return (x2d, shape, n_valid)."""
    shape = x.shape
    flat = x.reshape(-1)
    n = flat.shape[0]
    cols = LANE
    rows = -(-n // cols)
    pad = rows * cols - n
    flat = jnp.pad(flat, (0, pad))
    return flat.reshape(rows, cols), shape, n


@functools.partial(jax.jit, static_argnames=("impl", "block_rows"))
def lut_apply(x: jax.Array, table: LutTable, *, impl: str,
              block_rows: int = 256) -> jax.Array:
    """Apply a LUT table elementwise to any-shape x."""
    if impl == "reference":
        return ref_k.lut_interp_ref(x, table)
    x2d, shape, n = _pad_to_2d(x)
    rows = x2d.shape[0]
    br = min(block_rows, rows)
    while rows % br:
        br -= 1
    out = lut_k.lut_interp_2d(x2d, table, block_rows=br,
                              interpret=(impl == "interpret"))
    return out.reshape(-1)[:n].reshape(shape)


@functools.partial(jax.jit, static_argnames=("impl", "block_r", "block_c"))
def pim_linear(x: jax.Array, w: jax.Array, b: jax.Array | None = None, *,
               act_table: LutTable | None = None, impl: str,
               block_r: int = 256, block_c: int = 512) -> jax.Array:
    """(B, C) @ (R, C)^T with optional bias + fused LUT activation."""
    if impl == "reference":
        return ref_k.gemv_pim_ref(x, w, b, act_table=act_table)
    return gemv_k.gemv_pim_float(
        x, w, b, act_table=act_table, block_r=block_r, block_c=block_c,
        interpret=(impl == "interpret"))


@functools.partial(jax.jit, static_argnames=("impl", "block_r", "block_c"))
def pim_linear_int8(x_i8: jax.Array, x_scale: jax.Array, w_i8: jax.Array,
                    w_scale: jax.Array, *, impl: str,
                    block_r: int = 256, block_c: int = 512) -> jax.Array:
    if impl == "reference":
        return ref_k.gemv_pim_int8_ref(x_i8, x_scale, w_i8, w_scale)
    return gemv_k.gemv_pim_int8(
        x_i8, x_scale, w_i8, w_scale, block_r=block_r, block_c=block_c,
        interpret=(impl == "interpret"))


@functools.partial(jax.jit, static_argnames=("impl", "shift", "block_r", "block_c"))
def pim_linear_fixed(x_q: jax.Array, w_q: jax.Array, *, shift: int,
                     impl: str, block_r: int = 256,
                     block_c: int = 512) -> jax.Array:
    if impl == "reference":
        return ref_k.gemv_pim_fixed_ref(x_q, w_q, shift=shift)
    return gemv_k.gemv_pim_fixed(
        x_q, w_q, shift=shift, block_r=block_r, block_c=block_c,
        interpret=(impl == "interpret"))


@functools.partial(jax.jit, static_argnames=("impl", "scale", "softcap",
                                             "block_s"))
def pim_decode_attention(q, k, v, length, *, scale=None,
                         exp_table: LutTable | None = None,
                         softcap=None, window=None,
                         impl: str,
                         block_s: int = 256) -> jax.Array:
    if impl == "reference":
        return ref_k.decode_attention_ref(
            q, k, v, length, scale=scale, exp_table=exp_table,
            softcap=softcap, window=window)
    return attn_k.decode_attention(
        q, k, v, length, scale=scale, exp_table=exp_table, softcap=softcap,
        window=window, block_s=block_s, interpret=(impl == "interpret"))


@functools.partial(jax.jit, static_argnames=("impl", "scale", "softcap",
                                             "kv_splits"))
def pim_paged_attention(q, k_pages, v_pages, block_tables, length,
                        k_scales=None, v_scales=None, *,
                        scale=None, exp_table: LutTable | None = None,
                        softcap=None, window=None, kv_splits=None,
                        impl: str) -> jax.Array:
    """Decode attention over a paged KV pool (see serving/kvcache.py).
    The kernel runs one grid step per row and walks only that row's
    live pages inside it: from the page of its first in-window position
    through the page of position length - 1, a block of pages at a
    time, each page one DMA of every KV head; table entries outside
    that range are never read. int8/int4 pools pass their
    (P, Hkv, 1, page) scale rows as k_scales/v_scales; the kernel
    dequantizes (int4: unpacks) in VMEM, the oracle after the gather.
    `kv_splits` > 1 engages the KV-split (flash-decode) path above
    KV_SPLIT_MIN_CONTEXT resident tokens: per-split online-softmax
    partials merged by `merge_partial_softmax_stacked` (same log-sum-exp
    math, so results track the unsplit path to float-associativity
    tolerance)."""
    if impl == "reference":
        splits = paged_k.effective_kv_splits(
            kv_splits, block_tables.shape[1], k_pages.shape[2])
        if splits is not None:
            return ref_k.paged_attention_split_ref(
                q, k_pages, v_pages, block_tables, length,
                k_scales, v_scales, kv_splits=splits, scale=scale,
                exp_table=exp_table, softcap=softcap, window=window)
        return ref_k.paged_attention_ref(
            q, k_pages, v_pages, block_tables, length, k_scales, v_scales,
            scale=scale, exp_table=exp_table, softcap=softcap,
            window=window)
    return paged_k.paged_attention(
        q, k_pages, v_pages, block_tables, length, k_scales, v_scales,
        scale=scale, exp_table=exp_table, softcap=softcap, window=window,
        kv_splits=kv_splits, interpret=(impl == "interpret"))


@functools.partial(jax.jit, static_argnames=("impl", "scale", "softcap"))
def pim_paged_prefill_attention(q, k_pages, v_pages, block_tables, length,
                                start, k_scales=None, v_scales=None, *,
                                scale=None,
                                exp_table: LutTable | None = None,
                                softcap=None, window=None,
                                impl: str) -> jax.Array:
    """Chunked prefill attention over a paged KV pool: q (B, Sq, H, D) at
    absolute positions start..start+Sq-1 (see serving/kvcache.py).
    int8 pools pass scale rows as k_scales/v_scales. The speculative
    verify pass (serving/speculative.py) dispatches through this same
    entry point: scoring k+1 candidate tokens at decode time *is* a
    prefill chunk at the slot's current length."""
    if impl == "reference":
        return ref_k.paged_prefill_attention_ref(
            q, k_pages, v_pages, block_tables, length, start,
            k_scales, v_scales, scale=scale, exp_table=exp_table,
            softcap=softcap, window=window)
    return paged_pf_k.paged_prefill_attention(
        q, k_pages, v_pages, block_tables, length, start,
        k_scales, v_scales, scale=scale, exp_table=exp_table,
        softcap=softcap, window=window, interpret=(impl == "interpret"))


@functools.partial(jax.jit, static_argnames=("impl", "eps", "rms", "plus_one",
                                             "block_rows"))
def pim_layernorm(x, gamma, beta=None, *, eps: float = 1e-5,
                  rsqrt_table: LutTable | None = None, rms: bool = False,
                  plus_one: bool = False, impl: str,
                  block_rows: int = 256) -> jax.Array:
    """LayerNorm/RMSNorm over the last dim of any-rank x."""
    if impl == "reference":
        return ref_k.layernorm_lut_ref(
            x, gamma if not plus_one else (1.0 + gamma), beta, eps=eps,
            rsqrt_table=rsqrt_table, rms=rms)
    shape = x.shape
    x2 = x.reshape(-1, shape[-1])
    rows = x2.shape[0]
    br = min(block_rows, rows)
    while rows % br:
        br -= 1
    out = ln_k.layernorm_lut(
        x2, gamma, beta, eps=eps, rsqrt_table=rsqrt_table, rms=rms,
        plus_one=plus_one, block_rows=br, interpret=(impl == "interpret"))
    return out.reshape(shape)


@functools.partial(jax.jit, static_argnames=("impl", "block_rows"))
def pim_softmax(x: jax.Array, exp_table: LutTable, recip_table: LutTable,
                *, impl: str, block_rows: int = 128) -> jax.Array:
    """Row softmax over the last dim via the paper's LUT flow."""
    if impl == "reference":
        from repro.core import lut as lut_lib
        xf = x.astype(jnp.float32)
        m = jnp.max(xf, axis=-1, keepdims=True)
        p = lut_lib.apply_table(xf - m, exp_table)
        s = jnp.sum(p, axis=-1, keepdims=True)
        inv = lut_lib.lut_reciprocal(jnp.maximum(s, 1e-9), recip_table)
        return (p * inv).astype(x.dtype)
    shape = x.shape
    x2 = x.reshape(-1, shape[-1])
    out = sm_k.softmax_lut(x2, exp_table, recip_table, block_rows=block_rows,
                           interpret=(impl == "interpret"))
    return out.reshape(shape)
