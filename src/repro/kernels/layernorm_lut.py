"""Pallas TPU kernel: fused Layer/RMS norm with LUT-rsqrt (SAL-PIM C2).

Paper Sec. 3.2.1: layerNorm = reduce (S-ALU/C-ALU) -> LUT linear
interpolation for the reciprocal square root -> broadcast multiply.
The rsqrt range reduction ("bit-position" shifters) is done with float
exponent arithmetic: var = m * 2^e, rsqrt(var) = lut_rsqrt(m') * 2^(-e'/2)
with m' in [0.25, 1) and even e'.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.lut import LutTable
from repro.kernels.lut_interp import SMEM_SPEC, lut_eval, lut_operand


def _rsqrt_range_reduced(x, wb_ref, *, lo, inv_step, sections):
    """rsqrt via mantissa LUT + exponent halving (x > 0, fp32)."""
    bits = jax.lax.bitcast_convert_type(x, jnp.int32)
    e = ((bits >> 23) & 0xFF) - 126
    m = jax.lax.bitcast_convert_type(
        (bits & jnp.int32(0x007FFFFF)) | jnp.int32(0x3F000000), jnp.float32
    )
    odd = (e & 1) == 1
    m2 = jnp.where(odd, m * 0.5, m)
    e2 = jnp.where(odd, e + 1, e)
    r = lut_eval(m2, wb_ref, lo=lo, inv_step=inv_step, sections=sections)
    return r * jnp.exp2(-(e2 // 2).astype(jnp.float32))


def _ln_kernel(x_ref, g_ref, b_ref, wb_ref, o_ref, *,
               eps, use_lut, lo, inv_step, sections, rms, has_beta, plus_one):
    x = x_ref[...].astype(jnp.float32)            # (block_rows, d)
    if rms:
        var = jnp.mean(x * x, axis=-1, keepdims=True)
        xc = x
    else:
        mean = jnp.mean(x, axis=-1, keepdims=True)
        xc = x - mean
        var = jnp.mean(xc * xc, axis=-1, keepdims=True)
    v = var + eps
    if use_lut:
        inv = _rsqrt_range_reduced(v, wb_ref, lo=lo, inv_step=inv_step,
                                   sections=sections)
    else:
        inv = jax.lax.rsqrt(v)
    gamma = g_ref[...].astype(jnp.float32)
    if plus_one:
        gamma = 1.0 + gamma
    out = xc * inv * gamma
    if has_beta:
        out = out + b_ref[...].astype(jnp.float32)
    o_ref[...] = out.astype(o_ref.dtype)


def layernorm_lut(
    x: jax.Array,             # (N, d)
    gamma: jax.Array,         # (d,)
    beta: jax.Array | None = None,
    *,
    eps: float = 1e-5,
    rsqrt_table: LutTable | None = None,
    rms: bool = False,
    plus_one: bool = False,
    block_rows: int = 256,
    interpret: bool = False,
) -> jax.Array:
    n, d = x.shape
    block_rows = min(block_rows, n)
    assert n % block_rows == 0
    use_lut = rsqrt_table is not None
    wb, lut = lut_operand(rsqrt_table)
    has_beta = beta is not None
    b = beta if has_beta else jnp.zeros((d,), jnp.float32)

    kernel = functools.partial(
        _ln_kernel, eps=eps, use_lut=use_lut, rms=rms, has_beta=has_beta,
        plus_one=plus_one, **lut)
    return pl.pallas_call(
        kernel,
        grid=(n // block_rows,),
        in_specs=[
            pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
            pl.BlockSpec((1, d), lambda i: (0, 0)),
            pl.BlockSpec((1, d), lambda i: (0, 0)),
            SMEM_SPEC,
        ],
        out_specs=pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, d), x.dtype),
        interpret=interpret,
    )(x, gamma.reshape(1, d), b.reshape(1, d), wb)
