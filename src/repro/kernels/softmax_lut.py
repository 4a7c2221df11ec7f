"""Pallas TPU kernel: the paper's PIM softmax flow, standalone (C2+C3).

Faithful op order (paper Sec. 3.2.1 / 4.1):
    max            (S-ALU `max` op across the row)
 -> subtract, exp  (LUT-embedded subarray, 64 sections on [-reach, 0])
 -> reduce-sum     (C-ALU)
 -> reciprocal     (LUT on the mantissa after the bit-position shift —
                    range reduction by exponent, NOT a divide)
 -> multiply       (S-ALU elementwise)

The fused decode-attention kernel inlines this online; this standalone
version covers row-softmax uses (router logits, prefill attention) and is
the direct analogue of the paper's softmax micro-op.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.lut import LutTable
from repro.kernels.lut_interp import SMEM_SPEC, lut_eval, lut_operand


def _recip_range_reduced(x, wb_ref, lut):
    """1/x for x > 0: LUT on the mantissa, exponent negated (bit shift)."""
    bits = jax.lax.bitcast_convert_type(x, jnp.int32)
    e = ((bits >> 23) & 0xFF) - 126
    m = jax.lax.bitcast_convert_type(
        (bits & jnp.int32(0x007FFFFF)) | jnp.int32(0x3F000000), jnp.float32)
    return lut_eval(m, wb_ref, **lut) * jnp.exp2(-e.astype(jnp.float32))


def _softmax_kernel(x_ref, expwb_ref, recipwb_ref, o_ref, *, exp_lut,
                    recip_lut):
    x = x_ref[...].astype(jnp.float32)                    # (rows, S)
    m = jnp.max(x, axis=-1, keepdims=True)                # S-ALU max
    p = lut_eval(x - m, expwb_ref, **exp_lut)
    s = jnp.sum(p, axis=-1, keepdims=True)                # C-ALU reduce
    inv = _recip_range_reduced(jnp.maximum(s, 1e-9), recipwb_ref, recip_lut)
    o_ref[...] = (p * inv).astype(o_ref.dtype)            # S-ALU multiply


def softmax_lut(x: jax.Array, exp_table: LutTable, recip_table: LutTable,
                *, block_rows: int = 128, interpret: bool = False
                ) -> jax.Array:
    """Row softmax over the last dim of (N, S) with LUT exp + reciprocal."""
    n, s = x.shape
    block_rows = min(block_rows, n)
    while n % block_rows:
        block_rows -= 1
    ewb, exp_lut = lut_operand(exp_table)
    rwb, recip_lut = lut_operand(recip_table)
    return pl.pallas_call(
        functools.partial(_softmax_kernel, exp_lut=exp_lut,
                          recip_lut=recip_lut),
        grid=(n // block_rows,),
        in_specs=[
            pl.BlockSpec((block_rows, s), lambda i: (i, 0)),
            SMEM_SPEC,
            SMEM_SPEC,
        ],
        out_specs=pl.BlockSpec((block_rows, s), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, s), x.dtype),
        interpret=interpret,
    )(x, ewb, rwb)
