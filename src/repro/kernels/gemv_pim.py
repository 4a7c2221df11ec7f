"""Pallas TPU kernel: streaming GEMV with hierarchical accumulation (C1).

The S-ALU datapath, re-tiled for the TPU memory hierarchy:

  DRAM subarray rows streaming past shared MACs   ->  W tiles streaming
    HBM -> VMEM under an explicit BlockSpec grid
  32-bit accumulation registers in the S-ALU      ->  fp32/int32 VMEM
    scratch accumulator carried across the contraction grid axis
  bank-level broadcast input feeding              ->  x block broadcast to
    every R-tile (index_map pins the B x C block per contraction step)
  C-ALU cross-bank merge                          ->  left to the caller
    (jax.lax.psum over the `model` axis) — same split as the paper.

Three datapaths, matching DESIGN.md:
  * float (bf16/f32 weights, fp32 accum) — the serving path's kernel,
    compiled for TPU v5e;
  * int8 x int8 -> int32 MXU-native (per-row weight scales),
  * int16 Q-format -> int32 with shift/saturate writeback (faithful S-ALU;
    TPU MXU has no int16 mode).
The int8 and int16 paths are validated in interpret mode only: Mosaic
refuses their int32 dots on v5e (docs/kernels.md), and int8 serving
weights go through `serving/quantize.QTensor` instead.

An optional fused LUT epilogue applies the activation before writeback —
the paper's 'nonlinearity rides the same datapath' fusion.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.lut import LutTable
from repro.kernels.lut_interp import SMEM_SPEC, lut_eval, lut_operand


def _divisor_block(n: int, pref: int) -> int:
    """Contraction block: `pref` if it divides n, else the largest
    multiple of 128 below it that does, else n whole (a full-extent block
    is always a legal BlockSpec). The contraction axis must tile
    exactly — a ragged block would add garbage into every output."""
    for blk in range(pref, 0, -128):
        if blk % 128 == 0 and n % blk == 0:
            return blk
    return n


def _gemv_float_kernel(x_ref, w_ref, b_ref, wb_ref, o_ref, acc_ref, *,
                       n_c, fuse_act, has_bias, lut):
    c = pl.program_id(1)

    @pl.when(c == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...].astype(jnp.float32)          # (bb, bc)
    w = w_ref[...].astype(jnp.float32)          # (br, bc)
    acc_ref[...] += jnp.dot(x, w.T, preferred_element_type=jnp.float32)

    @pl.when(c == n_c - 1)
    def _writeback():
        acc = acc_ref[...]
        if has_bias:
            acc = acc + b_ref[...].astype(jnp.float32)
        if fuse_act:
            acc = lut_eval(acc, wb_ref, **lut)
        o_ref[...] = acc.astype(o_ref.dtype)


def gemv_pim_float(
    x: jax.Array,
    w: jax.Array,
    b: jax.Array | None = None,
    *,
    act_table: LutTable | None = None,
    block_r: int = 256,
    block_c: int = 512,
    block_b: int = 512,
    interpret: bool = False,
) -> jax.Array:
    """x (B, C) @ w (R, C)^T -> (B, R), optional bias + fused LUT activation.

    Block sizes follow the shared-MAC balance: block_c spans the streamed
    contraction (the subarray row burst), block_r the parallel output rows
    (the S-ALU lanes). fp32 accumulation across the contraction grid.
    Output rows and batch rows need not tile: the grid rounds up and the
    edge block's out-of-range rows are computed from padding and never
    written back (the LM head's vocab 50257 has no 128-multiple divisor).
    """
    B, C = x.shape
    R = w.shape[0]
    block_r = min(block_r, R)
    block_b = min(block_b, B)
    block_c = _divisor_block(C, block_c)
    n_r, n_c, n_b = pl.cdiv(R, block_r), C // block_c, pl.cdiv(B, block_b)

    fuse_act = act_table is not None
    wb, lut = lut_operand(act_table)
    has_bias = b is not None
    b_arr = b if has_bias else jnp.zeros((R,), jnp.float32)
    b2 = b_arr.reshape(1, R)

    kernel = functools.partial(
        _gemv_float_kernel, n_c=n_c, fuse_act=fuse_act, has_bias=has_bias,
        lut=lut)
    return pl.pallas_call(
        kernel,
        grid=(n_b * n_r, n_c),
        in_specs=[
            pl.BlockSpec((block_b, block_c), lambda i, c, n_r=n_r: (i // n_r, c)),
            pl.BlockSpec((block_r, block_c), lambda i, c, n_r=n_r: (i % n_r, c)),
            pl.BlockSpec((1, block_r), lambda i, c, n_r=n_r: (0, i % n_r)),
            SMEM_SPEC,
        ],
        out_specs=pl.BlockSpec((block_b, block_r),
                               lambda i, c, n_r=n_r: (i // n_r, i % n_r)),
        out_shape=jax.ShapeDtypeStruct((B, R), x.dtype),
        scratch_shapes=[pltpu.VMEM((block_b, block_r), jnp.float32)],
        interpret=interpret,
    )(x, w, b2, wb)


def _gemv_int8_kernel(x_ref, xs_ref, w_ref, ws_ref, o_ref, acc_ref, *, n_c):
    c = pl.program_id(1)

    @pl.when(c == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...].astype(jnp.int32)
    w = w_ref[...].astype(jnp.int32)
    acc_ref[...] += jax.lax.dot_general(
        x, w, (((1,), (1,)), ((), ())), preferred_element_type=jnp.int32
    )

    @pl.when(c == n_c - 1)
    def _writeback():
        out = acc_ref[...].astype(jnp.float32)
        out = out * xs_ref[...].astype(jnp.float32).T  # (bb,1)
        out = out * ws_ref[...].astype(jnp.float32)    # (1,br)
        o_ref[...] = out.astype(o_ref.dtype)


def gemv_pim_int8(
    x_i8: jax.Array,
    x_scale: jax.Array,
    w_i8: jax.Array,
    w_scale: jax.Array,
    *,
    block_r: int = 256,
    block_c: int = 512,
    interpret: bool = False,
) -> jax.Array:
    """int8 MXU path: (B, C) i8 @ (R, C) i8 -> f32 (B, R) with row scales."""
    B, C = x_i8.shape
    R = w_i8.shape[0]
    block_r = min(block_r, R)
    block_c = min(block_c, C)
    assert R % block_r == 0 and C % block_c == 0
    n_r, n_c = R // block_r, C // block_c
    xs = x_scale.reshape(1, B)
    ws = w_scale.reshape(1, R)
    kernel = functools.partial(_gemv_int8_kernel, n_c=n_c)
    return pl.pallas_call(
        kernel,
        grid=(n_r, n_c),
        in_specs=[
            pl.BlockSpec((B, block_c), lambda r, c: (0, c)),
            pl.BlockSpec((1, B), lambda r, c: (0, 0)),
            pl.BlockSpec((block_r, block_c), lambda r, c: (r, c)),
            pl.BlockSpec((1, block_r), lambda r, c: (0, r)),
        ],
        out_specs=pl.BlockSpec((B, block_r), lambda r, c: (0, r)),
        out_shape=jax.ShapeDtypeStruct((B, R), jnp.float32),
        scratch_shapes=[pltpu.VMEM((B, block_r), jnp.int32)],
        interpret=interpret,
    )(x_i8, xs, w_i8, ws)


def _gemv_fixed_kernel(x_ref, w_ref, o_ref, acc_ref, *, n_c, shift):
    c = pl.program_id(1)

    @pl.when(c == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...].astype(jnp.int32)
    w = w_ref[...].astype(jnp.int32)
    acc_ref[...] += jax.lax.dot_general(
        x, w, (((1,), (1,)), ((), ())), preferred_element_type=jnp.int32
    )

    @pl.when(c == n_c - 1)
    def _writeback():
        # S-ALU writeback: arithmetic right shift by the fraction width,
        # saturate to the 16-bit GBL width.
        shifted = jnp.right_shift(acc_ref[...], shift)
        o_ref[...] = jnp.clip(shifted, -32768, 32767).astype(jnp.int16)


def gemv_pim_fixed(
    x_q: jax.Array,
    w_q: jax.Array,
    *,
    shift: int,
    block_r: int = 256,
    block_c: int = 512,
    interpret: bool = False,
) -> jax.Array:
    """Faithful S-ALU int16 Q-format path (int32 accum, shift, saturate)."""
    B, C = x_q.shape
    R = w_q.shape[0]
    block_r = min(block_r, R)
    block_c = min(block_c, C)
    assert R % block_r == 0 and C % block_c == 0
    n_r, n_c = R // block_r, C // block_c
    kernel = functools.partial(_gemv_fixed_kernel, n_c=n_c, shift=shift)
    return pl.pallas_call(
        kernel,
        grid=(n_r, n_c),
        in_specs=[
            pl.BlockSpec((B, block_c), lambda r, c: (0, c)),
            pl.BlockSpec((block_r, block_c), lambda r, c: (r, c)),
        ],
        out_specs=pl.BlockSpec((B, block_r), lambda r, c: (0, r)),
        out_shape=jax.ShapeDtypeStruct((B, R), jnp.int16),
        scratch_shapes=[pltpu.VMEM((B, block_r), jnp.int32)],
        interpret=interpret,
    )(x_q, w_q)
