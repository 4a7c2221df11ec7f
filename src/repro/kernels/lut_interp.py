"""Pallas TPU kernel: LUT-based linear interpolation (SAL-PIM C2).

TPU adaptation of the LUT-embedded subarray: the (slope, intercept)
table lives in SMEM for the whole kernel, mirroring the activated LUT
rows held in the bit-line sense amps, and the per-MAT column-select
becomes a chain of vector selects — one compare-and-select per table
row, broadcasting that row's scalar pair to every lane whose section
index matches. The chain is `sections + 2` VPU ops deep and works on
any (rows, lanes) tile, so every kernel that interpolates (the fused
attention exp, the GEMV activation epilogue, LUT softmax/layernorm)
shares `lut_eval` below.

Layout: x is processed in (block_rows, 128) VMEM tiles; the flattened
table rides along as one SMEM operand (`lut_operand`).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.lut import LutTable

LANE = 128

# Whole-array SMEM operand: the flattened (slope, intercept) table.
SMEM_SPEC = pl.BlockSpec(memory_space=pltpu.SMEM)


def lut_operand(table: LutTable | None) -> tuple[jax.Array, dict]:
    """(flat table, static interpolation params) for `lut_eval`.

    The table is flattened row-major to (2 * (sections + 2),) f32 — slope
    then intercept per row — for its SMEM operand. With no table a
    one-row placeholder keeps the kernels' operand lists fixed."""
    if table is None:
        return (jnp.zeros((2,), jnp.float32),
                {"lo": 0.0, "inv_step": 1.0, "sections": -1})
    return (table.wb.astype(jnp.float32).reshape(-1),
            {"lo": table.lo, "inv_step": table.inv_step,
             "sections": table.sections})


def lut_eval(x, wb_ref, *, lo, inv_step, sections):
    """In-kernel y = W[sec(x)] * x + B[sec(x)] over an f32 tile of any
    shape, reading the table from the SMEM ref `wb_ref` (`lut_operand`).
    Section decode matches `core.lut.section_index`; the selects fetch
    the exact f32 table entries, so results equal `apply_table`."""
    idx = jnp.floor((x - lo) * inv_step).astype(jnp.int32) + 1
    idx = jnp.clip(idx, 0, sections + 1)
    w = jnp.zeros_like(x)
    b = jnp.zeros_like(x)
    for s in range(sections + 2):
        hit = idx == s
        w = jnp.where(hit, wb_ref[2 * s], w)
        b = jnp.where(hit, wb_ref[2 * s + 1], b)
    return w * x + b


def _lut_interp_kernel(x_ref, wb_ref, o_ref, **lut):
    x = x_ref[...].astype(jnp.float32)
    o_ref[...] = lut_eval(x, wb_ref, **lut).astype(o_ref.dtype)


def lut_interp_2d(x: jax.Array, table: LutTable, *, block_rows: int = 256,
                  interpret: bool = False) -> jax.Array:
    """Apply `table` to x of shape (M, 128*k) — core pallas_call wrapper.

    The public entry point (ops.lut_apply) handles arbitrary shapes by
    padding/reshaping into this layout.
    """
    m, n = x.shape
    if n % LANE:
        raise ValueError(f"lut_interp_2d needs 128-lane rows, got {n}")
    block_rows = min(block_rows, m)
    if m % block_rows:
        raise ValueError(f"{m} rows do not tile by {block_rows}")
    wb, lut = lut_operand(table)
    return pl.pallas_call(
        functools.partial(_lut_interp_kernel, **lut),
        grid=(m // block_rows,),
        in_specs=[
            pl.BlockSpec((block_rows, n), lambda i: (i, 0)),
            SMEM_SPEC,
        ],
        out_specs=pl.BlockSpec((block_rows, n), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((m, n), x.dtype),
        interpret=interpret,
    )(x, wb)
