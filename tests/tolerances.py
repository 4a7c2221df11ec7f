"""Tolerances derived from f32 accumulation order.

Two programs that compute the same model in f32 can still disagree in
the last bits: XLA's CPU backend picks a dot's blocking and its thread
split from the operand shapes and the device count, so a 4-token chunk,
a 13-token prefill and a 1-token decode step sum each contraction in
different orders. Reordering a length-n f32 sum moves its result by at
most about n * eps relative to the sum of the magnitudes of its terms.
A value computed after L layers has passed through L residual blocks,
each ending in a contraction no longer than max(d_model, d_ff), and one
more contraction (the K/V projection or the LM head) on top.
"""
from __future__ import annotations

import numpy as np

EPS_F32 = float(np.finfo(np.float32).eps)


def f32_reorder_tol(cfg) -> float:
    """rtol = atol for comparing two f32 paths through the whole of
    `cfg`'s stack that differ only in summation order: (L + 1)
    contractions of length <= max(d_model, d_ff), each worth n * eps.
    A wrong page, mask or position moves values by O(1), far above it."""
    n = max(cfg.d_model, cfg.d_ff)
    return (cfg.n_layers + 1) * n * EPS_F32
