"""Pipeline parallelism over the `pod` axis (GPipe schedule, shard_map).

Pods are the highest-latency boundary of the production mesh; pipeline
stages only need point-to-point transfers (collective_permute), which is
exactly the traffic pattern that survives a slow cross-pod link. The
launcher exposes this as `--pod-axis pipeline` (default keeps pods as an
extra data-parallel axis).

Implementation: the classic collective_permute ring. With P stages and M
microbatches, each device holds the parameters of its stage; microbatch
activations rotate through stages. Bubble fraction = (P-1)/(M+P-1).

`pipeline_forward` is deliberately self-contained (a uniform stack of
per-stage functions) — it is validated on an 8-fake-device mesh in
tests/test_distributed.py and wired to the block stack in launch/train.py.
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

Array = jax.Array


def pipeline_forward(
    stage_fn: Callable[[Array, Array], Array],
    stage_params: Array,          # (P, ...) one slice per stage
    x_micro: Array,               # (M, mb, ...) microbatched input
    *,
    axis_name: str,
) -> Array:
    """Run x through P sequential stages on the `axis_name` mesh axis.

    Inside shard_map: this device holds stage_params for ITS stage and the
    (M, mb, ...) microbatch queue. The GPipe loop runs M + P - 1 ticks; on
    tick t, the device processes microbatch (t - stage_idx) when it is in
    range, then passes its activation to the next stage.
    """
    p = jax.lax.axis_size(axis_name)
    stage = jax.lax.axis_index(axis_name)
    m = x_micro.shape[0]
    mb_shape = x_micro.shape[1:]
    total = m + p - 1

    # Mark loop carries as varying over the pipeline axis up front, or the
    # fori_loop carry types flip from invariant to varying after tick 1.
    out = jax.lax.pvary(jnp.zeros_like(x_micro), (axis_name,))
    carry_in = jax.lax.pvary(jnp.zeros(mb_shape, x_micro.dtype), (axis_name,))

    def tick(t, state):
        out, carry_in = state
        mb_idx = t - stage
        active = (mb_idx >= 0) & (mb_idx < m)
        # Stage 0 pulls from the queue; others use the permuted carry.
        safe_idx = jnp.clip(mb_idx, 0, m - 1)
        x_in = jnp.where(stage == 0, x_micro[safe_idx], carry_in)
        y = stage_fn(stage_params, x_in)
        y = jnp.where(active, y, jnp.zeros_like(y))
        # Last stage writes its finished microbatch; others forward it.
        # (branch-free: lax.cond breaks shard_map's varying-axis typing)
        write = active & (stage == p - 1)
        out = out.at[safe_idx].set(jnp.where(write, y, out[safe_idx]))
        carry_next = jax.lax.ppermute(
            y, axis_name, [(i, (i + 1) % p) for i in range(p)])
        return out, carry_next

    out, _ = jax.lax.fori_loop(0, total, tick, (out, carry_in))
    # Every stage's `out` is zeros except the last; share the result.
    return jax.lax.psum(out, axis_name)


def make_pipelined_fn(stage_fn: Callable, mesh: Mesh, axis_name: str,
                      n_micro: int):
    """Wrap stage_fn into a jit'd pipelined callable over `mesh`.

    stage_params must be stacked (P, ...); x must be (B, ...) with
    B % n_micro == 0.
    """
    def fn(stage_params, x):
        B = x.shape[0]
        mb = B // n_micro
        x_micro = x.reshape((n_micro, mb) + x.shape[1:])
        spec_p = P(axis_name)
        spec_x = P()   # microbatch queue replicated; stages stream it

        def inner(sp, xm):
            sp = jax.tree.map(lambda a: a[0], sp)  # this stage's slice
            return pipeline_forward(stage_fn, sp, xm, axis_name=axis_name)

        y = jax.shard_map(
            inner, mesh=mesh,
            in_specs=(jax.tree.map(lambda _: spec_p, stage_params), spec_x),
            out_specs=spec_x,
        )(stage_params, x_micro)
        return y.reshape((B,) + y.shape[2:])

    return jax.jit(fn)
