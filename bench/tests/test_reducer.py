"""The trace reduction, on three engine steps recorded on a TPU v5e.

`data/trace_gpt2m_poisson_3steps.json.gz` is a cut of a `--trace 1` run
of gpt2m.paper.poisson (one chip): every program, operation (HLO text
cut to 300 characters) and host span from the start of one engine step
to the end of the second step after it. Each of the three steps ran a
prefill chunk (64, 64 and 32 tokens) and a decode step of 32 slots.
"""
from __future__ import annotations

import os

import pytest

from bench import costs, device, spec, tracing
from bench.tracing import Ctx, Trace

DATA = os.path.join(os.path.dirname(__file__), "data",
                    "trace_gpt2m_poisson_3steps.json.gz")


@pytest.fixture(scope="module")
def trace():
    return Trace.from_json(DATA)


def test_programs_are_told_apart_by_their_kernels(trace):
    kinds = [p for p in trace.programs()
             if p[0] in ("decode", "prefill_chunk")]
    assert [k for k, _, _ in kinds] == ["prefill_chunk", "decode"] * 3
    dec = [d for k, _, d in kinds if k == "decode"]
    assert all(0.150 < d < 0.165 for d in dec)
    pre = [d for k, _, d in kinds if k == "prefill_chunk"]
    assert pre[2] < pre[0]          # the 32-token chunk is the shortest


def test_busy_is_the_union_of_programs(trace):
    lo, hi = trace.window()
    naive = 0.0
    edges = sorted((max(t, lo), min(t + d, hi)) for _, t, d in trace.modules)
    end = lo
    for a, b in edges:
        if b > end:
            naive += b - max(a, end)
            end = b
    busy = sum(b - a for a, b in trace.busy_intervals(lo, hi))
    assert busy == pytest.approx(naive, rel=1e-12)
    assert 0 < busy < hi - lo
    idle = sum(v for _, v in trace.idle_by_span())
    assert idle == pytest.approx(hi - lo - busy, rel=1e-9)


def test_gemv_shapes_read_from_the_hlo_text(trace):
    shapes = {tracing.gemv_shape(n) for n, _, _ in trace.ops
              if tracing.op_base(n) == tracing.GEMV}
    assert {(32, 1024, 1024), (32, 4096, 1024), (32, 1024, 4096),
            (32, 50257, 1024), (64, 1024, 1024), (1, 50257, 1024)} <= shapes


def test_top_op_and_names(trace):
    assert trace.top_ops(1)[0][0] == tracing.PAGED_DECODE
    assert tracing.op_base("%pim_linear.72 = bf16[32,4096]{1,0} "
                           "custom-call(bf16[32,1024]{1,0} %f)") == "pim_linear"
    assert tracing.module_base("jit__lambda(1143)") == "jit__lambda"


def test_readers_on_the_recorded_trace(trace):
    cell = spec.load_cell("gpt2m.paper.poisson")
    cfg, _ = spec.program_config(cell, impl="reference")
    lo, hi = trace.window()
    ctx = Ctx(loop="open", slots=32, page=16, pool_pages=769, seconds=1.0,
              window_recs=[], window_steps=[], request_traces={},
              compiles=0, shapes=costs.Shapes.of(cfg),
              peak=device.peaks("TPU v5 lite"), trace=trace,
              window_s=hi - lo,
              busy_s=sum(b - a for a, b in trace.busy_intervals(lo, hi)))
    read = {m: spec.metric_reader(m).read(ctx) for m in
            ("decode_step_ms", "prefill_chunk_ms.ttft", "gemv_roofline",
             "device_idle_share")}
    assert 150 < read["decode_step_ms"] < 165
    assert 30 < read["prefill_chunk_ms.ttft"] < 45
    assert 0 < read["gemv_roofline"] <= 100
    assert read["device_idle_share"] == pytest.approx(
        100 * (1 - ctx.busy_s / ctx.window_s))
    # no harness steps in this context: nothing to read, not a zero
    assert spec.metric_reader("paged_decode_roofline").read(ctx) is None
    assert spec.metric_reader("mfu").read(ctx) is None
