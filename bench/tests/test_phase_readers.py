"""The engine-phase readers (`prefill_wait_p95_ms`, `token_sync_idle_ms`,
`host_update_idle_ms`) on a synthetic trace with known gaps and spans,
and on the recorded three-step trace, which predates the phase spans."""
from __future__ import annotations

import os
import types

import numpy as np
import pytest

from bench import spec
from bench.tracing import Ctx, Trace
from repro.serving.telemetry import RequestTrace

DATA = os.path.join(os.path.dirname(__file__), "data",
                    "trace_gpt2m_poisson_3steps.json.gz")
MS = 1e-3
PHASES = ("admit", "prefill_prep", "prefill_chunk", "prefill_commit",
          "sample", "token_sync", "emit", "page_map", "decode_step")
# Each step is 10 ms long; its phases start at these offsets (ms), in
# PHASES order, and the last one ends with the step.
OFFSETS = (0, 1, 2, 2.5, 3, 4, 6, 7, 9)


def _ctx(trace, traces=None, recs=()):
    return Ctx(loop="open", slots=2, page=16, pool_pages=8, seconds=1.0,
               window_recs=list(recs), window_steps=[],
               request_traces=traces or {}, compiles=0, shapes=None,
               peak=None, trace=trace)


def _read(name, ctx):
    return spec.metric_reader(name).read(ctx)


@pytest.fixture
def synthetic():
    """Two steps at 0 and 10 ms. Programs run 0-4.5, 5.5-6.5, 9.5-10.5,
    10.8-15 and 19.5-22 ms, so the device is idle 4.5-5.5 (token_sync),
    6.5-9.5 (emit 0.5, page_map 2, decode_step 0.5), 10.5-10.8 (admit)
    and 15-19.5 (token_sync 1, emit 1, page_map 2, decode_step 0.5)."""
    spans = []
    for t0 in (0.0, 10.0):
        spans.append(("serve_step", t0 * MS, 10 * MS))
        ends = OFFSETS[1:] + (10,)
        spans += [(n, (t0 + a) * MS, (b - a) * MS)
                  for n, a, b in zip(PHASES, OFFSETS, ends)]
    # an eager program's host span nested in page_map counts once
    spans.append(("PjitFunction(scatter)", 7.5 * MS, 0.1 * MS))
    spans.sort(key=lambda s: s[1])
    busy = [(0, 4.5), (5.5, 6.5), (9.5, 10.5), (10.8, 15), (19.5, 22)]
    modules = [("jit_x", a * MS, (b - a) * MS) for a, b in busy]
    return Trace(modules=modules, ops=[], spans=spans)


def test_idle_is_split_by_intersection_not_midpoint(synthetic):
    ctx = _ctx(synthetic)
    # (1 + 1) ms over two steps
    assert _read("token_sync_idle_ms", ctx) == pytest.approx(1.0)
    # (0.5 + 2) + (0.3 + 1 + 2) ms over two steps; the 6.5-9.5 gap's
    # midpoint lies in page_map, but emit holds half a millisecond of it
    assert _read("host_update_idle_ms", ctx) == pytest.approx(2.9)
    lo, hi = synthetic.window()
    idle = hi - lo - sum(b - a for a, b in synthetic.busy_intervals(lo, hi))
    decode_share = 2 * 0.5 * MS
    assert (1.0 + 2.9) * 2 * MS + decode_share == pytest.approx(idle)


def test_phase_readers_read_nothing_without_their_spans(synthetic):
    bare = Trace(modules=synthetic.modules, ops=[],
                 spans=[s for s in synthetic.spans
                        if s[0] in ("serve_step", "decode_step")])
    for trace in (None, bare):
        ctx = _ctx(trace)
        assert _read("token_sync_idle_ms", ctx) is None
        assert _read("host_update_idle_ms", ctx) is None


def test_prefill_wait_reads_first_chunk_minus_admission():
    traces, recs = {}, []
    for uid, wait in enumerate((0.05, 0.2, 0.4, 0.9), start=1):
        tr = RequestTrace(uid, 64, 8, submit_t=10.0, admit_t=10.1)
        tr.chunks += [(10.1 + wait, 10.2 + wait, 64),
                      (11.0 + wait, 11.1 + wait, 64)]
        traces[uid] = tr
        recs.append(types.SimpleNamespace(uid=uid))
    # admitted but not yet prefilled, never admitted, refused (no uid)
    traces[5] = RequestTrace(5, 64, 8, submit_t=10.0, admit_t=10.3)
    traces[6] = RequestTrace(6, 64, 8, submit_t=10.0)
    recs += [types.SimpleNamespace(uid=u) for u in (5, 6, None)]
    got = _read("prefill_wait_p95_ms", _ctx(None, traces, recs))
    assert got == pytest.approx(
        np.percentile([50.0, 200.0, 400.0, 900.0], 95))
    assert _read("prefill_wait_p95_ms", _ctx(None, traces, recs[4:])) is None


def test_phase_readers_on_the_recorded_trace():
    ctx = _ctx(Trace.from_json(DATA))
    for name in ("prefill_wait_p95_ms", "token_sync_idle_ms",
                 "host_update_idle_ms"):
        assert _read(name, ctx) is None, name
