"""Serving telemetry: metric exactness, histogram bucket stability,
lifecycle traces, Chrome trace well-formedness, and the zero-cost
disabled mode (bit-identical engine outputs, empty registry)."""
from __future__ import annotations

import itertools
import json

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.salpim import SalPimConfig, SalPimEngine
from repro.models import api
from repro.serving.engine import EngineConfig, GenConfig, ServingEngine
from repro.serving.telemetry import (
    SCHEMA_VERSION, Counter, Histogram, MetricsRegistry, Telemetry,
    bench_metadata, log_bucket_edges,
)

ENGINE = SalPimEngine.create(SalPimConfig())
KEY = jax.random.PRNGKey(0)


def _setup(arch="gpt2_medium"):
    cfg = get_config(arch, smoke=True)
    params = api.init_params(KEY, cfg)
    return cfg, params


def _fake_clock(step=1.0):
    """Deterministic clock: 0, step, 2*step, ... per call."""
    c = itertools.count()
    return lambda: next(c) * step


# -- metrics ----------------------------------------------------------------

def test_counter_monotonic_and_exact():
    c = Counter()
    assert c.value == 0
    c.inc()
    c.inc(5)
    c.inc(0)
    assert c.value == 6
    with pytest.raises(AssertionError):
        c.inc(-1)


def test_log_bucket_edges_stable():
    # Bucket edges are a pure function of (lo, hi, buckets_per_decade) —
    # cross-run histogram comparability depends on these exact values.
    edges = log_bucket_edges(1e-3, 1.0, buckets_per_decade=1)
    np.testing.assert_allclose(edges, [1e-3, 1e-2, 1e-1, 1.0], rtol=1e-12)
    edges = log_bucket_edges(1e-5, 100.0, buckets_per_decade=5)
    assert edges[0] == pytest.approx(1e-5) and edges[-1] >= 100.0
    assert len(edges) == 36                       # 7 decades x 5 + 1
    ratios = np.diff(np.log10(edges))
    np.testing.assert_allclose(ratios, ratios[0], rtol=1e-9)
    # Same args -> identical edges (the stability contract).
    assert log_bucket_edges(1e-5, 100.0, 5) == edges


def test_histogram_buckets_and_percentiles():
    h = Histogram(lo=1e-3, hi=1.0, buckets_per_decade=1)
    for v in [5e-4, 5e-3, 5e-2, 5e-2, 2.0]:       # under, mid, mid, mid, over
        h.observe(v)
    d = h.to_dict()
    assert d["total"] == 5
    assert sum(d["counts"]) == 5
    assert d["counts"][0] == 1                    # underflow
    assert d["counts"][-1] == 1                   # overflow
    assert d["sum"] == pytest.approx(2.1055)
    # p50 lands in the [1e-2, 1e-1) bucket: geometric midpoint.
    assert d["p50"] == pytest.approx(np.sqrt(1e-2 * 1e-1))


def test_registry_created_on_touch():
    reg = MetricsRegistry()
    assert reg.empty
    reg.counter("a").inc()
    reg.counter("a").inc()                        # same object, not a new one
    assert reg.counter("a").value == 2
    assert not reg.empty
    snap = reg.snapshot()
    assert snap["counters"] == {"a": 2}
    reg.reset()
    assert reg.empty


# -- disabled mode ----------------------------------------------------------

def test_disabled_telemetry_is_noop():
    tel = Telemetry(enabled=False)
    tel.count("x")
    tel.gauge("y", 1.0)
    tel.observe("z", 0.5)
    tel.request_submitted(1, 4, 8)
    tel.request_admitted(1, 0)
    tel.chunk(1, 0.0, 1.0, 4)
    tel.tokens(1, 2.0)
    tel.spec_round(1, 0.0, 1.0, 4, 2)
    tel.request_finished(1)
    tel.record_step(0.0, 1.0, 0, 0, 0, 0, 1.0, 1, 2, 3, 0, 0)
    assert tel.registry.empty                     # nothing was ever created
    assert not tel.requests and not tel.steps


def test_annotate_requires_enabled():
    with pytest.raises(ValueError):
        Telemetry(enabled=False, annotate=True)


# -- lifecycle traces (scripted, fake clock) --------------------------------

def _scripted_telemetry():
    """Clock ticks 1s per call: submit@0, admit@1, tokens@2,3,4, finish@5."""
    tel = Telemetry(enabled=True, clock=_fake_clock())
    # _t0 consumed tick 0; script a two-request window.
    tel.request_submitted(1, prompt_tokens=4, max_new_tokens=3)   # t=1
    tel.request_submitted(2, prompt_tokens=6, max_new_tokens=2)   # t=2
    tel.request_admitted(1, slot=0)                               # t=3
    tel.chunk(1, 3.0, 3.5, 4)
    for t in (4.0, 5.0, 7.0):
        tel.tokens(1, t)
    tel.request_admitted(2, slot=1, shared_tokens=2)              # t=4
    tel.tokens(2, 5.0, n=2)                       # burst: zero intra-delta
    tel.record_step(3.0, 1.0, 0.1, 0.2, 0.0, 0.0, 0.5,
                    5, 3, 2, 1, 1)
    tel.request_finished(1)                                       # t=5
    tel.request_finished(2)                                       # t=6
    return tel


def test_lifecycle_counters_exact():
    tel = _scripted_telemetry()
    snap = tel.snapshot()
    c = snap["counters"]
    assert c["requests.submitted"] == 2
    assert c["requests.admitted"] == 2
    assert c["requests.finished"] == 2
    assert c["tokens.generated"] == 5
    assert c["prefill.tokens"] == 4 and c["prefill.chunks"] == 1
    assert snap["steps"]["count"] == 1
    assert snap["steps"]["phase_sec"]["decode"] == pytest.approx(0.5)
    assert snap["pool"]["occupancy_timeline"] == [[3.0, 5, 3, 2]]
    assert snap["schema_version"] == SCHEMA_VERSION


def test_per_request_summaries():
    tel = _scripted_telemetry()
    per = {r["uid"]: r for r in tel.snapshot()["requests"]["per_request"]}
    r1 = per[1]
    assert r1["queued_sec"] == pytest.approx(2.0)     # submit@1, admit@3
    assert r1["ttft_sec"] == pytest.approx(3.0)       # first token @4
    assert r1["tokens"] == 3 and r1["finished"]
    # Deltas are [1, 2]: nearest-rank p50 = 1, p99 = 2 — exact observed
    # gaps, not interpolations.
    assert r1["inter_token_p50_sec"] == pytest.approx(1.0)
    assert r1["inter_token_p99_sec"] == pytest.approx(2.0)
    r2 = per[2]
    assert r2["shared_tokens"] == 2
    assert r2["tokens"] == 2
    assert r2["inter_token_p50_sec"] == pytest.approx(0.0)  # burst


def test_snapshot_reset_window():
    tel = _scripted_telemetry()
    tel.request_submitted(3, 4, 4)                # still live at reset
    tel.reset()
    snap = tel.snapshot()
    assert snap["counters"] == {} and snap["steps"]["count"] == 0
    # Live requests keep their traces across the window boundary.
    assert snap["requests"]["live"] == 1
    assert snap["requests"]["per_request"][0]["uid"] == 3
    tel.tokens(3, tel.now())
    assert tel.snapshot()["counters"]["tokens.generated"] == 1


# -- Chrome trace export ----------------------------------------------------

def _check_trace(events):
    """Per-tid span discipline: every B has a matching E on its tid, and
    in file order (the format's nesting order) spans are well-nested."""
    stacks = {}
    for e in events:
        if e["ph"] == "B":
            stacks.setdefault(e["tid"], []).append(e["name"])
        elif e["ph"] == "E":
            assert stacks.get(e["tid"]), f"E with no open B on tid {e['tid']}"
            stacks[e["tid"]].pop()
    assert all(not s for s in stacks.values()), f"unclosed spans: {stacks}"


def test_chrome_trace_balanced_and_nested(tmp_path):
    tel = _scripted_telemetry()
    events = tel.chrome_trace_events()
    _check_trace(events)
    names = {e["name"] for e in events}
    assert {"request", "queued", "decode"} <= names
    # ph:"C" counter tracks carry the occupancy timeline.
    assert any(e["ph"] == "C" and e["name"] == "pool" for e in events)
    path = tmp_path / "trace.json"
    n = tel.export_chrome_trace(str(path))
    doc = json.loads(path.read_text())
    assert len(doc["traceEvents"]) == n
    assert doc["otherData"]["schema_version"] == SCHEMA_VERSION


# -- engine integration -----------------------------------------------------

def _drain(eng, reqs):
    uids = [eng.submit(p.copy(), max_new_tokens=n) for p, n in reqs]
    for _ in range(500):
        eng.step()
        if not eng.queue and all(a is None for a in eng.active):
            break
    else:
        raise AssertionError("engine did not drain")
    by = {r.uid: list(r.generated) for r in eng.finished}
    return [by[u] for u in uids]


def test_engine_telemetry_zero_cost_and_exact(tmp_path):
    cfg, params = _setup()
    rng = np.random.RandomState(0)
    reqs = [(rng.randint(2, cfg.vocab, size=rng.randint(4, 10)),
             int(rng.randint(3, 7))) for _ in range(4)]
    gen = GenConfig(temperature=0.0, stop_on_eos=False)
    tel = Telemetry(enabled=True)
    outs = {}
    for label, t in [("off", None), ("on", tel)]:
        eng = ServingEngine(params, cfg, ENGINE, EngineConfig(
            slots=2, max_len=24, gen=gen, page_size=8, prefill_chunk_tokens=4,
            telemetry=t))
        outs[label] = _drain(eng, reqs)
        if label == "off":
            # Zero-cost contract: the disabled default never touches the
            # registry, so it is provably empty after a full drain.
            assert eng.telemetry.registry.empty
            assert not eng.telemetry.enabled
        else:
            st = eng.stats()
    assert outs["on"] == outs["off"], "telemetry changed greedy outputs"

    n_new = sum(n for _, n in reqs)
    c = tel.snapshot()["counters"]
    assert c["tokens.generated"] == n_new
    assert c["requests.submitted"] == len(reqs)
    assert c["requests.finished"] == len(reqs)
    assert c["prefill.tokens"] == sum(len(p) for p, _ in reqs)

    # Satellite: stats() phase split — new fields present, old intact,
    # and the phases are sub-intervals of the measured step time.
    for k in ("step_sec", "admit_sec", "chunk_prefill_sec", "draft_sec",
              "verify_sec", "decode_sec", "model_sec_per_token",
              "sec_per_token", "tokens"):
        assert k in st, k
    phase_sum = (st["admit_sec"] + st["chunk_prefill_sec"] + st["draft_sec"]
                 + st["verify_sec"] + st["decode_sec"])
    assert phase_sum <= st["step_sec"] + 1e-6
    assert st["decode_sec"] > 0 and st["chunk_prefill_sec"] > 0

    # Engine-produced Chrome trace: balanced, nested, one tid per uid.
    events = tel.chrome_trace_events()
    _check_trace(events)
    req_tids = {e["tid"] for e in events
                if e["ph"] == "B" and e["name"] == "request"}
    assert len(req_tids) == len(reqs)
    path = tmp_path / "engine_trace.json"
    tel.export_chrome_trace(str(path))
    json.loads(path.read_text())                  # valid JSON document

    snap = tel.snapshot()
    assert len(snap["pool"]["occupancy_timeline"]) == snap["steps"]["count"]
    # The pool drains back to empty and the timeline saw real occupancy.
    assert snap["pool"]["occupancy_timeline"][-1][1] == 0
    assert max(t[1] for t in snap["pool"]["occupancy_timeline"]) > 0


def test_decode_walk_page_counters():
    """`decode.pages_walked` counts, in every layer, each slot's pages
    through the token its decode step appends, ceil((len + 1) / page)
    under global attention, and `decode.pages_tabled` the table's pages,
    slots x n_pages a layer: a hand-built batch of two prompts (5 and 9
    tokens, 4-token pages, one chunk per step) in three slots with
    8-page tables."""
    cfg, params = _setup()
    tel = Telemetry(enabled=True)
    eng = ServingEngine(params, cfg, ENGINE, EngineConfig(
        slots=3, max_len=32, gen=GenConfig(stop_on_eos=False),
        page_size=4, prefill_chunk_tokens=16, telemetry=tel))
    rng = np.random.RandomState(0)
    for n in (5, 9):
        eng.submit(rng.randint(2, cfg.vocab, size=n), max_new_tokens=6)
    walked = []
    for _ in range(3):
        eng.step()
        c = tel.snapshot()["counters"]
        walked.append(c["decode.pages_walked"])
    # Step 1: 5-token row (ceil(6/4) = 2), the 9-token prompt waiting
    # for its chunk and an empty slot (1 each). Step 2: lengths 6 and 9
    # (2 + 3) and the empty slot. Step 3: lengths 7 and 10 (2 + 3 + 1).
    L = cfg.n_layers
    assert cfg.window_for_layer(0) is None
    assert walked == [L * 4, L * (4 + 6), L * (4 + 6 + 6)]
    assert c["decode.pages_tabled"] == L * 3 * 3 * 8


@pytest.mark.parametrize("arch", ["gpt2_medium", "gemma2_2b"])
def test_prefill_walk_page_counters(arch):
    """`prefill.pages_walked` counts, in every layer, the table entries
    the chunk kernel's walk reads: from the page of the chunk's first
    query's first in-window key through the page of its last position;
    `prefill.pages_tabled` the row's entries a layer. One 40-token
    prompt in 8-token chunks (starts 0, 8, 16, 24, 32), 4-token pages,
    12-entry tables. Global layers read ceil(end / 4) pages a chunk:
    2 + 4 + 6 + 8 + 10 = 30. gemma2's local layers (window 16) start at
    page max(start - 15, 0) // 4 = 0, 0, 0, 2, 4: 2 + 4 + 6 + 6 + 6 =
    24."""
    cfg, params = _setup(arch)
    tel = Telemetry(enabled=True)
    eng = ServingEngine(params, cfg, ENGINE, EngineConfig(
        slots=2, max_len=48, gen=GenConfig(stop_on_eos=False),
        page_size=4, prefill_chunk_tokens=8, telemetry=tel))
    eng.submit(np.random.RandomState(0).randint(2, cfg.vocab, size=40),
               max_new_tokens=2)
    for _ in range(5):
        eng.step()
    c = tel.snapshot()["counters"]
    windows = [cfg.window_for_layer(i) for i in range(cfg.n_layers)]
    assert set(windows) <= {None, 16}
    assert c["prefill.chunks"] == 5
    assert c["prefill.pages_walked"] == sum(30 if w is None else 24
                                            for w in windows)
    assert c["prefill.pages_tabled"] == cfg.n_layers * 5 * 12


def test_bench_metadata_keys():
    meta = bench_metadata()
    for k in ("schema_version", "git_sha", "jax_version", "device_kind",
              "platform", "generated_utc"):
        assert k in meta, k
    assert meta["schema_version"] == SCHEMA_VERSION
    assert meta["jax_version"] == jax.__version__


def test_snapshot_golden_keys():
    """The snapshot schema is an external contract (CI artifacts, the
    bench regression checker, dashboards): every top-level section and
    the roofline section's shape are locked to SCHEMA_VERSION. Adding a
    key means bumping the version here AND in telemetry.py — that bump
    is what lets scripts/check_bench_regression.py tell a deliberate
    schema change from an accidental field drop."""
    assert SCHEMA_VERSION == 2
    cfg, params = _setup()
    tel = Telemetry(enabled=True, clock=_fake_clock(0.01))
    eng = ServingEngine(params, cfg, ENGINE, EngineConfig(
        slots=2, max_len=32, gen=GenConfig(stop_on_eos=False),
        page_size=8, prefill_chunk_tokens=8, telemetry=tel))
    rng = np.random.RandomState(0)
    for _ in range(2):
        eng.submit(rng.randint(2, cfg.vocab, size=6), max_new_tokens=4)
    steps = 0
    while eng.queue or any(a is not None for a in eng.active):
        eng.step()
        steps += 1
        assert steps < 200
    snap = tel.snapshot()
    assert set(snap) == {
        "schema_version", "counters", "gauges", "histograms", "steps",
        "pool", "requests", "prefix_cache", "admission", "scheduler",
        "roofline",
    }
    roof = snap["roofline"]
    assert set(roof) == {"hardware", "model", "phases"}
    assert set(roof["hardware"]) == {
        "name", "peak_flops", "peak_bytes_per_sec", "ridge_flops_per_byte"}
    for k in ("kv_dtype", "kv_scale_dtype", "kv_bytes_per_vector",
              "kv_bytes_per_token", "page_size", "page_bytes",
              "weight_stream_bytes", "draft_stream_bytes",
              "tensor_parallel", "gather_bytes_per_token", "model"):
        assert k in roof["model"], k
    assert roof["phases"], "no phase ran costs"
    for phase, row in roof["phases"].items():
        assert set(row) == {
            "bytes", "flops", "sec", "achieved_gbps", "achieved_gflops",
            "arithmetic_intensity", "bw_utilization", "bound",
        }, phase
    # Roofline achieved-bandwidth gauges land in the registry too.
    assert any(k.startswith("roofline.") and k.endswith(".achieved_gbps")
               for k in snap["gauges"])


# -- phase spans on the profiler clock ---------------------------------------

# The plain step's phases in step order (docs/observability.md).
PHASES = ("admit", "prefill_prep", "prefill_chunk", "prefill_commit",
          "sample", "token_sync", "emit", "page_map", "decode_step")


def _phase_engine(telemetry):
    cfg, params = _setup()
    eng = ServingEngine(params, cfg, ENGINE, EngineConfig(
        slots=3, max_len=32, gen=GenConfig(temperature=0.0,
                                           stop_on_eos=False),
        page_size=8, prefill_chunk_tokens=8,
        telemetry=telemetry))
    return cfg, eng


def _phase_prompts(cfg, seed):
    rng = np.random.RandomState(seed)
    return [(rng.randint(2, cfg.vocab, size=8), 4) for _ in range(3)]


def _host_spans(logdir):
    """(name, start_s, dur_s, stats) of the Python thread's events, on
    the profiler's clock."""
    import glob
    from jax.profiler import ProfileData
    path, = glob.glob(f"{logdir}/**/*.xplane.pb", recursive=True)
    spans = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            if line.name.startswith("python"):
                spans += [(e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9,
                           dict(e.stats)) for e in line.events]
    return sorted(spans, key=lambda s: s[1])


@pytest.fixture(scope="module")
def profiled_steps(tmp_path_factory):
    """Three profiled engine steps, each running one 8-token chunk (one
    per admitted request) and a decode step; a drain of the same shapes
    compiles everything first, so the trace holds no compilation."""
    cfg, eng = _phase_engine(Telemetry(enabled=True, annotate=True))
    _drain(eng, _phase_prompts(cfg, 0))
    for p, n in _phase_prompts(cfg, 1):
        eng.submit(p, max_new_tokens=n)
    logdir = str(tmp_path_factory.mktemp("profile"))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(logdir, profiler_options=opts)
    try:
        for _ in range(3):
            eng.step()
    finally:
        jax.profiler.stop_trace()
    return _host_spans(logdir)


def _steps(spans):
    return [s for s in spans if s[0] == "serve_step"]


def test_phase_spans_in_order_without_overlap(profiled_steps):
    steps = _steps(profiled_steps)
    assert len(steps) == 3
    for _, t, d, _ in steps:
        inner = [s for s in profiled_steps
                 if s[0] in PHASES and t <= s[1] <= t + d]
        assert tuple(s[0] for s in inner) == PHASES
        for (_, a, da, _), (_, b, _, _) in zip(inner, inner[1:]):
            assert a + da <= b                       # siblings, in order
        assert inner[-1][1] + inner[-1][2] <= t + d  # inside serve_step


def test_step_span_anchors_the_telemetry_clock(profiled_steps):
    offsets = [t - stats["tel_t"]
               for _, t, _, stats in _steps(profiled_steps)]
    assert len(offsets) == 3
    assert max(offsets) - min(offsets) < 1e-3


def test_engine_programs_are_named(profiled_steps):
    names = {s[0] for s in profiled_steps}
    assert {"PjitFunction(decode_step)",
            "PjitFunction(prefill_chunk)"} <= names


def test_no_profiler_annotation_built_when_off(monkeypatch):
    def refuse(*args, **kwargs):
        raise AssertionError("profiler annotation built with annotate off")

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", refuse)
    monkeypatch.setattr(jax.profiler, "StepTraceAnnotation", refuse)
    for tel in (None, Telemetry(enabled=True)):
        cfg, eng = _phase_engine(tel)
        assert len(_drain(eng, _phase_prompts(cfg, 0))) == 3


def test_greedy_outputs_identical_with_annotations():
    outs = []
    for annotate in (False, True):
        cfg, eng = _phase_engine(Telemetry(enabled=True, annotate=annotate))
        outs.append(_drain(eng, _phase_prompts(cfg, 2)))
    assert outs[0] == outs[1]
