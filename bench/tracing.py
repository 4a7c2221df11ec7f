"""From a run's profiler trace and records to its per-layer metrics.

The profiler runs from the window's open to its close, with the
program's own spans on (`Telemetry(enabled=True, annotate=True)`: a
`serve_step` span per engine step, `decode_step` and `prefill_chunk`
around each jitted call). The trace is reduced to three lists, times in
seconds on the profiler's clock:

  modules  programs that ran on the device ("XLA Modules" line)
  ops      operations inside them ("XLA Ops" line), control-flow
           containers (while, conditional, call) left out
  spans    the host's spans on its Python thread

As the trace names them (TPU v5e, jax 0.9): a program is
`jit_<name>(<fingerprint>)`; an operation is its HLO text,
`%<name>.<n> = <type>[<shape>] <opcode>(<operands>)`. The kernels carry
the names of the jitted wrappers that launch them: `pim_linear` (the
GEMV, kernels/gemv_pim.py), `pim_paged_attention` (paged decode,
kernels/paged_attention.py) and `pim_paged_prefill_attention` (chunked
prefill, kernels/paged_prefill.py). A program holding the prefill
kernel is a prefill chunk; one holding the paged decode kernel is a
decode step.

Each per-layer metric is a reader in `bench/metrics/<name>.py` with
`read(ctx) -> float | None`; None (nothing to read) leaves the metric
out of the result line.
"""
from __future__ import annotations

import collections
import dataclasses
import glob
import gzip
import json
import os
import re
import shutil
import tempfile

import numpy as np

GEMV = "pim_linear"
PAGED_DECODE = "pim_paged_attention"
PAGED_PREFILL = "pim_paged_prefill_attention"
CONTAINERS = {"while", "conditional", "call"}
TOP = 10


def op_base(name: str) -> str:
    """'%pim_linear.72 = bf16[...] custom-call(...)' -> 'pim_linear'."""
    head = name.split(" = ", 1)[0].lstrip("%")
    return re.sub(r"\.\d+$", "", head)


def module_base(name: str) -> str:
    """'jit__lambda(1143...)' -> 'jit__lambda'."""
    return name.split("(", 1)[0]


_SHAPE = re.compile(r"(\w+)\[([\d,]*)\]")


def gemv_shape(name: str) -> tuple[int, int, int] | None:
    """(rows, out R, in C) of a GEMV op from its HLO text: the result is
    (rows, R), the first operand (rows, C)."""
    try:
        result = name.split(" = ", 1)[1]
        out = _SHAPE.search(result)
        arg = _SHAPE.search(result.split("custom-call(", 1)[1])
    except IndexError:
        return None
    if out is None or arg is None:
        return None
    rows, r = (int(v) for v in out.group(2).split(","))
    return rows, r, int(arg.group(2).split(",")[1])


@dataclasses.dataclass
class Trace:
    modules: list          # (name, start_s, dur_s)
    ops: list
    spans: list

    @classmethod
    def from_xplane(cls, path: str) -> "Trace":
        from jax.profiler import ProfileData
        pd = ProfileData.from_file(path)
        modules, ops, spans = [], [], []
        for plane in pd.planes:
            if plane.name.startswith("/device:TPU:0"):
                for line in plane.lines:
                    if line.name == "XLA Modules":
                        modules += [(e.name, e.start_ns * 1e-9,
                                     e.duration_ns * 1e-9)
                                    for e in line.events]
                    elif line.name == "XLA Ops":
                        ops += [(e.name, e.start_ns * 1e-9,
                                 e.duration_ns * 1e-9)
                                for e in line.events
                                if op_base(e.name) not in CONTAINERS]
            elif plane.name == "/host:CPU":
                for line in plane.lines:
                    if line.name.startswith("python"):
                        spans += [(e.name, e.start_ns * 1e-9,
                                   e.duration_ns * 1e-9)
                                  for e in line.events]
        return cls(sorted(modules, key=lambda e: e[1]),
                   sorted(ops, key=lambda e: e[1]),
                   sorted(spans, key=lambda e: e[1]))

    def to_json(self, path: str) -> None:
        with gzip.open(path, "wt") as fh:
            json.dump(dataclasses.asdict(self), fh)

    @classmethod
    def from_json(cls, path: str) -> "Trace":
        with gzip.open(path, "rt") as fh:
            d = json.load(fh)
        return cls(*(list(map(tuple, d[k]))
                     for k in ("modules", "ops", "spans")))

    # -- reductions ---------------------------------------------------------
    def window(self) -> tuple[float, float] | None:
        """From the first engine step's start to the last one's end."""
        steps = [s for s in self.spans if s[0] == "serve_step"]
        if not steps:
            return None
        return steps[0][1], max(s[1] + s[2] for s in steps)

    def busy_intervals(self, lo: float, hi: float) -> list:
        """Union of program intervals, clipped to [lo, hi]."""
        out = []
        for _, t, d in self.modules:
            a, b = max(t, lo), min(t + d, hi)
            if b <= a:
                continue
            if out and a <= out[-1][1]:
                out[-1][1] = max(out[-1][1], b)
            else:
                out.append([a, b])
        return out

    def programs(self) -> list[tuple[str, float, float]]:
        """(kind, start, dur) of every program: "decode", "prefill_chunk"
        or the program's own name."""
        kinds = []
        j = 0
        ops = self.ops
        for name, t, d in self.modules:
            while j < len(ops) and ops[j][1] < t:
                j += 1
            kind = module_base(name)
            k = j
            while k < len(ops) and ops[k][1] < t + d:
                b = op_base(ops[k][0])
                if b == PAGED_PREFILL:
                    kind = "prefill_chunk"
                    break
                if b == PAGED_DECODE:
                    kind = "decode"
                k += 1
            kinds.append((kind, t, d))
        return kinds

    def op_seconds(self, base: str) -> float:
        return sum(d for n, _, d in self.ops if op_base(n) == base)

    def top_ops(self, n: int = TOP) -> list:
        acc = collections.Counter()
        for name, _, d in self.ops:
            acc[op_base(name)] += d
        return [[k, v] for k, v in acc.most_common(n)]

    def idle_by_span(self, n: int = TOP) -> list:
        """Idle device time inside the window, by the innermost host span
        running at each gap's midpoint ("no span" where none was)."""
        w = self.window()
        if w is None:
            return []
        busy = self.busy_intervals(*w)
        gaps, t = [], w[0]
        for a, b in busy:
            if a > t:
                gaps.append((t, a))
            t = b
        if w[1] > t:
            gaps.append((t, w[1]))
        acc = collections.Counter()
        starts = np.asarray([s[1] for s in self.spans])
        longest = max((s[2] for s in self.spans), default=0.0)
        for a, b in gaps:
            mid = 0.5 * (a + b)
            inner, best = "no span", float("inf")
            i = int(np.searchsorted(starts, mid, side="right")) - 1
            while i >= 0 and self.spans[i][1] >= mid - longest:
                name, t, d = self.spans[i]
                if mid <= t + d and d < best:
                    inner, best = name, d
                i -= 1
            acc[inner] += b - a
        return [[k, v] for k, v in acc.most_common(n)]


class Profile:
    """The profiler around the window, written under TMPDIR."""

    def __init__(self):
        self.dir = tempfile.mkdtemp(prefix="bench-trace-")

    def start(self) -> None:
        import jax
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.dir, profiler_options=opts)

    def stop(self) -> None:
        import jax
        jax.profiler.stop_trace()

    def trace(self) -> Trace:
        found = glob.glob(os.path.join(self.dir, "**", "*.xplane.pb"),
                          recursive=True)
        if not found:
            raise RuntimeError("the profiler wrote no trace")
        return Trace.from_xplane(found[0])

    def cleanup(self) -> None:
        shutil.rmtree(self.dir, ignore_errors=True)


@dataclasses.dataclass
class Ctx:
    """What per-layer readers read."""
    loop: str
    slots: int
    page: int
    pool_pages: int
    seconds: float
    window_recs: list        # run.Rec of requests due in the window
    window_steps: list       # run.Step of engine steps in the window
    request_traces: dict     # uid -> telemetry.RequestTrace
    compiles: int
    shapes: object           # costs.Shapes
    peak: dict | None
    trace: Trace | None
    busy_s: float | None = None
    window_s: float | None = None

    @staticmethod
    def percentile(xs, q) -> float:
        return float(np.percentile(np.asarray(xs, float), q))

    def programs(self, kind: str) -> list:
        if self.trace is None:
            return []
        return [p for p in self.trace.programs() if p[0] == kind]


def context(serve, compiles: int, dev: dict, prof: Profile | None,
            keep_trace: str | None = None) -> Ctx:
    from bench import costs, device
    e = serve.cell.traffic["engine"]
    trace = prof.trace() if prof is not None else None
    if trace is not None and keep_trace:
        trace.to_json(keep_trace)
    ctx = Ctx(
        loop=serve.cell.traffic["loop"], slots=e["slots"],
        page=e["page_size"], pool_pages=serve.eng.allocator.num_pages,
        seconds=serve.seconds, window_recs=serve.in_window(),
        window_steps=[s for s in serve.steps
                      if 0 <= s.t0 and s.t1 < serve.seconds],
        request_traces=dict(serve.telemetry.requests),
        compiles=compiles, shapes=costs.Shapes.of(serve.cfg),
        peak=device.peaks(dev["kind"]) if prof is not None else None,
        trace=trace)
    w = trace.window() if trace is not None else None
    if w is not None:
        ctx.window_s = w[1] - w[0]
        ctx.busy_s = sum(b - a for a, b in trace.busy_intervals(*w))
    return ctx


def per_layer(cell, ctx: Ctx) -> tuple[dict, dict]:
    """The cell's per-layer metrics that have something to read, and the
    breakdown of the trace."""
    from bench import spec
    out = {}
    for m in cell.per_layer:
        v = spec.metric_reader(m["name"]).read(ctx)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    breakdown = {}
    if ctx.trace is not None:
        breakdown = {"device_ops": ctx.trace.top_ops(),
                     "idle_gaps": ctx.trace.idle_by_span()}
    return out, breakdown
