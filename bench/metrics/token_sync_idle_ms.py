"""Engine loop: device-idle time under sampling and the token sync, per
engine step, ms.

The gaps in the union of program intervals inside the traced window
(first `serve_step` span's start to the last one's end), intersected
with the host's `sample` and `token_sync` spans (`ServingEngine.step`,
serving/engine.py, with `Telemetry(annotate=True)`), summed and divided
by the window's `serve_step` spans. A trace without these spans reads
nothing."""

PHASES = ("sample", "token_sync")


def _union(intervals):
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def _overlap(xs, ys):
    """Total length of the intersection of two sorted disjoint lists."""
    total, i, j = 0.0, 0, 0
    while i < len(xs) and j < len(ys):
        a, b = max(xs[i][0], ys[j][0]), min(xs[i][1], ys[j][1])
        if b > a:
            total += b - a
        if xs[i][1] < ys[j][1]:
            i += 1
        else:
            j += 1
    return total


def idle_ms_per_step(ctx, phases):
    """Device-idle ms per engine step inside the spans named `phases`;
    None when the trace has no serve_step or no such span."""
    if ctx.trace is None:
        return None
    spans = ctx.trace.spans
    steps = [(t, t + d) for n, t, d in spans if n == "serve_step"]
    held = [(t, t + d) for n, t, d in spans if n in phases]
    if not steps or not held:
        return None
    lo, hi = min(a for a, _ in steps), max(b for _, b in steps)
    gaps, t = [], lo
    for a, b in ctx.trace.busy_intervals(lo, hi):
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if hi > t:
        gaps.append((t, hi))
    return 1e3 * _overlap(gaps, _union(held)) / len(steps)


def read(ctx):
    return idle_ms_per_step(ctx, PHASES)
