"""The control, in the program's place, must come out not correct.

At a size the CPU holds, each cell's traffic is served, and the same
rows are then read through the reference computed in a precision below
the served bfloat16 (the limits file names, for each number compared,
the control its upper reading came from: int8 or float8 matrices).
Each such control's readings (the mean gap of the tokens it puts first
below the reference's best, and the mean distance of its logits from
the reference's) go through `judge` with the cell's own limits, so a
change that served in that precision would fail `correct`. On the chip,
at each cell's own size, the same readings are taken by
`bench/calibrate.py` (PERF.md gives them).
"""
from __future__ import annotations

import gc

import pytest

from bench import run
from bench.tests import smoke

CELLS = ["gpt2m.paper.poisson", "qwen2-1.5b.longdoc.closed16"]


@pytest.mark.parametrize("name", CELLS)
def test_control_fails_the_cells_limit(name):
    c = smoke.cell(name, tokens=200, control=True)
    limits = smoke.limits(c)
    s = run.Serve(c, 21, False)
    s.compile_drain()
    s.run(4.0)
    picked = s.sample()
    kept = s.kept_logits(picked)
    del s.eng
    gc.collect()
    controls = sorted(set(limits["control"].values()))
    found = run.compare(s, picked, kept, controls=tuple(controls))
    program_ok, checks = run.judge(found, limits)
    assert program_ok, checks
    for q in controls:
        control = dict(found, **{k: found[f"control_{q}_{k}"]
                                 for k in run.JUDGED})
        control_ok, checks = run.judge(control, limits)
        assert not control_ok, (q, checks)
