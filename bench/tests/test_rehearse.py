"""Each cell end to end on the CPU at smoke size, and the refusal to
measure without a chip."""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time

import pytest

from bench import run
from bench.tests import smoke

CELLS = ["gpt2m.paper.poisson", "qwen2-1.5b.longdoc.closed16"]
BIG_SEED = 2**33 + 12345


@pytest.mark.parametrize("name", CELLS)
def test_cell_runs_and_is_correct(name):
    c = smoke.cell(name)
    out = run.execute(c, BIG_SEED, 3.0, False, time.perf_counter(),
                      require_chip=False, limits=smoke.limits(c))
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    want = {m["name"] for m in c.end_to_end}
    assert set(out["metrics"]) == want
    # no device metric from a CPU run
    assert "memory_peak_bytes" not in out["device"]
    assert "busy_s" not in out["device"]
    assert list(out)[-1] == "checks"


@pytest.mark.parametrize("name", CELLS)
def test_kept_logits_are_the_ones_each_token_was_sampled_from(name):
    """Greedy serving: every kept row's argmax is the token served next."""
    import numpy as np
    s = run.Serve(smoke.cell(name), 5, False)
    s.compile_drain()
    s.run(3.0)
    picked = s.sample()
    kept = s.kept_logits(picked)
    assert picked and s.captures == []
    for r, rows in zip(picked, kept):
        assert rows.shape == (r.ntok - 1, s.cfg.vocab)
        assert list(rows.argmax(-1)) == list(r.req.generated[1:])


def test_traced_cpu_run_reports_host_metrics_only():
    c = smoke.cell("gpt2m.paper.poisson")
    out = run.execute(c, 7, 3.0, True, time.perf_counter(),
                      require_chip=False, limits=smoke.limits(c))
    assert out["correct"]
    got = set(out["metrics"])
    assert {"compiles_in_window", "decode_occupancy",
            "pool_peak_share", "gen_lag_p95_ms"} <= got
    device_metrics = {"decode_step_ms", "prefill_chunk_ms.ttft",
                      "gemv_roofline", "paged_decode_roofline",
                      "device_idle_share", "mfu"}
    assert not got & device_metrics
    assert out["metrics"]["compiles_in_window"]["value"] == 0


def test_without_a_chip_it_exits_nonzero_and_prints_no_result():
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", CELLS[0],
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=root, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode == run.EXIT_NO_CHIP
    assert p.stdout.strip() == ""
    for line in p.stdout.splitlines():
        with pytest.raises(json.JSONDecodeError):
            json.loads(line)
