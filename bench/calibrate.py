#!/usr/bin/env python3
"""Readings that `correct`'s limits are set from, many seeds, one process.

    python3 bench/calibrate.py --workload <name> --seeds 1,2,3 --seconds 30

For each seed: the cell's weights and engine, its set-up drain and
warm-up, a window of --seconds of its own traffic, the seeded sample of
tracked finished requests with their kept logits, and then, with the
engine freed, the numbers `correct` compares (bench/run.py `readings`),
on the same rows, for the program and for the reference run with int8,
and with float8, matrices: the mean gap by which a served token's (or
the control's first token's) float32-reference logit lies below the
reference's best, and the mean distance of the program's (or the
control's) logits from the reference's, both in units of that row's
logit std. One JSON line per seed, also appended to --out. Each limit
lies between the largest program reading and the smallest control
reading (PERF.md gives both).
"""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from bench import run  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out")
    args = ap.parse_args()
    from bench import device, spec
    cell = spec.load_cell(args.workload)
    device.enable_compile_cache()
    dev = device.describe(cell.chips)
    for seed in (int(x) for x in args.seeds.split(",")):
        t0 = time.perf_counter()
        s = run.Serve(cell, seed, False)
        s.compile_drain()
        s.run(args.seconds)
        setup = s.t_w0 - t0
        e2e = run.end_to_end(s, setup)
        picked = s.sample()
        kept = s.kept_logits(picked)
        del s.eng
        gc.collect()
        found = run.compare(s, picked, kept, controls=("int8", "fp8"))
        line = {"workload": cell.name, "seed": seed, "device": dev["kind"],
                **found, "requests": len(picked),
                "longest": max((r.ntok for r in picked), default=0),
                "e2e": e2e, "seconds": time.perf_counter() - t0}
        print(json.dumps(line), flush=True)
        if args.out:
            with open(args.out, "a") as fh:
                fh.write(json.dumps(line) + "\n")
        del s
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
