#!/usr/bin/env python3
"""Find an open-loop cell's knee: the same engine at several fixed rates.

    python3 bench/sweep.py --workload <name> --rates 1,2,3 --seconds 30

One engine, built and warmed once; for each rate, the traffic's own
warm-up (`warm_s` seconds of arrivals, longer than the longest
request's life, so the window starts in steady state), --seconds of the
cell's traffic at that rate, then a drain with no arrivals. Per rate it
prints the offered and completed rates, tokens/s, the time to first
token, and whether the queue grew: the number waiting for admission and
the live decode rows, averaged over the window's first, middle and last
thirds. The cell itself offers one fixed rate (its traffic file's
`rate_rps`), set once from such a sweep; nothing searches for a rate in
a measured run.
"""
import argparse
import copy
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from bench import run  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--out")
    args = ap.parse_args()
    from bench import device, spec
    cell = spec.load_cell(args.workload)
    device.enable_compile_cache()
    device.describe(cell.chips)
    s = run.Serve(cell, args.seed, False)
    s.compile_drain()
    for rate in (float(x) for x in args.rates.split(",")):
        s.cell = copy.deepcopy(cell)
        s.cell.traffic["rate_rps"] = rate
        s.run(args.seconds, track=False)
        T = args.seconds
        e2e = run.end_to_end(s, 0.0)
        thirds = [[st for st in s.steps
                   if i * T / 3 <= st.t1 < (i + 1) * T / 3] for i in range(3)]
        win = s.in_window()
        line = {"workload": cell.name, "rate_rps": rate,
                "offered_rps": len(win) / T,
                "completed_rps": sum(1 for r in win if r.finish_t is not None
                                     and r.finish_t < T) / T,
                "queue_thirds": [mean([st.queued for st in th])
                                 for th in thirds],
                "decode_rows_thirds": [mean([len(st.decode_lens)
                                             for st in th])
                                       for th in thirds],
                **e2e}
        print(json.dumps(line), flush=True)
        if args.out:
            with open(args.out, "a") as fh:
                fh.write(json.dumps(line) + "\n")
        s.eng.run(max_steps=100000)
    return 0


def mean(xs) -> float:
    return sum(xs) / max(len(xs), 1)


if __name__ == "__main__":
    sys.exit(main())
