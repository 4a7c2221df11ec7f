#!/usr/bin/env python3
"""Run one benchmark cell once on the chip and print its result line.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One process, one cell (`BENCHMARK.json` `workloads` entry):

  1. set-up: weights made on the device from --seed by the cell's
     family module; one `ServingEngine` built as the cell's traffic file
     says; a short drain through that same engine compiles (or loads
     from the persistent cache) every program shape the traffic uses;
     then the traffic's own warm-up (open loop: `warm_s` seconds of
     arrivals; closed loop: `warm_steps` engine steps);
  2. the window: --seconds of traffic through `submit()` / `step()`;
     in an open loop, requests due in it that have no first token at
     the close are served on, up to a minute, for that first token;
     from the warm-up on, the logits the engine samples a seeded subset
     of requests' tokens from are kept on the device, one gather a step;
  3. `correct`: a seeded sample of those requests that the window
     finished, the longest among them, is teacher-forced through the
     family's float32 reference once the engine is freed; the served
     tokens' gaps below the reference's best and the kept logits'
     distance from the reference's are compared with the cell's limits
     (`bench/limits/<cell>.json`);
  4. one JSON line: the cell's end-to-end metrics (--trace 0), or its
     per-layer metrics read from the profiler trace, the program's
     request traces and the harness's own records (--trace 1).

Without a TPU, or with fewer chips than the cell asks for, it exits 3
and prints no result. The last lines on stderr, and the result's last
key `checks`, give each number compared beside its limit.
"""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "src"))

GRACE_S = 60.0      # serving past the close for first tokens still owed
EXIT_NO_CHIP = 3
TRACK_SHARE = 0.5   # share of shorter requests whose logits are kept


@dataclasses.dataclass
class Rec:
    """The harness's own record of one request, times in seconds from
    the window's start (host clock)."""
    req: object              # the engine's Request
    prompt_len: int
    max_new: int
    due: float               # open loop: scheduled; closed: sent
    submit_t: float
    uid: int = 0
    first_t: float | None = None
    finish_t: float | None = None
    token_times: list = dataclasses.field(default_factory=list)
    cursor: int = 0          # prompt tokens prefilled so far
    ntok: int = 0
    slot: int | None = None  # engine slot, once its logits are kept
    row: int | None = None   # capture row its logits are kept in
    kept: list = dataclasses.field(default_factory=list)
    # kept[j]: the capture holding the logits the engine sampled token
    # j + 1 from (token 0 is sampled from the prefill's logits in the
    # same step that ends the prefill, so no step boundary sees them)


@dataclasses.dataclass
class Step:
    t0: float
    t1: float
    emitted: int             # tokens returned by this step
    decode_lens: list        # post-append KV lengths of continuing rows
    chunks: list             # (start, tokens) prefilled this step
    used_pages: int
    queued: int              # requests waiting for admission after it


class Serve:
    """One engine under one traffic mix; the window loop and its records."""

    def __init__(self, cell, seed: int, trace: bool):
        import jax
        import jax.numpy as jnp
        import numpy as np
        from bench import spec, weights
        from repro.models import api
        from repro.serving import EngineConfig, GenConfig, ServingEngine
        from repro.serving.telemetry import Telemetry
        self.cell, self.seed = cell, seed
        self.fam = spec.family(cell)
        self.cfg, engine = spec.program_config(cell)
        self.params = weights.make(self.fam.layout(cell.config), seed,
                                   jnp.dtype(cell.config["bench"]["dtype"]))
        weights.check_layout(self.params, jax.eval_shape(
            lambda: api.init_params(jax.random.PRNGKey(0), self.cfg)))
        e = cell.traffic["engine"]
        self.telemetry = (Telemetry(enabled=True, annotate=True) if trace
                          else None)
        self.eng = ServingEngine(self.params, self.cfg, engine, EngineConfig(
            paged=True, gen=GenConfig(temperature=0.0, stop_on_eos=False),
            telemetry=self.telemetry, **e))
        self.recs: list[Rec] = []
        self.inflight: list[Rec] = []
        self.steps: list[Step] = []
        self.t_w0 = 0.0          # window start, perf_counter seconds
        self.seconds = 0.0
        # The program's own logits of a seeded subset of requests, kept
        # on the device as the window serves them (see _keep_logits).
        from bench.traffic import DEFAULT_BLOCK, block_values
        t = cell.traffic
        self.rows = int(t["check"]["capture_rows"])
        self.long_min = int(block_values(
            t["output_len"], t.get("block", DEFAULT_BLOCK)).max()) // 2
        self._take = jax.jit(lambda x, i: jnp.take(x, i, axis=0))
        self.captures: list = []
        self.tracking = False
        self._pick = np.random.default_rng([seed, 1])

    # -- set-up ------------------------------------------------------------
    def compile_drain(self) -> None:
        """Drain one short request per prefill-chunk shape through the
        engine the window uses, so every program it will run is compiled
        (or read from the persistent cache) before the window."""
        import jax
        import numpy as np
        from bench import spec
        eng = self.eng
        rng = np.random.default_rng(0)
        chunk = self.cell.traffic["engine"]["prefill_chunk_tokens"]
        for s in spec.chunk_shapes(self.cell):
            eng.submit(rng.integers(1, self.cfg.vocab, size=min(s, chunk),
                                    dtype=np.int32), max_new_tokens=3)
        while eng.queue or any(a is not None for a in eng.active):
            eng.step()
        jax.block_until_ready(self._take(eng.last_logits,
                                         np.zeros(self.rows, np.int32)))

    # -- bookkeeping -------------------------------------------------------
    def _now(self) -> float:
        return time.perf_counter() - self.t_w0

    def _submit(self, item, due: float) -> None:
        prompt, max_new, _ = item
        t = self._now()
        try:
            uid = self.eng.submit(prompt, max_new_tokens=max_new)
        except ValueError as e:
            print(f"refused: {e}", file=sys.stderr)
            self.recs.append(Rec(None, len(prompt), max_new, due, t))
            return
        rec = Rec(self.eng.queue[-1], len(prompt), max_new, due, t, uid)
        self.recs.append(rec)
        self.inflight.append(rec)

    def _step(self) -> list[Rec]:
        """One engine step; returns the requests it finished."""
        t0 = self._now()
        self.eng.step()
        t1 = self._now()
        emitted, lens, chunks, done, firsts = 0, [], [], [], []
        for rec in self.inflight:
            req = rec.req
            c = req.prefill_cursor
            if c > rec.cursor:
                chunks.append((rec.cursor, c - rec.cursor))
                rec.cursor = c
            n = len(req.generated)
            if n > rec.ntok:
                if rec.ntok == 0:
                    rec.first_t = t1
                    firsts.append(rec)
                rec.token_times.extend([t1] * (n - rec.ntok))
                emitted += n - rec.ntok
                rec.ntok = n
                if not req.done:
                    lens.append(rec.prompt_len + n)
            if req.done:
                rec.finish_t = t1
                done.append(rec)
        if done:
            self.inflight = [r for r in self.inflight if not r.req.done]
        if self.tracking:
            self._keep_logits(firsts)
        a = self.eng.allocator
        self.steps.append(Step(t0, t1, emitted, lens, chunks, a.used_pages,
                               len(self.eng.queue)))
        return done

    def _keep_logits(self, firsts: list[Rec]) -> None:
        """Keep, on the device, the logits the engine will sample the
        tracked requests' next tokens from: one gather of `capture_rows`
        rows of `last_logits` per step, no host sync. A request is
        tracked from its first token while a capture row is free, if it
        asks for at least half the mix's longest output (so the longest
        are compared) or its seeded draw falls under TRACK_SHARE."""
        import numpy as np
        live = [r for r in self.inflight if r.row is not None]
        free = sorted(set(range(self.rows)) - {r.row for r in live})
        for rec in firsts:
            draw = self._pick.random()
            if (free and not rec.req.done and rec.max_new > 1
                    and (rec.max_new >= self.long_min
                         or draw < TRACK_SHARE)):
                rec.row = free.pop(0)
                rec.slot = next(i for i, a in enumerate(self.eng.active)
                                if a is rec.req)
                live.append(rec)
        if not live:
            return
        idx = np.zeros(self.rows, np.int32)
        for rec in live:
            idx[rec.row] = rec.slot
            rec.kept.append(len(self.captures))
        self.captures.append(self._take(self.eng.last_logits, idx))

    def _busy(self) -> bool:
        return bool(self.eng.queue) or any(a is not None
                                           for a in self.eng.active)

    # -- the loop ----------------------------------------------------------
    def run(self, seconds: float, on_open=None, on_close=None,
            track: bool = True) -> None:
        """Warm-up, then `seconds` of traffic; an open loop then serves
        on (no new requests) until every request due in the window has
        its first token, for at most GRACE_S. `on_open`/`on_close` run at
        the window's edges (the profiler's start and stop). With `track`,
        the logits of a seeded subset of requests are kept throughout."""
        from bench.traffic import RequestStream
        t = self.cell.traffic
        self.seconds = seconds
        self.recs, self.inflight, self.steps = [], [], []
        self.captures, self.tracking = [], track
        stream = RequestStream(t, self.seed, self.cfg.vocab)
        if t["loop"] == "open":
            self._open_loop(stream, seconds, on_open)
        else:
            self._closed_loop(stream, seconds, on_open)
        if on_close is not None:
            on_close()
        if t["loop"] != "open":
            return      # a closed loop's client is not owed a first token
        owed = [r for r in self.recs if 0 <= r.due < seconds and
                r.req is not None and r.first_t is None]
        while owed and self._now() < seconds + GRACE_S and self._busy():
            self._step()
            owed = [r for r in owed if r.first_t is None]

    def _open_loop(self, stream, seconds, on_open) -> None:
        self.t_w0 = time.perf_counter() + float(
            self.cell.traffic.get("warm_s", 0.0))
        nxt = stream.next()
        opened = False
        while True:
            now = self._now()
            if now >= seconds:
                # requests that fell due during the window's last step
                # are due in the window too: they go in now, late
                while nxt[2] < seconds:
                    self._submit(nxt, nxt[2])
                    nxt = stream.next()
                return
            if not opened and now >= 0:
                opened = True
                if on_open is not None:
                    on_open()
            while nxt[2] <= now:
                self._submit(nxt, nxt[2])
                nxt = stream.next()
            if self._busy():
                self._step()
            else:
                time.sleep(max(0.0, min(nxt[2], seconds if opened else 0.0)
                               - now))

    def _closed_loop(self, stream, seconds, on_open) -> None:
        t = self.cell.traffic
        self.t_w0 = time.perf_counter()
        for _ in range(t["clients"]):
            self._submit(stream.next(), self._now())
        for _ in range(t.get("warm_steps", 0)):
            for _ in self._step():
                self._submit(stream.next(), self._now())
        shift = time.perf_counter() - self.t_w0
        self.t_w0 += shift
        for r in self.recs:
            r.due -= shift
            r.submit_t -= shift
            r.first_t = None if r.first_t is None else r.first_t - shift
            r.finish_t = None if r.finish_t is None else r.finish_t - shift
            r.token_times = [x - shift for x in r.token_times]
        for s in self.steps:
            s.t0 -= shift
            s.t1 -= shift
        if on_open is not None:
            on_open()
        while self._now() < seconds:
            for _ in self._step():
                if self._now() < seconds:
                    self._submit(stream.next(), self._now())

    # -- after the window --------------------------------------------------
    def in_window(self) -> list[Rec]:
        return [r for r in self.recs if 0 <= r.due < self.seconds]

    def sample(self) -> list[Rec]:
        """The requests `correct` compares, among the tracked ones the
        window finished: the one that served the most tokens, then others
        drawn from the seed, until the traffic's `check.tokens` served
        tokens or `check.max_requests`."""
        import numpy as np
        chk = self.cell.traffic["check"]
        done = [r for r in self.recs if r.row is not None
                and r.finish_t is not None and 0 <= r.finish_t < self.seconds]
        if not done:
            return []
        done.sort(key=lambda r: (-r.ntok, r.uid))
        pick = [done[0]]
        rest = done[1:]
        order = np.random.default_rng(self.seed).permutation(len(rest))
        for i in order:
            if (sum(r.ntok for r in pick) >= chk["tokens"]
                    or len(pick) >= chk["max_requests"]):
                break
            pick.append(rest[i])
        return pick

    def kept_logits(self, picked: list[Rec]) -> list:
        """The picked requests' kept logits, on the host (one (n - 1,
        vocab) array each, for tokens 1..n-1); frees the captures."""
        import numpy as np
        host = {}
        out = []
        for r in picked:
            if len(r.kept) != r.ntok - 1:
                raise RuntimeError(f"request {r.uid}: {len(r.kept)} logit "
                                   f"rows kept for {r.ntok} tokens")
            for ci in r.kept:
                if ci not in host:
                    host[ci] = np.asarray(self.captures[ci])
            out.append(np.stack([host[ci][r.row] for ci in r.kept]))
        self.captures = []
        return out


def percentile(xs, q):
    import numpy as np
    return float(np.percentile(np.asarray(xs, float), q))


def end_to_end(s: Serve, setup_s: float) -> dict:
    """The cell's end-to-end metrics, from the harness's host clock."""
    T = s.seconds
    win = s.in_window()
    ttft = [(r.first_t - r.due) * 1e3 if r.first_t is not None
            else (T + GRACE_S - r.due) * 1e3 for r in win]
    gaps = [(b - a) * 1e3 for r in s.recs
            for a, b in zip(r.token_times, r.token_times[1:]) if 0 <= b < T]
    toks = sum(1 for r in s.recs for t in r.token_times if 0 <= t < T)
    vals = {"setup_s": setup_s, "output_tok_s": toks / T}
    if ttft:
        vals["ttft_p95_ms"] = percentile(ttft, 95)
    if gaps:
        vals["itl_p50_ms"] = percentile(gaps, 50)
        vals["itl_p95_ms"] = percentile(gaps, 95)
    return vals


def compare(s: Serve, picked: list[Rec], kept: list,
            controls: tuple = ()) -> dict:
    """Teacher-force the picked requests' prompts and served tokens
    through the float32 reference; return the numbers compared: the
    served tokens' gaps below the reference's best, and how far the
    program's kept logits (`kept`, from `Serve.kept_logits`) lie from the
    reference's. For each control precision in `controls` ("int8",
    "fp8"), the same two readings of that control at the same rows
    (bench/reference.py)."""
    import jax.numpy as jnp
    import numpy as np
    from bench.reference import (Reference, control_gaps, logit_errors,
                                 served_gaps)
    if not picked:
        return {"compared_tokens": 0}
    seqs, rows, toks, later = [], [], [], []
    n = 0
    for r in picked:
        gen = np.asarray(r.req.generated, np.int32)
        seqs.append(np.concatenate([np.asarray(r.req.prompt, np.int32),
                                    gen[:-1]]))
        rows.append(np.arange(r.prompt_len - 1, r.prompt_len - 1 + len(gen)))
        toks.append(gen)
        later.append(np.arange(n + 1, n + len(gen)))   # tokens 1..n-1
        n += len(gen)
    later = jnp.asarray(np.concatenate(later))
    q_block = s.cell.traffic["check"]["ref_q_block"]
    logits = Reference(s.fam, s.cell.config, s.params, q_block).logits(
        seqs, rows)
    gaps = np.asarray(served_gaps(logits, jnp.asarray(np.concatenate(toks))))
    errs = np.asarray(logit_errors(logits[later],
                                   jnp.asarray(np.concatenate(kept))))
    found = {**readings(gaps, errs), "compared_tokens": int(gaps.size),
             "requests_short": sum(1 for r in picked
                                   if r.ntok != r.max_new),
             "finite": bool(np.isfinite(gaps).all()
                            and np.isfinite(errs).all())}
    for quant in controls:
        ctrl = Reference(s.fam, s.cell.config, s.params, q_block,
                         quant=quant).logits(seqs, rows)
        got = readings(np.asarray(control_gaps(logits, ctrl)),
                       np.asarray(logit_errors(logits[later], ctrl[later])))
        for k, v in got.items():
            found[f"control_{quant}_{k}"] = v
        del ctrl
    return found


def readings(gaps, errs) -> dict:
    """The numbers compared (the mean gap and the mean logit error), the
    widest of each, and the share of rows whose token is not the
    reference's best."""
    return {"gap_mean_std": float(gaps.mean()),
            "logit_err_std": float(errs.mean()),
            "gap_max_std": float(gaps.max()),
            "logit_err_max_std": float(errs.max()),
            "off_best_share": float((gaps > 0).mean())}


JUDGED = ("gap_mean_std", "logit_err_std")


def judge(found: dict, limits: dict) -> tuple[bool, dict]:
    """Each number compared beside its limit; correct when all hold."""
    checks = {k: (found.get(k, float("inf")), limits[k]) for k in JUDGED}
    checks["compared_tokens_min"] = (found["compared_tokens"],
                                     limits["compared_tokens_min"])
    checks["requests_short"] = (found.get("requests_short", 0), 0)
    ok = (found.get("finite", False)
          and all(checks[k][0] <= checks[k][1] for k in JUDGED)
          and checks["compared_tokens_min"][0]
          >= checks["compared_tokens_min"][1]
          and checks["requests_short"][0] == 0)
    return ok, {k: {"value": v, "limit": lim}
                for k, (v, lim) in checks.items()}


def limits_of(cell) -> dict:
    with open(os.path.join(ROOT, "bench", "limits",
                           cell.name + ".json")) as fh:
        return json.load(fh)


def execute(cell, seed: int, seconds: float, trace: bool,
            t_process: float, require_chip: bool = True,
            keep_trace: str | None = None,
            limits: dict | None = None) -> dict:
    """Everything a run does after its arguments are read. Tests call it
    with `require_chip=False` at a small size; the result then carries no
    device metric."""
    import jax
    from bench import device, tracing
    dev = device.describe(cell.chips, require_chip)
    compiles = device.CompileCounter()
    s = Serve(cell, seed, trace)
    s.compile_drain()
    prof = tracing.Profile() if trace and require_chip else None
    counted = {}

    def on_open():
        counted["compiles"] = compiles.n
        if prof is not None:
            prof.start()

    def on_close():
        jax.block_until_ready(s.eng.last_logits)
        counted["compiles"] = compiles.n - counted["compiles"]
        if prof is not None:
            prof.stop()

    s.run(seconds, on_open, on_close)
    setup_s = s.t_w0 - t_process
    win = s.in_window()
    failed = sum(1 for r in win if r.req is None or (
        cell.traffic["loop"] == "open" and r.first_t is None))
    if require_chip:
        dev["memory_peak_bytes"] = device.peak_bytes()
    picked = s.sample()
    ctx = None
    if trace:
        ctx = tracing.context(s, counted["compiles"], dev, prof,
                              keep_trace)
    eng_stats = s.eng.stats()
    kept = s.kept_logits(picked)
    del s.eng, s.telemetry
    gc.collect()
    found = compare(s, picked, kept)
    ok, checks = judge(found, limits or limits_of(cell))
    out = {"correct": ok, "attempted": len(win), "failed": failed}
    if trace:
        metrics, breakdown = tracing.per_layer(cell, ctx)
        out["metrics"] = metrics
        if prof is not None:
            dev["busy_s"] = ctx.busy_s
            dev["window_s"] = ctx.window_s
            out["breakdown"] = breakdown
            prof.cleanup()
    else:
        e2e = end_to_end(s, setup_s)
        out["metrics"] = {m["name"]: {"value": e2e[m["name"]],
                                      "unit": m["unit"]}
                          for m in cell.end_to_end if m["name"] in e2e}
    out["device"] = dev
    out["engine"] = {k: eng_stats[k] for k in ("prefill_tokens",
                                               "peak_pages", "tokens")}
    out["readings"] = {k: found[k] for k in ("gap_max_std",
                                             "logit_err_max_std",
                                             "off_best_share") if k in found}
    out["checks"] = checks
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__,
                                 formatter_class=argparse.RawTextHelpFormatter)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", metavar="PATH",
                    help="also write the reduced trace (gzip JSON) here")
    args = ap.parse_args()
    from bench import device, spec
    try:
        cell = spec.load_cell(args.workload)
    except spec.SpecError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    device.enable_compile_cache()
    try:
        out = execute(cell, args.seed, args.seconds, bool(args.trace),
                      T_PROCESS, keep_trace=args.keep_trace)
    except device.NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return EXIT_NO_CHIP
    for k, v in out["checks"].items():
        print(f"check {k}: {v['value']} (limit {v['limit']})",
              file=sys.stderr)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
