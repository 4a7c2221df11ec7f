"""Engine loop: device-idle time under the step's host bookkeeping, per
engine step, ms.

As `token_sync_idle_ms`, for the `admit`, `prefill_prep`,
`prefill_commit`, `emit` and `page_map` spans of `ServingEngine.step`
(serving/engine.py): admission, chunk set-up, chunk activation,
releasing tokens and finished slots, and mapping the next write's
pages. A trace without these spans reads nothing."""
from bench import spec

PHASES = ("admit", "prefill_prep", "prefill_commit", "emit", "page_map")


def read(ctx):
    return spec.metric_reader("token_sync_idle_ms").idle_ms_per_step(
        ctx, PHASES)
