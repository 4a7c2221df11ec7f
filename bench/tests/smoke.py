"""Smoke-size copies of the benchmark's cells, for tests on the CPU.

Each cell keeps its family, loop and engine options, with widths, depth,
vocabulary, lengths and slots cut so that a window of a few seconds on
the CPU backend serves and finishes requests. Nothing here is measured.
"""
from __future__ import annotations

import copy

from bench import spec

CONFIGS = {
    "gpt2": {"n_layer": 2, "n_embd": 64, "n_head": 4, "vocab_size": 512,
             "n_positions": 256},
    "qwen2": {"num_hidden_layers": 2, "hidden_size": 64,
              "num_attention_heads": 4, "num_key_value_heads": 2,
              "intermediate_size": 160, "vocab_size": 512},
}

# Wider and deeper, for the control test: at the smallest size float8
# rounding moves the logits too little to show against a limit set at
# the published widths.
CONTROL_CONFIGS = {
    "gpt2": {"n_layer": 8, "n_embd": 128, "n_head": 4, "vocab_size": 4096},
    "qwen2": {"num_hidden_layers": 8, "hidden_size": 128,
              "num_attention_heads": 4, "num_key_value_heads": 2,
              "intermediate_size": 352, "vocab_size": 4096},
}

TRAFFIC = {
    "open": {"rate_rps": 12.0, "warm_s": 0.5,
             "prompt_len": {"choice": [8, 16, 32]},
             "output_len": {"loguniform": [1, 24]}},
    "closed": {"warm_steps": 2,
               "prompt_len": {"loguniform": [16, 64], "round_up": 16},
               "output_len": {"loguniform": [4, 24]}},
}

def limits(c: spec.Cell) -> dict:
    """The cell's own limits; a smoke window compares fewer tokens."""
    from bench import run
    return dict(run.limits_of(c), compared_tokens_min=20)


def cell(name: str, tokens: int = 60, control: bool = False) -> spec.Cell:
    c = copy.deepcopy(spec.load_cell(name))
    fam = c.config["bench"]["family"]
    c.config.update(CONFIGS[fam])
    if control:
        c.config.update(CONTROL_CONFIGS[fam])
    t = c.traffic
    t.update(TRAFFIC[t["loop"]])
    if t["loop"] == "closed":
        t["clients"] = min(t["clients"], 3)
    e = t["engine"]
    e.update(slots=min(e["slots"], 3), max_len=96, page_size=8,
             prefill_chunk_tokens=16)
    t["check"] = dict(t["check"], tokens=tokens, max_requests=12,
                      ref_q_block=32)
    return c
