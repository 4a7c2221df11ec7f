"""OLMoE on the serving path: the grouped expert GEMV kernel, the dropless
expert layer, and the paged engine against the benchmark's plain f32
reference (`bench/models/olmoe.py`) at the smoke size.

The smoke config (2 layers, d 64, 8 experts top-2, QK-norm, no top-k
renormalisation, f32) sets `router_cap_factor` 64 for training's GShard
path; every engine here sets it back to the published 1.25, so nothing
below leans on it: serving's dropless layer never drops.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, get_config
from repro.core.lut import LutBank
from repro.core.salpim import SalPimConfig, SalPimEngine
from repro.kernels import ops
from repro.kernels.expert_gemv import ROW_TILE
from repro.models import api
from repro.models import attention as attn_lib
from repro.models.moe import apply_moe_dropless
from repro.serving import EngineConfig, GenConfig, ServingEngine
from repro.serving.speculative import SpecConfig
from repro.serving.telemetry import Telemetry
from tolerances import f32_reorder_tol

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from bench import weights  # noqa: E402
from bench.reference import Reference  # noqa: E402

ENGINE = SalPimEngine.create(SalPimConfig())
GREEDY = GenConfig(temperature=0.0, stop_on_eos=False)


def _olmoe_module():
    path = os.path.join(REPO, "bench", "models", "olmoe.py")
    spec = importlib.util.spec_from_file_location("bench_family_olmoe", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


OLMOE = _olmoe_module()


def _ref_config(cfg) -> dict:
    """The reference's configuration keys for a program config."""
    return {"num_hidden_layers": cfg.n_layers, "hidden_size": cfg.d_model,
            "num_attention_heads": cfg.n_heads,
            "num_key_value_heads": cfg.n_kv_heads,
            "intermediate_size": cfg.moe_d_ff, "vocab_size": cfg.vocab,
            "num_experts": cfg.n_experts,
            "num_experts_per_tok": cfg.top_k,
            "norm_topk_prob": cfg.norm_topk_prob,
            "rms_norm_eps": cfg.norm_eps, "rope_theta": cfg.rope_theta,
            "max_position_embeddings": cfg.max_seq}


@pytest.fixture(scope="module")
def smoke():
    """(program config, reference config, seeded random weights in the
    benchmark's layout, f32)."""
    cfg = dataclasses.replace(get_config("olmoe_1b_7b", smoke=True),
                              router_cap_factor=1.25)
    c = _ref_config(cfg)
    params = weights.make(OLMOE.layout(c), 2**33 + 7, jnp.float32)
    weights.check_layout(params, jax.eval_shape(
        lambda: api.init_params(jax.random.PRNGKey(0), cfg)))
    return cfg, c, params


def _engine(params, cfg, slots, chunk=8, telemetry=None, **kw):
    return ServingEngine(params, cfg, ENGINE, EngineConfig(
        slots=slots, max_len=64, page_size=8,
        prefill_chunk_tokens=chunk, gen=GREEDY, telemetry=telemetry, **kw))


def _serve_tracked(params, cfg, prompts, track, slots, new=6, **kw):
    """Serve `prompts`; return the logits the engine sampled each token
    of request `track` from, (new, vocab), and its tokens."""
    eng = _engine(params, cfg, slots, **kw)
    uids = [eng.submit(p, max_new_tokens=new) for p in prompts]
    rows, req = [], None
    while eng.queue or any(a is not None for a in eng.active):
        for i, a in enumerate(eng.active):
            if a is not None and a.uid == uids[track] and not a.prefilling:
                rows.append(np.asarray(eng.last_logits[i]))
                req = a
        eng.step()
    return np.stack(rows), req


def _prompts(n, seed=0, vocab=512):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, vocab, int(k)).astype(np.int32)
            for k in rng.integers(5, 24, n)]


# ---------------------------------------------------------------------------
# The kernel
# ---------------------------------------------------------------------------

def _groups(sizes):
    sizes = np.asarray(sizes, np.int32)
    padded = -(-sizes // ROW_TILE) * ROW_TILE
    offs = np.cumsum(padded) - padded
    return jnp.asarray(offs, jnp.int32), jnp.asarray(sizes), \
        int(padded.sum()) + ROW_TILE


ROUTINGS = {
    "all_rows_to_one_expert": [0, 0, 37, 0],
    "empty_experts": [9, 0, 0, 16],
    "ragged_groups": [5, 17, 1, 3],
}


@pytest.mark.parametrize("routing", ROUTINGS)
@pytest.mark.parametrize("form", ["gate_up", "down", "gate_up_lut"])
def test_expert_gemv_interpret_matches_reference(routing, form):
    """The kernel in interpret mode against the jnp oracle: skewed
    routing, empty experts, groups that do not fill a row tile. Rows
    outside every group are random here and must read 0."""
    offs, sizes, M = _groups(ROUTINGS[routing])
    E, R, C = 4, 256, 128
    k = jax.random.split(jax.random.PRNGKey(3), 3)
    w = jax.random.normal(k[0], (E, R, C)) * C ** -0.5
    w_up = (jax.random.normal(k[1], (E, R, C)) * C ** -0.5
            if form != "down" else None)
    x = jax.random.normal(k[2], (M, C))
    table = LutBank.create(64).silu if form == "gate_up_lut" else None
    got, want = (ops.pim_expert_gemv(
        x, w, offs, sizes, w_up, max_rows=max(ROUTINGS[routing]),
        act_table=table, impl=impl) for impl in ("interpret", "reference"))
    # f32 products summed over C = 128 in different orders
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    mine = np.zeros(M, bool)
    for o, n in zip(np.asarray(offs), np.asarray(sizes)):
        mine[o:o + n] = True
    assert not np.asarray(got)[~mine].any()


# ---------------------------------------------------------------------------
# The dropless layer
# ---------------------------------------------------------------------------

def test_empty_slots_add_no_assignments(smoke):
    """Rows marked dead route to no expert: one live row hits exactly
    top_k experts, the dead rows' outputs are 0, and the live row's
    output is what it is with every row live."""
    cfg, _, params = smoke
    moe = jax.tree.map(lambda a: a[0], params["blocks"]["moe"])
    x = jax.random.normal(jax.random.PRNGKey(5), (6, cfg.d_model))
    live = jnp.zeros((6,), bool).at[2].set(True)
    out, hit = apply_moe_dropless(moe, x, cfg, ENGINE, live)
    full, hit_all = apply_moe_dropless(moe, x, cfg, ENGINE)
    assert int(hit) == cfg.top_k
    assert cfg.top_k <= int(hit_all) <= cfg.n_experts
    assert not np.asarray(out)[np.arange(6) != 2].any()
    np.testing.assert_array_equal(np.asarray(out[2]), np.asarray(full[2]))


def test_dropless_layer_matches_reference_moe(smoke):
    """The dropless layer against the reference's loop over every expert
    on a batch big enough that capacity dispatch at 1.25 would drop."""
    cfg, c, params = smoke
    moe = jax.tree.map(lambda a: a[0], params["blocks"]["moe"])
    x = jax.random.normal(jax.random.PRNGKey(6), (40, cfg.d_model))
    got, _ = apply_moe_dropless(moe, x, cfg, ENGINE)
    with jax.default_matmul_precision("highest"):
        want = OLMOE._moe(moe, x, c)
    tol = f32_reorder_tol(cfg)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=tol, atol=tol)


# ---------------------------------------------------------------------------
# The engine against the reference
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("chunk", [8, 64])
def test_engine_matches_reference_prefill_then_decode(smoke, chunk):
    """Chunked paged prefill (8-token chunks: every prompt takes two or
    three) or one-shot, then decoding through the cache, against the
    reference's full forward over prompt + served tokens. Both sides are
    f32 and differ only in summation order, so every logit agrees to
    `f32_reorder_tol`; a wrong page, position, norm or expert moves
    logits by O(1)."""
    cfg, c, params = smoke
    prompts = _prompts(3, seed=1)
    for track in range(len(prompts)):
        got, req = _serve_tracked(params, cfg, prompts, track, slots=4,
                                  chunk=chunk)
        seq = np.concatenate([req.prompt, req.generated[:-1]]).astype(
            np.int32)
        rows = np.arange(len(req.prompt), len(seq))   # tokens 1 .. n-1
        want = Reference(OLMOE, c, params, 32).logits([seq], [rows])
        tol = f32_reorder_tol(cfg) * float(np.abs(want).max())
        np.testing.assert_allclose(got, np.asarray(want), rtol=0, atol=tol)


def test_request_logits_do_not_depend_on_batch_mates(smoke):
    """A request's logits are bit-identical served alone (with empty
    slots) and among 7 other requests, which run ahead of it: nothing
    is dropped, so a row's experts and their weights are its own.
    GShard's capacity dispatch at factor 1.25 fails this."""
    cfg, _, params = smoke
    target = _prompts(1, seed=2)[0]
    others = _prompts(7, seed=3)
    alone, _ = _serve_tracked(params, cfg, [target], 0, slots=10, chunk=64)
    crowded, _ = _serve_tracked(params, cfg, others + [target], 7, slots=10,
                                chunk=64)
    np.testing.assert_array_equal(alone, crowded)


def test_speculative_verify_serves_moe_bit_identically(smoke):
    """The verify pass routes through the dropless layer too: greedy
    outputs with n-gram speculation equal plain decoding's."""
    cfg, _, params = smoke
    prompts = [np.tile(p[:4], 4) for p in _prompts(3, seed=4)]

    def drain(**kw):
        eng = _engine(params, cfg, 3, **kw)
        uids = [eng.submit(p, max_new_tokens=8) for p in prompts]
        done = {r.uid: r.generated for r in eng.run(max_steps=400)}
        return [done[u] for u in uids]

    assert drain(speculative=SpecConfig(mode="ngram", k=3)) == drain()


# ---------------------------------------------------------------------------
# Telemetry
# ---------------------------------------------------------------------------

def test_moe_counters_on_a_hand_built_batch(smoke):
    """One request alone: every decode step that carries it hits exactly
    top_k experts in each layer. Then two: assignments are live rows x
    top_k x layers, and the hits lie between one row's and two rows'."""
    cfg, _, params = smoke
    L, E, k = cfg.n_layers, cfg.n_experts, cfg.top_k
    new = 6
    tel = Telemetry(enabled=True)
    eng = _engine(params, cfg, 4, telemetry=tel)
    eng.submit(_prompts(1, seed=5)[0], max_new_tokens=new)
    eng.run()
    got = tel.snapshot()["counters"]
    steps = new - 1              # decode steps with the request live
    assert got["moe.assignments"] == steps * k * L
    assert got["moe.experts_hit"] == steps * k * L
    assert got["moe.experts_held"] == new * L * E   # and the last, empty

    tel = Telemetry(enabled=True)
    eng = _engine(params, cfg, 4, chunk=64, telemetry=tel)
    for p in _prompts(2, seed=6):
        eng.submit(p, max_new_tokens=new)
    eng.run()
    got = tel.snapshot()["counters"]
    assert got["moe.assignments"] == 2 * steps * k * L
    assert steps * k * L <= got["moe.experts_hit"] <= 2 * steps * k * L


# ---------------------------------------------------------------------------
# Configuration and layout
# ---------------------------------------------------------------------------

def test_olmoe_config_is_the_published_one():
    """QK-norm on, RMSNorm eps 1e-5, top-8 weights not renormalised, the
    router stored in the param dtype; the benchmark's layout is the
    program's parameter tree at the published widths."""
    cfg = get_config("olmoe_1b_7b")
    assert (cfg.qk_norm, cfg.norm_eps, cfg.norm_topk_prob) == (True, 1e-5,
                                                               False)
    assert (cfg.n_experts, cfg.top_k, cfg.moe_d_ff) == (64, 8, 1024)
    shapes = jax.eval_shape(lambda: api.init_params(jax.random.PRNGKey(0),
                                                    cfg))
    assert shapes["blocks"]["moe"]["router"].dtype == jnp.bfloat16
    c = dict(_ref_config(cfg), num_hidden_layers=cfg.n_layers)
    assert OLMOE.program_config(c) == dataclasses.replace(
        cfg, max_seq=c["max_position_embeddings"])
    made = jax.eval_shape(lambda: weights.make(OLMOE.layout(c), 1,
                                               jnp.bfloat16))
    weights.check_layout(made, shapes)


DENSE = [a for a in ARCHS if get_config(a, smoke=True).family == "dense"]


@pytest.mark.parametrize("arch", DENSE)
def test_qk_norm_off_leaves_dense_projections_bit_identical(arch):
    """Off (every config but OLMoE's), QK-norm adds no parameter and the
    attention's q, k and v are the plain projections, bit for bit, on
    both the sequence and the decode paths."""
    cfg = get_config(arch, smoke=True)
    assert not cfg.qk_norm
    p = api.init_params(jax.random.PRNGKey(0), cfg)["blocks"]["attn"]
    p = jax.tree.map(lambda a: a[0], p)
    assert "q_norm" not in p and "k_norm" not in p
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 3, cfg.d_model))
    q, k, v = attn_lib._project_qkv(p, x, cfg, ENGINE)
    for got, w, b in ((q, "wq", "bq"), (k, "wk", "bk"), (v, "wv", "bv")):
        want = ENGINE.linear(x, p[w], p.get(b))
        np.testing.assert_array_equal(np.asarray(got).reshape(want.shape),
                                      np.asarray(want))
    qd, kd, _ = attn_lib._decode_qkv(p, x[:, 0], cfg, ENGINE, None, None)
    np.testing.assert_array_equal(
        np.asarray(qd).reshape(2, -1),
        np.asarray(ENGINE.linear(x[:, 0], p["wq"], p.get("bq"))))
    np.testing.assert_array_equal(
        np.asarray(kd).reshape(2, -1),
        np.asarray(ENGINE.linear(x[:, 0], p["wk"], p.get("bk"))))
