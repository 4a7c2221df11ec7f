"""Qwen2: its mapping onto the program, its weights, its plain reference.

The reference follows the published Qwen2 (arXiv:2407.10671; the
`Qwen2ForCausalLM` of Qwen/Qwen2-1.5B): RMSNorm pre-norm blocks, grouped-
query attention (each key/value head serves num_attention_heads /
num_key_value_heads consecutive query heads) with biased q/k/v
projections and an unbiased output projection, rotary position
embeddings in the rotate-half form with base `rope_theta`, a SwiGLU MLP
(down(silu(gate(x)) * up(x))), a final RMSNorm and the LM head.
Straightforward `jax.numpy` in float32 at `highest` matmul precision,
one sequence at a time, no kernels, no cache, no batching.

Departure, as the program runs the model (listed under `assumed` in the
configuration file): the LM head is its own matrix, not the tied token
embedding. Both sides use the same weights, so both run the same model.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from bench.weights import Leaf


def program_config(c: dict):
    from repro.models.config import ModelConfig
    return ModelConfig(
        name="qwen2-1.5b", family="dense",
        n_layers=c["num_hidden_layers"], d_model=c["hidden_size"],
        n_heads=c["num_attention_heads"],
        n_kv_heads=c["num_key_value_heads"], d_ff=c["intermediate_size"],
        vocab=c["vocab_size"],
        head_dim=c["hidden_size"] // c["num_attention_heads"],
        qkv_bias=True, rope_theta=c["rope_theta"], activation="silu",
        gated_mlp=True, norm="rmsnorm", norm_eps=c["rms_norm_eps"],
        tie_embeddings=False, max_seq=c["max_position_embeddings"])


def layout(c: dict) -> dict:
    L, d, V = c["num_hidden_layers"], c["hidden_size"], c["vocab_size"]
    f = c["intermediate_size"]
    Dh = d // c["num_attention_heads"]
    nq, nkv = d, c["num_key_value_heads"] * Dh

    def norm(*lead):
        return {"g": Leaf((*lead, d), 0.1, 1.0)}

    return {
        "embed": Leaf((V, d), 0.02),
        "final_norm": norm(),
        "lm_head": Leaf((V, d), d ** -0.5),
        "blocks": {
            "ln1": norm(L),
            "attn": {"wq": Leaf((L, nq, d), d ** -0.5),
                     "wk": Leaf((L, nkv, d), d ** -0.5),
                     "wv": Leaf((L, nkv, d), d ** -0.5),
                     "wo": Leaf((L, d, nq), nq ** -0.5),
                     "bq": Leaf((L, nq), 0.02), "bk": Leaf((L, nkv), 0.02),
                     "bv": Leaf((L, nkv), 0.02)},
            "ln2": norm(L),
            "ffn": {"w_up": Leaf((L, f, d), d ** -0.5),
                    "w_gate": Leaf((L, f, d), d ** -0.5),
                    "w_down": Leaf((L, d, f), f ** -0.5)},
        },
    }


def _f32(a):
    return a.astype(jnp.float32)


def _rmsnorm(x, p, eps):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * _f32(p["g"])


def _rope(x, pos, theta):
    """x (S, heads, Dh) rotated by position; rotate-half pairing."""
    Dh = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, Dh, 2, dtype=jnp.float32) / Dh)
    ang = pos[:, None].astype(jnp.float32) * inv[None]       # (S, Dh/2)
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[:, None]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[:, None]
    x1, x2 = x[..., :Dh // 2], x[..., Dh // 2:]
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def embed(p: dict, tokens, c: dict):
    return _f32(p["embed"])[tokens]


def layer(lp: dict, x, c: dict, q_block: int):
    """One block over the whole sequence x (S, d), S % q_block == 0."""
    S, d = x.shape
    H, Hkv = c["num_attention_heads"], c["num_key_value_heads"]
    Dh = d // H
    eps = c["rms_norm_eps"]
    pos = jnp.arange(S)
    a = lp["attn"]
    h = _rmsnorm(x, lp["ln1"], eps)
    q = (h @ _f32(a["wq"]).T + _f32(a["bq"])).reshape(S, H, Dh)
    k = (h @ _f32(a["wk"]).T + _f32(a["bk"])).reshape(S, Hkv, Dh)
    v = (h @ _f32(a["wv"]).T + _f32(a["bv"])).reshape(S, Hkv, Dh)
    q = _rope(q, pos, c["rope_theta"])
    k = _rope(k, pos, c["rope_theta"])
    k = jnp.repeat(k, H // Hkv, axis=1)
    v = jnp.repeat(v, H // Hkv, axis=1)

    def attend(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * q_block, q_block)
        s = jnp.einsum("qhd,khd->hqk", qb, k) / math.sqrt(Dh)
        qpos = i * q_block + jnp.arange(q_block)
        s = jnp.where(pos[None, None] <= qpos[None, :, None], s, -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, -1), v)

    att = jax.lax.map(attend, jnp.arange(S // q_block)).reshape(S, d)
    x = x + att @ _f32(a["wo"]).T
    h = _rmsnorm(x, lp["ln2"], eps)
    f = lp["ffn"]
    return x + (jax.nn.silu(h @ _f32(f["w_gate"]).T)
                * (h @ _f32(f["w_up"]).T)) @ _f32(f["w_down"]).T


def head(p: dict, x, c: dict):
    return _rmsnorm(x, p["final_norm"], c["rms_norm_eps"]) \
        @ _f32(p["lm_head"]).T
