"""GPT-2: its mapping onto the program, its weights, its plain reference.

The reference follows the published GPT-2 (Radford et al. 2019; the
`GPT2LMHeadModel` of openai-community/gpt2-medium): learned positions
added to the token embedding, pre-LayerNorm blocks, causal multi-head
attention with biased q/k/v projections, a GELU (tanh form, `gelu_new`)
MLP, a final LayerNorm and the LM head. Straightforward `jax.numpy` in
float32 at `highest` matmul precision, one sequence at a time, no
kernels, no cache, no batching.

Departures, as the program runs the model (the configuration file lists
them under `assumed`): the attention output projection and the two MLP
projections carry no bias, and the LM head is its own matrix and not
the token embedding. Both sides use the same weights, so both run the
same model.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from bench.weights import Leaf


def program_config(c: dict):
    from repro.models.config import ModelConfig
    d = c["n_embd"]
    return ModelConfig(
        name="gpt2-medium", family="dense",
        n_layers=c["n_layer"], d_model=d, n_heads=c["n_head"],
        n_kv_heads=c["n_head"], d_ff=c["n_inner"] or 4 * d,
        vocab=c["vocab_size"], head_dim=d // c["n_head"],
        qkv_bias=True, learned_pos_emb=True, activation="gelu",
        gated_mlp=False, norm="layernorm", norm_eps=c["layer_norm_epsilon"],
        max_seq=c["n_positions"], tie_embeddings=False)


def layout(c: dict) -> dict:
    """Leaves in the program's layout: stacked blocks, (out, in) weights."""
    L, d, V = c["n_layer"], c["n_embd"], c["vocab_size"]
    f = c["n_inner"] or 4 * d

    def norm(*lead):
        return {"g": Leaf((*lead, d), 0.1, 1.0), "b": Leaf((*lead, d), 0.1)}

    return {
        "embed": Leaf((V, d), 0.02),
        "pos_embed": Leaf((c["n_positions"], d), 0.02),
        "final_norm": norm(),
        "lm_head": Leaf((V, d), d ** -0.5),
        "blocks": {
            "ln1": norm(L),
            "attn": {"wq": Leaf((L, d, d), d ** -0.5),
                     "wk": Leaf((L, d, d), d ** -0.5),
                     "wv": Leaf((L, d, d), d ** -0.5),
                     "wo": Leaf((L, d, d), d ** -0.5),
                     "bq": Leaf((L, d), 0.02), "bk": Leaf((L, d), 0.02),
                     "bv": Leaf((L, d), 0.02)},
            "ln2": norm(L),
            "ffn": {"w_up": Leaf((L, f, d), d ** -0.5),
                    "w_down": Leaf((L, d, f), f ** -0.5)},
        },
    }


def _f32(a):
    return a.astype(jnp.float32)


def _layernorm(x, p, eps):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * _f32(p["g"]) + _f32(p["b"])


def _gelu_new(x):
    return 0.5 * x * (1.0 + jnp.tanh(math.sqrt(2.0 / math.pi)
                                     * (x + 0.044715 * x ** 3)))


def embed(p: dict, tokens, c: dict):
    """tokens (S,) -> hidden (S, d) float32."""
    pos = jnp.arange(tokens.shape[0])
    return _f32(p["embed"])[tokens] + _f32(p["pos_embed"])[pos]


def layer(lp: dict, x, c: dict, q_block: int):
    """One block over the whole sequence x (S, d), S % q_block == 0.

    Queries go q_block rows at a time, so the (heads, rows, S) scores of
    a long sequence need not all be live at once."""
    S, d = x.shape
    H = c["n_head"]
    Dh = d // H
    eps = c["layer_norm_epsilon"]
    a = lp["attn"]
    h = _layernorm(x, lp["ln1"], eps)
    q = (h @ _f32(a["wq"]).T + _f32(a["bq"])).reshape(S, H, Dh)
    k = (h @ _f32(a["wk"]).T + _f32(a["bk"])).reshape(S, H, Dh)
    v = (h @ _f32(a["wv"]).T + _f32(a["bv"])).reshape(S, H, Dh)

    def attend(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * q_block, q_block)
        s = jnp.einsum("qhd,khd->hqk", qb, k) / math.sqrt(Dh)
        qpos = i * q_block + jnp.arange(q_block)
        s = jnp.where(jnp.arange(S)[None, None] <= qpos[None, :, None], s,
                      -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, -1), v)

    att = jax.lax.map(attend, jnp.arange(S // q_block)).reshape(S, d)
    x = x + att @ _f32(a["wo"]).T
    h = _layernorm(x, lp["ln2"], eps)
    f = lp["ffn"]
    return x + _gelu_new(h @ _f32(f["w_up"]).T) @ _f32(f["w_down"]).T


def head(p: dict, x, c: dict):
    """Final norm and LM head on rows x (n, d) -> logits (n, V)."""
    return _layernorm(x, p["final_norm"], c["layer_norm_epsilon"]) \
        @ _f32(p["lm_head"]).T
