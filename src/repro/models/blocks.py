"""Decoder/encoder blocks: norm wiring, residuals, per-family dispatch."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.salpim import SalPimEngine
from repro.models import attention as attn_lib
from repro.models import ffn as ffn_lib
from repro.models import moe as moe_lib
from repro.models.config import ModelConfig

Array = jax.Array


def init_norm(cfg: ModelConfig, d: int | None = None) -> dict:
    d = d or cfg.d_model
    if cfg.norm == "layernorm":
        return {"g": jnp.ones((d,), cfg.pdtype), "b": jnp.zeros((d,), cfg.pdtype)}
    if cfg.norm == "rmsnorm_plus1":  # gemma: store (weight), apply 1 + w
        return {"g": jnp.zeros((d,), cfg.pdtype)}
    return {"g": jnp.ones((d,), cfg.pdtype)}


def apply_norm(p: dict, x: Array, cfg: ModelConfig, engine: SalPimEngine) -> Array:
    if cfg.norm == "layernorm":
        return engine.layernorm(x, p["g"], p["b"], cfg.norm_eps)
    if cfg.norm == "rmsnorm_plus1":
        return engine.rmsnorm(x, p["g"], cfg.norm_eps, plus_one=True)
    return engine.rmsnorm(x, p["g"], cfg.norm_eps)


# ---------------------------------------------------------------------------
# Dense / MoE decoder block
# ---------------------------------------------------------------------------

def init_decoder_block(key, cfg: ModelConfig) -> dict:
    k1, k2 = jax.random.split(key)
    p = {
        "ln1": init_norm(cfg),
        "attn": attn_lib.init_attention(k1, cfg),
        "ln2": init_norm(cfg),
    }
    if cfg.family == "moe":
        p["moe"] = moe_lib.init_moe(k2, cfg)
    else:
        p["ffn"] = ffn_lib.init_ffn(k2, cfg)
    if cfg.post_norms:
        p["post_ln1"] = init_norm(cfg)
        p["post_ln2"] = init_norm(cfg)
    return p


def apply_decoder_block(
    p: dict, x: Array, cfg: ModelConfig, engine: SalPimEngine, *,
    cos: Array | None, sin: Array | None, window,
) -> Array:
    h = apply_norm(p["ln1"], x, cfg, engine)
    h = attn_lib.attention_fullseq(
        p["attn"], h, cfg, engine, cos=cos, sin=sin, window=window,
        causal=cfg.causal)
    if cfg.post_norms:
        h = apply_norm(p["post_ln1"], h, cfg, engine)
    x = x + h
    h = apply_norm(p["ln2"], x, cfg, engine)
    h = (moe_lib.apply_moe(p["moe"], h, cfg, engine) if cfg.family == "moe"
         else ffn_lib.apply_ffn(p["ffn"], h, cfg, engine))
    if cfg.post_norms:
        h = apply_norm(p["post_ln2"], h, cfg, engine)
    return x + h


def _prefill_block_skeleton(p, x, cfg, engine, attn_fn):
    """Shared prefill block: norm/attn/residual/ffn around `attn_fn`,
    which maps the normed hidden to (attn_out, (k, v)) for the cache."""
    h = apply_norm(p["ln1"], x, cfg, engine)
    h, (ck, cv) = attn_fn(h)
    if cfg.post_norms:
        h = apply_norm(p["post_ln1"], h, cfg, engine)
    x = x + h
    h = apply_norm(p["ln2"], x, cfg, engine)
    h = (moe_lib.apply_moe(p["moe"], h, cfg, engine) if cfg.family == "moe"
         else ffn_lib.apply_ffn(p["ffn"], h, cfg, engine))
    if cfg.post_norms:
        h = apply_norm(p["post_ln2"], h, cfg, engine)
    return x + h, (ck, cv)


def apply_decoder_block_prefill(
    p: dict, x: Array, cfg: ModelConfig, engine: SalPimEngine, *,
    cos, sin, window,
):
    """Like apply_decoder_block but also returns (k, v) for the cache."""
    return _prefill_block_skeleton(
        p, x, cfg, engine,
        lambda h: attn_lib.attention_fullseq(
            p["attn"], h, cfg, engine, cos=cos, sin=sin, window=window,
            causal=cfg.causal, return_kv=True))


def apply_decoder_block_prefill_chunk_paged(
    p: dict, x: Array, k_pages: Array, v_pages: Array, block_tables: Array,
    start: Array, length: Array, cfg: ModelConfig, engine: SalPimEngine, *,
    cos, sin, window, kv_scales=None,
):
    """Prefill block over one prompt chunk against the paged pool: the
    chunk's K/V is written directly into pool pages and its queries read
    all resident KV back through the block table (chunked paged prefill).
    The speculative verify pass (transformer.verify_tokens) runs this
    same block on its k+1 candidate tokens — a verify chunk at decode
    time is indistinguishable from a prompt chunk at this level.
    Returns (x', k_pages', v_pages'[, k_scale', v_scale'] — the scale
    pools ride along in int8-KV mode)."""
    ksc, vsc = kv_scales if kv_scales is not None else (None, None)
    return _decode_block_skeleton(
        p, x, cfg, engine,
        lambda h: attn_lib.attention_prefill_chunk_paged(
            p["attn"], h, k_pages, v_pages, block_tables, start, length,
            cfg, engine, cos=cos, sin=sin, window=window,
            k_scale=ksc, v_scale=vsc))


def _decode_block_skeleton(p, x, cfg, engine, attn_fn):
    """Shared single-token block: norm/attn/residual/ffn around `attn_fn`,
    which maps the normed hidden to (attn_out, *cache_outputs)."""
    h = apply_norm(p["ln1"], x, cfg, engine)
    res = attn_fn(h)
    h, cache_out = res[0], res[1:]
    if cfg.post_norms:
        h = apply_norm(p["post_ln1"], h, cfg, engine)
    x = x + h
    h = apply_norm(p["ln2"], x, cfg, engine)
    h = (moe_lib.apply_moe(p["moe"], h, cfg, engine) if cfg.family == "moe"
         else ffn_lib.apply_ffn(p["ffn"], h, cfg, engine))
    if cfg.post_norms:
        h = apply_norm(p["post_ln2"], h, cfg, engine)
    return (x + h, *cache_out)


def apply_decoder_block_decode_paged(
    p: dict, x: Array, k_pages: Array, v_pages: Array, block_tables: Array,
    lengths: Array, cfg: ModelConfig, engine: SalPimEngine, *, cos, sin,
    window, kv_scales=None,
):
    """Single-token step against a paged cache. Returns (x', k', v'
    [, k_scale', v_scale'] — scale pools ride along in int8-KV mode)."""
    ksc, vsc = kv_scales if kv_scales is not None else (None, None)
    return _decode_block_skeleton(
        p, x, cfg, engine,
        lambda h: attn_lib.attention_decode_paged(
            p["attn"], h, k_pages, v_pages, block_tables, lengths, cfg,
            engine, cos=cos, sin=sin, window=window,
            k_scale=ksc, v_scale=vsc))


def apply_decoder_block_decode(
    p: dict, x: Array, cache_k: Array, cache_v: Array, lengths: Array,
    cfg: ModelConfig, engine: SalPimEngine, *, cos, sin, window,
    kv_scales=None,
):
    """Single-token step. x (B, D). Returns (x', k', v'[, scales])."""
    return _decode_block_skeleton(
        p, x, cfg, engine,
        lambda h: attn_lib.attention_decode(
            p["attn"], h, cache_k, cache_v, lengths, cfg, engine,
            cos=cos, sin=sin, window=window, kv_scales=kv_scales))
