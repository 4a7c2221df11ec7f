"""Decoder-only LM covering dense / moe / ssm / hybrid families.

Public API (functional, flax-free):
    init_params(key, cfg)                      -> params pytree
    forward(params, tokens, cfg, engine)       -> logits (B, S, V)
    prefill(params, tokens, cfg, engine)       -> logits, Cache
    decode_step(params, token, cache, cfg, engine) -> logits, Cache
    loss_fn(params, batch, cfg, engine)        -> scalar loss, metrics

Layer stacks are scanned (stacked params, lax.scan) so HLO size — and
compile time on the 512-device dry-run — is depth-independent. Per-layer
heterogeneity (gemma2 local/global alternation) rides through the scan as
a traced (L,) window array with GLOBAL_WINDOW as the "no window" value.

The KV/SSM cache is a plain pytree (Cache) so it jits, shards, and
checkpoints like any other state.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.core.salpim import SalPimEngine
from repro.distributed.api import constrain
from repro.models import blocks as blk
from repro.models import mamba2 as m2
from repro.kernels.decode_attention import GLOBAL_WINDOW
from repro.models.config import ModelConfig
from repro.models.rope import mrope_cos_sin, rope_cos_sin

Array = jax.Array


@dataclasses.dataclass
class Cache:
    """Decode-time state. Fields are None when the family doesn't use them.

    k, v:       (L, B, Hkv, Smax, Dh)   attention KV
    lengths:    (B,) int32              valid tokens per sequence
    ssm:        (L, B, H, N, P)         Mamba2 state
    conv:       (L, B, K-1, conv_dim)   Mamba2 conv window
    shared_k/v: (A, B, Hkv, Smax, Dh)   zamba2 shared-attn KV (A applications)
    cross_k/v:  (L, B, Hkv, Senc, Dh)   enc-dec static cross-attention KV
    """

    lengths: Array
    k: Optional[Array] = None
    v: Optional[Array] = None
    ssm: Optional[Array] = None
    conv: Optional[Array] = None
    shared_k: Optional[Array] = None
    shared_v: Optional[Array] = None
    cross_k: Optional[Array] = None
    cross_v: Optional[Array] = None
    # int8 KV mode: per-vector dequant scales (L, B, Hkv, S)
    k_scale: Optional[Array] = None
    v_scale: Optional[Array] = None


jax.tree_util.register_pytree_node(
    Cache,
    lambda c: ((c.lengths, c.k, c.v, c.ssm, c.conv, c.shared_k, c.shared_v,
                c.cross_k, c.cross_v, c.k_scale, c.v_scale), None),
    lambda _, ch: Cache(*ch),
)


def _quantize_kv(x: Array) -> tuple[Array, Array]:
    """(..., S, D) -> int8 payload + (..., S) per-vector scale."""
    absmax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1)
    scale = jnp.maximum(absmax, 1e-8) / 127.0
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale[..., None]),
                 -127, 127).astype(jnp.int8)
    return q, scale.astype(jnp.bfloat16)


def _dequantize_kv(q: Array, scale: Array, dtype) -> Array:
    return (q.astype(jnp.float32) * scale[..., None]).astype(dtype)


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------

def _stack_layers(key, n: int, init_one):
    """vmap an init function over layer keys -> params stacked on axis 0."""
    keys = jax.random.split(key, n)
    return jax.vmap(init_one)(keys)


def init_params(key, cfg: ModelConfig) -> dict:
    ks = jax.random.split(key, 8)
    d = cfg.d_model
    p: dict[str, Any] = {
        "embed": (jax.random.normal(ks[0], (cfg.vocab, d)) * 0.02).astype(cfg.pdtype),
        "final_norm": blk.init_norm(cfg),
        "lm_head": (jax.random.normal(ks[1], (cfg.vocab, d)) * d**-0.5).astype(cfg.pdtype),
    }
    if cfg.learned_pos_emb:
        p["pos_embed"] = (jax.random.normal(ks[2], (cfg.max_seq, d)) * 0.02).astype(cfg.pdtype)
    if cfg.family in ("dense", "moe"):
        p["blocks"] = _stack_layers(
            ks[3], cfg.n_layers, lambda k: blk.init_decoder_block(k, cfg))
    elif cfg.family == "ssm":
        p["blocks"] = _stack_layers(
            ks[3], cfg.n_layers,
            lambda k: {"norm": blk.init_norm(cfg), "mamba": m2.init_mamba2(k, cfg)})
    elif cfg.family == "hybrid":
        p["blocks"] = _stack_layers(
            ks[3], cfg.n_layers,
            lambda k: {"norm": blk.init_norm(cfg), "mamba": m2.init_mamba2(k, cfg)})
        p["shared_attn"] = blk.init_decoder_block(ks[4], cfg)
    else:
        raise ValueError(cfg.family)
    return p


# ---------------------------------------------------------------------------
# Shared pieces
# ---------------------------------------------------------------------------

def _windows(cfg: ModelConfig) -> Array:
    return jnp.array(
        [cfg.window_for_layer(i) or GLOBAL_WINDOW for i in range(cfg.n_layers)],
        jnp.int32,
    )


def _rope(cfg: ModelConfig, positions: Array):
    """positions (...,) -> cos/sin (..., Dh/2); handles M-RoPE."""
    if cfg.learned_pos_emb:
        return None, None
    if cfg.mrope_sections is not None:
        pos3 = jnp.broadcast_to(positions[None], (3,) + positions.shape)
        return mrope_cos_sin(pos3, cfg.head_dim, cfg.rope_theta,
                             cfg.mrope_sections)
    return rope_cos_sin(positions, cfg.head_dim, cfg.rope_theta)


def _embed(p: dict, tokens: Array, cfg: ModelConfig, positions: Array | None = None) -> Array:
    x = jnp.take(p["embed"], tokens, axis=0).astype(cfg.cdtype)
    if cfg.embed_scale:
        x = x * jnp.asarray(cfg.d_model**0.5, cfg.cdtype)
    if cfg.learned_pos_emb:
        pos = positions if positions is not None else jnp.arange(tokens.shape[-1])
        x = x + jnp.take(p["pos_embed"], pos, axis=0).astype(cfg.cdtype)
    return constrain(x, "batch", None, None)


def _logits(p: dict, x: Array, cfg: ModelConfig, engine: SalPimEngine) -> Array:
    x = blk.apply_norm(p["final_norm"], x, cfg, engine)
    logits = engine.linear(x, p["lm_head"])
    if cfg.final_softcap is not None:
        logits = engine.nl.softcap(logits.astype(jnp.float32), cfg.final_softcap)
    return constrain(logits, "batch", None, "model")


def _maybe_remat(fn, cfg: ModelConfig):
    return jax.checkpoint(fn) if cfg.remat == "block" else fn


# ---------------------------------------------------------------------------
# Full-sequence forward (training / prefill math)
# ---------------------------------------------------------------------------

def forward(params: dict, tokens: Array, cfg: ModelConfig,
            engine: SalPimEngine) -> Array:
    """tokens (B, S) -> logits (B, S, V)."""
    B, S = tokens.shape
    x = _embed(params, tokens, cfg)
    cos, sin = _rope(cfg, jnp.arange(S))

    if cfg.family in ("dense", "moe"):
        def body(h, layer):
            bp, window = layer
            h = blk.apply_decoder_block(bp, h, cfg, engine,
                                        cos=cos, sin=sin, window=window)
            if cfg.seq_parallel_acts:
                h = constrain(h, "batch", "seq_tp", None)
            return h, None

        x, _ = jax.lax.scan(_maybe_remat(body, cfg), x,
                            (params["blocks"], _windows(cfg)))
    elif cfg.family == "ssm":
        def body(h, bp):
            r = blk.apply_norm(bp["norm"], h, cfg, engine)
            h = h + m2.apply_mamba2(bp["mamba"], r, cfg, engine)
            if cfg.seq_parallel_acts:
                h = constrain(h, "batch", "seq_tp", None)
            return h, None

        x, _ = jax.lax.scan(_maybe_remat(body, cfg), x, params["blocks"])
    elif cfg.family == "hybrid":
        x = _hybrid_fullseq(params, x, cfg, engine, cos, sin)
    else:
        raise ValueError(cfg.family)
    return _logits(params, x, cfg, engine)


def _hybrid_segments(cfg: ModelConfig) -> list[tuple[int, int]]:
    """[(start, end)) mamba-layer segments; shared attn runs before each."""
    every = max(cfg.hybrid_attn_every, 1)
    return [(s, min(s + every, cfg.n_layers))
            for s in range(0, cfg.n_layers, every)]


def _hybrid_fullseq(params, x, cfg, engine, cos, sin):
    def mamba_body(h, bp):
        r = blk.apply_norm(bp["norm"], h, cfg, engine)
        h = h + m2.apply_mamba2(bp["mamba"], r, cfg, engine)
        if cfg.seq_parallel_acts:
            h = constrain(h, "batch", "seq_tp", None)
        return h, None

    body = _maybe_remat(mamba_body, cfg)
    for (s, e) in _hybrid_segments(cfg):
        x = blk.apply_decoder_block(params["shared_attn"], x, cfg, engine,
                                    cos=cos, sin=sin, window=GLOBAL_WINDOW)
        seg = jax.tree.map(lambda a: a[s:e], params["blocks"])
        x, _ = jax.lax.scan(body, x, seg)
    return x


# ---------------------------------------------------------------------------
# Loss
# ---------------------------------------------------------------------------

def loss_fn(params: dict, batch: dict, cfg: ModelConfig,
            engine: SalPimEngine):
    """batch: {tokens (B,S), labels (B,S), mask (B,S)} -> (loss, metrics)."""
    logits = forward(params, batch["tokens"], cfg, engine)
    logits = logits.astype(jnp.float32)
    labels = batch["labels"]
    mask = batch.get("mask")
    if mask is None:
        mask = jnp.ones_like(labels, jnp.float32)
    logz = jax.scipy.special.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = (logz - gold) * mask
    denom = jnp.maximum(jnp.sum(mask), 1.0)
    loss = jnp.sum(nll) / denom
    metrics = {
        "loss": loss,
        "ppl_proxy": jnp.exp(jnp.minimum(loss, 20.0)),
        "tokens": jnp.sum(mask),
        "accuracy": jnp.sum((jnp.argmax(logits, -1) == labels) * mask) / denom,
    }
    return loss, metrics


# ---------------------------------------------------------------------------
# Prefill: full-seq forward that also materializes the decode cache
# ---------------------------------------------------------------------------

def init_cache(cfg: ModelConfig, batch: int, max_len: int,
               dtype=None) -> Cache:
    """Empty cache with room for max_len tokens."""
    dtype = dtype or cfg.cdtype
    L, Hkv, Dh = cfg.n_layers, cfg.n_kv_heads, cfg.head_dim
    lengths = jnp.zeros((batch,), jnp.int32)
    if cfg.family in ("dense", "moe"):
        shape = (L, batch, Hkv, max_len, Dh)
        if cfg.kv_dtype == "int8":
            return Cache(lengths=lengths,
                         k=jnp.zeros(shape, jnp.int8),
                         v=jnp.zeros(shape, jnp.int8),
                         k_scale=jnp.zeros(shape[:-1], jnp.bfloat16),
                         v_scale=jnp.zeros(shape[:-1], jnp.bfloat16))
        return Cache(lengths=lengths, k=jnp.zeros(shape, dtype),
                     v=jnp.zeros(shape, dtype))
    if cfg.family == "ssm":
        return Cache(
            lengths=lengths,
            ssm=jnp.zeros((L, batch, cfg.ssm_heads, cfg.ssm_state,
                           cfg.ssm_headdim), jnp.float32),
            conv=jnp.zeros((L, batch, cfg.ssm_conv - 1,
                            cfg.d_inner + 2 * cfg.ssm_state), dtype),
        )
    if cfg.family == "hybrid":
        A = len(_hybrid_segments(cfg))
        return Cache(
            lengths=lengths,
            ssm=jnp.zeros((L, batch, cfg.ssm_heads, cfg.ssm_state,
                           cfg.ssm_headdim), jnp.float32),
            conv=jnp.zeros((L, batch, cfg.ssm_conv - 1,
                            cfg.d_inner + 2 * cfg.ssm_state), dtype),
            shared_k=jnp.zeros((A, batch, Hkv, max_len, Dh), dtype),
            shared_v=jnp.zeros((A, batch, Hkv, max_len, Dh), dtype),
        )
    raise ValueError(cfg.family)


def prefill(params: dict, tokens: Array, cfg: ModelConfig,
            engine: SalPimEngine, *, max_len: int) -> tuple[Array, Cache]:
    """tokens (B, S) -> (last-position logits (B, V), primed Cache)."""
    B, S = tokens.shape
    assert max_len >= S
    x = _embed(params, tokens, cfg)
    cos, sin = _rope(cfg, jnp.arange(S))
    cache = init_cache(cfg, B, max_len)
    lengths = jnp.full((B,), S, jnp.int32)

    if cfg.family in ("dense", "moe"):
        def body(h, layer):
            bp, window = layer
            h, (ck, cv) = blk.apply_decoder_block_prefill(
                bp, h, cfg, engine, cos=cos, sin=sin, window=window)
            return h, (ck, cv)

        x, (ks, vs) = jax.lax.scan(_maybe_remat(body, cfg), x,
                                   (params["blocks"], _windows(cfg)))
        pad = max_len - S
        pad5 = ((0, 0), (0, 0), (0, 0), (0, pad), (0, 0))
        if cfg.kv_dtype == "int8":
            kq, ksc = _quantize_kv(ks)
            vq, vsc = _quantize_kv(vs)
            pad4 = ((0, 0), (0, 0), (0, 0), (0, pad))
            cache = Cache(lengths=lengths,
                          k=jnp.pad(kq, pad5), v=jnp.pad(vq, pad5),
                          k_scale=jnp.pad(ksc, pad4),
                          v_scale=jnp.pad(vsc, pad4))
        else:
            cache = Cache(
                lengths=lengths,
                k=jnp.pad(ks.astype(cfg.cdtype), pad5),
                v=jnp.pad(vs.astype(cfg.cdtype), pad5),
            )
    elif cfg.family == "ssm":
        def body(h, bp):
            r = blk.apply_norm(bp["norm"], h, cfg, engine)
            o, state, tail = m2.apply_mamba2(bp["mamba"], r, cfg, engine,
                                             return_state=True)
            return h + o, (state, tail)

        x, (states, tails) = jax.lax.scan(body, x, params["blocks"])
        cache = Cache(lengths=lengths, ssm=states.astype(jnp.float32),
                      conv=tails.astype(cfg.cdtype))
    elif cfg.family == "hybrid":
        x, cache = _hybrid_prefill(params, x, cfg, engine, cos, sin,
                                   lengths, max_len)
    else:
        raise ValueError(cfg.family)

    logits = _logits(params, x[:, -1], cfg, engine)
    return logits, cache


def _hybrid_prefill(params, x, cfg, engine, cos, sin, lengths, max_len):
    B, S = x.shape[0], x.shape[1]
    pad = max_len - S
    sk, sv, states, tails = [], [], [], []

    def mamba_body(h, bp):
        r = blk.apply_norm(bp["norm"], h, cfg, engine)
        o, state, tail = m2.apply_mamba2(bp["mamba"], r, cfg, engine,
                                         return_state=True)
        return h + o, (state, tail)

    for (s, e) in _hybrid_segments(cfg):
        h = blk.apply_norm(params["shared_attn"]["ln1"], x, cfg, engine)
        from repro.models import attention as attn_lib
        h, (ck, cv) = attn_lib.attention_fullseq(
            params["shared_attn"]["attn"], h, cfg, engine, cos=cos, sin=sin,
            window=None, causal=True, return_kv=True)
        x = x + h
        h = blk.apply_norm(params["shared_attn"]["ln2"], x, cfg, engine)
        from repro.models import ffn as ffn_lib
        x = x + ffn_lib.apply_ffn(params["shared_attn"]["ffn"], h, cfg, engine)
        sk.append(jnp.pad(ck.astype(cfg.cdtype), ((0, 0), (0, 0), (0, pad), (0, 0))))
        sv.append(jnp.pad(cv.astype(cfg.cdtype), ((0, 0), (0, 0), (0, pad), (0, 0))))
        seg = jax.tree.map(lambda a: a[s:e], params["blocks"])
        x, (st, tl) = jax.lax.scan(mamba_body, x, seg)
        states.append(st)
        tails.append(tl)
    cache = Cache(
        lengths=lengths,
        ssm=jnp.concatenate(states, 0).astype(jnp.float32),
        conv=jnp.concatenate(tails, 0).astype(cfg.cdtype),
        shared_k=jnp.stack(sk, 0),
        shared_v=jnp.stack(sv, 0),
    )
    return x, cache


def _paged_chunk_forward(params: dict, tokens: Array, block_tables: Array,
                         start: Array, k_pages: Array, v_pages: Array,
                         cfg: ModelConfig, engine: SalPimEngine,
                         k_scales: Array | None,
                         v_scales: Array | None):
    """Shared body of `prefill_chunk` and `verify_tokens`: run tokens
    (B, S) at absolute positions start..start+S-1 through the block
    stack against the page pool, writing each layer's chunk K/V into the
    mapped pages. Returns (hidden (B, S, D), k', v', k_scale', v_scale')
    — the two entry points differ only in which positions' logits they
    project."""
    if cfg.family not in ("dense", "moe"):
        raise ValueError("paged prefill unsupported for family "
                         f"{cfg.family!r}")
    if k_pages.dtype == jnp.int8 and k_scales is None:
        # Without this the fp write branch would astype float K/V to
        # int8 — silent garbage instead of a quantized write. (int4
        # pools are int8-dtype with a packed payload axis, so this
        # guard covers them too.)
        raise ValueError("int8 page pools need their scale pools: pass "
                         "k_scales/v_scales from the PagedCache")
    B, S = tokens.shape
    start = jnp.asarray(start, jnp.int32)
    pos = start[:, None] + jnp.arange(S)[None, :]            # (B, S)
    x = _embed(params, tokens, cfg,
               positions=pos if cfg.learned_pos_emb else None)
    cos, sin = _rope(cfg, pos)
    length = start + S

    # One scan body for both pool dtypes: None scale leaves ride through
    # the scan's xs/ys pytrees untouched (lax.scan slices only array
    # leaves), so the fp and int8 paths cannot drift apart.
    def body(h, layer):
        bp, window, kp, vp, ksc, vsc = layer
        h, nk, nv, *nsc = blk.apply_decoder_block_prefill_chunk_paged(
            bp, h, kp, vp, block_tables, start, length, cfg, engine,
            cos=cos, sin=sin, window=window,
            kv_scales=(ksc, vsc) if ksc is not None else None)
        return h, (nk, nv, *(nsc or (None, None)))

    x, (nk, nv, nks, nvs) = jax.lax.scan(
        _maybe_remat(body, cfg), x,
        (params["blocks"], _windows(cfg), k_pages, v_pages,
         k_scales, v_scales))
    return x, nk, nv, nks, nvs


def prefill_chunk(params: dict, tokens: Array, block_tables: Array,
                  start: Array, k_pages: Array, v_pages: Array,
                  cfg: ModelConfig, engine: SalPimEngine,
                  k_scales: Array | None = None,
                  v_scales: Array | None = None):
    """One chunk of paged prefill, written directly into pool pages.

    tokens (B, S) are prompt positions start[b] .. start[b]+S-1 of B
    sequences whose earlier chunks' K/V already live in the pool pages
    mapped by block_tables (B, n_pages). RoPE / learned positions are
    offset by `start`; each layer writes the chunk's K/V into its pages
    (`append_chunk_kv_pages`) and the chunk's queries attend over all
    resident KV [0, start+S) through the block table — there is no dense
    prefill arena and nothing to scatter afterwards. Chunking is exact:
    running a prompt in any chunk split reproduces the one-shot logits.

    Returns (last-position logits (B, V), k_pages', v_pages').
    int8/int4 pools (k_scales/v_scales (L, P, Hkv, 1, page) given) quantize each
    chunk at write time and return the 5-tuple with the updated scale
    pools. Prefix sharing composes: a shared prompt simply starts its
    first chunk at the shared offset (the caller COW-forks any shared
    page — payload and scale row — the chunk writes into).
    """
    x, nk, nv, nks, nvs = _paged_chunk_forward(
        params, tokens, block_tables, start, k_pages, v_pages, cfg,
        engine, k_scales, v_scales)
    logits = _logits(params, x[:, -1], cfg, engine)
    if k_scales is not None:
        return logits, nk, nv, nks, nvs
    return logits, nk, nv


def verify_tokens(params: dict, tokens: Array, block_tables: Array,
                  start: Array, k_pages: Array, v_pages: Array,
                  cfg: ModelConfig, engine: SalPimEngine,
                  k_scales: Array | None = None,
                  v_scales: Array | None = None):
    """Speculative verify pass: score k+1 candidate tokens per slot in
    one forward over the page pool (serving/speculative.py).

    tokens (B, S=k+1) hold, per decode slot, [t0, d1..dk] — the greedy
    token plus the drafter's proposals — at absolute positions
    start[b]..start[b]+k. This is exactly `prefill_chunk`'s computation
    (same block/attention path, same `append_chunk_kv_pages` write, same
    paged-prefill kernel dispatch) with one difference: the logits head
    runs at *all* S positions, because acceptance needs the target's
    greedy choice after every candidate. Returns (logits (B, S, V),
    k_pages', v_pages'[, k_scale', v_scale']). The caller commits the
    longest accepted prefix and rolls the rest back in-pool
    (`kvcache.rewind_slot` + `BlockAllocator.rewind`) — KV for accepted
    tokens is already resident, so no decode step re-computes it.
    """
    x, nk, nv, nks, nvs = _paged_chunk_forward(
        params, tokens, block_tables, start, k_pages, v_pages, cfg,
        engine, k_scales, v_scales)
    logits = _logits(params, x, cfg, engine)
    if k_scales is not None:
        return logits, nk, nv, nks, nvs
    return logits, nk, nv


# ---------------------------------------------------------------------------
# Decode: one token per call (the paper's generation-stage workload)
# ---------------------------------------------------------------------------


def _advance_lengths(lengths: Array) -> Array:
    """Advance only live sequences. Released serving slots park at
    length 0; unconditionally adding 1 every step made idle lengths
    creep without bound — attention then spans ever more garbage (trash
    pages on the paged backend) and KV appends scatter junk each step."""
    return lengths + (lengths > 0).astype(lengths.dtype)

def decode_step(params: dict, token: Array, cache, cfg: ModelConfig,
                engine: SalPimEngine):
    """token (B,) int32 -> (logits (B, V), updated cache).

    `cache` is either a dense `Cache` or a `serving.kvcache.PagedCache`;
    the paged form routes attention through the block-table kernel.

    Mesh-sharded pools need nothing here: the per-layer pool slices the
    scan hands to attention inherit the (L, P, Hkv, page, Dh) leaves'
    KV-head sharding (scan slices axis 0, the layer axis), and the
    shard_map region lives inside `models/attention.py` — this scan body
    is identical whether the pools are replicated or sharded.
    """
    from repro.serving.kvcache import PagedCache
    if isinstance(cache, PagedCache):
        return _decode_step_paged(params, token, cache, cfg, engine)
    B = token.shape[0]
    x = _embed(params, token[:, None], cfg, positions=cache.lengths[:, None] if cfg.learned_pos_emb else None)[:, 0]
    cos, sin = _rope(cfg, cache.lengths)

    if cfg.family in ("dense", "moe"):
        if cfg.kv_dtype == "int8":
            def body8(h, layer):
                bp, window, ck, cv, ks_, vs_ = layer
                h, nk, nv, nks, nvs = blk.apply_decoder_block_decode(
                    bp, h, ck, cv, cache.lengths, cfg, engine,
                    cos=cos, sin=sin, window=window, kv_scales=(ks_, vs_))
                return h, (nk, nv, nks, nvs)

            x, (nk, nv, nks, nvs) = jax.lax.scan(
                body8, x, (params["blocks"], _windows(cfg), cache.k,
                           cache.v, cache.k_scale, cache.v_scale))
            new_cache = Cache(lengths=_advance_lengths(cache.lengths), k=nk, v=nv,
                              k_scale=nks, v_scale=nvs)
        else:
            def body(h, layer):
                bp, window, ck, cv = layer
                h, nk, nv = blk.apply_decoder_block_decode(
                    bp, h, ck, cv, cache.lengths, cfg, engine,
                    cos=cos, sin=sin, window=window)
                return h, (nk, nv)

            x, (nk, nv) = jax.lax.scan(
                body, x, (params["blocks"], _windows(cfg), cache.k, cache.v))
            new_cache = Cache(lengths=_advance_lengths(cache.lengths), k=nk, v=nv)
    elif cfg.family == "ssm":
        def body(h, layer):
            bp, st, cv = layer
            r = blk.apply_norm(bp["norm"], h, cfg, engine)
            o, nst, ncv = m2.mamba2_decode_step(bp["mamba"], r, st, cv, cfg, engine)
            return h + o, (nst, ncv)

        x, (nst, ncv) = jax.lax.scan(body, x, (params["blocks"], cache.ssm,
                                               cache.conv))
        new_cache = Cache(lengths=_advance_lengths(cache.lengths), ssm=nst, conv=ncv)
    elif cfg.family == "hybrid":
        x, new_cache = _hybrid_decode(params, x, cache, cfg, engine, cos, sin)
    else:
        raise ValueError(cfg.family)

    return _logits(params, x, cfg, engine), new_cache


def _decode_step_paged(params: dict, token: Array, cache, cfg: ModelConfig,
                       engine: SalPimEngine):
    """Paged decode: the per-layer KV pools ride through the layer scan;
    the block table and lengths are shared across layers. int8 pools
    (cache.k_scale/v_scale present) carry their scale-row pools through
    the same scan — the append quantizes, the kernel dequantizes."""
    from repro.serving.kvcache import PagedCache

    if cfg.family not in ("dense", "moe"):
        raise ValueError(f"paged cache unsupported for family {cfg.family!r}")
    if cache.k_pages.dtype == jnp.int8 and cache.k_scale is None:
        raise ValueError("int8 page pools need their scale pools: the "
                         "PagedCache is missing k_scale/v_scale")

    x = _embed(params, token[:, None], cfg,
               positions=cache.lengths[:, None] if cfg.learned_pos_emb
               else None)[:, 0]
    cos, sin = _rope(cfg, cache.lengths)

    # One scan body for both pool dtypes (None scale leaves pass through
    # the scan pytrees), mirroring prefill_chunk.
    def body(h, layer):
        bp, window, kp, vp, ksc, vsc = layer
        h, nk, nv, *nsc = blk.apply_decoder_block_decode_paged(
            bp, h, kp, vp, cache.block_tables, cache.lengths, cfg, engine,
            cos=cos, sin=sin, window=window,
            kv_scales=(ksc, vsc) if ksc is not None else None)
        return h, (nk, nv, *(nsc or (None, None)))

    x, (nk, nv, nks, nvs) = jax.lax.scan(
        body, x, (params["blocks"], _windows(cfg), cache.k_pages,
                  cache.v_pages, cache.k_scale, cache.v_scale))
    new_cache = PagedCache(lengths=_advance_lengths(cache.lengths),
                           block_tables=cache.block_tables,
                           k_pages=nk, v_pages=nv,
                           k_scale=nks, v_scale=nvs)
    return _logits(params, x, cfg, engine), new_cache


def _hybrid_decode(params, x, cache: Cache, cfg, engine, cos, sin):
    from repro.models import attention as attn_lib
    from repro.models import ffn as ffn_lib

    def mamba_body(h, layer):
        bp, st, cv = layer
        r = blk.apply_norm(bp["norm"], h, cfg, engine)
        o, nst, ncv = m2.mamba2_decode_step(bp["mamba"], r, st, cv, cfg, engine)
        return h + o, (nst, ncv)

    segs = _hybrid_segments(cfg)
    nk, nv, nst_all, ncv_all = [], [], [], []
    for a, (s, e) in enumerate(segs):
        h = blk.apply_norm(params["shared_attn"]["ln1"], x, cfg, engine)
        h, ck, cv_ = attn_lib.attention_decode(
            params["shared_attn"]["attn"], h, cache.shared_k[a],
            cache.shared_v[a], cache.lengths, cfg, engine, cos=cos, sin=sin)
        x = x + h
        h = blk.apply_norm(params["shared_attn"]["ln2"], x, cfg, engine)
        x = x + ffn_lib.apply_ffn(params["shared_attn"]["ffn"], h, cfg, engine)
        nk.append(ck)
        nv.append(cv_)
        seg = jax.tree.map(lambda arr: arr[s:e], params["blocks"])
        segc_s = cache.ssm[s:e]
        segc_c = cache.conv[s:e]
        x, (nst, ncv) = jax.lax.scan(mamba_body, x, (seg, segc_s, segc_c))
        nst_all.append(nst)
        ncv_all.append(ncv)
    new_cache = Cache(
        lengths=_advance_lengths(cache.lengths),
        ssm=jnp.concatenate(nst_all, 0),
        conv=jnp.concatenate(ncv_all, 0),
        shared_k=jnp.stack(nk, 0),
        shared_v=jnp.stack(nv, 0),
    )
    return x, new_cache
