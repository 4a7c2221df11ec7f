"""Multi-head attention: GQA, RoPE/M-RoPE, SWA, softcap, bias; full-seq and
single-token decode forms.

Full-seq (train/prefill) keeps the two SAL-PIM accumulation directions as
two einsum contractions over the same (B, S, Hkv, D) K/V layout (never a
materialized transpose). Softmax routes through the engine — i.e. the
LUT exp/reciprocal path when the technique is on. Long sequences use
query-chunked (memory-efficient) attention via lax.scan.

Decode uses the fused kernel path (`engine.decode_attention`).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.salpim import SalPimEngine
from repro.distributed import api as dist_api
from repro.distributed.api import constrain
from repro.distributed.collectives import gather_heads
from repro.models.config import ModelConfig
from repro.models.rope import apply_rope

Array = jax.Array


def init_attention(key, cfg: ModelConfig, *, cross: bool = False) -> dict:
    d = cfg.d_model
    n_q = cfg.n_heads * cfg.head_dim
    n_kv = cfg.n_kv_heads * cfg.head_dim
    ks = jax.random.split(key, 4)
    scale_in = d ** -0.5
    p = {
        "wq": (jax.random.normal(ks[0], (n_q, d)) * scale_in).astype(cfg.pdtype),
        "wk": (jax.random.normal(ks[1], (n_kv, d)) * scale_in).astype(cfg.pdtype),
        "wv": (jax.random.normal(ks[2], (n_kv, d)) * scale_in).astype(cfg.pdtype),
        "wo": (jax.random.normal(ks[3], (d, n_q)) * (n_q ** -0.5)).astype(cfg.pdtype),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((n_q,), cfg.pdtype)
        p["bk"] = jnp.zeros((n_kv,), cfg.pdtype)
        p["bv"] = jnp.zeros((n_kv,), cfg.pdtype)
    del cross
    return p


def _project_qkv(p: dict, x: Array, cfg: ModelConfig, engine: SalPimEngine,
                 kv_x: Array | None = None):
    """x (B, S, D) -> q (B,S,H,Dh), k/v (B,Skv,Hkv,Dh)."""
    B, S, _ = x.shape
    kv_in = x if kv_x is None else kv_x
    Skv = kv_in.shape[1]
    q = engine.linear(x, p["wq"], p.get("bq"))
    k = engine.linear(kv_in, p["wk"], p.get("bk"))
    v = engine.linear(kv_in, p["wv"], p.get("bv"))
    q = q.reshape(B, S, cfg.n_heads, cfg.head_dim)
    k = k.reshape(B, Skv, cfg.n_kv_heads, cfg.head_dim)
    v = v.reshape(B, Skv, cfg.n_kv_heads, cfg.head_dim)
    return q, k, v


def _masked_softmax_attn(
    q: Array,              # (B, Sq, H, Dh)
    k: Array,              # (B, Sk, Hkv, Dh)
    v: Array,              # (B, Sk, Hkv, Dh)
    engine: SalPimEngine,
    cfg: ModelConfig,
    *,
    q_offset: Array | int,
    causal: bool,
    window: Optional[int],
) -> Array:
    B, Sq, H, Dh = q.shape
    Sk, Hkv = k.shape[1], k.shape[2]
    g = H // Hkv
    scale = cfg.attn_scale if cfg.attn_scale is not None else Dh ** -0.5
    qg = q.reshape(B, Sq, Hkv, g, Dh)
    # Direction 1: contract head_dim (Q x K^T) — no transpose of K.
    scores = jnp.einsum("bqhgd,bkhd->bhgqk", qg.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    if cfg.attn_softcap is not None:
        scores = engine.nl.softcap(scores, cfg.attn_softcap)
    q_pos = jnp.arange(Sq) + q_offset          # absolute query positions
    k_pos = jnp.arange(Sk)
    mask = jnp.ones((Sq, Sk), bool)
    if causal:
        mask = mask & (k_pos[None, :] <= q_pos[:, None])
    if window is not None:
        mask = mask & (k_pos[None, :] > q_pos[:, None] - window)
    probs = engine.softmax(scores, axis=-1, where=mask[None, None, None])
    # Direction 2: contract seq (S x V) over the same V layout.
    out = jnp.einsum("bhgqk,bkhd->bqhgd", probs.astype(v.dtype), v)
    return out.reshape(B, Sq, H, Dh)


def attention_fullseq(
    p: dict,
    x: Array,                      # (B, S, D)
    cfg: ModelConfig,
    engine: SalPimEngine,
    *,
    cos: Array | None,             # (S, Dh/2) or (B, S, Dh/2)
    sin: Array | None,
    window: Optional[int] = None,
    causal: bool = True,
    kv_x: Array | None = None,     # cross-attention source (B, Senc, D)
    cos_kv: Array | None = None,
    sin_kv: Array | None = None,
    return_kv: bool = False,
):
    B, S, D = x.shape
    q, k, v = _project_qkv(p, x, cfg, engine, kv_x=kv_x)
    if cos is not None:
        q = apply_rope(q, cos, sin)
        kc, ks_ = (cos, sin) if kv_x is None else (cos_kv, sin_kv)
        if kc is not None:
            k = apply_rope(k, kc, ks_)
    q = constrain(q, "batch", None, "model", None)
    k = constrain(k, "batch", None, "model", None)
    v = constrain(v, "batch", None, "model", None)

    Sk = k.shape[1]
    chunk = cfg.attn_chunk
    if S > chunk and S % chunk == 0:
        # Memory-efficient attention: scan over query chunks.
        n_chunks = S // chunk
        qs = q.reshape(B, n_chunks, chunk, cfg.n_heads, cfg.head_dim)
        qs = jnp.moveaxis(qs, 1, 0)           # (n, B, chunk, H, Dh)

        def body(_, qc_i):
            qc, i = qc_i
            out = _masked_softmax_attn(
                qc, k, v, engine, cfg,
                q_offset=i * chunk, causal=causal, window=window)
            return None, out

        _, outs = jax.lax.scan(body, None, (qs, jnp.arange(n_chunks)))
        out = jnp.moveaxis(outs, 0, 1).reshape(B, S, cfg.n_heads, cfg.head_dim)
    else:
        out = _masked_softmax_attn(q, k, v, engine, cfg, q_offset=0,
                                   causal=causal, window=window)
    out = engine.linear(out.reshape(B, S, -1), p["wo"])
    out = constrain(out, "batch", None, None)
    if return_kv:
        # Cache layout (B, Hkv, S, D): the bank-sequential concat target.
        return out, (jnp.moveaxis(k, 1, 2), jnp.moveaxis(v, 1, 2))
    return out


def _paged_tp_axis(n_kv_heads: int):
    """Mesh + mesh-axis name behind the logical "model" axis, when the
    paged attention step should run tensor-parallel: a mesh is active
    (`distributed.api.use_mesh` — the engine enters it around its jitted
    steps), the axis extent is > 1, and it divides the KV-head count so
    every shard owns whole KV heads (GQA query heads follow — q's head
    axis orders as (kv_head, group), so a contiguous H-block of size
    (Hkv/t)*g is exactly Hkv/t kv heads with all their query heads).
    Returns (None, None) otherwise and the caller stays single-device.
    """
    mesh = dist_api.current_mesh()
    if mesh is None:
        return None, None
    axis = dist_api.resolve_spec(("model",), mesh)[0]
    if axis is None:
        return None, None
    size = dist_api.axis_size(mesh, "model")
    if size <= 1 or n_kv_heads % size:
        return None, None
    return mesh, axis


def attention_prefill_chunk_paged(
    p: dict,
    x: Array,                      # (B, S, D) one prompt chunk per sequence
    k_pages: Array,                # (P, Hkv, page, Dh) shared pool
    v_pages: Array,
    block_tables: Array,           # (B, n_pages) int32
    start: Array,                  # (B,) absolute position of chunk token 0
    length: Array,                 # (B,) valid KV after this chunk (start+S)
    cfg: ModelConfig,
    engine: SalPimEngine,
    *,
    cos: Array | None,             # rope at positions start .. start+S-1
    sin: Array | None,
    window,
    k_scale: Array | None = None,  # (P, Hkv, 1, page) int8/int4 scale rows
    v_scale: Array | None = None,
):
    """Chunked paged prefill attention: write the chunk's K/V directly
    into pool pages, then attend over all resident KV [0, start+S) read
    back through the block table (earlier chunks included). Returns
    (out, k_pages', v_pages') — there is no dense K/V to scatter later.
    int8/int4 pools (scale rows given) quantize the chunk at write time
    (int4: nibble-packed by the append) and return
    (out, k_pages', v_pages', k_scale', v_scale').

    Under an active mesh (engine `mesh=`) the append + attention run
    inside `shard_map`: each shard appends its KV-head slice of the
    chunk into its local pool shard and attends its own query heads;
    the head outputs merge by `collectives.gather_heads` (an exact
    concatenation), so outputs stay bit-identical to one device.

    The speculative verify pass reuses this attention wholesale: its
    chunk is [t0, d1..dk] at the slot's decode frontier, so accepted
    candidates' KV is already pool-resident when the round commits and
    rejected tail KV is rolled back by rewinding lengths/tables (the
    write itself needs no undo — dead positions are length-masked and
    overwritten by the next append).
    """
    from repro.serving.kvcache import append_chunk_kv_pages

    B, S, D = x.shape
    q, k, v = _project_qkv(p, x, cfg, engine)
    if cos is not None:
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)
    q = constrain(q, "batch", None, "model", None)
    k = constrain(k, "batch", None, "model", None)
    v = constrain(v, "batch", None, "model", None)
    int8_kv = k_scale is not None
    scale = cfg.attn_scale if cfg.attn_scale is not None else cfg.head_dim ** -0.5

    def _write_and_attend(q, k, v, kp, vp, bt, st, ln, win, ksc, vsc):
        # Bank-sequential placement, chunk-granular: the chunk's K/V
        # lands in its pages before the read, so queries see their own
        # keys too.
        if ksc is not None:
            kp, vp, ksc, vsc = append_chunk_kv_pages(
                kp, vp, bt, st, k, v, ksc, vsc)
        else:
            kp, vp = append_chunk_kv_pages(kp, vp, bt, st, k, v)
        att = engine.paged_prefill_attention(
            q, kp, vp, bt, ln, st, ksc, vsc,
            scale=scale, softcap=cfg.attn_softcap, window=win)
        return att, kp, vp, ksc, vsc

    mesh, h_axis = _paged_tp_axis(cfg.n_kv_heads)
    if mesh is None:
        out, k_pages, v_pages, k_scale, v_scale = _write_and_attend(
            q, k, v, k_pages, v_pages, block_tables, start, length, window,
            k_scale, v_scale)
    else:
        out, k_pages, v_pages, k_scale, v_scale = _shard_map_paged(
            _write_and_attend, mesh, h_axis, head_axis=2,
            q=q, k=k, v=v, k_pages=k_pages, v_pages=v_pages,
            block_tables=block_tables, start=start, lengths=length,
            window=window, k_scale=k_scale, v_scale=v_scale)

    out = engine.linear(out.reshape(B, S, -1), p["wo"])
    out = constrain(out, "batch", None, None)
    if int8_kv:
        return out, k_pages, v_pages, k_scale, v_scale
    return out, k_pages, v_pages


def _shard_map_paged(write_and_attend, mesh, h_axis, *, head_axis,
                     q, k, v, k_pages, v_pages, block_tables,
                     start, lengths, window, k_scale, v_scale):
    """Run a paged append+attention region tensor-parallel on `mesh`.

    in_specs shard the head axis of q/k/v and the KV-head axis of the
    pools/scales over `h_axis`; block tables, lengths and positions are
    replicated (admission and page bookkeeping stay global). Inside the
    region each shard appends its KV-head slice into its local pool
    shard and attends its own contiguous query-head block — the same
    kernels, on a per-shard head slice — then `gather_heads` merges the
    head outputs by exact concatenation. The updated pool shards come
    back out still sharded (out_specs), so the engine's donated
    cache-in/cache-out round trip never re-lays-out the pools.

    `start` is None for the decode step (no chunk offset); `window`
    is None when the layer attends globally with no window scalar.
    """
    from jax.sharding import PartitionSpec as P

    rep = P()
    pool = P(None, h_axis, None, None)  # (P, Hkv, page, Dh); scale rows
                                        # (P, Hkv, 1, page) shard alike
    heads = P(*([None] * head_axis), h_axis, None)  # q/k/v, head axis sharded

    has = {"start": start is not None, "window": window is not None,
           "scales": k_scale is not None}
    args = [q, k, v, k_pages, v_pages, block_tables, lengths]
    in_specs = [heads, heads, heads, pool, pool, rep, rep]
    out_specs = [heads, pool, pool]
    if has["start"]:
        args.append(start)
        in_specs.append(rep)
    if has["window"]:
        args.append(jnp.asarray(window))
        in_specs.append(rep)
    if has["scales"]:
        args += [k_scale, v_scale]
        in_specs += [pool, pool]
        out_specs += [pool, pool]

    def region(q, k, v, kp, vp, bt, ln, *rest):
        rest = list(rest)
        st = rest.pop(0) if has["start"] else None
        win = rest.pop(0) if has["window"] else None
        ksc, vsc = rest if rest else (None, None)
        if st is None:
            att, kp, vp, ksc, vsc = write_and_attend(
                q, k, v, kp, vp, bt, ln, win, ksc, vsc)
        else:
            att, kp, vp, ksc, vsc = write_and_attend(
                q, k, v, kp, vp, bt, st, ln, win, ksc, vsc)
        att = gather_heads(att, h_axis, head_axis)
        out = [att, kp, vp]
        if ksc is not None:
            out += [ksc, vsc]
        return tuple(out)

    # Replicated out_spec for the merged heads: gather_heads already
    # made every shard's copy identical (check_vma=False because the
    # region may contain a pallas_call, which has no replication rule).
    out_specs[0] = rep
    res = jax.shard_map(region, mesh=mesh, in_specs=tuple(in_specs),
                        out_specs=tuple(out_specs), check_vma=False)(*args)
    if has["scales"]:
        att, kp, vp, ksc, vsc = res
    else:
        (att, kp, vp), ksc, vsc = res, None, None
    return att, kp, vp, ksc, vsc


def _quantize_vec(x: Array) -> tuple[Array, Array]:
    """(..., D) -> int8 + (...) bf16 scale; the dense int8 KV arena's
    storage form of `serving/quantize.quantize_vec`."""
    from repro.serving.quantize import quantize_vec
    return quantize_vec(x, scale_dtype=jnp.bfloat16)


def _decode_qkv(p: dict, x: Array, cfg: ModelConfig, engine: SalPimEngine,
                cos: Array | None, sin: Array | None):
    """Single-token projections + RoPE shared by the decode paths.
    x (B, D) -> q (B, H, Dh), k/v (B, Hkv, Dh)."""
    B, _ = x.shape
    q = engine.linear(x, p["wq"], p.get("bq")).reshape(B, cfg.n_heads, cfg.head_dim)
    k = engine.linear(x, p["wk"], p.get("bk")).reshape(B, cfg.n_kv_heads, cfg.head_dim)
    v = engine.linear(x, p["wv"], p.get("bv")).reshape(B, cfg.n_kv_heads, cfg.head_dim)
    if cos is not None:
        q = apply_rope(q[:, None], cos[:, None], sin[:, None])[:, 0]
        k = apply_rope(k[:, None], cos[:, None], sin[:, None])[:, 0]
    return q, k, v


def attention_decode_paged(
    p: dict,
    x: Array,                      # (B, D) one new token per sequence
    k_pages: Array,                # (P, Hkv, page, Dh) shared pool
    v_pages: Array,
    block_tables: Array,           # (B, n_pages) int32
    lengths: Array,                # (B,) tokens already in cache
    cfg: ModelConfig,
    engine: SalPimEngine,
    *,
    cos: Array | None,
    sin: Array | None,
    window: Optional[int] = None,
    k_scale: Array | None = None,  # (P, Hkv, 1, page) int8/int4 scale rows
    v_scale: Array | None = None,
):
    """One decode step against a paged cache; returns (out, k', v').
    int8/int4 pools (scale rows given) quantize the append at write
    time (int4: nibble-packed) and return
    (out, k', v', k_scale', v_scale').

    Under an active mesh (engine `mesh=`) the append + attention run
    inside `shard_map` on per-shard head slices — the memory-bound pool
    stream splits across every device's HBM — and the head outputs
    merge by exact concatenation (`collectives.gather_heads`), keeping
    greedy decode bit-identical to the single-device engine."""
    from repro.serving.kvcache import append_kv_pages

    B, _ = x.shape
    q, k, v = _decode_qkv(p, x, cfg, engine, cos, sin)
    int8_kv = k_scale is not None
    scale = cfg.attn_scale if cfg.attn_scale is not None else cfg.head_dim ** -0.5

    def _write_and_attend(q, k, v, kp, vp, bt, ln, win, ksc, vsc):
        # Bank-sequential concat, page-granular: append at each slot's
        # length.
        if ksc is not None:
            kp, vp, ksc, vsc = append_kv_pages(kp, vp, bt, ln, k, v,
                                               ksc, vsc)
        else:
            kp, vp = append_kv_pages(kp, vp, bt, ln, k, v)
        att = engine.paged_decode_attention(
            q, kp, vp, bt, ln + 1, ksc, vsc,
            scale=scale, softcap=cfg.attn_softcap, window=win)
        return att, kp, vp, ksc, vsc

    mesh, h_axis = _paged_tp_axis(cfg.n_kv_heads)
    if mesh is None:
        out, k_pages, v_pages, k_scale, v_scale = _write_and_attend(
            q, k, v, k_pages, v_pages, block_tables, lengths, window,
            k_scale, v_scale)
    else:
        out, k_pages, v_pages, k_scale, v_scale = _shard_map_paged(
            _write_and_attend, mesh, h_axis, head_axis=1,
            q=q, k=k, v=v, k_pages=k_pages, v_pages=v_pages,
            block_tables=block_tables, start=None, lengths=lengths,
            window=window, k_scale=k_scale, v_scale=v_scale)

    out = engine.linear(out.reshape(B, -1), p["wo"])
    if int8_kv:
        return out, k_pages, v_pages, k_scale, v_scale
    return out, k_pages, v_pages


def attention_decode(
    p: dict,
    x: Array,                      # (B, D) one new token per sequence
    cache_k: Array,                # (B, Hkv, Smax, Dh)
    cache_v: Array,
    lengths: Array,                # (B,) tokens already in cache
    cfg: ModelConfig,
    engine: SalPimEngine,
    *,
    cos: Array | None,             # (B, Dh/2) rope at current positions
    sin: Array | None,
    window: Optional[int] = None,
    update_cache: bool = True,
    kv_scales: Optional[tuple] = None,  # (k_scale, v_scale) int8-cache mode
):
    """One decode step; returns (out (B, D), new_k, new_v[, new_scales])."""
    B, _ = x.shape
    q, k, v = _decode_qkv(p, x, cfg, engine, cos, sin)

    int8_kv = kv_scales is not None
    if int8_kv:
        ksc, vsc = kv_scales                     # (B, Hkv, Smax)
        k_store, k_new_sc = _quantize_vec(k)     # int8 payloads
        v_store, v_new_sc = _quantize_vec(v)
    else:
        k_store, v_store = k, v

    if update_cache:
        # Sequential-bank concatenation: append the new K/V at `lengths`.
        if cfg.decode_uniform:
            # Steady-state batch decode: one shared position, a single
            # dynamic_update_slice (partitions across B/H/S shards).
            pos = lengths[0]
            cache_k = jax.lax.dynamic_update_slice(
                cache_k, k_store[:, :, None].astype(cache_k.dtype),
                (0, 0, pos, 0))
            cache_v = jax.lax.dynamic_update_slice(
                cache_v, v_store[:, :, None].astype(cache_v.dtype),
                (0, 0, pos, 0))
            if int8_kv:
                ksc = jax.lax.dynamic_update_slice(
                    ksc, k_new_sc[:, :, None], (0, 0, pos))
                vsc = jax.lax.dynamic_update_slice(
                    vsc, v_new_sc[:, :, None], (0, 0, pos))
        else:
            b_idx = jnp.arange(B)
            cache_k = cache_k.at[b_idx, :, lengths].set(
                k_store.astype(cache_k.dtype))
            cache_v = cache_v.at[b_idx, :, lengths].set(
                v_store.astype(cache_v.dtype))
            if int8_kv:
                ksc = ksc.at[b_idx, :, lengths].set(k_new_sc)
                vsc = vsc.at[b_idx, :, lengths].set(v_new_sc)
        valid = lengths + 1
    else:
        valid = lengths

    if int8_kv:
        k_read = (cache_k.astype(q.dtype) * ksc[..., None].astype(q.dtype))
        v_read = (cache_v.astype(q.dtype) * vsc[..., None].astype(q.dtype))
    else:
        k_read, v_read = cache_k, cache_v

    scale = cfg.attn_scale if cfg.attn_scale is not None else cfg.head_dim ** -0.5
    out = engine.decode_attention(
        q, k_read, v_read, valid, scale=scale,
        softcap=cfg.attn_softcap, window=window)
    out = engine.linear(out.reshape(B, -1), p["wo"])
    if int8_kv:
        return out, cache_k, cache_v, ksc, vsc
    return out, cache_k, cache_v
