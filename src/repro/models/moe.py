"""Mixture-of-Experts with expert parallelism (olmoe 64e/top-8, phi3.5 16e/top-2).

SAL-PIM mapping: experts are *independent weights* -> the paper's rule
"each channel gets weights that need no accumulation" puts the expert dim
on the `model` axis (EP). The router's softmax rides the LUT-exp path.

Dispatch is the GShard *grouped* formulation: tokens are split into G
groups (G aligned with the data axis), position-in-expert and capacity
are computed per group, and the dispatch buffer is (G, E, C, d) sharded
G->data, E->model. Every scatter/gather then addresses only local shards
— the dry-run HLO shows zero dispatch collectives; token->expert traffic
rides the (already necessary) resharding of the buffer between the G-major
and E-major einsum operands, which GSPMD lowers to the all-to-all
equivalent. Capacity-per-group is the standard GShard semantics.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.salpim import SalPimEngine
from repro.distributed.api import constrain
from repro.models.config import ModelConfig

Array = jax.Array


def init_moe(key, cfg: ModelConfig) -> dict:
    d = cfg.d_model
    f = cfg.moe_ff
    e = cfg.n_experts
    ks = jax.random.split(key, 4)
    p = {
        "router": (jax.random.normal(ks[0], (e, d)) * d**-0.5).astype(jnp.float32),
        "w_up": (jax.random.normal(ks[1], (e, f, d)) * d**-0.5).astype(cfg.pdtype),
        "w_down": (jax.random.normal(ks[2], (e, d, f)) * f**-0.5).astype(cfg.pdtype),
    }
    if cfg.gated_mlp:
        p["w_gate"] = (jax.random.normal(ks[3], (e, f, d)) * d**-0.5).astype(cfg.pdtype)
    return p



def _as_weight(w, dtype):
    """Materialize a weight operand: dequantize QTensor (int8 serving) or cast."""
    if type(w).__name__ == "QTensor":
        return (w.w_i8.astype(dtype)
                * w.scale[..., None].astype(dtype))
    return w.astype(dtype)

def _num_groups(n_tokens: int) -> int:
    for g in (256, 128, 64, 32, 16, 8, 4, 2):
        if n_tokens % g == 0 and n_tokens // g >= 32:
            return g
    return 1


def _capacity(cfg: ModelConfig, group_tokens: int) -> int:
    cap = int(cfg.router_cap_factor * cfg.top_k * group_tokens / cfg.n_experts)
    return min(max(cap, cfg.top_k), group_tokens)


def apply_moe(p: dict, x: Array, cfg: ModelConfig, engine: SalPimEngine,
              *, return_aux: bool = False):
    """x (..., D) -> (..., D). Per-group capacity drop (cf=1.25)."""
    if cfg.moe_impl == "shardmap" and not return_aux:
        from repro.distributed.api import current_mesh
        mesh = current_mesh()
        if (mesh is not None and "model" in mesh.axis_names
                and cfg.n_experts % mesh.shape["model"] == 0):
            T = 1
            for s in x.shape[:-1]:
                T *= s
            dp = 1
            for a in ("pod", "data"):
                if a in mesh.axis_names:
                    dp *= mesh.shape[a]
            if _num_groups(T) % dp == 0:
                return _apply_moe_shardmap(p, x, cfg, engine, mesh)
    return _apply_moe_gspmd(p, x, cfg, engine, return_aux=return_aux)


def _dispatch_local(xg, tii, tiw, E, C, e_lo, e_loc):
    """Group-local dispatch of tokens to experts in [e_lo, e_lo + e_loc).

    xg (G, Tg, d); tii/tiw (G, Tg, k). e_lo may be a traced per-shard
    offset (axis_index-derived); e_loc is static. Returns buf
    (G, e_loc, C, d) plus the combine indices. Identical capacity
    semantics to the gspmd path: position-in-expert is computed against
    ALL experts (so the capacity winner set matches), then filtered to
    the local expert slice.
    """
    G, Tg, d = xg.shape
    k = tii.shape[-1]
    assign = jax.nn.one_hot(tii, E, dtype=jnp.int32)
    flat = assign.transpose(0, 2, 1, 3).reshape(G, k * Tg, E)
    pos_in_e = jnp.cumsum(flat, axis=1) - flat
    pos = jnp.sum(pos_in_e * flat, axis=-1)
    eid = tii.transpose(0, 2, 1).reshape(G, k * Tg)
    keep = (pos < C) & (eid >= e_lo) & (eid < e_lo + e_loc)
    w_flat = tiw.transpose(0, 2, 1).reshape(G, k * Tg) * keep
    tok_idx = jnp.tile(jnp.arange(Tg), (k,))[None].repeat(G, 0)
    safe_pos = jnp.where(keep, pos, C - 1)
    local_eid = jnp.clip(eid - e_lo, 0, e_loc - 1)
    buf = jnp.zeros((G, e_loc, C, d), xg.dtype)
    src = (xg[jnp.arange(G)[:, None], tok_idx]
           * keep[..., None].astype(xg.dtype))
    buf = buf.at[jnp.arange(G)[:, None], local_eid, safe_pos].add(
        src, mode="drop")
    return buf, (local_eid, safe_pos, tok_idx, w_flat, keep)


def _apply_moe_shardmap(p: dict, x: Array, cfg: ModelConfig,
                        engine: SalPimEngine, mesh):
    """Explicit EP: dispatch/combine are shard-local; one psum('model').

    Device (data=i, model=j) holds token groups G_i (replicated over j)
    and experts E_j. It routes its own tokens to its own experts — zero
    dispatch communication — computes the expert FFN, combines locally,
    and a single psum over 'model' sums the per-expert-shard partial
    outputs. Cross-pod: the batch axis includes 'pod', handled by the
    in_specs; no pod collective is introduced.
    """
    from jax.sharding import PartitionSpec as P

    lead = x.shape[:-1]
    d = x.shape[-1]
    xt = x.reshape(-1, d)
    T = xt.shape[0]
    E, k = cfg.n_experts, cfg.top_k
    G = _num_groups(T)
    Tg = T // G
    C = _capacity(cfg, Tg)
    M = mesh.shape["model"]
    dp_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    act = engine.nl.activation(cfg.activation)

    def local(xg, router, w_gate, w_up, w_down):
        # xg (G_loc, Tg, d); expert weights already sliced to E_loc.
        j = jax.lax.axis_index("model")
        e_loc = E // M
        e_lo = j * e_loc
        logits = jnp.einsum("gtd,ed->gte", xg.astype(jnp.float32), router)
        weights = engine.softmax(logits, axis=-1)
        tiw, tii = jax.lax.top_k(weights, k)
        tiw = tiw / jnp.maximum(jnp.sum(tiw, -1, keepdims=True), 1e-9)
        buf, (leid, spos, tok, wf, keep) = _dispatch_local(
            xg, tii, tiw, E, C, e_lo, e_loc)
        if cfg.gated_mlp:
            h = act(jnp.einsum("gecd,efd->gecf", buf, _as_weight(w_gate, buf.dtype))) \
                * jnp.einsum("gecd,efd->gecf", buf, _as_weight(w_up, buf.dtype))
        else:
            h = act(jnp.einsum("gecd,efd->gecf", buf, _as_weight(w_up, buf.dtype)))
        out_buf = jnp.einsum("gecf,edf->gecd", h, _as_weight(w_down, h.dtype))
        gathered = out_buf[jnp.arange(buf.shape[0])[:, None], leid, spos]
        contrib = gathered * wf[..., None].astype(gathered.dtype)
        partial = jnp.zeros_like(xg).at[
            jnp.arange(buf.shape[0])[:, None], tok].add(contrib)
        return jax.lax.psum(partial, "model")

    xg = xt.reshape(G, Tg, d)
    gspec = P(dp_axes if len(dp_axes) > 1 else (dp_axes[0] if dp_axes else None))
    espec = P("model")
    out = jax.shard_map(
        local, mesh=mesh,
        in_specs=(gspec, P(), espec, espec, espec),
        out_specs=gspec,
        check_vma=False,
    )(xg, p["router"],
      p.get("w_gate", p["w_up"]), p["w_up"], p["w_down"])
    return out.reshape(*lead, d)


def _apply_moe_gspmd(p: dict, x: Array, cfg: ModelConfig,
                     engine: SalPimEngine, *, return_aux: bool = False):
    """Baseline: GSPMD auto-partitioned grouped dispatch."""
    lead = x.shape[:-1]
    d = x.shape[-1]
    xt = x.reshape(-1, d)
    T = xt.shape[0]
    E, k = cfg.n_experts, cfg.top_k
    G = _num_groups(T)
    Tg = T // G
    C = _capacity(cfg, Tg)

    logits = engine.linear(xt.astype(jnp.float32), p["router"])       # (T, E)
    weights_full = engine.softmax(logits, axis=-1)
    topw, topi = jax.lax.top_k(weights_full, k)                        # (T, k)
    topw = topw / jnp.maximum(jnp.sum(topw, -1, keepdims=True), 1e-9)

    # Group-local dispatch bookkeeping: (G, Tg, ...) with the group dim on
    # the data axis -> all indexing below is shard-local.
    xg = constrain(xt.reshape(G, Tg, d), "batch", None, None)
    tiw = topw.reshape(G, Tg, k)
    tii = topi.reshape(G, Tg, k)
    assign = jax.nn.one_hot(tii, E, dtype=jnp.int32)                   # (G,Tg,k,E)
    # slot-major cumsum so earlier tokens win capacity (GShard order)
    flat = assign.transpose(0, 2, 1, 3).reshape(G, k * Tg, E)
    pos_in_e = jnp.cumsum(flat, axis=1) - flat
    pos = jnp.sum(pos_in_e * flat, axis=-1)                            # (G, kTg)
    eid = tii.transpose(0, 2, 1).reshape(G, k * Tg)
    keep = pos < C
    w_flat = tiw.transpose(0, 2, 1).reshape(G, k * Tg) * keep

    tok_idx = jnp.tile(jnp.arange(Tg), (k,))[None].repeat(G, 0)        # (G, kTg)
    safe_pos = jnp.where(keep, pos, C - 1)

    # Scatter into (G, E, C, d): G->data, E->model; group-local writes.
    buf = jnp.zeros((G, E, C, d), xt.dtype)
    src = (xg[jnp.arange(G)[:, None], tok_idx] *
           keep[..., None].astype(xt.dtype))                           # (G,kTg,d)
    buf = buf.at[jnp.arange(G)[:, None], eid, safe_pos].add(src, mode="drop")
    buf = constrain(buf, "batch", "expert", None, None)

    # Expert FFN, batched over (G, E); weights sharded on `model`.
    if cfg.gated_mlp:
        gate = jnp.einsum("gecd,efd->gecf", buf, _as_weight(p["w_gate"], buf.dtype))
        up = jnp.einsum("gecd,efd->gecf", buf, _as_weight(p["w_up"], buf.dtype))
        h = engine.nl.activation(cfg.activation)(gate) * up
    else:
        h = engine.nl.activation(cfg.activation)(
            jnp.einsum("gecd,efd->gecf", buf, _as_weight(p["w_up"], buf.dtype)))
    h = constrain(h, "batch", "expert", None, None)
    out_buf = jnp.einsum("gecf,edf->gecd", h, _as_weight(p["w_down"], h.dtype))
    out_buf = constrain(out_buf, "batch", "expert", None, None)

    # Combine: gather each token's k expert outputs (group-local), weight.
    gathered = out_buf[jnp.arange(G)[:, None], eid, safe_pos]          # (G,kTg,d)
    contrib = gathered * w_flat[..., None].astype(gathered.dtype)
    out = jnp.zeros_like(xg).at[jnp.arange(G)[:, None], tok_idx].add(contrib)
    out = out.reshape(T, d)

    if return_aux:
        me = jnp.mean(weights_full, axis=0)
        ce = jnp.mean(
            jnp.sum(assign, axis=2).reshape(T, E).astype(jnp.float32), axis=0)
        aux = {
            "load_balance_loss": E * jnp.sum(me * ce),
            "drop_fraction": 1.0 - jnp.mean(keep.astype(jnp.float32)),
        }
        return out.reshape(*lead, d), aux
    return out.reshape(*lead, d)
