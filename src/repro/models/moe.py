"""Mixture-of-Experts FFN with expert parallelism (shard_map + all_to_all).

Layout (see docs/DESIGN.md §6):
  * tokens sequence-sharded over ('pod','data') × 'model' going in;
  * experts sharded over 'model' (kimi 384/16 = 24 per shard, deepseek 160/16 = 10);
  * each expert's d_ff sharded over 'data' (per-shard weight slice), producing a
    partial-sum output that is psum'd over 'data' *after* the return all_to_all
    (the un-dispatch deflates tokens k·cf-fold first — a deliberate collective-
    volume optimization, see EXPERIMENTS.md §Perf).

Dispatch is capacity-bounded (GShard-style token dropping) and implemented with
sort-free bucket slots (argsort + searchsorted) — static shapes throughout.
Without a mesh (CPU smoke tests) a dense fallback computes every expert.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from .layers import PDef
from .sharding import batch_axis_names, current_mesh, logical


def moe_defs(cfg) -> Dict[str, Any]:
    d = cfg.d_model
    mo = cfg.moe
    defs = {
        "router": PDef((d, mo.n_experts), (None, None)),
        "w_g": PDef((mo.n_experts, d, mo.d_expert), ("experts", None, "expert_dff")),
        "w_u": PDef((mo.n_experts, d, mo.d_expert), ("experts", None, "expert_dff")),
        "w_o": PDef((mo.n_experts, mo.d_expert, d), ("experts", "expert_dff", None)),
    }
    if mo.n_shared:
        f_sh = mo.n_shared * mo.d_expert
        defs["sh_g"] = PDef((d, f_sh), (None, "expert_dff"))
        defs["sh_u"] = PDef((d, f_sh), (None, "expert_dff"))
        defs["sh_o"] = PDef((f_sh, d), ("expert_dff", None))
    return defs


def bucket_slots(ids: jnp.ndarray, n_buckets: int) -> jnp.ndarray:
    """slot[i] = rank of element i within its bucket (stable, static shapes)."""
    n = ids.shape[0]
    order = jnp.argsort(ids)
    sorted_ids = ids[order]
    first = jnp.searchsorted(sorted_ids, jnp.arange(n_buckets), side="left")
    pos = jnp.arange(n) - first[sorted_ids]
    return jnp.zeros(n, jnp.int32).at[order].set(pos.astype(jnp.int32))


def _route(x_flat, router_w, mo):
    logits = (x_flat.astype(jnp.float32) @ router_w.astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    top_w, top_e = jax.lax.top_k(probs, mo.top_k)
    top_w = top_w / jnp.maximum(top_w.sum(-1, keepdims=True), 1e-9)
    # Switch-style load-balancing aux loss (local stats).
    E = mo.n_experts
    density = jnp.mean(jax.nn.one_hot(top_e[..., 0], E, dtype=jnp.float32), axis=0)
    prob_mean = jnp.mean(probs, axis=0)
    aux = E * jnp.sum(density * prob_mean)
    return top_w, top_e, aux


def _expert_ffn(buf, w_g, w_u, w_o, cdt):
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, w_g.astype(cdt)))
    u = jnp.einsum("ecd,edf->ecf", buf, w_u.astype(cdt))
    return jnp.einsum("ecf,efd->ecd", h * u, w_o.astype(cdt))


def _moe_dense_fallback(p, x, cfg):
    """No-mesh path: every expert on every token (reduced configs only)."""
    B, S, d = x.shape
    mo = cfg.moe
    cdt = jnp.bfloat16 if cfg.compute_dtype == "bfloat16" else jnp.float32
    xf = x.reshape(-1, d).astype(cdt)
    top_w, top_e, aux = _route(xf, p["router"], mo)
    h = jax.nn.silu(jnp.einsum("td,edf->tef", xf, p["w_g"].astype(cdt)))
    u = jnp.einsum("td,edf->tef", xf, p["w_u"].astype(cdt))
    outs = jnp.einsum("tef,efd->ted", h * u, p["w_o"].astype(cdt))
    gates = jnp.zeros((xf.shape[0], mo.n_experts), cdt).at[
        jnp.arange(xf.shape[0])[:, None], top_e].set(top_w.astype(cdt))
    y = jnp.einsum("te,ted->td", gates, outs)
    if mo.n_shared:
        y = y + (jax.nn.silu(xf @ p["sh_g"].astype(cdt))
                 * (xf @ p["sh_u"].astype(cdt))) @ p["sh_o"].astype(cdt)
    return y.reshape(B, S, d).astype(x.dtype), aux


def _moe_local(p, x, *, cfg, n_shards: int, e_loc: int, axis: str,
               data_axes: Tuple[str, ...], all_axes: Tuple[str, ...]):
    """Per-device body under shard_map (full mesh)."""
    mo = cfg.moe
    cdt = jnp.bfloat16 if cfg.compute_dtype == "bfloat16" else jnp.float32
    b_loc, s_loc, d = x.shape
    t = b_loc * s_loc
    xf = x.reshape(t, d).astype(cdt)
    top_w, top_e, aux = _route(xf, p["router"], mo)

    flat_e = top_e.reshape(-1)                              # (t*k,)
    src = jnp.repeat(jnp.arange(t, dtype=jnp.int32), mo.top_k)
    dest_shard = (flat_e // e_loc).astype(jnp.int32)
    local_e = (flat_e % e_loc).astype(jnp.int32)

    cap1 = int(math.ceil(t * mo.top_k / n_shards * mo.capacity_factor))
    slot1 = bucket_slots(dest_shard, n_shards)
    keep1 = slot1 < cap1
    send_idx = jnp.where(keep1, dest_shard * cap1 + slot1, n_shards * cap1)
    send = jnp.zeros((n_shards * cap1, d), cdt).at[send_idx].set(
        xf[src], mode="drop")
    send_e = jnp.full((n_shards * cap1,), 0, jnp.int32).at[send_idx].set(
        local_e, mode="drop")
    send_valid = jnp.zeros((n_shards * cap1,), jnp.bool_).at[send_idx].set(
        True, mode="drop")

    recv = jax.lax.all_to_all(send.reshape(n_shards, cap1, d), axis, 0, 0,
                              tiled=False).reshape(-1, d)
    recv_e = jax.lax.all_to_all(send_e.reshape(n_shards, cap1), axis, 0, 0,
                                tiled=False).reshape(-1)
    recv_valid = jax.lax.all_to_all(send_valid.reshape(n_shards, cap1), axis,
                                    0, 0, tiled=False).reshape(-1)

    n_recv = n_shards * cap1
    cap2 = int(math.ceil(n_recv / e_loc * mo.capacity_factor))
    eid = jnp.where(recv_valid, recv_e, e_loc)              # invalid → overflow
    slot2 = bucket_slots(eid, e_loc + 1)
    keep2 = (slot2 < cap2) & recv_valid
    buf_idx = jnp.where(keep2, eid * cap2 + slot2, e_loc * cap2)
    buf = jnp.zeros((e_loc * cap2 + 1, d), cdt).at[buf_idx].set(recv, mode="drop")
    buf = buf[:-1].reshape(e_loc, cap2, d)

    out = _expert_ffn(buf, p["w_g"], p["w_u"], p["w_o"], cdt)   # partial over f

    back = out.reshape(-1, d)[jnp.minimum(buf_idx, e_loc * cap2 - 1)]
    back = jnp.where(keep2[:, None], back, 0.0)
    ret = jax.lax.all_to_all(back.reshape(n_shards, cap1, d), axis, 0, 0,
                             tiled=False).reshape(-1, d)

    gathered = ret[jnp.minimum(send_idx, n_shards * cap1 - 1)]
    gathered = jnp.where(keep1[:, None], gathered, 0.0)
    y = jnp.zeros((t, d), cdt).at[src].add(
        gathered * top_w.reshape(-1)[:, None].astype(cdt))

    if mo.n_shared:
        y = y + (jax.nn.silu(xf @ p["sh_g"].astype(cdt))
                 * (xf @ p["sh_u"].astype(cdt))) @ p["sh_o"].astype(cdt)
    # d_ff slices are data-sharded → outputs are partial sums over 'data'.
    if data_axes:
        y = jax.lax.psum(y, data_axes)
    aux = jax.lax.pmean(aux, all_axes)
    return y.reshape(b_loc, s_loc, d).astype(x.dtype), aux


def _moe_replicated_local(p, x, *, cfg, n_shards: int, e_loc: int, axis: str,
                          data_axes: Tuple[str, ...], all_axes: Tuple[str, ...]):
    """Decode-shape path: tokens replicated over 'model' (S=1 cannot be
    sequence-sharded).  Replication substitutes the dispatch broadcast: every
    shard routes the full token set, computes only its *own* experts, and the
    expert outputs are combined with a psum over 'model' — the canonical
    all-gather + local-expert + reduce decode EP."""
    mo = cfg.moe
    cdt = jnp.bfloat16 if cfg.compute_dtype == "bfloat16" else jnp.float32
    b_loc, s_loc, d = x.shape
    t = b_loc * s_loc
    xf = x.reshape(t, d).astype(cdt)
    top_w, top_e, aux = _route(xf, p["router"], mo)
    my_shard = jax.lax.axis_index(axis)

    flat_e = top_e.reshape(-1)
    src = jnp.repeat(jnp.arange(t, dtype=jnp.int32), mo.top_k)
    mine = (flat_e // e_loc) == my_shard
    local_e = jnp.where(mine, flat_e % e_loc, e_loc)        # foreign → overflow
    cap = int(math.ceil(t * mo.top_k / e_loc * mo.capacity_factor))
    slot = bucket_slots(local_e, e_loc + 1)
    keep = (slot < cap) & mine
    idx = jnp.where(keep, local_e * cap + slot, e_loc * cap)
    buf = jnp.zeros((e_loc * cap + 1, d), cdt).at[idx].set(xf[src], mode="drop")
    buf = buf[:-1].reshape(e_loc, cap, d)
    out = _expert_ffn(buf, p["w_g"], p["w_u"], p["w_o"], cdt)
    gathered = out.reshape(-1, d)[jnp.minimum(idx, e_loc * cap - 1)]
    gathered = jnp.where(keep[:, None], gathered, 0.0)
    y = jnp.zeros((t, d), cdt).at[src].add(
        gathered * top_w.reshape(-1)[:, None].astype(cdt))
    y = jax.lax.psum(y, (axis,))                            # combine experts
    if mo.n_shared:
        y = y + (jax.nn.silu(xf @ p["sh_g"].astype(cdt))
                 * (xf @ p["sh_u"].astype(cdt))) @ p["sh_o"].astype(cdt)
    if data_axes:
        y = jax.lax.psum(y, data_axes)                      # d_ff partial sums
    aux = jax.lax.pmean(aux, all_axes)
    return y.reshape(b_loc, s_loc, d).astype(x.dtype), aux


def moe_apply(p, x, *, cfg) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """x: (B, S, D) → (y, aux_loss)."""
    mesh = current_mesh()
    if mesh is None or "model" not in mesh.axis_names or mesh.shape["model"] == 1:
        return _moe_dense_fallback(p, x, cfg)
    n_shards = mesh.shape["model"]
    e_loc = cfg.moe.n_experts // n_shards
    assert cfg.moe.n_experts % n_shards == 0
    batch_axes = batch_axis_names(mesh)
    data_axes = tuple(a for a in ("data",) if a in mesh.axis_names)
    pspecs = {
        "router": P(None, None),
        "w_g": P("model", None, "data"),
        "w_u": P("model", None, "data"),
        "w_o": P("model", "data", None),
    }
    if cfg.moe.n_shared:
        pspecs.update({"sh_g": P(None, "data"), "sh_u": P(None, "data"),
                       "sh_o": P("data", None)})
    seq_shardable = x.shape[1] % n_shards == 0
    body = _moe_local if seq_shardable else _moe_replicated_local
    x_spec = P(batch_axes, "model" if seq_shardable else None, None)
    fn = jax.shard_map(
        partial(body, cfg=cfg, n_shards=n_shards, e_loc=e_loc,
                axis="model", data_axes=data_axes,
                all_axes=tuple(mesh.axis_names)),
        mesh=mesh,
        in_specs=({k: pspecs[k] for k in p}, x_spec),
        out_specs=(x_spec, P()),
        check_vma=False)
    return fn(p, x)
