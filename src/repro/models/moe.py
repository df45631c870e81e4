"""Mixture-of-Experts layer with sort-based token dispatch.

Design (TPU/pjit-native, EP over the "model" mesh axis):

* router: dense ``[T, E]`` logits -> top-k experts per token.
* dispatch: flatten the ``T*k`` assignments, rank each within its expert
  (sort-free rank via one-hot prefix counts would be O(T*E); we use an
  argsort over expert ids — O(Tk log Tk) — the standard dropped-token
  formulation), keep rank < capacity, scatter tokens into an ``[E, C, d]``
  buffer sharded on E.
* expert FFN: three grouped einsums over ``[E, C, d]`` — the MXU-shaped
  path; sharded on E this is expert parallelism, XLA inserts the
  all-to-alls at the dispatch/return boundaries.
* return: gather each token's k outputs from the buffer and combine with
  router weights.  Dropped tokens (over capacity) contribute zero — the
  classic GShard/Switch behaviour, surfaced via aux telemetry.

Beyond-paper integration: router load statistics are *streaming hypersparse
updates* — per step, each expert's hit count is an associative-array update
(expert_id -> count).  ``router_stats_triples`` exposes them in exactly the
triple format the hierarchical array ingests (DESIGN.md section 3.4).

DeepSeek-v3 aux-free balancing is supported: a per-expert bias added to the
routing scores *for selection only* (gradient-free), updated outside the
step from the streaming load stats.
"""
from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from .config import ModelConfig, MoEConfig
from .layers import _dense_init

Params = Dict[str, Any]


def init_moe(key, cfg: ModelConfig) -> Params:
    m = cfg.moe
    d, f = cfg.d_model, m.d_expert
    ks = jax.random.split(key, 5)
    p = {
        "router": _dense_init(ks[0], (d, m.n_experts)),
        "wg": _dense_init(ks[1], (m.n_experts, d, f)),
        "wu": _dense_init(ks[2], (m.n_experts, d, f)),
        "wd": _dense_init(ks[3], (m.n_experts, f, d)),
    }
    if m.n_shared:
        p["shared"] = {
            "wg": _dense_init(ks[4], (d, m.n_shared * f)),
            "wu": _dense_init(jax.random.fold_in(ks[4], 1), (d, m.n_shared * f)),
            "wd": _dense_init(jax.random.fold_in(ks[4], 2), (m.n_shared * f, d)),
        }
    if m.router_aux_free:
        p["router_bias"] = jnp.zeros((m.n_experts,))
    return p


def _capacity(m: MoEConfig, n_tokens: int) -> int:
    c = int(math.ceil(n_tokens * m.top_k * m.capacity_factor / m.n_experts))
    return max(8, ((c + 7) // 8) * 8)  # pad to vector-lane multiple


def apply_moe(
    p: Params,
    cfg: ModelConfig,
    x: jax.Array,  # [B, S, d]
    ep_axis: Optional[str] = "model",
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Returns (out [B, S, d], aux telemetry dict)."""
    if ep_axis is not None and EP_CONTEXT["mesh"] is not None:
        return apply_moe_shardmap(p, cfg, x, ep_axis)
    m = cfg.moe
    B, S, d = x.shape
    T = B * S
    xt = x.reshape(T, d)
    logits = jnp.einsum("td,de->te", xt, p["router"].astype(x.dtype))
    logits = logits.astype(jnp.float32) * m.router_scale
    gates = jax.nn.softmax(logits, axis=-1)
    select_scores = logits + (p["router_bias"] if m.router_aux_free else 0.0)
    _, top_idx = lax.top_k(select_scores, m.top_k)  # [T, k]
    top_gates = jnp.take_along_axis(gates, top_idx, axis=1)  # [T, k]
    top_gates = top_gates / (top_gates.sum(-1, keepdims=True) + 1e-9)

    # ---- dispatch: rank within expert, drop over capacity --------------
    C = _capacity(m, T)
    flat_expert = top_idx.reshape(T * m.top_k)  # [A]
    order = jnp.argsort(flat_expert, stable=True)
    sorted_expert = flat_expert[order]
    arange_a = jnp.arange(T * m.top_k, dtype=jnp.int32)
    run_start = jnp.searchsorted(sorted_expert, sorted_expert, side="left")
    rank_sorted = arange_a - run_start.astype(jnp.int32)
    rank = jnp.zeros_like(rank_sorted).at[order].set(rank_sorted)  # [A]
    rank = rank.reshape(T, m.top_k)
    keep = rank < C  # [T, k]
    slot = jnp.where(keep, top_idx * C + rank, m.n_experts * C)  # drop -> OOB

    buf = jnp.zeros((m.n_experts * C, d), x.dtype)
    # each token is written to up to k expert slots
    for kk in range(m.top_k):
        buf = buf.at[slot[:, kk]].set(xt, mode="drop")
    buf = buf.reshape(m.n_experts, C, d)
    if ep_axis is not None:
        buf = lax.with_sharding_constraint(buf, P(ep_axis, None, None))

    # ---- expert FFN (grouped einsum, MXU path) -------------------------
    g = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, p["wg"].astype(x.dtype)))
    u = jnp.einsum("ecd,edf->ecf", buf, p["wu"].astype(x.dtype))
    eo = jnp.einsum("ecf,efd->ecd", g * u, p["wd"].astype(x.dtype))
    if ep_axis is not None:
        eo = lax.with_sharding_constraint(eo, P(ep_axis, None, None))
    eo = eo.reshape(m.n_experts * C, d)

    # ---- combine --------------------------------------------------------
    out = jnp.zeros((T, d), x.dtype)
    for kk in range(m.top_k):
        safe = jnp.minimum(slot[:, kk], m.n_experts * C - 1)
        contrib = eo[safe] * top_gates[:, kk : kk + 1].astype(x.dtype)
        out = out + jnp.where(keep[:, kk : kk + 1], contrib, 0)

    # ---- shared experts (always-on dense path) --------------------------
    if "shared" in p:
        s = p["shared"]
        sg = jax.nn.silu(jnp.einsum("td,df->tf", xt, s["wg"].astype(x.dtype)))
        su = jnp.einsum("td,df->tf", xt, s["wu"].astype(x.dtype))
        out = out + jnp.einsum("tf,fd->td", sg * su, s["wd"].astype(x.dtype))

    # ---- telemetry: streaming load stats as associative-array triples ---
    load = jnp.zeros((m.n_experts,), jnp.float32)
    for kk in range(m.top_k):
        load = load.at[top_idx[:, kk]].add(1.0, mode="drop")
    importance = gates.sum(0)
    # Switch-style aux loss (used when not aux-free)
    aux_loss = m.n_experts * jnp.mean(
        (load / (T * m.top_k)) * (importance / jnp.maximum(importance.sum(), 1e-9))
    )
    dropped = (T * m.top_k) - keep.sum()
    aux = {
        "expert_load": load,
        "moe_aux_loss": aux_loss,
        "moe_dropped": dropped.astype(jnp.int32),
    }
    return out.reshape(B, S, d), aux


def router_stats_triples(load: jax.Array, layer_idx: int):
    """Expose per-step expert load as (row=layer, col=expert, val=count)
    triples for the hierarchical associative-array telemetry stream."""
    e = load.shape[0]
    rows = jnp.full((e,), layer_idx, jnp.int32)
    cols = jnp.arange(e, dtype=jnp.int32)
    return rows, cols, load


def update_aux_free_bias(bias: jax.Array, load: jax.Array, lr: float = 1e-3) -> jax.Array:
    """DeepSeek-v3 aux-free balancing: nudge under-loaded experts up,
    over-loaded down (sign update on the violation)."""
    mean = load.mean()
    return bias + lr * jnp.sign(mean - load)


# ---------------------------------------------------------------------------
# shard_map expert parallelism (§Perf hillclimb)
# ---------------------------------------------------------------------------
# The pjit dispatch above sorts the GLOBAL [T*k] assignment vector — under
# GSPMD that is a cross-device sort (all-to-all ladder) and dominates the
# collective term for MoE cells.  The EP path below routes entirely locally:
# every model shard sees each data shard's tokens (replicated over "model"),
# ranks only the assignments destined to ITS E/tp experts, runs its local
# expert FFNs, and a single psum over "model" combines contributions.
# Communication per MoE layer = one [B_local, S, d] all-reduce — the same
# cost as a Megatron FFN, with no global sort and no E x C redistribution.

EP_CONTEXT = {"mesh": None, "dp": None}  # set by the launcher (trace-time)


def apply_moe_ep_local(
    xt: jax.Array,  # [T, d] local tokens (replicated across the ep axis)
    router,
    router_bias,
    wg,
    wu,
    wd,  # local expert weights [E_local, ...]
    cfg: ModelConfig,
    ep_axis: str,
):
    m = cfg.moe
    T, d = xt.shape
    tp = lax.axis_size(ep_axis)
    E_local = m.n_experts // tp
    my_lo = lax.axis_index(ep_axis) * E_local

    logits = jnp.einsum("td,de->te", xt, router.astype(xt.dtype))
    logits = logits.astype(jnp.float32) * m.router_scale
    gates = jax.nn.softmax(logits, axis=-1)
    select = logits + (router_bias if router_bias is not None else 0.0)
    _, top_idx = lax.top_k(select, m.top_k)
    top_gates = jnp.take_along_axis(gates, top_idx, axis=1)
    top_gates = top_gates / (top_gates.sum(-1, keepdims=True) + 1e-9)

    C = _capacity(m, T)
    mine = (top_idx >= my_lo) & (top_idx < my_lo + E_local)  # [T, k]
    local_e = jnp.where(mine, top_idx - my_lo, E_local)
    flat = local_e.reshape(-1)
    order = jnp.argsort(flat, stable=True)
    sorted_e = flat[order]
    arange_a = jnp.arange(flat.shape[0], dtype=jnp.int32)
    run_start = jnp.searchsorted(sorted_e, sorted_e, side="left").astype(jnp.int32)
    rank = jnp.zeros_like(arange_a).at[order].set(arange_a - run_start)
    rank = rank.reshape(T, m.top_k)
    keep = mine & (rank < C)
    slot = jnp.where(keep, local_e * C + rank, E_local * C)

    buf = jnp.zeros((E_local * C, d), xt.dtype)
    for kk in range(m.top_k):
        buf = buf.at[slot[:, kk]].set(xt, mode="drop")
    buf = buf.reshape(E_local, C, d)
    g = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, wg.astype(xt.dtype)))
    u = jnp.einsum("ecd,edf->ecf", buf, wu.astype(xt.dtype))
    eo = jnp.einsum("ecf,efd->ecd", g * u, wd.astype(xt.dtype)).reshape(E_local * C, d)

    out = jnp.zeros((T, d), xt.dtype)
    for kk in range(m.top_k):
        safe = jnp.minimum(slot[:, kk], E_local * C - 1)
        contrib = eo[safe] * top_gates[:, kk : kk + 1].astype(xt.dtype)
        out = out + jnp.where(keep[:, kk : kk + 1], contrib, 0)
    out = lax.psum(out, ep_axis)  # combine across expert shards

    load_local = jnp.zeros((E_local,), jnp.float32)
    for kk in range(m.top_k):
        load_local = load_local.at[jnp.where(mine[:, kk], local_e[:, kk], E_local)].add(
            1.0, mode="drop"
        )
    dropped = lax.psum(((~keep) & mine).sum(), ep_axis)
    return out, load_local, dropped


def apply_moe_shardmap(p: Params, cfg: ModelConfig, x: jax.Array, ep_axis: str):
    """shard_map-EP MoE; requires EP_CONTEXT set by the launcher."""
    import functools

    mesh = EP_CONTEXT["mesh"]
    dp = EP_CONTEXT["dp"]
    m = cfg.moe
    B, S, d = x.shape
    spec_x = P(dp, None, None)
    spec_e = P(ep_axis, None, None)

    @functools.partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=(spec_x, P(None, None), (P(None) if m.router_aux_free else P()),
                  spec_e, spec_e, spec_e),
        out_specs=(spec_x, P(ep_axis), P()),
        check_vma=False,
    )
    def run(x_l, router, rbias, wg, wu, wd):
        Bl, Sl, dl = x_l.shape
        xt = x_l.reshape(Bl * Sl, dl)
        out, load_l, dropped = apply_moe_ep_local(
            xt, router, rbias if m.router_aux_free else None, wg, wu, wd, cfg, ep_axis
        )
        # aggregate load over data shards for telemetry
        for a in (dp if isinstance(dp, tuple) else (dp,)):
            load_l = lax.psum(load_l, a)
        return out.reshape(Bl, Sl, dl), load_l, dropped

    rbias = p.get("router_bias", jnp.zeros((), jnp.float32))
    out, load, dropped = run(x, p["router"], rbias, p["wg"], p["wu"], p["wd"])

    if "shared" in p:
        s = p["shared"]
        xt = x.reshape(B * S, d)
        sg = jax.nn.silu(jnp.einsum("td,df->tf", xt, s["wg"].astype(x.dtype)))
        su = jnp.einsum("td,df->tf", xt, s["wu"].astype(x.dtype))
        out = out + jnp.einsum("tf,fd->td", sg * su, s["wd"].astype(x.dtype)).reshape(
            B, S, d
        )

    importance = load / jnp.maximum(load.sum(), 1.0)
    aux_loss = m.n_experts * jnp.mean(importance * importance)  # proxy on EP path
    aux = {
        "expert_load": load,
        "moe_aux_loss": aux_loss,
        "moe_dropped": dropped.astype(jnp.int32),
    }
    return out, aux
