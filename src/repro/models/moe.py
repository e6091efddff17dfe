"""Mixture-of-Experts layer: top-k routing with sort-based capacity
dispatch (gather -> grouped einsum -> scatter-add), a dropless dispatch
over the experts held on this device, plus a dense oracle used by tests.
Every dispatch reports ``MoEStats``, whose format this module alone
knows (``add_stats``, ``sum_stats``, ``aux_terms``).

Sharding: experts across the 'model' axis when E divides it (dbrx, EP);
otherwise each expert's d_ff is tensor-parallel (granite, E=40).  The
dispatch is written with global gathers so GSPMD inserts the all-to-all.
"""

from __future__ import annotations

from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp

from .. import tracing
from ..dist import constrain
from .config import ArchConfig
from .spec import ParamSpec

__all__ = ["MoEStats", "COUNTERS", "moe_specs", "moe_apply",
           "moe_apply_dense", "add_stats", "sum_stats", "aux_terms"]


class MoEStats(NamedTuple):
    """What an expert layer reports besides its output."""
    aux: jax.Array       # [B] each row's Switch loss, E sum_e f_e p_e over
    #                      its own tokens and all E experts
    routed: jax.Array    # assignments that landed on the held experts
    max_load: jax.Array  # the largest held expert's count over the mean


# the metrics ``aux_terms`` reports besides ``aux_loss``
COUNTERS = ("moe_routed", "moe_max_load")


def _stats(probs: jax.Array, onehot: jax.Array, B: int, cfg: ArchConfig
           ) -> MoEStats:
    """probs [T, E] and the top-k picks one-hot [T, K, E] -> MoEStats."""
    m = cfg.moe
    T, K, E = onehot.shape
    S = T // B
    first, H = m.held_range
    picks = onehot.sum(1)                                    # [T, E]
    f = picks.reshape(B, S, E).sum(1) / (S * K)              # [B, E]
    p = probs.reshape(B, S, E).mean(1)
    load = picks[:, first:first + H].sum(0)
    return MoEStats(E * jnp.sum(f * p, axis=-1), load.sum(),
                    load.max() / jnp.maximum(load.mean(), 1e-9))


def add_stats(total, stats):
    """The blocks' aux added over layers.  A block without an expert
    layer gives a scalar 0; expert layers give ``MoEStats``, whose aux
    and routed counts add and whose max_load is the largest layer's."""
    if not isinstance(stats, MoEStats):
        return total if isinstance(total, MoEStats) else total + stats
    if not isinstance(total, MoEStats):
        return stats
    return MoEStats(total.aux + stats.aux, total.routed + stats.routed,
                    jnp.maximum(total.max_load, stats.max_load))


def sum_stats(stacked):
    """``add_stats`` over the leading (layer-group) dim of a scan's aux."""
    if not isinstance(stacked, MoEStats):
        return stacked.sum()
    return MoEStats(stacked.aux.sum(0), stacked.routed.sum(0),
                    stacked.max_load.max(0))


def aux_terms(aux, weight: jax.Array):
    """(the aux loss a step adds, the metrics it reports).  Expert
    layers' ``MoEStats``: each row's aux weighted by its decode weight
    like its CE, sum_r w_r aux_r, so each row's gradient is its own;
    metrics ``aux_loss`` (the rows' mean, like ``mean_ce``) and the
    ``COUNTERS``.  A scalar (no expert layer): as it is."""
    if not isinstance(aux, MoEStats):
        return aux, {"aux_loss": aux}
    return (aux.aux * weight).sum(), {"aux_loss": aux.aux.mean(),
                                      "moe_routed": aux.routed,
                                      "moe_max_load": aux.max_load}


def _expert_axes(cfg: ArchConfig, prefix_len: int):
    L = tuple("layers" for _ in range(prefix_len))
    if cfg.moe.expert_shard == "ep":
        return (L + ("experts", None, None), L + ("experts", None, None))
    return (L + (None, None, "expert_mlp"), L + (None, "expert_mlp", None))


def moe_specs(cfg: ArchConfig, prefix_shape=()) -> dict:
    m = cfg.moe
    d, f, E = cfg.d_model, m.d_ff_expert, m.num_experts
    # storage: the held share, or all experts padded for EP divisibility;
    # the router stays E wide
    Ep = m.held_range[1]
    up_axes, down_axes = _expert_axes(cfg, len(prefix_shape))
    L = tuple("layers" for _ in prefix_shape)
    gated = cfg.act in ("swiglu", "geglu")
    out = {
        "router": ParamSpec(prefix_shape + (d, E), L + (None, None), scale=0.1),
        "wi": ParamSpec(prefix_shape + (Ep, d, f), up_axes),
        "wo": ParamSpec(prefix_shape + (Ep, f, d), down_axes),
    }
    if gated:
        out["wg"] = ParamSpec(prefix_shape + (Ep, d, f), up_axes)
    if m.num_shared:
        S = m.num_shared
        out["shared_wi"] = ParamSpec(prefix_shape + (S, d, f), up_axes)
        out["shared_wo"] = ParamSpec(prefix_shape + (S, f, d), down_axes)
        if gated:
            out["shared_wg"] = ParamSpec(prefix_shape + (S, d, f), up_axes)
    return out


def _act(g, u, act):
    if act == "swiglu":
        return jax.nn.silu(g) * u
    if act == "geglu":
        return jax.nn.gelu(g) * u
    return jax.nn.gelu(u)


def _expert_ffn(tokens, wi, wg, wo, act):
    """tokens [E, C, d] -> [E, C, d] through per-expert FFNs."""
    u = jnp.einsum("ecd,edf->ecf", tokens, wi)
    if wg is not None:
        g = jnp.einsum("ecd,edf->ecf", tokens, wg)
        h = _act(g, u, act)
    else:
        h = _act(None, u, act)
    return jnp.einsum("ecf,efd->ecd", h, wo)


def moe_apply(params: dict, x: jax.Array, cfg: ArchConfig
              ) -> Tuple[jax.Array, MoEStats]:
    """Top-k MoE.  x [B, S, d] -> (y [B, S, d], MoEStats): the stats'
    aux is each row's load-balancing loss (Switch), E * sum_e f_e * p_e,
    where f_e = the row's fraction of picks on e, p_e = its mean router
    prob.

    dispatch='global'  sorts over all B*S tokens (baseline; replicated
    dispatch buffers under GSPMD).
    dispatch='grouped' vmaps the dispatch over the batch dim, so the
    sort/gather/scatter stay local to each data shard; capacity is per
    sequence (C = cf*S*K/E).  See EXPERIMENTS.md Sec-Perf / granite.
    dispatch='dropless' drops nothing and computes only the held
    experts (``_moe_apply_dropless``).
    """
    if cfg.moe.dispatch == "grouped":
        return _moe_apply_grouped(params, x, cfg)
    if cfg.moe.dispatch == "dropless":
        return _moe_apply_dropless(params, x, cfg)
    m = cfg.moe
    B, S, d = x.shape
    T = B * S
    E, K = m.num_experts, m.top_k
    flat = x.reshape(T, d)

    logits = jnp.einsum("td,de->te", flat, params["router"]).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, top_ids = jax.lax.top_k(probs, K)             # [T, K]
    gate_vals = gate_vals / jnp.maximum(gate_vals.sum(-1, keepdims=True), 1e-9)

    stats = _stats(probs, jax.nn.one_hot(top_ids, E, dtype=jnp.float32),
                   B, cfg)

    # ---- sort-based capacity dispatch ----
    Ep = m.e_padded          # dummy expert rows stay at the sentinel
    C = max(1, int(m.capacity_factor * T * K / E))
    flat_e = top_ids.reshape(-1)                              # [T*K]
    flat_g = gate_vals.reshape(-1).astype(x.dtype)
    flat_t = jnp.repeat(jnp.arange(T), K)                     # token index per slot
    order = jnp.argsort(flat_e, stable=True)
    se, st, sg = flat_e[order], flat_t[order], flat_g[order]
    # position within each expert's group
    group_start = jnp.searchsorted(se, jnp.arange(E), side="left")
    pos = jnp.arange(T * K) - group_start[se]
    # dispatch index matrix: token id per (expert, slot); T = sentinel pad.
    # over-capacity slots have pos >= C and are dropped by scatter mode.
    disp = jnp.full((Ep, C), T, jnp.int32)
    disp = disp.at[se, pos].set(st.astype(jnp.int32), mode="drop")
    gmat = jnp.zeros((Ep, C), x.dtype)
    gmat = gmat.at[se, pos].set(sg, mode="drop")

    padded = jnp.concatenate([flat, jnp.zeros((1, d), flat.dtype)], axis=0)
    gathered = padded[disp]                                   # [E, C, d]
    gathered = constrain(gathered, "act_experts", None, None)

    y = _expert_ffn(gathered, params["wi"], params.get("wg"), params["wo"], cfg.act)
    y = y * gmat[..., None]

    out = jnp.zeros((T + 1, d), y.dtype).at[disp.reshape(-1)].add(
        y.reshape(Ep * C, d))[:T]

    # ---- shared experts (always-on) ----
    if m.num_shared:
        sh = _expert_ffn(
            jnp.broadcast_to(flat, (m.num_shared,) + flat.shape),
            params["shared_wi"], params.get("shared_wg"),
            params["shared_wo"], cfg.act).sum(0)
        out = out + sh

    return out.reshape(B, S, d).astype(x.dtype), stats


def _moe_apply_grouped(params: dict, x: jax.Array, cfg: ArchConfig
                       ) -> Tuple[jax.Array, MoEStats]:
    """Per-sequence capacity dispatch (vmapped over batch).

    Identical routing to the global path; only the capacity pool is per
    sequence, so the sort/gather/scatter indices never cross the batch
    dim — under GSPMD every dispatch buffer inherits the batch sharding
    and stays on its data shard (no replicated [E, B*S*K/E, d] temps, no
    all-gather of the sort keys).
    """
    m = cfg.moe
    B, S, d = x.shape
    E, K = m.num_experts, m.top_k
    Ep = m.e_padded
    C = max(1, int(m.capacity_factor * S * K / E))

    logits = jnp.einsum("bsd,de->bse", x,
                        params["router"]).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, top_ids = jax.lax.top_k(probs, K)               # [B, S, K]
    gate_vals = gate_vals / jnp.maximum(gate_vals.sum(-1, keepdims=True),
                                        1e-9)

    stats = _stats(probs.reshape(B * S, E),
                   jax.nn.one_hot(top_ids.reshape(B * S, K), E,
                                  dtype=jnp.float32), B, cfg)

    def dispatch_one(xb, ids, gates):
        """xb [S, d]; ids/gates [S, K] -> (y [S, d])."""
        flat_e = ids.reshape(-1)                               # [S*K]
        flat_g = gates.reshape(-1).astype(xb.dtype)
        flat_t = jnp.repeat(jnp.arange(S), K)
        order = jnp.argsort(flat_e, stable=True)
        se, st, sg = flat_e[order], flat_t[order], flat_g[order]
        group_start = jnp.searchsorted(se, jnp.arange(E), side="left")
        pos = jnp.arange(S * K) - group_start[se]
        disp = jnp.full((Ep, C), S, jnp.int32)
        disp = disp.at[se, pos].set(st.astype(jnp.int32), mode="drop")
        gmat = jnp.zeros((Ep, C), xb.dtype).at[se, pos].set(sg, mode="drop")
        padded = jnp.concatenate([xb, jnp.zeros((1, d), xb.dtype)], axis=0)
        return padded[disp], gmat, disp                        # [Ep, C, d]

    gathered, gmat, disp = jax.vmap(dispatch_one)(x, top_ids, gate_vals)
    gathered = constrain(gathered, "batch", "act_experts", None, None)

    def ffn_b(g):
        return _expert_ffn(g, params["wi"], params.get("wg"), params["wo"],
                           cfg.act)

    y = jax.vmap(ffn_b)(gathered) * gmat[..., None]            # [B, E, C, d]

    def scatter_one(yb, dispb):
        return jnp.zeros((S + 1, d), yb.dtype).at[dispb.reshape(-1)].add(
            yb.reshape(Ep * C, d))[:S]

    out = jax.vmap(scatter_one)(y, disp)                       # [B, S, d]

    if m.num_shared:
        flat = x.reshape(B * S, d)
        sh = _expert_ffn(
            jnp.broadcast_to(flat, (m.num_shared,) + flat.shape),
            params["shared_wi"], params.get("shared_wg"),
            params["shared_wo"], cfg.act).sum(0)
        out = out + sh.reshape(B, S, d)

    return out.astype(x.dtype), stats


def _held_experts(params: dict, flat: jax.Array, gates: jax.Array,
                  cfg: ArchConfig) -> jax.Array:
    """[T, d]: the held experts' part.  Every held expert runs on every
    token, its output times the token's gate on it (``gates`` [T, H],
    zero on the experts the token did not pick): T * H rows, at least
    the T * min(K, H) any routing can send here, so nothing is dropped
    and the cost is the same whatever the routing."""
    with jax.named_scope(tracing.MOE_EXPERTS):
        u = jnp.einsum("td,edf->tef", flat, params["wi"])
        g = jnp.einsum("td,edf->tef", flat, params["wg"]) \
            if "wg" in params else None
        h = (_act(g, u, cfg.act).astype(jnp.float32)
             * gates[..., None]).astype(u.dtype)
        return jnp.einsum("tef,efd->td", h, params["wo"])


def _moe_apply_dropless(params: dict, x: jax.Array, cfg: ArchConfig
                        ) -> Tuple[jax.Array, MoEStats]:
    """Top-k routing over all experts; every assignment to an expert held
    here (``MoEConfig.held``) is computed, none dropped, and the others
    are left to the devices that hold them (``_held_experts``).  A row's
    output and aux loss depend on that row alone."""
    m = cfg.moe
    B, S, d = x.shape
    T = B * S
    E, K = m.num_experts, m.top_k
    first, H = m.held_range
    with jax.named_scope(tracing.MOE_DISPATCH):
        flat = x.reshape(T, d)
        logits = jnp.einsum("td,de->te", flat,
                            params["router"]).astype(jnp.float32)
        probs = jax.nn.softmax(logits, axis=-1)
        top_ids = jax.lax.top_k(probs, K)[1]                 # [T, K]
        # the gates read through the one-hot picks, so their gradient
        # is elementwise, not a scatter into [T, E]
        onehot = jax.nn.one_hot(top_ids, E, dtype=jnp.float32)  # [T, K, E]
        gate_vals = jnp.sum(onehot * probs[:, None, :], axis=-1)
        gate_vals = gate_vals / jnp.maximum(
            gate_vals.sum(-1, keepdims=True), 1e-9)
        gates = jnp.sum(onehot[..., first:first + H]
                        * gate_vals[..., None], axis=1)      # [T, H]
        stats = _stats(probs, onehot, B, cfg)

    out = _held_experts(params, flat, gates, cfg)
    if m.num_shared:
        out = out + _expert_ffn(
            jnp.broadcast_to(flat, (m.num_shared,) + flat.shape),
            params["shared_wi"], params.get("shared_wg"),
            params["shared_wo"], cfg.act).sum(0)
    return out.reshape(B, S, d).astype(x.dtype), stats


def moe_apply_dense(params: dict, x: jax.Array, cfg: ArchConfig
                    ) -> Tuple[jax.Array, MoEStats]:
    """Oracle: run every expert on every token, weight by (renormalized)
    top-k gates.  O(E) compute — test-only."""
    m = cfg.moe
    B, S, d = x.shape
    T = B * S
    E, K = m.num_experts, m.top_k
    flat = x.reshape(T, d)
    logits = jnp.einsum("td,de->te", flat, params["router"]).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, top_ids = jax.lax.top_k(probs, K)
    gate_vals = gate_vals / jnp.maximum(gate_vals.sum(-1, keepdims=True), 1e-9)
    Ep = m.e_padded
    dense_gates = jnp.zeros((T, Ep), jnp.float32)
    dense_gates = jax.vmap(lambda g, i, row: row.at[i].set(g))(
        gate_vals, top_ids, dense_gates)

    all_y = _expert_ffn(
        jnp.broadcast_to(flat, (Ep,) + flat.shape),
        params["wi"], params.get("wg"), params["wo"], cfg.act)  # [Ep, T, d]
    out = jnp.einsum("te,etd->td", dense_gates.astype(x.dtype), all_y)

    stats = _stats(probs, jax.nn.one_hot(top_ids, E, dtype=jnp.float32),
                   B, cfg)

    if m.num_shared:
        sh = _expert_ffn(
            jnp.broadcast_to(flat, (m.num_shared,) + flat.shape),
            params["shared_wi"], params.get("shared_wg"),
            params["shared_wo"], cfg.act).sum(0)
        out = out + sh
    return out.reshape(B, S, d).astype(x.dtype), stats
