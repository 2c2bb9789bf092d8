"""Mixture-of-experts with capacity-based scatter dispatch (GShard-style).

Dispatch is built from scatters/gathers rather than the O(T·E·C) one-hot
einsum so the buffers stay at ``k/E`` of a dense-all-experts compute.
Expert weights are stacked on a leading ``experts`` axis → expert
parallelism falls out of the sharding rules ('experts' → 'model' when
divisible, else TP on the ff dim inside each expert).

Expert FFNs route through TT when the model's TTConfig covers the "ffn"
family: cores gain a leading experts axis and the chain is vmapped — the
paper's technique applied to expert stacks is a beyond-paper extension
(DESIGN.md §6).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig, MoEConfig
from repro.distributed import sharding as shd
from repro.distributed.sharding import shard_act
from .layers import linear_spec, linear_apply, mlp_spec, mlp_apply
from .spec import ParamSpec, is_spec, stack


def moe_spec(cfg: ModelConfig, dtype=jnp.float32) -> dict:
    m = cfg.moe
    d = cfg.d_model
    expert = mlp_spec(d, m.expert_ff, cfg.tt, dtype)
    # stack expert weights on a leading 'experts' axis
    def add_axis(s: ParamSpec) -> ParamSpec:
        import dataclasses
        return dataclasses.replace(s, shape=(m.num_experts,) + s.shape,
                                   axes=("experts",) + s.axes)
    experts = jax.tree.map(add_axis, expert, is_leaf=is_spec)
    out = {
        "router": ParamSpec((d, m.num_experts), ("embed", None), "normal",
                            1.0 / np.sqrt(d), dtype),
        "experts": experts,
    }
    if m.num_shared:
        out["shared"] = mlp_spec(d, m.shared_ff * m.num_shared, cfg.tt, dtype)
    return out


def _expert_mlp(experts_p, xs, backend):
    """xs [E, C, d] → [E, C, d] via per-expert GLU MLP (vmapped)."""
    return jax.vmap(lambda p, x: mlp_apply(p, x, backend))(experts_p, xs)


def dispatch_positions(e_flat: jax.Array, num_experts: int) -> jax.Array:
    """Position of each assignment within its expert's buffer, in flat
    (token-major) priority order — GShard semantics.

    Sort-based: a stable argsort by expert id preserves flat order within
    each expert, so `index_in_sorted − segment_start` IS the position.
    Replaces the cumsum-over-[T·k, E] formulation, which XLA lowers to an
    O(T·k·E·window) reduce-window — measured 93 % of the compiled MoE-layer
    FLOPs at 1M tokens (EXPERIMENTS.md §Perf, dsv2 hillclimb iter 1).
    """
    Tk = e_flat.shape[0]
    order = jnp.argsort(e_flat, stable=True)                      # [Tk]
    e_sorted = jnp.take(e_flat, order)
    seg_start = jnp.searchsorted(e_sorted, jnp.arange(num_experts))
    pos_sorted = jnp.arange(Tk, dtype=jnp.int32) \
        - seg_start[e_sorted].astype(jnp.int32)
    return jnp.zeros((Tk,), jnp.int32).at[order].set(pos_sorted)


def moe_apply(p, cfg: ModelConfig, x: jax.Array, backend="xla") -> jax.Array:
    """x [B, S, d] → [B, S, d]."""
    m = cfg.moe
    B, S, d = x.shape
    T = B * S
    xt = x.reshape(T, d)
    logits = xt @ p["router"]                                    # [T, E]
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    gate, eidx = jax.lax.top_k(probs, m.top_k)                   # [T, k]
    gate = gate / jnp.sum(gate, -1, keepdims=True)

    C = int(np.ceil(m.top_k * T / m.num_experts * m.capacity_factor))
    # round capacity up to a lane multiple: keeps the buffer's capacity dim
    # shardable (E < model-axis archs shard C instead of E) and MXU-aligned
    C = max(-(-C // 128) * 128, 8) if T >= 128 else max(C, 8)
    e_flat = eidx.reshape(-1)
    pos_in_e = dispatch_positions(e_flat, m.num_experts)          # [T*k]
    keep = pos_in_e < C
    # overflow assignments point one past the end → dropped by mode="drop"
    pos_in_e = jnp.where(keep, pos_in_e, C)

    tok = jnp.repeat(jnp.arange(T), m.top_k)
    buf = jnp.zeros((m.num_experts, C, d), x.dtype)
    buf = buf.at[e_flat, pos_in_e].set(xt[tok], mode="drop")
    # experts → model when divisible (EP), else capacity → model
    buf = shard_act(buf, ("act_experts", "act_moe_cap", None))

    ys = _expert_mlp(p["experts"], buf, backend)                  # [E, C, d]
    ys = shard_act(ys, ("act_experts", "act_moe_cap", None))

    # gather back and combine with gate weights
    y_tok = ys.at[e_flat, jnp.minimum(pos_in_e, C - 1)].get(
        mode="fill", fill_value=0)                                # [T*k, d]
    y_tok = jnp.where(keep[:, None], y_tok, 0)
    w = gate.reshape(-1)[:, None].astype(y_tok.dtype)
    y = jnp.zeros_like(xt).at[tok].add(y_tok * w)

    if m.num_shared:
        y = y + mlp_apply(p["shared"], xt, backend)
    return y.reshape(B, S, d)


# ---------------------------------------------------------------------------
# Expert-parallel dispatch (shard_map) — EXPERIMENTS.md §Perf iteration 2
# ---------------------------------------------------------------------------
#
# The global formulation above leaves the dispatch scatter to GSPMD, which
# (measured) replicates the [T·k, d] update tensor to every device — a
# 51 GB all-gather per MoE layer at 1M tokens.  Here the routing, the
# scatter AND the expert FFN are local to each (data, model) device and the
# only cross-device step is one psum over 'model':
#
#   case A (E % M == 0)  true EP: device j owns E/M experts; it scatters
#       only its experts' assignments; FFN weights arrive pre-sharded on
#       the experts axis; the psum returns rows to their token owners.
#   case B (E % M != 0, dense experts)  TP-inside-EP: every device holds
#       all experts' buffers but only ff/M of each weight matrix; the
#       down-projection partial sums ride the same psum.
#   case C (E % M != 0, TT experts)  capacity split: TT cores are tiny and
#       replicated (the paper's point), so each device computes complete
#       rows for the 1/M capacity slice `pos % M == j`.

def _experts_in_specs(cfg: ModelConfig, mesh, case: str):
    """shard_map in_specs for the expert-weight subtree."""
    spec_tree = moe_spec(cfg)["experts"]

    def f(s: ParamSpec):
        parts = [None] * len(s.shape)
        if case == "A":
            parts[0] = "model"                       # experts axis
        elif case == "B":
            if "ff" in s.axes:
                parts[s.axes.index("ff")] = "model"  # TP on ff
        # case C: fully replicated (TT cores)
        return P(*parts)

    return jax.tree.map(f, spec_tree, is_leaf=is_spec)


def moe_apply_ep(p, cfg: ModelConfig, x: jax.Array, backend="xla"
                 ) -> jax.Array:
    """Expert-parallel MoE.  Falls back to the global path when no mesh
    ctx is active or shapes don't divide."""
    ctx = shd.get_ctx()
    m = cfg.moe
    B, S, d = x.shape
    if ctx is None:
        return moe_apply(p, cfg, x, backend)
    mesh = ctx.mesh
    M = shd._axis_size(mesh, "model")
    batch_axes = shd._resolve_axis(mesh, ("pod", "data"))
    D = shd._axis_size(mesh, batch_axes)
    if M <= 1 or B % max(D, 1) != 0:
        return moe_apply(p, cfg, x, backend)

    tt = "tt" in p["experts"]["gate"] if "gate" in p["experts"] else False
    if m.num_experts % M == 0:
        case = "A"
    elif tt:
        case = "C"
    else:
        case = "B"

    E, k = m.num_experts, m.top_k
    T_loc = (B // max(D, 1)) * S
    # per-expert capacity per data shard; multiple of 8 (and of M in case C)
    C_e = int(np.ceil(k * T_loc / E * m.capacity_factor))
    mult = 8 * (M if case == "C" else 1)
    C_e = max(-(-C_e // mult) * mult, mult)

    E_own = E // M if case == "A" else E
    C_own = C_e // M if case == "C" else C_e

    def local_fn(x_loc, router_w, experts_p):
        j = jax.lax.axis_index("model")
        B_loc = x_loc.shape[0]
        xt = x_loc.reshape(B_loc * x_loc.shape[1], d)
        Tl = xt.shape[0]
        logits = xt @ router_w                               # [Tl, E]
        probs = jax.nn.softmax(logits.astype(jnp.float32), -1)
        gate, eidx = jax.lax.top_k(probs, k)
        gate = gate / jnp.sum(gate, -1, keepdims=True)
        e_flat = eidx.reshape(-1)
        pos = dispatch_positions(e_flat, E)                  # [Tl*k]
        tok = jnp.repeat(jnp.arange(Tl), k)

        if case == "A":
            e0 = j * E_own
            e_loc = e_flat - e0
            mine = (e_loc >= 0) & (e_loc < E_own) & (pos < C_e)
            row_e = jnp.where(mine, e_loc, 0)
            row_c = jnp.where(mine, pos, C_own)              # OOB → dropped
        elif case == "B":
            mine = pos < C_e
            row_e, row_c = e_flat, jnp.where(mine, pos, C_own)
        else:                                                # case C
            mine = (pos % M == j) & (pos < C_e)
            row_e = e_flat
            row_c = jnp.where(mine, pos // M, C_own)

        buf = jnp.zeros((E_own, C_own, d), x_loc.dtype)
        buf = buf.at[row_e, row_c].set(
            jnp.where(mine[:, None], xt[tok], 0), mode="drop")
        ys = _expert_mlp(experts_p, buf, backend)            # [E_own,C_own,d]
        y_tok = ys.at[row_e, jnp.minimum(row_c, C_own - 1)].get(
            mode="fill", fill_value=0)
        y_tok = jnp.where(mine[:, None], y_tok, 0)
        w = gate.reshape(-1)[:, None].astype(y_tok.dtype)
        y = jnp.zeros_like(xt).at[tok].add(y_tok * w)
        y = jax.lax.psum(y, "model")
        return y.reshape(x_loc.shape)

    bspec = P(batch_axes, None, None)
    fn = jax.shard_map(
        local_fn, mesh=mesh,
        in_specs=(bspec, P(None, None), _experts_in_specs(cfg, mesh, case)),
        out_specs=bspec, check_vma=False)
    y = fn(x, p["router"], p["experts"])
    if m.num_shared:
        y = y + mlp_apply(p["shared"], x.reshape(-1, d), backend
                          ).reshape(x.shape)
    return y
