"""Block assembly and scanned layer stacks.

A model is a list of *groups*; each group is a (period, count) pair where
``period`` is a tuple of BlockDefs executed in order and ``count`` is how
many times the period repeats.  Parameters of a group are stacked on a
leading 'layers' axis and the period body is scanned — HLO size stays O(1)
in depth (DESIGN.md §9).  Uniform models have a single (block,) period;
hybrids (jamba 1:7 attn:mamba, gemma3 5:1 local:global) use longer periods.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
from typing import Sequence

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.distributed.sharding import shard_act
from .attention import (_resume_dense, _resume_scatter, cross_attn,
                        cross_attn_spec, cross_kv, gqa_chunk_attn,
                        gqa_chunk_attn_ring, gqa_decode_attn,
                        gqa_decode_attn_paged, gqa_resume_attn,
                        gqa_self_attn, gqa_spec, mla_chunk_attn,
                        mla_decode_attn, mla_decode_attn_paged,
                        mla_resume_attn, mla_self_attn, mla_spec)
from .layers import mlp_apply, mlp_spec, rmsnorm_apply, rmsnorm_spec
from .moe import moe_apply_ep as moe_apply, moe_spec
from .spec import stack
from .ssm import ssm_decode, ssm_dims, ssm_forward, ssm_spec


@dataclasses.dataclass(frozen=True)
class BlockDef:
    mixer: str = "gqa"        # gqa | mla | ssm
    window: int = 0           # >0 → sliding-window attention (ring cache)
    ffn: str = "mlp"          # mlp | moe | none
    cross: bool = False       # add cross-attention (decoder of enc-dec)
    causal: bool = True       # False → encoder self-attention
    theta: float | None = None


Group = tuple[tuple[BlockDef, ...], int]

# When True, layer scans fully unroll.  The dry-run's roofline accounting
# sets this: XLA cost_analysis counts a while-loop body exactly once
# (verified empirically), so FLOP/byte/collective totals must come from
# unrolled reduced-depth compiles + linear extrapolation (launch/dryrun.py).
SCAN_UNROLL = False


# ---------------------------------------------------------------------------
# Specs
# ---------------------------------------------------------------------------

def block_spec(cfg: ModelConfig, bd: BlockDef, dtype) -> dict:
    out = {"ln1": rmsnorm_spec(cfg.d_model, "embed", dtype)}
    if bd.mixer == "gqa":
        out["attn"] = gqa_spec(cfg, dtype)
    elif bd.mixer == "mla":
        out["attn"] = mla_spec(cfg, dtype)
    elif bd.mixer == "ssm":
        out["ssm"] = ssm_spec(cfg, dtype)
    else:
        raise ValueError(bd.mixer)
    if bd.cross:
        out["ln_x"] = rmsnorm_spec(cfg.d_model, "embed", dtype)
        out["xattn"] = cross_attn_spec(cfg, dtype)
    if bd.ffn != "none":
        out["ln2"] = rmsnorm_spec(cfg.d_model, "embed", dtype)
        if bd.ffn == "moe":
            out["ffn"] = moe_spec(cfg, dtype)
        else:
            ff = cfg.moe.first_dense_ff if (bd.ffn == "dense0" and cfg.moe) \
                else cfg.d_ff
            out["ffn"] = mlp_spec(cfg.d_model, ff, cfg.tt, dtype)
    return out


def group_spec(cfg: ModelConfig, group: Group, dtype) -> dict:
    period, count = group
    ps = {f"b{i}": block_spec(cfg, bd, dtype) for i, bd in enumerate(period)}
    return stack(ps, count)


# ---------------------------------------------------------------------------
# Cache structure per block
# ---------------------------------------------------------------------------

def block_cache_shape(cfg: ModelConfig, bd: BlockDef, B: int, T: int,
                      enc_T: int, dtype) -> dict:
    """ShapeDtypeStructs of one block's decode cache."""
    sd = jax.ShapeDtypeStruct
    out: dict = {}
    if bd.mixer == "gqa":
        W = min(bd.window, T) if bd.window else T
        kv = (B, W, cfg.num_kv_heads, cfg.head_dim)
        out["k"], out["v"] = sd(kv, dtype), sd(kv, dtype)
    elif bd.mixer == "mla":
        m = cfg.mla
        out["ckv"] = sd((B, T, m.kv_lora), dtype)
        out["krope"] = sd((B, T, m.rope_head_dim), dtype)
    elif bd.mixer == "ssm":
        s = cfg.ssm
        d_inner, heads, conv_dim = ssm_dims(cfg)
        out["state"] = sd((B, heads, s.d_state, s.head_dim), jnp.float32)
        out["conv"] = sd((B, s.d_conv - 1, conv_dim), dtype)
    if bd.cross:
        kv = (B, enc_T, cfg.num_kv_heads, cfg.head_dim)
        out["xk"], out["xv"] = sd(kv, dtype), sd(kv, dtype)
    return out


def block_cache_kinds(bd: BlockDef) -> dict[str, str]:
    """Paging kind of each cache leaf of one block (DESIGN.md §7):

      'paged' — token-indexed, block-pageable and prefix-shareable
      'ring'  — window ring, block-pageable through the low table entries
                but never prefix-shared (contents are overwritten in place)
      'slot'  — fixed-size per-slot state (SSM state/conv tail, cross-attn
                encoder KV): stays [layers, num_slots, ...], unpaged
    """
    out: dict[str, str] = {}
    if bd.mixer == "gqa":
        out["k"] = out["v"] = "ring" if bd.window else "paged"
    elif bd.mixer == "mla":
        out["ckv"] = out["krope"] = "paged"
    elif bd.mixer == "ssm":
        out["state"] = out["conv"] = "slot"
    if bd.cross:
        out["xk"] = out["xv"] = "slot"
    return out


def block_paged_cache_shape(cfg: ModelConfig, bd: BlockDef, num_slots: int,
                            num_blocks: int, block: int, T: int, enc_T: int,
                            dtype) -> dict:
    """Paged twin of :func:`block_cache_shape`: pageable leaves become
    arenas [num_blocks + 1, block, ...] (the +1 is the write sentinel),
    'slot' leaves keep the dense per-slot layout."""
    sd = jax.ShapeDtypeStruct
    dense = block_cache_shape(cfg, bd, num_slots, T, enc_T, dtype)
    kinds = block_cache_kinds(bd)
    out = {}
    for name, s in dense.items():
        if kinds[name] == "slot":
            out[name] = s
        else:
            out[name] = sd((num_blocks + 1, block) + s.shape[2:], s.dtype)
    return out


# ---------------------------------------------------------------------------
# Block apply — full sequence (train / prefill / encoder)
# ---------------------------------------------------------------------------

def _ring_cache(k, W, true_len):
    """Build a ring layout (position p at slot p % W) from full-sequence
    k [B,S,...] with the write head at a *traced* true length — the
    bucketed-prefill twin of the static roll/pad construction.  Slot s
    receives the latest position p <= true_len-1 with p % W == s, or zeros
    if no such position exists."""
    L1 = jnp.asarray(true_len, jnp.int32) - 1
    s_idx = jnp.arange(W)
    p_idx = L1 - jnp.mod(L1 - s_idx, W)                   # [W]
    valid = p_idx >= 0
    g = jnp.take(k, jnp.clip(p_idx, 0), axis=1)
    vshape = (1, W) + (1,) * (k.ndim - 2)
    return jnp.where(valid.reshape(vshape), g, 0)


# ---------------------------------------------------------------------------
# Named scopes: every family's blocks put the same names into the HLO
# metadata (``op_name``), where the benchmark's device-trace readers find
# them (DESIGN.md §16).  A scope changes metadata only, never the program.
# ---------------------------------------------------------------------------

def _attn_scope(bd: BlockDef, name: str):
    """``jax.named_scope(name)`` around an attention mixer's work; SSM
    mixers stay outside every attention scope."""
    if bd.mixer in ("gqa", "mla"):
        return jax.named_scope(name)
    return contextlib.nullcontext()


def _ffn(p, cfg: ModelConfig, bd: BlockDef, x, backend):
    """The block's FFN (pre-norm, dense MLP or MoE, residual) under the
    ``ffn`` scope."""
    if bd.ffn == "none":
        return x
    with jax.named_scope("ffn"):
        h = rmsnorm_apply(p["ln2"], x, cfg.norm_eps)
        if bd.ffn == "moe":
            return x + moe_apply(p["ffn"], cfg, h, backend)
        return x + mlp_apply(p["ffn"], h, backend)


def block_fwd(p, cfg: ModelConfig, bd: BlockDef, x, positions, *,
              enc_out=None, want_cache: bool, T_cache: int = 0,
              plans=None, true_len=None):
    """Returns (x, cache_dict_or_None).

    ``plans`` is the model's PlanBook (kernels.plan): every projection in
    the block resolves its TT execution plan through it instead of a
    backend string.  ``plans=None`` keeps the legacy stringly-typed path
    (``cfg.tt.backend_spec``) for direct callers.

    ``true_len`` (optional traced scalar) marks positions >= true_len as
    right-padding from prompt-length bucketing: the window ring is built
    at the true write head, the SSM state treats padded steps as exact
    no-ops, and full/MLA cache rows beyond it are junk masked downstream
    by the cache position."""
    backend = plans if plans is not None else cfg.tt.backend_spec
    cache = {}
    h = rmsnorm_apply(p["ln1"], x, cfg.norm_eps)
    if bd.mixer == "gqa":
        y, (k, v) = gqa_self_attn(p["attn"], cfg, h, positions,
                                  window=bd.window, theta=bd.theta,
                                  backend=backend, causal=bd.causal)
        if want_cache:
            W = min(bd.window, T_cache) if bd.window else T_cache
            S = k.shape[1]
            if bd.window and true_len is not None:
                ck, cv = _ring_cache(k, W, true_len), _ring_cache(v, W,
                                                                  true_len)
            elif S >= W:
                # ring slots: position p lives at slot p % W
                ck = jnp.roll(k[:, -W:], S % W, axis=1)
                cv = jnp.roll(v[:, -W:], S % W, axis=1)
            else:
                pad = ((0, 0), (0, W - S), (0, 0), (0, 0))
                ck, cv = jnp.pad(k, pad), jnp.pad(v, pad)
            cache.update(k=ck, v=cv)
    elif bd.mixer == "mla":
        y, (ckv, krope) = mla_self_attn(p["attn"], cfg, h, positions,
                                        backend=backend)
        if want_cache:
            padlen = T_cache - ckv.shape[1]
            cache["ckv"] = jnp.pad(ckv, ((0, 0), (0, padlen), (0, 0)))
            cache["krope"] = jnp.pad(krope, ((0, 0), (0, padlen), (0, 0)))
    else:  # ssm
        y, state, conv_tail = ssm_forward(p["ssm"], cfg, h, backend,
                                          true_len=true_len)
        if want_cache:
            cache["state"] = state
            cache["conv"] = conv_tail.astype(x.dtype)
    x = x + y
    if bd.cross:
        h = rmsnorm_apply(p["ln_x"], x, cfg.norm_eps)
        x = x + cross_attn(p["xattn"], cfg, h,
                           *_enc_kv(p, cfg, bd, enc_out, cache, want_cache,
                                    backend),
                           backend=backend)
    x = _ffn(p, cfg, bd, x, backend)
    x = shard_act(x, ("act_batch", "act_seq", "act_embed"))
    return x, (cache if want_cache else None)


def _enc_kv(p, cfg, bd, enc_out, cache, want_cache, backend):
    k, v = cross_kv(p["xattn"], cfg, enc_out, backend)
    if want_cache:
        cache["xk"], cache["xv"] = k, v
    return k, v


# ---------------------------------------------------------------------------
# Block apply — single-token decode
# ---------------------------------------------------------------------------

def block_decode(p, cfg: ModelConfig, bd: BlockDef, x, cache: dict, pos,
                 plans=None, paged=None, active=None, layer=None):
    """``paged``: None for the dense slot-pool layout, else
    ``(block_tables [B, max_blocks], active [B])`` — attention leaves are
    block arenas addressed through the table; SSM/cross leaves are
    slot-indexed in both layouts.  ``active`` (optional [B] bool) gates
    every per-slot cache write: rows mid-chunked-prefill (and retired/free
    rows) must not have their state touched by the fused decode pass —
    paged attention leaves are already protected by the sentinel-block
    redirect, dense attention rows and SSM state/conv need the mask.
    ``layer`` (paged full attention only): the k/v leaves are the group's
    whole layer stack of arenas and ``layer`` the index of this one."""
    backend = plans if plans is not None else cfg.tt.backend_spec
    h = rmsnorm_apply(p["ln1"], x, cfg.norm_eps)
    new_cache = dict(cache)
    with _attn_scope(bd, "decode_attention"):
        if bd.mixer == "gqa":
            if paged is not None:
                bt, pact = paged
                y, nk, nv = gqa_decode_attn_paged(
                    p["attn"], cfg, h, cache["k"], cache["v"], bt, pos, pact,
                    window=bd.window, theta=bd.theta, backend=backend,
                    layer=layer)
            else:
                y, nk, nv = gqa_decode_attn(p["attn"], cfg, h, cache["k"],
                                            cache["v"], pos, window=bd.window,
                                            theta=bd.theta, backend=backend,
                                            active=active)
            new_cache.update(k=nk, v=nv)
        elif bd.mixer == "mla":
            if paged is not None:
                bt, pact = paged
                y, nckv, nkr = mla_decode_attn_paged(
                    p["attn"], cfg, h, cache["ckv"], cache["krope"], bt, pos,
                    pact, backend=backend)
            else:
                y, nckv, nkr = mla_decode_attn(p["attn"], cfg, h, cache["ckv"],
                                               cache["krope"], pos,
                                               backend=backend, active=active)
            new_cache.update(ckv=nckv, krope=nkr)
        else:
            y, st, cv = ssm_decode(p["ssm"], cfg, h, cache["state"],
                                   cache["conv"], backend)
            if active is not None:
                st = jnp.where(active[:, None, None, None], st, cache["state"])
                cv = jnp.where(active[:, None, None], cv, cache["conv"])
            new_cache.update(state=st, conv=cv)
    x = x + y
    if bd.cross:
        h = rmsnorm_apply(p["ln_x"], x, cfg.norm_eps)
        x = x + cross_attn(p["xattn"], cfg, h, cache["xk"], cache["xv"],
                           backend=backend)
    x = _ffn(p, cfg, bd, x, backend)
    return x, new_cache


# ---------------------------------------------------------------------------
# Group (scanned) application
# ---------------------------------------------------------------------------

def group_fwd(params, cfg: ModelConfig, group: Group, x, positions, *,
              enc_out=None, want_cache: bool, T_cache: int = 0,
              remat: bool = False, plans=None, true_len=None):
    """Scan the period body over the group's stacked params.
    Returns (x, stacked_caches_or_None).  ``plans`` (the model's PlanBook)
    is closure-captured by the scan body: one build-time-resolved plan per
    chain signature serves every scanned layer."""
    period, count = group

    def body(x, layer_params):
        caches = {}
        for i, bd in enumerate(period):
            x, c = block_fwd(layer_params[f"b{i}"], cfg, bd, x, positions,
                             enc_out=enc_out, want_cache=want_cache,
                             T_cache=T_cache, plans=plans,
                             true_len=true_len)
            if want_cache:
                caches[f"b{i}"] = c
        return x, (caches if want_cache else None)

    if remat:
        body = jax.checkpoint(body)
    x, caches = jax.lax.scan(body, x, params, unroll=SCAN_UNROLL or 1)
    return x, caches


def group_decode(params, cfg: ModelConfig, group: Group, x, caches, pos,
                 plans=None, paged=None, active=None):
    """Scan decode over stacked (params, caches).  Returns (x, new_caches).
    ``paged`` = (block_tables, active) switches attention leaves to the
    block-arena layout; ``active`` masks per-slot writes (see
    block_decode).

    Paged full-attention arenas ride the scan's carry as whole layer
    stacks instead of being sliced per layer: the new token's K/V is
    written into the stack in place and the paged attention kernel reads
    the layer's live blocks from it by index, so no layer's arena is
    copied out and back each step."""
    period, count = group
    whole = {f"b{i}" for i, bd in enumerate(period)
             if paged is not None and bd.mixer == "gqa" and not bd.window}
    arenas = {b: {n: caches[b][n] for n in ("k", "v")} for b in whole}
    sliced = {b: ({n: l for n, l in c.items() if n not in ("k", "v")}
                  if b in whole else c) for b, c in caches.items()}

    def body(carry, inp):
        x, arenas = carry
        layer_params, layer_caches, layer = inp
        new, new_arenas = {}, {}
        for i, bd in enumerate(period):
            b = f"b{i}"
            c = dict(layer_caches[b], **arenas.get(b, {}))
            x, c = block_decode(layer_params[b], cfg, bd, x, c, pos,
                                plans=plans, paged=paged, active=active,
                                layer=layer if b in whole else None)
            if b in whole:
                new_arenas[b] = {n: c.pop(n) for n in ("k", "v")}
            new[b] = c
        return (x, new_arenas), new

    (x, arenas), new_caches = jax.lax.scan(
        body, (x, arenas), (params, sliced, jnp.arange(count)),
        unroll=SCAN_UNROLL or 1)
    for b, a in arenas.items():
        new_caches[b] = dict(new_caches[b], **a)
    return x, new_caches


# ---------------------------------------------------------------------------
# Resume prefill over paged caches (prefix-reuse admission)
# ---------------------------------------------------------------------------

def block_resume(p, cfg: ModelConfig, bd: BlockDef, x, cache: dict, src_b,
                 dst_b, start, plans=None):
    """Suffix prefill of one block against its paged arenas: attends to the
    prefix gathered through ``src_b`` and scatters the updated logical
    cache back through ``dst_b`` (COW where the tables differ).  Only
    prefix-shareable mixers are legal here — the scheduler gates the
    resume path on ``Model.supports_prefix_reuse``."""
    backend = plans if plans is not None else cfg.tt.backend_spec
    h = rmsnorm_apply(p["ln1"], x, cfg.norm_eps)
    new_cache = dict(cache)
    if bd.mixer == "gqa" and not bd.window:
        y, nk, nv = gqa_resume_attn(p["attn"], cfg, h, cache["k"],
                                    cache["v"], src_b, dst_b, start,
                                    theta=bd.theta, backend=backend)
        new_cache.update(k=nk, v=nv)
    elif bd.mixer == "mla":
        y, nckv, nkr = mla_resume_attn(p["attn"], cfg, h, cache["ckv"],
                                       cache["krope"], src_b, dst_b, start,
                                       backend=backend)
        new_cache.update(ckv=nckv, krope=nkr)
    else:
        raise ValueError(
            f"mixer {bd.mixer!r} (window={bd.window}) does not support "
            "prefix-resume prefill")
    x = x + y
    x = _ffn(p, cfg, bd, x, backend)
    return x, new_cache


def group_resume(params, cfg: ModelConfig, group: Group, x, caches, src_b,
                 dst_b, start, plans=None):
    """Scan resume prefill over stacked (params, caches)."""
    period, count = group

    def body(x, inp):
        layer_params, layer_caches = inp
        new = {}
        for i, bd in enumerate(period):
            x, c = block_resume(layer_params[f"b{i}"], cfg, bd, x,
                                layer_caches[f"b{i}"], src_b, dst_b, start,
                                plans=plans)
            new[f"b{i}"] = c
        return x, new

    x, new_caches = jax.lax.scan(body, x, (params, caches),
                                 unroll=SCAN_UNROLL or 1)
    return x, new_caches


# ---------------------------------------------------------------------------
# Chunked prefill — one prompt chunk of one slot, inside the serving pool
# ---------------------------------------------------------------------------
#
# The chunked-prefill twin of block_resume, generalized two ways: it runs
# against either pool layout (``table=None`` → dense slot pool, else the
# slot's block table into the paged arenas), and it covers every mixer —
# windowed-ring layers rebuild their ring from gathered history (a chunk
# may span more than W positions) and SSM layers thread the recurrent
# state + conv tail across chunks, both exactly the state a monolithic
# prefill would have reached.  All tensor shapes are static in (C, layout),
# so the scheduler's mixed step stays one traced program per chunk config.

def block_chunk(p, cfg: ModelConfig, bd: BlockDef, x, cache: dict, slot,
                table, start, true_len, active, plans=None):
    """One prefill chunk of one slot through one block.

    x [1, C, d] at absolute positions start + t (rows >= true_len are
    right-padding); ``slot`` scalar int32 selects the row of slot-indexed
    leaves; ``table`` [max_blocks] int32 addresses paged arenas (None for
    the dense layout; callers redirect it to the write sentinel when the
    lane is inactive).  ``active`` (scalar bool) gates dense-row and
    slot-state writes so an unused lane is a no-op by value.
    """
    if bd.cross:
        raise ValueError("chunked prefill does not support cross-attention")
    backend = plans if plans is not None else cfg.tt.backend_spec
    C = x.shape[1]
    h = rmsnorm_apply(p["ln1"], x, cfg.norm_eps)
    new_cache = dict(cache)

    def _row(leaf):
        return jnp.take(leaf, slot, axis=0)[None]

    def _put(leaf, new_row):
        old = jnp.take(leaf, slot, axis=0)
        return leaf.at[slot].set(
            jnp.where(active, new_row.astype(leaf.dtype), old))

    with _attn_scope(bd, "chunk_attention"):
        if bd.mixer == "gqa" and not bd.window:
            if table is not None:
                dk = _resume_dense(cache["k"], table, C)
                dv = _resume_dense(cache["v"], table, C)
                y, dk, dv = gqa_chunk_attn(p["attn"], cfg, h, dk, dv, start,
                                           theta=bd.theta, backend=backend)
                new_cache["k"] = _resume_scatter(cache["k"], table, dk)
                new_cache["v"] = _resume_scatter(cache["v"], table, dv)
            else:
                T = cache["k"].shape[1]
                pad = lambda r: jnp.concatenate(
                    [r, jnp.zeros((1, C) + r.shape[2:], r.dtype)], axis=1)
                dk, dv = pad(_row(cache["k"])), pad(_row(cache["v"]))
                y, dk, dv = gqa_chunk_attn(p["attn"], cfg, h, dk, dv, start,
                                           theta=bd.theta, backend=backend)
                new_cache["k"] = _put(cache["k"], dk[0, :T])
                new_cache["v"] = _put(cache["v"], dv[0, :T])
        elif bd.mixer == "gqa":
            if table is not None:
                blk = cache["k"].shape[1]
                W = min(bd.window, table.shape[0] * blk)
                nblk = -(-W // blk)

                def _gather_ring(arena):
                    g = arena[table[:nblk]].reshape(
                        1, nblk * blk, *arena.shape[2:])
                    return g, g[:, :W]

                gk, rk = _gather_ring(cache["k"])
                gv, rv = _gather_ring(cache["v"])
                y, nk, nv = gqa_chunk_attn_ring(
                    p["attn"], cfg, h, rk, rv, start, true_len,
                    theta=bd.theta, backend=backend)

                def _scatter_ring(arena, g, new_ring):
                    merged = g.at[:, :W].set(new_ring.astype(g.dtype))
                    blocks = merged[0].reshape(nblk, blk, *arena.shape[2:])
                    return arena.at[table[:nblk]].set(blocks)

                new_cache["k"] = _scatter_ring(cache["k"], gk, nk)
                new_cache["v"] = _scatter_ring(cache["v"], gv, nv)
            else:
                rk, rv = _row(cache["k"]), _row(cache["v"])
                y, nk, nv = gqa_chunk_attn_ring(
                    p["attn"], cfg, h, rk, rv, start, true_len,
                    theta=bd.theta, backend=backend)
                new_cache["k"] = _put(cache["k"], nk[0])
                new_cache["v"] = _put(cache["v"], nv[0])
        elif bd.mixer == "mla":
            if table is not None:
                dckv = _resume_dense(cache["ckv"], table, C)
                dkr = _resume_dense(cache["krope"], table, C)
                y, dckv, dkr = mla_chunk_attn(p["attn"], cfg, h, dckv, dkr,
                                              start, backend=backend)
                new_cache["ckv"] = _resume_scatter(cache["ckv"], table, dckv)
                new_cache["krope"] = _resume_scatter(cache["krope"], table,
                                                     dkr)
            else:
                T = cache["ckv"].shape[1]
                pad = lambda r: jnp.concatenate(
                    [r, jnp.zeros((1, C) + r.shape[2:], r.dtype)], axis=1)
                dckv = pad(_row(cache["ckv"]))
                dkr = pad(_row(cache["krope"]))
                y, dckv, dkr = mla_chunk_attn(p["attn"], cfg, h, dckv, dkr,
                                              start, backend=backend)
                new_cache["ckv"] = _put(cache["ckv"], dckv[0, :T])
                new_cache["krope"] = _put(cache["krope"], dkr[0, :T])
        else:  # ssm — slot-indexed state in both layouts
            st, cv = _row(cache["state"]), _row(cache["conv"])
            fresh = start == 0
            st = jnp.where(fresh, jnp.zeros_like(st), st)
            cv = jnp.where(fresh, jnp.zeros_like(cv), cv)
            y, st2, tail = ssm_forward(p["ssm"], cfg, h, backend,
                                       true_len=true_len, s0=st, conv_hist=cv)
            new_cache["state"] = _put(cache["state"], st2[0])
            new_cache["conv"] = _put(cache["conv"], tail[0])
    x = x + y
    x = _ffn(p, cfg, bd, x, backend)
    return x, new_cache


def group_chunk(params, cfg: ModelConfig, group: Group, x, caches, slot,
                table, start, true_len, active, plans=None):
    """Scan one prefill chunk over stacked (params, caches)."""
    period, count = group

    def body(x, inp):
        layer_params, layer_caches = inp
        new = {}
        for i, bd in enumerate(period):
            x, c = block_chunk(layer_params[f"b{i}"], cfg, bd, x,
                               layer_caches[f"b{i}"], slot, table, start,
                               true_len, active, plans=plans)
            new[f"b{i}"] = c
        return x, new

    x, new_caches = jax.lax.scan(body, x, (params, caches),
                                 unroll=SCAN_UNROLL or 1)
    return x, new_caches
