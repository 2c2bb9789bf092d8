"""Attention variants: GQA (+qk-norm, sliding window) and MLA (DeepSeek-V2).

All projections route through ``linear_spec`` so the paper's TT technique
applies uniformly ("attn" family).  MLA's down/up projections are excluded
from TT by construction — MLA *is already* a low-rank factorization of the
KV path (DESIGN.md §5); TT composes with it on q/o only.

Cache contract (serving/kv_cache.py builds the buffers):
  full  : k,v [B, S_max, KV, hd], write at ``pos``
  ring  : k,v [B, W, KV, hd], write at ``pos % W`` (SWA / gemma3 local)
  mla   : ckv [B, S_max, kv_lora], krope [B, S_max, rope_hd]
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.distributed.sharding import model_axis_size, shard_act
from repro.kernels.paged_attention import paged_decode_attention
from .layers import (head_rmsnorm_apply, linear_apply, linear_spec,
                     rmsnorm_spec, rmsnorm_apply, rope)
from .spec import ParamSpec

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# GQA
# ---------------------------------------------------------------------------

def gqa_spec(cfg: ModelConfig, dtype=jnp.float32) -> dict:
    d, q_dim, kv_dim = cfg.d_model, cfg.q_dim, cfg.kv_dim
    out = {
        "q": linear_spec(d, q_dim, cfg.tt, "attn", ("embed", "heads"), dtype),
        "k": linear_spec(d, kv_dim, cfg.tt, "attn", ("embed", "heads"), dtype),
        "v": linear_spec(d, kv_dim, cfg.tt, "attn", ("embed", "heads"), dtype),
        "o": linear_spec(q_dim, d, cfg.tt, "attn", ("heads", "embed"), dtype),
    }
    if cfg.qk_norm:
        out["q_norm"] = ParamSpec((cfg.head_dim,), (None,), "ones", dtype=dtype)
        out["k_norm"] = ParamSpec((cfg.head_dim,), (None,), "ones", dtype=dtype)
    return out


def _qkv(p, cfg: ModelConfig, x, positions, theta, backend):
    """Returns (q, k, v, heads_ok).  TP strategy: if H divides the model
    axis, attention tensors shard on heads; otherwise the query-sequence dim
    is sharded and k/v replicated across 'model' (GSPMD otherwise replicates
    the O(S²) score tensors — measured 100× collective blow-up)."""
    B, S, _ = x.shape
    H, KV, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    q = linear_apply(p["q"], x, backend).reshape(B, S, H, hd)
    k = linear_apply(p["k"], x, backend).reshape(B, S, KV, hd)
    v = linear_apply(p["v"], x, backend).reshape(B, S, KV, hd)
    if cfg.qk_norm:
        q = head_rmsnorm_apply(p["q_norm"], q, cfg.norm_eps)
        k = head_rmsnorm_apply(p["k_norm"], k, cfg.norm_eps)
    q = rope(q, positions, theta)
    k = rope(k, positions, theta)
    msize = model_axis_size()
    heads_ok = H % msize == 0 and H >= msize
    if heads_ok:
        q = shard_act(q, ("act_batch", None, "act_heads", None))
    else:
        q = shard_act(q, ("act_batch", "act_seq", None, None))
    return q, k, v, heads_ok


def _expand_and_shard_kv(cfg, k, v, heads_ok):
    """Full-seq path: expand GQA k/v to H heads when heads shard cleanly so
    every attention tensor splits 16-way (no score-tensor replication)."""
    H, KV = cfg.num_heads, cfg.num_kv_heads
    if heads_ok:
        if KV < H:
            k = jnp.repeat(k, H // KV, axis=2)
            v = jnp.repeat(v, H // KV, axis=2)
        k = shard_act(k, ("act_batch", None, "act_heads", None))
        v = shard_act(v, ("act_batch", None, "act_heads", None))
    else:
        k = shard_act(k, ("act_batch", None, None, None))
        v = shard_act(v, ("act_batch", None, None, None))
    return k, v


def _gqa_scores_ctx(q, k, v, mask, scale):
    """q [B,S,H,hd], k/v [B,T,KV,hd], mask [B,1,1,S,T] or broadcastable."""
    B, S, H, hd = q.shape
    KV = k.shape[2]
    G = H // KV
    qg = q.reshape(B, S, KV, G, hd)
    scores = jnp.einsum("bskgh,btkh->bkgst", qg.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    scores = jnp.where(mask, scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    ctx = jnp.einsum("bkgst,btkh->bskgh", probs, v.astype(jnp.float32))
    return ctx.reshape(B, S, H * hd).astype(q.dtype)


def gqa_self_attn(p, cfg: ModelConfig, x, positions, *, window: int = 0,
                  theta: float | None = None, backend: str = "xla",
                  causal: bool = True):
    """Full-sequence self-attention (train / prefill / encoder)."""
    B, S, _ = x.shape
    theta = cfg.rope_theta if theta is None else theta
    q, k, v, heads_ok = _qkv(p, cfg, x, positions, theta, backend)
    k_cache, v_cache = k, v                       # pre-expansion, [B,S,KV,hd]
    k, v = _expand_and_shard_kv(cfg, k, v, heads_ok)
    i = positions[:, :, None]                     # [B,S,1] query pos
    j = positions[:, None, :]                     # [B,1,T] key pos
    mask = (j <= i) if causal else jnp.ones((B, S, S), bool)
    if window:
        mask = mask & (j > i - window)
    mask = mask[:, None, None]                    # [B,1,1,S,T]
    ctx = _gqa_scores_ctx(q, k, v, mask, 1.0 / np.sqrt(cfg.head_dim))
    y = linear_apply(p["o"], ctx, backend)
    return y, (k_cache, v_cache)


def gqa_decode_attn(p, cfg: ModelConfig, x, cache_k, cache_v, pos, *,
                    window: int = 0, theta: float | None = None,
                    backend: str = "xla", active=None):
    """One-token decode against a full or ring cache.

    x [B,1,d]; cache_k/v [B, T, KV, hd] (T = S_max or window W);
    pos: int32 — current absolute position, either a scalar shared by the
    whole batch or a per-row vector [B] (continuous-batching slots, each at
    its own depth).  ``active`` (optional [B] bool, per-slot mode) gates the
    cache write per row — slots mid-chunked-prefill must not have their
    partial K/V overwritten by the fused decode pass.
    Returns (y [B,1,d], new_k, new_v).
    """
    B = x.shape[0]
    T = cache_k.shape[1]
    theta = cfg.rope_theta if theta is None else theta
    per_slot = jnp.ndim(pos) == 1
    positions = (pos.astype(jnp.int32)[:, None] if per_slot
                 else jnp.full((B, 1), pos, jnp.int32))
    q, k, v, _ = _qkv(p, cfg, x, positions, theta, backend)
    idx = jnp.arange(T)
    if per_slot:
        pv = positions[:, 0]                      # [B]
        slot = pv % T if window else pv
        # per-row scatter: row b writes its [1,KV,hd] k/v at its own slot
        # (rows whose slot is out of range — retired/free slots at pos ≥ T —
        # simply don't write)
        wr = (idx[None, :] == slot[:, None])[:, :, None, None]
        if active is not None:
            wr = wr & active[:, None, None, None]
        cache_k = jnp.where(wr, k, cache_k)
        cache_v = jnp.where(wr, v, cache_v)
        if window:
            abs_pos = pv[:, None] - jnp.mod(pv[:, None] - idx[None, :], T)
            valid = abs_pos >= 0                  # [B,T]
        else:
            valid = idx[None, :] <= pv[:, None]
        mask = valid[:, None, None, None, :]      # [B,1,1,1,T]
    else:
        slot = pos % T if window else pos
        cache_k = jax.lax.dynamic_update_slice_in_dim(cache_k, k, slot,
                                                      axis=1)
        cache_v = jax.lax.dynamic_update_slice_in_dim(cache_v, v, slot,
                                                      axis=1)
        if window:
            # ring: slot s holds absolute position pos - ((pos - s) mod T)
            abs_pos = pos - jnp.mod(pos - idx, T)
            valid = abs_pos >= 0
        else:
            valid = idx <= pos
        mask = valid[None, None, None, None, :]   # [1,1,1,1,T]
    ctx = _gqa_scores_ctx(q, cache_k, cache_v, mask,
                          1.0 / np.sqrt(cfg.head_dim))
    y = linear_apply(p["o"], ctx, backend)
    return y, cache_k, cache_v


# ---------------------------------------------------------------------------
# Paged decode — block-table gather/scatter against a block arena
# ---------------------------------------------------------------------------
#
# Arena layout (DESIGN.md §7): per cache leaf, [num_blocks + 1, block, ...]
# at this (per-layer) level; logical token t of slot b lives at
# (bt[b, t // block], t % block).  The last arena block is the write
# sentinel: inactive slots are redirected there so a retired slot's stale
# block table can never corrupt storage reused by another request.

def gqa_decode_attn_paged(p, cfg: ModelConfig, x, arena_k, arena_v, bt, pos,
                          active, *, window: int = 0,
                          theta: float | None = None, backend: str = "xla",
                          layer=None):
    """One-token decode against a block-paged cache.

    x [B,1,d]; bt [B, max_blocks] int32; pos [B] int32; active [B] bool.
    Full-attention layers take the group's whole layer stack of arenas
    arena_k/v [L, nb+1, block, KV, hd] and ``layer`` (an int32 scalar)
    indexing it: the token is written there in place and the paged
    attention kernel (``kernels/paged_attention.py``) reads each row's
    live blocks from it.  Windowed layers take their own arenas
    [nb+1, block, KV, hd] and address them through the ring index
    ``pos % W`` (W = min(window, logical length)), reusing the low entries
    of the same block table — ring blocks are therefore never
    prefix-shared (the scheduler disables prefix caching for windowed
    models) — and attend over the gathered ring.  Returns (y [B,1,d], new
    arenas)."""
    B = x.shape[0]
    nb1, blk, KV, hd = arena_k.shape[-4:]
    sentinel = nb1 - 1
    theta = cfg.rope_theta if theta is None else theta
    T_logical = bt.shape[1] * blk
    W = min(window, T_logical) if window else T_logical
    positions = pos.astype(jnp.int32)[:, None]            # [B,1]
    q, k, v, _ = _qkv(p, cfg, x, positions, theta, backend)
    pv = positions[:, 0]
    wp = pv % W if window else pv
    phys = jnp.take_along_axis(bt, (wp // blk)[:, None], 1)[:, 0]
    phys = jnp.where(active, phys, sentinel)
    at = (phys, wp % blk) if layer is None else (layer, phys, wp % blk)
    arena_k = arena_k.at[at].set(k[:, 0])
    arena_v = arena_v.at[at].set(v[:, 0])
    scale = 1.0 / np.sqrt(cfg.head_dim)
    if not window:
        lengths = jnp.where(active, pv + 1, 0)
        ctx = paged_decode_attention(q[:, 0], arena_k, arena_v, bt, lengths,
                                     layer, scale=scale)
        y = linear_apply(p["o"], ctx.reshape(B, 1, -1), backend)
        return y, arena_k, arena_v
    nblk = -(-W // blk)
    gk = arena_k[bt[:, :nblk]].reshape(B, nblk * blk, KV, hd)[:, :W]
    gv = arena_v[bt[:, :nblk]].reshape(B, nblk * blk, KV, hd)[:, :W]
    idx = jnp.arange(W)
    abs_pos = pv[:, None] - jnp.mod(pv[:, None] - idx[None, :], W)
    mask = (abs_pos >= 0)[:, None, None, None, :]         # [B,1,1,1,W]
    ctx = _gqa_scores_ctx(q, gk, gv, mask, scale)
    y = linear_apply(p["o"], ctx, backend)
    return y, arena_k, arena_v


def mla_decode_attn_paged(p, cfg: ModelConfig, x, arena_ckv, arena_kr, bt,
                          pos, active, backend="xla"):
    """Absorbed-form MLA decode against block-paged latent arenas.

    arena_ckv [nb+1, block, kv_lora], arena_kr [nb+1, block, rope_hd];
    bt/pos/active as in gqa_decode_attn_paged.
    """
    m = cfg.mla
    B = x.shape[0]
    nb1, blk, _ = arena_ckv.shape
    sentinel = nb1 - 1
    positions = pos.astype(jnp.int32)[:, None]
    q_nope, q_rope = _mla_q(p, cfg, x, positions, backend)
    ckv, krope = _mla_compress(p, cfg, x, positions, backend)
    pv = positions[:, 0]
    phys = jnp.take_along_axis(bt, (pv // blk)[:, None], 1)[:, 0]
    phys = jnp.where(active, phys, sentinel)
    arena_ckv = arena_ckv.at[phys, pv % blk].set(ckv[:, 0])
    arena_kr = arena_kr.at[phys, pv % blk].set(krope[:, 0])
    T = bt.shape[1] * blk
    cckv = arena_ckv[bt].reshape(B, T, m.kv_lora)
    ckr = arena_kr[bt].reshape(B, T, m.rope_head_dim)
    valid = (jnp.arange(T)[None, :] <= positions)[:, None, None, :]
    ctx = _mla_absorbed_ctx(p, cfg, q_nope, q_rope, cckv, ckr, valid)
    y = linear_apply(p["o"], ctx.astype(x.dtype), backend)
    return y, arena_ckv, arena_kr


# ---------------------------------------------------------------------------
# Resume prefill — suffix attention over gathered prefix blocks (COW write)
# ---------------------------------------------------------------------------
#
# The prefix-reuse admission path: a request whose prompt prefix is already
# resident skips its prefill.  The suffix runs here — the logical cache is
# gathered densely through the *source* block table, the suffix K/V is
# computed and written into the dense buffer at its absolute positions,
# and the buffer is scattered back through the *destination* table.  A
# destination entry differing from its source entry IS the copy-on-write:
# content flows old block → dense buffer → new block, with the overwritten
# rows replaced in between.  Identical src/dst entries rewrite shared
# blocks with bitwise-identical gathered content (a no-op by value).

def _resume_dense(arena, src_b, S_pad):
    """Gather the logical cache [1, T_max + S_pad, ...] via src_b, with
    S_pad scratch rows appended so a dynamic_update_slice at start <= T_max
    never clamps/misaligns."""
    mb = src_b.shape[0]
    blk = arena.shape[1]
    dense = arena[src_b].reshape(1, mb * blk, *arena.shape[2:])
    pad = jnp.zeros((1, S_pad) + dense.shape[2:], dense.dtype)
    return jnp.concatenate([dense, pad], axis=1)


def _resume_scatter(arena, dst_b, dense):
    """Scatter the first T_max rows of the dense buffer back through the
    destination table (sentinel-padded entries collapse onto the scratch
    block)."""
    mb = dst_b.shape[0]
    blk = arena.shape[1]
    blocks = dense[0, :mb * blk].reshape(mb, blk, *arena.shape[2:])
    return arena.at[dst_b].set(blocks.astype(arena.dtype))


def gqa_chunk_attn(p, cfg: ModelConfig, x, dk, dv, start, *,
                   theta: float | None = None, backend: str = "xla"):
    """Chunk/suffix prefill against a *dense logical* cache buffer.

    x [1, S_pad, d] at absolute positions start + t; dk/dv
    [1, T + S_pad, KV, hd] — the logical cache with S_pad scratch rows
    appended so the write at ``start`` never clamps.  Writes the chunk K/V
    at its absolute positions and attends causally to prefix + itself.
    Full (non-windowed) attention only.  Returns (y, dk, dv).
    """
    B, S_pad, _ = x.shape
    theta = cfg.rope_theta if theta is None else theta
    positions = start + jnp.arange(S_pad)[None, :]        # [1, S_pad]
    q, k, v, heads_ok = _qkv(p, cfg, x, positions, theta, backend)
    dk = jax.lax.dynamic_update_slice(dk, k.astype(dk.dtype),
                                      (0, start, 0, 0))
    dv = jax.lax.dynamic_update_slice(dv, v.astype(dv.dtype),
                                      (0, start, 0, 0))
    kk, vv = _expand_and_shard_kv(cfg, dk, dv, heads_ok)
    j = jnp.arange(kk.shape[1])[None, None, :]            # [1,1,T]
    mask = (j <= positions[:, :, None])[:, None, None]    # [1,1,1,S,T]
    ctx = _gqa_scores_ctx(q, kk, vv, mask, 1.0 / np.sqrt(cfg.head_dim))
    y = linear_apply(p["o"], ctx, backend)
    return y, dk, dv


def gqa_resume_attn(p, cfg: ModelConfig, x, arena_k, arena_v, src_b, dst_b,
                    start, *, theta: float | None = None,
                    backend: str = "xla"):
    """Suffix prefill (x [1, S_pad, d] at absolute positions start + t)
    attending to the gathered prefix + itself; writes the suffix K/V back
    into the arenas through dst_b.  Full (non-windowed) attention only."""
    B, S_pad, _ = x.shape
    dk = _resume_dense(arena_k, src_b, S_pad)
    dv = _resume_dense(arena_v, src_b, S_pad)
    y, dk, dv = gqa_chunk_attn(p, cfg, x, dk, dv, start, theta=theta,
                               backend=backend)
    return y, _resume_scatter(arena_k, dst_b, dk), \
        _resume_scatter(arena_v, dst_b, dv)


def gqa_chunk_attn_ring(p, cfg: ModelConfig, x, ring_k, ring_v, start,
                        true_len, *, theta: float | None = None,
                        backend: str = "xla"):
    """Chunked prefill for a windowed-ring layer.

    x [1, C, d] at absolute positions start + t (rows >= true_len are
    right-padding); ring_k/v [1, W, KV, hd] hold the state *before* this
    chunk: slot w = K/V of the latest absolute position p <= start - 1 with
    p % W == w (zeros where no such p >= 0 exists — the `_ring_cache`
    convention).  A chunk may span more than W positions, so the ring is
    NOT updated in place (in-chunk overwrites would hide keys still inside
    an earlier query's window); instead the history is gathered densely,
    the chunk keys appended, every real query attends over absolute
    positions, and the ring is rebuilt for state after start + true_len - 1.
    Returns (y, new_ring_k, new_ring_v).
    """
    B, C, _ = x.shape
    W = ring_k.shape[1]
    theta = cfg.rope_theta if theta is None else theta
    positions = start + jnp.arange(C)[None, :]            # [1, C]
    q, k, v, heads_ok = _qkv(p, cfg, x, positions, theta, backend)
    # history entry i = absolute position start - W + i, stored at ring slot
    # (start - W + i) mod W == (start + i) mod W
    i_idx = jnp.arange(W)
    hist_slot = jnp.mod(start + i_idx, W)
    hk = jnp.take(ring_k, hist_slot, axis=1)
    hv = jnp.take(ring_v, hist_slot, axis=1)
    key_pos = jnp.concatenate([start - W + i_idx, start + jnp.arange(C)])
    ck = jnp.concatenate([hk, k.astype(hk.dtype)], axis=1)    # [1,W+C,KV,hd]
    cv = jnp.concatenate([hv, v.astype(hv.dtype)], axis=1)
    kk, vv = _expand_and_shard_kv(cfg, ck, cv, heads_ok)
    pq = positions[:, :, None]                            # [1,C,1]
    j = key_pos[None, None, :]                            # [1,1,W+C]
    mask = ((j <= pq) & (j > pq - W) & (j >= 0))[:, None, None]
    ctx = _gqa_scores_ctx(q, kk, vv, mask, 1.0 / np.sqrt(cfg.head_dim))
    y = linear_apply(p["o"], ctx, backend)
    # rebuild: slot w <- latest p <= L1 with p % W == w; that p indexes the
    # combined buffer at p - start + W (history region when p < start —
    # where it provably equals the old ring entry — chunk region otherwise)
    L1 = start + true_len - 1
    p_w = L1 - jnp.mod(L1 - i_idx, W)
    src = p_w - start + W
    nk = jnp.take(ck, src, axis=1)
    nv = jnp.take(cv, src, axis=1)
    ok = (p_w >= 0)[None, :, None, None]
    new_rk = jnp.where(ok, nk, jnp.zeros_like(nk)).astype(ring_k.dtype)
    new_rv = jnp.where(ok, nv, jnp.zeros_like(nv)).astype(ring_v.dtype)
    return y, new_rk, new_rv


def mla_chunk_attn(p, cfg: ModelConfig, x, dckv, dkr, start, backend="xla"):
    """MLA chunk/suffix prefill against dense latent buffers (absorbed
    form).  dckv [1, T + S_pad, kv_lora], dkr [1, T + S_pad, rope_hd] with
    S_pad scratch rows appended.  Returns (y, dckv, dkr)."""
    B, S_pad, _ = x.shape
    positions = start + jnp.arange(S_pad)[None, :]
    q_nope, q_rope = _mla_q(p, cfg, x, positions, backend)
    ckv, krope = _mla_compress(p, cfg, x, positions, backend)
    dckv = jax.lax.dynamic_update_slice(dckv, ckv.astype(dckv.dtype),
                                        (0, start, 0))
    dkr = jax.lax.dynamic_update_slice(dkr, krope.astype(dkr.dtype),
                                       (0, start, 0))
    j = jnp.arange(dckv.shape[1])[None, None, :]
    valid = (j <= positions[:, :, None])[:, None]         # [1,1,S,T]
    ctx = _mla_absorbed_ctx(p, cfg, q_nope, q_rope, dckv, dkr, valid)
    y = linear_apply(p["o"], ctx.astype(x.dtype), backend)
    return y, dckv, dkr


def mla_resume_attn(p, cfg: ModelConfig, x, arena_ckv, arena_kr, src_b,
                    dst_b, start, backend="xla"):
    """MLA suffix prefill over gathered latent arenas (absorbed form)."""
    B, S_pad, _ = x.shape
    dckv = _resume_dense(arena_ckv, src_b, S_pad)
    dkr = _resume_dense(arena_kr, src_b, S_pad)
    y, dckv, dkr = mla_chunk_attn(p, cfg, x, dckv, dkr, start,
                                  backend=backend)
    return y, _resume_scatter(arena_ckv, dst_b, dckv), \
        _resume_scatter(arena_kr, dst_b, dkr)


# ---------------------------------------------------------------------------
# Cross-attention (seamless decoder)
# ---------------------------------------------------------------------------

def cross_attn_spec(cfg: ModelConfig, dtype=jnp.float32) -> dict:
    return gqa_spec(cfg, dtype)


def cross_attn(p, cfg: ModelConfig, x, enc_k, enc_v, backend="xla"):
    """x [B,S,d] attends to precomputed encoder k/v [B,T,KV,hd]."""
    B, S, _ = x.shape
    H, hd = cfg.num_heads, cfg.head_dim
    q = linear_apply(p["q"], x, backend).reshape(B, S, H, hd)
    mask = jnp.ones((1, 1, 1, 1, enc_k.shape[1]), bool)
    ctx = _gqa_scores_ctx(q, enc_k, enc_v, mask, 1.0 / np.sqrt(hd))
    return linear_apply(p["o"], ctx, backend)


def cross_kv(p, cfg: ModelConfig, enc_out, backend="xla"):
    B, T, _ = enc_out.shape
    KV, hd = cfg.num_kv_heads, cfg.head_dim
    k = linear_apply(p["k"], enc_out, backend).reshape(B, T, KV, hd)
    v = linear_apply(p["v"], enc_out, backend).reshape(B, T, KV, hd)
    return k, v


# ---------------------------------------------------------------------------
# MLA (DeepSeek-V2 multi-head latent attention)
# ---------------------------------------------------------------------------

def mla_spec(cfg: ModelConfig, dtype=jnp.float32) -> dict:
    m = cfg.mla
    d, H = cfg.d_model, cfg.num_heads
    qk_head = m.nope_head_dim + m.rope_head_dim
    return {
        "q": linear_spec(d, H * qk_head, cfg.tt, "attn",
                         ("embed", "heads"), dtype),
        # low-rank KV path: dense by construction (already factorized)
        "kv_down": linear_spec(d, m.kv_lora + m.rope_head_dim, None, "mla",
                               ("embed", None), dtype),
        "kv_norm": rmsnorm_spec(m.kv_lora, None, dtype),
        "kv_up": linear_spec(m.kv_lora,
                             H * (m.nope_head_dim + m.v_head_dim), None,
                             "mla", (None, "heads"), dtype),
        "o": linear_spec(H * m.v_head_dim, d, cfg.tt, "attn",
                         ("heads", "embed"), dtype),
    }


def _mla_q(p, cfg, x, positions, backend):
    m = cfg.mla
    B, S, _ = x.shape
    H = cfg.num_heads
    qk_head = m.nope_head_dim + m.rope_head_dim
    q = linear_apply(p["q"], x, backend).reshape(B, S, H, qk_head)
    q_nope, q_rope = jnp.split(q, [m.nope_head_dim], axis=-1)
    q_rope = rope(q_rope, positions, cfg.rope_theta)
    return q_nope, q_rope


def _mla_compress(p, cfg, x, positions, backend):
    m = cfg.mla
    c = linear_apply(p["kv_down"], x, backend)
    ckv, krope = jnp.split(c, [m.kv_lora], axis=-1)
    ckv = rmsnorm_apply(p["kv_norm"], ckv, cfg.norm_eps)
    krope = rope(krope[:, :, None, :], positions,
                 cfg.rope_theta)[:, :, 0, :]
    return ckv, krope


def mla_self_attn(p, cfg: ModelConfig, x, positions, backend="xla"):
    """Expanded-form MLA for train/prefill.  Returns (y, (ckv, krope))."""
    m = cfg.mla
    B, S, _ = x.shape
    H = cfg.num_heads
    q_nope, q_rope = _mla_q(p, cfg, x, positions, backend)
    ckv, krope = _mla_compress(p, cfg, x, positions, backend)
    kv = linear_apply(p["kv_up"], ckv, backend).reshape(
        B, S, H, m.nope_head_dim + m.v_head_dim)
    k_nope, v = jnp.split(kv, [m.nope_head_dim], axis=-1)
    scale = 1.0 / np.sqrt(m.nope_head_dim + m.rope_head_dim)
    i, j = positions[:, :, None], positions[:, None, :]
    mask = (j <= i)[:, None]                      # [B,1,S,T]
    s = (jnp.einsum("bshn,bthn->bhst", q_nope.astype(jnp.float32),
                    k_nope.astype(jnp.float32))
         + jnp.einsum("bshr,btr->bhst", q_rope.astype(jnp.float32),
                      krope.astype(jnp.float32))) * scale
    probs = jax.nn.softmax(jnp.where(mask, s, NEG_INF), axis=-1)
    ctx = jnp.einsum("bhst,bthv->bshv", probs, v.astype(jnp.float32))
    y = linear_apply(p["o"], ctx.reshape(B, S, -1).astype(x.dtype), backend)
    return y, (ckv, krope)


def _mla_absorbed_ctx(p, cfg: ModelConfig, q_nope, q_rope, cache_ckv,
                      cache_krope, valid):
    """Absorbed-form MLA scores/context over a latent cache.

    q_nope/q_rope [B,S,H,·], cache_ckv [B,T,kv_lora],
    cache_krope [B,T,rope_hd], valid broadcastable to [B,H,S,T].
    Returns the flattened context [B, S, H·v_head_dim] (pre-o-projection).
    """
    m = cfg.mla
    B, S = q_nope.shape[:2]
    H = cfg.num_heads
    w_up = p["kv_up"]["w"].reshape(m.kv_lora, H,
                                   m.nope_head_dim + m.v_head_dim)
    w_uk, w_uv = jnp.split(w_up, [m.nope_head_dim], axis=-1)
    q_eff = jnp.einsum("bshn,lhn->bshl", q_nope.astype(jnp.float32),
                       w_uk.astype(jnp.float32))          # [B,S,H,kv_lora]
    scale = 1.0 / np.sqrt(m.nope_head_dim + m.rope_head_dim)
    s = (jnp.einsum("bshl,btl->bhst", q_eff,
                    cache_ckv.astype(jnp.float32))
         + jnp.einsum("bshr,btr->bhst", q_rope.astype(jnp.float32),
                      cache_krope.astype(jnp.float32))) * scale
    probs = jax.nn.softmax(jnp.where(valid, s, NEG_INF), axis=-1)
    ctx_l = jnp.einsum("bhst,btl->bshl", probs,
                       cache_ckv.astype(jnp.float32))     # latent context
    ctx = jnp.einsum("bshl,lhv->bshv", ctx_l, w_uv.astype(jnp.float32))
    return ctx.reshape(B, S, -1)


def mla_decode_attn(p, cfg: ModelConfig, x, cache_ckv, cache_krope, pos,
                    backend="xla", active=None):
    """Absorbed-form MLA decode: scores/context live in the latent space, so
    per-step cost is O(T·kv_lora) not O(T·H·head_dim) — the production path.

    cache_ckv [B, S_max, kv_lora], cache_krope [B, S_max, rope_hd].
    ``pos`` is a scalar or a per-row vector [B] (see gqa_decode_attn).
    ``active`` (optional [B] bool) gates the per-slot cache write.
    """
    B = x.shape[0]
    per_slot = jnp.ndim(pos) == 1
    positions = (pos.astype(jnp.int32)[:, None] if per_slot
                 else jnp.full((B, 1), pos, jnp.int32))
    q_nope, q_rope = _mla_q(p, cfg, x, positions, backend)
    ckv, krope = _mla_compress(p, cfg, x, positions, backend)
    if per_slot:
        idx = jnp.arange(cache_ckv.shape[1])
        wr = (idx[None, :] == positions)[:, :, None]    # [B,T,1]
        if active is not None:
            wr = wr & active[:, None, None]
        cache_ckv = jnp.where(wr, ckv, cache_ckv)
        cache_krope = jnp.where(wr, krope, cache_krope)
    else:
        cache_ckv = jax.lax.dynamic_update_slice_in_dim(
            cache_ckv, ckv, pos, 1)
        cache_krope = jax.lax.dynamic_update_slice_in_dim(
            cache_krope, krope, pos, 1)
    T = cache_ckv.shape[1]
    if per_slot:
        valid = (jnp.arange(T)[None, :]
                 <= positions)[:, None, None, :]        # [B,1,1,T]
    else:
        valid = (jnp.arange(T) <= pos)[None, None, None, :]
    ctx = _mla_absorbed_ctx(p, cfg, q_nope, q_rope, cache_ckv, cache_krope,
                            valid)
    y = linear_apply(p["o"], ctx.astype(x.dtype), backend)
    return y, cache_ckv, cache_krope
