"""Pallas TPU kernel for decode attention over a block-paged KV arena.

One query token per slot attends over that slot's cache, which lives in
the arena blocks its block table names (DESIGN.md §7).  The kernel reads
those blocks straight from the arena, in the arena's own dtype, and only
up to the slot's last live block; nothing is gathered or widened in HBM.

Grid ``(slot,)``.  The block table, the lengths and the layer index are
scalar-prefetched; the arenas stay in HBM (``pl.ANY``).  A slot's live
blocks are walked in chunks of ``BLOCKS_PER_STEP``: each chunk's K and V
blocks are copied into one of two VMEM buffers while the chunk before is
computed, and a table entry past the slot's last live block is never
copied.  A slot of length 0 copies nothing and writes zeros.  On the TPU
a block copy moves whole 128-lane rows, so the head dimension is a
multiple of 128 (128 in both benchmark configurations).

Per chunk, all query heads meet all ``blocks × block × KV`` cached rows
in one MXU matmul; a head keeps only the rows of its own KV head
(``h // G``) below the slot's length, and an online softmax (f32 max,
sum and accumulator in VMEM scratch) folds the chunk into the context.
Scores and context accumulate in f32; the V tile is widened to f32 in
VMEM, so the probabilities stay f32 through the PV product.

Compiled on the TPU, interpreted on the CPU
(``tt_contract._interpret_default``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

from .tt_contract import _compiler_params, _interpret_default

# arena blocks per copy-and-compute chunk
BLOCKS_PER_STEP = 8
NEG_INF = -1e30


def _body(bt_ref, len_ref, layer_ref, q_ref, k_hbm, v_hbm, o_ref, k_buf,
          v_buf, sems, m_ref, l_ref, acc_ref, *, block: int, group: int,
          scale: float):
    b = pl.program_id(0)
    length = len_ref[b]
    layer = layer_ref[0]
    _, per_step, _, kv, hd = k_buf.shape
    max_blocks = bt_ref.shape[1]
    live = (length + block - 1) // block
    chunks = (live + per_step - 1) // per_step
    f32 = jnp.float32

    def copies(c, slot):
        """The chunk's live blocks, each a (live, K copy, V copy)."""
        out = []
        for p in range(per_step):
            j = c * per_step + p
            blk = bt_ref[b, jnp.minimum(j, max_blocks - 1)]
            out.append((j < live,
                        pltpu.make_async_copy(k_hbm.at[layer, blk],
                                              k_buf.at[slot, p],
                                              sems.at[slot, 0]),
                        pltpu.make_async_copy(v_hbm.at[layer, blk],
                                              v_buf.at[slot, p],
                                              sems.at[slot, 1])))
        return out

    def start(c, slot):
        for ok, ck, cv in copies(c, slot):
            @pl.when(ok)
            def _start(ck=ck, cv=cv):
                ck.start()
                cv.start()

    def wait(c, slot):
        for ok, ck, cv in copies(c, slot):
            @pl.when(ok)
            def _wait(ck=ck, cv=cv):
                ck.wait()
                cv.wait()

    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(chunks > 0)
    def _first():
        start(0, 0)

    q = q_ref[0]                                        # [H, hd]
    H = q.shape[0]
    rows = per_step * block * kv
    dt = jnp.promote_types(q.dtype, k_buf.dtype)
    # bf16 operands multiply exactly into the f32 accumulator; f32 ones
    # take the MXU's full-precision passes
    qk_precision = jax.lax.Precision.HIGHEST if dt == f32 else None

    def fold(c, carry):
        slot = c % 2

        @pl.when(c + 1 < chunks)
        def _next():
            start(c + 1, 1 - slot)

        wait(c, slot)
        # row r holds position c·blocks·block + r // KV of KV head r % KV;
        # rows past the length (blocks never copied among them) are masked
        valid = (length - c * per_step * block) * kv
        k = k_buf[slot].reshape(rows, hd)
        s = jax.lax.dot_general(
            q.astype(dt), k.astype(dt), (((1,), (1,)), ((), ())),
            preferred_element_type=f32, precision=qk_precision) * scale
        r = jax.lax.broadcasted_iota(jnp.int32, (H, rows), 1)
        h = jax.lax.broadcasted_iota(jnp.int32, (H, rows), 0)
        ok = (r % kv == h // group) & (r < valid)
        s = jnp.where(ok, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        pr = jnp.where(ok, jnp.exp(s - m_new), 0.0)
        l_ref[...] = alpha * l_ref[...] + pr.sum(axis=1, keepdims=True)
        v = v_buf[slot].reshape(rows, hd)
        rv = jax.lax.broadcasted_iota(jnp.int32, (rows, hd), 0)
        v = jnp.where(rv < valid, v.astype(f32), 0.0)
        acc_ref[...] = alpha * acc_ref[...] + jnp.dot(
            pr, v, preferred_element_type=f32,
            precision=jax.lax.Precision.HIGHEST)
        m_ref[...] = m_new
        return carry

    jax.lax.fori_loop(0, chunks, fold, 0)
    den = l_ref[...]
    out = jnp.where(den > 0, acc_ref[...] / jnp.where(den > 0, den, 1.0), 0.0)
    o_ref[0] = out.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("per_step", "scale",
                                             "interpret"))
def _paged_decode_attn_call(q, arena_k, arena_v, bt, lengths, layer, *,
                            per_step: int, scale: float, interpret: bool):
    B, H, hd = q.shape
    _, _, block, kv, _ = arena_k.shape
    q_spec = pl.BlockSpec((1, H, hd), lambda b, *_: (b, 0, 0))
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    buf = pltpu.VMEM((2, per_step, block, kv, hd), arena_k.dtype)
    body = functools.partial(_body, block=block, group=H // kv, scale=scale)
    return pl.pallas_call(
        body,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(B,),
            in_specs=[q_spec, hbm, hbm],
            out_specs=q_spec,
            scratch_shapes=[buf, buf, pltpu.SemaphoreType.DMA((2, 2)),
                            pltpu.VMEM((H, 1), jnp.float32),
                            pltpu.VMEM((H, 1), jnp.float32),
                            pltpu.VMEM((H, hd), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((B, H, hd), q.dtype),
        compiler_params=_compiler_params("arbitrary"),
        interpret=interpret,
    )(bt, lengths, layer, q, arena_k, arena_v)


def paged_decode_attention(q: jax.Array, arena_k: jax.Array,
                           arena_v: jax.Array, bt: jax.Array,
                           lengths: jax.Array, layer: jax.Array, *,
                           scale: float,
                           interpret: bool | None = None) -> jax.Array:
    """Decode attention of one query token per slot over its paged cache.

    ``q [B, H, hd]``; ``arena_k``/``arena_v [L, nb + 1, block, KV, hd]``,
    a layer stack of arenas, of which ``layer`` (an int32 scalar) is read;
    ``bt [B, max_blocks]`` int32, the slots' block tables; ``lengths
    [B]`` int32, the positions each slot attends over (``pos + 1`` for a
    decoding row, 0 for an inactive one).  Returns the context ``[B, H,
    hd]`` in ``q``'s dtype; rows of length 0 are zeros.

    Under a multi-device mesh in context (``jax.set_mesh``) the kernel
    runs once per device in a ``shard_map``: on the KV-head axis where the
    'model' extent divides it (the pool's own partitioning,
    ``distributed.sharding.serve_cache_shardings``), on whole operands
    otherwise — GSPMD cannot partition a Mosaic kernel."""
    if interpret is None:
        interpret = _interpret_default()
    if q.shape[1] % arena_k.shape[3]:
        raise ValueError(
            f"{q.shape[1]} query heads do not group over "
            f"{arena_k.shape[3]} KV heads")
    call = functools.partial(
        _paged_decode_attn_call, per_step=min(BLOCKS_PER_STEP, bt.shape[1]),
        scale=float(scale), interpret=interpret)
    args = (q, arena_k, arena_v, bt.astype(jnp.int32),
            lengths.astype(jnp.int32),
            jnp.asarray(layer, jnp.int32).reshape(1))
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty or mesh.size == 1 or \
            set(mesh.manual_axes) == set(mesh.axis_names):
        return call(*args)
    msize = dict(mesh.shape).get("model", 1)
    heads = "model" if arena_k.shape[3] % msize == 0 else None
    qs, ars = P(None, heads, None), P(None, None, None, heads, None)
    return jax.shard_map(call, mesh=mesh,
                         in_specs=(qs, ars, ars, P(), P(), P()),
                         out_specs=qs, check_vma=False)(*args)
