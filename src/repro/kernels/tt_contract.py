"""Pallas TPU kernels for the TT einsum chain.

Two kernels (DESIGN.md §2 maps them onto the paper's §4.3 pipeline):

``tt_step``  — one einsum step ``out[m,b,r0] = Σ_{n,r1} G·X``, run as the
   tiled 2-D MXU matmul ``X[b, n·r1] @ P[n·r1, m·r0]`` over the *packed*
   core (``core.packing.pack_core``).  The (bm, bb, bn) VMEM tiling comes
   from the analytical model in ``core.packing.select_blocks`` (the
   paper's register-blocking / cache-tiling transfer).  Grid = (m-tiles,
   b-tiles, n-tiles), n innermost with fp32 accumulation in the revisited
   output block.

``tt_fused_chain`` — the whole d≥2 chain in ONE ``pallas_call`` over a
   batch-tiled grid: all d packed-core MXU matmuls with every inter-step
   relayout in VMEM, zero HBM intermediates.  This is the TPU-native answer
   to the paper's IREE critique: IREE's transpose-to-matmul layers live in
   HBM; ours live in vector registers.  ``tt_fused2`` is its d=2 entry
   point (the paper's §6.4 deploys length-2 solutions).  Eligibility is
   decided by the fused-chain VMEM-fit test
   (``core.packing.fused_chain_batch_tile``, the paper's Eq. 26–28
   analogue); chains that do not fit fall back to the per-step kernel,
   which round-trips intermediates through HBM.

The bodies avoid what the TPU compiler (Mosaic) refuses: no in-kernel
reshape splits or merges the lane (last) dim, and every matmul has one
contracting dim.  The chain body therefore puts tokens on lanes, where
each relayout moves whole ``(rows, batch tile)`` blocks (see
``_fused_chain_body``).

Each kernel has an **int8-resident variant** (``*_int8_pallas``, DESIGN.md
§8): the packed cores arrive as int8 and STAY int8 in VMEM — residency is
1 byte/elem, so the fit test admits chains whose fp32 weights alone bust
the VMEM budget.  Per-core fp32 scales ride in SMEM ([d, 1] block);
dequantization happens inside the kernel body: the int8 block is widened
to fp32 feeding the MXU and the symmetric per-core scale is folded into
the matmul epilogue (``(s·Q)·x == s·(Q·x)``, exact — the scale multiplies
the matmul output instead of materializing a scaled copy of the core).
Accumulation is fp32 throughout.  Each fp/int8 pair shares ONE jitted call
and ONE body (the padding / grid / BlockSpec scaffolding): the int8 trace
only appends the SMEM scale operand, so a fix to the tiling logic can
never reach one variant and miss the other.

Every kernel is compiled with ``vmem_limit_bytes = hw.VMEM_BUDGET_BYTES``,
the budget the fit models in ``core.packing`` price tiles against.

Every public entry increments a module-level launch counter
(``LAUNCH_COUNTS``) so benchmarks/tests can assert how many ``pallas_call``
launches a given forward issues (fused d-chain ⇒ exactly one).

Kernels compile for the TPU and run in interpret mode on the CPU
(``_interpret_default``).
"""
from __future__ import annotations

import collections
import functools
from typing import Sequence

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import hw
from repro.core.flops import prod
from repro.core.packing import (BlockPlan, fused_chain_batch_tile,
                                pack_core, step_tiles)

# Kernel-generation version: bumped whenever tiling semantics, packed
# layouts or the BlockPlan contract change incompatibly.  The autotune
# cache schema (autotune.CACHE_SCHEMA) and serialized execution plans
# (plan.PLAN_SCHEMA) are stamped with it, so persisted tiles/plans from an
# older kernel generation are silently ignored rather than mis-executed.
KERNEL_VERSION = 3

# pallas_call launches per kernel kind, counted at the (non-jitted) wrapper
# level so cached-trace executions are counted too.
LAUNCH_COUNTS: collections.Counter = collections.Counter()


def reset_launch_counts() -> None:
    LAUNCH_COUNTS.clear()


def launch_counts() -> dict[str, int]:
    return dict(LAUNCH_COUNTS)


def _interpret_default() -> bool:
    """Compiled on the TPU, interpreted on the CPU, refused elsewhere: on
    any other backend interpret mode would quietly stand in for the
    kernels.  On the TPU the fit models' constants must describe the
    chip, so its kind is checked against ``core.hw``."""
    backend = jax.default_backend()
    if backend == "cpu":
        return True
    if backend == "tpu":
        hw.require_target_device(jax.devices()[0].device_kind)
        return False
    raise RuntimeError(
        f"the Pallas TT kernels run compiled on a TPU or interpreted on the "
        f"CPU; the {backend!r} backend is neither — use backend='xla'")


def _compiler_params(*semantics: str) -> pltpu.CompilerParams:
    return pltpu.CompilerParams(dimension_semantics=semantics,
                                vmem_limit_bytes=hw.VMEM_BUDGET_BYTES)


def _round_up(x: int, mult: int) -> int:
    return -(-x // mult) * mult


def _pad_to(a: jax.Array, mults: Sequence[int]) -> jax.Array:
    """Zero-pad every axis of ``a`` up to a multiple of ``mults[axis]``."""
    widths = [(0, (-n) % m) for n, m in zip(a.shape, mults)]
    return jnp.pad(a, widths) if any(w for _, w in widths) else a


def _scales_smem(scales, d: int) -> jax.Array:
    """Per-core scales (execution order) → ``[d, 1]`` fp32 array for the
    SMEM block the int8 kernel bodies index as ``s_ref[j, 0]``."""
    s = jnp.asarray(scales, jnp.float32).reshape(-1)
    if s.shape[0] != d:
        raise ValueError(
            f"expected {d} per-core scales, got {s.shape[0]}")
    return s.reshape(d, 1)


def _require_int8(arrays, what: str) -> None:
    for a in arrays:
        if a.dtype != jnp.int8:
            raise ValueError(
                f"{what} must be int8 (got {a.dtype}) — quantize with "
                f"core.quant.pack_core_int8 / quantize_cores")


# ---------------------------------------------------------------------------
# Kernel 1: single einsum step, blocked + accumulated
# ---------------------------------------------------------------------------

def _tt_step_body(x_ref, p_ref, *rest):
    """out[b, m·r0] += X[b, n·r1] @ P[n·r1, m·r0] over one contraction
    tile, fp32 accumulation in the revisited output block.  With a scale
    operand the int8 tile is widened for the MXU and the SMEM scale is
    applied to the product."""
    o_ref = rest[-1]

    @pl.when(pl.program_id(2) == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    part = jnp.dot(x_ref[...].astype(jnp.float32),
                   p_ref[...].astype(jnp.float32),
                   preferred_element_type=jnp.float32)
    if len(rest) == 2:
        part = part * rest[0][0, 0]
    o_ref[...] += part


@functools.partial(jax.jit, static_argnames=("plan", "interpret"))
def _tt_step_call(G: jax.Array, X: jax.Array, plan: BlockPlan,
                  interpret: bool, scale: jax.Array | None = None
                  ) -> jax.Array:
    """Shared fp/int8 scaffolding: packing, padding, grid, BlockSpecs.
    ``scale`` (a [1, 1] fp32 array) appends the int8 SMEM operand; the
    tiling logic is single-sourced for both variants."""
    r0, n, m, r1 = G.shape
    b = X.shape[0]
    tb, tk, tn = step_tiles(min(plan.bm, m), min(plan.bb, b),
                            min(plan.bn, n), r1, r0)
    P = _pad_to(pack_core(G), (tk, tn))                 # [n·r1, m·r0]
    X2 = _pad_to(X.reshape(b, n * r1), (tb, tk))
    bp, kp = X2.shape
    grid = (P.shape[1] // tn, bp // tb, kp // tk)

    in_specs = [pl.BlockSpec((tb, tk), lambda i, j, k: (j, k)),
                pl.BlockSpec((tk, tn), lambda i, j, k: (k, i))]
    args = (X2, P)
    if scale is not None:
        in_specs.append(pl.BlockSpec((1, 1), lambda i, j, k: (0, 0),
                                     memory_space=pltpu.SMEM))
        args += (scale,)

    out = pl.pallas_call(
        _tt_step_body,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((tb, tn), lambda i, j, k: (j, i)),
        out_shape=jax.ShapeDtypeStruct((bp, P.shape[1]), jnp.float32),
        compiler_params=_compiler_params("parallel", "parallel",
                                         "arbitrary"),
        interpret=interpret,
    )(*args)
    # [b, m, r0] → the chain's [m, b, r0] state order
    return out[:b, :m * r0].reshape(b, m, r0).transpose(1, 0, 2)


def tt_step_pallas(G: jax.Array, X: jax.Array, plan: BlockPlan,
                   interpret: bool | None = None) -> jax.Array:
    """``G [r0, n, m, r1]``, ``X [b, n, r1]`` → ``out [m, b, r0]`` (fp32).

    Inputs are zero-padded to tile multiples (padding on the contraction
    contributes 0 to the accumulation; padding on m/b is sliced off), so
    block shapes never have to divide the problem — the paper's "padding
    ukernel" (§4.3.4) replaced by masked tiles.
    """
    if interpret is None:
        interpret = _interpret_default()
    LAUNCH_COUNTS["step"] += 1
    return _tt_step_call(G, X, plan, interpret)


def tt_step_int8_pallas(G: jax.Array, scale, X: jax.Array, plan: BlockPlan,
                        interpret: bool | None = None) -> jax.Array:
    """int8 variant of ``tt_step_pallas``: ``G [r0, n, m, r1]`` **int8**
    with one symmetric fp32 ``scale``, ``X [b, n, r1]`` → ``out [m, b, r0]``
    (fp32).  Packed-core tiles are int8-resident in VMEM (4× the fp32
    residency headroom in ``select_blocks``'s fit term); dequantization is
    the widen + epilogue scale inside the kernel body."""
    if interpret is None:
        interpret = _interpret_default()
    _require_int8([G], "step core G")
    LAUNCH_COUNTS["step_int8"] += 1
    return _tt_step_call(G, X, plan, interpret,
                         scale=_scales_smem([scale], 1))


# ---------------------------------------------------------------------------
# Kernel 2: fused arbitrary-depth chain (d=2 included)
# ---------------------------------------------------------------------------

def _fused_chain_body(*refs, ns, ms, ranks, quantized):
    """All d packed matmuls for one batch tile, every relayout in VMEM.

    Tokens sit on lanes.  Before the step on core t the state is
    ``R_t [n_t·r_t, b_t·bb]``: its rows are the contraction (n_t, r_t) of
    packed core P_t, its lanes are b_t blocks of bb tokens, one per index
    of the other modes in core.tt.tt_apply_batched's order
    (m_{t+1} … m_d, n_1 … n_{t-1}).  Each step is one MXU matmul
    ``P_tᵀ @ R_t → [m_t·r_{t-1}, b_t·bb]`` followed by the paper's §4.3.2
    inter-step relayout, which here only moves whole ``[r_{t-1}, bb]``
    blocks: row block (m_t) and the lane block's trailing index
    (n_{t-1}) trade places.  After the last step the rows are m_1 and the
    lane blocks (m_2 … m_d); strided stores into ``scr`` interleave them
    into the m-major output order, and one transpose per 128 tokens puts
    tokens back on rows.
    """
    if quantized:
        x_ref, *p_refs, s_ref, o_ref, scr = refs
    else:
        x_ref, *p_refs, o_ref, scr = refs
    d = len(ns)
    bb = x_ref.shape[0]
    f32 = jnp.float32
    xt = x_ref[...].astype(f32).T                     # [N, bb]
    bt = xt.shape[0] // ns[-1]
    xt = xt.reshape(bt, ns[-1], bb)
    state = jnp.concatenate([xt[j] for j in range(bt)], axis=1)
    for j, t in enumerate(range(d - 1, -1, -1)):
        # MXU matmul:  P_tᵀ [m_t·r_{t-1}, n_t·r_t] @ R_t [n_t·r_t, b_t·bb]
        q = jax.lax.dot_general(
            p_refs[j][...].astype(f32), state, (((0,), (0,)), ((), ())),
            preferred_element_type=f32)
        if quantized:
            q = q * s_ref[j, 0]
        if t == 0:
            break
        mt, r, ni = ms[t], ranks[t], ns[t - 1]
        nj = bt // ni
        state = jnp.concatenate([
            jnp.concatenate([q[k * r:(k + 1) * r,
                               (jj * ni + i) * bb:(jj * ni + i + 1) * bb]
                             for k in range(mt) for jj in range(nj)],
                            axis=1)
            for i in range(ni)], axis=0)              # [n_{t-1}·r_{t-1}, ·]
        bt = mt * nj
    m1, lanes = ms[0], hw.LANES
    for c in range(bb // lanes):
        for jb in range(bt):
            lo = jb * bb + c * lanes
            scr[pl.ds(jb, m1, stride=bt), :] = q[:, lo:lo + lanes]
        o_ref[c * lanes:(c + 1) * lanes, :] = scr[...].T.astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("dims", "block_b", "interpret"))
def _tt_fused_chain_call(x: jax.Array, packed: tuple[jax.Array, ...],
                         dims, block_b: int, interpret: bool,
                         scales: jax.Array | None = None) -> jax.Array:
    """Shared fp/int8 scaffolding (padding, grid, BlockSpecs); ``scales``
    ([d, 1] fp32, execution order) appends the int8 SMEM operand."""
    ns, ms, ranks = dims
    d = len(ns)
    B, N = x.shape
    M = prod(ms)
    # tokens go on lanes inside the kernel: the tile is whole lane widths
    bb = _round_up(min(block_b, B), hw.LANES)
    xp = _pad_to(x, (bb, 1))
    Bp = xp.shape[0]

    # packed cores in execution order (core d first); each is one whole-array
    # block so it is resident in VMEM for every grid step.
    p_specs = [pl.BlockSpec(p.shape, lambda i: (0, 0)) for p in packed]
    in_specs = [pl.BlockSpec((bb, N), lambda i: (i, 0))] + p_specs
    args = (xp,) + tuple(packed)
    if scales is not None:
        in_specs.append(pl.BlockSpec((d, 1), lambda i: (0, 0),
                                     memory_space=pltpu.SMEM))
        args += (scales,)
    body = functools.partial(_fused_chain_body, ns=ns, ms=ms, ranks=ranks,
                             quantized=scales is not None)

    out = pl.pallas_call(
        body,
        grid=(Bp // bb,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bb, M), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((Bp, M), x.dtype),
        scratch_shapes=[pltpu.VMEM((M, hw.LANES), jnp.float32)],
        compiler_params=_compiler_params("parallel"),
        interpret=interpret,
    )(*args)
    return out[:B]


def _check_chain_args(packed, ns) -> None:
    if not (len(packed) == len(ns) >= 2):
        raise ValueError(
            f"fused chain needs d >= 2 packed cores matching dims "
            f"(got {len(packed)} cores for {len(ns)} modes)")


def _fit_tile(x: jax.Array, dims, weight_itemsize: int) -> int:
    """The analytical VMEM-fit batch tile, or a ValueError when the chain
    is not VMEM-resident at any tile."""
    ns, ms, ranks = dims
    tile = fused_chain_batch_tile(ns, ms, ranks,
                                  itemsize=max(x.dtype.itemsize, 4),
                                  weight_itemsize=weight_itemsize)
    if tile is None:
        raise ValueError(
            "chain does not fit VMEM at any batch tile — use the per-step "
            "kernel (or backend='auto')")
    return tile


def _fused2_dims(dims: tuple[int, int, int, int, int]):
    n1, n2, m1, m2, r1 = dims
    return (n1, n2), (m1, m2), (1, r1, 1)


def tt_fused2_pallas(x: jax.Array, p2: jax.Array, p1: jax.Array,
                     dims: tuple[int, int, int, int, int],
                     block_b: int | None = None,
                     interpret: bool | None = None) -> jax.Array:
    """Fused d=2 TT layer.  ``x [B, n1·n2]`` → ``y [B, m1·m2]``.

    ``p2 [n2, m2·r1]``, ``p1 [n1·r1, m1]`` are the *packed* cores
    (core.packing.pack_core) — constant layout fixed at compile time.
    ``block_b=None`` selects the batch tile from the analytical VMEM model
    (``fused_chain_batch_tile``); callers with a measured winner (the
    autotuner) pass it explicitly.
    """
    if interpret is None:
        interpret = _interpret_default()
    chain = _fused2_dims(dims)
    if block_b is None:
        block_b = _fit_tile(x, chain, p1.dtype.itemsize)
    LAUNCH_COUNTS["fused2"] += 1
    return _tt_fused_chain_call(x, (p2, p1), chain, block_b, interpret)


def tt_fused2_int8_pallas(x: jax.Array, p2: jax.Array, p1: jax.Array,
                          scales,
                          dims: tuple[int, int, int, int, int],
                          block_b: int | None = None,
                          interpret: bool | None = None) -> jax.Array:
    """int8 fused d=2 TT layer.  ``x [B, n1·n2]`` → ``y [B, m1·m2]``.

    ``p2 [n2, m2·r1]``, ``p1 [n1·r1, m1]`` are **int8** packed cores
    (core.quant.pack_core_int8); ``scales`` are their fp32 scales in the
    same (execution) order ``[s2, s1]``.  The cores stay int8 in VMEM, so
    the analytical tile prices them at 1 byte/elem."""
    if interpret is None:
        interpret = _interpret_default()
    _require_int8([p1, p2], "fused2 packed cores")
    chain = _fused2_dims(dims)
    if block_b is None:
        block_b = _fit_tile(x, chain, 1)
    LAUNCH_COUNTS["fused2_int8"] += 1
    return _tt_fused_chain_call(x, (p2, p1), chain, block_b, interpret,
                                scales=_scales_smem(scales, 2))


def tt_fused_chain_pallas(x: jax.Array, packed: Sequence[jax.Array],
                          dims: tuple[tuple[int, ...], tuple[int, ...],
                                      tuple[int, ...]],
                          block_b: int | None = None,
                          interpret: bool | None = None) -> jax.Array:
    """Fused arbitrary-depth TT chain.  ``x [B, N] → y [B, M]``.

    ``packed`` are the pack_core() matrices in *execution* order (core d
    first): ``packed[j] = P_{d-j}`` of shape ``[n_t·r_t, m_t·r_{t-1}]``.
    ``dims = (ns, ms, ranks)`` is the TTPlan signature.  One ``pallas_call``
    over batch tiles runs the whole chain; intermediates never leave VMEM.

    ``block_b=None`` takes the analytical VMEM-fit tile
    (``fused_chain_batch_tile``); the autotuner passes a measured winner.
    Callers must ensure the chain fits (``fused_chain_batch_tile`` is not
    None) — the analytical fallback raises otherwise.  The kernel rounds
    the tile up to whole lane widths (128 tokens).
    """
    if interpret is None:
        interpret = _interpret_default()
    _check_chain_args(packed, dims[0])
    if block_b is None:
        block_b = _fit_tile(x, dims, packed[0].dtype.itemsize)
    LAUNCH_COUNTS["fused_chain"] += 1
    return _tt_fused_chain_call(x, tuple(packed), dims, block_b, interpret)


def tt_fused_chain_int8_pallas(x: jax.Array, packed: Sequence[jax.Array],
                               scales,
                               dims: tuple[tuple[int, ...], tuple[int, ...],
                                           tuple[int, ...]],
                               block_b: int | None = None,
                               interpret: bool | None = None) -> jax.Array:
    """int8 fused arbitrary-depth TT chain.  ``x [B, N] → y [B, M]``.

    ``packed`` are **int8** ``pack_core_int8`` matrices in *execution*
    order (core d first) and ``scales`` their fp32 scales in the same
    order.  One ``pallas_call`` runs the whole chain; the packed cores are
    int8-resident in VMEM for every grid step, so the default tile comes
    from the dtype-aware fit test (``weight_itemsize=1``) — chains whose
    fp32 weights bust the VMEM budget can still fuse here."""
    if interpret is None:
        interpret = _interpret_default()
    ns = dims[0]
    _check_chain_args(packed, ns)
    _require_int8(packed, "fused chain packed cores")
    if block_b is None:
        block_b = _fit_tile(x, dims, 1)
    LAUNCH_COUNTS["fused_chain_int8"] += 1
    return _tt_fused_chain_call(x, tuple(packed), dims, block_b, interpret,
                                scales=_scales_smem(scales, len(ns)))
