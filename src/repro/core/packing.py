"""Compile-time core packing and block-shape selection (paper §4.3 → TPU).

The paper's compiler pipeline for the einsum kernel is:
  array packing (compile-time re-layout of the constant core G)
  → vectorize the r-loop (multiples of vl)
  → register blocking chosen by an analytical load/store model (§4.3.4)
  → L2 cache tiling chosen by a cache-way occupancy model (§4.3.5).

TPU transfer (DESIGN.md §2): the constant core is packed into an
MXU-friendly matrix at parameter-build time; "registers" become VMEM tiles;
the L/S-instruction objective becomes an HBM-bytes-moved objective; the
L2-fit test (Eq. 26–28) becomes a VMEM-residency constraint.  The shape of
the model is identical — minimize memory traffic subject to a fast-memory
capacity — only the constants changed.

Every fit test takes a *per-operand* itemsize (DESIGN.md §8): ``itemsize``
prices the activations/states (fp32 accumulation ⇒ 4), ``weight_itemsize``
prices the resident packed cores (4 fp32, 2 bf16, 1 int8).  Int8-resident
weights shrink the residency term 4×, which directly enlarges the
fused-chain eligibility set and the batch tile.
"""
from __future__ import annotations

import dataclasses

from . import hw
from .flops import prod


def pack_core(G):
    """Compile-time array packing of one TT core.

    ``G [r_{t-1}, n_t, m_t, r_t]``  →  ``P [(n_t·r_t), (m_t·r_{t-1})]``
    so that the step contraction becomes ``state2 @ P`` on the MXU.  This is
    the paper's §4.3.1 re-layout: executed offline (at parameter build /
    checkpoint load), never at inference time.
    """
    r0, n, m, r1 = G.shape
    return G.transpose(1, 3, 2, 0).reshape(n * r1, m * r0)


@dataclasses.dataclass(frozen=True)
class BlockPlan:
    """Chosen VMEM tiling for one einsum step out[m,b,r0] += G·x."""
    bm: int          # m-tile
    bb: int          # b-tile
    bn: int          # n-tile (grid-accumulated)
    traffic_bytes: int
    vmem_bytes: int


def _round_up(x: int, mult: int) -> int:
    return ((x + mult - 1) // mult) * mult


# Row multiple of every step-kernel tile: the sublane tile of int8
# operands (bf16 needs 16, fp32 8), so one rule suits every dtype.
ROW_TILE = 32


def step_tiles(bm: int, bb: int, bn: int, rt: int, rt_1: int
               ) -> tuple[int, int, int]:
    """The 2-D tiles the step kernel runs a (bm, bb, bn) block as: rows of
    X, the contraction ``n·r_t`` and the output columns ``m·r_{t-1}``,
    each rounded up to the TPU (row, lane) tile that Mosaic requires."""
    return (_round_up(bb, ROW_TILE), _round_up(bn * rt, hw.LANES),
            _round_up(bm * rt_1, hw.LANES))


def _divisors_pow2(n: int, lo: int, hi: int):
    v = lo
    while v <= min(n, hi):
        yield v
        v *= 2
    if n < hi and (n & (n - 1)) != 0:
        yield n           # the full (non-pow2) extent, padded by mosaic


def select_blocks(mt: int, bt: int, nt: int, rt: int, rt_1: int,
                  itemsize: int = 4,
                  vmem_budget: int = hw.VMEM_BUDGET_BYTES,
                  weight_itemsize: int | None = None) -> BlockPlan:
    """Analytical block-shape selection (paper §4.3.4 step 2–3).

    HBM traffic model for grid (m/bm, b/bb, n/bn) with n innermost
    (accumulation):

      bytes(G)   = ceil(m/bm) … G re-read once per *b*-tile
      bytes(X)   = ceil(b/bb) … X re-read once per *m*-tile
      bytes(out) = written once

    Minimize total subject to double-buffered VMEM residency of the tiles
    the kernel really holds (:func:`step_tiles`: the packed-core tile
    ``[bn·rt, bm·rt_1]``, the X tile ``[bb, bn·rt]`` and the fp32 output
    tile ``[bb, bm·rt_1]``, lane dims padded to 128 and rows to
    ``ROW_TILE`` — the TPU analogue of the paper's vl-multiple rule):
      2·(w·tk·tn + itemsize·(tb·tk + tb·tn)) ≤ budget.

    ``weight_itemsize`` prices the resident G tile separately from the
    activation tiles (int8-resident cores: 1 byte/elem, DESIGN.md §8).
    """
    cands = select_blocks_candidates(mt, bt, nt, rt, rt_1, itemsize,
                                     vmem_budget, k=1,
                                     weight_itemsize=weight_itemsize)
    return cands[0]


def select_blocks_candidates(mt: int, bt: int, nt: int, rt: int, rt_1: int,
                             itemsize: int = 4,
                             vmem_budget: int = hw.VMEM_BUDGET_BYTES,
                             k: int = 4,
                             weight_itemsize: int | None = None
                             ) -> list[BlockPlan]:
    """Top-``k`` feasible block plans by the analytical traffic model,
    best first.  The empirical autotuner (kernels.autotune) times these
    on-device instead of trusting the model's ranking — the measured
    counterpart of the paper's §4.3.4 'pick the analytical argmin'."""
    w_item = itemsize if weight_itemsize is None else weight_itemsize
    g_total = mt * nt * rt * rt_1 * w_item
    x_total = bt * nt * rt * itemsize
    o_total = mt * bt * rt_1 * itemsize

    cands: list[BlockPlan] = []
    for bm in _divisors_pow2(mt, 8, 512):
        for bb in _divisors_pow2(bt, 8, 1024):
            for bn in _divisors_pow2(nt, 8, 2048):
                tb, tk, tn = step_tiles(bm, bb, bn, rt, rt_1)
                vmem = 2 * (w_item * tk * tn + itemsize * (tb * tk + tb * tn))
                if vmem > vmem_budget:
                    continue
                n_mtiles = -(-mt // bm)
                n_btiles = -(-bt // bb)
                traffic = (g_total * n_btiles + x_total * n_mtiles + o_total)
                cands.append(BlockPlan(bm, bb, bn, traffic, vmem))
    if not cands:         # degenerate tiny problem: single block
        return [BlockPlan(min(mt, 8), min(bt, 8), min(nt, 8),
                          g_total + x_total + o_total, 0)]
    cands.sort(key=lambda c: (c.traffic_bytes, -c.vmem_bytes))
    return cands[:k]


@dataclasses.dataclass(frozen=True)
class FitReport:
    """Priced VMEM-fit verdict for one whole chain — the structured form
    of the Eq. 26 test the plan resolver (kernels.plan) records in every
    ``TTExecutionPlan``, instead of each caller re-deriving it."""
    fits: bool                   # VMEM-resident at SOME power-of-two tile
    batch_tile: int | None       # the largest such tile (None when not)
    weight_bytes: int            # packed-core residency at weight_itemsize
    peak_state_bytes: int        # per-row peak consecutive state pair


def chain_fit_report(ns, ms, ranks, itemsize: int = 4,
                     vmem_budget: int = hw.VMEM_BUDGET_BYTES,
                     weight_itemsize: int | None = None) -> FitReport:
    """One-stop fused-chain fit verdict: the ``fused_chain_batch_tile``
    decision plus the byte terms it priced, so the caller can persist WHY
    a chain did or did not fuse (plan provenance, DESIGN.md §10)."""
    w_item = itemsize if weight_itemsize is None else weight_itemsize
    sizes = chain_state_sizes(ns, ms, ranks)
    w_elems = chain_weight_elems(ns, ms, ranks)
    peak = max((a + b for a, b in zip(sizes, sizes[1:])), default=sizes[0])
    tile = fused_chain_batch_tile(ns, ms, ranks, itemsize=itemsize,
                                  vmem_budget=vmem_budget,
                                  weight_itemsize=w_item)
    return FitReport(fits=tile is not None, batch_tile=tile,
                     weight_bytes=w_elems * w_item,
                     peak_state_bytes=peak * itemsize)


def chain_state_sizes(ns, ms, ranks) -> list[int]:
    """Per-batch-element feature sizes of the chain states s_0 … s_d.

    s_0 = N = Π n_t; after the step on core ``t`` (executed d → 1) the state
    is [m_t, b_t, r_{t-1}] flattened, so s_{d-t+1} = m_t·b_t·r_{t-1};
    s_d = M.  These are the intermediates the fused kernel keeps in VMEM.
    """
    d = len(ns)
    f = prod(ns)
    sizes = [f]
    for t in range(d - 1, -1, -1):
        bt = f // (ns[t] * ranks[t + 1])
        f = ms[t] * bt * ranks[t]
        sizes.append(f)
    return sizes


def chain_weight_elems(ns, ms, ranks) -> int:
    """Total element count of the packed cores P_1 … P_d."""
    return sum(ns[t] * ranks[t + 1] * ms[t] * ranks[t]
               for t in range(len(ns)))


def fused_chain_vmem_bytes(bb: int, ns, ms, ranks, itemsize: int = 4,
                           weight_itemsize: int | None = None) -> int:
    """VMEM the fused-chain kernel holds for one batch tile of ``bb`` rows
    (``kernels.tt_contract``): the double-buffered ``x``/``y`` tiles, the
    fp32 state pair with the relayout copy of each (tokens on lanes), the
    ``[M, 128]`` scratch that interleaves the output, the double-buffered
    packed cores and one core widened to fp32 for the MXU.  ``itemsize``
    prices activations and states, ``weight_itemsize`` the cores."""
    w_item = itemsize if weight_itemsize is None else weight_itemsize
    sizes = chain_state_sizes(ns, ms, ranks)
    peak = max(a + b for a, b in zip(sizes, sizes[1:]))
    widest = max(ns[t] * ranks[t + 1] * ms[t] * ranks[t]
                 for t in range(len(ns)))
    return (2 * itemsize * bb * (sizes[0] + sizes[-1])
            + 2 * itemsize * bb * peak
            + itemsize * sizes[-1] * hw.LANES
            + 2 * w_item * chain_weight_elems(ns, ms, ranks)
            + 4 * widest)


def fused_chain_batch_tile(ns, ms, ranks, itemsize: int = 4,
                           vmem_budget: int = hw.VMEM_BUDGET_BYTES,
                           weight_itemsize: int | None = None
                           ) -> int | None:
    """Largest power-of-two batch tile, from 1024 down to one lane width
    (128 rows — the kernel puts tokens on lanes), whose
    :func:`fused_chain_vmem_bytes` fits the budget, or ``None`` when even
    128 rows do not — the caller must then fall back to the per-step
    kernel.  This is the fused-chain analogue of the paper's L2-fit test
    (Eq. 26–28).  ``weight_itemsize=1`` (int8-resident cores) admits
    chains whose fp32 weights alone bust the budget."""
    bb = 1024
    while bb >= hw.LANES:
        if fused_chain_vmem_bytes(bb, ns, ms, ranks, itemsize,
                                  weight_itemsize) <= vmem_budget:
            return bb
        bb //= 2
    return None
