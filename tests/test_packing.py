"""Block-shape selection + VMEM models (paper §4.3.4/§4.3.5 → TPU),
including the per-operand-itemsize (weights vs activations) fit model of
DESIGN.md §8."""
from repro.core import hw
from repro.core.packing import (BlockPlan, chain_state_sizes,
                                fused_chain_batch_tile,
                                fused_chain_vmem_bytes, select_blocks,
                                step_tiles)


def test_select_blocks_respects_vmem_budget():
    plan = select_blocks(mt=4096, bt=8192, nt=64, rt=16, rt_1=16)
    assert plan.vmem_bytes <= hw.VMEM_BUDGET_BYTES
    assert plan.bm >= 8 and plan.bb >= 8 and plan.bn >= 8


def test_select_blocks_traffic_model_consistency():
    """The chosen plan minimizes the modeled traffic among a few manual
    alternatives (sanity on the objective, paper step 3)."""
    mt, bt, nt, rt, rt_1 = 1024, 2048, 32, 8, 8
    best = select_blocks(mt, bt, nt, rt, rt_1)

    def traffic(bm, bb):
        it = 4
        g = mt * nt * rt * rt_1 * it
        x = bt * nt * rt * it
        o = mt * bt * rt_1 * it
        return g * (-(-bt // bb)) + x * (-(-mt // bm)) + o

    assert best.traffic_bytes <= traffic(8, 8)
    assert best.traffic_bytes <= traffic(128, 128)


def test_select_blocks_tiny_problem():
    plan = select_blocks(mt=4, bt=4, nt=4, rt=1, rt_1=1)
    assert isinstance(plan, BlockPlan)
    assert plan.bm <= 8


def test_bigger_budget_never_increases_traffic():
    """Paper Eq. 26→28 intuition: more fast memory → no more HBM traffic."""
    small = select_blocks(2048, 4096, 64, 8, 8, vmem_budget=1 << 20)
    large = select_blocks(2048, 4096, 64, 8, 8, vmem_budget=64 << 20)
    assert large.traffic_bytes <= small.traffic_bytes


def test_step_tiles_are_tpu_aligned():
    """The step kernel runs a BlockPlan as 2-D tiles whose lane dims are
    whole 128-lane widths and whose rows suit int8 (32-row) tiles."""
    tb, tk, tn = step_tiles(bm=8, bb=99, bn=8, rt=16, rt_1=1)
    assert (tb, tk, tn) == (128, 128, 128)
    assert step_tiles(512, 1024, 64, 16, 16) == (1024, 1024, 8192)


def test_fused_chain_vmem_bytes_grows_with_tile():
    ns, ms, ranks = (8, 512), (1376, 8), (1, 16, 1)
    sizes = chain_state_sizes(ns, ms, ranks)
    small = fused_chain_vmem_bytes(128, ns, ms, ranks)
    big = fused_chain_vmem_bytes(256, ns, ms, ranks)
    # per-row cost: x/y tiles (double-buffered) and the state pair with
    # its relayout copy, all at fp32
    per_row = 2 * 4 * (sizes[0] + sizes[-1]) + 2 * 4 * max(
        a + b for a, b in zip(sizes, sizes[1:]))
    assert big - small == 128 * per_row


def test_fused_chain_batch_tile_is_largest_fitting_lane_multiple():
    ns, ms, ranks = (8, 512), (1376, 8), (1, 16, 1)
    tile = fused_chain_batch_tile(ns, ms, ranks)
    assert tile is not None and tile % hw.LANES == 0
    assert fused_chain_vmem_bytes(tile, ns, ms, ranks) <= \
        hw.VMEM_BUDGET_BYTES
    assert tile == 1024 or fused_chain_vmem_bytes(
        2 * tile, ns, ms, ranks) > hw.VMEM_BUDGET_BYTES
    # below one lane width nothing fits: the chain is step-fallback
    budget = fused_chain_vmem_bytes(hw.LANES, ns, ms, ranks) - 1
    assert fused_chain_batch_tile(ns, ms, ranks,
                                  vmem_budget=budget) is None


# ---------------------------------------------------------------------------
# Per-operand itemsize (DESIGN.md §8): int8-resident weights enlarge the
# eligibility set and never shrink a tile
# ---------------------------------------------------------------------------

def test_fused_chain_tile_grows_under_int8_residency():
    """The dtype-aware fit test: int8 weights never yield a smaller tile,
    and on a weight-dominated chain they admit a strictly larger one (or
    flip None → fused-eligible)."""
    ns, ms, ranks = (2, 4096), (4096, 2), (1, 512, 1)
    t_fp = fused_chain_batch_tile(ns, ms, ranks, weight_itemsize=4)
    t_bf = fused_chain_batch_tile(ns, ms, ranks, weight_itemsize=2)
    t_q = fused_chain_batch_tile(ns, ms, ranks, weight_itemsize=1)
    assert t_fp is None and t_bf is None      # 34/17 MB of cores, twice
    assert t_q == hw.LANES                    # 8.4 MB int8: fused
    # a smaller chain: tile is monotone non-decreasing as weights shrink
    ns2, ms2, ranks2 = (8, 8, 8), (8, 8, 8), (1, 8, 8, 1)
    tiles = [fused_chain_batch_tile(ns2, ms2, ranks2, weight_itemsize=w)
             for w in (4, 2, 1)]
    assert all(t is not None for t in tiles)
    assert tiles[0] <= tiles[1] <= tiles[2]
