"""The paged decode attention kernel (``kernels/paged_attention.py``),
interpreted on the CPU, against the path it replaces: the logical cache
gathered from the arena through the block table, widened to f32, and
attended over with the position mask (``attention._gqa_scores_ctx``).

Each case is a batch of rows at chosen positions, over tables built to
cover what the kernel must get right: the first and last position of a
block and of the logical cache, inactive rows, two tables sharing prefix
blocks, table entries past the live blocks pointing at the sentinel, and
rows with more live blocks than one of the kernel's copy-and-compute
chunks.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.paged_attention import BLOCKS_PER_STEP, \
    paged_decode_attention
from repro.models.attention import _gqa_scores_ctx

BLK, HD = 8, 16
LAST = 6 * BLK - 1                 # last logical position of a 6-entry table
LONG = (BLOCKS_PER_STEP + 3) * BLK  # a table longer than one chunk

# (positions of the rows, active mask, shared prefix, sentinel tails)
CASES = {
    "block_edges": ([0, BLK - 1, BLK, BLK + 1, LAST], None, False, False),
    "inactive_rows": ([3, 2 * BLK, 0, LAST], [True, False, True, False],
                      False, False),
    "shared_prefix": ([2 * BLK + 3, 3 * BLK - 1, BLK], None, True, False),
    "sentinel_tail": ([1, BLK + 2, 3 * BLK, LAST - BLK], None, False, True),
    "long_rows": ([LONG - 1, (BLOCKS_PER_STEP + 1) * BLK + 2, BLK - 1, 0],
                  [True, True, True, False], False, True),
}


def _tables(pos: np.ndarray, rng, shared: bool, tail: bool):
    """Distinct arena blocks for every row's table (``max_blocks`` entries,
    the logical cache covering the furthest position, at least 6); with
    ``shared`` rows 0 and 1 share their first two blocks (a published
    prefix); with ``tail`` the entries past each row's live blocks point
    at the sentinel, as the scheduler leaves what it has not reserved.
    Returns (tables, number of arena blocks without the sentinel)."""
    max_blocks = max(6, int(pos.max()) // BLK + 1)
    num_blocks = len(pos) * max_blocks + 2
    ids = rng.permutation(num_blocks)[:len(pos) * max_blocks]
    bt = ids.reshape(len(pos), max_blocks).astype(np.int32)
    if shared:
        bt[1, :2] = bt[0, :2]
    if tail:
        for b, p in enumerate(pos):
            bt[b, p // BLK + 1:] = num_blocks
    return bt, num_blocks


def _gather_path(q, ak, av, bt, pos, scale):
    """Today's path: gather the whole logical cache, attend in f32."""
    B, M = bt.shape
    KV = ak.shape[2]
    gk = ak[bt].reshape(B, M * BLK, KV, HD)
    gv = av[bt].reshape(B, M * BLK, KV, HD)
    mask = (jnp.arange(M * BLK)[None, :] <= pos[:, None])
    ctx = _gqa_scores_ctx(q[:, None], gk, gv,
                          mask[:, None, None, None, :], scale)
    return ctx.reshape(q.shape)


@pytest.mark.parametrize("case", sorted(CASES))
@pytest.mark.parametrize("heads,kv_heads", [(4, 4), (8, 2)],
                         ids=["mha", "gqa_g4"])
@pytest.mark.parametrize("layer", [0, 2])
def test_kernel_matches_gather_path(case, heads, kv_heads, layer):
    positions, active, shared, tail = CASES[case]
    rng = np.random.default_rng(sum(map(ord, case)) + heads)
    pos = np.asarray(positions, np.int32)
    act = np.ones(len(pos), bool) if active is None else np.asarray(active)
    bt, num_blocks = _tables(pos, rng, shared, tail)
    shape = (num_blocks + 1, BLK, kv_heads, HD)
    draw = lambda *s: jnp.asarray(rng.standard_normal(s),  # noqa: E731
                                  jnp.bfloat16)
    q = draw(len(pos), heads, HD)
    # a stack of three layers' arenas: the one read, and two others
    stack_k, stack_v = draw(3, *shape), draw(3, *shape)
    scale = 1.0 / np.sqrt(HD)
    lengths = jnp.asarray(np.where(act, pos + 1, 0), jnp.int32)
    got = paged_decode_attention(q, stack_k, stack_v, jnp.asarray(bt),
                                 lengths, jnp.int32(layer), scale=scale)
    want = _gather_path(q, stack_k[layer], stack_v[layer], jnp.asarray(bt),
                        jnp.asarray(pos), scale)
    got = np.asarray(got, np.float32)
    assert np.isfinite(got).all()
    np.testing.assert_array_equal(got[~act], 0.0)
    # both paths accumulate in f32 and round the context to bf16 once
    np.testing.assert_allclose(got[act], np.asarray(want, np.float32)[act],
                               rtol=1e-2, atol=1e-2)
