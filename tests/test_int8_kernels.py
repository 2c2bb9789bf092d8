"""Int8-resident kernels (DESIGN.md §8), interpret mode.

Contract under test: (1) every int8 kernel variant is *exact* against the
dequantize-then-fp32-chain reference (the in-kernel epilogue scale is
algebraically identical to pre-matmul dequantization); (2) the ``auto``
routing under int8 issues ONE ``pallas_call`` for a VMEM-resident chain
(``LAUNCH_COUNTS``); (3) the dtype-aware fit model admits chains under
int8 residency that are step-fallback in fp32 — the compound speedup the
whole PR is about.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import hw
from repro.core.packing import (BlockPlan, fused_chain_batch_tile,
                                fused_chain_vmem_bytes, pack_core)
from repro.core.quant import (dequantize_cores, pack_core_int8,
                              quantize_core, quantize_cores)
from repro.core.tt import make_plan, tt_apply, tt_init
from repro.kernels import autotune, tt_contract
from repro.kernels.ops import parse_backend_spec, tt_forward
from repro.kernels.tt_contract import (tt_fused2_int8_pallas,
                                       tt_fused_chain_int8_pallas,
                                       tt_step_int8_pallas)

KEY = jax.random.PRNGKey(0)


def _setup(ms, ns, rank, B=8, seed=0):
    plan = make_plan(ms, ns, rank)
    cores = tt_init(jax.random.PRNGKey(seed), plan)
    x = jax.random.normal(jax.random.PRNGKey(seed + 1), (B, plan.N))
    return plan, cores, x


def _int8_reference(cores, x):
    """Dequantize-then-fp32-chain: what the int8 kernels must reproduce."""
    qs, ss = quantize_cores(cores)
    return tt_apply(dequantize_cores(qs, ss, jnp.float32), x)


CHAIN_CASES = [
    ((16, 8), (4, 16), 8, 33),           # d=2, B % tile != 0
    ((8, 4, 4), (4, 4, 8), 4, 19),       # d=3, ragged batch
    ((9, 5, 7), (3, 7, 5), 4, 12),       # d=3 all-odd factors
    ((4, 4, 4, 2), (2, 4, 4, 4), 4, 21),  # d=4, ragged batch
]


@pytest.mark.parametrize("ms,ns,rank,B", CHAIN_CASES)
def test_fused_chain_int8_exact_vs_dequant_reference(ms, ns, rank, B):
    plan, cores, x = _setup(ms, ns, rank, B)
    pq = [pack_core_int8(G) for G in reversed(cores)]
    got = tt_fused_chain_int8_pallas(
        x, [p for p, _ in pq], [s for _, s in pq],
        (plan.ns, plan.ms, plan.ranks), block_b=8, interpret=True)
    want = _int8_reference(cores, x)
    assert got.shape == (B, plan.M)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_fused2_int8_exact_vs_dequant_reference():
    plan, cores, x = _setup((16, 8), (4, 16), 8, 9)
    (q2, s2), (q1, s1) = pack_core_int8(cores[1]), pack_core_int8(cores[0])
    got = tt_fused2_int8_pallas(
        x, q2, q1, [s2, s1],
        (plan.ns[0], plan.ns[1], plan.ms[0], plan.ms[1], plan.ranks[1]),
        block_b=8, interpret=True)
    want = _int8_reference(cores, x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_step_int8_exact_vs_dequant_reference():
    plan, cores, _ = _setup((8, 4, 4), (4, 4, 8), 4, 1)
    G = cores[1]
    r0, n, m, r1 = G.shape
    Gq, s = quantize_core(G)
    X = jax.random.normal(jax.random.PRNGKey(3), (19, n, r1))
    got = tt_step_int8_pallas(Gq, s, X, BlockPlan(8, 8, 8, 0, 0),
                              interpret=True)
    want = jnp.einsum("rnmk,bnk->mbr", Gq.astype(jnp.float32) * s, X)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_pack_core_int8_commutes_with_packing():
    """Packing is a pure relayout, so pack-then-quantize ==
    quantize-then-pack, bit for bit (same scale, same int codes)."""
    _, cores, _ = _setup((8, 4, 4), (4, 4, 8), 4)
    for G in cores:
        pq, ps = pack_core_int8(G)
        q, s = quantize_core(G)
        assert float(ps) == float(s)
        np.testing.assert_array_equal(np.asarray(pq),
                                      np.asarray(pack_core(q)))


# ---------------------------------------------------------------------------
# tt_forward dispatch: every backend, both core-input conventions
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", ["xla", "pallas_step", "pallas_fused",
                                     "auto"])
def test_tt_forward_int8_backends_agree(backend):
    plan, cores, x = _setup((8, 4, 4), (4, 4, 8), 4, 13)
    want = _int8_reference(cores, x)
    got = tt_forward(cores, x, backend=backend, interpret=True, tune="off",
                     weights="int8")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_tt_forward_prequantized_matches_on_the_fly():
    """Stored int8 cores + scales (models/layers quantized storage) must
    produce bit-identical output to on-the-fly quantization of the float
    cores — the serving consistency contract."""
    plan, cores, x = _setup((8, 4, 4), (4, 4, 8), 4, 13)
    qs, ss = quantize_cores(cores)
    on_the_fly = tt_forward(cores, x, backend="auto", interpret=True,
                            tune="off", weights="int8")
    stored = tt_forward(qs, x, backend="auto", interpret=True, tune="off",
                        scales=ss)      # weights='int8' implied by dtype
    np.testing.assert_array_equal(np.asarray(on_the_fly),
                                  np.asarray(stored))


def test_backend_suffix_parsing():
    assert parse_backend_spec("auto") == ("auto", None, None)
    assert parse_backend_spec("auto:measure") == ("auto", "measure", None)
    assert parse_backend_spec("auto:int8") == ("auto", None, "int8")
    assert parse_backend_spec("auto:measure:int8") == \
        ("auto", "measure", "int8")
    # fp32 alias (TTConfig spelling) normalizes to the canonical 'fp'
    assert parse_backend_spec("auto:off:fp32") == ("auto", "off", "fp")
    # explicit arguments win over the suffix
    assert parse_backend_spec("auto:off:int8", tune="measure",
                              weights="fp") == ("auto", "measure", "fp")
    with pytest.raises(ValueError):
        parse_backend_spec("auto:bogus")
    # duplicate suffix tokens of one category are a conflict, not a
    # silent first-one-wins
    with pytest.raises(ValueError, match="conflicting tune"):
        parse_backend_spec("auto:cached:measure")
    with pytest.raises(ValueError, match="conflicting weight"):
        parse_backend_spec("auto:fp:int8")


def test_int8_cores_without_scales_raise():
    plan, cores, x = _setup((8, 4, 4), (4, 4, 8), 4, 4)
    qs, ss = quantize_cores(cores)
    with pytest.raises(ValueError, match="scales"):
        tt_forward(qs, x, backend="auto", interpret=True, tune="off")
    # conflicting scales are rejected, never silently dropped
    with pytest.raises(ValueError, match="quantized on the fly"):
        tt_forward(cores, x, backend="auto", interpret=True, tune="off",
                   weights="int8", scales=ss)
    with pytest.raises(ValueError, match="silently ignored"):
        tt_forward(cores, x, backend="xla", scales=ss)


# ---------------------------------------------------------------------------
# Launch counting + int8-only fused eligibility
# ---------------------------------------------------------------------------

def test_auto_int8_dispatches_single_fused_launch():
    """auto + weights='int8' on a VMEM-resident d=3 chain must issue
    exactly ONE pallas_call, of the int8 chain kernel."""
    plan, cores, x = _setup((8, 4, 4), (4, 4, 8), 4, 16)
    tt_contract.reset_launch_counts()
    tt_forward(cores, x, backend="auto", interpret=True, tune="off",
               weights="int8")
    assert tt_contract.launch_counts() == {"fused_chain_int8": 1}
    tt_contract.reset_launch_counts()
    tt_forward(cores, x, backend="pallas_step", interpret=True, tune="off",
               weights="int8")
    assert tt_contract.launch_counts() == {"step_int8": 3}


def test_chain_fused_eligible_only_under_int8(monkeypatch):
    """The acceptance bar: a chain whose fp32 weights bust the VMEM budget
    (step fallback, d launches) must fuse to ONE launch under int8
    residency — same chain, same batch, only the resident dtype changed."""
    plan, cores, x = _setup((8, 4, 4), (4, 4, 8), 4, 16)
    # budget at which int8 cores fit at the smallest (one-lane) tile; fp32
    # cores cost 8 more bytes per weight element there
    budget = fused_chain_vmem_bytes(hw.LANES, plan.ns, plan.ms, plan.ranks,
                                    weight_itemsize=1)
    assert fused_chain_batch_tile(plan.ns, plan.ms, plan.ranks,
                                  vmem_budget=budget,
                                  weight_itemsize=4) is None
    assert fused_chain_batch_tile(plan.ns, plan.ms, plan.ranks,
                                  vmem_budget=budget,
                                  weight_itemsize=1) == hw.LANES

    import repro.kernels.plan as ttplan
    from repro.core.packing import chain_fit_report
    monkeypatch.setattr(
        ttplan, "chain_fit_report",
        lambda ns, ms, ranks, **kw: chain_fit_report(
            ns, ms, ranks, **dict(kw, vmem_budget=budget)))

    tt_contract.reset_launch_counts()
    got_fp = tt_forward(cores, x, backend="auto", interpret=True,
                        tune="off")
    assert tt_contract.launch_counts() == {"step": 3}, \
        "fp32 must fall back to the per-step kernel under this budget"

    tt_contract.reset_launch_counts()
    got_q = tt_forward(cores, x, backend="auto", interpret=True,
                       tune="off", weights="int8")
    assert tt_contract.launch_counts() == {"fused_chain_int8": 1}, \
        "int8 residency must re-admit the chain into the fused kernel"

    np.testing.assert_allclose(np.asarray(got_fp), np.asarray(got_q),
                               rtol=0.1, atol=0.1)   # quantization drift


# ---------------------------------------------------------------------------
# Autotuner: weight dtype in the key, int8 measure path
# ---------------------------------------------------------------------------

def test_explicit_weights_accepts_fp32_alias():
    """weights='fp32' (the TTConfig spelling) must normalize like the
    suffix form, not raise."""
    plan, cores, x = _setup((16, 8), (4, 16), 8, 8)
    base = tt_forward(cores, x, backend="xla")
    got = tt_forward(cores, x, backend="xla", weights="fp32")
    np.testing.assert_array_equal(np.asarray(got), np.asarray(base))
    with pytest.raises(ValueError, match="weight mode"):
        tt_forward(cores, x, backend="xla", weights="fp8")


def test_autotune_key_split_by_weight_itemsize(tmp_path):
    """bf16-resident cores (weight_itemsize=2 under fp32 activations)
    must not share a cache entry with fp32 cores of the same signature —
    a tile measured at 2 B/elem residency can bust VMEM at 4 B/elem."""
    cache = str(tmp_path / "tune.json")
    ns, ms, ranks = (4, 4, 8), (8, 4, 4), (1, 4, 4, 1)
    autotune.fused_tile(ns, ms, ranks, jnp.float32, 32, mode="measure",
                        interpret=True, cache_path=cache)
    autotune.fused_tile(ns, ms, ranks, jnp.float32, 32, mode="measure",
                        interpret=True, cache_path=cache,
                        weight_itemsize=2)
    import json
    entries = json.loads((tmp_path / "tune.json").read_text())
    assert {e.split("|")[-2] for e in entries} == {"wfp", "wfp2"}


def test_autotune_key_split_by_weight_dtype(tmp_path):
    cache = str(tmp_path / "tune.json")
    ns, ms, ranks = (4, 4, 8), (8, 4, 4), (1, 4, 4, 1)
    bb_fp = autotune.fused_tile(ns, ms, ranks, jnp.float32, 32,
                                mode="measure", interpret=True,
                                cache_path=cache)
    bb_q = autotune.fused_tile(ns, ms, ranks, jnp.float32, 32,
                               mode="measure", interpret=True,
                               cache_path=cache, weights="int8")
    assert bb_fp is not None and bb_q is not None
    import json
    entries = json.loads((tmp_path / "tune.json").read_text())
    assert len(entries) == 2
    assert {e.split("|")[-2] for e in entries} == {"wfp", "wint8"}


def test_autotune_atomic_write_leaves_no_temp_files(tmp_path):
    cache = str(tmp_path / "tune.json")
    autotune.fused_tile((4, 16), (16, 8), (1, 8, 1), jnp.float32, 16,
                        mode="measure", interpret=True, cache_path=cache)
    leftovers = [p for p in tmp_path.iterdir() if p.suffix == ".tmp"]
    assert leftovers == []
    assert (tmp_path / "tune.json").exists()
