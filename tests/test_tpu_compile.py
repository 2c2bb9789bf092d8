"""Compile the Pallas kernels for a described TPU v5e chip.

Interpret mode (every other kernel test) accepts bodies that the chip's
compiler, Mosaic, refuses: reshapes that split the lane dim, matmuls with
two contracting dims, more VMEM than the kernel may use.  These tests
compile each kernel of the serving path — the per-step kernel and the
fused d=2 / d≥3 chains, fp and int8-resident — at the deepseek-7b FFN
plans (``configs/deepseek_7b.py``, ``--tt ffn``, rank 16, ``min_factor=8``)
and at one d=3 plan of the same widths, and the paged decode attention
kernel at the deepseek-7b and granite-8b pools, for a ``v5e:2x2``
topology that is described, not attached.  Nothing runs; a passing
compile is not a chip run.

The topology is described inside a module-scoped fixture, never at import:
only one process may load the TPU library, and test collection happens in
every worker.  The persistent compilation cache is off around the
compiles (an entry written without a chip cannot be read back).
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import hw
from repro.core.flops import prod
from repro.core.packing import fused_chain_batch_tile, fused_chain_vmem_bytes
from repro.kernels import paged_attention, tt_contract
from repro.kernels.ops import tt_forward
from repro.kernels.plan import plan_tt_forward

# (ns, ms, ranks) of the deepseek-7b FFN chains: d_model 4096 → d_ff 11008
# (gate/up) and back (down), as the model's PlanBook resolves them
UP = ((8, 512), (1376, 8), (1, 16, 1))
DOWN = ((8, 1376), (512, 8), (1, 16, 1))
UP_D3 = ((8, 8, 64), (172, 8, 8), (1, 16, 16, 1))

CASES = [(UP, "pallas_fused2"), (DOWN, "pallas_fused2"),
         (UP_D3, "pallas_fused"),
         (UP, "pallas_step"), (DOWN, "pallas_step"), (UP_D3, "pallas_step")]


@pytest.fixture(scope="module")
def no_compile_cache():
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def topo(no_compile_cache):
    from jax.experimental import topologies
    with pytest.MonkeyPatch.context() as mp:
        if "TPU_LOG_DIR" not in os.environ:
            mp.setenv("TPU_LOG_DIR", "disabled")   # no compiler logs in /tmp
        try:
            return topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:            # no TPU compiler installed
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    assert topo.devices[0].device_kind == hw.TARGET_DEVICE_KIND
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


def _layer_args(one_chip, chain, weights, B, x_dtype=jnp.bfloat16):
    """Shapes of one TT layer as the bf16 model serves it: bf16 cores (or
    int8 cores with fp32 scales) and a [B, N] activation."""
    ns, ms, ranks = chain
    spec = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt,  # noqa: E731
                                                  sharding=one_chip)
    cdt = jnp.int8 if weights == "int8" else jnp.bfloat16
    cores = [spec((ranks[t], ns[t], ms[t], ranks[t + 1]), cdt)
             for t in range(len(ns))]
    scales = ([spec((), jnp.float32) for _ in ns]
              if weights == "int8" else None)
    return cores, scales, spec((B, prod(ns)), x_dtype)


@pytest.mark.parametrize("B", [8, 512])
@pytest.mark.parametrize("weights", ["fp", "int8"])
@pytest.mark.parametrize("chain,backend", CASES)
def test_tt_layer_compiles_for_v5e(one_chip, chain, backend, weights, B):
    ns, ms, ranks = chain
    plan = plan_tt_forward(ns, ms, ranks, backend=backend, weights=weights,
                           weight_itemsize=2, tune="off")
    assert plan.backend == backend
    cores, scales, x = _layer_args(one_chip, chain, weights, B)

    def layer(cores, scales, x):
        return tt_forward(cores, x, scales=scales, plan=plan,
                          interpret=False)

    hlo = _compile(layer, cores, scales, x)
    launches = len(ns) if backend == "pallas_step" else 1
    assert hlo.count("custom_call_target=\"tpu_custom_call\"") == launches


def test_vmem_bytes_is_what_the_compiler_reports(one_chip):
    """``hw.VMEM_BYTES`` is the VMEM size the v5e compiler allocates
    against: a scratch one tile larger is refused, naming that size."""
    rows = hw.VMEM_BYTES // (4 * hw.LANES) + 8

    def kernel(x_ref, o_ref, scratch):
        scratch[0:8, :] = x_ref[...]
        o_ref[...] = scratch[0:8, :]

    def fn(x):
        return pl.pallas_call(
            kernel, out_shape=jax.ShapeDtypeStruct((8, hw.LANES),
                                                   jnp.float32),
            scratch_shapes=[pltpu.VMEM((rows, hw.LANES), jnp.float32)],
            compiler_params=pltpu.CompilerParams(
                vmem_limit_bytes=2 * hw.VMEM_BYTES))(x)

    x = jax.ShapeDtypeStruct((8, hw.LANES), jnp.float32, sharding=one_chip)
    with pytest.raises(Exception, match=f"size={hw.VMEM_BYTES}\\)"):
        _compile(fn, x)


def test_fused_tile_needs_and_fits_the_vmem_limit(one_chip, monkeypatch):
    """The kernels compile with ``vmem_limit_bytes`` = the fit budget.  At
    the tile the fit model picks for the fp32 up-projection the kernel
    needs more than the compiler's default scoped VMEM: it compiles with
    the kernels' limit and is refused without it."""
    assert tt_contract._compiler_params("parallel").vmem_limit_bytes == \
        hw.VMEM_BUDGET_BYTES
    ns, ms, ranks = UP
    tile = fused_chain_batch_tile(ns, ms, ranks)
    assert fused_chain_vmem_bytes(tile, ns, ms, ranks) <= \
        hw.VMEM_BUDGET_BYTES
    cores, _, x = _layer_args(one_chip, UP, "fp", 1024, jnp.float32)
    packed = [jax.ShapeDtypeStruct((n * r1, m * r0), jnp.float32,
                                   sharding=one_chip)
              for r0, n, m, r1 in (c.shape for c in reversed(cores))]

    def chain(x, *p):
        return tt_contract.tt_fused_chain_pallas(x, p, UP, block_b=tile,
                                                 interpret=False)

    jax.clear_caches()
    assert "tpu_custom_call" in _compile(chain, x, *packed)
    monkeypatch.setattr(
        tt_contract, "_compiler_params",
        lambda *sem: pltpu.CompilerParams(dimension_semantics=sem))
    jax.clear_caches()
    try:
        with pytest.raises(Exception, match="vmem"):
            _compile(chain, x, *packed)
    finally:
        jax.clear_caches()


# (query heads, KV heads, arena blocks + sentinel, slots) of the paged
# pools the benchmark serves: deepseek-7b (MHA 32×128, 112 blocks of 64,
# 8 slots) and granite-8b (GQA, 8 KV heads, 544 blocks, 16 slots)
PAGED_POOLS = {"deepseek-7b": (32, 32, 113, 8), "granite-8b": (32, 8, 545, 16)}


@pytest.mark.parametrize("pool", sorted(PAGED_POOLS))
def test_paged_decode_attention_compiles_for_v5e(one_chip, pool):
    H, KV, nb1, B = PAGED_POOLS[pool]
    hd, blk, max_blocks = 128, 64, 64
    spec = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt,  # noqa: E731
                                                  sharding=one_chip)
    stack = spec((2, nb1, blk, KV, hd), jnp.bfloat16)

    def attend(q, ak, av, bt, lengths, layer):
        return paged_attention.paged_decode_attention(
            q, ak, av, bt, lengths, layer, scale=hd ** -0.5,
            interpret=False)

    hlo = _compile(attend, spec((B, H, hd), jnp.bfloat16), stack, stack,
                   spec((B, max_blocks), jnp.int32), spec((B,), jnp.int32),
                   spec((), jnp.int32))
    assert hlo.count("custom_call_target=\"tpu_custom_call\"") == 1


def test_paged_decode_step_reads_the_arena_in_the_kernel(one_chip,
                                                         monkeypatch):
    """The compiled decode step of a small paged model (deepseek-7b smoke,
    dense FFNs, heads of the full model's 128 lanes, which the kernel's
    block copies need) attends in the paged kernel, and nothing in it is
    an f32 copy of a cache gathered over every slot's logical length."""
    import dataclasses
    from repro.configs import build, get_config
    from repro.configs.base import TTConfig
    monkeypatch.setattr(paged_attention, "_interpret_default", lambda: False)
    cfg = dataclasses.replace(
        get_config("deepseek-7b", "smoke", tt=TTConfig(enabled=False)),
        head_dim=128)
    model = build(cfg, param_dtype=jnp.bfloat16)
    B, nb, blk, T = 4, 12, 16, 128
    on_chip = lambda s: jax.ShapeDtypeStruct(  # noqa: E731
        s.shape, s.dtype, sharding=one_chip)
    params = jax.tree.map(on_chip, model.abstract_params())
    cache = jax.tree.map(on_chip, model.paged_cache_shapes(B, nb, blk, T))
    tok = jax.ShapeDtypeStruct((B, 1), jnp.int32, sharding=one_chip)
    act = jax.ShapeDtypeStruct((B,), jnp.bool_, sharding=one_chip)
    jax.clear_caches()
    try:
        hlo = model.jitted_decode_step_masked().lower(
            params, cache, tok, act).compile().as_text()
    finally:
        jax.clear_caches()
    calls = [ln for ln in hlo.splitlines()
             if "tpu_custom_call" in ln and "_paged_decode_attn_call" in ln]
    assert calls
    gathered = f"f32[{B * (T // blk)},{blk},{cfg.num_kv_heads},{cfg.head_dim}]"
    assert gathered not in hlo
