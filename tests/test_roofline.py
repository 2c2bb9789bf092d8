"""Roofline accounting: HLO collective parser + the three-term model."""
import numpy as np

from repro.analysis.roofline import (Roofline, collective_bytes_from_hlo,
                                     model_flops_estimate)
from repro.core import hw


HLO = """
ENTRY main {
  %ag = f32[16,1024]{1,0} all-gather(f32[2,1024]{1,0} %p0), replica_groups=[2,8]<=[16], dimensions={0}
  %ar = bf16[4096]{0} all-reduce(bf16[4096]{0} %p1), replica_groups={{0,1,2,3},{4,5,6,7}}, to_apply=%add
  %rs = f32[512]{0} reduce-scatter(f32[4096]{0} %p2), replica_groups=[1,8]<=[8], dimensions={0}
  %cp = bf16[128,256]{1,0} collective-permute(bf16[128,256]{1,0} %p3), source_target_pairs={{0,1},{1,0}}
  %aa = f32[64,64]{1,0} all-to-all(f32[64,64]{1,0} %p4), replica_groups=[2,4]<=[8], dimensions={0}
  %ags = (f32[8]{0}, f32[64]{0}) all-gather-start(f32[8]{0} %p5), replica_groups=[1,8]<=[8], dimensions={0}
}
"""


def test_collective_parser_kinds_and_ring_factors():
    got = collective_bytes_from_hlo(HLO)
    # all-gather: out 16*1024*4 bytes, ring (g-1)/g with g=8; the -start op
    # has tuple type (operand f32[8], result f32[64]) → max = 256 B
    assert got["all-gather"] == (16 * 1024 * 4) * 7 / 8 + 64 * 4 * 7 / 8
    # all-reduce: 2·(g-1)/g·bytes, g=4
    assert got["all-reduce"] == 2 * (3 / 4) * 4096 * 2
    # reduce-scatter: ring moves (g-1)·out == (g-1)/g·in; out f32[512], g=8
    assert got["reduce-scatter"] == 7 * 512 * 4
    # permute: factor 1
    assert got["collective-permute"] == 128 * 256 * 2
    assert got["all-to-all"] == (3 / 4) * 64 * 64 * 4
    assert got["total"] == sum(v for k, v in got.items() if k != "total")


def test_parser_ignores_group_of_one():
    hlo = ('%ar = f32[64]{0} all-reduce(f32[64]{0} %x), '
           'replica_groups=[64,1]<=[64]')
    got = collective_bytes_from_hlo(hlo)
    assert got.get("all-reduce", 0.0) == 0.0


def test_parser_on_real_lowered_hlo():
    """Parse actual XLA output: a psum over a 1-device mesh lowers to an
    all-reduce with a singleton group (→ 0 bytes), proving the regexes
    match real HLO syntax, not just our synthetic lines."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, PartitionSpec as P
    mesh = Mesh(np.array(jax.devices()[:1]), ("x",))
    f = jax.shard_map(lambda a: jax.lax.psum(a, "x"), mesh=mesh,
                  in_specs=P(), out_specs=P())
    txt = jax.jit(f).lower(jnp.ones((8, 8))).compile().as_text()
    assert "all-reduce" in txt
    got = collective_bytes_from_hlo(txt)
    assert got["total"] == 0.0            # group size 1 → free


def test_roofline_terms_and_bottleneck():
    rl = Roofline(chips=256, flops_per_device=197e12,       # exactly 1 s
                  bytes_per_device=819e9 * 2,               # 2 s ← dominant
                  collective_per_device=50e9 * 0.5,         # 0.5 s
                  model_flops=197e12 * 256)
    assert rl.t_compute == 1.0
    assert rl.t_memory == 2.0
    assert rl.t_collective == 0.5
    assert rl.bottleneck == "memory"
    assert rl.t_bound == 2.0
    assert np.isclose(rl.useful_flops_ratio, 1.0)
    assert np.isclose(rl.roofline_fraction, 0.5)      # 1 s useful / 2 s bound
    d = rl.to_dict()
    assert d["bottleneck"] == "memory"


def test_model_flops_estimate():
    assert model_flops_estimate(100, 0, 10, "train") == 6.0 * 100 * 10
    assert model_flops_estimate(100, 40, 10, "train") == 6.0 * 40 * 10
    assert model_flops_estimate(100, 0, 10, "decode") == 2.0 * 100 * 10


def test_hw_constants_match_brief():
    assert hw.PEAK_FLOPS_BF16 == 197e12
    assert hw.HBM_BW == 819e9
    assert hw.ICI_BW == 50e9
