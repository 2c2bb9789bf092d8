"""Sharding rules + a real multi-device compile (8 host devices in a
subprocess so the main test process keeps seeing 1 device)."""
import json
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from repro.distributed import sharding as shd
from repro.models.spec import ParamSpec

SRC = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "src"))


def _mesh11():
    dev = np.array(jax.devices()[:1]).reshape(1, 1)
    return jax.sharding.Mesh(dev, ("data", "model"))


def test_param_pspec_logical_axes():
    mesh = _mesh11()
    # vocab → model axis
    s = ParamSpec((1024, 64), ("vocab", "embed"))
    p = shd.param_pspec(s, mesh)
    assert p[0] == "model" and p[1] is None
    # ff → model
    s = ParamSpec((64, 256), ("embed", "ff"))
    assert shd.param_pspec(s, mesh)[1] == "model"
    # heads → model
    s = ParamSpec((64, 8, 16), ("embed", "heads", "head_dim"))
    assert shd.param_pspec(s, mesh)[1] == "model"
    # TT cores: ranks/inputs replicated; the output-factor dim is
    # tensor-parallel when divisible (EXPERIMENTS §Perf it. 4)
    s = ParamSpec((1, 8, 8, 16), ("tt_r", "tt_n", "tt_m", "tt_r"))
    p = shd.param_pspec(s, mesh)
    assert p[0] is None and p[1] is None and p[3] is None
    assert p[2] in (None, "model")          # m shards iff divisible
    # layers axis never sharded
    s = ParamSpec((4, 64, 256), ("layers", "embed", "ff"))
    assert shd.param_pspec(s, mesh)[0] is None


def test_param_pspec_fsdp():
    mesh = _mesh11()
    s = ParamSpec((64, 256), ("embed", "ff"))
    p = shd.param_pspec(s, mesh, fsdp_axes=("data",))
    # largest free dim picks up the fsdp axis (embed: ff is taken by model)
    assert "data" in [a for a in jax.tree.leaves(list(p)) if a]


def test_shard_act_without_ctx_is_identity():
    import jax.numpy as jnp
    shd.set_ctx(None)
    x = jnp.ones((4, 4))
    y = shd.shard_act(x, ("act_batch", None))
    assert y is x


MULTIDEV_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import jax
import jax.numpy as jnp

from repro.configs import build, get_config
from repro.configs.shapes import concrete_batch
from repro.distributed import sharding as shd
from repro.models.spec import is_spec
from repro.training.optimizer import OptConfig
from repro.training.train_loop import TrainConfig, make_train_step

assert len(jax.devices()) == 8
mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 3)

cfg = get_config("qwen3_32b", "smoke")
model = build(cfg)
rules = dict(shd.ACT_RULES_TRAIN)
shd.set_ctx(shd.ShardCtx(mesh, rules, ("pod", "data")))

params = model.init(jax.random.PRNGKey(0))
shards = shd.param_shardings(model.param_specs(), mesh, fsdp=True)
params = jax.device_put(params, shards)
state = {"params": params,
         "opt": {"m": jax.device_put(jax.tree.map(jnp.zeros_like, params), shards),
                 "v": jax.device_put(jax.tree.map(jnp.zeros_like, params), shards),
                 "step": jnp.zeros((), jnp.int32)}}
batch = concrete_batch(cfg, 8, 16)
step = jax.jit(make_train_step(model, TrainConfig(
    opt=OptConfig(warmup_steps=0), remat=True,
    compute_dtype=jnp.float32)))
new_state, metrics = step(state, batch)
loss1 = float(metrics["loss"])

# single-device reference: same math must come out of the SPMD program
shd.set_ctx(None)
params_r = model.init(jax.random.PRNGKey(0))
state_r = {"params": params_r,
           "opt": {"m": jax.tree.map(jnp.zeros_like, params_r),
                   "v": jax.tree.map(jnp.zeros_like, params_r),
                   "step": jnp.zeros((), jnp.int32)}}
new_r, metrics_r = jax.jit(make_train_step(model, TrainConfig(
    opt=OptConfig(warmup_steps=0), remat=True,
    compute_dtype=jnp.float32)))(state_r, batch)

import numpy as np
wa = np.asarray(jax.device_get(new_state["params"]["embed"]["table"]))
wb = np.asarray(jax.device_get(new_r["params"]["embed"]["table"]))
print(json.dumps({
    "loss_spmd": loss1,
    "loss_ref": float(metrics_r["loss"]),
    "max_param_diff": float(np.max(np.abs(wa - wb))),
}))
"""


@pytest.mark.slow
def test_spmd_train_step_matches_single_device(tmp_path):
    """8-device (pod,data,model)=(2,2,2) SPMD train step == 1-device math.
    Proves: sharding rules produce a valid GSPMD program AND the program
    computes the same update."""
    script = tmp_path / "multidev.py"
    script.write_text(MULTIDEV_SCRIPT)
    env = dict(os.environ, PYTHONPATH=SRC)
    out = subprocess.run([sys.executable, str(script)], env=env,
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert abs(res["loss_spmd"] - res["loss_ref"]) < 1e-3, res
    assert res["max_param_diff"] < 1e-3, res


EP_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp
from repro.configs import get_config
from repro.distributed import sharding as shd
from repro.models.moe import moe_apply, moe_apply_ep, moe_spec
from repro.models.spec import init_tree

results = []
for arch, mesh_shape in (("mixtral_8x7b", (2, 4)),        # case A: E%M==0
                         ("deepseek_v2_lite_16b", (2, 4)),  # A + shared
                         ("mixtral_8x7b", (1, 8))):         # case B/C: E<M
    cfg = get_config(arch, "smoke")
    p = init_tree(jax.random.PRNGKey(0), moe_spec(cfg))
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 16, cfg.d_model))
    ref = moe_apply(p, cfg, x)
    mesh = jax.make_mesh(mesh_shape, ("data", "model"))
    shd.set_ctx(shd.ShardCtx(mesh, dict(shd.ACT_RULES_TRAIN), ("data",)))
    got = jax.jit(lambda pp, xx: moe_apply_ep(pp, cfg, xx))(p, x)
    shd.set_ctx(None)
    results.append(float(jnp.max(jnp.abs(got - ref))))
print(results)
assert all(d < 2e-4 for d in results), results
print("OK")
"""


@pytest.mark.slow
def test_expert_parallel_matches_global_dispatch(tmp_path):
    """shard_map EP MoE (cases A/B/C) == the global GSPMD formulation on an
    8-device mesh — the §Perf iteration-2 optimization changes layout, not
    math."""
    script = tmp_path / "ep.py"
    script.write_text(EP_SCRIPT)
    env = dict(os.environ, PYTHONPATH=SRC)
    out = subprocess.run([sys.executable, str(script)], env=env,
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "OK" in out.stdout


ELASTIC_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from repro.configs import build, get_config
from repro.distributed import sharding as shd
from repro.training import checkpoint as ckpt

cfg = get_config("deepseek_7b", "smoke")
model = build(cfg)
params = model.init(jax.random.PRNGKey(0))

# save from a (4 data, 2 model) mesh
mesh_a = jax.make_mesh((4, 2), ("data", "model"))
shards_a = shd.param_shardings(model.param_specs(), mesh_a, fsdp=True)
params_a = jax.device_put(params, shards_a)
ckpt.save("/tmp/elastic_ckpt", {"params": params_a}, step=1)

# restore onto a (2 data, 4 model) mesh — different DP/TP split
mesh_b = jax.make_mesh((2, 4), ("data", "model"))
shards_b = shd.param_shardings(model.param_specs(), mesh_b, fsdp=True)
restored, manifest = ckpt.restore("/tmp/elastic_ckpt", {"params": params},
                                  shardings={"params": shards_b})
for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(restored)):
    np.testing.assert_array_equal(np.asarray(jax.device_get(a)),
                                  np.asarray(jax.device_get(b)))
# every restored leaf actually lives on mesh_b
for leaf in jax.tree.leaves(restored):
    assert leaf.sharding.mesh.shape == mesh_b.shape, leaf.sharding
print("ELASTIC_OK")
"""


@pytest.mark.slow
def test_elastic_rescale_restore(tmp_path):
    """Checkpoint saved under one mesh restores bit-identically onto a
    different (DP, TP) split — the elastic-rescale path of fault.py."""
    script = tmp_path / "elastic.py"
    script.write_text(ELASTIC_SCRIPT)
    env = dict(os.environ, PYTHONPATH=SRC)
    out = subprocess.run([sys.executable, str(script)], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "ELASTIC_OK" in out.stdout


# ---------------------------------------------------------------------------
# Serving shardings (DESIGN.md §14)
# ---------------------------------------------------------------------------

def test_serve_param_rules_replicate_tt_cores():
    """Serving replicates every TT core dim (tt_m loses its training-time
    TP rule) while embeddings/LM head stay vocab-sharded."""
    mesh = _mesh11()
    s = ParamSpec((1, 8, 8, 16), ("tt_r", "tt_n", "tt_m", "tt_r"))
    p = shd.param_pspec(s, mesh, rules=shd.SERVE_PARAM_RULES)
    assert all(a is None for a in p)
    s = ParamSpec((1024, 64), ("vocab", "embed"))
    p = shd.param_pspec(s, mesh, rules=shd.SERVE_PARAM_RULES)
    assert p[0] == "model"


def test_serve_param_shardings_survive_quantized_tree():
    """serve_param_shardings walks the params tree, so the int8 checkpoint
    transform (same paths, int8 dtypes, extra ``scales`` leaves) gets a
    complete sharding tree — scales fall back to replicated."""
    from repro.configs import build, get_config
    from repro.configs.base import TTConfig

    cfg = get_config("deepseek_7b", "smoke",
                     tt=TTConfig(enabled=True, families=("ffn",),
                                 rank=4, min_factor=2))
    model = build(cfg)
    params = model.quantize_params(model.init(jax.random.PRNGKey(0)))
    mesh = _mesh11()
    shards = shd.serve_param_shardings(model.param_specs(), params, mesh)
    flat_p = jax.tree_util.tree_flatten_with_path(params)[0]
    flat_s = jax.tree.leaves(shards)
    assert len(flat_p) == len(flat_s)
    saw_scales = saw_sharded = False
    for (path, leaf), sh in zip(flat_p, jax.tree.leaves(shards)):
        assert isinstance(sh, jax.sharding.NamedSharding)
        keys = [str(getattr(p, "key", p)) for p in path]
        if "scales" in keys:
            saw_scales = True
            assert sh.spec == jax.sharding.PartitionSpec()
        if "model" in jax.tree.leaves(list(sh.spec)):
            saw_sharded = True
            assert "tt" not in keys     # cores replicated when serving
    assert saw_scales and saw_sharded


def test_serve_cache_shardings_kv_and_batch_axes():
    mesh = _mesh11()
    cache = {"l": {"k": np.zeros((2, 8, 32, 4, 16)),
                   "v": np.zeros((2, 8, 32, 4, 16)),
                   "lat": np.zeros((2, 8, 32, 24))},
             "pos": np.zeros((8,), np.int32),
             "block_tables": np.zeros((8, 4), np.int32)}
    shards = shd.serve_cache_shardings(cache, mesh)
    P = jax.sharding.PartitionSpec
    assert shards["l"]["k"].spec == P(None, None, None, "model", None)
    assert shards["l"]["v"].spec == P(None, None, None, "model", None)
    def replicated(spec):
        return all(a is None for a in spec)
    assert replicated(shards["l"]["lat"].spec)   # MLA latents replicated
    assert replicated(shards["pos"].spec)
    assert replicated(shards["block_tables"].spec)  # host-logical, replicated
    # dense pools pass batch=num_slots: slot axis picks up 'data' — on
    # this 1-device mesh the extent-1 data axis is skipped, so the rule
    # is only visible through the KV spec staying unchanged
    shards = shd.serve_cache_shardings(cache, mesh, batch=8)
    assert shards["l"]["k"].spec == P(None, None, None, "model", None)


def test_make_serve_mesh_validation():
    from repro.launch.mesh import make_serve_mesh
    m = make_serve_mesh(1)
    assert dict(m.shape) == {"data": 1, "model": 1}
    with pytest.raises(ValueError, match="device_count"):
        make_serve_mesh(len(jax.devices()) + 1)
    with pytest.raises(ValueError, match="divide"):
        make_serve_mesh(1, data=2)


SERVE_MESH_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import json
import jax
import numpy as np
from repro.configs import build, get_config
from repro.configs.shapes import concrete_batch
from repro.launch.mesh import make_serve_mesh
from repro.serving.scheduler import Request, Scheduler

assert len(jax.devices()) == 4
S, NEW = 8, 8


def decode(model, cfg, params, mesh, paged, sampled):
    key = jax.random.PRNGKey(7)
    sched = Scheduler(model, params, num_slots=2, cache_len=S + NEW + 4,
                      paged=paged, block_size=4, key=key, mesh=mesh)
    for b in range(2):
        toks = concrete_batch(cfg, 1, S, seed=b)["tokens"]
        kw = dict(temperature=1.0, top_k=3,
                  key=jax.random.fold_in(key, b)) if sampled else {}
        sched.submit(Request(uid=b, inputs={"tokens": toks},
                             max_new_tokens=NEW, **kw))
    done = sched.run()
    for f in sched.finished:
        done[f.uid] = f
    return [[int(t) for t in done[b].tokens] for b in range(2)]


for arch in ("qwen3_32b",            # gqa
             "gemma3_4b",            # local/global window
             "deepseek_v2_lite_16b", # mla + moe experts
             "mamba2_2p7b",          # ssm
             "jamba_v0_1_52b"):      # hybrid attn/ssm
    cfg = get_config(arch, "smoke")
    model = build(cfg)
    params = model.init(jax.random.PRNGKey(0))
    mesh = make_serve_mesh(4)
    for sampled in (False, True):
        ref = decode(model, cfg, params, None, False, sampled)
        got_d = decode(model, cfg, params, mesh, False, sampled)
        got_p = decode(model, cfg, params, mesh, True, sampled)
        tag = f"{arch} sampled={sampled}"
        assert got_d == ref, f"{tag}: dense sharded != single-device"
        assert got_p == ref, f"{tag}: paged sharded != single-device"
    print(arch, "OK")
print("MESH_INVARIANCE_OK")
"""


@pytest.mark.slow
def test_mesh_invariance_all_families(tmp_path):
    """Sharded serving is pure data placement: on a 4-device mesh the
    scheduler decodes token-identically to the single-device run — greedy
    and seeded sampling, dense and paged pools — across the gqa, window,
    MLA+MoE, SSM and hybrid families (DESIGN.md §14)."""
    script = tmp_path / "serve_mesh.py"
    script.write_text(SERVE_MESH_SCRIPT)
    env = dict(os.environ, PYTHONPATH=SRC)
    out = subprocess.run([sys.executable, str(script)], env=env,
                         capture_output=True, text=True, timeout=1800)
    assert out.returncode == 0, \
        out.stdout[-2000:] + out.stderr[-3000:]
    assert "MESH_INVARIANCE_OK" in out.stdout
