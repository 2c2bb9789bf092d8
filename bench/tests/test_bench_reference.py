"""The plain float32 reference against the program's ``Model.prefill``
on the same seeded weights, at smoke size on the CPU."""
import copy
import json
import os

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))


def _tiny(arch, families, kv, theta=10000.0, eps=1e-6):
    with open(os.path.join(HERE, "tiny.json")) as f:
        cfg = json.load(f)
    cfg = copy.deepcopy(cfg)
    cfg["arch"] = arch
    cfg["num_key_value_heads"] = kv
    cfg["rope_theta"] = theta
    cfg["rms_norm_eps"] = eps
    cfg["tt"]["families"] = families
    cfg["serving"]["param_dtype"] = "float32"
    return cfg


@pytest.mark.parametrize("arch,families,kv,theta,eps", [
    ("deepseek-7b", ["ffn"], 4, 1e4, 1e-6),
    ("deepseek-7b", ["ffn", "attn"], 4, 1e4, 1e-6),
    ("granite-8b", ["ffn"], 2, 1e6, 1e-6),  # the smoke variant's GQA, base
    ("granite-8b", ["ffn"], 2, 1e7, 1e-5),  # the file's rope base and eps
])
def test_reference_matches_prefill(arch, families, kv, theta, eps):
    import jax
    import jax.numpy as jnp
    from bench import run, weights
    from bench.reference import Reference

    cfg = _tiny(arch, families, kv, theta, eps)
    model = run.build_model(cfg)
    assert (model.cfg.rope_theta, model.cfg.norm_eps) == (theta, eps)
    params = weights.make_params(model.abstract_params(), 2**32 + 7)
    assert any("tt" in v for v in jax.tree_util.tree_leaves(
        jax.tree_util.tree_map_with_path(lambda p, x: str(p), params)))
    rng = np.random.default_rng(0)
    toks = rng.integers(0, cfg["vocab_size"], 41).astype(np.int32)
    ref = Reference(params, cfg).logits(toks, 0)
    assert ref.shape == (41, cfg["vocab_size"])
    prefill = jax.jit(model.prefill)
    for S in (1, 9, 41):
        got, _ = prefill(params, {"tokens": jnp.asarray(toks[None, :S])})
        got = np.asarray(got, np.float32).reshape(-1)
        want = ref[S - 1]
        err = np.abs(got - want).max() / np.abs(want).max()
        assert err < 1e-4, (S, err)


def test_int8_control_departs_from_float32():
    from bench import run, weights
    from bench.reference import Reference
    cfg = _tiny("deepseek-7b", ["ffn"], 4)
    model = run.build_model(cfg)
    params = weights.make_params(model.abstract_params(), 11)
    toks = np.arange(30, dtype=np.int32) * 5 % cfg["vocab_size"]
    f32 = Reference(params, cfg).logits(toks, 0)
    q8 = Reference(params, cfg, "int8").logits(toks, 0)
    rel = np.abs(q8 - f32).max() / np.abs(f32).max()
    assert 1e-4 < rel < 0.2
