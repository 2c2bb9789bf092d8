"""The dense reference module's counts at full width, pinned to the
integers the harness counted before the counts moved behind the module,
and the weight laws over trees with routers and experts."""
import json
import os

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
CONFIGS = os.path.join(os.path.dirname(HERE), "configs")

# token_flops at ctx 1, 1020, 4096; prompt_flops(1500); head_flops();
# tt_work(1024, 36); kv_bytes_per_token()
PINNED = {
    "deepseek-7b-tt": ((4375019520, 4875878400, 6387793920), 7115120640000,
                       838860800, (356348067840, 4349952000), 491520),
    "granite-8b-tt": ((3530096640, 4131127296, 5945425920), 5958254592000,
                      402653184, (521838526464, 6370099200), 147456),
}


@pytest.mark.parametrize("name", sorted(PINNED))
def test_dense_counts_are_pinned(name):
    from bench import run
    with open(os.path.join(CONFIGS, f"{name}.json")) as f:
        cfg = json.load(f)
    ref = run.load_reference(cfg)
    model = run.build_model(cfg)
    run.check_published(cfg, model, ref)
    d = ref.dims(cfg, model.abstract_params())
    tokens, prompt, head, tt, kv = PINNED[name]
    assert tuple(d.token_flops(c) for c in (1, 1020, 4096)) == tokens
    assert d.prompt_flops(1500) == prompt
    assert d.head_flops() == head
    assert d.tt_work(1024, 36) == tt
    assert d.kv_bytes_per_token() == kv


@pytest.mark.parametrize("arch", ["deepseek-v2-lite-16b", "mixtral-8x7b"])
def test_leaf_laws_cover_expert_trees(arch):
    import jax
    from bench import weights
    from repro.configs import build, get_config
    abstract = build(get_config(arch, "smoke")).abstract_params()
    laws = weights.leaf_laws(abstract)
    assert len(laws) == len(jax.tree.leaves(abstract))
    flat, _ = jax.tree_util.tree_flatten_with_path(abstract)
    router = [law for (path, leaf), law in zip(flat, laws)
              if weights._name(path[-1]) == "router"]
    assert router and all(law == ("normal", 64 ** -0.5) for law in router)


def test_unknown_leaf_raises_unless_the_module_gives_a_law():
    import jax
    import jax.numpy as jnp
    from bench import weights
    tree = {"ssm": {"A_log": jax.ShapeDtypeStruct((2, 8), jnp.float32),
                    "scale": jax.ShapeDtypeStruct((8,), jnp.float32)}}
    with pytest.raises(ValueError, match="no law for parameter ssm/A_log"):
        weights.leaf_laws(tree)
    laws = weights.leaf_laws(tree, {"A_log": lambda shape: ("zeros", 0.0)})
    assert laws == [("zeros", 0.0), ("ones", 0.0)]
