"""The seam between the harness and a configuration's reference module:
a configuration that names its own module runs through ``bench/run.py``
with no other harness file changed, here the DeepSeek-V2 smoke tree (MLA,
a dense first layer, routed and shared experts) under the plumbing stub
``reference_stub.py``; and the refusals where the module or a key it
checks is missing."""
import json
import os
import shutil
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
SEED = 2**33 + 9


def _config(name):
    with open(os.path.join(HERE, name)) as f:
        return json.load(f)


def test_a_configuration_with_its_own_module_runs():
    import jax
    from bench import run, weights
    from bench.tests.test_bench_run import E2E, TRAFFIC
    cfg = _config("tiny_mla_moe.json")
    ref = run.load_reference(cfg, HERE)
    assert ref.__name__ == "bench.reference_stub"
    model = run.build_model(cfg)
    run.check_published(cfg, model, ref)
    abstract = model.abstract_params()
    assert [k for k in abstract if k.startswith("g")] == ["g0", "g1"]
    assert len(weights.leaf_laws(abstract, getattr(ref, "LAWS", None))) \
        == len(jax.tree.leaves(abstract))
    # 3 layers, each caching a 32-wide latent and an 8-wide rope key
    assert ref.dims(cfg, abstract).kv_bytes_per_token() == 3 * 40 * 2

    cell = run.Cell("tiny-mla-moe", cfg, TRAFFIC, 1, E2E, [], reference=ref)
    out = run.run_cell(cell, SEED, 2.0, False)
    assert out["failed"] == 0 and out["attempted"] > 10
    assert set(out["metrics"]) == {n for n, _ in E2E}
    assert out["checks"]["compiles_in_window"]["value"] == 0
    assert out["checks"]["plan_resolutions"]["value"] == 0
    # float32 served against the program's own float32 forward
    assert out["correct"] is True, out["checks"]


@pytest.mark.parametrize("name,why", [("absent", "there is no"),
                                      ("../reference", "not a module name")])
def test_an_unknown_module_is_refused(name, why):
    from bench import run
    cfg = dict(_config("tiny.json"), reference=name)
    with pytest.raises(run.Refused, match=why):
        run.load_reference(cfg)
    with pytest.raises(run.Refused, match=why):
        run.Cell("x", cfg, {}, 1, [], [])


def test_entry_point_refuses_a_missing_module(tmp_path):
    """Exit 3 and no result line, before any look for a chip."""
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    cfg = dict(_config("tiny.json"), reference="absent")
    (tmp_path / "cfg.json").write_text(json.dumps(cfg))
    bench = {"configs": [{"name": "c", "file": "cfg.json"}],
             "workloads": [{"name": "c.chat", "config": "c",
                            "traffic": "chat-backlog", "chips": 1}],
             "end_to_end": [], "per_layer": []}
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    r = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "c.chat", "--seed",
         "1", "--seconds", "1", "--trace", "0"], cwd=tmp_path,
        env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True,
        text=True, timeout=120)
    assert r.returncode == 3
    assert "bench/reference_absent.py" in r.stderr
    assert '"correct"' not in r.stdout


@pytest.mark.parametrize("config,where,key", [
    ("tiny.json", None, "head_dim"),
    ("tiny_mla_moe.json", HERE, "kv_lora_rank")])
def test_a_file_missing_a_published_key_is_refused(config, where, key):
    from bench import run
    cfg = _config(config)
    del cfg[key]
    ref = run.load_reference(cfg, where or run.BENCH)
    with pytest.raises(run.Refused, match=key):
        run.check_published(cfg, run.build_model(cfg), ref)
