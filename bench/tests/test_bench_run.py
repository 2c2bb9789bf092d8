"""The harness end to end at smoke size on the CPU: the generator, the
``Scheduler`` window, the metric readers and the comparison that decides
``correct``; the int8 control and faults planted in the timed path must
come out not correct.  The chip's look is skipped (``chip=None``); the
real entry point refuses a host without a TPU."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))

TRAFFIC = {"prompt": {"median": 40, "sigma": 0.8, "min": 8, "max": 160},
           "output": {"median": 6, "sigma": 1.0, "min": 2, "max": 40},
           "max_total": 256, "set_size": 8, "shape_seed": 1,
           "arrival": {"kind": "poisson", "rate_per_s": 20.0}}
E2E = [("setup_s", "s"), ("tok_s", "tokens/s"), ("ttft_p95_ms", "ms"),
       ("itl_p50_ms", "ms"), ("itl_p95_ms", "ms")]
SEED = 2**33 + 5


def _cell(traffic=TRAFFIC, per_layer=()):
    from bench import run
    with open(os.path.join(HERE, "tiny.json")) as f:
        cfg = json.load(f)
    return run.Cell("tiny", cfg, traffic, 1, E2E, list(per_layer))


def test_peaks_known_kind_and_unknown_kind():
    from bench.peaks import peaks_for
    p = peaks_for("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12
    assert p["int8_ops_per_s"] == 393e12
    assert p["hbm_bytes_per_s"] == 819e9
    assert "Google Cloud" in p["source"]
    with pytest.raises(KeyError, match="no published peaks"):
        peaks_for("TPU v99")


def test_entry_point_refuses_a_host_without_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "ds7b-tt.chat-backlog", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=ROOT, env=env, capture_output=True,
        text=True, timeout=300)
    assert r.returncode != 0
    assert "not a TPU" in r.stderr
    assert '"correct"' not in r.stdout


def test_every_cell_resolves_to_files_of_its_own():
    from bench import run
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for wl in bench["workloads"]:
        cell = run.resolve_cell(wl["name"])
        names = [n for n, _ in cell.end_to_end + cell.per_layer]
        assert "setup_s" in names
        for n in names:
            mod = run.reader(n)
            assert callable(mod.read)
            assert mod.SOURCE in ("device_trace", "program_span",
                                  "program_counter", "host_clock")


@pytest.fixture(scope="module")
def tiny_run():
    from bench import run
    return run.run_cell(_cell(per_layer=[("batch_occupancy", "%"),
                                         ("prefill_tokens_per_step",
                                          "tokens"),
                                         ("step_mfu", "%")]),
                        SEED, 2.0, False, control=True)


def test_tiny_run_is_correct_and_reports(tiny_run):
    out = tiny_run
    assert out["correct"] is True, out["checks"]
    assert out["attempted"] > 10 and out["failed"] == 0
    m = out["metrics"]
    assert set(m) == {n for n, _ in E2E}
    assert all(v["value"] > 0 for v in m.values())
    assert m["itl_p95_ms"]["value"] >= m["itl_p50_ms"]["value"]
    assert list(out)[-1] == "checks"
    assert out["checks"]["plan_resolutions"]["value"] == 0
    assert out["checks"]["compiles_in_window"]["value"] == 0


def test_int8_control_fails_the_limit(tiny_run):
    con = tiny_run["control"]
    assert con["correct"] is False
    assert set(con["checks"]) == {"max_logit_gap", "max_logprob_err"}
    # the logprob needs no flipped token to fail
    err = con["checks"]["max_logprob_err"]
    assert err["value"] > err["limit"]
    assert tiny_run["checks"]["max_logprob_err"]["value"] < err["limit"]


def test_sample_holds_the_longest_and_many_requests():
    from types import SimpleNamespace
    from bench import check
    lens = [300 if u == 3 else 5 for u in range(20)]
    done = [SimpleNamespace(uid=u, tokens=[1] * n, logprobs=[0.0] * n)
            for u, n in enumerate(lens)]
    prompts = {u: np.full(2, u, np.int32) for u in range(20)}
    pairs = check.sample(done, prompts, SEED)
    # one request already holds SAMPLE_TOKENS; the sample still spreads
    assert len(pairs) == check.SAMPLE_REQUESTS
    assert pairs[0][0][0] == 3
    again = check.sample(done, prompts, SEED)
    assert [p[0][0] for p in again] == [p[0][0] for p in pairs]
    assert len(check.sample(done[:4], prompts, SEED)) == 4


def test_backlog_fills_the_server_in_set_up(monkeypatch):
    from bench import run, window
    filled = []

    def spy(sched, built, stamps, **kw):
        filled.append(len(built))
        return fill(sched, built, stamps, **kw)
    fill = window.fill
    monkeypatch.setattr(window, "fill", spy)
    backlog = dict(TRAFFIC, arrival={"kind": "backlog", "backlog_per_s": 40})
    out = run.run_cell(_cell(backlog), SEED + 1, 1.0, False)
    assert filled == [out["attempted"]] == [40]
    assert out["correct"] is True, out["checks"]
    assert out["metrics"]["tok_s"]["value"] > 0


def _fault(name, monkeypatch):
    from repro.models.model import Model
    from repro.serving.scheduler import Scheduler
    if name == "token_altered":
        emit = Scheduler._emit

        def altered(self, slot, tok, lp):
            emit(self, slot, (tok + 1) % self.model.cfg.vocab_size, lp)
        monkeypatch.setattr(Scheduler, "_emit", altered)
    elif name == "state_unchanged":
        step = Model.decode_step

        def frozen(self, params, cache, token, active=None):
            logits, new = step(self, params, cache, token, active)
            return logits, {**new, "g0": cache["g0"]}
        monkeypatch.setattr(Model, "decode_step", frozen)
    elif name == "half_batch":
        step = Model.decode_step

        def half(self, params, cache, token, active=None):
            logits, new = step(self, params, cache, token, active)
            B = logits.shape[0]
            return logits.at[B // 2:].set(logits[:B - B // 2]), new
        monkeypatch.setattr(Model, "decode_step", half)


@pytest.mark.parametrize("fault", ["token_altered", "state_unchanged",
                                   "half_batch"])
def test_planted_fault_is_not_correct(fault, monkeypatch):
    from bench import run
    _fault(fault, monkeypatch)
    out = run.run_cell(_cell(), SEED, 2.0, False)
    gap = out["checks"]["max_logit_gap"]
    assert out["correct"] is False
    assert gap["value"] > gap["limit"]


def test_window_arithmetic():
    from bench import cost
    from bench.window import Window
    d = cost.Dims(2, 64, 4, 2, 16, 256, (((8, 8), (16, 8), (1, 4, 1)),))
    w = Window(t0=10.0, t_end=12.0, setup_s=5.0, due={0: 9.5, 1: 10.5},
               prompt_len={0: 20, 1: 30},
               stamps={0: [10.2, 10.4, 10.7], 1: [11.0, 11.5, 12.5]},
               submitted=2, lateness=[0.0, 0.0], steps=4,
               stats={"steps_run": 3, "prefill_chunks": 2}, chunk_size=32,
               num_slots=4, dims=d, peaks={})
    assert w.tokens() == 5
    assert sorted(np.round(w.gaps_s(), 6)) == [0.2, 0.3, 0.5]
    assert sorted(np.round(w.ttfts_s(), 6)) == [0.5, 0.7]
    assert w.decode_tokens() == 3 and w.prefill_rows() == 50
    assert w.served_flops() == (cost.prompt_flops(d, 20)
                                + cost.prompt_flops(d, 30)
                                + cost.token_flops(d, 21)
                                + cost.token_flops(d, 22)
                                + cost.token_flops(d, 31)
                                + 5 * cost.head_flops(d))
