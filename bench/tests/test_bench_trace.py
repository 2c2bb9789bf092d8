"""The trace reduction: busy time, idle share, self time, kernel and step
time and the breakdown, on small normalized traces, and the reading of a
real ``.xplane.pb`` recorded here on the CPU."""
import json
import os

import pytest

from bench import trace

HERE = os.path.dirname(os.path.abspath(__file__))
MS = 1e6


def _norm():
    # window 0..100 ms; one device; a loop op (10..60) holding two kernels
    # and a fusion; an op straddling the window's end; host spans
    ops = [["%while.1 = (s32[]) while(%t)", 10 * MS, 50 * MS],
           ["%_tt_fused_chain_call.3 = bf16[256,11008]{1,0} custom-call(%x)",
            12 * MS, 8 * MS],
           ["%fusion.7 = bf16[8,64]{1,0} fusion(%a)", 25 * MS, 20 * MS],
           ["%_tt_fused_chain_call.4 = bf16[256,4096]{1,0} custom-call(%y)",
            50 * MS, 5 * MS],
           ["%fusion.9 = f32[8]{0} fusion(%b)", 70 * MS, 10 * MS],
           ["%copy.2 = f32[8]{0} copy(%c)", 95 * MS, 10 * MS]]
    mods = [["jit_mixed_step(12)", 10 * MS, 50 * MS],
            ["jit_decode_step(13)", 70 * MS, 10 * MS],
            ["jit_pick(14)", 95 * MS, 10 * MS]]
    host = [["bench.step", 5 * MS, 58 * MS],
            ["bench.step", 66 * MS, 20 * MS],
            ["bench.submit", 86 * MS, 8 * MS]]
    return {"window": [0.0, 100 * MS],
            "devices": {"/device:TPU:0": {"ops": ops, "modules": mods}},
            "host": host}


def test_busy_and_idle():
    n = _norm()
    # busy: 10..60, 70..80, 95..100 (clipped) = 65 ms
    assert trace.busy_s(n) == pytest.approx(0.065)
    assert trace.window_s(n) == pytest.approx(0.1)
    from bench.metrics import idle_share
    w = type("W", (), {"trace": n})
    assert idle_share.read(w) == pytest.approx(35.0)


def test_self_time_subtracts_nested_ops():
    ops = _norm()["devices"]["/device:TPU:0"]["ops"]
    st = trace.self_times(ops, [0.0, 100 * MS])
    assert st[ops[0][0]] == pytest.approx(17 * MS)
    assert st[ops[2][0]] == pytest.approx(20 * MS)
    assert st[ops[5][0]] == pytest.approx(5 * MS)


def test_kernel_and_step_time():
    n = _norm()
    assert trace.kernel_ns(n, ("_tt_fused_chain_call",)) == pytest.approx(
        13 * MS)
    assert trace.module_runs(n, ("mixed_step", "decode_step")) == (
        2, pytest.approx(60 * MS))
    from bench.metrics import step_ms, tt_ms
    w = type("W", (), {"trace": n})
    assert step_ms.read(w) == pytest.approx(30.0)
    assert tt_ms.read(w) == pytest.approx(6.5)


def test_idle_gaps_named_by_host_span():
    gaps = trace.idle_gaps(_norm())
    assert gaps[0] == ("bench.submit", pytest.approx(0.015))   # 80..95
    assert sorted(gaps[1:]) == [("bench.step", pytest.approx(0.010)),
                                ("idle", pytest.approx(0.010))]
    b = trace.breakdown(_norm())
    assert b["device_ops"][0] == ["%fusion.7 bf16[8,64] fusion",
                                  pytest.approx(0.020)]
    assert len(b["idle_gaps"]) == 3
    json.dumps(b)


def test_no_device_ops_reads_nothing():
    n = _norm()
    n["devices"] = {}
    from bench.metrics import idle_share, step_ms, tt_ms
    w = type("W", (), {"trace": n})
    assert idle_share.read(w) is None
    assert step_ms.read(w) is None and tt_ms.read(w) is None


def test_normalize_reads_a_recorded_trace(tmp_path):
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    trace.start(str(tmp_path))
    with jax.profiler.TraceAnnotation(trace.WINDOW_SPAN):
        with jax.profiler.TraceAnnotation("bench.step"):
            f(x).block_until_ready()
    trace.stop()
    n = trace.normalize(trace.xplane_path(str(tmp_path)))
    assert n["window"][1] > n["window"][0]
    assert [h[0] for h in n["host"]] == ["bench.step"]
    assert n["devices"] == {}                 # the CPU is no device here


def test_recorded_chip_trace():
    """A quarter second of a real trace (deepseek-7b-tt decoding on one
    TPU v5e): the reduction's numbers on it, held fixed."""
    with open(os.path.join(HERE, "trace_ds7b_chat_backlog.json")) as f:
        n = json.load(f)
    assert trace.busy_s(n) == pytest.approx(0.246947, abs=2e-6)
    assert trace.window_s(n) == pytest.approx(0.25)
    from bench.metrics import idle_share, step_ms, tt_ms
    w = type("W", (), {"trace": n})
    assert idle_share.read(w) == pytest.approx(1.2213, abs=1e-3)
    runs, ns = trace.module_runs(n, step_ms.STEP_PROGRAMS)
    assert runs == 2 and ns == pytest.approx(245880068, abs=4)
    assert trace.kernel_ns(n, tt_ms.KERNELS) == pytest.approx(488981, abs=4)
    assert tt_ms.read(w) == pytest.approx(0.2444905, abs=1e-6)
    b = trace.breakdown(n)
    assert b["device_ops"][0][0] == "%convert.91 f32[512,64,32,128] convert"
    assert b["idle_gaps"][0] == ["bench.step", pytest.approx(0.003035,
                                                             abs=2e-6)]


def test_tt_roofline_on_the_recorded_trace():
    """Decode at 8 rows on the recorded trace: the TT work's least time
    (bytes-bound) over the kernels' time, a share under 100%."""
    from bench import cost
    from bench.metrics import tt_roofline
    with open(os.path.join(HERE, "trace_ds7b_chat_backlog.json")) as f:
        n = json.load(f)
    up, down = ((8, 512), (1376, 8), (1, 16, 1)), \
        ((8, 1376), (512, 8), (1, 16, 1))
    dims = cost.Dims(30, 4096, 32, 32, 128, 102400, (up, up, down))
    w = type("W", (), {"trace": n, "stats": {"steps_run": 2},
                       "dims": dims, "prefill_rows": lambda self: 0,
                       "decode_tokens": lambda self: 16,
                       "peaks": {"bf16_flops_per_s": 197e12,
                                 "hbm_bytes_per_s": 819e9}})()
    flops, byts = cost.tt_work(dims, 16, 2)
    assert byts / 819e9 > flops / 197e12
    want = 100 * (byts / 819e9) / (488981 / 1e9)
    assert tt_roofline.read(w) == pytest.approx(want, rel=1e-5)
    assert 0 < want < 100
