"""The paged decode attention readers (``decode_attn_ms``,
``decode_attn_roofline``): their arithmetic, their silence without a
traced kernel or the ``decode_kv_tokens`` counter, and the scheduler's
counter against the positions each decode pass attends over."""
import json
import os

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))


def _decode_window(trace, stats):
    from bench import cost
    from bench.peaks import PEAKS
    dims = cost.Dims(layers=30, d_model=4096, heads=32, kv_heads=32,
                     head_dim=128, vocab=102400, ffn=())
    return type("W", (), {"trace": trace, "stats": stats, "dims": dims,
                          "peaks": PEAKS["TPU v5 lite"]})


def _trace(kernel_ns=(3e6, 5e6)):
    """Two decode-step executions, each with one paged kernel op."""
    ops = [["%_paged_decode_attn_call.12 = bf16[8,32,128] custom-call()",
            10e6 + 100e6 * i, ns] for i, ns in enumerate(kernel_ns)]
    ops.append(["%fusion.3 = bf16[8,4096] fusion()", 20e6, 1e6])
    mods = [["jit_decode_step(123)", 9e6 + 100e6 * i, 50e6]
            for i in range(len(kernel_ns))]
    return {"window": [0.0, 1e9], "host": [],
            "devices": {"/device:TPU:0": {"ops": ops, "modules": mods}}}


def test_decode_attention_readers():
    from bench.metrics import decode_attn_ms, decode_attn_roofline
    w = _decode_window(_trace(), {"decode_kv_tokens": 4000})
    assert decode_attn_ms.read(w) == pytest.approx(4.0)
    # 4000 positions × 30 layers × K and V × 32 × 128 × 2 B over 819 GB/s,
    # against 8 ms of kernel time
    least = 4000 * 30 * 2 * 32 * 128 * 2 / 819e9
    assert decode_attn_roofline.read(w) == pytest.approx(
        100 * least / 8e-3)


@pytest.mark.parametrize("trace,stats,silent", [
    (None, {"decode_kv_tokens": 4000}, {"ms", "roofline"}),   # untraced
    (_trace(), {"steps_run": 3}, {"roofline"}),       # no such counter
    (_trace(()), {"decode_kv_tokens": 4000}, {"ms", "roofline"})],
    ids=["untraced", "no_counter", "no_kernel"])
def test_decode_attention_readers_read_nothing_without_inputs(trace, stats,
                                                              silent):
    from bench.metrics import decode_attn_ms, decode_attn_roofline
    w = _decode_window(trace, stats)
    read = {"ms": decode_attn_ms.read(w),
            "roofline": decode_attn_roofline.read(w)}
    assert {k for k, v in read.items() if v is None} == silent


def test_decode_kv_tokens_counts_positions_attended():
    """The scheduler's counter against the positions each decode pass
    attends over: a request of prompt P and N tokens is decoded N - 1
    times (its first token comes from prefill), at pos P, …, P + N - 2,
    attending over pos + 1 positions each time."""
    import jax
    from bench import run
    from repro.serving.scheduler import Request, Scheduler
    with open(os.path.join(HERE, "tiny.json")) as f:
        cfg = json.load(f)
    srv = cfg["serving"]
    model = run.build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    sched = Scheduler(model, params, num_slots=2, cache_len=96, eos_id=None,
                      paged=True, block_size=16, num_blocks=12,
                      chunk_prefill=True, chunk_size=int(srv["chunk_size"]),
                      prefill_budget=int(srv["prefill_budget"]))
    shapes = [(5, 4), (40, 7), (17, 3)]
    for uid, (P, N) in enumerate(shapes):
        toks = (np.arange(P, dtype=np.int32) * 31 + uid) % 256
        sched.submit(Request(uid=uid, inputs={"tokens": toks[None]},
                             max_new_tokens=N))
    sched.run()
    want = sum(P + i for P, N in shapes for i in range(1, N))
    assert sched.stats()["decode_kv_tokens"] == want
    sched.reset_stats()
    assert sched.stats()["decode_kv_tokens"] == 0
