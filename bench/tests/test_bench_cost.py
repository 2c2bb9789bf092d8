"""The benchmark's own operation and byte counts against the program's
flop model (core/flops.py, paper Eq. 11/13) on the DSE's FFN plans."""
import pytest

from bench import cost
from repro.core.flops import tt_flops, tt_params

# (ns, ms, ranks) the DSE picks at rank 16, min factor 8
PLANS = {
    "deepseek-7b up": ((8, 512), (1376, 8), (1, 16, 1)),
    "deepseek-7b down": ((8, 1376), (512, 8), (1, 16, 1)),
    "granite-8b up": ((8, 512), (1792, 8), (1, 16, 1)),
    "granite-8b down": ((8, 1792), (512, 8), (1, 16, 1)),
    "three cores": ((8, 8, 64), (172, 8, 8), (1, 16, 16, 1)),
}


@pytest.mark.parametrize("name", sorted(PLANS))
def test_tt_flops_match_eq11(name):
    ns, ms, ranks = PLANS[name]
    assert cost.tt_flops_per_row(ns, ms, ranks) == tt_flops(
        ms, ns, ranks, bias=False)
    assert cost.tt_core_params(ns, ms, ranks) == tt_params(
        ms, ns, ranks, bias=False)


def test_deepseek_ffn_is_a_small_share_of_dense():
    ns, ms, ranks = PLANS["deepseek-7b up"]
    f = cost.tt_flops_per_row(ns, ms, ranks)
    assert 3e6 < f < 5e6                  # about 3.9 MFLOP a token
    assert f < 0.05 * 2 * 4096 * 11008


@pytest.mark.parametrize("name", sorted(PLANS))
def test_call_bytes(name):
    ns, ms, ranks = PLANS[name]
    N = 1
    for n in ns:
        N *= n
    M = 1
    for m in ms:
        M *= m
    w = tt_params(ms, ns, ranks, bias=False) * 2
    assert cost.tt_call_bytes(ns, ms, ranks, rows=0) == w
    assert cost.tt_call_bytes(ns, ms, ranks, rows=10) == w + 10 * (N + M) * 2


def test_tt_work_counts_cores_per_call_and_rows_once():
    chains = (PLANS["deepseek-7b up"], PLANS["deepseek-7b up"],
              PLANS["deepseek-7b down"])
    d = cost.Dims(30, 4096, 32, 32, 128, 102400, chains)
    f1, b1 = cost.tt_work(d, rows=100, calls_per_matrix=1)
    f2, b2 = cost.tt_work(d, rows=100, calls_per_matrix=3)
    assert f1 == f2 == 30 * 100 * sum(cost.tt_flops_per_row(*c)
                                      for c in chains)
    w = 30 * sum(cost.tt_call_bytes(*c, rows=0) for c in chains)
    assert b2 - b1 == 2 * w


def test_prompt_flops_is_the_sum_of_its_tokens():
    d = cost.Dims(2, 64, 4, 2, 16, 256, (PLANS["three cores"],))
    P = 37
    assert cost.prompt_flops(d, P) == sum(cost.token_flops(d, p + 1)
                                          for p in range(P))
