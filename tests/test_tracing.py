"""The serving step's own instrumentation (DESIGN.md §16): the named scopes
every family's blocks put into the HLO metadata, and the scheduler's host
phase counters and first-token split, read on an injectable virtual clock."""
import re

import jax
import jax.numpy as jnp
import pytest

from repro.configs import build, get_config
from repro.configs.base import TTConfig
from repro.configs.shapes import concrete_batch
from repro.serving.scheduler import Request, Scheduler

SCOPES = ("decode_attention", "chunk_attention", "ffn", "lm_head")
DISPATCH_S = 2.0 ** -6           # exact in binary: sums stay exact
PICK_S = 2.0 ** -8


def _tt_model(arch):
    tt = TTConfig(enabled=True, families=("ffn",), rank=4, backend="auto",
                  min_factor=2)
    model = build(get_config(arch, "smoke", tt=tt), param_dtype=jnp.bfloat16)
    return model, model.init(jax.random.PRNGKey(0))


def _op_scopes(hlo: str) -> set:
    return {part for name in re.findall(r'op_name="([^"]*)"', hlo)
            for part in name.split("/") if part in SCOPES}


@pytest.mark.parametrize("arch", ["deepseek-7b", "granite-8b"])
def test_step_programs_carry_the_named_scopes(arch):
    """The compiled mixed step names all four scopes in its op_name
    metadata; the masked decode step every scope but chunk attention."""
    model, params = _tt_model(arch)
    s = Scheduler(model, params, num_slots=2, cache_len=64, paged=True,
                  block_size=16, num_blocks=8, chunk_prefill=True,
                  chunk_size=16)
    s._ensure_pool_chunked()
    K, C, B = s.chunk_lanes, s.chunk_size, s.num_slots
    dec = [params, s.cache, jnp.zeros((B, 1), jnp.int32),
           jnp.ones((B,), bool)]
    lanes = [jnp.zeros((K, C), jnp.int32), jnp.zeros((K,), jnp.int32),
             jnp.zeros((K,), jnp.int32), jnp.ones((K,), jnp.int32),
             jnp.ones((K,), bool),
             jnp.full((K, s.max_blocks), s.num_blocks, jnp.int32)]
    mixed = model.jitted_mixed_step(K, C).lower(*dec, *lanes)
    decode = model.jitted_decode_step_masked().lower(*dec)
    assert _op_scopes(mixed.compile().as_text()) == set(SCOPES)
    assert _op_scopes(decode.compile().as_text()) == set(SCOPES) - {
        "chunk_attention"}


def _clocked(monkeypatch):
    """A chunked paged scheduler on a virtual clock that moves only where
    this test moves it: DISPATCH_S per step-program call, PICK_S per
    pick."""
    model, params = _tt_model("deepseek-7b")
    clk = {"t": 0.0}
    calls = {"dispatch": 0, "pick": 0}

    def timed(kind, fn):
        def run(*a, **k):
            calls[kind] += 1
            clk["t"] += DISPATCH_S if kind == "dispatch" else PICK_S
            return fn(*a, **k)
        return run

    for name in ("jitted_mixed_step", "jitted_decode_step_masked"):
        get = getattr(model, name)
        monkeypatch.setattr(model, name,
                            lambda *a, _get=get: timed("dispatch", _get(*a)))
    s = Scheduler(model, params, num_slots=2, cache_len=64, paged=True,
                  block_size=16, num_blocks=8, chunk_prefill=True,
                  chunk_size=16, clock=lambda: clk["t"])
    s._pick = timed("pick", s._pick)
    return s, clk, calls


def test_phase_counters_and_first_token_split(monkeypatch):
    s, clk, calls = _clocked(monkeypatch)
    toks = concrete_batch(s.model.cfg, 2, 12)["tokens"]
    for uid in range(2):                  # due a second before the start
        s.submit(Request(uid=uid, inputs={"tokens": toks[uid:uid + 1]},
                         max_new_tokens=3), submit_time=-1.0)
    s.run()
    st = s.stats()
    h = st["host_s"]
    # the clock moves only in dispatch and in the picks; both requests'
    # one-chunk prompts complete in a mixed step (a first-token pick)
    assert st["host_steps"] == calls["dispatch"]
    assert h["dispatch"] == calls["dispatch"] * DISPATCH_S
    assert h["first_token"] == 2 * PICK_S
    assert h["pick"] == (calls["pick"] - 2) * PICK_S
    assert h["step"] == clk["t"]
    assert h["admit"] == h["inputs"] == h["sync"] == h["emit"] == 0.0
    assert st["host_max_s"]["dispatch"] == DISPATCH_S
    assert st["host_max_s"]["step"] == DISPATCH_S + 2 * PICK_S
    # both admitted at t=0, a second after they were due; one chunk lane,
    # so uid 1's prompt waits a step behind uid 0's
    done = {f.uid: f for f in s.finished}
    assert st["first_tokens"] == 2
    assert st["ttft_queue_s"] == 2.0
    assert done[0].first_token_time == DISPATCH_S + PICK_S
    assert st["ttft_prefill_s"] == sum(f.first_token_time
                                       for f in done.values())
    s.reset_stats()
    st = s.stats()
    assert st["host_s"] == st["host_max_s"] == {}
    assert st["host_steps"] == st["first_tokens"] == 0
    assert st["ttft_queue_s"] == st["ttft_prefill_s"] == 0.0


def test_preempted_request_counts_its_last_admission(monkeypatch):
    """A request preempted before its first token is timed from the
    admission that produced the token, not its first one."""
    s, clk, _ = _clocked(monkeypatch)
    toks = concrete_batch(s.model.cfg, 2, 40)["tokens"]
    s.resize(num_slots=1)
    s.submit(Request(uid=0, inputs={"tokens": toks[0:1]},
                     max_new_tokens=2), submit_time=0.0)
    s.step()                              # uid 0 admitted, first chunk
    clk["t"] = 10.0
    s.submit(Request(uid=1, inputs={"tokens": toks[1:2]},
                     max_new_tokens=2, priority=1), submit_time=10.0)
    s.run()                     # uid 1 preempts; uid 0 resumes later
    assert s.preemptions == 1
    done = {f.uid: f for f in s.finished}
    resumed = done[1].finish_time        # uid 0 re-admitted when uid 1 left
    st = s.stats()
    assert st["first_tokens"] == 2
    # uid 1 was admitted when due; uid 0, due at 0, counts from `resumed`
    assert st["ttft_queue_s"] == pytest.approx(resumed)
    assert done[0].tokens.shape == (2,)
