"""Plan-compile-execute pipeline (kernels.plan, DESIGN.md §10).

Covers the plan-resolution contract: deterministic re-resolution, JSON
serialize/deserialize roundtrips, every concrete backend reachable from
``auto`` on some shape/dtype, the legacy string-spec shim compiling to
plans identical to explicit kwargs, malformed-spec rejection, the
versioned autotune cache (stale entries ignored, whole plans persisted),
and the serving contract: model build resolves each TT layer's plan
exactly once — a scheduler decode run performs ZERO re-planning.
"""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import build, get_config
from repro.configs.base import TTConfig
from repro.configs.shapes import concrete_batch
from repro.core.tt import make_plan, tt_init
from repro.kernels import autotune, plan as ttplan
from repro.kernels.ops import BACKENDS, tt_forward
from repro.kernels.plan import (PlanBook, TTExecutionPlan, plan_tt_forward,
                                resolve_plan)
from repro.serving.engine import generate
from repro.serving.scheduler import Request, Scheduler

KEY = jax.random.PRNGKey(0)

# d=3 chain whose fp32 packed cores alone bust the 32 MiB VMEM budget
# (bench_quant's showcase): step-fallback in fp32, fused under int8
BIG = ((256, 2, 16), (4, 4, 64), 128)          # (ms, ns, rank)
SMALL3 = ((8, 4, 4), (4, 4, 8), 4)


def _chain(ms, ns, rank):
    tp = make_plan(ms, ns, rank)
    return tp.ns, tp.ms, tp.ranks


# ---------------------------------------------------------------------------
# Resolution determinism + serialization
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", ["auto", "pallas_step", "xla"])
def test_same_inputs_resolve_identical_plan(tmp_path, backend):
    ns, ms, ranks = _chain(*SMALL3)
    kw = dict(batch=16, dtype=jnp.float32, backend=backend, tune="off")
    p1 = plan_tt_forward(ns, ms, ranks, **kw)
    p2 = plan_tt_forward(ns, ms, ranks, **kw)
    assert p1 == p2
    # the memoized resolver returns the same OBJECT without re-resolving
    n0 = ttplan.plan_resolutions()
    m1 = resolve_plan(ns, ms, ranks, **kw)
    n1 = ttplan.plan_resolutions()
    m2 = resolve_plan(ns, ms, ranks, **kw)
    assert m1 is m2 and ttplan.plan_resolutions() == n1 > n0


@pytest.mark.parametrize("backend", ["auto", "pallas_step", "xla"])
def test_plan_json_roundtrip(backend):
    ns, ms, ranks = _chain(*SMALL3)
    p = plan_tt_forward(ns, ms, ranks, batch=16, backend=backend,
                        tune="off")
    rt = TTExecutionPlan.from_json_dict(p.to_json_dict())
    assert rt == p
    # through an actual JSON string (the cache file format)
    rt2 = TTExecutionPlan.from_json_dict(json.loads(
        json.dumps(p.to_json_dict())))
    assert rt2 == p


def test_json_rejects_unknown_schema():
    ns, ms, ranks = _chain(*SMALL3)
    obj = plan_tt_forward(ns, ms, ranks, tune="off").to_json_dict()
    obj["schema"] = 999
    with pytest.raises(ValueError, match="schema"):
        TTExecutionPlan.from_json_dict(obj)


# ---------------------------------------------------------------------------
# auto routing: every concrete backend reachable
# ---------------------------------------------------------------------------

def test_every_backend_reachable_from_auto():
    got = {}
    # d=1: a single core is a plain matmul — XLA
    got["xla"] = plan_tt_forward((4,), (8,), (1, 1), backend="auto")
    # d=2 → the fused2 fast path
    ns, ms, ranks = _chain((16, 8), (4, 16), 8)
    got["pallas_fused2"] = plan_tt_forward(ns, ms, ranks, backend="auto")
    # small d=3, VMEM-resident → fused chain
    ns, ms, ranks = _chain(*SMALL3)
    got["pallas_fused"] = plan_tt_forward(ns, ms, ranks, backend="auto")
    # huge d=3 in fp32 → step fallback
    ns, ms, ranks = _chain(*BIG)
    got["pallas_step"] = plan_tt_forward(ns, ms, ranks, backend="auto")
    for want, p in got.items():
        assert p.backend == want, (want, p.describe())
        assert p.requested == "auto"
    concrete = set(BACKENDS) - {"auto"}
    assert {p.backend for p in got.values()} == concrete
    # the int8 twin of the huge chain re-enters the fused set (DESIGN.md §8)
    p8 = plan_tt_forward(ns, ms, ranks, backend="auto", weights="int8")
    assert p8.backend == "pallas_fused" and p8.fused_eligible
    assert not got["pallas_step"].fused_eligible


def test_fit_verdict_is_priced():
    ns, ms, ranks = _chain(*BIG)
    fp = plan_tt_forward(ns, ms, ranks, backend="auto")
    q = plan_tt_forward(ns, ms, ranks, backend="auto", weights="int8")
    assert fp.fit_weight_bytes == 4 * q.fit_weight_bytes
    assert fp.fit_peak_state_bytes == q.fit_peak_state_bytes > 0


# ---------------------------------------------------------------------------
# String-spec shim
# ---------------------------------------------------------------------------

def test_string_shim_produces_identical_plans():
    ns, ms, ranks = _chain(*SMALL3)
    explicit = plan_tt_forward(ns, ms, ranks, batch=16,
                               backend="auto", tune="off", weights="int8")
    via_spec = plan_tt_forward(ns, ms, ranks, batch=16,
                               backend="auto:off:int8")
    assert via_spec == explicit


def test_string_shim_tt_forward_matches_plan_path():
    tp = make_plan(*SMALL3)
    cores = tt_init(KEY, tp)
    x = jax.random.normal(jax.random.PRNGKey(1), (9, tp.N))
    plan = plan_tt_forward(tp.ns, tp.ms, tp.ranks, batch=9, tune="off")
    y_plan = tt_forward(cores, x, plan=plan, interpret=True)
    with pytest.deprecated_call():
        y_str = tt_forward(cores, x, backend="auto:off", interpret=True)
    np.testing.assert_array_equal(np.asarray(y_plan), np.asarray(y_str))


@pytest.mark.parametrize("spec", ["xla::int8", "xla:", ":int8", "auto::",
                                  "pallas_step:cached:"])
def test_malformed_specs_with_empty_tokens_rejected(spec):
    with pytest.raises(ValueError, match="empty token"):
        ttplan.compile_spec(spec)


def test_spec_errors_list_all_valid_tokens():
    """The rejection message names every token class in one place."""
    for spec in ("xla::", "auto:bogus", "nonsense"):
        with pytest.raises(ValueError) as ei:
            ttplan.compile_spec(spec)
        msg = str(ei.value)
        for frag in ("backends", "tune modes", "weight modes"):
            assert frag in msg, (spec, msg)


def test_tt_forward_rejects_mismatched_plan():
    tp = make_plan(*SMALL3)
    cores = tt_init(KEY, tp)
    x = jax.random.normal(jax.random.PRNGKey(1), (4, tp.N))
    other = plan_tt_forward(*_chain((16, 8), (4, 16), 8), tune="off")
    with pytest.raises(ValueError, match="plan/chain mismatch"):
        tt_forward(cores, x, plan=other, interpret=True)
    good = plan_tt_forward(tp.ns, tp.ms, tp.ranks, tune="off")
    with pytest.raises(ValueError, match="conflicts with the plan"):
        tt_forward(cores, x, plan=good, weights="int8", interpret=True)


# ---------------------------------------------------------------------------
# Versioned autotune cache
# ---------------------------------------------------------------------------

def test_stale_cache_entries_silently_ignored(tmp_path):
    """Entries without a schema field (pre-plan caches), with a stale
    schema, or in unknown formats must be dropped at load — never crash,
    never served."""
    path = tmp_path / "tune.json"
    path.write_text(json.dumps({
        "legacy|no-schema": {"block_b": 512},
        "stale|old-schema": {"schema": 0, "block_b": 256},
        "weird|not-a-dict": [1, 2, 3],
        "ok|current": {"schema": autotune.CACHE_SCHEMA, "block_b": 64},
    }))
    cache = autotune.AutotuneCache.load(str(path))
    assert set(cache.entries) == {"ok|current"}
    # a garbage file (not even a dict) is an empty cache, not a crash
    path.write_text(json.dumps([1, 2]))
    assert autotune.AutotuneCache.load(str(path)).entries == {}


def test_put_stamps_schema(tmp_path):
    cache = autotune.AutotuneCache.load(str(tmp_path / "t.json"))
    cache.put("k", {"block_b": 8})
    on_disk = json.loads((tmp_path / "t.json").read_text())
    assert on_disk["k"]["schema"] == autotune.CACHE_SCHEMA


def test_measure_mode_persists_whole_plan(tmp_path):
    """tune='measure' stores the WHOLE resolved plan (versioned JSON);
    a later cached-mode resolution deserializes it — identical plan, zero
    new measurements, zero analytic re-derivation."""
    cache = str(tmp_path / "tune.json")
    ns, ms, ranks = _chain(*SMALL3)
    p1 = plan_tt_forward(ns, ms, ranks, batch=16, backend="auto",
                         tune="measure", interpret=True, cache_path=cache)
    assert p1.source == "measured"
    entries = json.loads((tmp_path / "tune.json").read_text())
    pkeys = [k for k in entries if k.startswith("plan.auto|")]
    assert len(pkeys) == 1 and entries[pkeys[0]]["kind"] == "plan"
    autotune.clear_memory_caches()          # force the disk round-trip
    n = autotune.N_MEASUREMENTS
    p2 = plan_tt_forward(ns, ms, ranks, batch=16, backend="auto",
                         tune="cached", interpret=True, cache_path=cache)
    assert p2 == p1
    assert autotune.N_MEASUREMENTS == n, "cached plan hit must not re-time"


# ---------------------------------------------------------------------------
# PlanBook + serving: build-time resolution, zero re-planning
# ---------------------------------------------------------------------------

def _tt_model(backend="auto"):
    cfg = get_config("deepseek_7b", "smoke",
                     tt=TTConfig(enabled=True, families=("ffn", "attn"),
                                 rank=4, min_factor=2, backend=backend))
    model = build(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


def test_planbook_primes_all_tt_layers():
    cfg, model, params = _tt_model()
    n0 = ttplan.plan_resolutions()
    book = model.plan_book
    assert len(book) > 0
    assert ttplan.plan_resolutions() > n0
    for p in book.plans.values():
        assert p.backend in ("xla", "pallas_step", "pallas_fused2",
                             "pallas_fused")
        assert p.requested == "auto"
    # the book is built exactly once per model
    assert model.plan_book is book


def test_scheduler_decode_performs_zero_replanning():
    """The acceptance counter: after model build + one warm-up request,
    a continuous-batching run over NEW requests (including new prompt
    lengths, which retrace prefill) resolves ZERO plans."""
    cfg, model, params = _tt_model()
    sched = Scheduler(model, params, num_slots=2, cache_len=24)
    warm = concrete_batch(cfg, 1, 6)
    sched.submit(Request(uid=-1, inputs={"tokens": warm["tokens"]},
                         max_new_tokens=3))
    sched.run()
    n0 = ttplan.plan_resolutions()
    for uid, S in enumerate((6, 9, 4)):     # 9 and 4 are NEW prefill shapes
        b = concrete_batch(cfg, 1, S, seed=uid)
        sched.submit(Request(uid=uid, inputs={"tokens": b["tokens"]},
                             max_new_tokens=4))
    out = sched.run()
    assert len(out) == 3
    assert ttplan.plan_resolutions() == n0, \
        "serving must execute build-time plans only (zero re-planning)"


def test_quantized_params_served_with_int8_plans_once():
    """Quantizing a checkpoint introduces each layer's int8 twin plan —
    resolved once on first use, then never again."""
    cfg, model, params = _tt_model()
    qparams = model.quantize_params(params)
    batch = dict(concrete_batch(cfg, 2, 6), cache_len=12)
    r1 = generate(model, qparams, batch, steps=3)
    n0 = ttplan.plan_resolutions()
    r2 = generate(model, qparams, batch, steps=3)
    assert ttplan.plan_resolutions() == n0
    np.testing.assert_array_equal(np.asarray(r1.tokens),
                                  np.asarray(r2.tokens))
    # int8 storage forced int8 plans through the same book
    assert any(p.weights == "int8" for p in model.plan_book.plans.values())
