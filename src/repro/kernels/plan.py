"""Plan-compile-execute pipeline for TT layer dispatch (DESIGN.md §10).

The paper's deployment story is ahead-of-time: prune the TTD design space,
pick a decomposition, apply the compiler optimizations once per layer,
then ship the compiled artifact.  This module is that split made explicit
for the kernel stack: every dispatch decision that used to be re-derived
at trace time from a ``"<backend>:<tune>:<weights>"`` string — backend
routing, the VMEM fit verdict, fused-chain eligibility, block/tile
selection, autotune cache lookups — is resolved ONCE into a frozen,
serializable :class:`TTExecutionPlan`, and every layer of the stack
(``kernels.ops.tt_forward``, ``models/layers.linear_apply``, the DSE's
measured rerank, the serving scheduler) consumes the plan instead of
re-deciding.

Three levels of API, outermost first:

``PlanBook``
    Per-model plan registry.  Built once at model-build time from the
    model's ``TTConfig`` + param dtype; ``prime()`` walks the param-spec
    tree and resolves a plan for every TT layer, so scanned stacks and the
    serving scheduler never plan inside a trace.  ``plan_for_cores`` is
    the trace-time lookup (a dict hit on the chain signature).

``resolve_plan``
    Process-wide memoized resolver — same inputs always return the same
    plan object.  ``clear_plan_memo()`` drops the memo (tests).

``plan_tt_forward``
    The actual resolver: subsumes the old ``parse_backend_spec`` + auto
    routing + ``select_blocks``/``fused_chain_batch_tile`` + autotune-cache
    lookup.  Every call increments ``PLAN_RESOLUTIONS`` so tests and the
    CI smoke can assert that serving performs ZERO re-planning.

Legacy ``"<backend>[:<tune>][:<weights>]"`` strings keep working through
``compile_spec`` (a deprecation shim): the string is parsed once and
compiled into a plan; new code passes explicit fields or a plan object.

Whole plans are persisted in the versioned autotune JSON cache
(``schema`` = :data:`PLAN_SCHEMA`, kind ``plan.<requested-backend>``) in
measure mode, so a deployment's resolved plans survive process restarts
exactly like measured tiles do.
"""
from __future__ import annotations

import dataclasses
import warnings
from typing import Sequence

import jax.numpy as jnp

from repro.core import hw
from repro.core.flops import prod
from repro.core.packing import BlockPlan, chain_fit_report
from . import autotune

# Bumped together with autotune.CACHE_SCHEMA / tt_contract.KERNEL_VERSION:
# a serialized plan is only valid for the kernel generation it was
# resolved against.
PLAN_SCHEMA = autotune.CACHE_SCHEMA

# Nominal batch the model stack plans at.  The kernels clamp every tile to
# the runtime extent (min(tile, dim) + padding), so one build-time plan
# serves prefill (large token batches) and decode (tiny ones) without
# re-resolution; 128 rows is one full MXU face, the natural anchor.
PLANNING_BATCH = 128

BACKENDS = ("xla", "pallas_step", "pallas_fused2", "pallas_fused", "auto")

# accepted weight-mode tokens ('fp32'/'float32' are aliases kept for
# TTConfig readability; canonical modes are autotune.WEIGHT_MODES)
WEIGHT_ALIASES = {"fp": "fp", "fp32": "fp", "float32": "fp", "int8": "int8"}

# number of plan resolutions actually executed (memo/PlanBook hits do not
# count).  Serving tests assert this stays flat across a decode run.
PLAN_RESOLUTIONS = 0


def plan_resolutions() -> int:
    return PLAN_RESOLUTIONS


def _token_help() -> str:
    """All valid spec tokens, in one place (satellite: malformed specs
    must name every accepted token class)."""
    return (f"backends {BACKENDS}, tune modes {autotune.TUNE_MODES}, "
            f"weight modes {tuple(WEIGHT_ALIASES)}")


def normalize_weights(weights: str | None) -> str | None:
    if weights is None:
        return None
    if weights not in WEIGHT_ALIASES:
        raise ValueError(
            f"unknown weight mode {weights!r}: expected one of "
            f"{tuple(WEIGHT_ALIASES)}")
    return WEIGHT_ALIASES[weights]


def compile_spec(backend: str, tune: str | None = None,
                 weights: str | None = None, *, warn: bool = False
                 ) -> tuple[str, str | None, str | None]:
    """DEPRECATION SHIM: split ``"<backend>[:<tune>][:<weights>]"`` into
    its (backend, tune, weights) parts, rejecting malformed specs.

    Suffix tokens are classified by membership (tune modes vs weight
    modes) so the order is free; explicit ``tune=``/``weights=`` arguments
    always win over suffix tokens.  Empty tokens (``"xla::int8"``, a
    trailing ``":"``, a leading ``":"``) are rejected outright.  New code
    should pass explicit fields to ``plan_tt_forward`` / ``resolve_plan``
    or hand a :class:`TTExecutionPlan` to ``tt_forward`` directly.
    """
    weights = normalize_weights(weights)
    if ":" in backend:
        if warn:
            warnings.warn(
                "string backend specs ('<backend>:<tune>:<weights>') are "
                "deprecated — resolve a TTExecutionPlan (kernels.plan) and "
                "pass plan=... instead", DeprecationWarning, stacklevel=3)
        backend, *suffix = backend.split(":")
        if not backend or any(not tok for tok in suffix):
            raise ValueError(
                f"malformed backend spec with empty token(s): expected "
                f"'<backend>[:<tune>][:<weights>]' built from "
                f"{_token_help()}")
        suffix_tune = suffix_weights = None
        for tok in suffix:
            if tok in autotune.TUNE_MODES:
                if suffix_tune is not None:
                    raise ValueError(
                        f"conflicting tune-mode suffixes "
                        f"{suffix_tune!r} and {tok!r} in backend spec")
                suffix_tune = tok
            elif tok in WEIGHT_ALIASES:
                if suffix_weights is not None:
                    raise ValueError(
                        f"conflicting weight-mode suffixes "
                        f"{suffix_weights!r} and {tok!r} in backend spec")
                suffix_weights = WEIGHT_ALIASES[tok]
            else:
                raise ValueError(
                    f"unknown backend suffix {tok!r}: valid tokens are "
                    f"{_token_help()}")
        tune = tune if tune is not None else suffix_tune
        weights = weights if weights is not None else suffix_weights
    if backend not in BACKENDS:
        raise ValueError(
            f"unknown backend {backend!r}: valid tokens are {_token_help()}")
    return backend, tune, weights


# ---------------------------------------------------------------------------
# The plan
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class TTExecutionPlan:
    """Fully resolved execution recipe for one TT chain.

    Frozen and hashable (usable as a jit static argument / memo key);
    equality is field-wise, so 'same inputs → identical plan' is a simple
    ``==``.  ``backend`` is always CONCRETE — ``auto`` is resolved away at
    planning time and only survives in ``requested``.
    """
    ns: tuple[int, ...]            # input factors (core order, t = 1..d)
    ms: tuple[int, ...]            # output factors
    ranks: tuple[int, ...]         # r_0 .. r_d
    requested: str                 # what the caller asked for (may be 'auto')
    backend: str                   # resolved concrete backend
    weights: str                   # 'fp' | 'int8' (resident core dtype class)
    tune: str                      # autotune mode the plan was resolved under
    dtype: str                     # activation dtype name
    batch: int                     # planning batch (tiles clamp at runtime)
    weight_itemsize: int           # resident bytes/elem of the packed cores
    fused_eligible: bool           # whole-chain VMEM fit verdict (d >= 2)
    fit_weight_bytes: int          # packed-core residency the verdict priced
    fit_peak_state_bytes: int      # peak per-row state pair the verdict priced
    block_b: int | None = None     # fused-path batch tile
    step_plans: tuple[BlockPlan, ...] | None = None  # per-step (exec order)
    source: str = "analytic"       # 'analytic' | 'cached' | 'measured'

    @property
    def d(self) -> int:
        return len(self.ns)

    @property
    def N(self) -> int:
        return prod(self.ns)

    @property
    def M(self) -> int:
        return prod(self.ms)

    def describe(self) -> str:
        tile = (f"block_b={self.block_b}" if self.block_b is not None else
                f"steps={len(self.step_plans or ())}")
        return (f"TTExecutionPlan[{self.requested}->{self.backend} "
                f"d={self.d} n={'x'.join(map(str, self.ns))} "
                f"m={'x'.join(map(str, self.ms))} w={self.weights} "
                f"{tile} fused_ok={self.fused_eligible} src={self.source}]")

    # ------------------------------------------------------------- JSON
    def to_json_dict(self) -> dict:
        return {
            "schema": PLAN_SCHEMA,
            "ns": list(self.ns), "ms": list(self.ms),
            "ranks": list(self.ranks),
            "requested": self.requested, "backend": self.backend,
            "weights": self.weights, "tune": self.tune,
            "dtype": self.dtype, "batch": self.batch,
            "weight_itemsize": self.weight_itemsize,
            "fused_eligible": self.fused_eligible,
            "fit_weight_bytes": self.fit_weight_bytes,
            "fit_peak_state_bytes": self.fit_peak_state_bytes,
            "block_b": self.block_b,
            "step_plans": None if self.step_plans is None else [
                [p.bm, p.bb, p.bn, p.traffic_bytes, p.vmem_bytes]
                for p in self.step_plans],
            "source": self.source,
        }

    @classmethod
    def from_json_dict(cls, obj: dict) -> "TTExecutionPlan":
        if not isinstance(obj, dict) or obj.get("schema") != PLAN_SCHEMA:
            raise ValueError(
                f"unsupported plan schema {obj.get('schema') if isinstance(obj, dict) else obj!r}"
                f" (this build reads schema {PLAN_SCHEMA})")
        sp = obj["step_plans"]
        return cls(
            ns=tuple(obj["ns"]), ms=tuple(obj["ms"]),
            ranks=tuple(obj["ranks"]),
            requested=obj["requested"], backend=obj["backend"],
            weights=obj["weights"], tune=obj["tune"],
            dtype=obj["dtype"], batch=int(obj["batch"]),
            weight_itemsize=int(obj["weight_itemsize"]),
            fused_eligible=bool(obj["fused_eligible"]),
            fit_weight_bytes=int(obj["fit_weight_bytes"]),
            fit_peak_state_bytes=int(obj["fit_peak_state_bytes"]),
            block_b=None if obj["block_b"] is None else int(obj["block_b"]),
            step_plans=None if sp is None else tuple(
                BlockPlan(int(a), int(b), int(c), int(t), int(v))
                for a, b, c, t, v in sp),
            source=obj["source"],
        )


# ---------------------------------------------------------------------------
# Resolution
# ---------------------------------------------------------------------------

def _validate_chain(ns, ms, ranks) -> None:
    d = len(ns)
    if d < 1 or len(ms) != d or len(ranks) != d + 1:
        raise ValueError(
            f"inconsistent chain signature: ns={ns} ms={ms} ranks={ranks}")


def plan_tt_forward(ns: Sequence[int], ms: Sequence[int],
                    ranks: Sequence[int], *,
                    batch: int = PLANNING_BATCH, dtype=jnp.float32,
                    backend: str = "auto", tune: str | None = None,
                    weights: str | None = None,
                    weight_itemsize: int | None = None,
                    interpret: bool | None = None,
                    vmem_budget: int | None = None,
                    cache_path: str | None = None) -> TTExecutionPlan:
    """Resolve ONE execution plan for the chain ``(ns, ms, ranks)``.

    Subsumes the old string-spec round-trip: backend routing (including
    ``auto``), the dtype-aware VMEM fit verdict, fused-chain eligibility,
    fused batch-tile / per-step block-plan selection, and the autotune
    cache consultation all happen here, once.  ``tune='measure'``
    additionally persists the WHOLE resolved plan in the versioned
    autotune cache, so a later ``tune='cached'`` resolution of the same
    signature deserializes it without touching the analytic model.

    ``vmem_budget`` overrides the hardware VMEM budget (tests); a
    non-default budget skips the autotuner and resolves purely
    analytically, since measured tiles are only valid for the real budget.
    """
    global PLAN_RESOLUTIONS
    ns, ms, ranks = tuple(map(int, ns)), tuple(map(int, ms)), \
        tuple(map(int, ranks))
    _validate_chain(ns, ms, ranks)
    requested, tune, weights = compile_spec(backend, tune, weights)
    tune = tune or "cached"
    if tune not in autotune.TUNE_MODES:
        raise ValueError(
            f"unknown tune mode {tune!r}: valid tokens are {_token_help()}")
    weights = weights or "fp"
    d = len(ns)
    dtype_name = jnp.dtype(dtype).name
    itemsize = max(jnp.dtype(dtype).itemsize, 4)
    w_item = 1 if weights == "int8" else (weight_itemsize or itemsize)
    budget = hw.VMEM_BUDGET_BYTES if vmem_budget is None else vmem_budget
    custom_budget = budget != hw.VMEM_BUDGET_BYTES
    wtag = autotune._weight_tag(weights, w_item, itemsize)

    # whole-plan cache: a measure-mode run persists its resolution; later
    # cached-mode resolutions of the same signature deserialize it.
    use_plan_cache = tune in ("cached", "measure") and not custom_budget
    pkey = autotune.plan_key(f"plan.{requested}", ns, ms, ranks, dtype,
                             batch, wtag)
    if use_plan_cache:
        hit = autotune.get_cache(cache_path).get(pkey)
        if hit is not None and hit.get("kind") == "plan":
            try:
                plan = TTExecutionPlan.from_json_dict(hit["plan"])
            except (ValueError, KeyError, TypeError):
                plan = None          # stale/unknown entry: ignore, re-resolve
            if plan is not None:
                PLAN_RESOLUTIONS += 1
                return plan

    fit = chain_fit_report(ns, ms, ranks, itemsize=itemsize,
                           vmem_budget=budget, weight_itemsize=w_item)
    fused_ok = d >= 2 and fit.fits

    resolved = requested
    if requested == "auto":
        if d < 2:
            resolved = "xla"          # a single core is a plain matmul
        elif d == 2:
            resolved = "pallas_fused2"
        elif fused_ok:
            resolved = "pallas_fused"
        else:
            resolved = "pallas_step"
    elif requested == "pallas_fused2" and d != 2:
        raise ValueError(
            f"fused2 backend requires a length-2 plan, got d={d}")
    elif requested == "pallas_fused":
        if d < 2:
            raise ValueError(
                f"fused chain backend requires d >= 2, got d={d}")
        if not fused_ok:
            raise ValueError(
                "chain does not fit VMEM — use pallas_step (or "
                "backend='auto')")

    block_b: int | None = None
    step_plans: tuple[BlockPlan, ...] | None = None
    source = "analytic"
    if resolved in ("pallas_fused2", "pallas_fused"):
        if custom_budget:
            block_b = fit.batch_tile
        else:
            block_b, source = autotune.fused_tile_ex(
                ns, ms, ranks, dtype, batch, mode=tune, interpret=interpret,
                cache_path=cache_path, weights=weights,
                weight_itemsize=weight_itemsize)
        # fused2 tolerates block_b=None (the kernel falls back to its own
        # d=2 analytic tile); the general chain must be VMEM-resident
        if resolved == "pallas_fused" and block_b is None:
            raise ValueError(
                "chain does not fit VMEM at any batch tile — use "
                "pallas_step (or backend='auto')")
    elif resolved == "pallas_step":
        plans, srcs = [], []
        b = batch * prod(ns)
        for t in range(d - 1, -1, -1):
            nt, mt = ns[t], ms[t]
            rt, rt_1 = ranks[t + 1], ranks[t]
            bt = max(b // (nt * rt), 1)
            sp, src = autotune.step_plan_ex(
                mt, bt, nt, rt, rt_1, dtype, mode=tune, interpret=interpret,
                cache_path=cache_path, weights=weights,
                weight_itemsize=weight_itemsize)
            plans.append(sp)
            srcs.append(src)
            b = mt * bt * rt_1
        step_plans = tuple(plans)
        for lvl in ("measured", "cached"):
            if lvl in srcs:
                source = lvl
                break

    plan = TTExecutionPlan(
        ns=ns, ms=ms, ranks=ranks, requested=requested, backend=resolved,
        weights=weights, tune=tune, dtype=dtype_name, batch=batch,
        weight_itemsize=w_item, fused_eligible=fused_ok,
        fit_weight_bytes=fit.weight_bytes,
        fit_peak_state_bytes=fit.peak_state_bytes,
        block_b=block_b, step_plans=step_plans, source=source)
    PLAN_RESOLUTIONS += 1
    if use_plan_cache and tune == "measure":
        autotune.get_cache(cache_path).put(
            pkey, {"kind": "plan", "plan": plan.to_json_dict()})
    return plan


# ---------------------------------------------------------------------------
# Process-wide memoized resolution
# ---------------------------------------------------------------------------

_PLAN_MEMO: dict = {}


def resolve_plan(ns, ms, ranks, *, batch: int = PLANNING_BATCH,
                 dtype=jnp.float32, backend: str = "auto",
                 tune: str | None = None, weights: str | None = None,
                 weight_itemsize: int | None = None,
                 interpret: bool | None = None,
                 cache_path: str | None = None) -> TTExecutionPlan:
    """Memoized :func:`plan_tt_forward`: the same planning inputs return
    the same plan object without re-resolution (and without incrementing
    ``PLAN_RESOLUTIONS``)."""
    key = (tuple(ns), tuple(ms), tuple(ranks), batch,
           jnp.dtype(dtype).name, backend, tune, weights, weight_itemsize,
           interpret, cache_path or autotune._default_cache_path())
    plan = _PLAN_MEMO.get(key)
    if plan is None:
        plan = plan_tt_forward(
            ns, ms, ranks, batch=batch, dtype=dtype, backend=backend,
            tune=tune, weights=weights, weight_itemsize=weight_itemsize,
            interpret=interpret, cache_path=cache_path)
        _PLAN_MEMO[key] = plan
    return plan


def clear_plan_memo() -> None:
    """Drop the process-wide plan memo (tests that monkeypatch the fit
    model or the autotune cache must clear it)."""
    _PLAN_MEMO.clear()


# ---------------------------------------------------------------------------
# Per-model plan registry
# ---------------------------------------------------------------------------

def chain_signature(core_shapes: Sequence[Sequence[int]]
                    ) -> tuple[tuple[int, ...], tuple[int, ...],
                               tuple[int, ...]]:
    """(ns, ms, ranks) of a core list given per-core shapes.  Only the
    trailing 4 dims are read, so stacked specs (scan layers, MoE experts)
    resolve to the per-layer chain they execute as."""
    quads = [tuple(int(v) for v in s[-4:]) for s in core_shapes]
    ns = tuple(q[1] for q in quads)
    ms = tuple(q[2] for q in quads)
    ranks = tuple(q[0] for q in quads) + (quads[-1][3],)
    return ns, ms, ranks


class PlanBook:
    """Build-time plan registry for one model.

    One PlanBook per Model: construction fixes the policy (requested
    backend, tune mode, configured weight mode, planning batch);
    ``prime()`` resolves every TT layer's plan from the param-spec tree so
    no plan is ever resolved inside a jit trace; ``plan_for_cores`` is the
    trace-time lookup the layer stack calls — a dict hit on the chain
    signature (per layer, per weight dtype), falling back to one memoized
    resolution for signatures that appear only at runtime (e.g. an int8
    twin after ``Model.quantize_params``).

    The object is deliberately opaque to jax: it threads through the model
    stack as a static python value (closure-captured by scan/vmap bodies),
    replacing the stringly-typed ``cfg.tt.backend_spec``.
    """

    def __init__(self, backend: str = "auto", tune: str = "cached",
                 weights: str = "fp", batch: int = PLANNING_BATCH,
                 weight_itemsize: int | None = None,
                 interpret: bool | None = None,
                 cache_path: str | None = None):
        self.backend, self.tune, cfg_weights = compile_spec(
            backend, tune, weights)
        self.weights = cfg_weights or "fp"
        self.batch = batch
        self.weight_itemsize = weight_itemsize
        self.interpret = interpret
        self.cache_path = cache_path
        self._plans: dict = {}

    @classmethod
    def from_tt_config(cls, tt, param_dtype=jnp.float32,
                       batch: int | None = None) -> "PlanBook":
        """Policy from a ``configs.base.TTConfig`` + the model's param
        dtype (which prices fp core residency: bf16 params plan at
        2 B/elem)."""
        backend, tune, weights = tt.plan_policy
        return cls(backend=backend, tune=tune, weights=weights,
                   batch=batch or PLANNING_BATCH,
                   weight_itemsize=jnp.dtype(param_dtype).itemsize)

    def __len__(self) -> int:
        return len(self._plans)

    @property
    def plans(self) -> dict:
        return dict(self._plans)

    def plan_for(self, ns, ms, ranks, *, weights: str | None = None,
                 weight_itemsize: int | None = None,
                 dtype=jnp.float32) -> TTExecutionPlan:
        weights = normalize_weights(weights) or self.weights
        w_item = (1 if weights == "int8"
                  else (weight_itemsize or self.weight_itemsize))
        key = (tuple(ns), tuple(ms), tuple(ranks), weights, w_item,
               jnp.dtype(dtype).name)
        plan = self._plans.get(key)
        if plan is None:
            plan = resolve_plan(
                ns, ms, ranks, batch=self.batch, dtype=dtype,
                backend=self.backend, tune=self.tune, weights=weights,
                weight_itemsize=w_item, interpret=self.interpret,
                cache_path=self.cache_path)
            self._plans[key] = plan
        return plan

    def plan_for_cores(self, cores) -> TTExecutionPlan:
        """Trace-time lookup for a concrete core list (jax arrays or
        tracers — only shapes/dtypes are read).  int8-stored cores force
        the int8 plan regardless of the configured mode."""
        ns, ms, ranks = chain_signature([c.shape for c in cores])
        if cores[0].dtype == jnp.int8:
            weights, w_item = "int8", 1
        else:
            weights = self.weights
            w_item = (1 if weights == "int8"
                      else jnp.dtype(cores[0].dtype).itemsize)
        return self.plan_for(ns, ms, ranks, weights=weights,
                             weight_itemsize=w_item)

    def prime(self, spec_tree) -> int:
        """Resolve a plan for every TT bundle in a param-spec tree
        (models/spec.ParamSpec leaves).  Returns the number of distinct
        plans resolved.  Called at model build; after this, serving
        performs zero plan resolutions."""
        before = len(self._plans)

        def walk(node):
            if not isinstance(node, dict):
                return
            for k, v in node.items():
                if k == "tt" and isinstance(v, dict):
                    d = sum(1 for kk in v if kk.startswith("c"))
                    specs = [v[f"c{t}"] for t in range(d)]
                    ns, ms, ranks = chain_signature(
                        [s.shape for s in specs])
                    w_item = jnp.dtype(specs[0].dtype).itemsize
                    self.plan_for(ns, ms, ranks,
                                  weight_itemsize=w_item)
                elif isinstance(v, dict):
                    walk(v)

        walk(spec_tree)
        return len(self._plans) - before
