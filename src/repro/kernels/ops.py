"""Public jit'd entry points for TT layer application.

``tt_forward(cores, x, bias, plan=...)`` EXECUTES a resolved
:class:`kernels.plan.TTExecutionPlan` (DESIGN.md §10): the plan already
carries the concrete backend, the fused batch tile or per-step block
plans, the weight mode and the VMEM fit verdict, so execution is a pure
dispatch — no string parsing, no fit heuristics, no autotune lookups.

Backends a plan can resolve to:

  'xla'           — paper-faithful einsum chain lowered by XLA
                    (the "IREE-class compiler" baseline of Figs. 12–14)
  'pallas_step'   — chain with one blocked Pallas kernel per einsum step
                    (every intermediate round-trips through HBM)
  'pallas_fused2' — single fused kernel for d=2 plans (paper §6.4 deploys
                    length-2 solutions; this is the d=2 fast path)
  'pallas_fused'  — single fused kernel for ANY depth d ≥ 2: all packed
                    matmuls + relayouts in VMEM, zero HBM intermediates

Without ``plan=`` the call goes through the DEPRECATION SHIM: the
``backend`` string (optionally a legacy ``"<backend>[:<tune>][:<weights>]"``
spec, e.g. ``"auto:measure:int8"``) is compiled into a plan by the
memoized resolver ``kernels.plan.resolve_plan`` at the call's batch size.
The behavior is identical to the plan path — ``'auto'`` routes fused2 at
d=2, the fused chain when the dtype-aware VMEM fit admits it, per-step
otherwise — but model code should resolve plans ONCE at build time
(``models``' PlanBook) instead of per call.

``weights='int8'`` (DESIGN.md §8) keeps the packed cores int8 all the way
into VMEM: the Pallas backends dispatch to the ``*_int8_pallas`` kernel
variants (in-kernel dequant, fp32 accumulation), and the fit verdict is
priced at 1-byte weight residency — chains that are step-fallback in fp32
can fuse under int8.  Cores may arrive either as float (quantized on the
fly, symmetric per-core scales) or pre-quantized int8 with an explicit
``scales`` sequence (models/layers quantized storage).  The fp path prices
weight residency at the cores' own itemsize (bf16 cores count 2 bytes).
"""
from __future__ import annotations

import functools
from typing import Sequence

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core.packing import pack_core
from repro.core.quant import dequantize_cores, quantize_cores
from repro.core.tt import tt_apply
from . import autotune
from . import plan as planner
from .plan import BACKENDS, WEIGHT_ALIASES, TTExecutionPlan  # noqa: F401
from .tt_contract import (tt_fused2_int8_pallas, tt_fused2_pallas,
                          tt_fused_chain_int8_pallas, tt_fused_chain_pallas,
                          tt_step_int8_pallas, tt_step_pallas)

# legacy alias (plan.WEIGHT_ALIASES is canonical)
_WEIGHT_ALIASES = WEIGHT_ALIASES


def chain_dims(cores: Sequence[jax.Array]
               ) -> tuple[tuple[int, ...], tuple[int, ...], tuple[int, ...]]:
    """(ns, ms, ranks) signature of a core list (the TTPlan triple)."""
    ns = tuple(int(G.shape[1]) for G in cores)
    ms = tuple(int(G.shape[2]) for G in cores)
    ranks = tuple(int(G.shape[0]) for G in cores) + (int(cores[-1].shape[3]),)
    return ns, ms, ranks


def parse_backend_spec(backend: str, tune: str | None = None,
                       weights: str | None = None
                       ) -> tuple[str, str | None, str | None]:
    """Split ``"<backend>[:<tune>][:<weights>]"`` into its parts
    (deprecation shim — see ``kernels.plan.compile_spec``, which this
    delegates to).  Malformed specs (unknown or empty tokens, duplicate
    token classes) raise a ValueError naming every valid token."""
    return planner.compile_spec(backend, tune, weights)


def _chain_with_step_kernel(cores: Sequence[jax.Array], x: jax.Array,
                            interpret: bool | None,
                            step_plans: Sequence,
                            scales: Sequence[jax.Array] | None = None
                            ) -> jax.Array:
    """Paper chain where each einsum runs in the blocked Pallas kernel.
    Layout between steps follows the paper exactly: reshapes only.
    ``step_plans`` are the plan's per-step BlockPlans in execution order
    (core d first); the kernel clamps tiles to the runtime extents, so a
    plan resolved at the nominal planning batch serves any batch.
    With ``scales`` the cores are int8-resident (one launch of the int8
    step kernel per core)."""
    B = x.shape[0]
    state = x.reshape(-1)
    b = state.shape[0]
    for j, t in enumerate(range(len(cores) - 1, -1, -1)):
        G = cores[t]
        r0, nt, mt, r1 = G.shape
        if b % (nt * r1) != 0:
            raise ValueError(
                f"TT chain/input mismatch at step {t}: state of {b} "
                f"elements is not divisible by n_{t}·r_{t} = {nt}·{r1} "
                f"(core shape {tuple(G.shape)}) — the core list is "
                f"inconsistent with x.shape[-1] or the inter-core ranks")
        bt = b // (nt * r1)
        st = state.reshape(bt, nt, r1)
        bplan = step_plans[j]
        if scales is not None:
            out = tt_step_int8_pallas(G, scales[t], st, bplan,
                                      interpret=interpret)
        else:
            out = tt_step_pallas(G, st, bplan, interpret=interpret)
        state = out.reshape(-1).astype(x.dtype)   # [m, b, r0] flattened
        b = state.shape[0]
    M = b // B
    return state.reshape(M, B).T


def _run_pallas(plan: TTExecutionPlan, x2: jax.Array,
                cores: list[jax.Array], scales: list[jax.Array] | None,
                interpret: bool | None) -> jax.Array:
    """Dispatch a Pallas plan; ``scales`` selects the int8 kernels."""
    ns, ms, ranks = plan.ns, plan.ms, plan.ranks
    if plan.backend == "pallas_fused2":
        dims2 = (ns[0], ns[1], ms[0], ms[1], ranks[1])
        p2, p1 = pack_core(cores[1]), pack_core(cores[0])
        if scales is not None:
            return tt_fused2_int8_pallas(x2, p2, p1, [scales[1], scales[0]],
                                         dims2, block_b=plan.block_b,
                                         interpret=interpret)
        return tt_fused2_pallas(x2, p2, p1, dims=dims2,
                                block_b=plan.block_b, interpret=interpret)
    if plan.backend == "pallas_fused":
        if plan.block_b is None:
            raise ValueError(
                "malformed plan: pallas_fused without a batch tile — "
                "re-resolve with kernels.plan.plan_tt_forward")
        packed = [pack_core(G) for G in reversed(cores)]
        if scales is not None:
            return tt_fused_chain_int8_pallas(
                x2, packed, list(reversed(scales)), (ns, ms, ranks),
                block_b=plan.block_b, interpret=interpret)
        return tt_fused_chain_pallas(x2, packed, (ns, ms, ranks),
                                     block_b=plan.block_b,
                                     interpret=interpret)
    if plan.step_plans is None or len(plan.step_plans) != plan.d:
        raise ValueError(
            "malformed plan: pallas_step without per-step block plans "
            "— re-resolve with kernels.plan.plan_tt_forward")
    return _chain_with_step_kernel(cores, x2, interpret, plan.step_plans,
                                   scales=scales)


def _on_each_device(fn, *args):
    """``fn(*args)``; under a multi-device mesh in context
    (``jax.set_mesh``, as the serving scheduler sets it) the call runs
    once per device on replicated operands instead.  GSPMD cannot
    partition a Mosaic kernel, so the kernel must see whole operands; the
    TT cores are replicated anyway, and each device computes the layer for
    the full batch.  Inside a ``shard_map`` whose axes are all manual the
    call already runs per device."""
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty or mesh.size == 1 or \
            set(mesh.manual_axes) == set(mesh.axis_names):
        return fn(*args)
    return jax.shard_map(fn, mesh=mesh, in_specs=P(), out_specs=P(),
                         check_vma=False)(*args)


def tt_forward(cores: Sequence[jax.Array], x: jax.Array,
               bias: jax.Array | None = None, backend: str = "auto",
               interpret: bool | None = None,
               tune: str | None = None,
               weights: str | None = None,
               scales: Sequence[jax.Array] | jax.Array | None = None,
               plan: TTExecutionPlan | None = None) -> jax.Array:
    """Apply a TT layer to ``x [..., N]`` → ``[..., M]``.

    ``plan=`` executes a pre-resolved :class:`TTExecutionPlan` directly —
    the model stack resolves each layer's plan once at build time and
    passes it here, so tracing performs zero planning.  Without a plan the
    call compiles one from the legacy arguments: ``backend`` may embed the
    tune and/or weight mode as ``"<backend>:<tune>:<weights>"`` (a
    deprecated spelling); explicit ``tune=`` / ``weights=`` arguments win
    over the suffix.  ``weights='int8'`` runs the int8-resident kernel
    path: float ``cores`` are quantized on the fly (symmetric per-core
    scales), pre-quantized int8 ``cores`` require the matching ``scales``.
    Int8 cores passed without a weight mode imply ``weights='int8'``.
    """
    ns, ms, ranks = chain_dims(cores)
    Nc = 1
    for n in ns:
        Nc *= n
    if Nc != x.shape[-1]:
        raise ValueError(
            f"TT core list with input modes {ns} (prod={Nc}) does not "
            f"match x.shape[-1]={x.shape[-1]}")
    for t in range(len(cores) - 1):
        if cores[t].shape[3] != cores[t + 1].shape[0]:
            raise ValueError(
                f"TT rank mismatch between cores {t} and {t + 1}: "
                f"r={cores[t].shape[3]} vs r={cores[t + 1].shape[0]}")

    if plan is not None:
        if (plan.ns, plan.ms, plan.ranks) != (ns, ms, ranks):
            raise ValueError(
                f"plan/chain mismatch: plan is for n={plan.ns} m={plan.ms} "
                f"r={plan.ranks}, cores are n={ns} m={ms} r={ranks}")
        # the plan is authoritative: conflicting legacy arguments are an
        # error, never silently dropped
        if backend not in ("auto", plan.requested, plan.backend):
            raise ValueError(
                f"backend={backend!r} conflicts with the plan "
                f"({plan.requested!r} -> {plan.backend!r}) — drop the "
                f"argument or re-plan")
        if tune is not None and tune != plan.tune:
            raise ValueError(
                f"tune={tune!r} conflicts with the plan's tune mode "
                f"{plan.tune!r} — drop the argument or re-plan")
        if weights is not None and \
                planner.normalize_weights(weights) != plan.weights:
            raise ValueError(
                f"weights={weights!r} conflicts with the plan's weight "
                f"mode {plan.weights!r} — drop the argument or re-plan")
        weights = plan.weights
    else:
        backend, tune, weights = planner.compile_spec(
            backend, tune, weights, warn=True)
        tune = tune or "cached"
        if tune not in autotune.TUNE_MODES:
            raise ValueError(
                f"unknown tune mode {tune!r}: expected one of "
                f"{autotune.TUNE_MODES}")
        if weights is None and cores[0].dtype == jnp.int8:
            weights = "int8"
        weights = weights or "fp"

    # --------------------------------------------------------- core storage
    qcores: list[jax.Array] | None = None
    qscales: list[jax.Array] | None = None
    if weights == "int8":
        if cores[0].dtype == jnp.int8:
            if scales is None:
                raise ValueError(
                    "pre-quantized int8 cores require the matching per-core "
                    "scales (core.quant.quantize_cores)")
            qcores, qscales = list(cores), list(scales)
        else:
            if scales is not None:
                raise ValueError(
                    "scales are only accepted with pre-quantized int8 "
                    "cores; float cores are quantized on the fly with "
                    "their own scales — externally calibrated scales "
                    "would be silently discarded here")
            qcores, qscales = quantize_cores(cores)
        w_itemsize = 1
    elif cores[0].dtype == jnp.int8:
        raise ValueError(
            "int8 cores cannot run the float path — pass weights='int8' "
            "with their scales")
    else:
        if scales is not None:
            raise ValueError(
                "scales were passed but weights is not 'int8' — they "
                "would be silently ignored")
        w_itemsize = jnp.dtype(cores[0].dtype).itemsize

    lead, N = x.shape[:-1], x.shape[-1]
    x2 = x.reshape(-1, N)
    B = x2.shape[0]

    if plan is None:
        plan = planner.resolve_plan(
            ns, ms, ranks, batch=B, dtype=x.dtype, backend=backend,
            tune=tune, weights=weights, weight_itemsize=w_itemsize,
            interpret=interpret)

    # ------------------------------------------------------------ execution
    if plan.backend == "xla":
        if weights == "int8":
            y = tt_apply(dequantize_cores(qcores, qscales, jnp.float32),
                         x2.astype(jnp.float32))
        else:
            y = tt_apply(cores, x2)
    elif plan.backend in ("pallas_step", "pallas_fused2", "pallas_fused"):
        y = _on_each_device(
            functools.partial(_run_pallas, plan, interpret=interpret), x2,
            qcores if weights == "int8" else list(cores), qscales)
    else:
        raise ValueError(
            f"plan resolved to unknown backend {plan.backend!r}")

    if bias is not None:
        y = y + bias
    return y.reshape(lead + (y.shape[-1],)).astype(x.dtype)
