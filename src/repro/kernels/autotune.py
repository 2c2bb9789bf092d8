"""Empirical block-plan autotuner for the TT Pallas kernels (DESIGN.md §2).

The paper picks block shapes with a purely analytical load/store model
(§4.3.4–4.3.5).  The model ranks candidates well but its constants are
guesses; this module closes the loop the way production autotuners do:

  1. enumerate a handful of candidates FROM the analytical model
     (``core.packing``: top-k ``select_blocks_candidates`` for the per-step
     kernel, the VMEM-fit tile ± one octave for the fused kernels),
  2. time each candidate on the device actually executing (interpret-mode
     timing on CPU containers — relative ranking is what transfers),
  3. persist the winner in a JSON cache keyed by
     (kernel kind, shape, ranks, dtype, weight dtype, jax backend)
     so every later call — including in other processes — is a dict lookup.

The weight dtype is part of the key because it changes both the feasible
set (int8-resident cores shrink the VMEM residency term 4×, DESIGN.md §8)
and the measured kernel (the ``*_int8_pallas`` variants are timed when
``weights='int8'``).  The cache file is written atomically (temp file +
``os.replace``) so concurrent benchmark runs never leave a truncated JSON.

Tune modes (threaded through ``kernels.ops.tt_forward``):

  'off'      — analytical plan only, never read or write the cache
  'cached'   — use a persisted winner if present, else analytical (no
               timing; the default — safe inside jit traces and prod paths)
  'measure'  — time candidates on miss and persist the winner

The cache file defaults to ``~/.cache/repro/autotune.json`` and is
overridden by ``$REPRO_AUTOTUNE_CACHE`` or an explicit ``cache_path=``.
"""
from __future__ import annotations

import dataclasses
import json
import os
import tempfile
import time
from typing import Callable, Sequence

import jax
import jax.numpy as jnp

from repro.core import hw
from repro.core.flops import prod
from repro.core.packing import (BlockPlan, fused_chain_batch_tile,
                                select_blocks_candidates)
from .tt_contract import (KERNEL_VERSION, tt_fused2_int8_pallas,
                          tt_fused2_pallas, tt_fused_chain_int8_pallas,
                          tt_fused_chain_pallas, tt_step_int8_pallas,
                          tt_step_pallas)

TUNE_MODES = ("off", "cached", "measure")
WEIGHT_MODES = ("fp", "int8")       # resident dtype class of the cores

# Versioned cache schema, tied to the kernel generation: every entry is
# stamped ``"schema": CACHE_SCHEMA`` on write, and load() silently drops
# entries from other schemas (or malformed/unknown formats) — an old
# cache file survives a kernel migration instead of crashing it or, worse,
# serving tiles measured against different kernel semantics.
CACHE_SCHEMA = KERNEL_VERSION

# number of candidate timings actually executed (tests assert cache hits
# run zero of these)
N_MEASUREMENTS = 0


def _default_cache_path() -> str:
    return os.environ.get(
        "REPRO_AUTOTUNE_CACHE",
        os.path.join(os.path.expanduser("~"), ".cache", "repro",
                     "autotune.json"))


@dataclasses.dataclass
class AutotuneCache:
    """JSON-file-backed plan cache with an in-memory mirror."""
    path: str
    entries: dict

    @classmethod
    def load(cls, path: str) -> "AutotuneCache":
        entries = {}
        if os.path.exists(path):
            try:
                with open(path) as f:
                    raw = json.load(f)
            except (json.JSONDecodeError, OSError):
                raw = {}
            if isinstance(raw, dict):
                # keep only entries of THIS schema; stale generations and
                # unknown formats are ignored, never an error
                entries = {k: v for k, v in raw.items()
                           if isinstance(v, dict)
                           and v.get("schema") == CACHE_SCHEMA}
        return cls(path, entries)

    def get(self, key: str) -> dict | None:
        return self.entries.get(key)

    def put(self, key: str, value: dict) -> None:
        """Insert + persist.  The write is atomic (temp file in the same
        directory + ``os.replace``): a reader — or a concurrent benchmark
        process — can never observe a truncated ``autotune_cache.json``,
        only the old or the new complete file.  Every entry is stamped
        with the current ``CACHE_SCHEMA``."""
        self.entries[key] = dict(value, schema=CACHE_SCHEMA)
        dirname = os.path.dirname(self.path) or "."
        os.makedirs(dirname, exist_ok=True)
        fd, tmp = tempfile.mkstemp(dir=dirname, suffix=".tmp",
                                   prefix=os.path.basename(self.path) + ".")
        try:
            with os.fdopen(fd, "w") as f:
                json.dump(self.entries, f, indent=1, sort_keys=True)
            os.replace(tmp, self.path)
        except BaseException:
            try:
                os.unlink(tmp)
            except OSError:
                pass
            raise


_CACHES: dict[str, AutotuneCache] = {}


def get_cache(cache_path: str | None = None) -> AutotuneCache:
    path = cache_path or _default_cache_path()
    if path not in _CACHES:
        _CACHES[path] = AutotuneCache.load(path)
    return _CACHES[path]


def clear_memory_caches() -> None:
    """Drop in-memory mirrors (tests use this to prove disk round-trips)."""
    _CACHES.clear()


def plan_key(kind: str, ns: Sequence[int], ms: Sequence[int],
             ranks: Sequence[int], dtype, B: int,
             weights: str = "fp") -> str:
    return "|".join([
        kind,
        "n" + "x".join(map(str, ns)),
        "m" + "x".join(map(str, ms)),
        "r" + "x".join(map(str, ranks)),
        jnp.dtype(dtype).name,
        f"B{B}",
        f"w{weights}",
        jax.default_backend(),
    ])


# ---------------------------------------------------------------------------
# Timing
# ---------------------------------------------------------------------------

def _median_time(fn: Callable[[], jax.Array], warmup: int = 1,
                 iters: int = 3) -> float:
    global N_MEASUREMENTS
    N_MEASUREMENTS += 1
    for _ in range(warmup):
        jax.block_until_ready(fn())
    ts = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        ts.append(time.perf_counter() - t0)
    ts.sort()
    return ts[len(ts) // 2]


def _pow2_neighbors(v: int, B: int, lo: int = hw.LANES,
                    hi: int = 1024) -> list[int]:
    """The analytical pick and two octaves below it, clipped to
    [lo, min(hi, B-ish)]; ``lo`` is one lane width, the smallest tile the
    fused kernels run.  Never above ``v``: for the fused kernels ``v``
    is the LARGEST VMEM-feasible tile, so any larger candidate would win
    interpret-mode timing (no VMEM there) and persist a plan that busts
    VMEM on real hardware."""
    cap = min(hi, v, max(lo, 1 << (max(B - 1, 1)).bit_length()))
    cands = {max(lo, min(c, cap)) for c in (v // 4, v // 2, v)}
    return sorted(cands)


# ---------------------------------------------------------------------------
# Fused-kernel batch-tile tuning (d=2 and d>=3)
# ---------------------------------------------------------------------------

def _weight_itemsize(weights: str, weight_itemsize: int | None) -> int | None:
    if weights not in WEIGHT_MODES:
        raise ValueError(
            f"weights must be one of {WEIGHT_MODES}, got {weights!r}")
    return 1 if weights == "int8" else weight_itemsize


def _weight_tag(weights: str, w_item: int | None, itemsize: int) -> str:
    """Cache-key tag for the resident weight dtype.  fp cores whose
    itemsize differs from the activation itemsize (bf16 cores under fp32
    accumulation) get their byte width in the tag — a tile measured under
    2-byte residency must not be served to a 4-byte-core model with the
    same shape signature."""
    if weights == "int8":
        return "int8"
    eff = itemsize if w_item is None else w_item
    return "fp" if eff == itemsize else f"fp{eff}"


def _fp_weight_dtype(w_item: int | None, itemsize: int):
    """Stand-in core dtype for fp measure-mode timing, matched to the
    weight itemsize actually being ranked."""
    eff = itemsize if w_item is None else w_item
    return jnp.bfloat16 if eff == 2 else jnp.float32


def fused_tile(ns: tuple[int, ...], ms: tuple[int, ...],
               ranks: tuple[int, ...], dtype, B: int,
               mode: str = "cached", interpret: bool | None = None,
               cache_path: str | None = None,
               weights: str = "fp",
               weight_itemsize: int | None = None) -> int | None:
    """Batch tile for the fused chain (see :func:`fused_tile_ex`)."""
    return fused_tile_ex(ns, ms, ranks, dtype, B, mode=mode,
                         interpret=interpret, cache_path=cache_path,
                         weights=weights,
                         weight_itemsize=weight_itemsize)[0]


def fused_tile_ex(ns: tuple[int, ...], ms: tuple[int, ...],
                  ranks: tuple[int, ...], dtype, B: int,
                  mode: str = "cached", interpret: bool | None = None,
                  cache_path: str | None = None,
                  weights: str = "fp",
                  weight_itemsize: int | None = None
                  ) -> tuple[int | None, str]:
    """Batch tile for the fused chain (any d ≥ 2), plus its provenance
    ('analytic' | 'cached' | 'measured') — the plan resolver records the
    provenance in the ``TTExecutionPlan``.  The tile is None when the
    chain is not VMEM-resident at any tile (caller falls back to per-step).

    ``weights='int8'`` prices the resident cores at 1 byte/elem in the
    analytic fit AND times the ``*_int8_pallas`` kernels in measure mode —
    chains that are step-fallback in fp32 can come back fused here.
    ``weight_itemsize`` overrides the fp weight pricing (e.g. 2 for bf16
    cores under fp32 activations)."""
    if mode not in TUNE_MODES:
        raise ValueError(f"tune mode must be one of {TUNE_MODES}: {mode!r}")
    itemsize = max(jnp.dtype(dtype).itemsize, 4)
    w_item = _weight_itemsize(weights, weight_itemsize)
    analytic = fused_chain_batch_tile(ns, ms, ranks, itemsize=itemsize,
                                      weight_itemsize=w_item)
    if analytic is None:
        return None, "analytic"
    if mode == "off":
        return analytic, "analytic"

    key = plan_key("fused_chain", ns, ms, ranks, dtype, B,
                   _weight_tag(weights, w_item, itemsize))
    cache = get_cache(cache_path)
    hit = cache.get(key)
    if hit is not None:
        return int(hit["block_b"]), "cached"
    if mode == "cached":
        return analytic, "analytic"

    # mode == 'measure': time the analytic pick ± one octave
    d = len(ns)
    keys = jax.random.split(jax.random.PRNGKey(0), d + 1)
    x = jax.random.normal(keys[0], (B, prod(ns)), jnp.float32).astype(dtype)
    pshapes = [(ns[t] * ranks[t + 1], ms[t] * ranks[t])
               for t in range(d - 1, -1, -1)]
    if weights == "int8":
        packed = [jax.random.randint(keys[1 + j], shp, -127, 128, jnp.int8)
                  for j, shp in enumerate(pshapes)]
        scales = [jnp.asarray(1.0, jnp.float32)] * d
    else:
        wdtype = _fp_weight_dtype(w_item, itemsize)
        packed = [jax.random.normal(keys[1 + j], shp, jnp.float32
                                    ).astype(wdtype)
                  for j, shp in enumerate(pshapes)]
        scales = None
    dims = (tuple(ns), tuple(ms), tuple(ranks))
    timed: dict[str, float] = {}
    for bb in _pow2_neighbors(analytic, B):
        if d == 2:
            n1, n2 = ns
            m1, m2 = ms
            d2 = (n1, n2, m1, m2, ranks[1])
            if weights == "int8":
                fn = lambda bb=bb: tt_fused2_int8_pallas(
                    x, packed[0], packed[1], scales, d2,
                    block_b=bb, interpret=interpret)
            else:
                fn = lambda bb=bb: tt_fused2_pallas(
                    x, packed[0], packed[1], d2,
                    block_b=bb, interpret=interpret)
        elif weights == "int8":
            fn = lambda bb=bb: tt_fused_chain_int8_pallas(
                x, packed, scales, dims, block_b=bb, interpret=interpret)
        else:
            fn = lambda bb=bb: tt_fused_chain_pallas(
                x, packed, dims, block_b=bb, interpret=interpret)
        timed[str(bb)] = _median_time(fn)
    best = int(min(timed, key=timed.get))
    cache.put(key, {"block_b": best, "time_s": timed[str(best)],
                    "source": "measured", "analytic_block_b": analytic,
                    "weights": weights, "candidates": timed})
    return best, "measured"


# ---------------------------------------------------------------------------
# Per-step BlockPlan tuning
# ---------------------------------------------------------------------------

def step_plan(mt: int, bt: int, nt: int, rt: int, rt_1: int, dtype,
              mode: str = "cached", interpret: bool | None = None,
              cache_path: str | None = None, k: int = 4,
              weights: str = "fp",
              weight_itemsize: int | None = None) -> BlockPlan:
    """Blocked-step plan (see :func:`step_plan_ex`)."""
    return step_plan_ex(mt, bt, nt, rt, rt_1, dtype, mode=mode,
                        interpret=interpret, cache_path=cache_path, k=k,
                        weights=weights,
                        weight_itemsize=weight_itemsize)[0]


def step_plan_ex(mt: int, bt: int, nt: int, rt: int, rt_1: int, dtype,
                 mode: str = "cached", interpret: bool | None = None,
                 cache_path: str | None = None, k: int = 4,
                 weights: str = "fp",
                 weight_itemsize: int | None = None
                 ) -> tuple[BlockPlan, str]:
    """Blocked-step plan plus its provenance ('analytic' | 'cached' |
    'measured'): analytical argmin, or the measured winner among the
    analytical top-k (the paper's §4.3.4 selection, but benchmarked).
    ``weights='int8'`` prices the G tile at 1 byte/elem and times the
    int8 step kernel."""
    if mode not in TUNE_MODES:
        raise ValueError(f"tune mode must be one of {TUNE_MODES}: {mode!r}")
    itemsize = max(jnp.dtype(dtype).itemsize, 4)
    w_item = _weight_itemsize(weights, weight_itemsize)
    cands = select_blocks_candidates(mt, bt, nt, rt, rt_1, itemsize, k=k,
                                     weight_itemsize=w_item)
    if mode == "off":
        return cands[0], "analytic"

    key = plan_key("step", (nt,), (mt,), (rt_1, rt), dtype, bt,
                   _weight_tag(weights, w_item, itemsize))
    cache = get_cache(cache_path)
    hit = cache.get(key)
    if hit is not None:
        return BlockPlan(int(hit["bm"]), int(hit["bb"]), int(hit["bn"]),
                         int(hit.get("traffic_bytes", 0)),
                         int(hit.get("vmem_bytes", 0))), "cached"
    if mode == "cached" or len(cands) == 1:
        return cands[0], "analytic"

    k1, k2 = jax.random.split(jax.random.PRNGKey(0))
    X = jax.random.normal(k2, (bt, nt, rt), jnp.float32).astype(dtype)
    if weights == "int8":
        G = jax.random.randint(k1, (rt_1, nt, mt, rt), -127, 128, jnp.int8)
        one = jnp.asarray(1.0, jnp.float32)
        timed = [(_median_time(lambda p=p: tt_step_int8_pallas(
            G, one, X, p, interpret=interpret)), p) for p in cands]
    else:
        G = jax.random.normal(k1, (rt_1, nt, mt, rt), jnp.float32
                              ).astype(_fp_weight_dtype(w_item, itemsize))
        timed = [(_median_time(lambda p=p: tt_step_pallas(
            G, X, p, interpret=interpret)), p) for p in cands]
    t_best, best = min(timed, key=lambda tp: tp[0])
    cache.put(key, {"bm": best.bm, "bb": best.bb, "bn": best.bn,
                    "traffic_bytes": best.traffic_bytes,
                    "vmem_bytes": best.vmem_bytes,
                    "time_s": t_best, "source": "measured",
                    "weights": weights,
                    "candidates": {f"{p.bm}x{p.bb}x{p.bn}": t
                                   for t, p in timed}})
    return best, "measured"
