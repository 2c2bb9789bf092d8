"""The one traffic generator: reads a mix's parameters from
``bench/traffic/<name>.json`` and turns them, with a run's seed, into the
requests the window offers.

Every seed gets the same work.  Lengths are the quantiles of a lognormal
(median, sigma), clipped, at ``set_size`` evenly spaced probabilities;
prompts and outputs are paired by a fixed permutation drawn from the
mix's own ``shape_seed``.  The stream is made of blocks of ``set_size``
requests, each block the whole set in an order that ``shape_seed`` and
the block's index fix.  An open-loop Poisson mix draws its gaps as
exponential quantiles at ``1 / rate_per_s``, ordered the same way; a
backlog has every request due at the window's start.  The run's seed
picks the token ids (and, elsewhere, the weights), never the sizes or
their order: a window serves a few blocks at most, and an order drawn
from the seed would change what it serves.
"""
from __future__ import annotations

import dataclasses
import json
import math
import os
from statistics import NormalDist

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))


@dataclasses.dataclass(frozen=True)
class Req:
    uid: int
    due_s: float                 # offset from the window's start
    prompt: np.ndarray           # [prompt_len] int32
    max_new: int


def load(name: str) -> dict:
    with open(os.path.join(HERE, "traffic", f"{name}.json")) as f:
        return json.load(f)


def _quantiles(n: int, median: float, sigma: float, lo: int, hi: int
               ) -> np.ndarray:
    z = [NormalDist().inv_cdf((i + 0.5) / n) for i in range(n)]
    xs = [round(math.exp(math.log(median) + sigma * q)) for q in z]
    return np.clip(np.asarray(xs, np.int64), lo, hi)


def length_set(spec: dict) -> tuple[np.ndarray, np.ndarray]:
    """(prompt_lens, output_lens) of one block, in the fixed pairing."""
    n = int(spec["set_size"])
    p, o = spec["prompt"], spec["output"]
    prompts = _quantiles(n, p["median"], p["sigma"], p["min"], p["max"])
    outs = _quantiles(n, o["median"], o["sigma"], o["min"], o["max"])
    outs = outs[np.random.default_rng(spec["shape_seed"]).permutation(n)]
    outs = np.minimum(outs, int(spec["max_total"]) - prompts)
    if (outs < 1).any():
        raise ValueError("a prompt leaves no room for output under "
                         "max_total")
    return prompts, outs


def gap_set(spec: dict) -> np.ndarray:
    """Inter-arrival gaps (s) of one block of an open-loop Poisson mix."""
    n = int(spec["set_size"])
    rate = float(spec["arrival"]["rate_per_s"])
    return np.asarray([-math.log(1.0 - (i + 0.5) / n) / rate
                       for i in range(n)])


def generate(spec: dict, seed: int, seconds: float, vocab: int
             ) -> list[Req]:
    """The requests due in a window of ``seconds``; a backlog offers
    ``backlog_per_s`` × ``seconds`` of them, more than the server can
    finish, all due at once."""
    n = int(spec["set_size"])
    prompts, outs = length_set(spec)
    arrival = spec["arrival"]
    kind = arrival["kind"]
    if kind == "backlog":
        want = math.ceil(float(arrival["backlog_per_s"]) * seconds)
    elif kind == "poisson":
        want = math.ceil(float(arrival["rate_per_s"]) * seconds * 1.5) + n
    else:
        raise ValueError(f"unknown arrival kind {kind!r}")
    blocks = -(-want // n)
    rng = np.random.default_rng([int(seed), 0x7AFF1C])
    gaps = gap_set(spec) if kind == "poisson" else None
    out: list[Req] = []
    t = 0.0
    for b in range(blocks):
        shape = np.random.default_rng([int(spec["shape_seed"]), b])
        order = shape.permutation(n)
        gorder = shape.permutation(n)
        for j in range(n):
            k = order[j]
            if gaps is not None:
                t += gaps[gorder[j]]
                if t > seconds:
                    return out
            toks = rng.integers(0, vocab, int(prompts[k]), dtype=np.int32)
            out.append(Req(uid=len(out), due_s=t if gaps is not None else 0.0,
                           prompt=toks, max_new=int(outs[k])))
    return out
