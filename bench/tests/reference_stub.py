"""A stand-in reference module for the CPU tests of the harness's seam:
a configuration file that gives ``"reference": "stub"`` and is loaded
from this directory gets its layer equations from here.

It checks plumbing only and must never back a cell of ``BENCHMARK.json``:
its ``Reference`` is the program's own forward pass (``Model`` in float32
over every position), not an independent one, so it cannot catch a fault
of the program; and its counts are a crude tally of the weights (two
operations per weight, every expert counted, no attention term), not the
model's operations.  It serves the DeepSeek-V2 family's layer tree (MLA
attention, a dense first layer, routed and shared experts) so that a tree
other than one uniform stack of dense blocks runs through ``bench/run.py``.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from bench import cost

BUCKET = 64


def published(c) -> dict:
    """DeepSeek-V2's published keys, as the program runs them."""
    return {"num_hidden_layers": c.num_layers, "hidden_size": c.d_model,
            "num_attention_heads": c.num_heads,
            "num_key_value_heads": c.num_kv_heads,
            "intermediate_size": c.moe.first_dense_ff,
            "moe_intermediate_size": c.moe.expert_ff,
            "n_routed_experts": c.moe.num_experts,
            "num_experts_per_tok": c.moe.top_k,
            "n_shared_experts": c.moe.num_shared,
            "first_k_dense_replace": int(c.moe.first_dense_ff > 0),
            "kv_lora_rank": c.mla.kv_lora,
            "qk_rope_head_dim": c.mla.rope_head_dim,
            "qk_nope_head_dim": c.mla.nope_head_dim,
            "v_head_dim": c.mla.v_head_dim, "vocab_size": c.vocab_size,
            "rope_theta": c.rope_theta, "rms_norm_eps": c.norm_eps,
            "tie_word_embeddings": c.tie_embeddings}


def _chains(node, out):
    """(copies, (ns, ms, ranks)) of every TT matrix under ``node``; a
    matrix stacked over layers and experts counts once per copy."""
    if "tt" in node:
        tt = node["tt"]
        shapes = [tt[f"c{t}"].shape for t in range(len(tt))]
        cores = [s[-4:] for s in shapes]
        out.append((math.prod(shapes[0][:-4]),
                    (tuple(c[1] for c in cores), tuple(c[2] for c in cores),
                     tuple(c[0] for c in cores) + (cores[-1][3],))))
        return out
    for v in node.values():
        if isinstance(v, dict):
            _chains(v, out)
    return out


class Dims:
    def __init__(self, params):
        stack = {k: v for k, v in params.items() if k.startswith("g")}
        self.chains = _chains(stack, [])
        flat, _ = jax.tree_util.tree_flatten_with_path(stack)
        self.dense = sum(math.prod(leaf.shape) for path, leaf in flat
                         if getattr(path[-1], "key", None) in ("w", "router"))
        self.d_model, self.vocab = params["lm_head"]["w"].shape
        # MLA caches the latent and the rope key of every layer
        self.kv_values = sum(
            g["b0"]["attn"]["kv_down"]["w"].shape[0]
            * g["b0"]["attn"]["kv_down"]["w"].shape[-1]
            for g in stack.values())

    def token_flops(self, ctx: int) -> int:
        return 2 * self.dense + sum(
            n * cost.tt_flops_per_row(*c) for n, c in self.chains)

    def prompt_flops(self, prompt_len: int) -> int:
        return prompt_len * self.token_flops(0)

    def head_flops(self) -> int:
        return 2 * self.d_model * self.vocab

    def tt_work(self, rows: int, calls_per_matrix: int) -> tuple[int, int]:
        flops = sum(n * rows * cost.tt_flops_per_row(*c)
                    for n, c in self.chains)
        byts = sum(n * (cost.tt_call_bytes(*c, rows=rows)
                        + (calls_per_matrix - 1)
                        * cost.tt_call_bytes(*c, rows=0))
                   for n, c in self.chains)
        return flops, byts

    def kv_bytes_per_token(self) -> int:
        return self.kv_values * cost.KV_BYTES


def dims(cfg: dict, params) -> Dims:
    return Dims(params)


def _forward(model, params, tokens):
    from repro.models.transformer import group_fwd
    with jax.default_matmul_precision("highest"):
        x, _ = model._embed_inputs(params, {"tokens": tokens})
        pos = jnp.arange(tokens.shape[1])[None]
        for gi, g in enumerate(model.groups):
            x, _ = group_fwd(params[f"g{gi}"], model.cfg, g, x, pos,
                             want_cache=False, plans=model.plan_book)
        return model._logits(params, x)[0]


class Reference:
    """The program's own float32 logits at every position (causal, so the
    padding to a bucket leaves the positions that count alone)."""

    def __init__(self, params: dict, cfg: dict, precision: str = "f32"):
        if precision != "f32":
            raise ValueError("the stub has no control")
        from bench.run import build_model
        f32 = dict(cfg, serving=dict(cfg["serving"], param_dtype="float32"))
        self.model = build_model(f32)
        self.p = jax.tree.map(lambda a: a.astype(jnp.float32), params)
        self.forward = jax.jit(functools.partial(_forward, self.model))

    def logits(self, tokens: np.ndarray, first: int) -> np.ndarray:
        S = len(tokens)
        toks = np.zeros(-(-S // BUCKET) * BUCKET, np.int32)
        toks[:S] = tokens
        out = self.forward(self.p, jnp.asarray(toks)[None])
        return np.asarray(out[first:S], np.float32)
