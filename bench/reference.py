"""Plain float32 forward of a dense decoder with MHA or GQA attention and
gated FFNs whose matrices are dense or tensor-train (TT) factorised: the
yardstick the served tokens are compared with.

This is the dense reference module.  A configuration file picks its
module by ``"reference": "<name>"`` (``bench/reference_<name>.py``), or
this one without the key; ``bench/run.py`` loads it by file path and
reads everything that depends on the layer equations from it:

- ``Reference(params, cfg, precision)``: ``.logits(tokens, first)``, the
  float32 logits; ``precision="int8"`` is the control.
- ``published(model_cfg)``: {published key: the value the program runs};
  the run is refused where the file lacks a key or gives another value.
- ``dims(cfg, params)``: the counts the window and the readers use:
  ``token_flops(ctx)``, ``prompt_flops(P)``, ``head_flops()``,
  ``tt_work(rows, calls)``, ``kv_bytes_per_token()``.
- ``LAWS`` (optional): {leaf name: fn(shape) -> (law, std)} for leaves
  ``bench/weights.py`` has no law of its own for.  This module has none.

It imports nothing of the program.  It reads the weights the benchmark
drew (``bench/weights.py``) by their names in the parameter tree, and the
shape constants from the configuration file.  The mathematics:

  x_0 = E[tok];  per layer l:
    h = rmsnorm(x) ⊙ g1
    q, k, v = h W_q, h W_k, h W_v, split into heads of ``head_dim``;
    q, k rotated by RoPE (half-split pairs, base ``rope_theta``);
    query head j reads key/value head j // (heads / kv_heads);
    a = softmax(q kᵀ / sqrt(head_dim), causal) v;  x += a W_o
    h = rmsnorm(x) ⊙ g2;  x += D(silu(G(h)) ⊙ U(h))
  logits = (rmsnorm(x) ⊙ g_f) W_lm

A TT matrix with cores C_t [r_{t-1}, n_t, m_t, r_t] maps x [N = n_1…n_d,
n_1 slowest] to y [M = m_1…m_d, m_1 slowest]:
  y[m_1…m_d] = Σ x[n_1…n_d] Π_t C_t[r_{t-1}, n_t, m_t, r_t].

Every matmul runs at ``highest`` precision.  It is computed one layer at
a time (a jitted layer applied L times), over one sequence padded to a
bucket of ``BUCKET`` positions: causal attention keeps the padding out of
the positions that count.

``precision="int8"`` is the control: every weight matrix (and each TT
core) symmetrically quantised to int8, per output column (per core), and
every matmul input per row, then computed as above.  It is the step below
the bf16 the configuration serves in.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench import cost

BUCKET = 512


def published(c) -> dict:
    """The published keys of a dense decoder, as the program runs them."""
    return {"num_hidden_layers": c.num_layers, "hidden_size": c.d_model,
            "num_attention_heads": c.num_heads,
            "num_key_value_heads": c.num_kv_heads, "head_dim": c.head_dim,
            "intermediate_size": c.d_ff, "vocab_size": c.vocab_size,
            "rope_theta": c.rope_theta, "rms_norm_eps": c.norm_eps,
            "tie_word_embeddings": c.tie_embeddings}


def ffn_chains(params) -> list:
    """(ns, ms, ranks) of each FFN matrix, from the cores' shapes."""
    out = []
    for name in ("gate", "up", "down"):
        tt = params["g0"]["b0"]["ffn"][name].get("tt")
        if tt is None:
            continue
        cores = [tt[f"c{t}"].shape[-4:] for t in range(len(tt))]
        out.append((tuple(c[1] for c in cores), tuple(c[2] for c in cores),
                    tuple(c[0] for c in cores) + (cores[-1][3],)))
    return out


def dims(cfg: dict, params) -> cost.Dims:
    return cost.Dims.from_config(cfg, ffn_chains(params))


def _fq(x, axis):
    """Symmetric int8 fake quantisation with one scale per slice along
    ``axis`` (the reduced axis)."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    s = jnp.where(s > 0, s, 1.0)
    return jnp.clip(jnp.round(x / s), -127, 127) * s


def _tt(x, cores, q8):
    """Contract from the last core to the first, as the TT chain does:
    the state [S, n_1…n_{t-1}, n_t, r_t, m_{t+1}…m_d] stays small."""
    S = x.shape[0]
    if q8:
        x = _fq(x, -1)
    n_last = cores[-1].shape[1]
    t = x.reshape(S, x.shape[1] // n_last, n_last, 1, 1)
    for i in reversed(range(len(cores))):
        c = cores[i].astype(jnp.float32)
        if q8:
            c = _fq(c, None)
        a, _, o, _ = c.shape
        t = jnp.einsum("sqnbk,anob->sqaok", t, c)
        q, k = t.shape[1], t.shape[4]
        if i:
            n_prev = cores[i - 1].shape[1]
            t = t.reshape(S, q // n_prev, n_prev, a, o * k)
    return t.reshape(S, -1)


def _proj(p, x, q8):
    if "tt" in p:
        tt = p["tt"]
        return _tt(x, [tt[f"c{t}"] for t in range(len(tt))], q8)
    w = p["w"].astype(jnp.float32)
    if q8:
        w, x = _fq(w, 0), _fq(x, -1)
    return x @ w


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * scale.astype(jnp.float32)


def _rope(x, pos, theta):
    half = x.shape[-1] // 2
    freqs = 1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _layer(x, stacked, i, shape, q8):
    H, KV, hd, theta, eps = shape
    p = jax.tree.map(lambda a: a[i], stacked)
    S = x.shape[0]
    pos = jnp.arange(S)
    h = _rms(x, p["ln1"]["scale"], eps)
    a = p["attn"]
    q = _rope(_proj(a["q"], h, q8).reshape(S, H, hd), pos, theta)
    k = _rope(_proj(a["k"], h, q8).reshape(S, KV, hd), pos, theta)
    v = _proj(a["v"], h, q8).reshape(S, KV, hd)
    k = jnp.repeat(k, H // KV, axis=1)
    v = jnp.repeat(v, H // KV, axis=1)
    s = jnp.einsum("shd,thd->hst", q, k) / np.sqrt(hd)
    s = jnp.where(pos[None, :, None] >= pos[None, None, :], s, -jnp.inf)
    ctx = jnp.einsum("hst,thd->shd", jax.nn.softmax(s, -1), v)
    x = x + _proj(a["o"], ctx.reshape(S, H * hd), q8)
    h = _rms(x, p["ln2"]["scale"], eps)
    f = p["ffn"]
    g = jax.nn.silu(_proj(f["gate"], h, q8)) * _proj(f["up"], h, q8)
    return x + _proj(f["down"], g, q8)


@functools.partial(jax.jit, static_argnames=("shape", "q8"))
def _layer_jit(x, stacked, i, shape, q8):
    with jax.default_matmul_precision("highest"):
        return _layer(x, stacked, i, shape, q8)


@functools.partial(jax.jit, static_argnames=("q8",))
def _embed(table, toks, q8):
    e = table[toks].astype(jnp.float32)
    return _fq(e, -1) if q8 else e


@functools.partial(jax.jit, static_argnames=("eps", "q8"))
def _head(x, final_scale, lm, eps, q8):
    with jax.default_matmul_precision("highest"):
        return _proj(lm, _rms(x, final_scale, eps), q8)


class Reference:
    """``logits(tokens, first)``: float32 logits at positions ``first`` …
    ``len(tokens) - 1`` of one sequence."""

    def __init__(self, params: dict, cfg: dict, precision: str = "f32"):
        if precision not in ("f32", "int8"):
            raise ValueError(precision)
        self.p = params
        self.q8 = precision == "int8"
        self.shape = (int(cfg["num_attention_heads"]),
                      int(cfg["num_key_value_heads"]),
                      int(cfg["head_dim"]), float(cfg["rope_theta"]),
                      float(cfg["rms_norm_eps"]))
        self.eps = float(cfg["rms_norm_eps"])
        self.groups = sorted(k for k in params if k.startswith("g"))
        if self.groups != ["g0"] or list(params["g0"]) != ["b0"]:
            raise ValueError("the reference covers one uniform stack of "
                             "decoder blocks")
        self.stack = params["g0"]["b0"]
        self.layers = int(self.stack["ln1"]["scale"].shape[0])

    def logits(self, tokens: np.ndarray, first: int) -> np.ndarray:
        S = len(tokens)
        Sb = -(-S // BUCKET) * BUCKET
        toks = np.zeros(Sb, np.int32)
        toks[:S] = tokens
        x = _embed(self.p["embed"]["table"], jnp.asarray(toks), self.q8)
        for i in range(self.layers):
            x = _layer_jit(x, self.stack, jnp.asarray(i, jnp.int32),
                           self.shape, self.q8)
        n = S - first
        nb = -(-n // BUCKET) * BUCKET
        rows = jax.lax.dynamic_slice_in_dim(
            jnp.pad(x, ((0, nb), (0, 0))), first, nb, 0)
        out = _head(rows, self.p["final_norm"]["scale"], self.p["lm_head"],
                    self.eps, self.q8)
        return np.asarray(out[:n], np.float32)
