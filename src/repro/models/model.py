"""Model facade: one object per architecture exposing the four entry points
the launcher lowers — ``loss`` (train), ``prefill``, ``decode_step`` and
``init_cache`` — plus param-spec/init plumbing.

The layer plan (groups of scanned periods) comes from the arch config
(configs/<arch>.py::layer_plan); multimodal frontends are stubs operating on
precomputed embeddings supplied by input_specs (per the assignment brief).
"""
from __future__ import annotations

import collections
import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.distributed.sharding import shard_act
from repro.kernels.plan import PlanBook
from .layers import (embed_apply, embed_spec, linear_apply, linear_spec,
                     quantize_tt_params, rmsnorm_apply, rmsnorm_spec)
from .spec import ParamSpec, abstract_tree, count_params, init_tree
from .transformer import (BlockDef, Group, block_cache_kinds,
                          block_cache_shape, block_paged_cache_shape,
                          group_chunk, group_decode, group_fwd,
                          group_resume, group_spec)


def bucket_length(S: int, limit: int, floor: int = 16) -> int:
    """Prompt-length bucket: next power of two >= S (min ``floor``),
    clamped to ``limit`` — varied-length traffic compiles O(log limit)
    prefill variants instead of one per distinct length."""
    if S > limit:
        raise ValueError(f"prompt length {S} exceeds cache length {limit}")
    b = max(floor, 1 << max(S - 1, 0).bit_length())
    return min(b, limit)


@dataclasses.dataclass
class Model:
    cfg: ModelConfig
    groups: list[Group]                  # decoder (or only) stack
    enc_groups: list[Group] | None = None
    param_dtype: Any = jnp.float32
    # jitted entry-point cache: serving calls generate() repeatedly; the
    # jit wrappers must be built once per model (not per call) or every
    # generate() retraces prefill + decode_step from scratch.  The cache is
    # a bounded LRU: a long-running server sees arbitrarily many distinct
    # prompt/cache lengths, and every distinct ``cache_len`` keys a separate
    # jitted prefill (trace + compiled executable) — unbounded, that's a
    # slow leak.  Decode/splice entries (a handful, shape-stable) share the
    # same LRU but in practice never fall out of a size-8 window.
    jit_cache_size: int = 8
    _jit_cache: collections.OrderedDict = dataclasses.field(
        default_factory=collections.OrderedDict, repr=False, compare=False)
    # Per-model TT execution-plan registry (kernels.plan, DESIGN.md §10):
    # built lazily on first use from the TTConfig + param dtype, primed
    # from the param-spec tree so every TT layer's plan is resolved
    # exactly once at build time — prefill/decode traces and the serving
    # scheduler perform ZERO plan resolutions.
    _plan_book: Any = dataclasses.field(
        default=None, repr=False, compare=False)
    # prefill trace/compile counter: every jitted-prefill build (exact or
    # bucketed) increments it, so tests can assert bucketing bounds the
    # number of compiled variants to O(log cache_len)
    prefill_builds: int = 0

    @property
    def plan_book(self) -> PlanBook:
        if self._plan_book is None:
            book = PlanBook.from_tt_config(self.cfg.tt, self.param_dtype)
            book.prime(self.param_specs())
            self._plan_book = book
        return self._plan_book

    def _jit_get(self, key, build):
        """LRU lookup: hit refreshes recency, miss builds and may evict."""
        fn = self._jit_cache.get(key)
        if fn is not None:
            self._jit_cache.move_to_end(key)
            return fn
        fn = build()
        self._jit_cache[key] = fn
        while len(self._jit_cache) > max(self.jit_cache_size, 1):
            self._jit_cache.popitem(last=False)
        return fn

    # ------------------------------------------------------------------ specs
    def param_specs(self) -> dict:
        cfg, dt = self.cfg, self.param_dtype
        specs: dict = {"embed": embed_spec(cfg.vocab_size, cfg.d_model, dt),
                       "final_norm": rmsnorm_spec(cfg.d_model, "embed", dt)}
        if not cfg.tie_embeddings:
            specs["lm_head"] = linear_spec(cfg.d_model, cfg.vocab_size,
                                           cfg.tt, "lm_head",
                                           ("embed", "vocab"), dt)
        for gi, g in enumerate(self.groups):
            specs[f"g{gi}"] = group_spec(cfg, g, dt)
        if self.enc_groups is not None:
            specs["enc_norm"] = rmsnorm_spec(cfg.d_model, "embed", dt)
            for gi, g in enumerate(self.enc_groups):
                specs[f"enc_g{gi}"] = group_spec(cfg, g, dt)
        if cfg.frontend == "vit":
            specs["projector"] = linear_spec(cfg.frontend_dim, cfg.d_model,
                                             None, "frontend",
                                             (None, "embed"), dt)
        if cfg.frontend == "speech":
            specs["frontend_proj"] = linear_spec(cfg.frontend_dim,
                                                 cfg.d_model, None,
                                                 "frontend", (None, "embed"),
                                                 dt)
        return specs

    def init(self, key: jax.Array) -> dict:
        return init_tree(key, self.param_specs())

    def abstract_params(self) -> dict:
        return abstract_tree(self.param_specs())

    def num_params(self) -> int:
        return count_params(self.param_specs())

    def quantize_params(self, params: dict) -> dict:
        """int8-quantize every TT core bundle of a parameter tree
        (checkpoint transform, DESIGN.md §8).  The returned tree is served
        by the same entry points — prefill, decode_step and the
        continuous-batching scheduler all route through ``linear_apply``,
        which detects the int8 storage and runs the int8-resident kernel
        path."""
        return quantize_tt_params(params)

    # -------------------------------------------------------------- embedding
    def _embed_inputs(self, params, batch) -> tuple[jax.Array, jax.Array]:
        """Returns (x [B,S,d], loss_mask [B,S])."""
        cfg = self.cfg
        tokens = batch["tokens"]
        x = embed_apply(params["embed"], tokens, cfg.d_model,
                        scale=cfg.tie_embeddings)
        mask = jnp.ones(tokens.shape, bool)
        if cfg.frontend == "vit":
            img = linear_apply(params["projector"], batch["image_embeds"])
            x = jnp.concatenate([img.astype(x.dtype), x], axis=1)
            mask = jnp.concatenate(
                [jnp.zeros(img.shape[:2], bool), mask], axis=1)
        return x, mask

    def _encode(self, params, batch) -> jax.Array:
        """Seamless encoder over precomputed speech-frame embeddings."""
        cfg = self.cfg
        frames = batch["speech_embeds"]
        x = linear_apply(params["frontend_proj"], frames)
        B, S, _ = x.shape
        positions = jnp.broadcast_to(jnp.arange(S), (B, S))
        for gi, g in enumerate(self.enc_groups):
            x, _ = group_fwd(params[f"enc_g{gi}"], cfg, g, x, positions,
                             want_cache=False, plans=self.plan_book)
        return rmsnorm_apply(params["enc_norm"], x, cfg.norm_eps)

    def _logits(self, params, x) -> jax.Array:
        """Final norm and head, under the ``lm_head`` scope."""
        cfg = self.cfg
        with jax.named_scope("lm_head"):
            x = rmsnorm_apply(params["final_norm"], x, cfg.norm_eps)
            if cfg.tie_embeddings:
                logits = x @ params["embed"]["table"].T
            else:
                logits = linear_apply(params["lm_head"], x, self.plan_book)
            return shard_act(logits.astype(jnp.float32),
                             ("act_batch", None, "act_vocab"))

    # ------------------------------------------------------------------ train
    def loss(self, params, batch, remat: bool = True) -> jax.Array:
        cfg = self.cfg
        enc_out = self._encode(params, batch) if cfg.enc_dec else None
        x, mask = self._embed_inputs(params, batch)
        B, S, _ = x.shape
        x = shard_act(x, ("act_batch", "act_seq", "act_embed"))
        positions = jnp.broadcast_to(jnp.arange(S), (B, S))
        for gi, g in enumerate(self.groups):
            x, _ = group_fwd(params[f"g{gi}"], cfg, g, x, positions,
                             enc_out=enc_out, want_cache=False, remat=remat,
                             plans=self.plan_book)
        logits = self._logits(params, x)
        tokens = batch["tokens"]
        off = S - tokens.shape[1]                    # frontend prefix length
        lg = logits[:, off:, :][:, :-1]
        tgt = tokens[:, 1:]
        msk = mask[:, off:][:, 1:]
        lse = jax.nn.logsumexp(lg, axis=-1)
        ll = jnp.take_along_axis(lg, tgt[..., None], axis=-1)[..., 0]
        nll = (lse - ll) * msk
        return jnp.sum(nll) / jnp.maximum(jnp.sum(msk), 1)

    def activation_stats(self, params, batches: list[dict]) -> dict:
        """Per-projection input second moments over a calibration set —
        the data term of activation-aware DSE scoring (DESIGN.md §12).

        Runs the training forward *eagerly* (``remat=False``, no jit)
        under ``layers.capture_activation_stats`` so every
        ``linear_apply`` streams its input Gram matrix to the host; scan
        and vmap inside the stack are fine (the accumulator is
        order-invariant).  Returns ``{(N, M): {"sigma": [N, N] float64,
        "count": rows}}`` where sigma = E[x xᵀ] aggregated across all
        layers sharing that projection shape."""
        from .layers import capture_activation_stats
        with capture_activation_stats() as store:
            with jax.disable_jit():
                for b in batches:
                    self.loss(params, b, remat=False)
            jax.effects_barrier()
        return {key: {"sigma": slot["gram"] / max(slot["count"], 1.0),
                      "count": slot["count"]}
                for key, slot in store.items()}

    # ---------------------------------------------------------------- serving
    def prefill(self, params, batch) -> tuple[jax.Array, dict]:
        """Process the full prompt; return (last-token logits, cache).

        ``batch["prompt_len"]`` (optional, a traced int32 scalar) marks the
        true sequence length when the prompt was right-padded to a bucket
        (``bucket_length``): the window ring and SSM state are built at the
        true write head, ``cache["pos"]`` is the true length, and the
        logits are taken at position prompt_len - 1 — padded junk rows in
        full/MLA caches sit beyond ``pos`` and are masked by every decode
        path."""
        cfg = self.cfg
        enc_out = self._encode(params, batch) if cfg.enc_dec else None
        x, _ = self._embed_inputs(params, batch)
        B, S, _ = x.shape
        positions = jnp.broadcast_to(jnp.arange(S), (B, S))
        plen = batch.get("prompt_len")
        cache: dict = {"pos": (jnp.asarray(S, jnp.int32) if plen is None
                               else jnp.asarray(plen, jnp.int32))}
        T = batch.get("cache_len", S)
        for gi, g in enumerate(self.groups):
            x, c = group_fwd(params[f"g{gi}"], cfg, g, x, positions,
                             enc_out=enc_out, want_cache=True, T_cache=T,
                             plans=self.plan_book, true_len=plen)
            cache[f"g{gi}"] = c
        if plen is None:
            xl = x[:, -1:, :]
        else:
            xl = jax.lax.dynamic_slice_in_dim(
                x, jnp.asarray(plen, jnp.int32) - 1, 1, axis=1)
        logits = self._logits(params, xl)
        return logits, cache

    def decode_step(self, params, cache: dict, token: jax.Array,
                    active: jax.Array | None = None
                    ) -> tuple[jax.Array, dict]:
        """token [B,1] int32 → (logits [B,1,V], updated cache).

        ``cache["pos"]`` may be a scalar (classic fixed batch: every row at
        the same depth) or a per-row vector [B] (continuous-batching slot
        pool).  With vector positions an optional ``active`` mask [B] bool
        freezes retired/free slots: their position does not advance, so
        they re-write the same (dead) cache row every step until an
        admission splices fresh state over them.

        A cache carrying ``block_tables`` is block-paged (DESIGN.md §7):
        attention leaves are arenas addressed through the per-slot table,
        and inactive slots' writes are redirected to the sentinel block —
        a retired slot's stale table must never touch storage reused by a
        later request.
        """
        cfg = self.cfg
        pos = cache["pos"]
        bt = cache.get("block_tables")
        x = embed_apply(params["embed"], token, cfg.d_model,
                        scale=cfg.tie_embeddings)
        inc = 1 if active is None else active.astype(pos.dtype)
        new_cache = {"pos": pos + inc}
        paged = None
        if bt is not None:
            new_cache["block_tables"] = bt
            act = (jnp.ones(pos.shape, bool) if active is None else active)
            paged = (bt, act)
        for gi, g in enumerate(self.groups):
            x, c = group_decode(params[f"g{gi}"], cfg, g, x,
                                cache[f"g{gi}"], pos,
                                plans=self.plan_book, paged=paged,
                                active=active)
            new_cache[f"g{gi}"] = c
        logits = self._logits(params, x)
        return logits, new_cache

    def splice_cache(self, cache: dict, row_cache: dict, slot) -> dict:
        """Write a single-request cache (batch dim 1, same ``cache_len``)
        into row ``slot`` of a slot-pool cache — the admission path of the
        continuous-batching scheduler.  Every leaf except ``pos`` is
        [layers, B, ...] (batch at axis 1); ``pos`` is [B] in the pool and
        a scalar (the prompt length) in the prefill output."""
        out = {"pos": cache["pos"].at[slot].set(
            row_cache["pos"].astype(cache["pos"].dtype))}
        for k, v in cache.items():
            if k == "pos":
                continue
            out[k] = jax.tree.map(
                lambda pool, new: pool.at[:, slot].set(
                    new[:, 0].astype(pool.dtype)), v, row_cache[k])
        return out

    def splice_cache_paged(self, cache: dict, row_cache: dict, slot,
                           blocks) -> dict:
        """Paged twin of :meth:`splice_cache`: scatter a single-request
        dense row cache (prefilled at the pool's logical ``cache_len``)
        into the arena blocks named by ``blocks`` [max_blocks] int32 (the
        slot's full table row, sentinel-padded past its allocation — the
        junk scattered there collapses onto the scratch block).  'slot'
        leaves (SSM state/conv, cross-attn KV) splice per-slot as before.
        """
        out = {"pos": cache["pos"].at[slot].set(
            row_cache["pos"].astype(cache["pos"].dtype)),
            "block_tables": cache["block_tables"].at[slot].set(
                blocks.astype(cache["block_tables"].dtype))}
        for gi, (period, _count) in enumerate(self.groups):
            g_new = {}
            for i, bd in enumerate(period):
                kinds = block_cache_kinds(bd)
                b_new = {}
                for name, pool in cache[f"g{gi}"][f"b{i}"].items():
                    row = row_cache[f"g{gi}"][f"b{i}"][name]
                    if kinds[name] == "slot":
                        b_new[name] = pool.at[:, slot].set(
                            row[:, 0].astype(pool.dtype))
                        continue
                    blk = pool.shape[2]
                    r = row[:, 0]                     # [layers, T_row, ...]
                    T_row = r.shape[1]
                    nblk = -(-T_row // blk)
                    pad = nblk * blk - T_row
                    if pad:
                        r = jnp.pad(r, ((0, 0), (0, pad))
                                    + ((0, 0),) * (r.ndim - 2))
                    r = r.reshape(r.shape[0], nblk, blk, *r.shape[2:])
                    b_new[name] = pool.at[:, blocks[:nblk]].set(
                        r.astype(pool.dtype))
                g_new[f"b{i}"] = b_new
            out[f"g{gi}"] = g_new
        return out

    def prefill_resume(self, params, arrays, cache: dict, slot, src_blocks,
                       dst_blocks, start, true_suf) -> tuple[jax.Array,
                                                             dict]:
        """Prefix-reuse admission (DESIGN.md §7): run prefill over only the
        *suffix* tokens (``arrays["tokens"]`` [1, S_pad], right-padded,
        ``true_suf`` real) starting at absolute position ``start``; the
        covered prefix is gathered from resident arena blocks through
        ``src_blocks`` and never recomputed.  The updated logical cache is
        scattered back through ``dst_blocks`` — entries differing from
        ``src_blocks`` are the copy-on-write blocks.  Returns (last-token
        logits [1,1,V], updated pool cache)."""
        cfg = self.cfg
        x, _ = self._embed_inputs(params, arrays)
        start = jnp.asarray(start, jnp.int32)
        new_cache = {
            "pos": cache["pos"].at[slot].set(
                (start + true_suf).astype(cache["pos"].dtype)),
            "block_tables": cache["block_tables"].at[slot].set(
                dst_blocks.astype(cache["block_tables"].dtype))}
        for gi, g in enumerate(self.groups):
            x, c = group_resume(params[f"g{gi}"], cfg, g, x,
                                cache[f"g{gi}"], src_blocks, dst_blocks,
                                start, plans=self.plan_book)
            new_cache[f"g{gi}"] = c
        xl = jax.lax.dynamic_slice_in_dim(
            x, jnp.asarray(true_suf, jnp.int32) - 1, 1, axis=1)
        logits = self._logits(params, xl)
        return logits, new_cache

    def chunk_step(self, params, cache: dict, tokens, slot, start, true_len,
                   active, table=None) -> tuple[jax.Array, dict]:
        """One prefill chunk of one slot, in place in the serving pool.

        ``tokens`` [1, C] (rows >= true_len are right-padding) are the
        prompt slice [start, start + true_len); ``slot`` addresses the pool
        row, ``table`` [max_blocks] the paged arenas (None = dense layout;
        pass it sentinel-redirected when ``active`` is False).  ``active``
        (scalar bool) makes an unused lane a no-op by value.  Returns
        (logits [1,1,V] at position start + true_len - 1, updated cache) —
        the logits matter only on the final chunk, where the scheduler
        picks the first generated token from them.
        """
        cfg = self.cfg
        x = embed_apply(params["embed"], tokens, cfg.d_model,
                        scale=cfg.tie_embeddings)
        slot = jnp.asarray(slot, jnp.int32)
        start = jnp.asarray(start, jnp.int32)
        true_len = jnp.asarray(true_len, jnp.int32)
        pos = cache["pos"]
        new_cache = {"pos": pos.at[slot].set(jnp.where(
            active, (start + true_len).astype(pos.dtype), pos[slot]))}
        if table is not None:
            bt = cache["block_tables"]
            new_cache["block_tables"] = bt.at[slot].set(jnp.where(
                active, table.astype(bt.dtype), bt[slot]))
        for gi, g in enumerate(self.groups):
            x, c = group_chunk(params[f"g{gi}"], cfg, g, x, cache[f"g{gi}"],
                               slot, table, start, true_len, active,
                               plans=self.plan_book)
            new_cache[f"g{gi}"] = c
        xl = jax.lax.dynamic_slice_in_dim(x, true_len - 1, 1, axis=1)
        logits = self._logits(params, xl)
        return logits, new_cache

    def mixed_step(self, params, cache: dict, token, active, ck_tokens,
                   ck_slot, ck_start, ck_true, ck_active, ck_tables=None
                   ) -> tuple[jax.Array, jax.Array, dict]:
        """Fused serving step: K prefill-chunk lanes + the masked decode
        pass, one traced program (the chunked-prefill tentpole).

        ck_tokens [K, C] int32, ck_slot/ck_start/ck_true [K] int32,
        ck_active [K] bool, ck_tables [K, max_blocks] int32 (paged pools
        only; rows of unused lanes must be sentinel-filled).  Chunk lanes
        run before the decode pass, so a lane finishing its prompt this
        step is decodable the next; the decode pass masks every per-slot
        write with ``active``, leaving mid-prefill rows untouched.
        Returns (decode logits [B,1,V], chunk logits [K,V] at each lane's
        last true position, updated cache)."""
        K = ck_tokens.shape[0]
        ck_logits = []
        for j in range(K):
            tbl = None if ck_tables is None else ck_tables[j]
            lg, cache = self.chunk_step(
                params, cache, ck_tokens[j:j + 1], ck_slot[j], ck_start[j],
                ck_true[j], ck_active[j], table=tbl)
            ck_logits.append(lg[0, 0])
        dec_logits, cache = self.decode_step(params, cache, token, active)
        return dec_logits, jnp.stack(ck_logits), cache

    def copy_blocks(self, cache: dict, src, dst) -> dict:
        """Copy one arena block's content ``src`` → ``dst`` in every
        pageable leaf — the eager COW at chunked admission with a
        fully-covered prefix (the last matched block is about to be
        partially overwritten through the slot's own table)."""
        out = dict(cache)
        for gi, (period, _count) in enumerate(self.groups):
            g_new = {}
            for i, bd in enumerate(period):
                kinds = block_cache_kinds(bd)
                b_new = {}
                for name, pool in cache[f"g{gi}"][f"b{i}"].items():
                    if kinds[name] == "slot":
                        b_new[name] = pool
                    else:
                        b_new[name] = pool.at[:, dst].set(pool[:, src])
                g_new[f"b{i}"] = b_new
            out[f"g{gi}"] = g_new
        return out

    @property
    def supports_chunked_prefill(self) -> bool:
        """Chunked prefill covers every self-mixer — full attention, MLA,
        windowed-ring (history-gathered), SSM (state-threaded) — but not
        enc-dec cross-attention or multimodal frontends, whose admission
        stays monolithic."""
        if self.cfg.enc_dec or self.cfg.frontend is not None:
            return False
        return all(not bd.cross
                   for period, _count in self.groups for bd in period)

    @property
    def supports_prefix_reuse(self) -> bool:
        """Prefix blocks are shareable only when every mixer's cache rows
        are pure functions of the token prefix *and* are never overwritten
        in place: full attention and MLA qualify; window rings (contents
        cycle), SSM state (whole-history summary) and enc-dec/multimodal
        frontends do not."""
        if self.cfg.enc_dec or self.cfg.frontend is not None:
            return False
        for period, _count in self.groups:
            for bd in period:
                if bd.mixer == "ssm" or bd.cross or (
                        bd.mixer == "gqa" and bd.window):
                    return False
        return True

    # --------------------------------------------------- jitted entry points
    def jitted_prefill(self, cache_len: int | None = None,
                       shape_key=None):
        """jit(prefill) with the static ``cache_len`` closed over, cached
        per (model, cache_len) so repeated generate() calls reuse traces.

        ``shape_key`` splits the LRU entry further (the scheduler passes
        the prompt length): a jax.jit wrapper retains one executable per
        input shape it has seen, so a single long-lived wrapper fed many
        prompt lengths would accumulate them beyond the LRU's reach —
        per-length entries make eviction actually free the executables."""
        def build():
            self.prefill_builds += 1

            def prefill(params, arrays):
                b = (dict(arrays, cache_len=cache_len)
                     if cache_len is not None else arrays)
                return self.prefill(params, b)
            return jax.jit(prefill)
        return self._jit_get(("prefill", cache_len, shape_key), build)

    def jitted_prefill_bucketed(self, cache_len: int):
        """Host wrapper around jit(prefill) with prompt-length bucketing:
        the token prompt is right-padded to the next power of two (min 16,
        clamped to the cache length) and the true length rides along as a
        traced scalar, so varied-length traffic compiles O(log cache_len)
        prefill variants (``prefill_builds`` counts them) instead of one
        per distinct prompt length."""
        def build_for(S_pad):
            def build():
                self.prefill_builds += 1

                def prefill(params, arrays, plen):
                    return self.prefill(params, dict(
                        arrays, cache_len=cache_len, prompt_len=plen))
                return jax.jit(prefill)
            return self._jit_get(("prefill_b", cache_len, S_pad), build)

        def call(params, arrays):
            toks = arrays["tokens"]
            S_tok = int(toks.shape[1])
            extra = (int(arrays["image_embeds"].shape[1])
                     if self.cfg.frontend == "vit" else 0)
            S_pad = bucket_length(S_tok, cache_len - extra)
            if S_pad != S_tok:
                toks = jnp.pad(toks, ((0, 0), (0, S_pad - S_tok)))
                arrays = dict(arrays, tokens=toks)
            return build_for(S_pad)(
                params, arrays, jnp.asarray(extra + S_tok, jnp.int32))
        return call

    def jitted_decode_step(self):
        """jit(decode_step) with the cache donated, cached per model."""
        return self._jit_get(
            "decode_step",
            lambda: jax.jit(lambda params, cache, token:
                            self.decode_step(params, cache, token),
                            donate_argnums=(1,)))

    def jitted_decode_step_masked(self, mesh=None):
        """jit(decode_step) with a per-slot ``active`` mask (vector-pos
        slot-pool cache), cache donated.

        With a ``mesh`` the logits output is pinned replicated while the
        cache stays compiler-placed: the final all-gather of the
        tensor-parallel logits happens *inside* this executable (one step
        = one program, collectives compiled in), and the downstream pick
        never sees a vocab-sharded operand (a sharded top-k would compile
        into a distributed sort — tens of rendezvous per step)."""
        def build():
            out_shardings = None
            if mesh is not None:
                from jax.sharding import NamedSharding, PartitionSpec
                out_shardings = (NamedSharding(mesh, PartitionSpec()), None)
            return jax.jit(self.decode_step, donate_argnums=(1,),
                           out_shardings=out_shardings)
        return self._jit_get(("decode_step_masked", mesh), build)

    def jitted_mixed_step(self, K: int, C: int, mesh=None):
        """jit(mixed_step), cache donated, one LRU entry per chunk config
        (K lanes × C tokens) so distinct configs stay individually
        evictable.  With a mesh both logits outputs are pinned replicated
        (same rationale as :meth:`jitted_decode_step_masked`)."""
        def build():
            out_shardings = None
            if mesh is not None:
                from jax.sharding import NamedSharding, PartitionSpec
                rep = NamedSharding(mesh, PartitionSpec())
                out_shardings = (rep, rep, None)
            return jax.jit(self.mixed_step, donate_argnums=(1,),
                           out_shardings=out_shardings)
        return self._jit_get(("mixed_step", K, C, mesh), build)

    def jitted_copy_blocks(self):
        """jit(copy_blocks), pool donated — the eager COW block copy."""
        return self._jit_get(
            "copy_blocks",
            lambda: jax.jit(self.copy_blocks, donate_argnums=(0,)))

    def jitted_splice(self):
        """jit(splice_cache) with the pool cache donated: admission writes
        one row in place instead of copying the whole pool."""
        return self._jit_get(
            "splice",
            lambda: jax.jit(self.splice_cache, donate_argnums=(0,)))

    def jitted_splice_paged(self):
        """jit(splice_cache_paged), pool donated — admission scatters the
        prefilled row into its arena blocks in place."""
        return self._jit_get(
            "splice_paged",
            lambda: jax.jit(self.splice_cache_paged, donate_argnums=(0,)))

    def jitted_prefill_resume(self, cache_len: int):
        """Host wrapper around jit(prefill_resume) with the suffix bucketed
        like :meth:`jitted_prefill_bucketed` (one trace per suffix bucket),
        pool cache donated."""
        def build_for(S_pad):
            def build():
                self.prefill_builds += 1
                return jax.jit(self.prefill_resume, donate_argnums=(2,))
            return self._jit_get(("resume", cache_len, S_pad), build)

        def call(params, arrays, cache, slot, src_blocks, dst_blocks,
                 start, true_suf):
            toks = arrays["tokens"]
            S_tok = int(toks.shape[1])
            S_pad = bucket_length(S_tok, cache_len)
            if S_pad != S_tok:
                toks = jnp.pad(toks, ((0, 0), (0, S_pad - S_tok)))
                arrays = dict(arrays, tokens=toks)
            return build_for(S_pad)(
                params, arrays, cache, jnp.asarray(slot, jnp.int32),
                jnp.asarray(src_blocks, jnp.int32),
                jnp.asarray(dst_blocks, jnp.int32),
                jnp.asarray(start, jnp.int32),
                jnp.asarray(true_suf, jnp.int32))
        return call

    # --------------------------------------------------------------- caching
    def cache_shapes(self, B: int, T: int, enc_T: int = 0,
                     dtype=jnp.bfloat16) -> dict:
        """ShapeDtypeStruct tree of a decode cache at context length T."""
        cfg = self.cfg
        out: dict = {"pos": jax.ShapeDtypeStruct((), jnp.int32)}
        for gi, (period, count) in enumerate(self.groups):
            g = {}
            for i, bd in enumerate(period):
                g[f"b{i}"] = block_cache_shape(cfg, bd, B, T, enc_T, dtype)
            out[f"g{gi}"] = jax.tree.map(
                lambda s: jax.ShapeDtypeStruct((count,) + s.shape, s.dtype),
                g)
        return out

    def paged_cache_shapes(self, num_slots: int, num_blocks: int,
                           block: int, cache_len: int, enc_T: int = 0,
                           dtype=jnp.bfloat16) -> dict:
        """ShapeDtypeStruct tree of a block-paged pool (DESIGN.md §7):
        attention leaves become arenas [layers, num_blocks + 1, block, ...]
        shared by all slots through per-slot block tables; SSM/cross
        leaves stay [layers, num_slots, ...]; ``pos`` is [num_slots] and
        ``block_tables`` [num_slots, ceil(cache_len/block)]."""
        cfg = self.cfg
        max_blocks = -(-cache_len // block)
        out: dict = {
            "pos": jax.ShapeDtypeStruct((num_slots,), jnp.int32),
            "block_tables": jax.ShapeDtypeStruct((num_slots, max_blocks),
                                                 jnp.int32)}
        for gi, (period, count) in enumerate(self.groups):
            g = {}
            for i, bd in enumerate(period):
                g[f"b{i}"] = block_paged_cache_shape(
                    cfg, bd, num_slots, num_blocks, block, cache_len,
                    enc_T, dtype)
            out[f"g{gi}"] = jax.tree.map(
                lambda s: jax.ShapeDtypeStruct((count,) + s.shape, s.dtype),
                g)
        return out

    def init_cache(self, B: int, T: int, enc_T: int = 0,
                   dtype=jnp.bfloat16, *, paged: bool = False,
                   num_blocks: int | None = None, block: int = 64) -> dict:
        """Zeroed decode cache.  ``paged=True`` builds the block-paged pool
        instead (B = num_slots; block tables initialized to the sentinel),
        the layout the continuous-batching scheduler serves — see
        DESIGN.md §7 for the migration notes."""
        if not paged:
            shapes = self.cache_shapes(B, T, enc_T, dtype)
            return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                                shapes)
        if num_blocks is None:
            num_blocks = B * (-(-T // block))
        shapes = self.paged_cache_shapes(B, num_blocks, block, T, enc_T,
                                         dtype)
        cache = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), shapes)
        cache["block_tables"] = jnp.full(shapes["block_tables"].shape,
                                         num_blocks, jnp.int32)
        return cache


# ---------------------------------------------------------------------------
# Builders
# ---------------------------------------------------------------------------

def build_model(cfg: ModelConfig, layer_plan: list[Group],
                enc_plan: list[Group] | None = None,
                param_dtype=jnp.float32) -> Model:
    return Model(cfg, layer_plan, enc_plan, param_dtype)
