"""TransformerLM — the flagship distributed model (causal LM / classifier).

The reference exposes transformer capability as layers (TransformerLayer.scala,
BERT.scala) used by the text estimators (tfpark/text/). Here the flagship model
additionally exercises every parallelism axis: batch over dp/fsdp, params over
fsdp+tp (megatron layout, parallel.sharding.TP_RULES), sequence over sp via
ring/Ulysses attention. This is the model behind ``__graft_entry__``.
"""

from __future__ import annotations

from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..nn import layers as L
from ..nn.layers.attention import TransformerLayer
from ..nn.layers.normalization import LayerNormalization
from ..nn.module import Layer, as_compute, get_initializer, param_dtype
from ..nn.topology import KerasNet
from .common.zoo_model import register_model


@register_model("TransformerLM")
class TransformerLM(Layer, KerasNet):
    """Decoder-only transformer over int token ids (B, T) → logits (B, T, V).

    .. note:: **remat policy remap.** ``remat=True`` now means ``'flash'``
       (checkpoint with the flash-attention save policy: the kernel's
       out/lse are pinned so backward never re-runs the O(T²) attention
       forward — strictly faster than full recompute wherever flash runs).
       Callers wanting the minimum-memory classic behavior — recompute
       EVERYTHING in backward, and the only correct choice when attention
       took the non-flash path — must now pass ``remat='full'`` explicitly.
       ``remat='dots'`` additionally saves matmul outputs (less recompute,
       more memory). See ``_remat_policy`` for the exact policies.
    """

    def __init__(self, vocab: int, hidden_size: int = 256, n_block: int = 4,
                 n_head: int = 8, seq_len: int = 512,
                 intermediate_size: Optional[int] = None,
                 attn_strategy: str = "auto", remat=False, name=None):
        super().__init__(name=name)
        self.vocab = vocab
        self.hidden_size = hidden_size
        self.n_block = n_block
        self.seq_len = seq_len
        self.intermediate_size = intermediate_size
        self.attn_strategy = attn_strategy
        # remat: False | "flash" (True) | "full" | "dots".
        #   "flash": jax.checkpoint with FLASH_REMAT_POLICY — the flash
        #            kernel's out/lse are saved so backward never re-runs the
        #            O(T^2) attention forward; only projections/LN/MLP
        #            recompute. Strictly dominates "full" wherever flash runs
        #            (BENCH batch-32 remat: 0.406 MFU full → ≥0.5 flash).
        #   "full":  plain jax.checkpoint (recompute EVERYTHING incl.
        #            attention) — the minimum-memory fallback, and the only
        #            correct choice when attention took the non-flash path
        #            (full_attention saves no lse to reuse).
        #   "dots":  flash policy + dots_with_no_batch_dims_saveable — also
        #            keeps matmul outputs; less recompute, more memory.
        self.remat = "flash" if remat is True else remat
        self.blocks = [
            TransformerLayer(hidden_size, n_head, intermediate_size, causal=True,
                             attn_strategy=attn_strategy,
                             name=f"{self.name}_block{i}")
            for i in range(n_block)
        ]
        self.ln_f = LayerNormalization(name=f"{self.name}_lnf")
        self.layers = list(self.blocks) + [self.ln_f]  # canonical order (persistence)

    @property
    def input_shape(self):
        return (self.seq_len,)

    def _remat_policy(self):
        """Resolve ``self.remat`` to a jax.checkpoint policy (None = save
        nothing, i.e. classic full rematerialization)."""
        if self.remat == "full":
            return None
        from ..ops.flash_attention import FLASH_REMAT_POLICY

        if self.remat == "dots":
            return jax.checkpoint_policies.save_from_both_policies(
                FLASH_REMAT_POLICY,
                jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
        if self.remat in ("flash", True):
            return FLASH_REMAT_POLICY
        raise ValueError(f"unknown remat mode {self.remat!r}; "
                         "known: False, True/'flash', 'full', 'dots'")

    def build(self, rng, input_shape=None):
        ks = jax.random.split(rng, self.n_block + 3)
        params = {
            "token_embeddings": jax.random.normal(
                ks[0], (self.vocab, self.hidden_size), param_dtype()) * 0.02,
            "pos_embeddings": jax.random.normal(
                ks[1], (self.seq_len, self.hidden_size), param_dtype()) * 0.02,
            "logits_kernel": get_initializer("glorot_uniform")(
                ks[2], (self.hidden_size, self.vocab), param_dtype()),
        }
        for i, blk in enumerate(self.blocks):
            p, _ = blk.build(ks[3 + i], (None, self.hidden_size))
            params[f"block{i}"] = p
        lnf, _ = self.ln_f.build(ks[-1], (None, self.hidden_size))
        params["ln_f"] = lnf
        return params, {}

    def cast_at_use(self, params):
        # the head and what each block declares. Not the two embedding
        # tables: their f32 rows are summed BEFORE the cast (apply_features,
        # prefill, decode_step), so rounding the tables first is other
        # arithmetic; not ln_f, which computes in f32
        flags = {"token_embeddings": False, "pos_embeddings": False,
                 "logits_kernel": True,
                 "ln_f": self.ln_f.cast_at_use(params["ln_f"])}
        for i, blk in enumerate(self.blocks):
            flags[f"block{i}"] = blk.cast_at_use(params[f"block{i}"])
        return flags

    def apply_features(self, params, x, *, training=False, rng=None):
        """Hidden states BEFORE the LM head: (B, T, hidden).

        Pair with :func:`analytics_zoo_tpu.ops.fused_ce.fused_softmax_xent`
        (``fused_softmax_xent(h, params["logits_kernel"], labels)``) to train
        without ever materializing the (B, T, vocab) logits — at vocab 32k
        the f32 logits are 1 GB per 8k tokens, which is what pushes big
        batches into rematerialization."""
        ids = jnp.asarray(x, jnp.int32)
        h = jnp.take(params["token_embeddings"], ids, axis=0)
        h = h + params["pos_embeddings"][: ids.shape[1]][None]
        h = as_compute(h)
        rngs = (jax.random.split(rng, self.n_block) if rng is not None
                else [None] * self.n_block)

        for i, blk in enumerate(self.blocks):
            if self.remat:
                # trade FLOPs for HBM: recompute block activations in backward,
                # except what the remat policy pins (see __init__)
                apply_fn = jax.checkpoint(
                    lambda p, h, blk=blk, r=rngs[i]: blk.apply(
                        p, {}, h, training=training, rng=r)[0],
                    policy=self._remat_policy())
                h = apply_fn(params[f"block{i}"], h)
            else:
                h, _ = blk.apply(params[f"block{i}"], {}, h, training=training,
                                 rng=rngs[i])
        h, _ = self.ln_f.apply(params["ln_f"], {}, h)
        return h

    def apply(self, params, state, x, *, training=False, rng=None):
        h = self.apply_features(params, x, training=training, rng=rng)
        logits = h @ jnp.asarray(params["logits_kernel"], h.dtype)
        return logits, state

    # -------------------------------------------------------- decode serving
    # prefill()/decode_step(): the autoregressive path behind the continuous
    # batcher (serving/generation.py). Both are pure functions of
    # (params, cache, ...) with shapes fixed by the KVCacheConfig, so each
    # compiles exactly once per (batch, bucket) — the pow2 discipline the
    # one-shot serving path already follows.

    def init_kv_cache(self, n_slots: int, *, page_size: int = 16,
                      max_seq_len: Optional[int] = None,
                      n_pages: Optional[int] = None, dtype=None):
        """Build a paged KV cache for ``n_slots`` concurrent decode
        sequences. Returns ``(KVCacheConfig, cache)`` where ``cache`` is the
        page-pool pytree threaded through :meth:`prefill`/
        :meth:`decode_step`: ``{"k": (k_0, ...), "v": (v_0, ...)}``, one pool
        per layer, each ``(n_pages, page_size, n_heads, head_dim)``."""
        from ..nn.module import compute_dtype
        from ..ops.kv_cache import KVCacheConfig, init_cache

        max_seq = int(max_seq_len or self.seq_len)
        pps = -(-max_seq // page_size)          # ceil: full pages only
        if pps * page_size > self.seq_len:
            # validate the ROUNDED capacity: pps*page_size is what decode
            # positions can actually reach, and positions past the table
            # would silently clamp to the last row (corrupt embeddings)
            raise ValueError(
                f"max_seq_len {max_seq} rounds up to {pps * page_size} "
                f"(full pages of {page_size}), exceeding the model's "
                f"position table ({self.seq_len}); choose max_seq_len <= "
                f"{self.seq_len // page_size * page_size}")
        attn = self.blocks[0].attn
        cfg = KVCacheConfig(
            n_layers=self.n_block, n_heads=attn.n_head,
            head_dim=attn.head_dim, n_slots=n_slots, page_size=page_size,
            pages_per_slot=pps, n_pages=n_pages,
            dtype=dtype or compute_dtype())
        return cfg, init_cache(cfg)

    def _thread_cache(self, params, cache, h, layer_fn):
        """Run the blocks in order, each on ITS layer's K and V pool:
        ``layer_fn(blk, block_params, h, k_pool, v_pool) -> (h, k_pool,
        v_pool)``. Returns ``(h, cache)`` with the same pytree structure as
        ``cache`` — no leaf is sliced out of or stored back into a larger
        array, so with the cache donated every pool aliases input to output
        and the scatter inside ``layer_fn`` writes in place."""
        k_pools, v_pools = [], []
        for i, blk in enumerate(self.blocks):
            h, kp, vp = layer_fn(blk, params[f"block{i}"], h,
                                 cache["k"][i], cache["v"][i])
            k_pools.append(kp)
            v_pools.append(vp)
        return h, {"k": tuple(k_pools), "v": tuple(v_pools)}

    def prefill(self, params, cache, ids, lengths, table, *, page_size: int):
        """One batched forward that fills the cache and returns last-token
        logits.

        ``ids``: (B, T_bucket) int32, right-padded to a pow2 bucket that
        divides ``page_size``; ``lengths``: (B,) true prompt lengths;
        ``table``: (B, pages_per_slot) int32 page tables (entries past the
        allocated prefix = scratch). Causal masking means pad positions are
        never attended by valid queries, so their scratch writes are inert.
        ``cache`` holds one pool per layer (:func:`~analytics_zoo_tpu.ops.
        kv_cache.init_cache`); each block's K/V are scattered into its own
        pool. Returns ``(logits (B, V) f32 — at position length-1, cache)``.
        """
        from ..ops.kv_cache import prefill_write

        ids = jnp.asarray(ids, jnp.int32)
        lengths = jnp.asarray(lengths, jnp.int32)
        h = jnp.take(params["token_embeddings"], ids, axis=0)
        h = h + params["pos_embeddings"][: ids.shape[1]][None]
        h = as_compute(h)

        def layer(blk, p, h, k_pool, v_pool):
            h, k, v = blk.apply_with_kv(p, h)
            return (h, prefill_write(k_pool, table, k, page_size=page_size),
                    prefill_write(v_pool, table, v, page_size=page_size))

        h, cache = self._thread_cache(params, cache, h, layer)
        h, _ = self.ln_f.apply(params["ln_f"], {}, h)
        last = jnp.take_along_axis(
            h, jnp.maximum(lengths - 1, 0)[:, None, None].astype(jnp.int32),
            axis=1)[:, 0]                                    # (B, hidden)
        logits = last @ jnp.asarray(params["logits_kernel"], last.dtype)
        return logits.astype(jnp.float32), cache

    def prefill_from(self, params, cache, ids, start, lengths, table, *,
                     page_size: int):
        """Chunked SUFFIX prefill: run the tokens from the divergence point
        of a shared-prefix hit against an already-populated cache prefix.

        ``ids``: (B, T_bucket) int32 — the suffix tokens, occupying
        positions ``start .. start + T_bucket - 1``; ``start``: (B,) int32
        — the first position to compute (everything below it is already in
        the cache via shared prefix pages); ``lengths``: (B,) — the TOTAL
        true prompt length (``start + true suffix length``). ``table`` must
        map every position below ``lengths`` to a real page and positions
        the bucket padding spills into to scratch. Suffix token ``i``
        attends causally to the whole cached prefix plus suffix tokens
        ``<= i`` (the speculative verify step's masking, reused block by
        block); padding rows' K/V land in-page past the true length,
        invisible through the length mask and overwritten by decode before
        ever becoming visible. Returns ``(logits (B, V) f32 — at position
        ``lengths - 1``, cache)``. With ``start == 0`` this is semantically
        :meth:`prefill` (modulo write path); the warm/cold bit-identity
        tests pin that equivalence.
        """
        start = jnp.asarray(start, jnp.int32)
        lengths = jnp.asarray(lengths, jnp.int32)
        return self.prefill_chunk(params, cache, ids, start, lengths - start,
                                  table, page_size=page_size)

    def prefill_chunk(self, params, cache, ids, n_done, n_valid, table, *,
                      page_size: int):
        """One fixed-shape prefill CHUNK: run ``ids`` against a cache that
        already holds ``n_done`` tokens of the same prompt — the
        :meth:`prefill_from` machinery generalized from "resume after a
        cached prefix" to "resume after any boundary", so a long prompt is
        many identical chunk dispatches instead of one whole-prompt bucket.

        ``ids``: (B, chunk_tokens) int32 — tokens at positions ``n_done ..
        n_done + chunk_tokens - 1``, right-padded past ``n_valid``;
        ``n_done``: (B,) int32 — tokens already written to the cache (page
        boundary NOT required: a chunk may start mid-page, the verify-step
        write path scatters per position); ``n_valid``: (B,) int32 — true
        tokens in this chunk (``<= chunk_tokens``; the final chunk of a
        prompt is short). ``table`` must be wide enough for every position
        this chunk writes (``(n_done + chunk_tokens - 1) // page_size + 1``
        pages) with entries past the allocated rows pointing at scratch —
        padding-lane K/V land in scratch and their keys read back masked,
        so they contribute exactly 0.0 to every softmax (bit-neutral).
        Returns ``(logits (B, V) f32 — at position ``n_done + n_valid - 1``,
        cache)``; compiled ONCE per (chunk_tokens, B).
        """
        ids = jnp.asarray(ids, jnp.int32)
        n_done = jnp.asarray(n_done, jnp.int32)
        n_valid = jnp.asarray(n_valid, jnp.int32)
        t = ids.shape[1]
        positions = n_done[:, None] + jnp.arange(t, dtype=jnp.int32)[None]
        h = jnp.take(params["token_embeddings"], ids, axis=0)
        h = h + jnp.take(params["pos_embeddings"], positions, axis=0)
        h = as_compute(h)
        h, cache = self._thread_cache(
            params, cache, h,
            lambda blk, p, h, k_pool, v_pool: blk.verify_step(
                p, h, k_pool, v_pool, table, n_done, page_size=page_size))
        h, _ = self.ln_f.apply(params["ln_f"], {}, h)
        last_row = jnp.maximum(n_valid - 1, 0)
        last = jnp.take_along_axis(
            h, last_row[:, None, None].astype(jnp.int32), axis=1)[:, 0]
        logits = last @ jnp.asarray(params["logits_kernel"], last.dtype)
        return logits.astype(jnp.float32), cache

    def decode_step(self, params, cache, ids, lengths, table, seeds,
                    token_idx, temperature, *, page_size: int,
                    top_k: int = 0):
        """One fixed-shape decode step over every slot.

        ``ids``: (B,) int32 — the token sampled by the previous step (or
        prefill); ``lengths``: (B,) — tokens already cached, i.e. the
        position ``ids`` occupies; ``seeds``/``token_idx``/``temperature``:
        (B,) per-request sampling state (see
        :func:`analytics_zoo_tpu.ops.kv_cache.sample_tokens`). Returns
        ``(next_ids (B,) int32, logits (B, V) f32, cache)`` — one pool per
        layer, the same pytree with identical shapes in and out (the
        decode-shape-stability invariant), so a donated cache is written
        where it lies.
        """
        from ..ops.kv_cache import sample_tokens

        ids = jnp.asarray(ids, jnp.int32)
        lengths = jnp.asarray(lengths, jnp.int32)
        h = jnp.take(params["token_embeddings"], ids, axis=0)[:, None]
        h = h + jnp.take(params["pos_embeddings"], lengths, axis=0)[:, None]
        h = as_compute(h)
        h, cache = self._thread_cache(
            params, cache, h,
            lambda blk, p, h, k_pool, v_pool: blk.decode_step(
                p, h, k_pool, v_pool, table, lengths, page_size=page_size))
        h, _ = self.ln_f.apply(params["ln_f"], {}, h)
        logits = (h[:, 0] @ jnp.asarray(params["logits_kernel"], h.dtype)
                  ).astype(jnp.float32)
        next_ids = sample_tokens(logits, seeds, token_idx, temperature,
                                 top_k=top_k)
        return next_ids, logits, cache

    def verify_step(self, params, cache, ids, lengths, table, seeds,
                    token_idx, temperature, *, page_size: int,
                    top_k: int = 0):
        """One fixed-shape speculative VERIFY step: score ``k`` tokens per
        slot in one dispatch (the multi-token twin of :meth:`decode_step`).

        ``ids``: (B, k) int32 — column 0 is the previous step's sampled
        token (certain), columns 1..k-1 the drafted continuation; they
        occupy positions ``lengths .. lengths + k - 1`` (the caller has
        pages allocated through position ``lengths + k - 1``).
        ``token_idx``: (B,) — ordinal of the FIRST token this step emits.
        Returns ``(accepted (B,) int32, tokens (B, k) int32, draft_probs
        (B, k-1) f32, cache)`` — ``tokens[:, :accepted+1]`` are the emitted
        tokens (see :func:`analytics_zoo_tpu.ops.speculative.
        verify_draft_tokens`); cache shapes identical in and out, same as
        the decode step (ONE compiled executable per (k, slot-count)).
        """
        from ..ops.speculative import verify_draft_tokens

        ids = jnp.asarray(ids, jnp.int32)
        lengths = jnp.asarray(lengths, jnp.int32)
        k = ids.shape[1]
        positions = lengths[:, None] + jnp.arange(k, dtype=jnp.int32)[None]
        h = jnp.take(params["token_embeddings"], ids, axis=0)
        h = h + jnp.take(params["pos_embeddings"], positions, axis=0)
        h = as_compute(h)
        h, cache = self._thread_cache(
            params, cache, h,
            lambda blk, p, h, k_pool, v_pool: blk.verify_step(
                p, h, k_pool, v_pool, table, lengths, page_size=page_size))
        h, _ = self.ln_f.apply(params["ln_f"], {}, h)
        logits = (h @ jnp.asarray(params["logits_kernel"], h.dtype)
                  ).astype(jnp.float32)                       # (B, k, V)
        accepted, tokens, draft_probs = verify_draft_tokens(
            logits, ids[:, 1:], seeds, token_idx, temperature, top_k=top_k)
        return accepted, tokens, draft_probs, cache

    def compute_output_shape(self, input_shape):
        return tuple(input_shape) + (self.vocab,)

    def constructor_config(self):
        return dict(vocab=self.vocab, hidden_size=self.hidden_size,
                    n_block=self.n_block, n_head=self.blocks[0].attn.n_head,
                    seq_len=self.seq_len,
                    intermediate_size=self.intermediate_size,
                    attn_strategy=self.attn_strategy, remat=self.remat)


@register_model("PipelinedTransformerLM")
class PipelinedTransformerLM(Layer, KerasNet):
    """TransformerLM whose blocks run as a GPipe pipeline over the ``pp`` axis.

    The pp *training-engine strategy*: block parameters are built STACKED on a
    leading ``(n_block, ...)`` axis (one pytree, congruent across blocks), the
    Estimator shards that axis over ``pp`` via :meth:`param_spec`, and
    ``apply`` runs the blocks through
    :func:`analytics_zoo_tpu.parallel.pipeline_apply` — the ``lax.scan`` +
    ``ppermute`` GPipe schedule, differentiable end to end, so
    ``Estimator.fit`` trains through the pipeline with no engine special
    cases. Embeddings / final LN / LM head stay replicated outside the
    pipeline (they are O(tokens·H) next to the blocks' O(tokens·H²)).

    Off a pp mesh (pp==1 or no context) the same model applies its blocks
    sequentially, so one checkpoint format serves both layouts.

    Parity: the reference has no pipeline engine (single-node BigDL); this is
    the TPU-native extension point SURVEY §2.2 marks as the pp row.
    """

    def __init__(self, vocab: int, hidden_size: int = 256, n_block: int = 4,
                 n_head: int = 8, seq_len: int = 512,
                 intermediate_size: Optional[int] = None,
                 n_microbatches: int = 4, attn_strategy: str = "full",
                 name=None):
        super().__init__(name=name)
        self.vocab = vocab
        self.hidden_size = hidden_size
        self.n_block = n_block
        self.seq_len = seq_len
        self.intermediate_size = intermediate_size
        self.n_microbatches = n_microbatches
        self.attn_strategy = attn_strategy
        # ONE block instance: all blocks share structure; per-block params
        # live on the stacked leading axis
        self.block = TransformerLayer(hidden_size, n_head, intermediate_size,
                                      causal=True, attn_strategy=attn_strategy,
                                      name=f"{self.name}_block")
        self.ln_f = LayerNormalization(name=f"{self.name}_lnf")
        self.layers = [self.block, self.ln_f]

    @property
    def input_shape(self):
        return (self.seq_len,)

    def build(self, rng, input_shape=None):
        ks = jax.random.split(rng, self.n_block + 4)
        params = {
            "token_embeddings": jax.random.normal(
                ks[0], (self.vocab, self.hidden_size), param_dtype()) * 0.02,
            "pos_embeddings": jax.random.normal(
                ks[1], (self.seq_len, self.hidden_size), param_dtype()) * 0.02,
            "logits_kernel": get_initializer("glorot_uniform")(
                ks[2], (self.hidden_size, self.vocab), param_dtype()),
        }
        per_block = [self.block.build(ks[3 + i], (None, self.hidden_size))[0]
                     for i in range(self.n_block)]
        from ..parallel.pipeline import stack_stage_params

        params["blocks"] = stack_stage_params(per_block)
        lnf, _ = self.ln_f.build(ks[-1], (None, self.hidden_size))
        params["ln_f"] = lnf
        return params, {}

    def _pp_mesh(self):
        try:
            from ..common.context import get_zoo_context

            mesh = get_zoo_context(auto_init=False).mesh
        except RuntimeError:
            return None, 1
        pp = mesh.shape.get("pp", 1) if mesh is not None else 1
        return (mesh, pp) if pp > 1 else (None, 1)

    def param_spec(self, path, leaf):
        """``(path, leaf) -> PartitionSpec`` for Estimator(param_sharding=...):
        stacked block leaves shard their leading block axis over ``pp``
        (each device holds exactly its stage's weights, the GPipe layout);
        everything else is replicated.

        Matches a path key that IS ``'blocks'`` — a substring test would
        also capture unrelated params that merely mention "blocks" in a
        nested name and mis-shard them. The key sits below the train
        state's own (``params``; ``opt_state`` and the optimizer's moment
        containers), so it is looked for anywhere on the path: testing
        ``path[0]`` placed every stacked leaf whole on every device."""
        from jax.sharding import PartitionSpec as P

        if (any(getattr(k, "key", None) == "blocks" for k in path)
                and getattr(leaf, "ndim", 0) >= 1):
            _, pp = self._pp_mesh()
            if pp > 1 and self.n_block % pp:
                raise ValueError(
                    f"n_block={self.n_block} is not divisible by the mesh's "
                    f"pp={pp}: pipeline stages must hold equal block counts. "
                    f"Choose n_block as a multiple of pp (or shrink pp).")
            return P("pp")
        return P()

    def _apply_block_stack(self, stacked, h, training):
        """Sequentially apply ``k`` stacked blocks (leaves (k, ...)) — the
        per-stage body inside the pipeline, and the whole model off-mesh."""
        k = jax.tree_util.tree_leaves(stacked)[0].shape[0]
        for j in range(k):
            p_j = jax.tree_util.tree_map(lambda p: p[j], stacked)
            h, _ = self.block.apply(p_j, {}, h, training=training)
        return h

    def apply_features(self, params, x, *, training=False, rng=None):
        ids = jnp.asarray(x, jnp.int32)
        h = jnp.take(params["token_embeddings"], ids, axis=0)
        h = h + params["pos_embeddings"][: ids.shape[1]][None]
        h = as_compute(h)
        mesh, pp = self._pp_mesh()
        if pp > 1:
            if self.n_block % pp:
                raise ValueError(f"n_block={self.n_block} not divisible by "
                                 f"pp={pp}")
            from ..parallel.pipeline import pipeline_apply

            k = self.n_block // pp
            # (n_block, ...) -> (pp, k, ...): sharded P('pp') on the leading
            # axis this regroup is device-local (contiguous blocks per stage)
            stages = jax.tree_util.tree_map(
                lambda p: p.reshape((pp, k) + p.shape[1:]), params["blocks"])
            h = pipeline_apply(
                lambda sp, a: self._apply_block_stack(sp, a, training),
                stages, h, mesh, n_microbatches=self.n_microbatches)
        else:
            h = self._apply_block_stack(params["blocks"], h, training)
        h, _ = self.ln_f.apply(params["ln_f"], {}, h)
        return h

    def apply(self, params, state, x, *, training=False, rng=None):
        h = self.apply_features(params, x, training=training, rng=rng)
        logits = h @ jnp.asarray(params["logits_kernel"], h.dtype)
        return logits, state

    def compute_output_shape(self, input_shape):
        return tuple(input_shape) + (self.vocab,)

    def constructor_config(self):
        return dict(vocab=self.vocab, hidden_size=self.hidden_size,
                    n_block=self.n_block, n_head=self.block.attn.n_head,
                    seq_len=self.seq_len,
                    intermediate_size=self.intermediate_size,
                    n_microbatches=self.n_microbatches,
                    attn_strategy=self.attn_strategy)


def lm_loss(y_true, logits):
    """Next-token cross entropy over (B, T) int targets and (B, T, V) logits.

    lse-form (CE = logsumexp(z) − z[label]) so only (B, T) reductions
    materialize in f32 — the log_softmax form writes a second full (B, T, V)
    f32 tensor, which at batch 32 × seq 2048 × 32k vocab is 8 GB of HBM
    traffic per step for no mathematical difference."""
    logits = jnp.asarray(logits, jnp.float32)
    labels = jnp.asarray(y_true, jnp.int32)
    lse = jax.nn.logsumexp(logits, axis=-1)                      # (B, T)
    picked = jnp.take_along_axis(logits, labels[..., None],
                                 axis=-1)[..., 0]                # (B, T)
    return jnp.mean(lse - picked)
