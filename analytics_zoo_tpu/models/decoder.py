"""CachedDecoder — what the decoder-only language models of the tree share.

A decoder is an embedding, a list of *mixers* (:mod:`~analytics_zoo_tpu.nn.
layers.mixers`: the part of a layer that moves information between positions,
and the only part that keeps anything between steps), a block around each, and
a head. Everything that visits the layers with a cache walks that one list
(:meth:`CachedDecoder._walk`) and hands each layer its own leaves of the
cache, so ``prefill``, ``prefill_chunk``, ``prefill_from``, ``decode_step``,
``verify_step`` and ``init_kv_cache`` are written once, here, whatever the
architecture. A model supplies what is really its own:

* ``build`` / ``cast_at_use``: its parameter tree;
* ``self.mixers``: one entry a layer, a mixer or, for a layer that runs
  several side by side on the same input, a tuple of them; and
  ``self.seq_len``;
* ``_embed(params, ids, positions=None)``: ids ``(...)`` -> hidden states
  ``(..., hidden)`` in the compute dtype; ``positions`` of the same shape as
  ``ids``, or None for ``0 .. T-1`` (a model with no position signal ignores
  them);
* ``_block(i, params, h, mix)``: layer ``i``'s block around its mixer, called
  as ``mix(mixer_params, x) -> (y, state)``; returns ``(h, state)``. For a
  layer of several mixers ``mix`` is a tuple of such functions, in the
  entry's order, and ``state`` the states they returned, merged;
* ``_head(params, h, one=None)``: final norm and LM head, (B, T, hidden) ->
  (B, T, V); or, given ``one``, a function that leaves one position a sequence
  (B, 1, hidden), the logits of that position, (B, V). Where ``one`` is
  applied and where the unit axis is dropped is the model's: each keeps the
  order its programs have always had (on the CPU a bfloat16 matmul rounds
  otherwise with the unit axis than without).

Serving: :class:`~analytics_zoo_tpu.serving.generation.ContinuousBatcher`
calls these entry points and nothing else of a model. What it offers follows
from what the mixers keep (``state_kind``): pages can be shared, written k
tokens at a time and resumed at any boundary, so a model of pages alone gets
the prefix cache, speculation, chunked prefill and preemption; a per-slot
state can be none of these, and the batcher refuses them in words for a model
with one such layer (``KVCacheConfig.slot_state``).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from ..nn.module import Layer, compute_dtype
from ..nn.topology import KerasNet
from ..ops.kv_cache import (PAGES, SCRATCH_PAGE, SLOT, KVCacheConfig,
                            StepContext, init_cache, sample_tokens)


def _side_by_side(layer):
    """The mixers of one entry of ``self.mixers``."""
    return layer if isinstance(layer, tuple) else (layer,)


def _last(counts):
    """``one`` of ``_head``: (B, T, hidden) -> (B, 1, hidden), the last of the
    first ``counts[b]`` positions of sequence ``b``."""
    return lambda h: jnp.take_along_axis(
        h, jnp.maximum(counts - 1, 0)[:, None, None], axis=1)


class CachedDecoder(Layer, KerasNet):
    """Base of ``TransformerLM`` and ``HybridLM`` (module docstring). Every
    entry point is a pure function of ``(params, cache, ...)`` with shapes
    fixed by the :class:`KVCacheConfig`, so each compiles once a (batch,
    bucket)."""

    @property
    def input_shape(self):
        return (self.seq_len,)

    def compute_output_shape(self, input_shape):
        return tuple(input_shape) + (self.vocab,)

    def init_kv_cache(self, n_slots: int, *, page_size: int = 16,
                      max_seq_len: Optional[int] = None,
                      n_pages: Optional[int] = None, dtype=None):
        """``(KVCacheConfig, cache)`` for ``n_slots`` concurrent sequences:
        ``{"k": (k_0, ...), "v": (v_0, ...)}``, one pool ``(n_pages,
        page_size, pool_heads, head_dim)`` for each layer with a mixer that
        keeps pages, and for each with one that keeps a slot state the leaves
        its ``slot_state`` names, ``(n_slots, ...)`` each
        (:func:`~analytics_zoo_tpu.ops.kv_cache.init_cache`)."""
        max_seq = int(max_seq_len or self.seq_len)
        pps = -(-max_seq // page_size)          # ceil: full pages only
        if pps * page_size > self.seq_len:
            # the ROUNDED capacity is what decode positions can reach, and a
            # position past a position table would clamp to its last row
            raise ValueError(
                f"max_seq_len {max_seq} rounds up to {pps * page_size} (full "
                f"pages of {page_size}), beyond the {self.seq_len} positions "
                f"the model is declared for; choose max_seq_len <= "
                f"{self.seq_len // page_size * page_size}")
        dtype = dtype or compute_dtype()
        # a layer of one mixer names its kind as a word, as it always has
        kinds = tuple(layer.state_kind if not isinstance(layer, tuple)
                      else tuple(m.state_kind for m in layer)
                      for layer in self.mixers)
        every = [m for layer in self.mixers for m in _side_by_side(layer)]
        paged = next((m for m in every if m.state_kind == PAGES), None)
        slot = next((m for m in every if m.state_kind == SLOT), None)
        cfg = KVCacheConfig(
            n_layers=len(self.mixers),
            n_heads=paged.pool_heads if paged else 1,
            head_dim=paged.head_dim if paged else 1,
            n_slots=n_slots, page_size=page_size, pages_per_slot=pps,
            n_pages=n_pages, dtype=dtype,
            layer_kinds=kinds if slot else (),
            slot_state=slot.slot_state(dtype) if slot else ())
        return cfg, init_cache(cfg)

    def apply(self, params, state, x, *, training=False, rng=None):
        """The teacher-forced forward, no cache: (B, T) ids -> (B, T, V)."""
        h, _ = self._walk(
            params, self._embed(params, jnp.asarray(x, jnp.int32)),
            lambda mixer, p, h, _: mixer.apply(p, {}, h, training=training))
        return self._head(params, h), state

    # ---------------------------------------------------------- the walker

    def _walk(self, params, h, mix, cache=None):
        """Every layer in order: ``mix(mixer, params, h, state) -> (y,
        state)`` is the layer's mixer in whatever form the caller runs
        (whole sequence, prefill, decode), ``state`` that mixer's own leaves
        of ``cache`` (None without one); the block around it is the model's
        (``_block``), which is handed one such function a mixer of the layer.
        Returns ``(h, cache)``, the cache with the structure it came in: no
        leaf is sliced out of or stored back into a larger array, so with the
        cache donated every leaf aliases input to output and a mixer's scatter
        writes where the leaf lies."""
        new = None if cache is None else {k: list(v) for k, v in cache.items()}
        kept = [[m.state_kind for m in _side_by_side(layer)]
                for layer in self.mixers]

        def kind_of(name):
            return PAGES if name in ("k", "v") else SLOT

        for i, layer in enumerate(self.mixers):
            # layer i's leaves of a kind are the j-th of that kind in the
            # cache (KVCacheConfig.index_in_kind)
            at = {kind: sum(kind in kinds for kinds in kept[:i])
                  for kind in kept[i]}

            def run(p, x, mixer):   # what the block calls, once, right now
                state = None if cache is None else {
                    name: cache[name][at[mixer.state_kind]] for name in cache
                    if kind_of(name) == mixer.state_kind}
                with jax.named_scope(mixer.scope):
                    return mix(mixer, p, x, state)

            runs = tuple(functools.partial(run, mixer=m)
                         for m in _side_by_side(layer))
            h, state = self._block(
                i, params, h, runs if isinstance(layer, tuple) else runs[0])
            if cache is not None:
                for name, leaf in state.items():
                    new[name][at[kind_of(name)]] = leaf
        if new is not None:
            new = {k: tuple(v) for k, v in new.items()}
        return h, new

    # ------------------------------------------------------ decode serving

    def prefill(self, params, cache, ids, lengths, table, *, page_size: int,
                slots=None):
        """One batched forward that fills the cache and returns last-token
        logits. ``ids``: (B, T_bucket) int32, right-padded to a bucket that
        ``page_size`` divides; ``lengths``: (B,) true prompt lengths;
        ``table``: (B, pages_per_slot) page tables (entries past the
        allocated prefix = scratch); ``slots``: (B,) int32, the slot each row
        fills (default ``0 .. B-1``; a model of pages alone ignores them).
        The pages take K and V of the bucket (causal masking means padding is
        never attended by a valid query, so its scratch writes are inert); a
        slot's state is that of its TRUE length, written whole, so nothing of
        the slot's last stream is left. Returns ``(logits (B, V) f32 at
        position length - 1, cache)``."""
        ids = jnp.asarray(ids, jnp.int32)
        lengths = jnp.asarray(lengths, jnp.int32)
        at = StepContext(
            jnp.asarray(table, jnp.int32), lengths, page_size,
            slots=(jnp.arange(lengths.shape[0], dtype=jnp.int32)
                   if slots is None else jnp.asarray(slots, jnp.int32)))
        h, cache = self._walk(
            params, self._embed(params, ids),
            lambda mixer, p, h, state: mixer.prefill(p, h, state, at), cache)
        logits = self._head(params, h, _last(lengths))
        return logits.astype(jnp.float32), cache

    def _step(self, params, cache, h, first, table, *, page_size: int):
        """The cached step behind ``decode_step`` (one new token a row),
        ``verify_step`` and ``prefill_chunk`` (several): ``h`` (B, T, hidden),
        the embedded tokens at positions ``first .. first + T - 1``, written
        into the cache and attended in one pass. A row whose table row is all
        scratch holds no stream: its K and V land in scratch, and its slot
        state stays as it was. Returns ``(h, cache)``."""
        table = jnp.asarray(table, jnp.int32)
        at = StepContext(table, first, page_size,
                         live=table[:, 0] != SCRATCH_PAGE)
        return self._walk(
            params, h,
            lambda mixer, p, h, state: mixer.decode(p, h, state, at), cache)

    def _embed_from(self, params, ids, first):
        """``ids`` (B, T) at positions ``first .. first + T - 1``."""
        each = first[:, None] + jnp.arange(ids.shape[1], dtype=jnp.int32)[None]
        return self._embed(params, ids, each)

    def decode_step(self, params, cache, ids, lengths, table, seeds,
                    token_idx, temperature, *, page_size: int,
                    top_k: int = 0):
        """One fixed-shape decode step over every slot. ``ids``: (B,) int32,
        the token sampled by the previous step (or prefill); ``lengths``:
        (B,), tokens already cached, i.e. the position ``ids`` occupies;
        ``seeds`` / ``token_idx`` / ``temperature``: (B,) per-request
        sampling state (:func:`~analytics_zoo_tpu.ops.kv_cache.
        sample_tokens`). Returns ``(next_ids (B,) int32, logits (B, V) f32,
        cache)``: the same pytree with identical shapes in and out (the
        decode-shape-stability invariant), so a donated cache is written
        where it lies; a row the batcher sits out for a step (all scratch)
        resumes from the state it had."""
        ids = jnp.asarray(ids, jnp.int32)
        lengths = jnp.asarray(lengths, jnp.int32)
        h, cache = self._step(
            params, cache, self._embed(params, ids, lengths)[:, None],
            lengths, table, page_size=page_size)
        # one token a row: the position wanted is the only one
        logits = self._head(params, h, lambda h: h).astype(jnp.float32)
        next_ids = sample_tokens(logits, seeds, token_idx, temperature,
                                 top_k=top_k)
        return next_ids, logits, cache

    def verify_step(self, params, cache, ids, lengths, table, seeds,
                    token_idx, temperature, *, page_size: int,
                    top_k: int = 0):
        """One fixed-shape speculative VERIFY step: score ``k`` tokens per
        slot in one dispatch (the multi-token twin of :meth:`decode_step`).
        ``ids``: (B, k) int32, column 0 the previous step's sampled token
        (certain), columns 1..k-1 the drafted continuation; they occupy
        positions ``lengths .. lengths + k - 1`` (the caller has pages
        allocated through the last). ``token_idx``: (B,), ordinal of the
        FIRST token this step emits. Returns ``(accepted (B,) int32, tokens
        (B, k) int32, draft_probs (B, k-1) f32, cache)``;
        ``tokens[:, :accepted+1]`` are the emitted tokens
        (:func:`~analytics_zoo_tpu.ops.speculative.verify_draft_tokens`);
        ONE compiled executable per (k, slot-count)."""
        from ..ops.speculative import verify_draft_tokens

        ids = jnp.asarray(ids, jnp.int32)
        lengths = jnp.asarray(lengths, jnp.int32)
        h, cache = self._step(
            params, cache, self._embed_from(params, ids, lengths), lengths,
            table, page_size=page_size)
        logits = self._head(params, h).astype(jnp.float32)      # (B, k, V)
        accepted, tokens, draft_probs = verify_draft_tokens(
            logits, ids[:, 1:], seeds, token_idx, temperature, top_k=top_k)
        return accepted, tokens, draft_probs, cache

    def prefill_chunk(self, params, cache, ids, n_done, n_valid, table, *,
                      page_size: int):
        """One fixed-shape prefill CHUNK: run ``ids`` against a cache that
        already holds ``n_done`` tokens of the same prompt, so a long prompt
        is many identical chunk dispatches instead of one whole-prompt
        bucket. ``ids``: (B, chunk_tokens) int32, tokens at positions
        ``n_done .. n_done + chunk_tokens - 1``, right-padded past
        ``n_valid``; ``n_done``: (B,) tokens already in the cache (a chunk
        may start mid-page: the write scatters per position); ``n_valid``:
        (B,) true tokens in this chunk (the final chunk of a prompt is
        short). ``table`` must be wide enough for every position this chunk
        writes (``(n_done + chunk_tokens - 1) // page_size + 1`` pages) with
        entries past the allocated rows pointing at scratch: padding-lane K/V
        land in scratch and their keys read back masked, so they contribute
        exactly 0.0 to every softmax (bit-neutral). Returns ``(logits (B, V)
        f32 at position n_done + n_valid - 1, cache)``; compiled ONCE per
        (chunk_tokens, B)."""
        ids = jnp.asarray(ids, jnp.int32)
        n_done = jnp.asarray(n_done, jnp.int32)
        n_valid = jnp.asarray(n_valid, jnp.int32)
        h, cache = self._step(
            params, cache, self._embed_from(params, ids, n_done), n_done,
            table, page_size=page_size)
        logits = self._head(params, h, _last(n_valid))
        return logits.astype(jnp.float32), cache

    def prefill_from(self, params, cache, ids, start, lengths, table, *,
                     page_size: int):
        """Chunked SUFFIX prefill: the tokens from the divergence point of a
        shared-prefix hit against an already-populated cache prefix. ``ids``:
        (B, T_bucket), the suffix, at positions ``start .. start + T_bucket -
        1``; ``start``: (B,) the first position to compute (everything below
        it is in the cache through shared prefix pages); ``lengths``: (B,)
        the TOTAL true prompt length. ``table`` maps every position below
        ``lengths`` to a real page and what the bucket padding spills into to
        scratch; padding rows' K/V land in-page past the true length,
        invisible through the length mask and overwritten by decode before
        ever becoming visible. Returns ``(logits (B, V) f32 at position
        lengths - 1, cache)``. With ``start == 0`` this is :meth:`prefill`
        but for the write path; the warm/cold bit-identity tests pin that."""
        start = jnp.asarray(start, jnp.int32)
        lengths = jnp.asarray(lengths, jnp.int32)
        return self.prefill_chunk(params, cache, ids, start, lengths - start,
                                  table, page_size=page_size)


__all__ = ["CachedDecoder"]
