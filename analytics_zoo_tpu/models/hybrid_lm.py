"""HybridLM — a decoder whose layers are of more than one kind.

One description says what the model is: ``layer_types``, the kind of sequence
mixer of each layer (``"full_attention"``:
:class:`~analytics_zoo_tpu.nn.layers.mixers.QKNormAttention`, which caches K
and V in pages; ``"linear_attention"``:
:class:`~analytics_zoo_tpu.nn.layers.mixers.GatedDeltaNet`, which keeps a
fixed-size state a slot). Everything that visits the layers walks that one
list (:meth:`HybridLM._walk`) and hands each layer its own part of the cache,
so ``apply``, ``prefill``, ``decode_step``, ``cast_at_use`` and
``init_kv_cache`` are each written once, not once a kind. The architecture is
Olmo-Hybrid's (``model_type: olmo_hybrid``): no position signal at all, and
the Olmo 2 / Olmo 3 block, whose RMS norms sit on the output of each branch,
inside the residual:

    h = E[ids]
    h = h + RMS(Mixer_l(h));  h = h + RMS(W_down(silu(h W_gate) * (h W_up)))
    logits = RMS(h) W_head

Serving: :class:`~analytics_zoo_tpu.serving.generation.ContinuousBatcher`
serves it as it serves ``TransformerLM`` (same ``prefill`` / ``decode_step``
contract), with two differences that follow from the per-slot state: a
prefill is told which slots it fills (``slots``; given none it fills slots
``0 .. B-1``), and a decode step leaves the state of a row that holds no
stream (its page-table row is all scratch) exactly as it was. There is no
``verify_step``, ``prefill_from`` or ``prefill_chunk``: speculation, prefix
reuse and chunked prefill would each have to snapshot or resume the recurrent
state, and the batcher refuses them for this model in words.
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax
import jax.numpy as jnp

from ..nn.layers.mixers import (GatedDeltaNet, GatedMLP, QKNormAttention,
                                StepContext)
from ..nn.layers.normalization import rms_norm
from ..nn.module import (Layer, as_compute, compute_dtype, get_initializer,
                         param_dtype)
from ..nn.topology import KerasNet
from ..ops.kv_cache import PAGES, SLOT
from .common.zoo_model import register_model

FULL, LINEAR = "full_attention", "linear_attention"


@register_model("HybridLM")
class HybridLM(Layer, KerasNet):
    """Decoder-only LM over int token ids (B, T) -> logits (B, T, V), built
    from ``layer_types`` (module docstring). ``n_layer`` keeps the first so
    many layers of the pattern (a deployment that holds one stage of the
    model); ``seq_len`` bounds a served sequence (no table depends on it)."""

    def __init__(self, vocab: int, hidden_size: int, intermediate_size: int,
                 layer_types: Sequence[str], n_head: int,
                 linear_num_heads: int, linear_key_head_dim: int,
                 linear_value_head_dim: int, linear_conv_kernel_dim: int = 4,
                 n_layer: Optional[int] = None, seq_len: int = 65536,
                 rms_norm_eps: float = 1e-6, attn_strategy: str = "auto",
                 name=None):
        super().__init__(name=name)
        layer_types = list(layer_types)[:n_layer]
        unknown = set(layer_types) - {FULL, LINEAR}
        if unknown or not layer_types:
            raise ValueError(f"layer_types must name {FULL!r} or {LINEAR!r} "
                             f"for each layer, got {sorted(unknown) or 'none'}")
        self.vocab = vocab
        self.hidden_size = hidden_size
        self.intermediate_size = intermediate_size
        self.layer_types = layer_types
        self.seq_len = seq_len
        self.epsilon = rms_norm_eps
        self._config = dict(
            vocab=vocab, hidden_size=hidden_size,
            intermediate_size=intermediate_size, layer_types=layer_types,
            n_head=n_head, linear_num_heads=linear_num_heads,
            linear_key_head_dim=linear_key_head_dim,
            linear_value_head_dim=linear_value_head_dim,
            linear_conv_kernel_dim=linear_conv_kernel_dim, seq_len=seq_len,
            rms_norm_eps=rms_norm_eps, attn_strategy=attn_strategy)
        self.mixers = [
            QKNormAttention(hidden_size, n_head, rms_norm_eps, attn_strategy,
                            name=f"{self.name}_layer{i}_attn")
            if kind == FULL else
            GatedDeltaNet(hidden_size, linear_num_heads, linear_key_head_dim,
                          linear_value_head_dim, linear_conv_kernel_dim,
                          rms_norm_eps, name=f"{self.name}_layer{i}_gdn")
            for i, kind in enumerate(layer_types)]
        self.mlp = GatedMLP(hidden_size, intermediate_size,
                            name=f"{self.name}_mlp")
        self.layers = list(self.mixers) + [self.mlp]
        # layer i's leaves are the _leaf_of[i]-th of its kind in the cache
        # (KVCacheConfig.index_in_kind)
        kinds = [m.state_kind for m in self.mixers]
        self._leaf_of = [kinds[:i].count(k) for i, k in enumerate(kinds)]

    @property
    def input_shape(self):
        return (self.seq_len,)

    def build(self, rng, input_shape=None):
        ks = jax.random.split(rng, 2 * len(self.mixers) + 2)
        ones = jnp.ones((self.hidden_size,), param_dtype())
        params = {
            "token_embeddings": jax.random.normal(
                ks[0], (self.vocab, self.hidden_size), param_dtype()) * 0.02,
            "final_norm": ones,
            "logits_kernel": get_initializer("glorot_uniform")(
                ks[1], (self.hidden_size, self.vocab), param_dtype()),
        }
        for i, mixer in enumerate(self.mixers):
            params[f"layer{i}"] = {
                "mixer": mixer.build(ks[2 + 2 * i])[0], "mixer_norm": ones,
                "mlp": self.mlp.build(ks[3 + 2 * i])[0], "mlp_norm": ones}
        return params, {}

    def cast_at_use(self, params):
        # what is read only through a cast to the compute dtype: the matmul
        # kernels and the embedding table (its rows are cast as they are
        # gathered, nothing is summed before); never a norm's scale
        flags = {"token_embeddings": True, "final_norm": False,
                 "logits_kernel": True}
        for i, mixer in enumerate(self.mixers):
            p = params[f"layer{i}"]
            flags[f"layer{i}"] = {
                "mixer": mixer.cast_at_use(p["mixer"]), "mixer_norm": False,
                "mlp": self.mlp.cast_at_use(p["mlp"]), "mlp_norm": False}
        return flags

    # ---------------------------------------------------------- the walker

    def _walk(self, params, h, mix, cache=None):
        """Every layer in order: ``mix(mixer, params, h, state) -> (y,
        state)`` is the layer's mixer in whatever form the caller runs
        (whole sequence, prefill, decode), ``state`` that layer's own leaves
        of ``cache`` (None without one); the block around it is written here,
        once. Returns ``(h, cache)``, the cache with the structure it came
        in: no leaf is sliced out of or stored back into a larger array, so
        a donated cache is updated where it lies."""
        new = None if cache is None else {k: list(v) for k, v in cache.items()}
        for i, mixer in enumerate(self.mixers):
            p = params[f"layer{i}"]
            state = None
            if cache is not None:
                j = self._leaf_of[i]
                names = [n for n in cache
                         if (n in ("k", "v")) == (mixer.state_kind == PAGES)]
                state = {name: cache[name][j] for name in names}
            with jax.named_scope(mixer.scope):
                y, state = mix(mixer, p["mixer"], h, state)
            h = h + rms_norm(y, p["mixer_norm"], self.epsilon)
            y, _ = self.mlp.apply(p["mlp"], {}, h)
            h = h + rms_norm(y, p["mlp_norm"], self.epsilon)
            if cache is not None:
                for name, leaf in state.items():
                    new[name][j] = leaf
        if new is not None:
            new = {k: tuple(v) for k, v in new.items()}
        return h, new

    def _embed(self, params, ids):
        return as_compute(jnp.take(params["token_embeddings"],
                                   jnp.asarray(ids, jnp.int32), axis=0))

    def _head(self, params, h):
        h = rms_norm(h, params["final_norm"], self.epsilon)
        return h @ jnp.asarray(params["logits_kernel"], h.dtype)

    # ------------------------------------------------------------- forward

    def apply(self, params, state, x, *, training=False, rng=None):
        h, _ = self._walk(
            params, self._embed(params, x),
            lambda mixer, p, h, _: mixer.apply(p, {}, h, training=training))
        return self._head(params, h), state

    # ------------------------------------------------------ decode serving

    def init_kv_cache(self, n_slots: int, *, page_size: int = 16,
                      max_seq_len: Optional[int] = None,
                      n_pages: Optional[int] = None, dtype=None):
        """``(KVCacheConfig, cache)`` for ``n_slots`` concurrent sequences:
        K and V pools for the full-attention layers only, and for the others
        the leaves of :meth:`GatedDeltaNet.slot_state`, ``(n_slots, ...)``
        each (:func:`~analytics_zoo_tpu.ops.kv_cache.init_cache`)."""
        from ..ops.kv_cache import KVCacheConfig, init_cache

        max_seq = int(max_seq_len or self.seq_len)
        pps = -(-max_seq // page_size)
        if pps * page_size > self.seq_len:
            raise ValueError(
                f"max_seq_len {max_seq} rounds up to {pps * page_size} (full "
                f"pages of {page_size}), beyond the {self.seq_len} positions "
                f"the model is declared for")
        dtype = dtype or compute_dtype()
        kinds = tuple(m.state_kind for m in self.mixers)
        full = next((m for m in self.mixers if m.state_kind == PAGES), None)
        linear = next((m for m in self.mixers if m.state_kind == SLOT), None)
        cfg = KVCacheConfig(
            n_layers=len(self.mixers),
            n_heads=full.pool_heads if full else 1,
            head_dim=full.head_dim if full else 1,
            n_slots=n_slots, page_size=page_size, pages_per_slot=pps,
            n_pages=n_pages, dtype=dtype, layer_kinds=kinds,
            slot_state=linear.slot_state(dtype) if linear else ())
        return cfg, init_cache(cfg)

    def prefill(self, params, cache, ids, lengths, table, *, page_size: int,
                slots=None):
        """One batched forward that fills the cache and returns last-token
        logits. ``ids``: (B, T_bucket) int32, right-padded; ``lengths``: (B,)
        true prompt lengths; ``table``: (B, pages_per_slot) page tables;
        ``slots``: (B,) int32, the slot each row fills (default ``0 .. B-1``).
        The pages take K and V of the bucket (padding lands in scratch); a
        slot's recurrent state and convolution tail are those of its TRUE
        length, written whole, so nothing of the slot's last stream is left.
        Returns ``(logits (B, V) f32 at position length - 1, cache)``."""
        lengths = jnp.asarray(lengths, jnp.int32)
        b = lengths.shape[0]
        at = StepContext(
            jnp.asarray(table, jnp.int32), lengths, page_size,
            slots=(jnp.arange(b, dtype=jnp.int32) if slots is None
                   else jnp.asarray(slots, jnp.int32)))
        h, cache = self._walk(
            params, self._embed(params, ids),
            lambda mixer, p, h, state: mixer.prefill(p, h, state, at),
            cache)
        last = jnp.take_along_axis(
            h, jnp.maximum(lengths - 1, 0)[:, None, None], axis=1)
        return self._head(params, last)[:, 0].astype(jnp.float32), cache

    def decode_step(self, params, cache, ids, lengths, table, seeds,
                    token_idx, temperature, *, page_size: int,
                    top_k: int = 0):
        """One fixed-shape decode step over every slot; arguments and
        results as ``TransformerLM.decode_step``. A row whose table row is
        all scratch holds no stream: its K and V land in scratch, and its
        recurrent state and convolution tail stay as they were (a row the
        batcher sits out for a step resumes from the state it had)."""
        from ..ops.kv_cache import SCRATCH_PAGE, sample_tokens

        table = jnp.asarray(table, jnp.int32)
        at = StepContext(table, jnp.asarray(lengths, jnp.int32), page_size,
                         live=table[:, 0] != SCRATCH_PAGE)
        h, cache = self._walk(
            params, self._embed(params, ids)[:, None],
            lambda mixer, p, h, state: mixer.decode(p, h, state, at),
            cache)
        logits = self._head(params, h)[:, 0].astype(jnp.float32)
        next_ids = sample_tokens(logits, seeds, token_idx, temperature,
                                 top_k=top_k)
        return next_ids, logits, cache

    def compute_output_shape(self, input_shape):
        return tuple(input_shape) + (self.vocab,)

    def constructor_config(self):
        return dict(self._config)


__all__ = ["HybridLM"]
