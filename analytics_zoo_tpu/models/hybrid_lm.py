"""HybridLM — a decoder whose layers are of more than one kind.

One description says what the model is: ``layer_types``, the kind of sequence
mixer of each layer (``"full_attention"``:
:class:`~analytics_zoo_tpu.nn.layers.mixers.QKNormAttention`, which caches K
and V in pages; ``"linear_attention"``:
:class:`~analytics_zoo_tpu.nn.layers.mixers.GatedDeltaNet`, which keeps a
fixed-size state a slot). Everything that visits the layers is
:class:`~analytics_zoo_tpu.models.decoder.CachedDecoder`'s, which walks that
one list and hands each layer its own part of the cache; what is written here
is the model's own. The architecture is Olmo-Hybrid's (``model_type:
olmo_hybrid``): no position signal at all, and the Olmo 2 / Olmo 3 block,
whose RMS norms sit on the output of each branch, inside the residual:

    h = E[ids]
    h = h + RMS(Mixer_l(h));  h = h + RMS(W_down(silu(h W_gate) * (h W_up)))
    logits = RMS(h) W_head

Serving: :class:`~analytics_zoo_tpu.serving.generation.ContinuousBatcher`
serves it as it serves ``TransformerLM`` (the same entry points). What follows
from a linear layer's per-slot state: a prefill writes the slots it is told
(``slots``; given none, slots ``0 .. B-1``), a decode step leaves the state of
a row that holds no stream (its page-table row is all scratch) exactly as it
was, and speculation, prefix reuse, chunked prefill and preemption, which
would each have to snapshot or resume the recurrent state, are refused by the
batcher in words. A pattern of full attention alone keeps pages alone and is
refused nothing.
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax
import jax.numpy as jnp

from ..nn.layers.mixers import GatedDeltaNet, GatedMLP, QKNormAttention
from ..nn.layers.normalization import rms_norm
from ..nn.module import as_compute, get_initializer, param_dtype
from .common.zoo_model import register_model
from .decoder import CachedDecoder

FULL, LINEAR = "full_attention", "linear_attention"


@register_model("HybridLM")
class HybridLM(CachedDecoder):
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

    # ------------------------------------- what CachedDecoder asks of a model

    def _embed(self, params, ids, positions=None):
        return as_compute(jnp.take(params["token_embeddings"], ids, axis=0))

    def _block(self, i, params, h, mix):
        p = params[f"layer{i}"]
        y, state = mix(p["mixer"], h)
        h = h + rms_norm(y, p["mixer_norm"], self.epsilon)
        y, _ = self.mlp.apply(p["mlp"], {}, h)
        return h + rms_norm(y, p["mlp_norm"], self.epsilon), state

    def _head(self, params, h, one=None):
        # the one position wanted, then the norm and the matmul, its unit
        # axis dropped last
        if one is not None:
            h = one(h)
        h = rms_norm(h, params["final_norm"], self.epsilon)
        logits = h @ jnp.asarray(params["logits_kernel"], h.dtype)
        return logits if one is None else logits[:, 0]

    def constructor_config(self):
        return dict(self._config)


__all__ = ["HybridLM"]
