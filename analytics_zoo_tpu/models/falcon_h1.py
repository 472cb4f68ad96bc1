"""FalconH1LM — a decoder whose every layer runs two mixers side by side.

The architecture is Falcon-H1's (``model_type: falcon_h1``): each layer norms
its input once and hands it to a grouped-KV rotary attention
(:class:`~analytics_zoo_tpu.nn.layers.mixers.RotaryGQAttention`, which caches
K and V in pages) and to a Mamba-2 state-space mixer
(:class:`~analytics_zoo_tpu.nn.layers.mixers.Mamba2Mixer`, which keeps a
fixed-size state a slot), adds both into the residual, then a gated SiLU MLP.
Its muP scalars are part of the mathematics, not of the initialisation. With
``RMS(x; w) = x / sqrt(mean(x^2) + eps) * w``:

    h = E[ids] * embedding_multiplier
    u = RMS(h; w_in)
    h = h + Attn(u * attention_in_multiplier) * attention_out_multiplier
          + SSM(u) * ssm_out_multiplier
    u = RMS(h; w_ff)
    h = h + W_down(silu((u W_gate) * mlp_multipliers[0]) * (u W_up)) * mlp_multipliers[1]
    logits = (RMS(h; w_f) W_head) * lm_head_multiplier

``key_multiplier`` lives in the attention mixer, ``ssm_in_multiplier`` and the
five ``ssm_multipliers`` in the state-space mixer. Everything that visits the
layers is :class:`~analytics_zoo_tpu.models.decoder.CachedDecoder`'s; a layer's
entry of ``self.mixers`` is the pair, so each layer keeps both kinds of state
and the walker hands each mixer its own leaves.

Serving: :class:`~analytics_zoo_tpu.serving.generation.ContinuousBatcher`
serves it through the same entry points as every decoder. It keeps a per-slot
state, so it is served as ``HybridLM`` with linear layers is: a prefill writes
the slots it is told, a row that holds no stream keeps its state, and
speculation, prefix reuse, chunked prefill and preemption are refused in
words.
"""

from __future__ import annotations

from typing import Sequence

import jax
import jax.numpy as jnp

from ..nn.layers.mixers import GatedMLP, Mamba2Mixer, RotaryGQAttention
from ..nn.layers.normalization import rms_norm
from ..nn.module import as_compute, get_initializer, param_dtype
from .common.zoo_model import register_model
from .decoder import CachedDecoder


def _matmul(x, kernel):
    return x @ jnp.asarray(kernel, x.dtype)


def _scaled(x, by: float):
    """``x * by`` with the product in float32, handed back in ``x``'s dtype
    (most of the multipliers are no power of two)."""
    return (x.astype(jnp.float32) * by).astype(x.dtype)


@register_model("FalconH1LM")
class FalconH1LM(CachedDecoder):
    """Decoder-only LM over int token ids (B, T) -> logits (B, T, V) (module
    docstring). ``n_layer`` layers, all alike (a deployment that holds one
    stage of the model keeps the first so many); ``seq_len`` bounds a served
    sequence (no table depends on it)."""

    def __init__(self, vocab: int, hidden_size: int, intermediate_size: int,
                 n_layer: int, n_head: int, n_kv_head: int, head_dim: int,
                 mamba_n_heads: int, mamba_d_head: int, mamba_d_state: int,
                 mamba_n_groups: int = 1, mamba_d_conv: int = 4,
                 mamba_chunk_size: int = 128, rope_theta: float = 1e11,
                 seq_len: int = 262144, rms_norm_eps: float = 1e-5,
                 embedding_multiplier: float = 1.0,
                 lm_head_multiplier: float = 1.0,
                 attention_in_multiplier: float = 1.0,
                 attention_out_multiplier: float = 1.0,
                 key_multiplier: float = 1.0,
                 ssm_in_multiplier: float = 1.0,
                 ssm_out_multiplier: float = 1.0,
                 ssm_multipliers: Sequence[float] = (1.0,) * 5,
                 mlp_multipliers: Sequence[float] = (1.0, 1.0),
                 attn_strategy: str = "auto", name=None):
        super().__init__(name=name)
        self.vocab = vocab
        self.hidden_size = hidden_size
        self.seq_len = seq_len
        self.epsilon = rms_norm_eps
        self.embedding_multiplier = embedding_multiplier
        self.lm_head_multiplier = lm_head_multiplier
        self.attention_in_multiplier = attention_in_multiplier
        self.attention_out_multiplier = attention_out_multiplier
        self.ssm_out_multiplier = ssm_out_multiplier
        self.mlp_multipliers = tuple(mlp_multipliers)
        self._config = dict(
            vocab=vocab, hidden_size=hidden_size,
            intermediate_size=intermediate_size, n_layer=n_layer,
            n_head=n_head, n_kv_head=n_kv_head, head_dim=head_dim,
            mamba_n_heads=mamba_n_heads, mamba_d_head=mamba_d_head,
            mamba_d_state=mamba_d_state, mamba_n_groups=mamba_n_groups,
            mamba_d_conv=mamba_d_conv, mamba_chunk_size=mamba_chunk_size,
            rope_theta=rope_theta, seq_len=seq_len, rms_norm_eps=rms_norm_eps,
            embedding_multiplier=embedding_multiplier,
            lm_head_multiplier=lm_head_multiplier,
            attention_in_multiplier=attention_in_multiplier,
            attention_out_multiplier=attention_out_multiplier,
            key_multiplier=key_multiplier,
            ssm_in_multiplier=ssm_in_multiplier,
            ssm_out_multiplier=ssm_out_multiplier,
            ssm_multipliers=list(ssm_multipliers),
            mlp_multipliers=list(mlp_multipliers),
            attn_strategy=attn_strategy)
        self.mixers = [
            (RotaryGQAttention(hidden_size, n_head, n_kv_head, head_dim,
                               rope_theta, key_multiplier, attn_strategy,
                               name=f"{self.name}_layer{i}_attn"),
             Mamba2Mixer(hidden_size, mamba_n_heads, mamba_d_head,
                         mamba_d_state, mamba_n_groups, mamba_d_conv,
                         mamba_chunk_size, rms_norm_eps, ssm_in_multiplier,
                         ssm_multipliers, name=f"{self.name}_layer{i}_ssm"))
            for i in range(n_layer)]
        self.mlp = GatedMLP(hidden_size, intermediate_size,
                            name=f"{self.name}_mlp")
        self.layers = [m for pair in self.mixers for m in pair] + [self.mlp]

    def build(self, rng, input_shape=None):
        ks = jax.random.split(rng, 3 * len(self.mixers) + 2)
        ones = jnp.ones((self.hidden_size,), param_dtype())
        params = {
            "token_embeddings": jax.random.normal(
                ks[0], (self.vocab, self.hidden_size), param_dtype()) * 0.02,
            "final_norm": ones,
            "logits_kernel": get_initializer("glorot_uniform")(
                ks[1], (self.hidden_size, self.vocab), param_dtype()),
        }
        for i, (attn, ssm) in enumerate(self.mixers):
            mlp = self.mlp.build(ks[4 + 3 * i])[0]
            # drawn wider by what multiplies its product, as the mixers draw
            # their in-projections: the gate then sees a plain projection
            mlp["gate_kernel"] = _scaled(mlp["gate_kernel"],
                                         1.0 / self.mlp_multipliers[0])
            params[f"layer{i}"] = {
                "input_norm": ones,
                "attn": attn.build(ks[2 + 3 * i])[0],
                "ssm": ssm.build(ks[3 + 3 * i])[0],
                "mlp_norm": ones, "mlp": mlp}
        return params, {}

    def cast_at_use(self, params):
        # what is read only through a cast to the compute dtype: the matmul
        # kernels and the embedding table; never a norm's scale
        flags = {"token_embeddings": True, "final_norm": False,
                 "logits_kernel": True}
        for i, (attn, ssm) in enumerate(self.mixers):
            p = params[f"layer{i}"]
            flags[f"layer{i}"] = {
                "input_norm": False, "attn": attn.cast_at_use(p["attn"]),
                "ssm": ssm.cast_at_use(p["ssm"]), "mlp_norm": False,
                "mlp": self.mlp.cast_at_use(p["mlp"])}
        return flags

    # ------------------------------------- what CachedDecoder asks of a model

    def _embed(self, params, ids, positions=None):
        # no position signal here: the attention turns q and k by theirs
        return _scaled(as_compute(jnp.take(params["token_embeddings"], ids,
                                           axis=0)),
                       self.embedding_multiplier)

    def _block(self, i, params, h, mix):
        p = params[f"layer{i}"]
        attend, scan = mix
        u = rms_norm(h, p["input_norm"], self.epsilon)
        a, pages = attend(p["attn"], _scaled(u, self.attention_in_multiplier))
        s, slot = scan(p["ssm"], u)
        both = (a.astype(jnp.float32) * self.attention_out_multiplier
                + s.astype(jnp.float32) * self.ssm_out_multiplier)
        h = h + both.astype(h.dtype)
        u = rms_norm(h, p["mlp_norm"], self.epsilon)
        gate, down = self.mlp_multipliers
        y = _matmul(jax.nn.silu(_scaled(_matmul(u, p["mlp"]["gate_kernel"]),
                                        gate))
                    * _matmul(u, p["mlp"]["up_kernel"]),
                    p["mlp"]["down_kernel"])
        return h + _scaled(y, down), {**(pages or {}), **(slot or {})}

    def _head(self, params, h, one=None):
        # the one position wanted, then the norm and the matmul, its unit
        # axis dropped last
        if one is not None:
            h = one(h)
        h = rms_norm(h, params["final_norm"], self.epsilon)
        logits = _scaled(_matmul(h, params["logits_kernel"]),
                         self.lm_head_multiplier)
        return logits if one is None else logits[:, 0]

    def constructor_config(self):
        return dict(self._config)


__all__ = ["FalconH1LM"]
