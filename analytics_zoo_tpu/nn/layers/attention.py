"""Attention / transformer layers.

Parity: TransformerLayer.scala and BERT.scala
(/root/reference/zoo/src/main/scala/com/intel/analytics/zoo/pipeline/api/keras/
layers/) — GPT-style decoder blocks and BERT encoder with embeddings + pooler.

TPU-native differences from the reference:
* attention dispatches through :mod:`analytics_zoo_tpu.ops.attention`, so the same
  layer runs single-chip full attention or ring/Ulysses sequence-parallel attention
  depending on the mesh (the reference is single-node fixed-length only);
* QKV is ONE fused matmul (D → 3·H·Dh) to keep the MXU busy;
* weights carry logical sharding hints consumed by parallel.sharding (tp rules).
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ...ops.attention import (count_route, full_attention,
                              prefer_flash_single_device, sharded_attention)
from ...ops.kv_cache import (PAGES, decode_attention, decode_attention_multi,
                             for_query_heads, paged_read, paged_write_multi,
                             prefill_write)
from ...ops.paged_attention import paged_attention, use_kernel
from ..activations import get_activation
from ..module import Layer, as_compute, get_initializer, param_dtype
from .normalization import LayerNormalization


class PositionalEmbedding(Layer):
    """Learned position embeddings added to token embeddings (BERT.scala style)."""

    def __init__(self, max_len: int, dim: int, name=None, input_shape=None):
        super().__init__(name=name, input_shape=input_shape)
        self.max_len = max_len
        self.dim = dim

    def build(self, rng, input_shape):
        table = jax.random.normal(rng, (self.max_len, self.dim), param_dtype()) * 0.02
        return {"pos_embeddings": table}, {}

    def apply(self, params, state, x, *, training=False, rng=None):
        t = x.shape[1]
        return x + jnp.asarray(params["pos_embeddings"][:t], x.dtype), state


class MultiHeadAttention(Layer):
    """Self-attention with fused QKV projection and strategy dispatch.

    It is a *mixer* (:mod:`.mixers`): ``apply`` is the whole sequence with no
    cache, ``prefill(params, x, cache, at)`` the same forward that also
    leaves K and V of the bucket in this layer's pages, ``decode(params, x,
    cache, at)`` one OR MORE new tokens a row against them (a decode step at
    width 1; a speculative verify step and a prefill chunk beyond). ``cache``
    is ``{"k", "v"}``, this layer's two pools; ``at`` a
    :class:`~analytics_zoo_tpu.ops.kv_cache.StepContext`. A subclass with
    another projection overrides ``qkv_proj`` / ``out_proj`` and inherits
    routing, write and attend; one whose K and V have fewer heads than Q
    (grouped KV heads) says how many in ``n_kv_head``, and one whose
    projection depends on the positions reads them from ``first``."""

    state_kind = PAGES
    scope = "zoo_full_layer"

    def __init__(self, hidden_size: int, n_head: int, causal: bool = False,
                 attn_strategy: str = "auto", name=None, input_shape=None):
        super().__init__(name=name, input_shape=input_shape)
        assert hidden_size % n_head == 0
        self.hidden_size = hidden_size
        self.n_head = n_head
        self.head_dim = hidden_size // n_head
        self.n_kv_head = n_head         # heads of K and V: a divisor of n_head
        self.pool_heads = n_head        # the heads axis of this layer's pools
        self.causal = causal
        self.attn_strategy = attn_strategy

    def build(self, rng, input_shape):
        d = input_shape[-1]
        k1, k2 = jax.random.split(rng)
        init = get_initializer("glorot_uniform")
        params = {
            "qkv_kernel": init(k1, (d, 3 * self.hidden_size), param_dtype()),
            "qkv_bias": jnp.zeros((3 * self.hidden_size,), param_dtype()),
            "out_kernel": init(k2, (self.hidden_size, self.hidden_size),
                               param_dtype()),
            "out_bias": jnp.zeros((self.hidden_size,), param_dtype()),
        }
        return params, {}

    def cast_at_use(self, params):
        # both projections' kernels and biases: qkv_proj / out_proj
        return jax.tree_util.tree_map(lambda _: True, params)

    def qkv_proj(self, params, x, first=None):
        """Fused QKV projection → (q, k, v), each (B, T, n_head, head_dim).
        Shared by the batched forward and the KV-cache prefill/decode paths
        so cached K/V are definitionally the ones ``apply`` would compute.
        ``first``: (B,) the position of each row's first token (None: 0),
        for a projection that depends on it; this one does not."""
        b, t, _ = x.shape
        qkv = x @ jnp.asarray(params["qkv_kernel"], x.dtype) + jnp.asarray(
            params["qkv_bias"], x.dtype)
        qkv = qkv.reshape(b, t, 3, self.n_head, self.head_dim)
        return qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]

    def out_proj(self, params, o):
        """(B, T, n_head, head_dim) attention output → (B, T, hidden)."""
        b, t = o.shape[:2]
        o = o.reshape(b, t, self.hidden_size)
        return o @ jnp.asarray(params["out_kernel"], o.dtype) + jnp.asarray(
            params["out_bias"], o.dtype)

    def _pool_width(self, a):
        """Pad the heads axis of (B, T, H, D) with zeros: K and V to
        ``pool_heads``, Q to the query heads that attend so many."""
        extra = (self.pool_heads - self.n_kv_head) * (
            a.shape[2] // self.n_kv_head)
        return jnp.pad(a, ((0, 0), (0, 0), (0, extra), (0, 0))) if extra else a

    def _attend(self, q, k, v, t, training=False):
        """Strategy dispatch shared by ``apply`` and ``prefill``.
        ``training`` says that a backward pass follows the call."""
        k, v = for_query_heads(k, self.n_head), for_query_heads(v, self.n_head)
        mesh = self._mesh()
        if mesh is not None and self.attn_strategy != "full":
            return sharded_attention(q, k, v, mesh,
                                     strategy=self.attn_strategy,
                                     causal=self.causal, backward=training)
        flash = self._flash_single_device(t, training,
                                          q.shape[0] * q.shape[2])
        count_route(flash, training)
        if flash:
            # no mesh context: an explicit 'flash' means the kernel (a
            # length its tiles cannot cover is an error naming the shape),
            # and 'auto' prefers it on TPU from the length at which it beats
            # XLA full attention, which is shorter when a backward follows
            # and the batch is large (ops.attention.FLASH_FROM_TOKENS,
            # FLASH_BACKWARD_*); past 16k it is the only option, where the
            # (H, T, T) scores OOM
            from ...ops.flash_attention import flash_attention

            return flash_attention(q, k, v, self.causal)
        return full_attention(q, k, v, causal=self.causal)

    def apply(self, params, state, x, *, training=False, rng=None):
        x = as_compute(x)
        q, k, v = self.qkv_proj(params, x)
        o = self._attend(q, k, v, x.shape[1], training)
        return self.out_proj(params, o), state

    def prefill(self, params, x, cache, at):
        """``apply`` in inference mode (same strategy dispatch: flash at long
        T), with K and V of the bucket scattered into this layer's pages;
        the bucket's padding lands in scratch."""
        x = as_compute(x)
        q, k, v = self.qkv_proj(params, x)
        o = self._attend(q, k, v, x.shape[1])
        cache = {name: prefill_write(cache[name], at.table,
                                     self._pool_width(a),
                                     page_size=at.page_size)
                 for name, a in (("k", k), ("v", v))}
        return self.out_proj(params, o), cache

    def decode(self, params, x, cache, at):
        """``x``: (B, q_len, hidden), the hidden states of ``q_len`` new
        tokens a row, at positions ``at.lengths .. at.lengths + q_len - 1``
        (``at.lengths`` == tokens already cached). Their K and V are written
        BEFORE attending, so a token sees itself; token i attends causally to
        the whole prefix and the new tokens up to itself: the fused
        paged-attention kernel when routed on (``ops.paged_attention.
        use_kernel``), else plain dot against the gathered cache. Shapes are
        fixed throughout (the ``decode-shape-stability`` lint invariant)."""
        x = as_compute(x)
        q_len = x.shape[1]
        q, k, v = (self._pool_width(a)
                   for a in self.qkv_proj(params, x, at.lengths))
        pos = at.lengths
        k_pages = paged_write_multi(cache["k"], at.table, pos, k,
                                    page_size=at.page_size)
        v_pages = paged_write_multi(cache["v"], at.table, pos, v,
                                    page_size=at.page_size)
        if use_kernel():
            # fused path: page gather + QK + softmax + PV entirely in VMEM —
            # the (B, T_max, H, D) contiguous copy below never exists
            o = paged_attention(q, k_pages.astype(q.dtype),
                                v_pages.astype(q.dtype), at.table,
                                pos + q_len, page_size=at.page_size)
        else:
            ks = paged_read(k_pages, at.table).astype(q.dtype)
            vs = paged_read(v_pages, at.table).astype(q.dtype)
            if q_len == 1:
                o = decode_attention(q[:, 0], ks, vs, pos + 1)[:, None]
            else:
                o = decode_attention_multi(q, ks, vs, pos + q_len)
        return self.out_proj(params, o[:, :, :self.n_head]), \
            {"k": k_pages, "v": v_pages}

    def _flash_single_device(self, t: int, training: bool = False,
                             batch_heads: int = 1) -> bool:
        if t <= 1:
            # single-query decode step: flash tiling is pure overhead at
            # query length 1 — plain dot attention regardless of strategy
            return False
        if self.attn_strategy == "flash":
            return True
        if self.attn_strategy == "auto":
            return prefer_flash_single_device(t, training, batch_heads)
        return False

    def _mesh(self):
        try:
            from ...common.context import get_zoo_context

            return get_zoo_context(auto_init=False).mesh
        except RuntimeError:
            return None

    def compute_output_shape(self, input_shape):
        return tuple(input_shape[:-1]) + (self.hidden_size,)


class TransformerLayer(Layer):
    """One pre-LN transformer block: MHA + MLP with residuals.

    Parity: TransformerLayer.scala (GPT-style block; the reference uses post-LN —
    pre-LN chosen here for training stability, same capability).
    """

    def __init__(self, hidden_size: int, n_head: int, intermediate_size: Optional[int] = None,
                 causal: bool = False, activation="gelu", dropout: float = 0.0,
                 attn_strategy: str = "auto", name=None, input_shape=None):
        super().__init__(name=name, input_shape=input_shape)
        self.hidden_size = hidden_size
        self.intermediate = intermediate_size or 4 * hidden_size
        self.dropout = dropout
        self.activation = get_activation(activation)
        self.attn = MultiHeadAttention(hidden_size, n_head, causal=causal,
                                       attn_strategy=attn_strategy,
                                       name=f"{self.name}_attn")
        self.ln1 = LayerNormalization(name=f"{self.name}_ln1")
        self.ln2 = LayerNormalization(name=f"{self.name}_ln2")

    def build(self, rng, input_shape):
        d = input_shape[-1]
        ks = jax.random.split(rng, 4)
        init = get_initializer("glorot_uniform")
        attn_p, _ = self.attn.build(ks[0], input_shape)
        ln1_p, _ = self.ln1.build(ks[1], input_shape)
        ln2_p, _ = self.ln2.build(ks[2], input_shape)
        k_up, k_down = jax.random.split(ks[3])
        params = {
            "attn": attn_p,
            "ln1": ln1_p,
            "ln2": ln2_p,
            "mlp_up_kernel": init(k_up, (d, self.intermediate), param_dtype()),
            "mlp_up_bias": jnp.zeros((self.intermediate,), param_dtype()),
            "mlp_down_kernel": init(k_down, (self.intermediate, self.hidden_size),
                                    param_dtype()),
            "mlp_down_bias": jnp.zeros((self.hidden_size,), param_dtype()),
        }
        return params, {}

    def cast_at_use(self, params):
        # the MLP's kernels and biases (block) and the attention's own; the
        # normalizations compute in f32 from f32 scales
        flags = {k: True for k in params}
        for name in ("attn", "ln1", "ln2"):
            flags[name] = getattr(self, name).cast_at_use(params[name])
        return flags

    def block(self, params, x, mix):
        """The block, written once: ``x + mix(ln1(x))``, then ln2 + MLP +
        residual. ``mix(attn_params, h) -> (a, state)`` is the attention in
        whatever form the caller runs it (whole sequence, prefill, decode),
        ``state`` what it leaves of its cache. Returns ``(x_out, state)``."""
        x = as_compute(x)
        h, _ = self.ln1.apply(params["ln1"], {}, x)
        a, state = mix(params["attn"], h)
        x = x + a
        h, _ = self.ln2.apply(params["ln2"], {}, x)
        h = h @ jnp.asarray(params["mlp_up_kernel"], x.dtype) + jnp.asarray(
            params["mlp_up_bias"], x.dtype)
        h = self.activation(h)
        h = h @ jnp.asarray(params["mlp_down_kernel"], x.dtype) + jnp.asarray(
            params["mlp_down_bias"], x.dtype)
        return x + h, state

    def apply(self, params, state, x, *, training=False, rng=None):
        def mix(p, h):
            a, _ = self.attn.apply(p, {}, h, training=training, rng=rng)
            if training and self.dropout > 0 and rng is not None:
                keep = 1.0 - self.dropout
                a = jnp.where(
                    jax.random.bernoulli(jax.random.fold_in(rng, 1), keep,
                                         a.shape), a / keep, 0.0).astype(a.dtype)
            return a, None

        return self.block(params, x, mix)[0], state

    def compute_output_shape(self, input_shape):
        return tuple(input_shape[:-1]) + (self.hidden_size,)


class BERT(Layer):
    """BERT encoder: token+position+segment embeddings, N blocks, pooled output.

    Parity: BERT.scala (nBlock, nHead, hiddenSize, maxPositionLen, ...). Returns
    (sequence_output, pooled_output) like the reference's BERT layer outputs.
    """

    def __init__(self, vocab: int, hidden_size: int = 768, n_block: int = 12,
                 n_head: int = 12, seq_len: int = 512, intermediate_size: int = 3072,
                 attn_strategy: str = "auto", name=None, input_shape=None):
        super().__init__(name=name, input_shape=input_shape)
        self.vocab = vocab
        self.hidden_size = hidden_size
        self.n_block = n_block
        self.seq_len = seq_len
        self.blocks = [
            TransformerLayer(hidden_size, n_head, intermediate_size,
                             causal=False, attn_strategy=attn_strategy,
                             name=f"{self.name}_block{i}")
            for i in range(n_block)
        ]
        self.ln_f = LayerNormalization(name=f"{self.name}_lnf")

    def build(self, rng, input_shape):
        ks = jax.random.split(rng, self.n_block + 4)
        tok = jax.random.normal(ks[0], (self.vocab, self.hidden_size),
                                param_dtype()) * 0.02
        pos = jax.random.normal(ks[1], (self.seq_len, self.hidden_size),
                                param_dtype()) * 0.02
        seg = jax.random.normal(ks[2], (2, self.hidden_size), param_dtype()) * 0.02
        params = {"token_embeddings": tok, "pos_embeddings": pos,
                  "segment_embeddings": seg}
        for i, blk in enumerate(self.blocks):
            p, _ = blk.build(ks[3 + i], (None, self.hidden_size))
            params[f"block{i}"] = p
        lnf_p, _ = self.ln_f.build(ks[-1], (None, self.hidden_size))
        params["ln_f"] = lnf_p
        kp = jax.random.split(ks[-1])[0]
        params["pooler_kernel"] = get_initializer("glorot_uniform")(
            kp, (self.hidden_size, self.hidden_size), param_dtype())
        params["pooler_bias"] = jnp.zeros((self.hidden_size,), param_dtype())
        return params, {}

    def apply(self, params, state, x, *, training=False, rng=None):
        # x: int ids (B, T) or [ids, segment_ids]
        if isinstance(x, (list, tuple)):
            ids, segs = x
        else:
            ids, segs = x, None
        ids = jnp.asarray(ids, jnp.int32)
        h = jnp.take(params["token_embeddings"], ids, axis=0)
        h = h + params["pos_embeddings"][: ids.shape[1]][None]
        if segs is not None:
            h = h + jnp.take(params["segment_embeddings"],
                             jnp.asarray(segs, jnp.int32), axis=0)
        h = as_compute(h)
        rngs = (jax.random.split(rng, self.n_block) if rng is not None
                else [None] * self.n_block)
        for i, blk in enumerate(self.blocks):
            h, _ = blk.apply(params[f"block{i}"], {}, h, training=training,
                             rng=rngs[i])
        h, _ = self.ln_f.apply(params["ln_f"], {}, h)
        pooled = jnp.tanh(h[:, 0] @ jnp.asarray(params["pooler_kernel"], h.dtype)
                          + jnp.asarray(params["pooler_bias"], h.dtype))
        return (h, pooled), state

    def compute_output_shape(self, input_shape):
        t = input_shape[0] if input_shape else self.seq_len
        return [(t, self.hidden_size), (self.hidden_size,)]
