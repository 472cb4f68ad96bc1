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
from .decoder import CachedDecoder


@register_model("TransformerLM")
class TransformerLM(CachedDecoder):
    """Decoder-only transformer over int token ids (B, T) → logits (B, T, V):
    learned positions, the pre-LN GPT-2 block (:class:`TransformerLayer`), a
    LayerNorm before the head. The cache-threaded entry points (``prefill``,
    ``decode_step``, ...) are :class:`CachedDecoder`'s.

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
        self.mixers = [blk.attn for blk in self.blocks]
        self.ln_f = LayerNormalization(name=f"{self.name}_lnf")
        self.layers = list(self.blocks) + [self.ln_f]  # canonical order (persistence)

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
        # tables: their f32 rows are summed BEFORE the cast (_embed), so
        # rounding the tables first is other arithmetic; not ln_f, which
        # computes in f32
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
        h = self._embed(params, jnp.asarray(x, jnp.int32))
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

    # ------------------------------------- what CachedDecoder asks of a model

    def _embed(self, params, ids, positions=None):
        h = jnp.take(params["token_embeddings"], ids, axis=0)
        if positions is None:
            h = h + params["pos_embeddings"][: ids.shape[1]][None]
        else:
            h = h + jnp.take(params["pos_embeddings"], positions, axis=0)
        return as_compute(h)

    def _block(self, i, params, h, mix):
        return self.blocks[i].block(params[f"block{i}"], h, mix)

    def _head(self, params, h, one=None):
        # ln_f over every position, then the one wanted, its unit axis
        # dropped before the matmul
        h, _ = self.ln_f.apply(params["ln_f"], {}, h)
        if one is not None:
            h = one(h)[:, 0]
        return h @ jnp.asarray(params["logits_kernel"], h.dtype)

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
