"""The GPT-2 block, plainly: the reference the benchmark holds the program to.

Float32 ``jax.numpy`` at ``highest`` matmul precision; no kernel, no cache, no
batching tricks, and no import from ``analytics_zoo_tpu``. It reads the
parameter tree ``TransformerLM.build`` makes (``token_embeddings``,
``pos_embeddings``, ``block<i>/{ln1, attn, ln2, mlp_*}``, ``ln_f``,
``logits_kernel``) and follows the published architecture (Radford et al.
2019; the block of ``transformers``' ``GPT2Block``):

    h   = wte[ids] + wpe[positions]
    h  += proj(softmax(causal(q k^T / sqrt(d))) v),  q, k, v = split(ln1(h) W_qkv + b)
    h  += W_down gelu_tanh(ln2(h) W_up + b_up) + b_down
    out = ln_f(h) W_head

Departures, shared with the program and listed in every configuration's
``assumed``: the head ``W_head`` is a separate matrix (GPT-2 ties it to
``wte``), and the GELU is the tanh form for both published ``gelu_new`` and
``gelu``.

One block is one jitted function, called once per layer, so that the reference
compiles in seconds at any depth.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32


def _layer_norm(x, p, epsilon):
    mean = x.mean(-1, keepdims=True)
    var = ((x - mean) ** 2).mean(-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + epsilon) * p["gamma"].astype(F32) \
        + p["beta"].astype(F32)


def _gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        np.sqrt(2.0 / np.pi) * (x + 0.044715 * x ** 3)))


@functools.partial(jax.jit, static_argnames=("n_head", "epsilon"))
def _block(p, h, *, n_head, epsilon):
    with jax.default_matmul_precision("highest"):
        p = jax.tree_util.tree_map(lambda a: a.astype(F32), p)
        b, t, d = h.shape
        a = p["attn"]
        qkv = _layer_norm(h, p["ln1"], epsilon) @ a["qkv_kernel"] \
            + a["qkv_bias"]
        # the fused projection is laid out (3, head, head_dim) along its
        # output axis
        qkv = qkv.reshape(b, t, 3, n_head, d // n_head)
        q, k, v = (qkv[:, :, i].transpose(0, 2, 1, 3) for i in range(3))
        scores = q @ k.transpose(0, 1, 3, 2) / np.sqrt(d // n_head)
        causal = np.tril(np.ones((t, t), bool))
        scores = jnp.where(causal, scores, -jnp.inf)
        out = jax.nn.softmax(scores, axis=-1) @ v           # (b, head, t, dh)
        out = out.transpose(0, 2, 1, 3).reshape(b, t, d)
        h = h + out @ a["out_kernel"] + a["out_bias"]
        up = _layer_norm(h, p["ln2"], epsilon) @ p["mlp_up_kernel"] \
            + p["mlp_up_bias"]
        return h + _gelu_tanh(up) @ p["mlp_down_kernel"] + p["mlp_down_bias"]


@jax.jit
def _embed(wte, wpe, ids):
    return wte.astype(F32)[ids] + wpe.astype(F32)[: ids.shape[1]][None]


@functools.partial(jax.jit, static_argnames=("epsilon",))
def _head(ln_f, w_head, h, *, epsilon):
    with jax.default_matmul_precision("highest"):
        return _layer_norm(h, jax.tree_util.tree_map(
            lambda a: a.astype(F32), ln_f), epsilon) @ w_head.astype(F32)


def logits(params, ids, *, n_head: int, epsilon: float = 1e-5):
    """(B, T) token ids -> (B, T, vocab) float32 logits."""
    ids = jnp.asarray(ids, jnp.int32)
    h = _embed(params["token_embeddings"], params["pos_embeddings"], ids)
    n_block = sum(1 for k in params if k.startswith("block"))
    for i in range(n_block):
        h = _block(params[f"block{i}"], h, n_head=n_head, epsilon=epsilon)
    return _head(params["ln_f"], params["logits_kernel"], h, epsilon=epsilon)


@jax.jit
def _xent(lg, labels):
    lse = jax.nn.logsumexp(lg, axis=-1)
    picked = jnp.take_along_axis(lg, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(lse - picked)


def loss(params, ids, labels, *, n_head: int, epsilon: float = 1e-5) -> float:
    """Mean next-token cross entropy of a batch, one sequence at a time (the
    float32 logits of a whole batch at a 50k vocabulary would not fit beside
    a training state)."""
    ids, labels = np.asarray(ids), np.asarray(labels)
    per_seq = [float(_xent(logits(params, ids[i:i + 1], n_head=n_head,
                                  epsilon=epsilon)[0],
                           jnp.asarray(labels[i], jnp.int32)))
               for i in range(ids.shape[0])]
    return float(np.mean(per_seq))
