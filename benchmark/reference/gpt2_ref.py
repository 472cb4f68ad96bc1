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

``precision="fp8"`` and ``precision="int8"`` are controls of the serving
cells' correctness check, not references: the same arithmetic with both
operands of every weight product rounded (symmetric; a scale per row of the
activations and per output column of the weights) to float8 e4m3, or to int8.
A check that cannot tell the control from the reference cannot tell a later
PR's quiet step down in precision either. On the v5e the int8 one reads
twice what the bfloat16 program itself reads (127 levels a scale are about
bfloat16's 8 bits), too near for a limit between them, so no number taken
from outputs fails it: what fails it is that its operands are 8 bits wide,
which ``lowered_block`` shows (PERF.md, section 2).
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


def _product(x, w, precision):
    """``x @ w``, or the product of both rounded to ``precision``."""
    if precision is None:
        return x @ w
    if precision == "int8":
        top = 127.0

        def rounded(a):
            return jnp.round(a).astype(jnp.int8).astype(F32)
    elif precision == "fp8":
        top = float(jnp.finfo(jnp.float8_e4m3fn).max)

        def rounded(a):
            return a.astype(jnp.float8_e4m3fn).astype(F32)
    else:
        raise ValueError(f"no such precision {precision!r}")
    sx = jnp.max(jnp.abs(x), axis=-1, keepdims=True) / top
    sw = jnp.max(jnp.abs(w), axis=0, keepdims=True) / top
    sx, sw = jnp.where(sx == 0, 1.0, sx), jnp.where(sw == 0, 1.0, sw)
    return (rounded(x / sx) @ rounded(w / sw)) * sx * sw


@functools.partial(jax.jit, static_argnames=("n_head", "epsilon", "precision"))
def _block(p, h, *, n_head, epsilon, precision=None):
    with jax.default_matmul_precision("highest"):
        p = jax.tree_util.tree_map(lambda a: a.astype(F32), p)
        b, t, d = h.shape
        a = p["attn"]
        qkv = _product(_layer_norm(h, p["ln1"], epsilon), a["qkv_kernel"],
                       precision) + a["qkv_bias"]
        # the fused projection is laid out (3, head, head_dim) along its
        # output axis
        qkv = qkv.reshape(b, t, 3, n_head, d // n_head)
        q, k, v = (qkv[:, :, i].transpose(0, 2, 1, 3) for i in range(3))
        scores = q @ k.transpose(0, 1, 3, 2) / np.sqrt(d // n_head)
        causal = np.tril(np.ones((t, t), bool))
        scores = jnp.where(causal, scores, -jnp.inf)
        out = jax.nn.softmax(scores, axis=-1) @ v           # (b, head, t, dh)
        out = out.transpose(0, 2, 1, 3).reshape(b, t, d)
        h = h + _product(out, a["out_kernel"], precision) + a["out_bias"]
        up = _product(_layer_norm(h, p["ln2"], epsilon), p["mlp_up_kernel"],
                      precision) + p["mlp_up_bias"]
        return h + _product(_gelu_tanh(up), p["mlp_down_kernel"], precision) \
            + p["mlp_down_bias"]


@jax.jit
def _embed(wte, wpe, ids):
    return wte.astype(F32)[ids] + wpe.astype(F32)[: ids.shape[1]][None]


@functools.partial(jax.jit, static_argnames=("epsilon", "precision"))
def _head(ln_f, w_head, h, *, epsilon, precision=None):
    with jax.default_matmul_precision("highest"):
        return _product(_layer_norm(h, jax.tree_util.tree_map(
            lambda a: a.astype(F32), ln_f), epsilon), w_head.astype(F32),
            precision)


def logits(params, ids, *, n_head: int, epsilon: float = 1e-5,
           precision=None):
    """(B, T) token ids -> (B, T, vocab) float32 logits."""
    ids = jnp.asarray(ids, jnp.int32)
    h = _embed(params["token_embeddings"], params["pos_embeddings"], ids)
    n_block = sum(1 for k in params if k.startswith("block"))
    for i in range(n_block):
        h = _block(params[f"block{i}"], h, n_head=n_head, epsilon=epsilon,
                   precision=precision)
    return _head(params["ln_f"], params["logits_kernel"], h, epsilon=epsilon,
                 precision=precision)


def lowered_block(params, ids, *, n_head: int, epsilon: float = 1e-5,
                  precision=None) -> str:
    """StableHLO text of the first block as ``logits`` runs it: the types
    that its products' operands are rounded to stand there."""
    ids = jnp.asarray(ids, jnp.int32)
    h = _embed(params["token_embeddings"], params["pos_embeddings"], ids)
    return _block.lower(params["block0"], h, n_head=n_head, epsilon=epsilon,
                        precision=precision).as_text()


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
