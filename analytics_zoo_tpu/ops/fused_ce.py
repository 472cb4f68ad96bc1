"""Fused LM-head softmax cross-entropy — O(chunk×V) logits memory.

The reference bounds sequence models by single-node memory (SURVEY §5.7);
its largest classifier heads materialize full (N, V) score matrices. For a
TPU LM at vocab 32k, f32 logits are 1 GB per 8k tokens — at batch 32 ×
seq 2048 that is 8 GB of HBM, which is what forces large batches into
rematerialization (a sweep made before PR 1 saw batches ≥16 drop to ~0.35
MFU under remat; not measured on the current code). This op is the LM-head
analog of flash attention: never hold the full logits.

Mechanism (``jax.custom_vjp``, like ops/flash_attention.py):

* forward: ``lax.scan`` over token chunks — each step computes the chunk's
  logits ``z = h_c @ W`` (in the operands' promoted dtype: bf16 operands hit
  the MXU bf16 path with f32 accumulation, f32 operands stay full
  precision), reduces them to ``logsumexp`` + the label logit, and drops
  them; only (N,) reductions survive.
* backward: recompute each chunk's logits, form ``softmax − onehot`` scaled
  by the incoming cotangent, and accumulate ``dh_c = dz @ Wᵀ`` and
  ``dW += h_cᵀ @ dz`` — the recompute costs one extra ``N·H·V`` matmul
  (+25% of head FLOPs) in exchange for never materializing (N, V).

Fidelity (tests/test_fused_ce.py, vs the direct lse-form loss): with f32
operands, value and grads match to ~1e-5. With bf16 operands the VALUE
still matches to ~2e-5 (reductions are f32 either way) but ``dW`` is only
bf16-close (rtol ~1e-2): it accumulates through bf16 multiplies in a
different order than the direct path's einsum-VJP.

When to use: this is a MEMORY tool, not a speed tool. Measured on a v5e at
vocab 32k / hidden 1024: batch 16 trains WITHOUT rematerialization through
this path (the direct loss OOMs), but where the direct path fits it is
~6% faster (171 vs 181 ms/step at batch 8) because the backward's logits
recompute costs more than the saved HBM traffic at this scale. Reach for
it when the (N, V) logits (or the remat they force) are the binding
constraint — very large vocabs, long sequences, or big per-chip batches.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def _chunk_logits(h_c, kernel):
    """``h_c @ kernel`` in the operands' promoted dtype with f32 accumulation
    — the same discipline as the model's direct head matmul (low-precision
    operands use the MXU fast path; f32 operands stay full precision)."""
    dt = jnp.result_type(h_c.dtype, kernel.dtype)
    return jax.lax.dot_general(
        h_c.astype(dt), kernel.astype(dt),
        (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)


def _prepare(h, labels, chunk):
    """Flatten to a token axis, pad to a chunk multiple, reshape for scan.

    Shared by forward and backward so both ALWAYS agree on the chunking —
    a divergence here would be a silent wrong-gradient bug. Returns
    ``(h3, l3, valid3, n)``: (n_chunks, chunk, H) activations,
    (n_chunks, chunk) labels, validity mask, and the true token count."""
    H = h.shape[-1]
    hf, lf = h.reshape(-1, H), labels.reshape(-1)
    n = hf.shape[0]
    if n == 0:
        raise ValueError(
            "fused_softmax_xent: zero tokens (h has an empty leading shape); "
            "the mean over n=0 tokens is undefined")
    chunk = min(chunk, n)
    pad = (-n) % chunk
    if pad:
        hf = jnp.concatenate([hf, jnp.zeros((pad, H), hf.dtype)])
        lf = jnp.concatenate([lf, jnp.zeros((pad,), lf.dtype)])
    n_chunks = hf.shape[0] // chunk
    h3 = hf.reshape(n_chunks, chunk, H)
    l3 = lf.reshape(n_chunks, chunk).astype(jnp.int32)
    valid3 = (jnp.arange(hf.shape[0]) < n).reshape(n_chunks, chunk)
    return h3, l3, valid3, n


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def fused_softmax_xent(h, kernel, labels, chunk: int = 4096):
    """Mean softmax cross-entropy of ``h @ kernel`` against int ``labels``.

    ``h``: (..., H) activations (any leading shape), ``kernel``: (H, V),
    ``labels``: int array matching ``h``'s leading shape. ``chunk`` is the
    token-chunk size (static); peak extra memory is ``chunk × V`` f32.
    """
    loss, _ = _vjp_fwd(h, kernel, labels, chunk)
    return loss


def _vjp_fwd(h, kernel, labels, chunk):
    h3, l3, valid3, n = _prepare(h, labels, chunk)

    def step(acc, xs):
        h_c, l_c, v_c = xs
        z = _chunk_logits(h_c, kernel)                       # (chunk, V) f32
        lse = jax.nn.logsumexp(z, axis=-1)                   # (chunk,)
        picked = jnp.take_along_axis(z, l_c[:, None], axis=-1)[:, 0]
        return acc + jnp.sum(jnp.where(v_c, lse - picked, 0.0)), None

    total, _ = jax.lax.scan(step, jnp.float32(0), (h3, l3, valid3))
    return total / n, (h, kernel, labels)


def _vjp_bwd(chunk, res, g):
    h, kernel, labels = res
    h3, l3, valid3, n = _prepare(h, labels, chunk)
    scale = (g / n).astype(jnp.float32)

    def step(dW, xs):
        h_c, l_c, v_c = xs
        z = _chunk_logits(h_c, kernel)                       # recompute
        p = jax.nn.softmax(z, axis=-1)
        dz = p - jax.nn.one_hot(l_c, z.shape[-1], dtype=jnp.float32)
        dz = jnp.where(v_c[:, None], dz, 0.0) * scale        # (chunk, V)
        dt = jnp.result_type(h_c.dtype, kernel.dtype)
        dh_c = jax.lax.dot_general(                          # dz @ Wᵀ
            dz.astype(dt), kernel.astype(dt),
            (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
        dW = dW + jax.lax.dot_general(                       # h_cᵀ @ dz
            h_c.astype(dt), dz.astype(dt),
            (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        return dW, dh_c

    dW, dh3 = jax.lax.scan(
        step, jnp.zeros(kernel.shape, jnp.float32), (h3, l3, valid3))
    dh = dh3.reshape(-1, h.shape[-1])[:n].reshape(h.shape)
    # Integer primals take a float0 symbolic-zero cotangent per JAX convention
    # (a zeros_like int array only works while nothing extracts this grad).
    dlabels = np.zeros(np.shape(labels), dtype=jax.dtypes.float0)
    return (dh.astype(h.dtype), dW.astype(kernel.dtype), dlabels)


fused_softmax_xent.defvjp(_vjp_fwd, _vjp_bwd)
