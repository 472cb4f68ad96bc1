"""The Olmo-Hybrid decoder, plainly: the reference the benchmark holds the
program to.

Float32 ``jax.numpy`` at ``highest`` matmul precision; no kernel, no cache, no
chunking, and no import from ``analytics_zoo_tpu`` (the controls' rounding is
``gpt2_ref``'s, imported). It reads the parameter
tree ``HybridLM.build`` makes (``token_embeddings``, ``layer<i>/{mixer,
mixer_norm, mlp, mlp_norm}``, ``final_norm``, ``logits_kernel``) and follows
the published architecture (``model_type: olmo_hybrid``; the block of Olmo 2
and 3, arXiv:2501.00656; the linear layers are Gated DeltaNet,
arXiv:2412.06464, in the form of ``fla.layers.GatedDeltaNet``). With
``RMS(x; w) = x / sqrt(mean(x^2) + eps) * w``:

    h  = E[ids]                                   (no position signal at all)
    h += RMS(Mixer_l(h); w_a);   h += RMS(W_down(silu(h W_gate) * (h W_up)); w_m)
    logits = RMS(h; w_f) W_head

    full layer:   q = RMS(h W_q; w_q), k = RMS(h W_k; w_k) over the whole
                  vector, v = h W_v; softmax(causal(q k^T / sqrt(d))) v; W_o
    linear layer: q~, k~, v~ = h W_qkv;  z = h W_g;  b, a = h W_ba
                  u_t = silu(sum_j w[:, j] u~_{t-3+j})      (zeros before t = 0)
                  q_t = u^q_t / |u^q_t| / sqrt(d_k),  k_t = u^k_t / |u^k_t|   a head
                  beta_t = 2 sigmoid(b_t),  alpha_t = exp(-exp(A_log) softplus(a_t + dt_bias))
                  S_t = alpha_t S_{t-1} (I - beta_t k_t k_t^T) + beta_t v_t k_t^T
                  y_t = RMS(S_t q_t; w_o) * silu(z_t) a head;  W_o

The recurrence runs token by token (``lax.scan``), which is the definition
and nothing else. A layer is one jitted function, called once a layer with
that layer's weights cast to float32 as they are handed over, so the
reference compiles in seconds at any depth and holds one layer in float32 at
a time; a full layer's heads are taken one at a time (30 heads' scores over
3,000 tokens are 1 GB a copy, beside 8 GB of weights). Which kind a layer is,
is read from its parameters (a linear layer has a ``conv_kernel``).
Departures from the published description, shared with the program, are in
the configuration's ``assumed``.

``precision="fp8"`` and ``precision="int8"`` are the controls of the serving
cell's correctness check, not references: the same arithmetic with both
operands of every weight product rounded as ``gpt2_ref`` rounds them.
``precision="bf16"`` is a witness, not a control: both operands of every
weight product rounded to bfloat16, the precision the configuration states,
everything else as the reference; what it reads against the reference is
what bfloat16 operands alone cost this model, apart from the program.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

# ``x @ w``, or the product of both rounded to the control's precision: the
# one rounding both references share
from benchmark.reference.gpt2_ref import _product as _control_product

F32 = jnp.float32


def _product(x, w, precision):
    if precision == "bf16":
        return (x.astype(jnp.bfloat16).astype(F32)
                @ w.astype(jnp.bfloat16).astype(F32))
    return _control_product(x, w, precision)


def _rms(x, w, epsilon):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + epsilon) * w


def _full_attention(p, h, *, n_head, epsilon, precision):
    b, t, d = h.shape
    q, k, v = jnp.split(_product(h, p["qkv_kernel"], precision), 3, axis=-1)
    q, k = _rms(q, p["q_norm"], epsilon), _rms(k, p["k_norm"], epsilon)
    q, k, v = (a.reshape(b, t, n_head, d // n_head).transpose(0, 2, 1, 3)
               .reshape(b * n_head, t, d // n_head) for a in (q, k, v))
    causal = np.tril(np.ones((t, t), bool))

    def head(qkv):                      # one head at a time: (t, t) scores
        q_h, k_h, v_h = qkv
        scores = jnp.where(causal, q_h @ k_h.T / np.sqrt(d // n_head),
                           -jnp.inf)
        return jax.nn.softmax(scores, axis=-1) @ v_h

    out = jax.lax.map(head, (q, k, v)).reshape(b, n_head, t, d // n_head)
    return _product(out.transpose(0, 2, 1, 3).reshape(b, t, d),
                    p["out_kernel"], precision)


def _gated_delta_net(p, h, *, n_head, key_dim, value_dim, epsilon, precision):
    b, t, _ = h.shape
    taps = p["conv_kernel"].shape[1]
    pre = _product(h, p["qkv_kernel"], precision)
    z = _product(h, p["gate_kernel"], precision)
    ba = _product(h, p["ba_kernel"], precision)
    padded = jnp.pad(pre, ((0, 0), (taps - 1, 0), (0, 0)))
    u = jax.nn.silu(sum(padded[:, j:j + t] * p["conv_kernel"][:, j]
                        for j in range(taps)))
    qk = n_head * key_dim
    q = u[..., :qk].reshape(b, t, n_head, key_dim)
    k = u[..., qk:2 * qk].reshape(b, t, n_head, key_dim)
    v = u[..., 2 * qk:].reshape(b, t, n_head, value_dim)
    q = q / jnp.sqrt(jnp.sum(q * q, -1, keepdims=True) + epsilon) \
        / np.sqrt(key_dim)
    k = k / jnp.sqrt(jnp.sum(k * k, -1, keepdims=True) + epsilon)
    beta = 2.0 * jax.nn.sigmoid(ba[..., :n_head])
    alpha = jnp.exp(-jnp.exp(p["A_log"])
                    * jax.nn.softplus(ba[..., n_head:] + p["dt_bias"]))

    def token(s, xs):                   # s: (b, head, value, key)
        q_t, k_t, v_t, a_t, b_t = xs
        sk = jnp.einsum("bhvk,bhk->bhv", s, k_t)
        s = a_t[..., None, None] * (
            s - b_t[..., None, None] * sk[..., None] * k_t[..., None, :]) \
            + b_t[..., None, None] * v_t[..., None] * k_t[..., None, :]
        return s, jnp.einsum("bhvk,bhk->bhv", s, q_t)

    _, o = jax.lax.scan(
        token, jnp.zeros((b, n_head, value_dim, key_dim), F32),
        tuple(jnp.moveaxis(a, 1, 0) for a in (q, k, v, alpha, beta)))
    o = jnp.moveaxis(o, 0, 1)                               # (b, t, head, dv)
    y = _rms(o, p["norm_scale"], epsilon) * jax.nn.silu(
        z.reshape(b, t, n_head, value_dim))
    return _product(y.reshape(b, t, n_head * value_dim), p["out_kernel"],
                    precision)


@functools.partial(jax.jit, static_argnames=(
    "n_head", "linear_heads", "key_dim", "value_dim", "epsilon", "precision"))
def _block(p, h, *, n_head, linear_heads, key_dim, value_dim, epsilon,
           precision=None):
    with jax.default_matmul_precision("highest"):
        p = jax.tree_util.tree_map(lambda a: a.astype(F32), p)
        if "conv_kernel" in p["mixer"]:
            y = _gated_delta_net(p["mixer"], h, n_head=linear_heads,
                                 key_dim=key_dim, value_dim=value_dim,
                                 epsilon=epsilon, precision=precision)
        else:
            y = _full_attention(p["mixer"], h, n_head=n_head, epsilon=epsilon,
                                precision=precision)
        h = h + _rms(y, p["mixer_norm"], epsilon)
        m = p["mlp"]
        y = _product(jax.nn.silu(_product(h, m["gate_kernel"], precision))
                     * _product(h, m["up_kernel"], precision),
                     m["down_kernel"], precision)
        return h + _rms(y, p["mlp_norm"], epsilon)


@jax.jit
def _embed(table, ids):
    return table.astype(F32)[ids]


@functools.partial(jax.jit, static_argnames=("epsilon", "precision"))
def _head(w_f, w_head, h, *, epsilon, precision=None):
    with jax.default_matmul_precision("highest"):
        return _product(_rms(h, w_f.astype(F32), epsilon), w_head.astype(F32),
                        precision)


def logits(params, ids, *, n_head: int, linear_heads: int, key_dim: int,
           value_dim: int, epsilon: float = 1e-6, precision=None):
    """(B, T) token ids -> (B, T, vocab) float32 logits."""
    ids = jnp.asarray(ids, jnp.int32)
    h = _embed(params["token_embeddings"], ids)
    n_layer = sum(1 for k in params if k.startswith("layer"))
    for i in range(n_layer):
        h = _block(params[f"layer{i}"], h, n_head=n_head,
                   linear_heads=linear_heads, key_dim=key_dim,
                   value_dim=value_dim, epsilon=epsilon, precision=precision)
    return _head(params["final_norm"], params["logits_kernel"], h,
                 epsilon=epsilon, precision=precision)


def lowered_block(params, ids, *, n_head: int, linear_heads: int,
                  key_dim: int, value_dim: int, epsilon: float = 1e-6,
                  precision=None) -> str:
    """StableHLO text of the first layer as ``logits`` runs it: the types
    that its products' operands are rounded to stand there."""
    ids = jnp.asarray(ids, jnp.int32)
    h = _embed(params["token_embeddings"], ids)
    return _block.lower(params["layer0"], h, n_head=n_head,
                        linear_heads=linear_heads, key_dim=key_dim,
                        value_dim=value_dim, epsilon=epsilon,
                        precision=precision).as_text()
