"""The Falcon-H1 decoder, plainly: the reference the benchmark holds the
program to.

Float32 ``jax.numpy`` at ``highest`` matmul precision; no kernel, no cache, no
chunking, and no import from ``analytics_zoo_tpu`` (the controls' rounding is
``gpt2_ref``'s, imported). It reads the parameter tree ``FalconH1LM.build``
makes (``token_embeddings``, ``layer<i>/{input_norm, attn, ssm, mlp_norm,
mlp}``, ``final_norm``, ``logits_kernel``) and follows the published
architecture (``model_type: falcon_h1``: a Mamba-2 mixer, arXiv:2405.21060,
and a grouped-query rotary attention in parallel on the same normed input;
the muP multipliers of the published config are part of the mathematics).
With ``RMS(x; w) = x / sqrt(mean(x^2) + eps) * w`` and ``s_*`` the
multipliers:

    h = E[ids] * s_embedding
    u = RMS(h; w_in)
    h = h + Attn(u * s_attention_in) * s_attention_out + SSM(u) * s_ssm_out
    u = RMS(h; w_ff)
    h = h + W_down(silu((u W_gate) * s_mlp[0]) * (u W_up)) * s_mlp[1]
    logits = (RMS(h; w_f) W_head) * s_lm_head

    Attn(x): q = x W_q, k = (x W_k) * s_key, v = x W_v; rotary on q and k over
             the whole head (rotate-half pairing, position t, theta); query
             head j attends KV head j // (heads / KV heads);
             softmax(causal(q k^T / sqrt(d))) v; W_o
    SSM(x):  p = ((x * s_ssm_in) W_in) * m, m the five s_ssm spread over the
             segments [z | x | B | C | dt]
             xBC_t = silu(sum_j w[:, j] xBC~_{t-3+j} + b)   (zeros before t = 0)
             dt_t = softplus(dt~_t + dt_bias),  A = -exp(A_log)      a head
             S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T   (head i reads group
                                                           i // (heads / groups))
             y_t = S_t C_t + D x_t
             y = RMSgroup(y * silu(z); w_n)       (the norm over each group)
             W_out

The recurrence runs token by token (``lax.scan``), which is the definition
and nothing else. A layer is one jitted function, called once a layer with
that layer's weights cast to float32 as they are handed over, so the
reference compiles in seconds at any depth and holds one layer in float32 at
a time; attention's heads are taken one at a time; the head is taken by
blocks of columns and the logits are put together on the host (5120 x 261,120
in float32 is 5.3 GB, and 2,048 rows of logits 2.1 GB, beside 10.5 GB of
weights). Departures from the published description, shared with the
program, are in the configuration's ``assumed``.

``precision="fp8"`` and ``precision="int8"`` are the controls of the serving
cell's correctness check, not references: the same arithmetic with both
operands of every weight product rounded as ``gpt2_ref`` rounds them.
``precision="bf16"`` is a witness, not a control: both operands of every
weight product rounded to bfloat16, the precision the configuration states.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

# ``x @ w``, or the product of both rounded to the control's precision: the
# one rounding the references share
from benchmark.reference.gpt2_ref import _product as _control_product

F32 = jnp.float32

#: columns of the head one jitted call computes
HEAD_BLOCK = 32640

#: static arguments of a layer: the configuration's counts and multipliers
_STATIC = ("n_head", "n_kv_head", "head_dim", "ssm_heads", "ssm_head_dim",
           "state_dim", "n_groups", "rope_theta", "epsilon",
           "attention_in_multiplier", "attention_out_multiplier",
           "key_multiplier", "ssm_in_multiplier", "ssm_out_multiplier",
           "ssm_multipliers", "mlp_multipliers", "precision")


def _product(x, w, precision):
    if precision == "bf16":
        return (x.astype(jnp.bfloat16).astype(F32)
                @ w.astype(jnp.bfloat16).astype(F32))
    return _control_product(x, w, precision)


def _rms(x, w, epsilon):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + epsilon) * w


def _rotary(a, theta):
    """(b, t, heads, d): element i of a head turns with element i + d / 2 by
    the angle t * theta ** (-2 i / d)."""
    t, d = a.shape[1], a.shape[-1]
    half = d // 2
    angle = jnp.arange(t, dtype=F32)[:, None] * (
        float(theta) ** (-jnp.arange(half, dtype=F32) / half))[None, :]
    cos, sin = jnp.cos(angle)[None, :, None], jnp.sin(angle)[None, :, None]
    lo, hi = a[..., :half], a[..., half:]
    return jnp.concatenate([lo * cos - hi * sin, hi * cos + lo * sin], -1)


def _attention(p, x, *, n_head, n_kv_head, head_dim, rope_theta,
               key_multiplier, precision):
    b, t, _ = x.shape
    qkv = _product(x, p["qkv_kernel"], precision)
    q = qkv[..., :n_head * head_dim].reshape(b, t, n_head, head_dim)
    k = qkv[..., n_head * head_dim:(n_head + n_kv_head) * head_dim].reshape(
        b, t, n_kv_head, head_dim) * key_multiplier
    v = qkv[..., (n_head + n_kv_head) * head_dim:].reshape(
        b, t, n_kv_head, head_dim)
    q, k = _rotary(q, rope_theta), _rotary(k, rope_theta)
    # query head j attends KV head j // group
    k, v = (jnp.repeat(a, n_head // n_kv_head, axis=2) for a in (k, v))
    q, k, v = (a.transpose(0, 2, 1, 3).reshape(b * n_head, t, head_dim)
               for a in (q, k, v))
    causal = np.tril(np.ones((t, t), bool))

    def head(qkv_h):                    # one head at a time: (t, t) scores
        q_h, k_h, v_h = qkv_h
        scores = jnp.where(causal, q_h @ k_h.T / np.sqrt(head_dim), -jnp.inf)
        return jax.nn.softmax(scores, axis=-1) @ v_h

    out = jax.lax.map(head, (q, k, v)).reshape(b, n_head, t, head_dim)
    return _product(out.transpose(0, 2, 1, 3).reshape(b, t, n_head * head_dim),
                    p["out_kernel"], precision)


def _state_space(p, x, *, ssm_heads, ssm_head_dim, state_dim, n_groups,
                 epsilon, ssm_in_multiplier, ssm_multipliers, precision):
    b, t, _ = x.shape
    inner, bc = ssm_heads * ssm_head_dim, n_groups * state_dim
    taps = p["conv_kernel"].shape[1]
    m = np.repeat(np.asarray(ssm_multipliers, np.float32),
                  [inner, inner, bc, bc, ssm_heads])
    proj = _product(x * ssm_in_multiplier, p["in_kernel"], precision) * m
    z, xbc, dt = (proj[..., :inner], proj[..., inner:2 * inner + 2 * bc],
                  proj[..., 2 * inner + 2 * bc:])
    padded = jnp.pad(xbc, ((0, 0), (taps - 1, 0), (0, 0)))
    u = jax.nn.silu(sum(padded[:, j:j + t] * p["conv_kernel"][:, j]
                        for j in range(taps)) + p["conv_bias"])
    xs = u[..., :inner].reshape(b, t, ssm_heads, ssm_head_dim)
    # head i reads group i // (heads / groups) of B and C
    bm, cm = (jnp.repeat(a.reshape(b, t, n_groups, state_dim),
                         ssm_heads // n_groups, axis=2)
              for a in (u[..., inner:inner + bc], u[..., inner + bc:]))
    dt = jax.nn.softplus(dt + p["dt_bias"])
    decay = jnp.exp(dt * -jnp.exp(p["A_log"]))

    def token(s, now):                  # s: (b, head, head_dim, state)
        x_t, b_t, c_t, dt_t, a_t = now
        s = a_t[..., None, None] * s \
            + (dt_t[..., None] * x_t)[..., None] * b_t[..., None, :]
        return s, jnp.einsum("bhpn,bhn->bhp", s, c_t)

    _, y = jax.lax.scan(
        token, jnp.zeros((b, ssm_heads, ssm_head_dim, state_dim), F32),
        tuple(jnp.moveaxis(a, 1, 0) for a in (xs, bm, cm, dt, decay)))
    y = jnp.moveaxis(y, 0, 1) + p["D"][:, None] * xs    # (b, t, head, head_dim)
    y = y.reshape(b, t, inner) * jax.nn.silu(z)
    y = y.reshape(b, t, n_groups, inner // n_groups)
    y = y / jnp.sqrt(jnp.mean(y * y, axis=-1, keepdims=True) + epsilon)
    return _product(y.reshape(b, t, inner) * p["norm_scale"], p["out_kernel"],
                    precision)


@functools.partial(jax.jit, static_argnames=_STATIC)
def _block(p, h, *, n_head, n_kv_head, head_dim, ssm_heads, ssm_head_dim,
           state_dim, n_groups, rope_theta, epsilon, attention_in_multiplier,
           attention_out_multiplier, key_multiplier, ssm_in_multiplier,
           ssm_out_multiplier, ssm_multipliers, mlp_multipliers,
           precision=None):
    with jax.default_matmul_precision("highest"):
        p = jax.tree_util.tree_map(lambda a: a.astype(F32), p)
        u = _rms(h, p["input_norm"], epsilon)
        a = _attention(p["attn"], u * attention_in_multiplier, n_head=n_head,
                       n_kv_head=n_kv_head, head_dim=head_dim,
                       rope_theta=rope_theta, key_multiplier=key_multiplier,
                       precision=precision)
        s = _state_space(p["ssm"], u, ssm_heads=ssm_heads,
                         ssm_head_dim=ssm_head_dim, state_dim=state_dim,
                         n_groups=n_groups, epsilon=epsilon,
                         ssm_in_multiplier=ssm_in_multiplier,
                         ssm_multipliers=ssm_multipliers, precision=precision)
        h = h + a * attention_out_multiplier + s * ssm_out_multiplier
        u, m = _rms(h, p["mlp_norm"], epsilon), p["mlp"]
        y = _product(
            jax.nn.silu(_product(u, m["gate_kernel"], precision)
                        * mlp_multipliers[0])
            * _product(u, m["up_kernel"], precision),
            m["down_kernel"], precision)
        return h + y * mlp_multipliers[1]


@functools.partial(jax.jit, static_argnames=("multiplier",))
def _embed(table, ids, *, multiplier):
    return table[ids].astype(F32) * multiplier


@functools.partial(jax.jit, static_argnames=("epsilon",))
def _final_norm(w_f, h, *, epsilon):
    return _rms(h, w_f.astype(F32), epsilon)


@functools.partial(jax.jit,
                   static_argnames=("first", "width", "multiplier",
                                    "precision"))
def _head_block(w_head, h, *, first, width, multiplier, precision=None):
    with jax.default_matmul_precision("highest"):
        w = jax.lax.dynamic_slice_in_dim(w_head, first, width, axis=1)
        return _product(h, w.astype(F32), precision) * multiplier


def _layer_kwargs(kw):
    kw = dict(kw)
    for name in ("ssm_multipliers", "mlp_multipliers"):
        kw[name] = tuple(float(v) for v in kw[name])
    return kw


def logits(params, ids, *, embedding_multiplier: float,
           lm_head_multiplier: float, precision=None, **layer):
    """(B, T) token ids -> (B, T, vocab) float32 logits, on the host."""
    ids = jnp.asarray(ids, jnp.int32)
    layer = _layer_kwargs(layer)
    h = _embed(params["token_embeddings"], ids,
               multiplier=float(embedding_multiplier))
    n_layer = sum(1 for k in params if k.startswith("layer"))
    for i in range(n_layer):
        h = _block(params[f"layer{i}"], h, precision=precision, **layer)
    h = _final_norm(params["final_norm"], h, epsilon=layer["epsilon"])
    w_head = params["logits_kernel"]
    vocab = w_head.shape[1]
    # the control's rounding scales a column of the weights by itself, so a
    # block of columns reads what the whole matrix would
    return np.concatenate([
        np.asarray(_head_block(
            w_head, h, first=first, width=min(HEAD_BLOCK, vocab - first),
            multiplier=float(lm_head_multiplier), precision=precision))
        for first in range(0, vocab, HEAD_BLOCK)], axis=-1)


def lowered_block(params, ids, *, embedding_multiplier: float,
                  lm_head_multiplier: float, precision=None, **layer) -> str:
    """StableHLO text of the first layer as ``logits`` runs it: the types
    that its products' operands are rounded to stand there."""
    ids = jnp.asarray(ids, jnp.int32)
    h = _embed(params["token_embeddings"], ids,
               multiplier=float(embedding_multiplier))
    return _block.lower(params["layer0"], h, precision=precision,
                        **_layer_kwargs(layer)).as_text()
