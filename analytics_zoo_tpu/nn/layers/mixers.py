"""Sequence mixers of a decoder, and the hybrid decoder's gated MLP.

A *mixer* is the part of a decoder layer that moves information between
positions. Every decoder of the tree is a list of them behind one interface,
walked once whatever their kinds
(:class:`~analytics_zoo_tpu.models.decoder.CachedDecoder`):

* :class:`~.attention.MultiHeadAttention`: full softmax attention behind a
  biased fused QKV projection (the GPT-2 block's). It keeps K and V of every
  cached token, in pages (``state_kind = PAGES``), and holds the one cached
  attention step of the tree.
* :class:`QKNormAttention`: that mixer with another projection: no bias, no
  position signal of its own, an RMS norm over the whole query and key
  vectors (Olmo 2's QK-norm).
* :class:`GatedDeltaNet`: linear attention by the gated delta rule
  (arXiv:2412.06464, in the form of ``fla.layers.GatedDeltaNet``). It keeps,
  for each slot, a float32 matrix state a head and the last rows that went
  into its short convolution (``state_kind = SLOT``): a fixed size, whatever
  the sequence's length.

The interface: ``state_kind`` says what the layer keeps between steps,
``cast_at_use(params)`` which leaves it reads only through a cast,
``apply(params, state, x)`` is the whole sequence with no cache (the
teacher-forced forward; JAX differentiates it), ``prefill(params, x, cache,
at)`` the same forward that also leaves the layer's cache as the sequence
leaves it, and ``decode(params, x, cache, at)`` the new tokens of each row
against it: one or more a row where the state is pages (a decode step, a
verify step, a prefill chunk), one where it is a slot's. ``cache`` is a dict
of THIS layer's leaves (``{"k", "v"}`` or ``slot_state()``'s names), ``at`` a
:class:`~analytics_zoo_tpu.ops.kv_cache.StepContext`.
"""

from __future__ import annotations

from typing import Any, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ...ops.kv_cache import SLOT, StepContext
from ..module import Layer, as_compute, get_initializer, param_dtype
from .attention import MultiHeadAttention
from .normalization import rms_norm


def _matmul(x, kernel):
    return x @ jnp.asarray(kernel, x.dtype)


class GatedMLP(Layer):
    """``W_down(silu(x W_gate) * (x W_up))``, no bias."""

    def __init__(self, hidden_size: int, intermediate_size: int, name=None):
        super().__init__(name=name)
        self.hidden_size = hidden_size
        self.intermediate_size = intermediate_size

    def build(self, rng, input_shape=None):
        ks = jax.random.split(rng, 3)
        init = get_initializer("glorot_uniform")
        d, i = self.hidden_size, self.intermediate_size
        return {"gate_kernel": init(ks[0], (d, i), param_dtype()),
                "up_kernel": init(ks[1], (d, i), param_dtype()),
                "down_kernel": init(ks[2], (i, d), param_dtype())}, {}

    def cast_at_use(self, params):
        return jax.tree_util.tree_map(lambda _: True, params)

    def apply(self, params, state, x, *, training=False, rng=None):
        x = as_compute(x)
        h = jax.nn.silu(_matmul(x, params["gate_kernel"])) * _matmul(
            x, params["up_kernel"])
        return _matmul(h, params["down_kernel"]), state


class QKNormAttention(MultiHeadAttention):
    """Full causal attention, QK-norm, no position signal, no bias:
    :class:`MultiHeadAttention` with another projection. Routing (XLA full
    attention, the flash kernel on a TPU from 2k tokens in a prefill and from
    fewer when a backward follows a large batch, sequence-parallel forms under
    a mesh), the write into the pages and the cached attend are inherited.

    The page pools hold the heads rounded up to a multiple of 8
    (``pool_heads``; 32 for 30): the TPU tiles the axis before the last by 8,
    so the pool occupies that much HBM either way, and ``zoo_paged_attention``
    slices the heads axis, which Mosaic takes only tile-aligned. The heads
    added are zeros in Q, K and V and are cut from the output."""

    def __init__(self, hidden_size: int, n_head: int, epsilon: float = 1e-6,
                 attn_strategy: str = "auto", name=None):
        super().__init__(hidden_size, n_head, causal=True,
                         attn_strategy=attn_strategy, name=name)
        self.pool_heads = -(-n_head // 8) * 8
        self.epsilon = epsilon

    def build(self, rng, input_shape=None):
        k1, k2 = jax.random.split(rng)
        init = get_initializer("glorot_uniform")
        d = self.hidden_size
        return {"qkv_kernel": init(k1, (d, 3 * d), param_dtype()),
                "q_norm": jnp.ones((d,), param_dtype()),
                "k_norm": jnp.ones((d,), param_dtype()),
                "out_kernel": init(k2, (d, d), param_dtype())}, {}

    def cast_at_use(self, params):
        return {"qkv_kernel": True, "q_norm": False, "k_norm": False,
                "out_kernel": True}

    def qkv_proj(self, params, x):
        b, t, d = x.shape
        q, k, v = jnp.split(_matmul(x, params["qkv_kernel"]), 3, axis=-1)
        q = rms_norm(q, params["q_norm"], self.epsilon)
        k = rms_norm(k, params["k_norm"], self.epsilon)
        return tuple(a.reshape(b, t, self.n_head, self.head_dim)
                     for a in (q, k, v))

    def out_proj(self, params, o):
        b, t = o.shape[:2]
        return _matmul(o.reshape(b, t, self.hidden_size), params["out_kernel"])


class GatedDeltaNet(Layer):
    """Linear attention by the gated delta rule (module docstring).

    ``n_head`` heads of key width ``key_dim`` and value width ``value_dim``;
    a causal depthwise convolution of ``conv_size`` taps, then SiLU, on the
    projected q, k and v; q and k L2-normalised a head (q also scaled by
    ``key_dim ** -0.5``); ``beta = 2 sigmoid(b)`` (negative eigenvalues
    allowed), ``alpha = exp(-exp(A_log) softplus(a + dt_bias))``; the output
    RMS-normed a head and gated by ``silu(z)``. The recurrence runs in
    float32 whatever the compute dtype, and the state is kept so between
    steps (:mod:`analytics_zoo_tpu.ops.gated_delta`)."""

    state_kind = SLOT
    scope = "zoo_gdn_layer"

    def __init__(self, hidden_size: int, n_head: int, key_dim: int,
                 value_dim: int, conv_size: int = 4, epsilon: float = 1e-6,
                 name=None):
        super().__init__(name=name)
        self.hidden_size = hidden_size
        self.n_head = n_head
        self.key_dim = key_dim
        self.value_dim = value_dim
        self.conv_size = conv_size
        self.epsilon = epsilon
        self.qk_width = n_head * key_dim
        self.v_width = n_head * value_dim
        self.conv_width = 2 * self.qk_width + self.v_width

    def slot_state(self, dtype) -> Tuple[Tuple[str, Tuple[int, ...], Any], ...]:
        """What one slot keeps of this layer: the matrix state of all heads
        (``ops.gated_delta.state_to_lanes``), float32, and the rows of q, k
        and v before the convolution that the next token's taps reach back
        to, in the compute dtype."""
        return (("recurrent", (self.key_dim, self.v_width), jnp.float32),
                ("conv", (self.conv_size - 1, self.conv_width), dtype))

    def build(self, rng, input_shape=None):
        ks = jax.random.split(rng, 7)
        init = get_initializer("glorot_uniform")
        d, h = self.hidden_size, self.n_head
        # A uniform in [0, 16) and dt log-uniform in [1e-3, 0.1], as fla
        # draws them, so that alpha spans fast and slow heads
        a = jax.random.uniform(ks[4], (h,), jnp.float32, 1e-3, 16.0)
        dt = jnp.exp(jax.random.uniform(ks[5], (h,), jnp.float32,
                                        np.log(1e-3), np.log(0.1)))
        return {
            "qkv_kernel": init(ks[0], (d, self.conv_width), param_dtype()),
            "gate_kernel": init(ks[1], (d, self.v_width), param_dtype()),
            "ba_kernel": init(ks[2], (d, 2 * h), param_dtype()),
            "conv_kernel": jax.random.uniform(
                ks[3], (self.conv_width, self.conv_size), param_dtype(),
                -0.5, 0.5),
            "A_log": jnp.log(a),
            "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),   # softplus^-1(dt)
            "norm_scale": jnp.ones((self.value_dim,), param_dtype()),
            "out_kernel": init(ks[6], (self.v_width, d), param_dtype()),
        }, {}

    def cast_at_use(self, params):
        flags = {name: False for name in params}
        for name in ("qkv_kernel", "gate_kernel", "ba_kernel", "out_kernel"):
            flags[name] = True
        return flags

    # -- the parts the three entry points share ---------------------------

    def _project(self, params, x):
        """x (B, T, hidden) -> the convolution's input (B, T, conv_width),
        the output gate z (B, T, v_width), beta and log alpha (B, T, H)."""
        ba = _matmul(x, params["ba_kernel"]).astype(jnp.float32)
        b, a = ba[..., :self.n_head], ba[..., self.n_head:]
        log_alpha = -jnp.exp(params["A_log"].astype(jnp.float32)) \
            * jax.nn.softplus(a + params["dt_bias"].astype(jnp.float32))
        return (_matmul(x, params["qkv_kernel"]),
                _matmul(x, params["gate_kernel"]),
                2.0 * jax.nn.sigmoid(b), log_alpha)

    def _conv(self, params, window):
        """``window`` (B, T + conv_size - 1, conv_width), the rows before the
        first output first -> silu of the causal depthwise convolution, (B,
        T, conv_width) float32, split and normalised into q, k, v."""
        w = params["conv_kernel"].astype(jnp.float32)
        t = window.shape[1] - self.conv_size + 1
        window = window.astype(jnp.float32)
        u = sum(window[:, j:j + t] * w[:, j] for j in range(self.conv_size))
        u = jax.nn.silu(u)
        b = u.shape[0]
        q, k, v = jnp.split(u, (self.qk_width, 2 * self.qk_width), axis=-1)
        q = q.reshape(b, t, self.n_head, self.key_dim)
        k = k.reshape(b, t, self.n_head, self.key_dim)

        def unit(a):
            return a * jax.lax.rsqrt(
                jnp.sum(a * a, axis=-1, keepdims=True) + self.epsilon)

        return (unit(q) * self.key_dim ** -0.5, unit(k),
                v.reshape(b, t, self.n_head, self.value_dim))

    def _finish(self, params, o, z):
        """o (B, T, H, dv) float32, z (B, T, v_width) -> (B, T, hidden)."""
        b, t = o.shape[:2]
        z = z.reshape(b, t, self.n_head, self.value_dim)
        y = rms_norm(o, params["norm_scale"], self.epsilon) \
            * jax.nn.silu(z.astype(jnp.float32))
        return _matmul(y.reshape(b, t, self.v_width).astype(z.dtype),
                       params["out_kernel"])

    def _sequence(self, params, x, lengths, *, kernel: bool):
        """The whole-sequence forward: ``(y, final state (B, H, dk, dv), the
        convolution's input)``. Positions from ``lengths`` on (a bucket's
        padding) leave the state as it is."""
        from ...ops.gated_delta import gated_delta_chunked

        x = as_compute(x)
        pre, z, beta, log_alpha = self._project(params, x)
        if lengths is not None:
            valid = (jnp.arange(x.shape[1])[None, :]
                     < lengths[:, None])[..., None]
            beta = jnp.where(valid, beta, 0.0)
            log_alpha = jnp.where(valid, log_alpha, 0.0)
        pad = jnp.zeros((x.shape[0], self.conv_size - 1, self.conv_width),
                        pre.dtype)
        window = jnp.concatenate([pad, pre], axis=1)
        q, k, v = self._conv(params, window)
        o, final = gated_delta_chunked(q, k, v, log_alpha, beta, kernel=kernel)
        return self._finish(params, o, z), final, window

    # -- the entry points ---------------------------------------------------

    def apply(self, params, state, x, *, training=False, rng=None):
        return self._sequence(params, x, None, kernel=False)[0], state

    def prefill(self, params, x, cache, at: StepContext):
        from ...ops.gated_delta import state_to_lanes

        y, final, window = self._sequence(params, x, at.lengths, kernel=True)
        # the rows the token at position ``length`` will reach back to: the
        # window leads with conv_size - 1 rows, so position p is row p + 3
        rows = at.lengths[:, None] + jnp.arange(self.conv_size - 1)[None, :]
        tail = jnp.take_along_axis(window, rows[..., None], axis=1)
        return y, {
            "recurrent": cache["recurrent"].at[at.slots].set(
                state_to_lanes(final)),
            "conv": cache["conv"].at[at.slots].set(
                tail.astype(cache["conv"].dtype))}

    def decode(self, params, x, cache, at: StepContext):
        """One token a row: the recurrence has no wider step that could be
        taken back, which is why the batcher refuses this kind of state
        speculation and chunked prefill."""
        from ...ops.gated_delta import gdn_decode

        if x.shape[1] != 1:
            raise ValueError(f"{type(self).__name__}.decode takes one token "
                             f"a row, got {x.shape[1]}")
        x = as_compute(x)
        pre, z, beta, log_alpha = self._project(params, x)
        window = jnp.concatenate([cache["conv"].astype(pre.dtype), pre],
                                 axis=1)
        q, k, v = self._conv(params, window)
        o, recurrent = gdn_decode(cache["recurrent"], q[:, 0], k[:, 0],
                                  v[:, 0], jnp.exp(log_alpha[:, 0]),
                                  beta[:, 0], at.live)
        tail = jnp.where(at.live[:, None, None],
                         window[:, 1:].astype(cache["conv"].dtype),
                         cache["conv"])
        return self._finish(params, o[:, None], z), \
            {"recurrent": recurrent, "conv": tail}


__all__ = ["GatedDeltaNet", "GatedMLP", "QKNormAttention", "StepContext"]
