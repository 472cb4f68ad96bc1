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
* :class:`RotaryGQAttention`: that mixer with rotary positions and grouped
  KV heads (fewer heads of K and V than of Q, which is what its pages hold).
* :class:`GatedDeltaNet`: linear attention by the gated delta rule
  (arXiv:2412.06464, in the form of ``fla.layers.GatedDeltaNet``). It keeps,
  for each slot, a float32 matrix state a head and the last rows that went
  into its short convolution (``state_kind = SLOT``): a fixed size, whatever
  the sequence's length.
* :class:`Mamba2Mixer`: a selective state-space layer (Mamba-2,
  arXiv:2405.21060). The same kind of state: a float32 matrix a head and a
  convolution's tail.

A layer may run more than one mixer on the same input (an attention and a
state-space mixer side by side): the decoder's ``mixers`` entry is then a
tuple of them, each is handed its own leaves, and the layer keeps both kinds
of state.

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

from typing import Any, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ...ops.kv_cache import SLOT, StepContext
from ..module import Layer, as_compute, get_initializer, param_dtype
from .attention import MultiHeadAttention
from .normalization import rms_norm


def _matmul(x, kernel):
    return x @ jnp.asarray(kernel, x.dtype)


def _tail_at(window, lengths, taps: int):
    """The rows of a short convolution's input that the token at position
    ``lengths[b]`` will reach back to. ``window`` (B, taps - 1 + T, width)
    leads with ``taps - 1`` rows, so position p is row p + taps - 1."""
    rows = lengths[:, None] + jnp.arange(taps - 1)[None, :]
    return jnp.take_along_axis(window, rows[..., None], axis=1)


def _next_tail(window, tail, live):
    """A decode step's new tail: the window (the old tail and the new row)
    less its first row, in the tail's dtype; a row that is not ``live``
    keeps the tail it had."""
    return jnp.where(live[:, None, None], window[:, 1:].astype(tail.dtype),
                     tail)


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

    def qkv_proj(self, params, x, first=None):
        b, t, d = x.shape
        q, k, v = jnp.split(_matmul(x, params["qkv_kernel"]), 3, axis=-1)
        q = rms_norm(q, params["q_norm"], self.epsilon)
        k = rms_norm(k, params["k_norm"], self.epsilon)
        return tuple(a.reshape(b, t, self.n_head, self.head_dim)
                     for a in (q, k, v))

    def out_proj(self, params, o):
        b, t = o.shape[:2]
        return _matmul(o.reshape(b, t, self.hidden_size), params["out_kernel"])


class RotaryGQAttention(MultiHeadAttention):
    """Full causal attention with rotary positions and grouped KV heads, no
    bias, no QK norm: :class:`MultiHeadAttention` with another projection.
    ``n_head`` query heads and ``n_kv_head`` heads of K and V, all of
    ``head_dim``; query head ``j`` attends KV head ``j // (n_head /
    n_kv_head)``. The pages hold the KV heads only (``pool_heads``), and the
    cached attend runs the query heads of a KV head as rows of one dot
    (:func:`~analytics_zoo_tpu.ops.paged_attention.paged_attention`).

    The rotation is over the whole head in the rotate-half pairing (element
    ``i`` with ``i + head_dim / 2``), by the angle ``position * theta ** (-2 i
    / head_dim)``, computed in float32 whatever the compute dtype: at theta
    1e11 the slow pairs turn by 1e-10 a position, which bfloat16 cannot hold
    beside a position of thousands. K is scaled by ``key_multiplier`` before
    it is turned (a muP scalar; 1 leaves it as it is); ``build`` draws K's
    columns that much wider, as muP means them to be, so that the scores have
    the spread they have without a multiplier (under a plain Glorot draw a
    ``key_multiplier`` of 0.011 leaves every softmax flat)."""

    def __init__(self, hidden_size: int, n_head: int, n_kv_head: int,
                 head_dim: int, rope_theta: float = 10000.0,
                 key_multiplier: float = 1.0, attn_strategy: str = "auto",
                 name=None):
        super().__init__(hidden_size, n_head, causal=True,
                         attn_strategy=attn_strategy, name=name)
        if n_head % n_kv_head:
            raise ValueError(f"{n_head} query heads are no multiple of "
                             f"{n_kv_head} KV heads")
        self.head_dim = head_dim
        self.n_kv_head = self.pool_heads = n_kv_head
        self.rope_theta = float(rope_theta)     # a config's may be an int
        self.key_multiplier = key_multiplier

    def build(self, rng, input_shape=None):
        k1, k2 = jax.random.split(rng)
        init = get_initializer("glorot_uniform")
        d, hd = self.hidden_size, self.head_dim
        n_q, n_kv = self.n_head * hd, self.n_kv_head * hd
        wider = np.repeat(np.float32([1.0, 1.0 / self.key_multiplier, 1.0]),
                          [n_q, n_kv, n_kv])
        qkv = init(k1, (d, n_q + 2 * n_kv), param_dtype())
        return {"qkv_kernel": (qkv * wider).astype(qkv.dtype),
                "out_kernel": init(k2, (n_q, d), param_dtype())}, {}

    def _rotation(self, t: int, first):
        """``(cos, sin)`` of the angles of ``t`` tokens a row, ``(B or 1, t,
        1, head_dim / 2)`` float32: row ``b``'s tokens are at ``first[b] ..
        first[b] + t - 1`` (``first`` None: 0)."""
        half = self.head_dim // 2
        at = jnp.arange(t, dtype=jnp.float32)[None, :]
        if first is not None:
            at = at + first.astype(jnp.float32)[:, None]
        angle = at[..., None] * (self.rope_theta ** (
            -jnp.arange(half, dtype=jnp.float32) / half))
        return jnp.cos(angle)[:, :, None], jnp.sin(angle)[:, :, None]

    @staticmethod
    def _turn(a, rotation, scale: float = 1.0):
        """(B, T, H, head_dim) scaled and turned, in float32."""
        cos, sin = rotation
        half = a.shape[-1] // 2
        a32 = a.astype(jnp.float32) * scale
        lo, hi = a32[..., :half], a32[..., half:]
        return jnp.concatenate([lo * cos - hi * sin, hi * cos + lo * sin],
                               -1).astype(a.dtype)

    def qkv_proj(self, params, x, first=None):
        b, t, _ = x.shape
        n_q, n_kv, hd = self.n_head, self.n_kv_head, self.head_dim
        q, k, v = jnp.split(_matmul(x, params["qkv_kernel"]),
                            (n_q * hd, (n_q + n_kv) * hd), axis=-1)
        rotation = self._rotation(t, first)
        return (self._turn(q.reshape(b, t, n_q, hd), rotation),
                self._turn(k.reshape(b, t, n_kv, hd), rotation,
                           self.key_multiplier),
                v.reshape(b, t, n_kv, hd))

    def out_proj(self, params, o):
        b, t = o.shape[:2]
        return _matmul(o.reshape(b, t, self.n_head * self.head_dim),
                       params["out_kernel"])


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
        tail = _tail_at(window, at.lengths, self.conv_size)
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
        tail = _next_tail(window, cache["conv"], at.live)
        return self._finish(params, o[:, None], z), \
            {"recurrent": recurrent, "conv": tail}


class Mamba2Mixer(Layer):
    """A selective state-space layer (Mamba-2; module docstring).

    One projection makes five segments, ``[z | x | B | C | dt]``: the gate
    and the input of ``n_head`` heads of ``head_dim`` each, ``B`` and ``C`` of
    ``n_groups`` groups of ``state_dim`` (head ``i`` reads group ``i //
    (n_head / n_groups)``), and a step size a head. ``in_multiplier`` scales
    the layer's input and ``multipliers`` the five segments (muP scalars;
    ones leave them as they are; ``build`` draws each segment's columns
    wider by what multiplies them, as muP means them to be, so that z, x, B,
    C and dt have the spread a plain projection gives them: under a plain
    Glorot draw the published multipliers leave the recurrence a millionth
    of the layer's output, where no comparison sees it). A causal depthwise convolution of
    ``conv_size`` taps with a bias, then SiLU, runs over x, B and C together;
    ``dt = softplus(dt + dt_bias)``, ``A = -exp(A_log)`` a head; the
    recurrence is ``S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T``, ``y_t = S_t
    C_t + D x_t`` (:mod:`analytics_zoo_tpu.ops.ssd`); the output is gated by
    ``silu(z)`` and then RMS-normed over each group's share of the heads. The
    recurrence runs in float32 whatever the compute dtype, and the state is
    kept so between steps."""

    state_kind = SLOT
    scope = "zoo_ssd_layer"

    def __init__(self, hidden_size: int, n_head: int, head_dim: int,
                 state_dim: int, n_groups: int = 1, conv_size: int = 4,
                 chunk_size: int = 128, epsilon: float = 1e-5,
                 in_multiplier: float = 1.0,
                 multipliers: Sequence[float] = (1.0,) * 5, name=None):
        super().__init__(name=name)
        if n_head % n_groups:
            raise ValueError(f"{n_head} heads are no multiple of "
                             f"{n_groups} groups")
        self.hidden_size = hidden_size
        self.n_head = n_head
        self.head_dim = head_dim
        self.state_dim = state_dim
        self.n_groups = n_groups
        self.conv_size = conv_size
        self.chunk_size = chunk_size
        self.epsilon = epsilon
        self.in_multiplier = in_multiplier
        self.inner = n_head * head_dim
        self.bc_width = n_groups * state_dim
        self.conv_width = self.inner + 2 * self.bc_width
        #: where the projection's segments end: z, x, B, C (dt is the rest)
        self.cuts = tuple(np.cumsum([self.inner, self.inner, self.bc_width,
                                     self.bc_width]).tolist())
        widths = [self.inner, self.inner, self.bc_width, self.bc_width, n_head]
        #: the segments' multipliers, spread over the projection's columns
        self.scale = np.repeat(np.asarray(multipliers, np.float32), widths)

    def slot_state(self, dtype) -> Tuple[Tuple[str, Tuple[int, ...], Any], ...]:
        """What one slot keeps of this layer: the matrix state of every head,
        transposed (``ops.ssd``: state rows by the head's width), float32,
        and the rows of x, B and C before the convolution that the next
        token's taps reach back to, in the compute dtype."""
        return (("ssm", (self.n_head, self.state_dim, self.head_dim),
                 jnp.float32),
                ("conv", (self.conv_size - 1, self.conv_width), dtype))

    def build(self, rng, input_shape=None):
        ks = jax.random.split(rng, 5)
        init = get_initializer("glorot_uniform")
        d, h = self.hidden_size, self.n_head
        # A uniform in [1, 16] and dt log-uniform in [1e-3, 0.1], as Mamba-2
        # draws them, so that the decay spans fast and slow heads
        a = jax.random.uniform(ks[2], (h,), jnp.float32, 1.0, 16.0)
        dt = jnp.exp(jax.random.uniform(ks[3], (h,), jnp.float32,
                                        np.log(1e-3), np.log(0.1)))
        in_kernel = init(ks[0], (d, self.cuts[-1] + h), param_dtype())
        return {
            "in_kernel": (in_kernel / (self.in_multiplier * self.scale)
                          ).astype(in_kernel.dtype),
            "conv_kernel": jax.random.uniform(
                ks[1], (self.conv_width, self.conv_size), param_dtype(),
                -0.5, 0.5),
            "conv_bias": jnp.zeros((self.conv_width,), param_dtype()),
            "A_log": jnp.log(a),
            "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),   # softplus^-1(dt)
            "D": jnp.ones((h,), jnp.float32),
            "norm_scale": jnp.ones((self.inner,), param_dtype()),
            "out_kernel": init(ks[4], (self.inner, d), param_dtype()),
        }, {}

    def cast_at_use(self, params):
        return {name: name in ("in_kernel", "out_kernel") for name in params}

    # -- the parts the three entry points share ---------------------------

    def _project(self, params, x):
        """x (B, T, hidden) -> the gate z (B, T, inner) float32, the
        convolution's input (B, T, conv_width) as the projection gave it
        (its multipliers are applied where it is read, so the tail a slot
        keeps is these rows exactly) and dt (B, T, H) float32, after its
        softplus."""
        p = _matmul(x * jnp.asarray(self.in_multiplier, x.dtype),
                    params["in_kernel"])
        z = p[..., :self.cuts[0]].astype(jnp.float32) \
            * self.scale[:self.cuts[0]]
        dt = p[..., self.cuts[3]:].astype(jnp.float32) \
            * self.scale[self.cuts[3]:]
        dt = jax.nn.softplus(dt + params["dt_bias"].astype(jnp.float32))
        return z, p[..., self.cuts[0]:self.cuts[3]], dt

    def _conv(self, params, window):
        """``window`` (B, T + conv_size - 1, conv_width), the rows before the
        first output first -> silu of the causal depthwise convolution in
        float32, split into x (B, T, H, P), B and C (B, T, G, N)."""
        w = params["conv_kernel"].astype(jnp.float32)
        t = window.shape[1] - self.conv_size + 1
        window = window.astype(jnp.float32) \
            * self.scale[self.cuts[0]:self.cuts[3]]
        u = sum(window[:, j:j + t] * w[:, j] for j in range(self.conv_size))
        u = jax.nn.silu(u + params["conv_bias"].astype(jnp.float32))
        b = u.shape[0]
        x, bm, cm = jnp.split(u, (self.inner, self.inner + self.bc_width),
                              axis=-1)
        return (x.reshape(b, t, self.n_head, self.head_dim),
                bm.reshape(b, t, self.n_groups, self.state_dim),
                cm.reshape(b, t, self.n_groups, self.state_dim))

    def _finish(self, params, y, x, z, dtype):
        """y, x (B, T, H, P) float32, z (B, T, inner) -> (B, T, hidden): the
        skip, the gate, the norm by group, the output projection."""
        b, t = y.shape[:2]
        y = y + params["D"].astype(jnp.float32)[:, None] * x
        y = y.reshape(b, t, self.inner) * jax.nn.silu(z)
        y = y.reshape(b, t, self.n_groups, self.inner // self.n_groups)
        y = y * jax.lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True)
                              + self.epsilon)
        y = y.reshape(b, t, self.inner) * params["norm_scale"].astype(
            jnp.float32)
        return _matmul(y.astype(dtype), params["out_kernel"])

    def _a(self, params):
        return -jnp.exp(params["A_log"].astype(jnp.float32))

    def _sequence(self, params, x, lengths, *, kernel: bool):
        """The whole-sequence forward: ``(y, final state (B, H, N, P), the
        convolution's input)``. Positions from ``lengths`` on (a bucket's
        padding) leave the state as it is."""
        from ...ops.ssd import ssd_chunked

        x = as_compute(x)
        z, pre, dt = self._project(params, x)
        if lengths is not None:
            valid = jnp.arange(x.shape[1])[None, :] < lengths[:, None]
            dt = jnp.where(valid[..., None], dt, 0.0)
        pad = jnp.zeros((x.shape[0], self.conv_size - 1, self.conv_width),
                        pre.dtype)
        window = jnp.concatenate([pad, pre], axis=1)
        xs, bm, cm = self._conv(params, window)
        y, final = ssd_chunked(xs, dt, self._a(params), bm, cm,
                               chunk=self.chunk_size, kernel=kernel)
        return self._finish(params, y, xs, z, x.dtype), final, window

    # -- the entry points ---------------------------------------------------

    def apply(self, params, state, x, *, training=False, rng=None):
        return self._sequence(params, x, None, kernel=False)[0], state

    def prefill(self, params, x, cache, at: StepContext):
        y, final, window = self._sequence(params, x, at.lengths, kernel=True)
        tail = _tail_at(window, at.lengths, self.conv_size)
        return y, {
            "ssm": cache["ssm"].at[at.slots].set(final),
            "conv": cache["conv"].at[at.slots].set(
                tail.astype(cache["conv"].dtype))}

    def decode(self, params, x, cache, at: StepContext):
        """One token a row: the recurrence has no wider step that could be
        taken back, which is why the batcher refuses this kind of state
        speculation and chunked prefill."""
        from ...ops.ssd import ssd_decode

        if x.shape[1] != 1:
            raise ValueError(f"{type(self).__name__}.decode takes one token "
                             f"a row, got {x.shape[1]}")
        x = as_compute(x)
        z, pre, dt = self._project(params, x)
        window = jnp.concatenate([cache["conv"].astype(pre.dtype), pre],
                                 axis=1)
        xs, bm, cm = self._conv(params, window)
        y, ssm = ssd_decode(cache["ssm"], xs[:, 0], dt[:, 0], self._a(params),
                            bm[:, 0], cm[:, 0], at.live)
        tail = _next_tail(window, cache["conv"], at.live)
        return self._finish(params, y[:, None], xs, z, x.dtype), \
            {"ssm": ssm, "conv": tail}


__all__ = ["GatedDeltaNet", "GatedMLP", "Mamba2Mixer", "QKNormAttention",
           "RotaryGQAttention", "StepContext"]
