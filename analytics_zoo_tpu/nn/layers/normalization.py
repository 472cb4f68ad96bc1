"""Normalization layers.

Parity: BatchNormalization.scala, LayerNorm (used inside TransformerLayer.scala),
WithinChannelLRN2D/SpatialLRN equivalents omitted (deprecated in practice).

BatchNorm moving statistics are *state*, not params — they ride the state pytree so
``jax.grad`` never sees them, and under data parallelism the batch statistics are
averaged across the ``dp`` mesh axis with a ``psum`` when inside shard_map (XLA
inserts the collective when the batch axis is sharded under jit).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from ..module import Layer, param_dtype


class BatchNormalization(Layer):
    """BatchNorm over the channel (last) axis by default.

    ``dim_ordering='th'`` normalizes axis 1 (channels-first conv feature maps),
    matching the reference's BatchNormalization.scala default for CNNs.
    """

    def __init__(self, epsilon: float = 1e-3, momentum: float = 0.99,
                 axis: int = -1, scale: bool = True, center: bool = True,
                 name=None, input_shape=None):
        super().__init__(name=name, input_shape=input_shape)
        self.epsilon = epsilon
        self.momentum = momentum
        self.axis = axis
        self.scale = scale
        self.center = center

    def _param_shape(self, input_shape):
        full = (None,) + tuple(input_shape)
        axis = self.axis if self.axis >= 0 else len(full) + self.axis
        return (full[axis],), axis

    def build(self, rng, input_shape):
        shape, _ = self._param_shape(input_shape)
        params = {}
        if self.scale:
            params["gamma"] = jnp.ones(shape, param_dtype())
        if self.center:
            params["beta"] = jnp.zeros(shape, param_dtype())
        state = {
            "moving_mean": jnp.zeros(shape, jnp.float32),
            "moving_var": jnp.ones(shape, jnp.float32),
        }
        return params, state

    def apply(self, params, state, x, *, training=False, rng=None):
        ndim = x.ndim
        axis = self.axis if self.axis >= 0 else ndim + self.axis
        reduce_axes = tuple(i for i in range(ndim) if i != axis)
        bshape = [1] * ndim
        bshape[axis] = x.shape[axis]

        if training:
            mean = jnp.mean(x.astype(jnp.float32), axis=reduce_axes)
            var = jnp.var(x.astype(jnp.float32), axis=reduce_axes)
            m = self.momentum
            new_state = {
                "moving_mean": m * state["moving_mean"] + (1 - m) * mean,
                "moving_var": m * state["moving_var"] + (1 - m) * var,
            }
        else:
            mean, var = state["moving_mean"], state["moving_var"]
            new_state = state

        inv = jax.lax.rsqrt(var + self.epsilon)
        y = (x.astype(jnp.float32) - mean.reshape(bshape)) * inv.reshape(bshape)
        if self.scale:
            y = y * params["gamma"].reshape(bshape)
        if self.center:
            y = y + params["beta"].reshape(bshape)
        return y.astype(x.dtype), new_state


class LayerNormalization(Layer):
    """LayerNorm over the last axis (TransformerLayer.scala internal LN parity)."""

    def __init__(self, epsilon: float = 1e-5, name=None, input_shape=None):
        super().__init__(name=name, input_shape=input_shape)
        self.epsilon = epsilon

    def build(self, rng, input_shape):
        d = input_shape[-1]
        return {"gamma": jnp.ones((d,), param_dtype()),
                "beta": jnp.zeros((d,), param_dtype())}, {}

    def apply(self, params, state, x, *, training=False, rng=None):
        xf = x.astype(jnp.float32)
        mean = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.var(xf, axis=-1, keepdims=True)
        y = (xf - mean) * jax.lax.rsqrt(var + self.epsilon)
        y = y * params["gamma"] + params["beta"]
        return y.astype(x.dtype), state


class RMSNorm(Layer):
    """Root-mean-square norm over the last axis, in float32, with a learned
    scale and no bias: ``x / sqrt(mean(x^2) + epsilon) * scale``."""

    def __init__(self, epsilon: float = 1e-6, name=None, input_shape=None):
        super().__init__(name=name, input_shape=input_shape)
        self.epsilon = epsilon

    def build(self, rng, input_shape):
        return {"scale": jnp.ones((input_shape[-1],), param_dtype())}, {}

    def apply(self, params, state, x, *, training=False, rng=None):
        return rms_norm(x, params["scale"], self.epsilon), state


def rms_norm(x, scale, epsilon: float = 1e-6):
    """``x / sqrt(mean(x^2) + epsilon) * scale`` over the last axis, computed
    in float32 and handed back in ``x``'s dtype."""
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + epsilon)
    return (y * scale.astype(jnp.float32)).astype(x.dtype)
