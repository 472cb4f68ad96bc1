"""Functional module system — the substrate of the Keras-style layer API.

Design: layers are *stateless descriptions*; parameters and mutable state (e.g.
BatchNorm moving stats) live in explicit pytrees threaded through ``apply``. This is
the TPU-native replacement for the reference's BigDL ``AbstractModule`` object graph
(every zoo Keras layer wraps one — /root/reference/zoo/.../pipeline/api/keras/layers/):
under ``jax.jit`` the whole model becomes a single traced XLA program, so there is no
module runtime to keep thread-safe and no per-layer buffers to manage.

Conventions
-----------
* ``build(rng, input_shape) -> (params, state)`` — ``input_shape`` EXCLUDES the batch
  dimension (matching the reference Keras-1 ``inputShape`` convention).
* ``apply(params, state, x, training=False, rng=None) -> (y, new_state)`` — arrays
  INCLUDE the batch dimension. Stateless layers return ``state`` unchanged.
* Params are float32 by default; compute runs in the active precision policy's
  ``compute_dtype`` (bfloat16 on TPU keeps the MXU at full rate).
"""

from __future__ import annotations

import contextlib
import threading
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from ..common.locks import traced_lock

Shape = Tuple[Optional[int], ...]
PyTree = Any

# ------------------------------------------------------------------ precision policy

# zoo-lock: leaf
_POLICY_LOCK = traced_lock("module._POLICY_LOCK")
_POLICY = {"param_dtype": jnp.float32, "compute_dtype": jnp.float32}


def set_policy(param_dtype=None, compute_dtype=None) -> None:
    with _POLICY_LOCK:
        if param_dtype is not None:
            _POLICY["param_dtype"] = jnp.dtype(param_dtype)
        if compute_dtype is not None:
            _POLICY["compute_dtype"] = jnp.dtype(compute_dtype)


def param_dtype():
    return _POLICY["param_dtype"]


def compute_dtype():
    pinned = getattr(_PINNED, "compute_dtype", None)
    return _POLICY["compute_dtype"] if pinned is None else pinned


@contextlib.contextmanager
def precision_policy(param_dtype=None, compute_dtype=None):
    """Scoped :func:`set_policy`: engage a precision override for the dynamic
    extent of the block (restored on exit). The training engine wraps its
    jitted-step dispatches in this so ``TrainConfig.compute_dtype`` affects
    exactly the traces it owns without leaking a global policy change."""
    prev = dict(_POLICY)
    set_policy(param_dtype, compute_dtype)
    try:
        yield
    finally:
        with _POLICY_LOCK:
            _POLICY.clear()
            _POLICY.update(prev)


_PINNED = threading.local()


@contextlib.contextmanager
def pinned_compute_dtype(dtype):
    """Hold :func:`compute_dtype` to ``dtype`` on THIS thread for the block,
    whatever the process-wide policy says or becomes. For an owner of jitted
    executables that read the policy once and trace later, on a thread of
    their own (the generation batcher): its traces see its dtype, and no
    other thread's trace sees a change."""
    prev = getattr(_PINNED, "compute_dtype", None)
    _PINNED.compute_dtype = jnp.dtype(dtype)
    try:
        yield
    finally:
        _PINNED.compute_dtype = prev


# ---------------------------------------------------------------------- initializers


def _fans(shape: Sequence[int]) -> Tuple[int, int]:
    if len(shape) == 1:
        return shape[0], shape[0]
    if len(shape) == 2:
        return shape[0], shape[1]
    receptive = int(np.prod(shape[:-2]))
    return shape[-2] * receptive, shape[-1] * receptive


def glorot_uniform(rng, shape, dtype):
    fan_in, fan_out = _fans(shape)
    limit = np.sqrt(6.0 / (fan_in + fan_out))
    return jax.random.uniform(rng, shape, dtype, -limit, limit)


def glorot_normal(rng, shape, dtype):
    fan_in, fan_out = _fans(shape)
    std = np.sqrt(2.0 / (fan_in + fan_out))
    return jax.random.normal(rng, shape, dtype) * jnp.asarray(std, dtype)


def he_normal(rng, shape, dtype):
    fan_in, _ = _fans(shape)
    return jax.random.normal(rng, shape, dtype) * jnp.asarray(np.sqrt(2.0 / fan_in), dtype)


def lecun_normal(rng, shape, dtype):
    fan_in, _ = _fans(shape)
    return jax.random.normal(rng, shape, dtype) * jnp.asarray(np.sqrt(1.0 / fan_in), dtype)


def normal_init(rng, shape, dtype):
    return jax.random.normal(rng, shape, dtype) * jnp.asarray(0.01, dtype)


def uniform_init(rng, shape, dtype):
    return jax.random.uniform(rng, shape, dtype, -0.05, 0.05)


def zeros_init(rng, shape, dtype):
    return jnp.zeros(shape, dtype)


def ones_init(rng, shape, dtype):
    return jnp.ones(shape, dtype)


INITIALIZERS: Dict[str, Callable] = {
    "glorot_uniform": glorot_uniform,
    "xavier": glorot_uniform,
    "glorot_normal": glorot_normal,
    "he_normal": he_normal,
    "lecun_normal": lecun_normal,
    "normal": normal_init,
    "gaussian": normal_init,
    "uniform": uniform_init,
    "zero": zeros_init,
    "zeros": zeros_init,
    "one": ones_init,
    "ones": ones_init,
}


def get_initializer(init: Union[str, Callable]) -> Callable:
    if callable(init):
        return init
    try:
        return INITIALIZERS[init]
    except KeyError:
        raise ValueError(f"unknown initializer {init!r}; known: {sorted(INITIALIZERS)}")


# -------------------------------------------------------------------------- layers

_NAME_COUNTS: Dict[str, int] = {}
# zoo-lock: leaf
_NAME_LOCK = traced_lock("module._NAME_LOCK")


def _auto_name(cls_name: str) -> str:
    with _NAME_LOCK:
        n = _NAME_COUNTS.get(cls_name, 0)
        _NAME_COUNTS[cls_name] = n + 1
    return f"{cls_name.lower()}_{n}"


class Layer:
    """Base class for all layers.

    Subclasses implement :meth:`build`, :meth:`apply`, :meth:`compute_output_shape`.
    """

    def __init__(self, name: Optional[str] = None, input_shape: Optional[Shape] = None):
        self.name = name or _auto_name(type(self).__name__)
        self.input_shape_hint = tuple(input_shape) if input_shape is not None else None

    # --- interface -----------------------------------------------------------
    def build(self, rng, input_shape: Shape) -> Tuple[PyTree, PyTree]:
        """Create (params, state) for ``input_shape`` (batch dim excluded)."""
        return {}, {}

    def apply(self, params: PyTree, state: PyTree, x, *, training: bool = False,
              rng=None) -> Tuple[Any, PyTree]:
        raise NotImplementedError

    def compute_output_shape(self, input_shape: Shape) -> Shape:
        return input_shape

    def cast_at_use(self, params: PyTree) -> PyTree:
        """Which leaves of ``params`` every forward of this layer reads only
        as ``jnp.asarray(leaf, compute_dtype)``: one bool per leaf, in the
        structure of ``params``. Such a leaf may be handed over already in
        the compute dtype (the cast is then the identity and the arithmetic
        the same, bit for bit); generation serving holds them so. False
        (the default) for a leaf that is also read at its own precision:
        normalization scales, embedding tables summed before the cast."""
        return jax.tree_util.tree_map(lambda _: False, params)

    def regularization(self, params: PyTree):
        """Regularization loss contribution for this layer's ``params``
        (summed into the training loss by the Estimator). Layers with
        ``w_regularizer``/``b_regularizer`` override the default 0."""
        total = 0.0
        w_reg = getattr(self, "w_regularizer", None)
        b_reg = getattr(self, "b_regularizer", None)
        if w_reg is not None and isinstance(params, dict) and "kernel" in params:
            total = total + w_reg(params["kernel"])
        if b_reg is not None and isinstance(params, dict) and "bias" in params:
            total = total + b_reg(params["bias"])
        return total

    # --- functional-graph sugar ---------------------------------------------
    def __call__(self, node_or_nodes):
        """Connect this layer into a functional graph (Keras ``layer.inputs(node)``
        parity — see Model/Input in analytics_zoo_tpu.nn.graph)."""
        from .graph import Node, apply_layer

        return apply_layer(self, node_or_nodes)

    def __repr__(self):
        return f"{type(self).__name__}(name={self.name!r})"

    # --- conveniences --------------------------------------------------------
    def init(self, rng, input_shape: Shape) -> Tuple[PyTree, PyTree]:
        return self.build(rng, input_shape)

    def param_count(self, params: PyTree) -> int:
        return sum(int(np.prod(p.shape)) for p in jax.tree_util.tree_leaves(params))


def as_compute(x):
    """Cast activations to the compute dtype (mixed-precision entry)."""
    dt = compute_dtype()
    if jnp.issubdtype(jnp.asarray(x).dtype, jnp.floating) and jnp.asarray(x).dtype != dt:
        return jnp.asarray(x, dt)
    return x


def cast_params(params: PyTree, dtype) -> PyTree:
    return jax.tree_util.tree_map(
        lambda p: p.astype(dtype) if jnp.issubdtype(p.dtype, jnp.floating) else p, params)


def split_rng(rng, n: int):
    if rng is None:
        return [None] * n
    return list(jax.random.split(rng, n))


def merge_shapes(shape: Shape, batch: Optional[int] = None) -> Tuple[int, ...]:
    """Concrete shape for tracing: replace None batch with a dummy size."""
    return tuple(batch if s is None else s for s in ((batch,) + tuple(shape)))
