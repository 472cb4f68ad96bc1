"""Pipeline parallelism — GPipe-style microbatching over the ``pp`` mesh axis
(SURVEY.md §2.2: "stage mesh axis + jax.lax collective permute microbatching").

Design (TPU-idiomatic, no per-stage Python processes):
* stage parameters are STACKED on a leading axis and sharded over ``pp`` so each
  device holds exactly its stage's weights;
* inside ``shard_map`` every device runs the same program: at step t it applies
  its stage to the activation it holds, then ``ppermute``s the result to the
  next stage. After ``n_micro + n_stages - 1`` steps every microbatch has
  flowed through every stage (the classic pipeline schedule, bubble =
  (n_stages-1)/(n_micro+n_stages-1));
* the loop is a ``lax.scan`` → one compiled program, differentiable (JAX
  autodiff through ``ppermute`` gives the reverse schedule for backward).

The stage function must be shape-preserving (hidden size constant across
stages) — the standard transformer-block pipeline regime.
"""

from __future__ import annotations

import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.lax import axis_size
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P


def stack_stage_params(params_list):
    """[per-stage pytree] → one pytree with a leading stage axis (to shard
    over ``pp``)."""
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *params_list)


def _pipeline_local(stage_params, x_micro, *, stage_fn, axis_name: str):
    """Runs INSIDE shard_map. ``stage_params``: this device's stage params
    (leading stage axis already consumed by sharding → shape (1, ...) per leaf);
    ``x_micro``: (n_micro, micro_B, ...) — full microbatch stream, present on
    stage 0 (other stages receive via the ring).
    """
    n_stages = axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    my_params = jax.tree_util.tree_map(lambda p: p[0], stage_params)
    n_micro = x_micro.shape[0]
    total = n_micro + n_stages - 1
    perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]

    micro_shape = x_micro.shape[1:]
    carry_in = jnp.zeros(micro_shape, x_micro.dtype)   # activation in flight
    outputs = jnp.zeros((n_micro,) + micro_shape, x_micro.dtype)

    def step(state, t):
        carry, outputs = state
        # stage 0 injects microbatch t (while it exists); others use the ring input
        inject = jnp.where(t < n_micro, jnp.minimum(t, n_micro - 1), 0)
        x_in = jnp.where(idx == 0,
                         jax.lax.dynamic_index_in_dim(x_micro, inject, 0,
                                                      keepdims=False),
                         carry)
        y = stage_fn(my_params, x_in)
        # last stage records finished microbatch (micro t arrives at stage s at
        # step t + s; on the last stage: out_t = t - (n_stages - 1))
        out_t = t - (n_stages - 1)
        record = jnp.logical_and(idx == n_stages - 1, out_t >= 0)
        outputs = jax.lax.cond(
            record,
            lambda o: jax.lax.dynamic_update_index_in_dim(
                o, y, jnp.maximum(out_t, 0), 0),
            lambda o: o, outputs)
        carry = jax.lax.ppermute(y, axis_name, perm)
        return (carry, outputs), None

    (carry, outputs), _ = jax.lax.scan(step, (carry_in, outputs),
                                       jnp.arange(total))
    # outputs live on the last stage; broadcast so every shard returns them
    # (psum over the one-hot owner is a broadcast on the pp ring)
    owner = (idx == n_stages - 1).astype(outputs.dtype)
    outputs = jax.lax.psum(outputs * owner, axis_name)
    return outputs


def pipeline_apply(stage_fn: Callable[[Any, jnp.ndarray], jnp.ndarray],
                   stacked_params, x: jnp.ndarray, mesh, *,
                   n_microbatches: int, axis_name: str = "pp"):
    """Apply ``n_stages`` copies of ``stage_fn`` as a pipeline.

    Args:
        stage_fn: ``(stage_params, activation) -> activation`` (shape-preserving).
        stacked_params: pytree with leading stage axis == mesh.shape[axis_name].
        x: global batch (B, ...); B must divide by n_microbatches.
        mesh: the global mesh (other axes replicated here; compose via vmap/dp
              sharding of the batch upstream).
    Returns the final-stage activations, shape (B, ...).
    """
    n_stages = mesh.shape[axis_name]
    b = x.shape[0]
    if b % n_microbatches:
        raise ValueError(f"batch {b} not divisible by {n_microbatches} microbatches")
    x_micro = x.reshape((n_microbatches, b // n_microbatches) + x.shape[1:])

    param_specs = jax.tree_util.tree_map(
        lambda p: P(axis_name), stacked_params)
    fn = shard_map(
        functools.partial(_pipeline_local, stage_fn=stage_fn,
                          axis_name=axis_name),
        mesh=mesh,
        in_specs=(param_specs, P()),     # params stage-sharded, stream replicated
        out_specs=P(),
        check_vma=False)
    out = fn(stacked_params, x_micro)
    return out.reshape((b,) + x.shape[1:])
