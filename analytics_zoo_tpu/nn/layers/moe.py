"""Mixture-of-Experts layer + expert parallelism over the ``ep`` mesh axis
(SURVEY.md §2.2 "EP/MoE: expert mesh axis + all-to-all" — absent in the
reference, first-class here).

GShard-style dense dispatch: the top-k router produces a dispatch one-hot
``(tokens, experts, capacity)``; expert compute is ONE batched einsum over the
expert dimension (MXU-shaped), and the combine einsum weights expert outputs
back per token. Under a mesh with ``ep > 1`` a sharding constraint places the
expert dimension on ``ep`` — GSPMD inserts the all-to-alls (the idiomatic TPU
form of expert parallelism; no manual collectives).

Load-balancing: the standard auxiliary loss (mean gate fraction × mean router
probability per expert, scaled by n_experts²) is returned in the layer state
under ``"aux_loss"`` so training loops can add it.
"""

from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from ..module import Layer, as_compute, get_initializer, param_dtype
from ...nn.activations import get_activation


class MoE(Layer):
    """Token-wise top-k mixture of expert MLPs: (B, T, D) → (B, T, D)."""

    def __init__(self, hidden_size: int, n_experts: int = 8,
                 intermediate_size: Optional[int] = None, top_k: int = 2,
                 capacity_factor: float = 1.25, activation="gelu",
                 ep_axis: str = "ep", name=None, input_shape=None):
        super().__init__(name=name, input_shape=input_shape)
        self.hidden_size = hidden_size
        self.n_experts = int(n_experts)
        self.intermediate = intermediate_size or 4 * hidden_size
        self.top_k = int(top_k)
        self.capacity_factor = float(capacity_factor)
        self.activation = get_activation(activation)
        self.ep_axis = ep_axis

    def build(self, rng, input_shape):
        d = input_shape[-1]
        k_router, k_up, k_down = jax.random.split(rng, 3)
        init = get_initializer("glorot_uniform")
        dt = param_dtype()
        return {
            "router_kernel": init(k_router, (d, self.n_experts), dt),
            # leading expert axis — shard over ep for expert parallelism
            "expert_up": init(k_up, (self.n_experts, d, self.intermediate), dt),
            "expert_up_bias": jnp.zeros((self.n_experts, self.intermediate), dt),
            "expert_down": init(k_down,
                                (self.n_experts, self.intermediate, d), dt),
            "expert_down_bias": jnp.zeros((self.n_experts, d), dt),
        }, {}

    def cast_at_use(self, params):
        # router, experts and their biases: every read in ``apply``
        return jax.tree_util.tree_map(lambda _: True, params)

    def _ep_constraint(self, x, spec_with_expert_dim):
        """Pin the expert dim to the ep axis when running under a mesh.

        No zoo context / ep==1 → no-op. With ep>1, a failing constraint
        (e.g. n_experts not divisible by ep) RAISES: the user asked for
        expert parallelism and silently running replicated would hide it.
        """
        try:
            from ...common.context import get_zoo_context

            mesh = get_zoo_context(auto_init=False).mesh
        except RuntimeError:
            return x  # no context initialized
        if mesh.shape.get(self.ep_axis, 1) <= 1:
            return x
        if self.n_experts % mesh.shape[self.ep_axis]:
            raise ValueError(
                f"n_experts={self.n_experts} not divisible by "
                f"{self.ep_axis}={mesh.shape[self.ep_axis]}")
        from jax.sharding import NamedSharding

        return jax.lax.with_sharding_constraint(
            x, NamedSharding(mesh, spec_with_expert_dim))

    def apply(self, params, state, x, *, training=False, rng=None):
        from jax.sharding import PartitionSpec as P

        x = as_compute(x)
        b, t, d = x.shape
        tokens = x.reshape(b * t, d)
        n_tok = b * t
        E = self.n_experts
        cap = max(1, int(math.ceil(self.top_k * n_tok / E
                                   * self.capacity_factor)))

        logits = (tokens @ jnp.asarray(params["router_kernel"], x.dtype)
                  ).astype(jnp.float32)
        probs = jax.nn.softmax(logits, axis=-1)              # (N, E)

        # top-k gating with per-expert capacity (GShard dispatch tensors)
        gate_vals, gate_idx = jax.lax.top_k(probs, self.top_k)  # (N, k)
        gate_vals = gate_vals / jnp.clip(
            gate_vals.sum(-1, keepdims=True), 1e-9)
        dispatch = jnp.zeros((n_tok, E, cap), jnp.float32)
        combine = jnp.zeros((n_tok, E, cap), jnp.float32)
        # running per-expert fill across slots: slot s's positions start after
        # ALL slot<s assignments to that expert (GShard's locations2 offset) —
        # without it, a slot-0 and a slot-1 token routed to the same expert
        # collide on one capacity slot and their embeddings get summed
        expert_fill = jnp.zeros((E,), jnp.float32)
        for slot in range(self.top_k):
            e = gate_idx[:, slot]                            # (N,)
            onehot = jax.nn.one_hot(e, E, dtype=jnp.float32)  # (N, E)
            pos = (jnp.cumsum(onehot, axis=0) - onehot
                   + expert_fill[None, :])                   # (N, E)
            pos_tok = jnp.sum(pos * onehot, axis=1).astype(jnp.int32)  # (N,)
            keep = pos_tok < cap
            pos_oh = jax.nn.one_hot(jnp.minimum(pos_tok, cap - 1), cap,
                                    dtype=jnp.float32)
            contrib = (onehot * keep[:, None])[:, :, None] * pos_oh[:, None, :]
            dispatch = dispatch + contrib
            combine = combine + contrib * gate_vals[:, slot][:, None, None]
            expert_fill = expert_fill + onehot.sum(axis=0)

        # expert input: (E, cap, D) — the all-to-all boundary under ep
        expert_in = jnp.einsum("nec,nd->ecd", dispatch,
                               tokens.astype(jnp.float32)).astype(x.dtype)
        expert_in = self._ep_constraint(expert_in, P(self.ep_axis, None, None))
        h = jnp.einsum("ecd,edi->eci", expert_in,
                       jnp.asarray(params["expert_up"], x.dtype))
        h = self.activation(h + jnp.asarray(params["expert_up_bias"],
                                            x.dtype)[:, None, :])
        out = jnp.einsum("eci,eid->ecd", h,
                         jnp.asarray(params["expert_down"], x.dtype))
        out = out + jnp.asarray(params["expert_down_bias"], x.dtype)[:, None, :]
        out = self._ep_constraint(out, P(self.ep_axis, None, None))

        y = jnp.einsum("nec,ecd->nd", combine,
                       out.astype(jnp.float32)).astype(x.dtype)

        # load-balance aux loss (Switch/GShard form)
        frac_tokens = jnp.mean(dispatch.sum(-1), axis=0)      # (E,)
        frac_probs = jnp.mean(probs, axis=0)                  # (E,)
        aux = jnp.sum(frac_tokens * frac_probs) * (E ** 2) / self.top_k
        new_state = dict(state)
        new_state["aux_loss"] = aux
        return y.reshape(b, t, d), new_state

    def compute_output_shape(self, input_shape):
        return tuple(input_shape)
