"""Weight-update sharding (ZeRO-1) over the ``dp`` mesh axis.

This is the last core subsystem of the reference rebuilt TPU-native: BigDL's
``AllReduceParameter`` (Topology.scala:1129-1131, 1578-1597) slices the flat
parameter vector across nodes, reduces each gradient slice to its owner, runs
the optimizer update for that slice only, and broadcasts updated slices back.
On a pure data-parallel mesh the equivalent exchange is

    reduce-scatter(grads) → shard-local optimizer update → all-gather(params)

("Automatic Cross-Replica Sharding of Weight Update in Data-Parallel Training",
Xu et al. 2020): per-step gradient communication stays one collective round,
and optimizer state (plus the f32 master weights of the mixed-precision path)
shrinks to ``1/dp`` per device.

Two implementations, selected by the training engine:

* **flat** (pure-dp mesh) — the BigDL layout, literally: the gradient pytree is
  flattened to one padded f32 vector inside ``shard_map``; ``psum_scatter``
  hands each replica its slice, the optimizer updates that slice against a
  flat (sharded) optimizer state, and one tiled ``all_gather`` rebuilds the
  replicated params. The collective count per *global* step is structural —
  gradient accumulation scans microbatches over device-local grads, so K
  microbatches still cost exactly one reduce-scatter + one all-gather.
* **gspmd** (meshes that also shard params over ``fsdp``/``tp``) —
  :func:`make_update_sharding` extends the per-leaf
  :func:`~analytics_zoo_tpu.parallel.sharding.make_param_sharding` specs with a
  ``dp`` axis on the largest divisible dim; optimizer state is *placed* with
  those specs and the step constrains grads to them, letting the SPMD
  partitioner place the reduce-scatter/all-gather pair (the Xu et al.
  mechanism). Composes with the existing fsdp/tp rules; collective placement
  inside an accumulation scan is XLA's choice on this path.
"""

from __future__ import annotations

from typing import Any, Callable, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.lax import axis_size
from jax.sharding import PartitionSpec as P

__all__ = [
    "FlatParamMeta", "FlatUpdateState", "MasterWeightsState",
    "collective_counts", "flat_exchange", "flat_meta", "flatten_tree",
    "make_update_sharding", "shard_spec_over_axis",
    "unflatten_tree", "with_master_weights",
]


# --------------------------------------------------------- gspmd per-leaf specs
def shard_spec_over_axis(spec: P, shape: Sequence[int], mesh,
                         axis: str = "dp") -> P:
    """Extend ``spec`` with ``axis`` on the largest divisible dim.

    Used to derive the optimizer-state/gradient-shard placement from a param's
    base (fsdp/tp) spec: prefers an unsharded dim; otherwise appends ``axis``
    to an existing dim's axis tuple when the combined product still divides;
    leaves the spec unchanged (replicated update for that leaf) when nothing
    divides — small biases/scalars are not worth a collective.

    For 2-D leaves the *row* dim (dim 0) wins ties: embedding tables are
    ``(vocab, embed)`` and row sharding is what the sharded-gather path and
    row-delta publishing key on, so an oblong table with ``embed`` larger
    than the per-shard vocab slice must still shard by rows, not columns.
    Dims of other ranks keep the largest-first order (best bytes/shard).
    """
    size = mesh.shape.get(axis, 1)
    shape = tuple(shape)
    if size <= 1 or not shape:
        return spec
    entries = list(spec) + [None] * (len(shape) - len(spec))
    entries = entries[: len(shape)]
    used = set()
    for e in entries:
        for a in (e if isinstance(e, tuple) else (e,)):
            if a is not None:
                used.add(a)
    if axis in used:
        return P(*entries)

    def axprod(e) -> int:
        p = 1
        for a in (e if isinstance(e, tuple) else ((e,) if e else ())):
            p *= mesh.shape[a]
        return p

    if len(shape) == 2:
        # (vocab, embed) tables: rows first, regardless of which dim is larger
        order = [0, 1]
    else:
        order = sorted(range(len(shape)), key=lambda i: -shape[i])
    for i in order:
        if entries[i] is None and shape[i] % size == 0:
            entries[i] = axis
            return P(*entries)
    for i in order:
        cur = axprod(entries[i])
        if entries[i] is not None and shape[i] % (cur * size) == 0:
            e = entries[i] if isinstance(entries[i], tuple) else (entries[i],)
            entries[i] = e + (axis,)
            return P(*entries)
    return P(*entries)


def make_update_sharding(mesh, base_rule: Optional[Callable] = None,
                         axis: str = "dp") -> Callable:
    """``(path, leaf) -> PartitionSpec`` for optimizer-state placement: the
    param's base spec (fsdp/tp rules, or replicated) plus ``axis`` on the
    largest divisible dim. Congruent with the grad shards the step's
    ``with_sharding_constraint`` produces."""

    def rule(path, leaf) -> P:
        shape = tuple(getattr(leaf, "shape", ()))
        base = base_rule(path, leaf) if base_rule is not None else P()
        return shard_spec_over_axis(base, shape, mesh, axis)

    return rule


# ------------------------------------------------------------- flat exchange
class FlatParamMeta(NamedTuple):
    """Static flattening layout of a param pytree (BigDL AllReduceParameter's
    flat-vector view): leaf order/shapes/dtypes + dp-padded total length."""

    treedef: Any
    shapes: Tuple[Tuple[int, ...], ...]
    sizes: Tuple[int, ...]
    dtypes: Tuple[Any, ...]
    n: int
    npad: int
    n_shards: int

    @property
    def shard_size(self) -> int:
        return self.npad // self.n_shards


class FlatUpdateState(NamedTuple):
    """Optimizer state of the flat exchange: the inner transformation's state
    over the flat (npad,) vector — dp-sharded — plus the f32 master-weight
    shard of the mixed-precision path (``None`` when params are already f32,
    in which case the master shard is re-sliced from the replicated params
    each step instead of stored)."""

    inner_state: Any
    master: Any


class MasterWeightsState(NamedTuple):
    """State of :func:`with_master_weights` (gspmd/replicated mixed-precision
    path): inner optimizer state + the f32 master copy of the params."""

    inner_state: Any
    master: Any


def flat_meta(params, n_shards: int) -> FlatParamMeta:
    leaves, treedef = jax.tree_util.tree_flatten(params)
    shapes = tuple(tuple(l.shape) for l in leaves)
    sizes = tuple(int(np.prod(s)) if s else 1 for s in shapes)
    dtypes = tuple(jnp.asarray(l).dtype for l in leaves)
    n = int(sum(sizes))
    npad = ((n + n_shards - 1) // n_shards) * n_shards
    return FlatParamMeta(treedef, shapes, sizes, dtypes, n, npad, n_shards)


def flatten_tree(tree, meta: FlatParamMeta, dtype=jnp.float32):
    """Pytree → one (npad,) vector in ``dtype`` (zero-padded tail)."""
    leaves = jax.tree_util.tree_leaves(tree)
    vec = jnp.concatenate([jnp.ravel(l).astype(dtype) for l in leaves])
    if meta.npad > meta.n:
        vec = jnp.pad(vec, (0, meta.npad - meta.n))
    return vec


def unflatten_tree(vec, meta: FlatParamMeta):
    """(npad,) vector → pytree with the meta's original shapes/dtypes."""
    out, off = [], 0
    for shape, size, dt in zip(meta.shapes, meta.sizes, meta.dtypes):
        out.append(jax.lax.slice_in_dim(vec, off, off + size)
                   .reshape(shape).astype(dt))
        off += size
    return jax.tree_util.tree_unflatten(meta.treedef, out)


def flat_opt_init(tx: optax.GradientTransformation, params,
                  meta: FlatParamMeta, keep_master: bool) -> FlatUpdateState:
    """Global-view init (arrays are full (npad,) vectors; the engine places
    them dp-sharded). ``params`` may be any float dtype — masters are f32."""
    flat32 = flatten_tree(params, meta, jnp.float32)
    return FlatUpdateState(tx.init(flat32), flat32 if keep_master else None)


def flat_exchange(params, grads, opt_state: FlatUpdateState,
                  meta: FlatParamMeta, tx: optax.GradientTransformation, *,
                  axis: str = "dp",
                  clip_norm: Optional[float] = None,
                  clip_value: Optional[tuple] = None):
    """One weight-update exchange; runs INSIDE ``shard_map`` (manual over
    ``axis``). ``grads`` are this replica's local-mean grads.

    Returns ``(new_params, new_opt_state, grad_norm)``; ``grad_norm`` is the
    f32 global (pre-clip) gradient L2 norm. Exactly one grad-sized collective
    round per call: ``psum_scatter`` in, tiled ``all_gather`` out (the norm
    rides a scalar psum).
    """
    n = axis_size(axis)
    shard = meta.shard_size
    idx = jax.lax.axis_index(axis)

    gflat = flatten_tree(grads, meta, jnp.float32)
    # mean over replicas: local grads are means over the local micro/batch
    gshard = jax.lax.psum_scatter(gflat, axis, scatter_dimension=0,
                                  tiled=True) / n
    gnorm = jnp.sqrt(jax.lax.psum(jnp.sum(gshard * gshard), axis))
    if clip_norm is not None:
        # f32 global-norm clipping computed across the scattered shards —
        # optax.clip_by_global_norm would only see one shard here
        gshard = gshard * jnp.minimum(1.0, clip_norm / (gnorm + 1e-12))
    if clip_value is not None:
        lo, hi = clip_value
        gshard = jnp.clip(gshard, lo, hi)

    if opt_state.master is not None:
        master = opt_state.master        # persistent f32 shard (bf16 params)
    else:                                # f32 params: re-slice, store nothing
        pflat = flatten_tree(params, meta, jnp.float32)
        master = jax.lax.dynamic_slice_in_dim(pflat, idx * shard, shard)

    updates, inner2 = tx.update(gshard, opt_state.inner_state, master)
    master2 = optax.apply_updates(master, updates)

    # all-gather in the MODEL dtype: under bf16 params the param broadcast
    # costs half the bytes of the f32 masters
    gather_dt = meta.dtypes[0] if len(set(meta.dtypes)) == 1 else jnp.float32
    new_flat = jax.lax.all_gather(master2.astype(gather_dt), axis, axis=0,
                                  tiled=True)
    new_params = unflatten_tree(new_flat, meta)
    new_opt = FlatUpdateState(inner2,
                              master2 if opt_state.master is not None else None)
    return new_params, new_opt, gnorm


# ------------------------------------------------- master weights (gspmd path)
def with_master_weights(tx: optax.GradientTransformation
                        ) -> optax.GradientTransformation:
    """Wrap ``tx`` so f32 master weights live in (and only in) the optimizer
    state: ``update`` expects f32 grads, runs ``tx`` against the masters, and
    returns the NEW low-precision params as the "updates" (the engine installs
    them directly instead of ``optax.apply_updates``)."""

    def init(params):
        master = jax.tree_util.tree_map(
            lambda p: p.astype(jnp.float32)
            if jnp.issubdtype(jnp.asarray(p).dtype, jnp.floating) else p,
            params)
        return MasterWeightsState(tx.init(master), master)

    def update(grads, state, params=None):
        g32 = jax.tree_util.tree_map(lambda g: g.astype(jnp.float32), grads)
        updates, inner2 = tx.update(g32, state.inner_state, state.master)
        master2 = optax.apply_updates(state.master, updates)
        if params is not None:
            new_params = jax.tree_util.tree_map(
                lambda m, p: m.astype(jnp.asarray(p).dtype), master2, params)
        else:
            new_params = master2
        return new_params, MasterWeightsState(inner2, master2)

    return optax.GradientTransformation(init, update)


# --------------------------------------------------------------- HLO forensics
# The HLO collective counter moved onto the shared static-analysis rule
# engine (analysis/rules/collectives.py) where it backs the
# "collective-budget-hlo" rule; re-exported here so existing callers (the
# bench, tests) keep their import path.
from ..analysis.rules.collectives import collective_counts  # noqa: E402
