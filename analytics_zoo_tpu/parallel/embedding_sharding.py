"""Mesh-row-sharded embedding tables with a model-parallel gather.

The north-star NCF recommender caps out where its user/item tables fit one
chip's HBM. The reference solved the capacity wall host-side with its PMem
feature layer; the TPU-native answer is the DLRM/BigDL-2.0 recipe (PAPERS.md
"BigDL 2.0"): shard the table by ROWS over a mesh axis and make the lookup a
model-parallel exchange instead of a local gather —

    all-gather(ids)  →  owner-shard partial gather  →  reduce-scatter(rows)

Each shard holds ``rows/n`` contiguous table rows. The (batch-sharded) lookup
ids are all-gathered so every shard sees the full batch, each shard gathers
the rows it OWNS (zeros elsewhere), and one tiled ``psum_scatter`` both sums
the partials (each id is owned by exactly one shard, so the "sum" is an exact
select — no float reassociation) and hands every shard its batch slice back.
Exactly one small int collective in, one row-sized collective out.

The backward pass is the transpose by construction: the row-grad
reduce-scatter transposes to an all-gather, the masked owner-gather
transposes to a scatter-add into the LOCAL shard only — so sparse-touched
rows update shard-locally and the dense replicated ``(vocab, embed)``
gradient never exists on any device. This composes with the ZeRO-1 gspmd
machinery unchanged: the table's base spec ``P(axis, None)`` already carries
the axis, so :func:`~.update_sharding.shard_spec_over_axis` leaves it alone
and the optimizer state lands congruently sharded (1/n rows of Adam moments
per device).

Serving-side, the capacity wall is solved by the host hot-row cache instead
(:mod:`analytics_zoo_tpu.serving.rowcache`) — unmarked model instances fall
back to a plain ``jnp.take`` and never need a mesh.
"""

from __future__ import annotations

from typing import Any, Callable, List, NamedTuple, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

__all__ = [
    "TableSharding", "owned_row_range", "pad_rows", "row_shard_spec",
    "shard_embedding_tables", "sharded_gather", "sharded_table_layers",
]


class TableSharding(NamedTuple):
    """How a marked embedding layer's table is laid out: mesh + the row axis
    (``shard_batch`` selects the training exchange — batch-sharded ids,
    all-gather in / reduce-scatter out — vs the replicated-batch serving
    exchange, masked gather + psum)."""

    mesh: Any
    axis: str = "dp"
    shard_batch: bool = True


def pad_rows(rows: int, n_shards: int) -> int:
    """Smallest row count >= ``rows`` divisible by ``n_shards`` (vocab
    padding: the +1-row id convention rarely divides a mesh axis)."""
    return ((int(rows) + n_shards - 1) // n_shards) * n_shards


def owned_row_range(rows: int, n_shards: int, shard: int) -> Tuple[int, int]:
    """Global ``[lo, hi)`` row range owned by ``shard`` under contiguous
    row sharding — the layout the gather, the row-delta publisher and the
    hot-row cache all key on."""
    per = rows // n_shards
    return shard * per, (shard + 1) * per


def row_shard_spec(shape, mesh, axis: str = "dp") -> P:
    """``P(axis, None)`` when the table's rows divide the axis, else
    replicated — the base param spec for a row-sharded table."""
    n = mesh.shape.get(axis, 1)
    if len(shape) == 2 and n > 1 and shape[0] % n == 0:
        return P(axis, None)
    return P(*([None] * len(shape)))


def sharded_gather(table, ids, mesh, axis: str = "dp", *,
                   shard_batch: bool = True):
    """Model-parallel row lookup: ``table`` is ``(rows, W)`` sharded
    ``P(axis, None)``, ``ids`` is any integer shape; returns
    ``ids.shape + (W,)`` rows.

    ``shard_batch=True`` (training): ids are laid ``P(axis)`` — the exchange
    is all-gather(ids) → owner partial gather → tiled reduce-scatter(rows),
    and the result stays batch-sharded. ``shard_batch=False`` (replicated
    batch, e.g. eval on a training mesh): every shard gathers its owned rows
    for the full batch and one ``psum`` rebuilds replicated rows.

    Falls back to a plain ``jnp.take`` when the axis is trivial or the rows
    don't divide (pad with :func:`pad_rows` first). Out-of-range ids return
    ZERO rows (no shard owns them) — unlike ``jnp.take``'s clamp — so padded
    vocab tails read as explicit zeros.
    """
    from jax import shard_map

    n = mesh.shape.get(axis, 1) if mesh is not None else 1
    ids = jnp.asarray(ids, jnp.int32)
    out_shape = tuple(ids.shape) + (table.shape[1],)
    flat = ids.reshape(-1)
    if n <= 1 or table.shape[0] % n != 0:
        return jnp.take(table, flat, axis=0).reshape(out_shape)
    rows_per = table.shape[0] // n
    use_batch = shard_batch and flat.shape[0] % n == 0

    def owned_partial(local_table, all_ids):
        loc = all_ids - jax.lax.axis_index(axis) * rows_per
        ok = (loc >= 0) & (loc < rows_per)
        part = jnp.take(local_table, jnp.where(ok, loc, 0), axis=0)
        return jnp.where(ok[:, None], part,
                         jnp.zeros((), local_table.dtype))

    if use_batch:
        def block(local_table, local_ids):
            all_ids = jax.lax.all_gather(local_ids, axis, tiled=True)
            part = owned_partial(local_table, all_ids)
            return jax.lax.psum_scatter(part, axis, scatter_dimension=0,
                                        tiled=True)

        out = shard_map(block, mesh=mesh,
                        in_specs=(P(axis, None), P(axis)),
                        out_specs=P(axis, None), check_vma=False)(table, flat)
    else:
        def block(local_table, all_ids):
            return jax.lax.psum(owned_partial(local_table, all_ids), axis)

        out = shard_map(block, mesh=mesh, in_specs=(P(axis, None), P()),
                        out_specs=P(), check_vma=False)(table, flat)
    return out.reshape(out_shape)


def sharded_table_layers(model) -> List[Any]:
    """Embedding-bearing layers of ``model`` (recursing through containers)
    whose tables CAN shard — i.e. expose a 2-D ``embeddings`` param."""
    from ..nn.layers.embedding import Embedding, FusedPairEmbedding

    out, stack, seen = [], [model], set()
    while stack:
        node = stack.pop()
        if id(node) in seen:
            continue
        seen.add(id(node))
        for layer in getattr(node, "layers", []) or []:
            if isinstance(layer, (Embedding, FusedPairEmbedding)):
                out.append(layer)
            elif getattr(layer, "layers", None):
                stack.append(layer)
    return out


def shard_embedding_tables(model, mesh, *, axis: str = "dp",
                           min_rows: int = 0,
                           shard_batch: bool = True) -> Callable:
    """Mark every divisible embedding table in ``model`` for the sharded
    gather and return the matching ``(path, leaf) -> PartitionSpec``
    param-sharding rule for the :class:`~...engine.Estimator`.

    Marking is per LAYER INSTANCE: the training model gathers through the
    mesh while a separately-constructed serving copy of the same
    architecture stays on the plain single-device ``jnp.take`` path. Tables
    whose rows don't divide the axis (pad the vocab with :func:`pad_rows`)
    or fall under ``min_rows`` stay replicated — a tiny table is not worth
    a collective round.

    The returned rule shards ONLY ``embeddings`` leaves the walk marked;
    everything else replicates, and the ZeRO-1 update-sharding rule
    (:func:`~.update_sharding.make_update_sharding`) then extends the dense
    leaves with the usual dp shard while leaving the already-axis-bearing
    tables untouched.
    """
    n = mesh.shape.get(axis, 1)

    def eligible(rows: int) -> bool:
        return n > 1 and rows % n == 0 and rows >= min_rows

    marked_shapes = set()
    for layer in sharded_table_layers(model):
        rows = (layer.user_count + layer.item_count
                if hasattr(layer, "user_count") else layer.input_dim)
        if eligible(int(rows)):
            layer.table_sharding = TableSharding(mesh, axis, shard_batch)
            marked_shapes.add(int(rows))

    def rule(path, leaf) -> P:
        shape = tuple(getattr(leaf, "shape", ()))
        keys = [str(getattr(p, "key", getattr(p, "name", p))) for p in path]
        if (len(shape) == 2 and keys and keys[-1] == "embeddings"
                and shape[0] in marked_shapes and eligible(shape[0])):
            return P(axis, None)
        return P(*([None] * len(shape)))

    return rule
