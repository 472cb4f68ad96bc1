"""Weight-update sharding (ZeRO-1) over the ``dp`` mesh axis.

This is the last core subsystem of the reference rebuilt TPU-native: BigDL's
``AllReduceParameter`` (Topology.scala:1129-1131, 1578-1597) slices the flat
parameter vector across nodes, reduces each gradient slice to its owner, runs
the optimizer update for that slice only, and broadcasts updated slices back.
On a pure data-parallel mesh the equivalent exchange is

    reduce-scatter(grads) → shard-local optimizer update → all-gather(params)

("Automatic Cross-Replica Sharding of Weight Update in Data-Parallel Training",
Xu et al. 2020): per-step gradient communication moves each gradient byte
once, and optimizer state (plus the f32 master weights of the mixed-precision
path) shrinks to ``1/dp`` per device.

Two implementations, selected by the training engine:

* **flat** (pure-dp mesh) — the BigDL layout, literally: inside ``shard_map``
  the gradient pytree is viewed as one f32 matrix, every leaf taking whole
  rows of it, cut into equal **buckets** of rows (:func:`flat_meta`; about
  one transformer block each, exactly one for a model that fits one). Per
  bucket, ``psum_scatter`` hands each replica its column block, the
  optimizer updates that shard against the bucket's (sharded) optimizer
  state, and one tiled ``all_gather`` rebuilds the bucket's replicated
  params. A bucket is stacked from the row blocks of the leaves it covers,
  never sliced out of the whole view, so it depends on those gradients alone
  and its all-gather can run under the next bucket's reduction and update;
  one whole-vector exchange could start only after the LAST gradient and ran
  wholly exposed (three tenths of the step on four v5e chips). In a model of
  several buckets the view is as wide as the parameter matrices' own minor
  dimension (or a divisor of it), so a matrix enters by **its own rows**:
  whole column blocks of whole lane tiles, cut and stacked, where a raveled
  and re-cut matrix is re-tiled element by element on its way into the
  bucket and again on its way out. A matrix of an odd width whose ROWS are
  a whole number of view widths (a head of 2,048 x 50,257) enters by the
  rows of its **transpose**: as many rows of the view as its ravel took,
  at one transposing pass each way (on a v5e a bitcast: the compiler keeps
  such a parameter with its rows minor, which is why its ravel was walked
  row by row, in a ``while`` loop). All buckets have one shape and go
  through the same two jitted functions, so the traced step defines each
  collective once. The
  collective count per *global* step is structural — gradient accumulation
  scans microbatches over device-local grads, so K microbatches still cost
  exactly one reduce-scatter + one all-gather per bucket.
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
    "adopt_flat_layout", "collective_counts", "flat_bucket", "flat_exchange",
    "flat_meta", "flat_opt_init", "flatten_tree", "make_update_sharding",
    "shard_spec_over_axis", "unflatten_buckets", "with_master_weights",
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
#: Target length of one bucket of the flat exchange, in elements: about one
#: transformer block at hidden size 2048 (12 * 2048**2). The bucket length a
#: model gets is derived from its parameter count (``flat_meta``): a model that
#: fits in one target gets exactly one bucket, a larger one gets equal buckets
#: of at most about this length.
BUCKET_TARGET_LEN = 48 * 2 ** 20
#: Most columns of one replica's shard of a bucket (a power of two). A bucket
#: travels as a ``(rows, n_shards * cols)`` matrix scattered over its MINOR
#: dimension: the v5e compiler emits a real reduce-scatter for that (128-4096
#: columns a shard), and rewrites a scatter over the major or only dimension,
#: where a shard is one contiguous block, to a whole-operand all-reduce.
SHARD_COLS = 1024
#: A large shard's row count is rounded up to a multiple of this: the same
#: compiler falls back to the all-reduce when it cannot cut the rows into
#: chunks (11,512 = 8 x 1,439 rows, 1,439 prime, did; 11,520 did not).
SHARD_ROWS_MULTIPLE = 128
#: Lanes of one tile of the chip's memory: a matrix enters the view by its
#: own rows only where a shard of one of its column blocks is whole tiles.
LANES = 128
#: Rows of one tile at the narrowest parameter dtype (bf16: 16 rows): the
#: matrices whose row count is a multiple take the view's first rows, so
#: every block of theirs starts on a tile.
ROW_TILE = 16


class FlatParamMeta(NamedTuple):
    """Static flattening layout of a param pytree (BigDL AllReduceParameter's
    flat-vector view): leaf order/shapes/dtypes, and the cut of the flat view
    into ``n_buckets`` equal buckets. The flat view is a matrix of
    ``n_shards * cols`` columns in which leaf ``i`` takes ``leaf_rows[i]``
    rows, the leaves following one another in ``order``; a bucket is
    ``shard_shape[0]`` consecutive rows (the last one zero-padded), exchanged
    and its state held as that ``bucket_shape`` matrix; replica ``i`` owns
    column block ``i`` (``shard_shape``) of every bucket.

    A leaf's rows are its **row matrix**. Where ``col_blocks[i]`` is 0 that
    is the raveled leaf, zero-padded to whole rows. Where it is ``k > 0`` the
    leaf (of two or more dimensions, seen as the matrix of its minor one) is
    ``k`` times as wide as the view and enters by its own rows: its ``k``
    column blocks one below the other, each row of the view a piece of one
    row of the leaf, so nothing is re-tiled on the way into a bucket or out
    of it. Where it is ``-k`` the leaf is a matrix whose columns do not fit
    the view but whose rows are ``k`` view widths, and it enters by the rows
    of its **transpose**, exactly as an own-rows leaf of the transposed
    shape would: as many rows as its ravel takes, none of them padding, at
    one transposing pass each way (the ravel of the four-chip cell's head,
    2,048 x 50,257, was walked row by row in two ``while`` loops, 12 ms of
    an exchange of 70; its transposes are bitcasts there, because the
    chip's compiler keeps that parameter with its rows minor). Every leaf
    starts on a row, so a bucket is stacked from whole row blocks of the
    leaves it covers; a bucket concatenated from 1-D pieces had to be
    re-tiled into its matrix, which cost the four-chip cell a tenth of its
    exchange and half the step's compile time, and matrices raveled into
    rows of another width 6 ms more of its 76."""

    treedef: Any
    shapes: Tuple[Tuple[int, ...], ...]
    sizes: Tuple[int, ...]
    dtypes: Tuple[Any, ...]
    n: int                          # parameters (no padding counted)
    n_shards: int
    n_buckets: int
    shard_shape: Tuple[int, int]
    leaf_rows: Tuple[int, ...]
    order: Tuple[int, ...]          # leaves in the order they take rows
    col_blocks: Tuple[int, ...]     # 0: raveled; k: own rows; -k: transpose's

    @property
    def bucket_shape(self) -> Tuple[int, int]:
        rows, cols = self.shard_shape
        return rows, self.n_shards * cols

    @property
    def bucket_len(self) -> int:
        rows, width = self.bucket_shape
        return rows * width

    @property
    def npad(self) -> int:
        """Elements of the whole flat view, padding included."""
        return self.n_buckets * self.bucket_len

    @property
    def own_rows_share(self) -> float:
        """Share of the parameters that enter the view by their own rows or
        their transpose's: everything that is not raveled."""
        return sum(z for z, k in zip(self.sizes, self.col_blocks) if k) / max(
            1, self.n)

    @property
    def layout(self) -> Optional[np.ndarray]:
        """What tells this view from another of the same bucket shape (its
        width and rows, the leaves' order and signed column blocks), or
        ``None`` for the plain view: every leaf raveled, in tree order."""
        if not any(self.col_blocks):
            return None
        return np.asarray(self.bucket_shape + self.order + self.col_blocks,
                          np.int32)

    def pieces(self, b: int) -> Tuple[Tuple[int, int, int], ...]:
        """``(leaf index, first row, end row)`` of the row blocks of the
        leaves' row matrices that fall in bucket ``b``, in flat order."""
        rows = self.shard_shape[0]
        lo, hi = b * rows, (b + 1) * rows
        out, off = [], 0
        for i in self.order:
            n_rows = self.leaf_rows[i]
            s, e = max(lo, off), min(hi, off + n_rows)
            if s < e:
                out.append((i, s - off, e - off))
            off += n_rows
        return tuple(out)

    def blocks(self, i: int, r0: int, r1: int):
        """Rows ``[r0, r1)`` of leaf ``i``'s row matrix as ``(column block,
        first row, end row)`` runs of the rows of the leaf (of its transpose
        where ``col_blocks[i]`` is negative)."""
        rows = self.leaf_rows[i] // abs(self.col_blocks[i])
        while r0 < r1:
            j = r0 // rows
            end = min(r1, (j + 1) * rows)
            yield j, r0 - j * rows, end - j * rows
            r0 = end


class FlatUpdateState(NamedTuple):
    """Optimizer state of the flat exchange, one entry per bucket: the inner
    transformation's state over that bucket's ``bucket_shape`` matrix —
    dp-sharded over its columns — plus the f32 master weights of the
    mixed-precision path (``master`` is ``None`` when params are already f32,
    in which case the master shard is re-sliced from the replicated params
    each step instead of stored). The buckets' matrices, stacked, are the
    flat view of :class:`FlatParamMeta`. ``layout`` is that meta's
    ``layout``: ``None`` for the plain view, else the small vector that a
    snapshot carries so that it is never read under another view whose
    buckets happen to have the same shape."""

    inner_state: Any
    master: Any
    layout: Any = None


class MasterWeightsState(NamedTuple):
    """State of :func:`with_master_weights` (gspmd/replicated mixed-precision
    path): inner optimizer state + the f32 master copy of the params."""

    inner_state: Any
    master: Any


def flat_meta(params, n_shards: int,
              bucket_len: Optional[int] = None) -> FlatParamMeta:
    """Layout of ``params`` for the flat exchange over ``n_shards`` replicas.
    ``bucket_len`` is the TARGET bucket length in elements (default
    :data:`BUCKET_TARGET_LEN`): the flat view is cut into equal buckets of
    about that length, as few as that takes (one for a model that fits, in
    the plain view: every leaf raveled, in tree order).

    How a leaf enters (``col_blocks``) is decided by its shape alone. That a
    matrix entering by its transpose's rows is cheaper than its ravel is
    measured for one layout only: a v5e's compiler keeps a ``[k * width,
    odd]`` bf16 parameter with its rows minor (``{0,1}``), so both
    transposes are bitcasts (the four-chip cell's head on the chip; a
    2,048 x 1,000 one compiled in ``tests/test_mosaic_compile.py``). Where
    a compiler kept such a matrix with its columns minor, the ravel was the
    free operation and each transpose is a pass over the leaf."""
    leaves, treedef = jax.tree_util.tree_flatten(params)
    shapes = tuple(tuple(l.shape) for l in leaves)
    sizes = tuple(int(np.prod(s)) if s else 1 for s in shapes)
    dtypes = tuple(jnp.dtype(l.dtype) for l in leaves)
    n = int(sum(sizes))
    target = BUCKET_TARGET_LEN if bucket_len is None else int(bucket_len)
    # a shard's columns: the most, up to SHARD_COLS, at which padding every
    # leaf to whole rows wastes under 1/64 of the parameters
    cols = SHARD_COLS
    while cols > 1 and 64 * sum(-z % (n_shards * cols) for z in sizes) > n:
        cols //= 2

    def own_rows(c):
        # k: by its own rows; -k: a matrix that cannot, by its transpose's
        w = n_shards * c
        return tuple(s[-1] // w if len(s) >= 2 and s[-1] % w == 0
                     else -(s[0] // w) if len(s) == 2 and s[0] % w == 0
                     else 0 for s in shapes)

    col_blocks = (0,) * len(shapes)          # the plain view
    if n > target:
        # several buckets: of the widths a shard of which is whole lane
        # tiles, the one (the widest) at which the matrices that can enter
        # by their own rows or their transpose's hold the most parameters
        most, c = 0, cols
        while c >= LANES:
            share = sum(z for z, k in zip(sizes, own_rows(c)) if k)
            if share > most:
                most, cols, col_blocks = share, c, own_rows(c)
            c //= 2
    width = n_shards * cols
    leaf_rows = tuple(-(-z // width) for z in sizes)
    # matrices of whole row tiles first, then the other matrices, then the
    # raveled leaves: each group in tree order
    order = tuple(sorted(range(len(shapes)), key=lambda i: (
        2 if not col_blocks[i]
        else int(leaf_rows[i] // abs(col_blocks[i]) % ROW_TILE > 0))))
    total = sum(leaf_rows)
    rows = -(-total // max(1, -(-total * width // target)))
    # the largest power of two, at most SHARD_ROWS_MULTIPLE, that pads the
    # rows by under 1/64
    mult = min(SHARD_ROWS_MULTIPLE, 1 << max(0, (rows // 64).bit_length() - 1))
    rows = -(-rows // mult) * mult
    return FlatParamMeta(treedef, shapes, sizes, dtypes, n, n_shards,
                         -(-total // rows), (rows, cols), leaf_rows, order,
                         col_blocks)


def flat_bucket(tree, meta: FlatParamMeta, b: int, dtype=jnp.float32):
    """Bucket ``b`` of the flat view of ``tree`` as a ``bucket_shape`` matrix
    in ``dtype``, stacked from the row blocks of the leaves it covers
    (zero rows below the last leaf) — never sliced out of the whole view, so
    it depends on those leaves alone."""
    leaves = jax.tree_util.tree_leaves(tree)
    rows, width = meta.bucket_shape
    parts, have = [], 0
    for i, r0, r1 in meta.pieces(b):
        if meta.col_blocks[i]:
            matrix = leaves[i].reshape(-1, meta.shapes[i][-1])
            if meta.col_blocks[i] < 0:
                matrix = matrix.T
            parts += [jax.lax.slice(matrix, (a0, j * width),
                                    (a1, (j + 1) * width)).astype(dtype)
                      for j, a0, a1 in meta.blocks(i, r0, r1)]
        else:
            n_rows = meta.leaf_rows[i]
            flat = jnp.ravel(leaves[i])
            if flat.size < n_rows * width:
                flat = jnp.pad(flat, (0, n_rows * width - flat.size))
            block = flat.reshape(n_rows, width)
            if (r0, r1) != (0, n_rows):
                block = jax.lax.slice_in_dim(block, r0, r1)
            parts.append(block.astype(dtype))
        have += r1 - r0
    if have < rows:
        parts.append(jnp.zeros((rows - have, width), dtype))
    return jnp.concatenate(parts) if len(parts) > 1 else parts[0]


def flatten_tree(tree, meta: FlatParamMeta, dtype=jnp.float32):
    """Pytree → the whole flat view as one (npad,) vector in ``dtype``."""
    return jnp.concatenate([jnp.ravel(flat_bucket(tree, meta, b, dtype))
                            for b in range(meta.n_buckets)])


def unflatten_buckets(buckets: Sequence[Any], meta: FlatParamMeta):
    """Per-bucket ``bucket_shape`` matrices → pytree with the meta's original
    shapes/dtypes. A leaf that spans buckets is joined from its row blocks,
    one that entered by its own rows from its column blocks, one that
    entered by its transpose's likewise and transposed back."""
    blocks = [[[] for _ in range(max(1, abs(k)))] for k in meta.col_blocks]
    for b, bucket in enumerate(buckets):
        off = 0
        for i, r0, r1 in meta.pieces(b):
            runs = (meta.blocks(i, r0, r1) if meta.col_blocks[i]
                    else ((0, r0, r1),))
            for j, a0, a1 in runs:
                blocks[i][j].append(
                    jax.lax.slice_in_dim(bucket, off, off + a1 - a0))
                off += a1 - a0
    out = []
    for cols, k, size, shape, dt in zip(blocks, meta.col_blocks, meta.sizes,
                                        meta.shapes, meta.dtypes):
        cols = [jnp.concatenate(rows) if len(rows) > 1 else rows[0]
                for rows in cols]
        if k:
            leaf = jnp.concatenate(cols, axis=1) if len(cols) > 1 else cols[0]
            if k < 0:
                leaf = leaf.T
        else:
            leaf = jnp.ravel(cols[0])
            if leaf.size > size:
                leaf = jax.lax.slice_in_dim(leaf, 0, size)
        out.append(leaf.reshape(shape).astype(dt))
    return jax.tree_util.tree_unflatten(meta.treedef, out)


def flat_opt_init(tx: optax.GradientTransformation, params,
                  meta: FlatParamMeta, keep_master: bool) -> FlatUpdateState:
    """Global-view init (each bucket's arrays are whole ``bucket_shape``
    matrices; the engine places them dp-sharded over their columns).
    ``params`` may be any float dtype — masters are f32."""
    flat32 = tuple(flat_bucket(params, meta, b, jnp.float32)
                   for b in range(meta.n_buckets))
    layout = meta.layout
    return FlatUpdateState(tuple(tx.init(m) for m in flat32),
                           flat32 if keep_master else None,
                           None if layout is None else jnp.asarray(layout))


def adopt_flat_layout(restored: FlatUpdateState, template: FlatUpdateState,
                      meta: FlatParamMeta) -> FlatUpdateState:
    """Bring a checkpoint's flat optimizer state (already unflattened into
    ``template``'s structure) into ``meta``'s layout. A one-bucket state
    written as plain ``(npad,)`` vectors — the layout before bucketing: the
    raveled leaves end to end — holds the same values in the same order and
    is re-padded leaf by leaf; any other shape mismatch, and a state whose
    ``layout`` is another view's, is refused in words, never reinterpreted
    (a snapshot with or without a ``layout`` where this view has none or
    one does not reach here: it has another count of leaves)."""
    width = meta.bucket_shape[1]
    if restored.layout is not None and not np.array_equal(
            np.asarray(restored.layout), meta.layout):
        raise ValueError(
            "its optimizer state was written under another flat view of "
            "the same bucket shape (other leaves enter by their own rows, or "
            "by their transpose's)")

    def adopt(path, got, want):
        got, shape = np.asarray(got), tuple(want.shape)
        if got.shape == shape:
            return got
        if (shape == meta.bucket_shape and meta.n_buckets == 1
                and got.ndim == 1 and got.size >= meta.n):
            leaves = np.split(got[:meta.n], np.cumsum(meta.sizes)[:-1])
            flat = np.concatenate([np.pad(l, (0, r * width - l.size))
                                   for l, r in zip(leaves, meta.leaf_rows)])
            return np.pad(flat, (0, meta.npad - flat.size)).reshape(shape)
        raise ValueError(
            f"optimizer-state leaf {jax.tree_util.keystr(path)} is "
            f"{got.shape} in the checkpoint and {shape} here: it was written "
            f"under another flat update-sharding layout")

    return jax.tree_util.tree_map_with_path(adopt, restored, template)


def flat_exchange(params, grads, opt_state: FlatUpdateState,
                  meta: FlatParamMeta, tx: optax.GradientTransformation, *,
                  axis: str = "dp",
                  clip_norm: Optional[float] = None,
                  clip_value: Optional[tuple] = None):
    """One weight-update exchange; runs INSIDE ``shard_map`` (manual over
    ``axis``). ``grads`` are this replica's local-mean grads.

    Returns ``(new_params, new_opt_state, grad_norm)``; ``grad_norm`` is the
    f32 global (pre-clip) gradient L2 norm. One grad-sized collective round
    per BUCKET per call: a reduce-scatter in, a tiled ``all_gather`` out (the
    norm rides one scalar psum over all buckets' shards). Every bucket goes
    through the same two jitted functions, so the traced step defines each
    collective once and calls it ``meta.n_buckets`` times; a bucket's
    reduction depends only on the gradients it covers, and its update and
    gather only on its own reduction (and, under ``clip_norm``, on the norm).
    """
    n = axis_size(axis)
    cols = meta.shard_shape[1]
    keep_master = opt_state.master is not None
    # all-gather in the MODEL dtype: under bf16 params the param broadcast
    # costs half the bytes of the f32 masters
    gather_dt = meta.dtypes[0] if len(set(meta.dtypes)) == 1 else jnp.float32

    @jax.jit
    def reduce_bucket(gbucket):
        # mean over replicas: local grads are means over the local micro/batch
        return jax.lax.psum_scatter(gbucket, axis, scatter_dimension=1,
                                    tiled=True) / n

    @jax.jit
    def update_bucket(gshard, inner, master):
        if not keep_master:              # f32 params: ``master`` is the whole
            master = jax.lax.dynamic_slice_in_dim(    # bucket, re-slice it
                master, jax.lax.axis_index(axis) * cols, cols, axis=1)
        updates, inner2 = tx.update(gshard, inner, master)
        master2 = optax.apply_updates(master, updates)
        gathered = jax.lax.all_gather(master2.astype(gather_dt), axis,
                                      axis=1, tiled=True)
        return gathered, inner2, master2

    buckets = range(meta.n_buckets)
    gshards = [reduce_bucket(flat_bucket(grads, meta, b)) for b in buckets]
    gnorm = jnp.sqrt(jax.lax.psum(sum(jnp.sum(g * g) for g in gshards), axis))
    if clip_norm is not None:
        # f32 global-norm clipping computed across the scattered shards —
        # optax.clip_by_global_norm would only see one shard here
        scale = jnp.minimum(1.0, clip_norm / (gnorm + 1e-12))
        gshards = [g * scale for g in gshards]
    if clip_value is not None:
        lo, hi = clip_value
        gshards = [jnp.clip(g, lo, hi) for g in gshards]

    gathered, inner2, master2 = zip(*(
        update_bucket(gshards[b], opt_state.inner_state[b],
                      opt_state.master[b] if keep_master     # f32 shard
                      else flat_bucket(params, meta, b))     # f32 bucket
        for b in buckets))
    new_params = unflatten_buckets(gathered, meta)
    new_opt = FlatUpdateState(inner2, master2 if keep_master else None,
                              opt_state.layout)
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
