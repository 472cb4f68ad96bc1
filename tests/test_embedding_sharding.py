"""Mesh-row-sharded embedding tables (ISSUE 19 tentpole part 1).

Exactness strategy: the sharded gather is pure SELECTION — every output row
is one table row (psum/psum_scatter partials have exactly one nonzero
contributor per id), so forward parity vs ``jnp.take`` is asserted
byte-exact. End-to-end training parity uses ids UNIQUE within the batch so
the backward scatter-add has no collisions and any summation-order
divergence can only come from the dense tower, which gets a one-ulp-scale
tolerance.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from analytics_zoo_tpu.analysis.rules import lint_sharded_gather
from analytics_zoo_tpu.common import TrainConfig
from analytics_zoo_tpu.engine import Estimator
from analytics_zoo_tpu.nn import Sequential
from analytics_zoo_tpu.nn import layers as L
from analytics_zoo_tpu.nn.layers.embedding import Embedding, FusedPairEmbedding
from analytics_zoo_tpu.parallel import collective_counts
from analytics_zoo_tpu.parallel import embedding_sharding as es

pytestmark = pytest.mark.embedding

AXES = ("dp", "fsdp", "tp", "sp", "pp", "ep")


def _mesh(n=8):
    return Mesh(np.array(jax.devices()[:n]).reshape((n,) + (1,) * 5), AXES)


def _table(rows=64, width=16, seed=0):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.standard_normal((rows, width)), jnp.float32)


def _place(mesh, table, spec=P("dp", None)):
    return jax.device_put(table, NamedSharding(mesh, spec))


# ------------------------------------------------------------ gather parity
@pytest.mark.parametrize("shard_batch", [True, False])
def test_sharded_gather_matches_take_byte_exact(zoo_ctx, shard_batch):
    mesh = _mesh()
    table = _table(rows=64, width=16)
    ids = jnp.asarray(np.random.default_rng(1).integers(0, 64, 40), jnp.int32)
    want = np.asarray(jnp.take(table, ids, axis=0))
    got = jax.jit(lambda t, i: es.sharded_gather(
        t, i, mesh, "dp", shard_batch=shard_batch))(_place(mesh, table), ids)
    np.testing.assert_array_equal(np.asarray(got), want)


def test_sharded_gather_multi_dim_ids_byte_exact(zoo_ctx):
    """(B, 2) pair ids — the FusedPairEmbedding shape — flatten row-major so
    batch-sharding of the flat vector matches batch-sharding of the pairs."""
    mesh = _mesh()
    table = _table(rows=48, width=8)
    ids = jnp.asarray(
        np.random.default_rng(2).integers(0, 48, (16, 2)), jnp.int32)
    want = np.asarray(jnp.take(table, ids, axis=0))
    got = jax.jit(lambda t, i: es.sharded_gather(t, i, mesh, "dp"))(
        _place(mesh, table), ids)
    assert got.shape == (16, 2, 8)
    np.testing.assert_array_equal(np.asarray(got), want)


def test_sharded_gather_out_of_range_yields_zero_rows(zoo_ctx):
    """No shard owns an out-of-range id → explicit zero rows (documented
    divergence from ``jnp.take``'s clamp; padded vocab tails read as 0)."""
    mesh = _mesh()
    table = _table(rows=32, width=4)
    ids = jnp.asarray([0, 31, 32, 1000, -1], jnp.int32)
    got = np.asarray(es.sharded_gather(table, ids, mesh, "dp",
                                       shard_batch=False))
    np.testing.assert_array_equal(got[0], np.asarray(table)[0])
    np.testing.assert_array_equal(got[1], np.asarray(table)[31])
    assert not got[2].any() and not got[3].any() and not got[4].any()


def test_sharded_gather_fallbacks(zoo_ctx):
    """Trivial axis or indivisible rows fall back to plain take (clamping
    semantics included); indivisible batch falls back to replicated mode."""
    mesh = _mesh()
    mesh1 = Mesh(np.array(jax.devices()[:1]).reshape((1,) + (1,) * 5), AXES)
    table = _table(rows=30, width=4)           # 30 % 8 != 0
    ids = jnp.asarray([0, 29, 5], jnp.int32)
    np.testing.assert_array_equal(
        np.asarray(es.sharded_gather(table, ids, mesh, "dp")),
        np.asarray(jnp.take(table, ids, axis=0)))
    np.testing.assert_array_equal(
        np.asarray(es.sharded_gather(_table(32, 4), ids, mesh1, "dp")),
        np.asarray(jnp.take(_table(32, 4), ids, axis=0)))
    # divisible table, batch of 3: replicated-exchange path, still exact
    np.testing.assert_array_equal(
        np.asarray(es.sharded_gather(_table(32, 4), ids, mesh, "dp")),
        np.asarray(jnp.take(_table(32, 4), ids, axis=0)))


# ------------------------------------------------------- backward locality
def test_sharded_gather_grad_is_sharded_scatter_add(zoo_ctx):
    """d(table) from the sharded gather equals the dense scatter-add AND
    comes back laid out ``P("dp", None)`` — each shard only ever held its
    own rows' gradient (no dense replicated grad materialises)."""
    mesh = _mesh()
    table = _place(mesh, _table(rows=64, width=8))
    ids = jnp.asarray(np.random.default_rng(3).integers(0, 64, 32), jnp.int32)
    cot = jnp.asarray(
        np.random.default_rng(4).standard_normal((32, 8)), jnp.float32)

    def loss(t):
        return jnp.vdot(es.sharded_gather(t, ids, mesh, "dp"), cot)

    g = jax.jit(jax.grad(loss))(table)
    dense = jnp.zeros((64, 8), jnp.float32).at[ids].add(cot)
    np.testing.assert_allclose(np.asarray(g), np.asarray(dense),
                               rtol=0, atol=1e-6)
    assert g.sharding.spec in (P("dp"), P("dp", None))
    assert g.addressable_shards[0].data.shape == (8, 8)


def test_per_device_table_bytes_one_over_shards(zoo_ctx):
    mesh = _mesh()
    table = _place(mesh, _table(rows=512, width=32))
    per_dev = table.addressable_shards[0].data.nbytes
    assert per_dev == table.nbytes // 8


# ------------------------------------------------------------- marking API
def test_shard_embedding_tables_marks_and_rules(zoo_ctx):
    mesh = _mesh()
    model = Sequential([
        FusedPairEmbedding(40, 24, 8, 8, mf_dim=4, input_shape=(2,)),
        L.Dense(1)])
    rule = es.shard_embedding_tables(model, mesh, axis="dp")
    emb = model.layers[0]
    assert emb.table_sharding == es.TableSharding(mesh, "dp", True)
    params, _ = model.build(jax.random.PRNGKey(0), (2,))
    specs = jax.tree_util.tree_map_with_path(
        lambda p, l: rule(p, l), params)
    flat = {jax.tree_util.keystr(p): s for p, s in
            jax.tree_util.tree_flatten_with_path(specs)[0]}
    table_specs = [s for k, s in flat.items() if "embeddings" in k]
    assert table_specs == [P("dp", None)]
    assert all(s == P() or not any(s) for k, s in flat.items()
               if "embeddings" not in k)


def test_shard_embedding_tables_skips_indivisible_and_small(zoo_ctx):
    mesh = _mesh()
    m1 = Sequential([Embedding(30, 4, input_shape=(3,))])   # 30 % 8 != 0
    es.shard_embedding_tables(m1, mesh)
    assert getattr(m1.layers[0], "table_sharding", None) is None
    m2 = Sequential([Embedding(32, 4, input_shape=(3,))])
    es.shard_embedding_tables(m2, mesh, min_rows=64)
    assert getattr(m2.layers[0], "table_sharding", None) is None
    es.shard_embedding_tables(m2, mesh)
    assert m2.layers[0].table_sharding is not None


def test_helpers(zoo_ctx):
    assert es.pad_rows(30, 8) == 32 and es.pad_rows(32, 8) == 32
    assert es.owned_row_range(64, 8, 0) == (0, 8)
    assert es.owned_row_range(64, 8, 7) == (56, 64)
    mesh = _mesh()
    assert es.row_shard_spec((64, 8), mesh) == P("dp", None)
    assert es.row_shard_spec((30, 8), mesh) == P(None, None)


# --------------------------------------------------- end-to-end train parity
def test_sharded_training_matches_replicated(zoo_ctx):
    """FusedPair model trained with the table sharded P("dp", None) over the
    8-way mesh lands within float tolerance of the same model trained
    replicated — same ids, unique per batch (collision-free scatter-add)."""
    rows_u, rows_i = 40, 24     # 64 rows total, divides 8
    B = 16                      # <= rows_i so item ids stay unique
    rng = np.random.default_rng(7)
    users = rng.permutation(rows_u)[:B].astype(np.int32)
    items = rng.permutation(rows_i)[:B].astype(np.int32)
    x = np.stack([users, items], axis=1)
    y = rng.integers(0, 2, (B, 1)).astype(np.float32)

    def build(shard):
        model = Sequential([
            FusedPairEmbedding(rows_u, rows_i, 8, 8, mf_dim=4,
                               input_shape=(2,)),
            L.Dense(8, activation="relu"), L.Dense(1)])
        mesh = _mesh()
        kw = {}
        if shard:
            kw["param_sharding"] = es.shard_embedding_tables(model, mesh)
        cfg = TrainConfig(shuffle=False, log_every_n_steps=10 ** 9)
        est = Estimator(model, optimizer="sgd", loss="mse", config=cfg,
                        mesh=mesh, **kw)
        est.fit((x, y), batch_size=B, epochs=3)
        return est

    e_rep, e_sh = build(False), build(True)
    table = e_sh.train_state["params"]["0_fusedpairembedding"]["embeddings"]
    assert table.sharding.spec in (P("dp"), P("dp", None))
    assert table.addressable_shards[0].data.shape[0] == 64 // 8
    for (pa, a), (pb, b) in zip(
            jax.tree_util.tree_flatten_with_path(
                jax.device_get(e_rep.train_state["params"]))[0],
            jax.tree_util.tree_flatten_with_path(
                jax.device_get(e_sh.train_state["params"]))[0]):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=0, atol=5e-6,
            err_msg=jax.tree_util.keystr(pa))


def test_sharded_opt_state_is_shard_local(zoo_ctx):
    """Under the gspmd update path the table's Adam moments land
    ``P("dp", None)`` — 1/n rows of optimizer state per device, no dense
    moment tensors anywhere."""
    model = Sequential([
        FusedPairEmbedding(40, 24, 8, 8, mf_dim=4, input_shape=(2,)),
        L.Dense(1)])
    mesh = _mesh()
    rule = es.shard_embedding_tables(model, mesh)
    cfg = TrainConfig(shuffle=False, log_every_n_steps=10 ** 9,
                      update_sharding=True)
    est = Estimator(model, optimizer="adam", loss="mse", config=cfg,
                    mesh=mesh, param_sharding=rule)
    assert est._update_mode() == "gspmd"
    x = np.stack([np.arange(8, dtype=np.int32),
                  np.arange(8, dtype=np.int32) % 24], axis=1)
    y = np.ones((8, 1), np.float32)
    est.fit((x, y), batch_size=8, epochs=1)
    moments = [l for p, l in jax.tree_util.tree_flatten_with_path(
        est.train_state["opt_state"])[0]
        if "embeddings" in jax.tree_util.keystr(p)
        and getattr(l, "ndim", 0) == 2]
    assert moments, "expected 2-D table moments in opt_state"
    for m in moments:
        assert m.sharding.spec in (P("dp"), P("dp", None))
        assert m.addressable_shards[0].data.shape[0] == 64 // 8
    # the model-parallel gather's collective pair is in the compiled step:
    # ids all-gathered to the owner shards, rows back by reduce-scatter
    counts = collective_counts(est._train_step.lower(
        est.train_state, est._to_global((x, y))).compile().as_text())
    assert counts.get("all-gather", 0) >= 1, counts
    assert counts.get("reduce-scatter", 0) >= 1, counts


def test_shard_local_gather_fits_where_the_dense_table_cannot():
    """``lint_sharded_gather`` traces the block ONE device executes: with a
    table 4x the per-device budget, rows/8 and the batch's partials fit; a
    budget under the shard itself is a finding."""
    rows, width, batch = 4096, 16, 64
    table_bytes = rows * width * 4
    assert lint_sharded_gather(rows, width, batch, 8,
                               hbm_budget_bytes=table_bytes // 4) == []
    tight = lint_sharded_gather(rows, width, batch, 8,
                                hbm_budget_bytes=table_bytes // 16)
    assert [f.rule for f in tight] == ["hbm-budget"]
    with pytest.raises(ValueError, match="must divide"):
        lint_sharded_gather(rows + 1, width, batch, 8)
