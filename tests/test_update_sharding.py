"""ZeRO-1 weight-update sharding + microbatch grad accumulation + bf16 mixed
precision (ISSUE 5).

Byte-exactness strategy: float reassociation makes "K microbatches == one big
batch" only approximately true for arbitrary data (XLA reduction orders
differ), so the exact tests use *dyadic-rational* data — inputs in {-1,0,1},
labels and weights multiples of 1/8, a linear model, and power-of-two batch
splits. Every product and partial sum is then exactly representable in f32,
so ANY summation order yields the same bits and a byte-level mismatch can
only come from a structural bug (wrong scaling, dropped microbatch, slice
misalignment), never from rounding.
"""

import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from analytics_zoo_tpu.analysis.memory import memory_fields
from analytics_zoo_tpu.common import (MeshConfig, TrainConfig,
                                      init_zoo_context, reset_zoo_context)
from analytics_zoo_tpu.common import telemetry as _tm
from analytics_zoo_tpu.engine import Estimator
from analytics_zoo_tpu.nn import Sequential
from analytics_zoo_tpu.nn import layers as L
from analytics_zoo_tpu.nn.optimizers import SGD, Adam
from analytics_zoo_tpu.parallel import make_param_sharding
from analytics_zoo_tpu.parallel import update_sharding as upd

pytestmark = pytest.mark.multichip

AXES = ("dp", "fsdp", "tp", "sp", "pp", "ep")


def _dyadic_data(B=32, D=8, O=4, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.integers(-1, 2, size=(B, D)).astype(np.float32)
    y = rng.integers(-2, 3, size=(B, O)).astype(np.float32)
    return x, y


def _dyadic_model(D=8, H=16, O=4, bias=False):
    """Two Dense layers, linear; ``bias``: the first has a bias vector."""
    return Sequential([L.Dense(H, use_bias=bias, input_shape=(D,)),
                       L.Dense(O, use_bias=False)])


def _dyadic_estimator(cfg, x, y, optimizer=None, mesh=None, D=8, H=16, O=4,
                      bias=False):
    """Linear two-Dense model whose initial weights are rounded to multiples
    of 1/8 (exact f32 arithmetic on the dyadic data)."""
    est = Estimator(_dyadic_model(D, H, O, bias),
                    optimizer=optimizer or SGD(lr=0.5), loss="mse",
                    config=cfg, mesh=mesh)
    state = est._init_state((x, y), seed=0)
    state["params"] = jax.tree_util.tree_map(
        lambda p: jnp.round(p.astype(jnp.float32) * 8) / 8
        if jnp.issubdtype(p.dtype, jnp.floating) else p, state["params"])
    if est._mp_dtype is not None:
        state["params"] = jax.tree_util.tree_map(
            lambda p: p.astype(est._mp_dtype), state["params"])
    est.train_state = est._place_state(state)
    return est


def _leaves(est):
    return [np.asarray(l) for l in
            jax.tree_util.tree_leaves(jax.device_get(
                est.train_state["params"]))]


# ``bucket_target`` (conftest): with a 48-element target the 192-parameter toy
# (three rows of 64: two of the first leaf, one of the second) exchanges 3
# buckets in the plain view. ``_OWN_ROWS``: a hidden layer as wide as one
# lane tile a shard, under a target the toy exceeds, so the first kernel
# enters the view by its own rows and the second, (hidden, 4), by its
# transpose's: (hidden size, bucket target) for the 2-device mesh and for the
# 8-device one. ``_HEAD``: the same hidden layer with a bias vector (raveled)
# and a head of an odd width, (hidden, 5), again in three buckets
_OWN_ROWS = {2: (256, 1024), 8: (1024, 4096)}
_HEAD = {2: (256, 1280), 8: (1024, 5120)}
#: (bucket target or None, hidden, outputs, bias) of the views the
#: parametrised tests below run on the 8-device mesh, and on the 2-device one
_VIEWS8 = [pytest.param(None, 16, 4, False, id="one_bucket"),
           pytest.param(48, 16, 4, False, id="plain"),
           pytest.param(_OWN_ROWS[8][1], _OWN_ROWS[8][0], 4, False,
                        id="own_rows"),
           pytest.param(_HEAD[8][1], _HEAD[8][0], 5, True, id="head")]
_VIEWS2 = [pytest.param(48, 16, 4, False, id="plain"),
           pytest.param(_OWN_ROWS[2][1], _OWN_ROWS[2][0], 4, False,
                        id="own_rows"),
           pytest.param(_HEAD[2][1], _HEAD[2][0], 5, True, id="head")]


def _view_before(meta):
    """``meta``'s view as the version before built it: the same width, rows
    and buckets, with every leaf that now enters by its transpose's rows
    raveled, and so behind the matrices."""
    blocks = tuple(max(0, k) for k in meta.col_blocks)
    return meta._replace(col_blocks=blocks, order=tuple(sorted(
        range(len(blocks)), key=lambda i: not blocks[i])))


def _col_blocks(hidden, bias):
    """What ``flat_meta`` records of the toy's leaves in its several-bucket
    view: the first kernel by its own rows, the bias raveled, the second
    kernel (hidden rows, a few columns) by its transpose's."""
    if hidden == 16:
        return (0, 0)
    return (0, 1, -1) if bias else (1, -1)


#: the XLA flag under which a bf16 value is rounded wherever the program
#: says it is, fused or not
_ROUNDED = "--xla_allow_excess_precision=false"


def _mesh2():
    return Mesh(np.array(jax.devices()[:2]).reshape((2,) + (1,) * 5), AXES)


def _compiled_step(est, x, y):
    return est._make_train_step().lower(
        est.train_state, est._to_global((x, y))).compile()


# ------------------------------------------------------- accumulation equiv
@pytest.mark.parametrize("shuffle", [False, True])
def test_grad_accum_matches_big_batch_byte_exact_f32(zoo_ctx, shuffle):
    """K microbatches == one big batch, bit-for-bit in f32 on dyadic data.

    Single-step equality is byte-exact on BOTH update paths: every product
    and partial sum is exactly representable, so no reduction order can
    change it. Later steps walk off the dyadic lattice (update granularity
    compounds past the f32 mantissa), and XLA is then free to order the
    backward-dot reduction differently for the micro and the full batch
    shape — on the flat-sharded path as much as on the replicated one — so
    multi-step results are compared within one ulp."""
    x, y = _dyadic_data(B=64)
    for sharded in (False, True):
        common = dict(shuffle=shuffle, log_every_n_steps=10 ** 9,
                      update_sharding=sharded)
        e1 = _dyadic_estimator(TrainConfig(**common), x, y)
        eK = _dyadic_estimator(TrainConfig(grad_accum_steps=4, **common),
                               x, y)
        e1.fit((x, y), batch_size=64, epochs=1)       # exactly one step
        eK.fit((x, y), batch_size=64, epochs=1)
        for a, b in zip(_leaves(e1), _leaves(eK)):
            np.testing.assert_array_equal(
                a, b, err_msg=f"1-step sharded={sharded} shuffle={shuffle}")
        e1.fit((x, y), batch_size=32, epochs=4)       # 6 more steps
        eK.fit((x, y), batch_size=32, epochs=4)
        for a, b in zip(_leaves(e1), _leaves(eK)):
            np.testing.assert_allclose(
                a, b, rtol=0, atol=2e-7,
                err_msg=f"multi-step sharded={sharded} shuffle={shuffle}")


def test_grad_accum_matches_big_batch_bf16_tolerance(zoo_ctx):
    """Mixed precision: K vs 1 stays within bf16 tolerance (reassociation in
    bf16 rounds, so exact equality is not claimed)."""
    x, y = _dyadic_data(B=64)
    common = dict(shuffle=False, log_every_n_steps=10 ** 9,
                  compute_dtype="bfloat16", update_sharding=True)
    e1 = _dyadic_estimator(TrainConfig(**common), x, y)
    eK = _dyadic_estimator(TrainConfig(grad_accum_steps=4, **common), x, y)
    e1.fit((x, y), batch_size=32, epochs=2)
    eK.fit((x, y), batch_size=32, epochs=2)
    for a, b in zip(_leaves(e1), _leaves(eK)):
        np.testing.assert_allclose(a.astype(np.float32),
                                   b.astype(np.float32), rtol=0.05, atol=0.03)


def test_grad_accum_rejects_indivisible_batch(zoo_ctx):
    x, y = _dyadic_data(B=60)
    est = _dyadic_estimator(
        TrainConfig(grad_accum_steps=4, log_every_n_steps=10 ** 9), x, y)
    with pytest.raises(ValueError, match="grad_accum_steps"):
        est.fit((x, y), batch_size=60, epochs=1)


# -------------------------------------------------- sharded vs replicated
def test_sharded_update_bit_parity_two_devices(zoo_ctx):
    """One adam step on a 2-device dp mesh: the flat reduce-scatter/shard-
    update/all-gather exchange must be bit-identical to the replicated
    update (on 2 devices both reduce orders are the single add x0+x1; with
    exact-arithmetic data the whole step is deterministic)."""
    x, y = _dyadic_data(B=32)
    ests = {}
    for sharded in (False, True):
        cfg = TrainConfig(shuffle=False, log_every_n_steps=10 ** 9,
                          update_sharding=sharded)
        est = _dyadic_estimator(cfg, x, y, optimizer=Adam(lr=1e-2),
                                mesh=_mesh2())
        est.fit((x, y), batch_size=32, epochs=1)      # exactly one step
        ests[sharded] = est
    assert ests[True]._update_mode() == "flat"
    for a, b in zip(_leaves(ests[False]), _leaves(ests[True])):
        np.testing.assert_array_equal(a, b)
    # multi-step: adam's rsqrt denormalizes the dyadic lattice, so later
    # steps are compared within tight fp32 tolerance instead of bitwise
    for sharded in (False, True):
        ests[sharded].fit((x, y), batch_size=32, epochs=5)
    for a, b in zip(_leaves(ests[False]), _leaves(ests[True])):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("bucket_len,hidden", [
    (None, 64), (512, 64), (_OWN_ROWS[8][1], _OWN_ROWS[8][0])])
def test_flat_opt_state_is_one_over_dp(zoo_ctx, bucket_target, bucket_len,
                                       hidden):
    """ZeRO-1 memory claim on the 8-way dp mesh: per-device optimizer-state
    bytes ≈ replicated/8 (within padding + replicated scalar count leaves),
    with one bucket, with three, and with kernels that enter their buckets
    by their own rows and by their transpose's."""
    x, y = _dyadic_data(B=64, D=16)
    if bucket_len:
        bucket_target(bucket_len)

    def opt_bytes(est):
        return sum(l.addressable_shards[0].data.nbytes
                   for l in jax.tree_util.tree_leaves(
                       est.train_state["opt_state"])
                   if hasattr(l, "addressable_shards"))

    base = dict(shuffle=False, log_every_n_steps=10 ** 9)
    e_r = _dyadic_estimator(TrainConfig(update_sharding=False, **base), x, y,
                            optimizer=Adam(1e-3), D=16, H=hidden, O=4)
    e_s = _dyadic_estimator(TrainConfig(update_sharding=True, **base), x, y,
                            optimizer=Adam(1e-3), D=16, H=hidden, O=4)
    assert e_s._update_mode() == "flat"
    meta = e_s._flat_meta
    if hidden == 64:
        assert (meta.n_buckets, meta.layout) == (3 if bucket_len else 1, None)
    else:
        assert meta.n_buckets == 5 and meta.col_blocks == (1, -1)
    r, s = opt_bytes(e_r), opt_bytes(e_s)
    assert s <= r / 8 * 1.35 + 512, (r, s)

    # and the sharded-update step costs no more device memory than the
    # replicated one (arguments + temporaries of the compiled step)
    def step_bytes(est):
        return memory_fields(_compiled_step(est, x, y))["hbm_peak_bytes"]

    assert step_bytes(e_s) <= step_bytes(e_r) * 1.02


@pytest.mark.parametrize("bucket_len", [None, 48])
def test_one_gradient_collective_per_bucket_per_global_step(
        zoo_ctx, bucket_target, bucket_len):
    """The flat path's structural guarantee: compiled HLO has exactly one
    grad-sized reduce-scatter and one all-gather per BUCKET, the counts do
    NOT grow with grad_accum_steps (the K-microbatch scan accumulates
    device-local grads, so none sits inside it), and no reduction runs as an
    all-reduce of a bucket's or the vector's length."""
    import re

    from analytics_zoo_tpu.analysis.rules.collectives import (
        jaxpr_collective_counts)

    x, y = _dyadic_data(B=64)
    if bucket_len:
        bucket_target(bucket_len)
    counts = {}
    for K in (1, 4):
        cfg = TrainConfig(shuffle=False, log_every_n_steps=10 ** 9,
                          update_sharding=True, grad_accum_steps=K)
        est = _dyadic_estimator(cfg, x, y)
        meta = est._flat_meta
        assert meta.n_buckets == (3 if bucket_len else 1)
        hlo = _compiled_step(est, x, y).as_text()
        counts[K] = upd.collective_counts(hlo)
        for shape in re.findall(r"\[([\d,]*)\][^ ]* all-reduce(?:-start)?\(",
                                hlo):
            n_elem = int(np.prod([int(d) for d in shape.split(",") if d]))
            assert n_elem < meta.bucket_len // meta.n_shards, (shape, hlo)
        census = jaxpr_collective_counts(jax.make_jaxpr(
            est._with_policy(est._step_fn()))(est.train_state,
                                              est._to_global((x, y))))
        assert census["in_loop"] == {}, census
        assert census["counts"]["reduce-scatter"] == meta.n_buckets
        assert census["counts"]["all-gather"] == meta.n_buckets
    assert counts[1] == counts[4], counts
    assert counts[4].get("reduce-scatter", 0) == meta.n_buckets, counts
    assert counts[4].get("all-gather", 0) == meta.n_buckets, counts


@pytest.mark.parametrize("bucket_len,hidden,out,bias", _VIEWS8)
def test_lowered_step_defines_each_collective_once(zoo_ctx, bucket_target,
                                                   bucket_len, hidden, out,
                                                   bias):
    """The benchmark's check (benchmark/drivers/train_fit.py) counts TEXT
    occurrences in the lowered step and wants one reduce_scatter and one
    all_gather (``benchmark/traffic/fit-2k-zero1.json``): every bucket has
    the one bucket shape and goes through the same two jitted functions, so
    each collective is defined once however many buckets call it and however
    the leaves enter them. A refactor that inlines the buckets, or gives a
    leaf a bucket in a shape of its own, fails here before it fails on the
    chip."""
    x, y = _dyadic_data(B=64, O=out)
    if bucket_len:
        bucket_target(bucket_len)
    est = _dyadic_estimator(
        TrainConfig(shuffle=False, log_every_n_steps=10 ** 9,
                    update_sharding=True), x, y, H=hidden, O=out, bias=bias)
    assert est._flat_meta.n_buckets == (3 if bucket_len else 1)
    assert est._flat_meta.col_blocks == (
        _col_blocks(hidden, bias) if bucket_len else (0, 0))
    text = est.lower_train_step((x, y)).as_text()
    assert text.count('stablehlo.reduce_scatter"') == 1
    assert text.count('stablehlo.all_gather"') == 1


def test_small_model_is_one_bucket_one_exchange(zoo_ctx):
    """A model smaller than one bucket target gets exactly one bucket, with
    no padding when its leaves fill whole rows: the one-reduce-scatter,
    one-all-gather exchange it had before there were buckets."""
    from analytics_zoo_tpu.analysis.rules.collectives import (
        jaxpr_collective_counts)

    x, y = _dyadic_data(B=64)
    est = _dyadic_estimator(
        TrainConfig(shuffle=False, log_every_n_steps=10 ** 9,
                    update_sharding=True), x, y)
    meta = est._flat_meta
    assert (meta.n_buckets, meta.n, meta.npad) == (1, 192, 192)
    assert meta.bucket_shape == (3, 64) and meta.shard_shape == (3, 8)
    census = jaxpr_collective_counts(jax.make_jaxpr(est._step_fn())(
        est.train_state, est._to_global((x, y))))
    assert census["counts"]["reduce-scatter"] == 1
    assert census["counts"]["all-gather"] == 1


def test_flat_step_traces_the_flash_kernel_at_a_batch_dp_divides(zoo_ctx):
    """Inside the flat step's ``shard_map`` a device holds its rows of the
    batch already: when dp divides that many rows too (8 a chip on dp=8),
    ``sharded_attention`` must call the kernel on them as they are and not
    wrap it in a second ``shard_map``, which does not trace (ROADMAP C2)."""
    from analytics_zoo_tpu.data import FeatureSet
    from analytics_zoo_tpu.models.transformer import TransformerLM, lm_loss
    from analytics_zoo_tpu.ops.attention import _ROUTES

    model = TransformerLM(vocab=64, hidden_size=32, n_block=2, n_head=2,
                          seq_len=128, attn_strategy="flash")
    est = Estimator(model, optimizer=Adam(lr=1e-3), loss=lm_loss,
                    mesh=zoo_ctx.mesh,
                    config=TrainConfig(update_sharding=True,
                                       log_every_n_steps=1))
    ids = np.random.default_rng(0).integers(0, 64, (64, 128)).astype(np.int32)
    before = _ROUTES.labels(route="flash", backward="1").value()
    est.fit(FeatureSet.from_numpy(ids, ids), batch_size=64, epochs=1)
    assert est._update_mode() == "flat"
    assert np.isfinite(float(est.trainer_state.last_loss))
    assert _ROUTES.labels(route="flash", backward="1").value() - before == 2


def test_flat_meta_cuts_equal_buckets_from_the_leaves():
    """Bucket geometry: every leaf starts on a row of the flat view (padded
    to whole rows), buckets are equal runs of rows, a bucket is stacked from
    exactly the row blocks it covers, and flatten and unflatten are inverses
    across leaves that straddle buckets and leaves that need padding."""
    rng = np.random.default_rng(0)
    params = {"a": rng.normal(size=(8, 16)).astype(np.float32),
              "b": rng.normal(size=(7,)).astype(np.float32),
              "c": rng.normal(size=(16, 4)).astype(np.float32)}
    meta = upd.flat_meta(params, 2, bucket_len=40)
    # 16-column rows waste 9 of 199 elements on "b"; 8-column rows waste 1
    assert meta.shard_shape == (5, 4) and meta.bucket_shape == (5, 8)
    assert meta.leaf_rows == (16, 1, 8) and meta.n == 199
    assert (meta.n_buckets, meta.bucket_len, meta.npad) == (5, 40, 200)
    assert meta.pieces(0) == ((0, 0, 5),)
    assert meta.pieces(3) == ((0, 15, 16), (1, 0, 1), (2, 0, 3))
    assert meta.pieces(4) == ((2, 3, 8),)
    view = np.concatenate([params["a"].ravel(), np.pad(params["b"], (0, 1)),
                           params["c"].ravel()])
    for b in range(meta.n_buckets):
        got = np.asarray(upd.flat_bucket(params, meta, b))
        np.testing.assert_array_equal(
            got, view[b * 40:(b + 1) * 40].reshape(5, 8))
    np.testing.assert_array_equal(
        np.asarray(upd.flatten_tree(params, meta)), view)
    back = upd.unflatten_buckets(
        [upd.flat_bucket(params, meta, b) for b in range(meta.n_buckets)],
        meta)
    for k in params:
        np.testing.assert_array_equal(np.asarray(back[k]), params[k])
    # a large model: rows of SHARD_COLS columns a shard, the row count
    # rounded so the chip's compiler can chunk it; a model under one target:
    # one bucket, and fewer columns where whole rows would waste too much
    big = {"w": jax.ShapeDtypeStruct((612_917_248,), jnp.bfloat16)}
    meta = upd.flat_meta(big, 4)
    assert meta.n_buckets == 13 and meta.shard_shape == (11520, 1024)
    one = upd.flat_meta({"w": jax.ShapeDtypeStruct((1000, 1000),
                                                    jnp.float32)}, 4)
    assert one.n_buckets == 1 and one.shard_shape == (246, 1024)
    odd = upd.flat_meta({"w": jax.ShapeDtypeStruct((300, 300), jnp.float32),
                         "b": jax.ShapeDtypeStruct((300,), jnp.float32)}, 4)
    assert odd.n_buckets == 1 and odd.shard_shape == (89, 256)
    assert odd.npad <= odd.n * (1 + 1 / 64)


def _cell_tree():
    """The four-chip training cell's parameter tree, by shape."""
    from analytics_zoo_tpu.models.transformer import TransformerLM

    model = TransformerLM(vocab=50257, hidden_size=2048, n_block=8, n_head=16,
                          seq_len=2048, intermediate_size=8192)
    return jax.eval_shape(lambda key: model.build(key)[0],
                          jax.random.PRNGKey(0))


def test_flat_meta_lays_matrices_in_by_their_own_rows():
    """In a model of several buckets the view is as wide as the matrices'
    own minor dimension allows (a shard of whole lane tiles): a matrix that
    many columns wide, or a multiple, enters by its own rows, its column
    blocks one below the other, the matrices of whole row tiles first; a
    matrix that cannot but has that many rows, or a multiple, enters by its
    transpose's rows; every other leaf is raveled as in the plain view,
    after them. Bucket then unflatten is the identity, and a tree under one
    target keeps the plain view in one bucket."""
    rng = np.random.default_rng(0)
    params = {"a": rng.normal(size=(16, 512)).astype(np.float32),
              "b": rng.normal(size=(256,)).astype(np.float32),
              "c": rng.normal(size=(256, 4)).astype(np.float32),
              "d": rng.normal(size=(3, 256)).astype(np.float32),
              "e": rng.normal(size=(32, 256)).astype(np.float32),
              "f": rng.normal(size=(16, 384)).astype(np.float32),
              "g": rng.normal(size=(4, 4, 512)).astype(np.float32)}
    meta = upd.flat_meta(params, 2, bucket_len=3072)
    assert meta.shard_shape == (12, 128) and meta.bucket_shape == (12, 256)
    # "a" is two column blocks of 16 rows, "d" and "e" one, "g" (seen as
    # sixteen rows of 512) two; "c" has one view width of rows and enters
    # as the four rows of its transpose; "f" is a matrix of one and a half
    # widths with sixteen rows and is raveled like "b"
    assert meta.col_blocks == (2, 0, -1, 1, 1, 0, 2)
    assert meta.leaf_rows == (32, 1, 4, 3, 32, 24, 32)
    assert meta.order == (0, 4, 6, 2, 3, 1, 5) and meta.n_buckets == 11
    assert meta.own_rows_share == (8192 + 1024 + 768 + 8192 + 8192) / meta.n
    # bucket 1 holds the last four rows of "a"'s first column block and the
    # first eight of its second
    assert meta.pieces(1) == ((0, 12, 24),)
    assert list(meta.blocks(0, 12, 24)) == [(0, 12, 16), (1, 0, 8)]
    assert meta.pieces(8) == ((2, 0, 4), (3, 0, 3), (1, 0, 1), (5, 0, 4))
    assert list(meta.blocks(2, 0, 4)) == [(0, 0, 4)]
    a, g = params["a"], params["g"].reshape(16, 512)
    view = np.concatenate([
        a[:, :256], a[:, 256:], params["e"], g[:, :256], g[:, 256:],
        params["c"].T, params["d"], params["b"].reshape(1, 256),
        params["f"].reshape(24, 256)])
    view = np.pad(view, ((0, 11 * 12 - len(view)), (0, 0)))
    buckets = [upd.flat_bucket(params, meta, b) for b in range(meta.n_buckets)]
    for b, got in enumerate(buckets):
        np.testing.assert_array_equal(np.asarray(got),
                                      view[b * 12:(b + 1) * 12])
    back = upd.unflatten_buckets(buckets, meta)
    for k in params:
        np.testing.assert_array_equal(np.asarray(back[k]), params[k])
    # the raveled leaves take the rows they take in the plain view of that
    # width, one after the other
    assert [meta.leaf_rows[i] for i in (1, 5)] == [
        -(-params[k].size // 256) for k in "bf"]
    # under one target: the plain view, one bucket, nothing to tell it by
    one = upd.flat_meta(params, 2)
    assert one.n_buckets == 1 and one.layout is None
    assert one.col_blocks == (0,) * 7 and one.order == tuple(range(7))
    whole = np.asarray(upd.flatten_tree(params, one))
    np.testing.assert_array_equal(whole[:8192 + 256], np.concatenate(
        [params["a"].ravel(), params["b"]]))      # raveled, in tree order
    # the four-chip cell: 2,048 columns (512 a shard) take every matrix by
    # its own rows but the head, (2048, 50257), which enters by the 50,257
    # rows of its transpose: as many as its ravel took, so the view has the
    # rows and the buckets it had with the head raveled, and only the
    # vectors are raveled still
    cell = upd.flat_meta(_cell_tree(), 4)
    assert cell.n_buckets == 13 and cell.shard_shape == (23040, 512)
    assert round(cell.own_rows_share, 4) == 0.9996
    names = [jax.tree_util.keystr(p) for p, _ in
             jax.tree_util.tree_leaves_with_path(_cell_tree())]
    assert [names[i] for i in cell.order[32:36]] == [
        "['pos_embeddings']", "['logits_kernel']", "['token_embeddings']",
        "['block0']['attn']['out_bias']"]
    head = names.index("['logits_kernel']")
    assert cell.col_blocks[head] == -1 and cell.leaf_rows[head] == 50257
    assert sum(cell.leaf_rows) == 299_276 == sum(
        -(-z // 2048) for z in cell.sizes)
    assert {k for k, s in zip(cell.col_blocks, cell.shapes)
            if len(s) == 1} == {0}
    assert cell.npad <= cell.n * (1 + 1 / 64)


def test_flat_meta_lays_a_head_in_by_the_rows_of_its_transpose():
    """A head of an odd width beside own-rows matrices and vectors: its
    columns fit no view, its rows are two view widths, so it is recorded as
    entering by the two column blocks of its transpose, one below the other,
    behind the matrices of whole row tiles and before the raveled vectors.
    It takes the rows its ravel took, so the view's rows and buckets are the
    raveled view's; bucket then unflatten is the identity bit for bit, in
    bf16 too; the layout tells the view from the one that raveled the head;
    and under one target the tree keeps the plain view."""
    rng = np.random.default_rng(1)
    params = {"bias": rng.normal(size=(512,)).astype(np.float32),
              "emb": rng.normal(size=(37, 256)).astype(np.float32),
              "head": rng.normal(size=(512, 37)).astype(np.float32),
              "ln": rng.normal(size=(256,)).astype(np.float32),
              "w": rng.normal(size=(256, 512)).astype(np.float32),
              "wide": rng.normal(size=(2, 512, 37)).astype(np.float32)}
    meta = upd.flat_meta(params, 2, bucket_len=8192)
    assert meta.bucket_shape == (31, 256)
    # "wide" has three dimensions and stays raveled, as does a matrix
    # neither of whose dimensions fits ("f" of the test above)
    assert meta.col_blocks == (0, 1, -2, 0, 2, 0)
    assert meta.leaf_rows == (2, 37, 74, 1, 512, 148)
    assert meta.order == (4, 1, 2, 0, 3, 5) and meta.n_buckets == 25
    raveled = _view_before(meta)
    assert raveled.col_blocks == (0, 1, 0, 0, 2, 0)
    assert sum(meta.leaf_rows) == sum(-(-z // 256) for z in meta.sizes)
    assert not np.array_equal(meta.layout, raveled.layout)
    assert meta.own_rows_share == (37 * 256 + 512 * 37 + 256 * 512) / meta.n
    # the head's rows 20 to 52 of 74: the last 17 rows of its transpose's
    # first column block, the first 15 of its second
    assert list(meta.blocks(2, 20, 52)) == [(0, 20, 37), (1, 0, 15)]
    w, t = params["w"], params["head"].T
    view = np.concatenate([
        w[:, :256], w[:, 256:], params["emb"], t[:, :256], t[:, 256:],
        params["bias"].reshape(2, 256), params["ln"].reshape(1, 256),
        np.pad(params["wide"].ravel(), (0, 148 * 256 - 2 * 512 * 37)
               ).reshape(148, 256)])
    view = np.pad(view, ((0, 25 * 31 - len(view)), (0, 0)))
    for dtype in (np.float32, jnp.bfloat16):
        tree = {k: jnp.asarray(v, dtype) for k, v in params.items()}
        m = meta._replace(dtypes=(jnp.dtype(dtype),) * 6)
        buckets = [upd.flat_bucket(tree, m, b, jnp.dtype(dtype))
                   for b in range(m.n_buckets)]
        for b, got in enumerate(buckets):
            np.testing.assert_array_equal(
                np.asarray(got.astype(np.float32)),
                np.asarray(jnp.asarray(view[b * 31:(b + 1) * 31], dtype
                                       ).astype(np.float32)))
        back = upd.unflatten_buckets(buckets, m)
        for k in params:
            assert back[k].dtype == dtype and back[k].shape == tree[k].shape
            np.testing.assert_array_equal(
                np.asarray(back[k].astype(np.float32)),
                np.asarray(tree[k].astype(np.float32)))
    one = upd.flat_meta(params, 2)
    assert one.n_buckets == 1 and one.layout is None
    assert one.col_blocks == (0,) * 6 and one.order == tuple(range(6))


@pytest.mark.parametrize("precision", ["f32", "bf16"])
def test_flat_exchange_is_bit_identical_under_every_view(precision):
    """The same gradients through ``flat_exchange`` on two devices, three
    Adam steps, f32 params or bf16 params with f32 masters: the view with a
    head entering by its transpose's rows gives the parameters, the masters
    and the moments of the plain one-bucket view and of the view that ravels
    the head (what the version before built) bit for bit. The view decides
    which rows of which bucket carry an element, never its arithmetic."""
    import optax
    from jax import shard_map

    dtype = jnp.float32 if precision == "f32" else jnp.bfloat16
    rng = np.random.default_rng(2)
    shapes = {"bias": (512,), "emb": (37, 256), "head": (512, 37),
              "ln": (256,), "w": (256, 512)}
    params = {k: jnp.asarray(rng.normal(size=s), dtype)
              for k, s in shapes.items()}
    grads = [{k: jnp.asarray(rng.normal(size=s), dtype)
              for k, s in shapes.items()} for _ in range(3)]
    mesh = Mesh(np.array(jax.devices()[:2]), ("dp",))
    tx = optax.adam(1e-2)
    head = upd.flat_meta(params, 2, bucket_len=8192)
    views = {"one": upd.flat_meta(params, 2), "head": head,
             "raveled": _view_before(head)}
    assert views["one"].n_buckets == 1 and head.n_buckets > 3
    assert head.col_blocks == (0, 1, -2, 0, 2)
    assert views["raveled"].col_blocks == (0, 1, 0, 0, 2)

    def run(meta):
        opt = upd.flat_opt_init(tx, params, meta,
                                keep_master=precision == "bf16")
        specs = jax.tree_util.tree_map(
            lambda l: P(None, "dp") if l.shape == meta.bucket_shape else P(),
            opt)
        replicated = jax.tree_util.tree_map(lambda _: P(), params)
        step = jax.jit(shard_map(
            lambda p, g, o: upd.flat_exchange(p, g, o, meta, tx), mesh=mesh,
            in_specs=(replicated, replicated, specs),
            out_specs=(replicated, specs, P()), check_vma=False))
        p = params
        for g in grads:
            p, opt, _ = step(p, g, opt)
        f32 = meta._replace(dtypes=(jnp.dtype("float32"),) * len(shapes))
        state = [opt.inner_state[b][0] for b in range(meta.n_buckets)]
        trees = [p, upd.unflatten_buckets([s.mu for s in state], f32),
                 upd.unflatten_buckets([s.nu for s in state], f32)]
        if opt.master is not None:
            trees.append(upd.unflatten_buckets(list(opt.master), f32))
        return [np.asarray(l.astype(jnp.float32)) for t in trees
                for l in jax.tree_util.tree_leaves(t)]

    got = {name: run(meta) for name, meta in views.items()}
    for name in ("one", "raveled"):
        for a, b in zip(got[name], got["head"]):
            np.testing.assert_array_equal(a, b, err_msg=name)


@pytest.mark.parametrize("target,hidden,out,bias", _VIEWS2)
@pytest.mark.parametrize("precision", ["f32", "bf16"])
@pytest.mark.parametrize("optimizer", ["sgd_momentum", "adam"])
def test_bucketed_update_bit_parity_two_devices(zoo_ctx, bucket_target,
                                                request, optimizer, precision,
                                                hidden, target, out, bias):
    """Three buckets on a 2-device dp mesh: in the plain view; with the
    first kernel entering by its own rows and the second by its transpose's;
    and with a bias vector raveled between them and a head of an odd width.
    The view changes which collective carries an element, never its
    arithmetic: one step and six more are bit-identical to the one-bucket
    exchange, f32 params or bf16 params with f32 masters (where a leaf
    enters by its transpose under bf16 compute, in a process of its own
    whose compiler keeps no excess precision: see below). Against the
    REPLICATED update one f32 step is bit-identical and six more stay within
    1e-5; under bf16 compute the replicated path sums the bf16 gradients
    across replicas before the cast where the flat path casts to f32 first,
    so there the comparison is at bf16's grain."""
    make = {"sgd_momentum": lambda: SGD(lr=0.5, momentum=0.5),
            "adam": lambda: Adam(lr=1e-2)}[optimizer]
    extra = {"compute_dtype": "bfloat16"} if precision == "bf16" else {}
    x, y = _dyadic_data(B=32, O=out)
    ests = {}
    for name, sharded in (("replicated", False), ("one", True),
                          ("three", True)):
        if name == "three":
            bucket_target(target)
        cfg = TrainConfig(shuffle=False, log_every_n_steps=10 ** 9,
                          update_sharding=sharded, **extra)
        ests[name] = _dyadic_estimator(cfg, x, y, optimizer=make(),
                                       mesh=_mesh2(), H=hidden, O=out,
                                       bias=bias)
    one, three = ests["one"]._flat_meta, ests["three"]._flat_meta
    assert (one.n_buckets, one.layout) == (1, None)
    assert three.n_buckets == 3
    assert three.col_blocks == _col_blocks(hidden, bias)
    bf16_grain = dict(rtol=0, atol=2 ** -7)
    transposed = [k < 0 for k in three.col_blocks]

    def compare(steps):
        got = {k: [l.astype(np.float32) for l in _leaves(e)]
               for k, e in ests.items()}
        for a, b in zip(got["one"], got["three"]):
            np.testing.assert_array_equal(a, b, err_msg=f"{steps} step(s)")
        for a, b in zip(got["replicated"], got["three"]):
            if precision == "bf16":
                np.testing.assert_allclose(a, b, **bf16_grain)
            elif steps == 1:
                np.testing.assert_array_equal(a, b)
            else:
                np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)

    for est in ests.values():
        est.fit((x, y), batch_size=32, epochs=1)      # exactly one step
    if (precision == "bf16" and any(transposed)
            and _ROUNDED not in os.environ.get("XLA_FLAGS", "")):
        # XLA:CPU fuses the transpose into the matmul that makes the leaf's
        # gradient and, allowed excess precision (its default), drops that
        # gradient's rounding to bf16 on the way: the GRADIENT of that one
        # leaf, which bf16 does not hold exactly even on the dyadic data,
        # keeps bits the one-bucket fit rounds away. Here: the first step
        # leaves every other leaf bit-equal and that leaf within a bf16 ulp
        # of its largest element. Then this very case runs again in a
        # process whose compiler rounds where the program says so, and
        # holds every assertion below bit for bit.
        got = {k: [l.astype(np.float32) for l in _leaves(ests[k])]
               for k in ("one", "three")}
        for t, a, b in zip(transposed, got["one"], got["three"]):
            np.testing.assert_allclose(
                a, b, rtol=0, atol=np.abs(a).max() * 2 ** -7 if t else 0)
        env = {k: v for k, v in os.environ.items()
               if not k.startswith("PYTEST_")}
        env["XLA_FLAGS"] = f"{env.get('XLA_FLAGS', '')} {_ROUNDED}".strip()
        child = subprocess.run(
            [sys.executable, "-m", "pytest", "-q", "-p", "no:cacheprovider",
             f"{__file__}::{request.node.name}"],
            env=env, capture_output=True, text=True, timeout=300)
        assert child.returncode == 0 and "1 passed" in child.stdout, (
            child.stdout[-4000:] + child.stderr[-2000:])
        return
    compare(1)
    for est in ests.values():
        est.fit((x, y), batch_size=32, epochs=7)      # six more
    compare(7)
    if precision == "bf16":
        # the f32 masters, read back leaf by leaf from the three buckets,
        # are the one-bucket masters bit for bit
        masters = {k: jax.tree_util.tree_leaves(upd.unflatten_buckets(
            [jnp.asarray(m) for m in jax.device_get(
                ests[k].train_state["opt_state"]).master],
            ests[k]._flat_meta._replace(
                dtypes=(jnp.dtype("float32"),) * (2 + bias))))
            for k in ("one", "three")}
        for a, b in zip(masters["one"], masters["three"]):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("hidden,target", [(16, 48), _OWN_ROWS[2]],
                         ids=["plain", "own_rows"])
def test_bucketed_clip_norm_matches_replicated(zoo_ctx, bucket_target,
                                               hidden, target):
    """gradient_clip_norm with several buckets: the norm is one scalar psum
    over all buckets' shards, so the clipped update equals the replicated
    clipped update (and the reported norm is the global one)."""
    x, y = _dyadic_data(B=32)
    bucket_target(target)
    ests, norms = {}, {}
    for sharded in (False, True):
        cfg = TrainConfig(shuffle=False, log_every_n_steps=1,
                          update_sharding=sharded, gradient_clip_norm=0.25)
        est = _dyadic_estimator(cfg, x, y, optimizer=SGD(lr=0.5),
                                mesh=_mesh2(), H=hidden)
        snap0 = _tm.snapshot().get("zoo_train_grad_norm", {}).get(
            "samples", {}).get("", {"sum": 0.0})["sum"]
        est.fit((x, y), batch_size=32, epochs=1)
        norms[sharded] = _tm.snapshot()["zoo_train_grad_norm"][
            "samples"][""]["sum"] - snap0
        ests[sharded] = est
    assert ests[True]._flat_meta.n_buckets == 3
    assert (ests[True]._flat_meta.layout is not None) == (hidden > 16)
    assert norms[True] > 0.25                  # the clip engaged
    np.testing.assert_allclose(norms[True], norms[False], rtol=1e-6)
    for a, b in zip(_leaves(ests[False]), _leaves(ests[True])):
        np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-7)


# ----------------------------------------------------------- mixed precision
def test_mixed_precision_trains_with_f32_masters(zoo_ctx):
    """bf16 params + f32 masters in the (sharded) optimizer state; the loss
    curve still goes down and the f32 grad norm lands in telemetry."""
    rng = np.random.default_rng(3)
    x = rng.normal(size=(256, 16)).astype(np.float32)
    w = rng.normal(size=(16, 4)).astype(np.float32)
    y = x @ w + 0.01 * rng.normal(size=(256, 4)).astype(np.float32)
    model = Sequential([L.Dense(32, activation="relu", input_shape=(16,)),
                        L.Dense(4)])
    est = Estimator(model, optimizer=Adam(1e-2), loss="mse",
                    config=TrainConfig(shuffle=False, log_every_n_steps=1,
                                       compute_dtype="bfloat16",
                                       update_sharding=True))
    snap0 = _tm.snapshot()
    est.fit((x, y), batch_size=64, epochs=1)
    first = float(est.trainer_state.last_loss)
    est.fit((x, y), batch_size=64, epochs=8)
    assert float(est.trainer_state.last_loss) < first
    # model params are bf16; the f32 values live only in the sharded masters
    p0 = jax.tree_util.tree_leaves(est.train_state["params"])[0]
    assert p0.dtype == jnp.bfloat16
    (master,) = est.train_state["opt_state"].master     # one bucket
    assert master.dtype == jnp.float32
    assert master.sharding.spec == P(None, "dp")
    snap1 = _tm.snapshot()

    def count(snap):
        return snap.get("zoo_train_grad_norm", {}).get(
            "samples", {}).get("", {"count": 0})["count"]

    assert count(snap1) > count(snap0)


def test_mixed_precision_gspmd_masters_replicated_mesh(zoo_ctx):
    """compute_dtype without update_sharding: masters live in
    MasterWeightsState (with_master_weights), params are bf16."""
    x, y = _dyadic_data(B=64)
    est = _dyadic_estimator(
        TrainConfig(shuffle=False, log_every_n_steps=10 ** 9,
                    compute_dtype="bfloat16"), x, y)
    est.fit((x, y), batch_size=32, epochs=1)
    opt = est.train_state["opt_state"]
    assert isinstance(opt, upd.MasterWeightsState)
    m0 = jax.tree_util.tree_leaves(opt.master)[0]
    assert m0.dtype == jnp.float32
    p0 = jax.tree_util.tree_leaves(est.train_state["params"])[0]
    assert p0.dtype == jnp.bfloat16


# ------------------------------------------------------------- gspmd compose
def test_gspmd_mode_composes_with_fsdp_tp():
    """dp=2 x fsdp=2 x tp=2 mesh with the megatron rules: update sharding
    falls to the gspmd path, optimizer-state leaves gain a dp axis on top of
    their fsdp/tp spec, and training still converges."""
    from analytics_zoo_tpu.models.transformer import TransformerLM, lm_loss

    reset_zoo_context()
    ctx = init_zoo_context(mesh=MeshConfig(dp=2, fsdp=2, tp=2))
    try:
        model = TransformerLM(vocab=64, hidden_size=32, n_block=1, n_head=2,
                              seq_len=16, attn_strategy="full")
        est = Estimator(model, optimizer=Adam(lr=0.01), loss=lm_loss,
                        mesh=ctx.mesh,
                        param_sharding=make_param_sharding(ctx.mesh),
                        config=TrainConfig(log_every_n_steps=10 ** 9,
                                           update_sharding=True,
                                           grad_accum_steps=2))
        assert est._update_mode() == "gspmd"
        rng = np.random.default_rng(0)
        x = rng.integers(0, 64, size=(256, 16)).astype("int32")
        y = np.roll(x, -1, axis=1)
        est.fit((x, y), batch_size=64, epochs=1)
        first = float(est.trainer_state.last_loss)
        est.fit((x, y), batch_size=64, epochs=6)
        assert float(est.trainer_state.last_loss) < first
        n_dp = 0
        for leaf in jax.tree_util.tree_leaves(est.train_state["opt_state"]):
            spec = getattr(getattr(leaf, "sharding", None), "spec", None)
            if spec is None:
                continue
            axes = set()
            for e in spec:
                axes.update(e if isinstance(e, tuple) else (e,))
            if "dp" in axes:
                n_dp += 1
        assert n_dp > 0
    finally:
        reset_zoo_context()


def test_shard_spec_over_axis_rules(zoo_ctx):
    mesh = jax.sharding.Mesh(
        np.array(jax.devices()).reshape((2, 2, 2) + (1,) * 3), AXES)
    f = upd.shard_spec_over_axis
    assert f(P(), (64, 8), mesh, "dp") == P("dp", None)
    # 2-D row preference: the row dim wins even when the column dim is larger
    # — an oblong (vocab, embed) table with embed > vocab/shards must still
    # shard by rows so the sharded-gather/row-delta paths stay row-keyed
    assert f(P(), (8, 64), mesh, "dp") == P("dp", None)
    assert f(P(), (6, 4096), mesh, "dp") == P("dp", None)
    # rows not divisible → falls back to the column dim
    assert f(P(), (7, 64), mesh, "dp") == P(None, "dp")
    # composes: appends dp to an fsdp-sharded dim when it still divides
    assert f(P("fsdp", "tp"), (7, 64), mesh, "dp") == P("fsdp", ("tp", "dp"))
    # nothing divides → unchanged (replicated update for the leaf)
    assert f(P(), (3, 5), mesh, "dp") == P(None, None)
    # scalars untouched
    assert f(P(), (), mesh, "dp") == P()
    # already dp-sharded → unchanged
    assert f(P("dp", None), (4, 4), mesh, "dp") == P("dp", None)
    # 3-D and above keep largest-first selection
    assert f(P(), (4, 64, 8), mesh, "dp") == P(None, "dp", None)


# --------------------------------------------------------- sharding satellite
def test_sanitize_raises_on_overdividing_tuple_axes(zoo_ctx):
    mesh = jax.sharding.Mesh(
        np.array(jax.devices()).reshape((2, 2, 2) + (1,) * 3), AXES)
    rule = make_param_sharding(mesh,
                               rules=(("kern", P(("fsdp", "tp"), None)),))

    class K:
        def __init__(self, key):
            self.key = key

    # combined (fsdp, tp) = 4 does not divide 6 → friendly error w/ the path
    with pytest.raises(ValueError, match=r"block0/kern.*combined"):
        rule((K("block0"), K("kern")), np.zeros((6, 8), "float32"))
    # a SINGLE over-dividing axis still falls back to replicated on that dim
    rule2 = make_param_sharding(mesh, rules=(("kern", P("tp", None)),))
    assert rule2((K("kern"),), np.zeros((63, 8), "float32")) == P(None, None)


# ---------------------------------------------------------------- durability
@pytest.mark.parametrize("bucket_len,hidden,out,bias", _VIEWS8)
def test_flat_mode_checkpoint_roundtrip(zoo_ctx, tmp_path, bucket_target,
                                        bucket_len, hidden, out, bias):
    x, y = _dyadic_data(B=64, O=out)
    if bucket_len:
        bucket_target(bucket_len)
    cfg = TrainConfig(shuffle=False, log_every_n_steps=10 ** 9,
                      update_sharding=True, checkpoint_dir=str(tmp_path))
    est = _dyadic_estimator(cfg, x, y, optimizer=Adam(1e-2), H=hidden, O=out,
                            bias=bias)
    est.fit((x, y), batch_size=32, epochs=2)
    it = est.trainer_state.iteration
    # fresh estimator resumes from the flat-layout checkpoint
    cfg2 = TrainConfig(shuffle=False, log_every_n_steps=10 ** 9,
                       update_sharding=True, checkpoint_dir=str(tmp_path))
    est2 = Estimator(_dyadic_model(H=hidden, O=out, bias=bias),
                     optimizer=Adam(1e-2), loss="mse", config=cfg2)
    est2.load(str(tmp_path), sample_batch=(x, y))
    # the flat-layout state (FlatUpdateState + dp-sharded vectors) round-trips
    assert est2.trainer_state.iteration == it
    assert isinstance(est2.train_state["opt_state"], upd.FlatUpdateState)
    assert est2._flat_meta.n_buckets == (3 if bucket_len else 1)
    assert (est2.train_state["opt_state"].layout is not None) == (hidden > 16)
    for a, b in zip(_leaves(est), _leaves(est2)):
        np.testing.assert_array_equal(a, b)
    for a, b in zip(*(jax.tree_util.tree_leaves(jax.device_get(
            e.train_state["opt_state"])) for e in (est, est2))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    est2.fit((x, y), batch_size=32, epochs=3)         # resumes, 1 more epoch
    assert est2.trainer_state.iteration == it + 2


@pytest.mark.parametrize("bucket_len,hidden,out,bias,written_as", [
    (None, 16, 4, False, "vector"), (48, 16, 4, False, "vector"),
    (_OWN_ROWS[8][1], _OWN_ROWS[8][0], 4, False, "plain_buckets"),
    (_OWN_ROWS[8][1], _OWN_ROWS[8][0], 4, False, "another_view"),
    (_OWN_ROWS[8][1], _OWN_ROWS[8][0], 4, False, "transposes_raveled"),
    (_HEAD[8][1], _HEAD[8][0], 5, True, "transposes_raveled")])
def test_old_flat_layout_checkpoint_is_repadded_or_refused(
        zoo_ctx, tmp_path, bucket_target, bucket_len, hidden, out, bias,
        written_as):
    """A checkpoint whose flat optimizer state is one ``(npad,)`` vector per
    slot (the layout before bucketing): a one-bucket estimator re-pads it
    into its ``bucket_shape`` (same flat order); a several-bucket one refuses
    it in words. So does an estimator whose first kernel enters its buckets
    by its own rows when the snapshot's buckets, of the very same shape,
    were stacked in the plain view (every leaf raveled, in tree order: what
    the version before wrote), in a view of other leaves, or in the view
    of the version that raveled what now enters by its transpose's rows
    (the same buckets, rows and width; the head's rows hold its ravel). It
    is never read as if it were the new layout."""
    import optax

    from analytics_zoo_tpu.engine import checkpoint as ckpt

    x, y = _dyadic_data(B=64, O=out)
    if bucket_len:
        bucket_target(bucket_len)
    cfg = TrainConfig(shuffle=False, log_every_n_steps=10 ** 9,
                      update_sharding=True, compute_dtype="bfloat16")
    est = _dyadic_estimator(cfg, x, y, optimizer=Adam(1e-2), H=hidden, O=out,
                            bias=bias)
    state = jax.device_get(est.train_state)
    if written_as != "vector":
        mine, meta = state["opt_state"], est._flat_meta
        rows = 5 if bias else 4
        assert mine.master[0].shape == (rows, 1024) and len(mine.master) == 3
        if written_as == "transposes_raveled":
            # the same buckets: a transpose takes the rows the ravel took
            assert sum(meta.leaf_rows) == sum(-(-z // 1024)
                                              for z in meta.sizes)
            other = _view_before(meta).layout
            assert other.shape == meta.layout.shape
        else:
            other = (None if written_as == "plain_buckets"
                     else np.asarray(mine.layout)[::-1].copy())
        old = dict(state, opt_state=mine._replace(layout=other))
        ckpt.save_checkpoint(str(tmp_path), old, iteration=7, epoch=1)
        with pytest.raises(ValueError, match=(
                rf"3 bucket\(s\) of \({rows}, 1024\), matrices by their own "
                r"rows.*"
                + ("16 leaves, template has 17"
                   if written_as == "plain_buckets" else "another flat view")
                + r".*Resume it with the version that wrote it")):
            est.load(str(tmp_path))
        return
    flat = np.arange(192, dtype=np.float32) / 8          # npad = n = 192
    old = dict(state, opt_state=upd.FlatUpdateState(
        optax.adam(1e-2).init(jnp.asarray(flat)), flat))
    assert len(jax.tree_util.tree_leaves(old)) == len(
        jax.tree_util.tree_leaves(state)) or bucket_len
    ckpt.save_checkpoint(str(tmp_path), old, iteration=7, epoch=1)
    if bucket_len:
        with pytest.raises(ValueError, match=r"3 bucket\(s\) of \(1, 64\)"):
            est.load(str(tmp_path))
        return
    est.load(str(tmp_path))
    assert est.trainer_state.iteration == 7
    (master,) = est.train_state["opt_state"].master
    assert master.shape == (3, 64) and master.sharding.spec == P(None, "dp")
    np.testing.assert_array_equal(np.asarray(master).ravel(), flat)


def test_bf16_checkpoint_roundtrip(zoo_ctx, tmp_path):
    """npz has no bfloat16 — leaves round-trip as raw |V2 bytes and must be
    view-cast back from the template (the bug the verify drive caught)."""
    x, y = _dyadic_data(B=64)
    cfg = dict(shuffle=False, log_every_n_steps=10 ** 9,
               update_sharding=True, compute_dtype="bfloat16",
               checkpoint_dir=str(tmp_path))
    est = _dyadic_estimator(TrainConfig(**cfg), x, y, optimizer=Adam(1e-2))
    est.fit((x, y), batch_size=32, epochs=2)
    model = Sequential([L.Dense(16, use_bias=False, input_shape=(8,)),
                        L.Dense(4, use_bias=False)])
    est2 = Estimator(model, optimizer=Adam(1e-2), loss="mse",
                     config=TrainConfig(**cfg))
    est2.load(str(tmp_path), sample_batch=(x, y))
    for a, b in zip(_leaves(est), _leaves(est2)):
        assert a.dtype == b.dtype == jnp.bfloat16
        np.testing.assert_array_equal(a, b)
    (m,) = est2.train_state["opt_state"].master
    assert m.dtype == jnp.float32


_OOM_DUMP = """RESOURCE_EXHAUSTED: XLA:TPU compile permanent error. Ran out of memory in memory space hbm. Used 17.54G of 15.48G hbm. Exceeded hbm capacity by 2.06G.

Largest program allocations in hbm:

  1. Size: 8.00G
     Operator: op_name="jit(step)/jit(main)/dot_general"
     Shape: f32[32,2048,32768]{2,1,0:T(8,128)}
     Unpadded size: 8.00G
     XLA label: fusion.123 = fusion(...)
     Allocation type: HLO temp
     ==========================

  2. Size: 8.00M
     Operator: op_name="params[\\'pos_embeddings\\']"
     Shape: f32[2048,1024]{0,1:T(8,128)}
     Unpadded size: 8.00M
     XLA label: copy.425 = copy(params__pos_embeddings__.1)
     Allocation type: HLO temp
     ==========================
"""


def test_parse_xla_memory_analysis_structured():
    from analytics_zoo_tpu.analysis.memory import parse_xla_memory_analysis

    out = parse_xla_memory_analysis(_OOM_DUMP)
    assert out["hbm_peak_bytes"] == int(17.54 * 2 ** 30)
    assert out["hbm_capacity_bytes"] == int(15.48 * 2 ** 30)
    top = out["top_allocations"]
    assert len(top) == 2
    assert top[0]["size_bytes"] == 8 * 2 ** 30
    assert top[0]["op_name"].endswith("dot_general")
    assert top[0]["allocation_type"] == "HLO temp"
    assert top[1]["size_bytes"] == 8 * 2 ** 20
    assert top[1]["shape"].startswith("f32[2048,1024]")
    # no dump → None, not a half-filled dict
    assert parse_xla_memory_analysis("all good") is None


def test_memory_fields_structured_vs_text_parity():
    """memory_fields reads the structured PJRT stats when present and the
    text dump otherwise — both land in the same hbm_peak_bytes field."""
    from analytics_zoo_tpu.analysis.memory import memory_fields

    class _Structured:
        def memory_analysis(self):
            class S:
                temp_size_in_bytes = 1000
                argument_size_in_bytes = 2000
                output_size_in_bytes = 500
                alias_size_in_bytes = 300
            return S()

    class _Text:
        def memory_analysis(self):
            return _OOM_DUMP

    class _Broken:
        def memory_analysis(self):
            raise RuntimeError("no analysis on this backend")

    s = memory_fields(_Structured())
    assert s["hbm_peak_bytes"] == 3000
    assert s["alias_size_in_bytes"] == 300
    t = memory_fields(_Text())
    assert t["hbm_peak_bytes"] == int(17.54 * 2 ** 30)
    assert memory_fields(_Broken()) == {}


# ------------------------------------------------------------------ orca knobs
def test_orca_fit_threads_update_sharding_knobs(zoo_ctx):
    from analytics_zoo_tpu.orca.learn import Estimator as OrcaEstimator

    rng = np.random.default_rng(0)
    x = rng.normal(size=(128, 8)).astype(np.float32)
    y = rng.normal(size=(128, 2)).astype(np.float32)
    model = Sequential([L.Dense(16, activation="relu", input_shape=(8,)),
                        L.Dense(2)])
    est = OrcaEstimator.from_keras(model, loss="mse", optimizer="adam")
    snap0 = _tm.snapshot().get("zoo_train_grad_norm", {}).get(
        "samples", {}).get("", {"count": 0})["count"]
    est.fit((x, y), epochs=1, batch_size=32, grad_accum_steps=2,
            update_sharding=True)
    stats = est.train_stats()
    n = stats.get("zoo_train_grad_norm", {}).get(
        "samples", {}).get("", {"count": 0})["count"]
    assert n >= snap0
    # the engine underneath really engaged the flat exchange
    eng = model.estimator
    assert isinstance(eng.train_state["opt_state"], upd.FlatUpdateState)
