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


def _dyadic_estimator(cfg, x, y, optimizer=None, mesh=None, D=8, H=16, O=4):
    """Linear two-Dense model whose initial weights are rounded to multiples
    of 1/8 (exact f32 arithmetic on the dyadic data)."""
    model = Sequential([L.Dense(H, use_bias=False, input_shape=(D,)),
                        L.Dense(O, use_bias=False)])
    est = Estimator(model, optimizer=optimizer or SGD(lr=0.5), loss="mse",
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
    mesh2 = Mesh(np.array(jax.devices()[:2]).reshape((2,) + (1,) * 5), AXES)
    x, y = _dyadic_data(B=32)
    ests = {}
    for sharded in (False, True):
        cfg = TrainConfig(shuffle=False, log_every_n_steps=10 ** 9,
                          update_sharding=sharded)
        est = _dyadic_estimator(cfg, x, y, optimizer=Adam(lr=1e-2),
                                mesh=mesh2)
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


def test_flat_opt_state_is_one_over_dp(zoo_ctx):
    """ZeRO-1 memory claim on the 8-way dp mesh: per-device optimizer-state
    bytes ≈ replicated/8 (within padding + replicated scalar count leaves)."""
    x, y = _dyadic_data(B=64, D=16)

    def opt_bytes(est):
        return sum(l.addressable_shards[0].data.nbytes
                   for l in jax.tree_util.tree_leaves(
                       est.train_state["opt_state"])
                   if hasattr(l, "addressable_shards"))

    base = dict(shuffle=False, log_every_n_steps=10 ** 9)
    e_r = _dyadic_estimator(TrainConfig(update_sharding=False, **base), x, y,
                            optimizer=Adam(1e-3), D=16, H=64, O=4)
    e_s = _dyadic_estimator(TrainConfig(update_sharding=True, **base), x, y,
                            optimizer=Adam(1e-3), D=16, H=64, O=4)
    assert e_s._update_mode() == "flat"
    r, s = opt_bytes(e_r), opt_bytes(e_s)
    assert s <= r / 8 * 1.35 + 512, (r, s)

    # and the sharded-update step costs no more device memory than the
    # replicated one (arguments + temporaries of the compiled step)
    def step_bytes(est):
        return memory_fields(est._make_train_step().lower(
            est.train_state, est._to_global((x, y))).compile()
        )["hbm_peak_bytes"]

    assert step_bytes(e_s) <= step_bytes(e_r) * 1.02


def test_one_gradient_collective_per_global_step(zoo_ctx):
    """The flat path's structural guarantee: compiled HLO has exactly one
    grad-sized reduce-scatter and collective counts do NOT grow with
    grad_accum_steps (the K-microbatch scan accumulates device-local grads)."""
    x, y = _dyadic_data(B=64)
    counts = {}
    for K in (1, 4):
        cfg = TrainConfig(shuffle=False, log_every_n_steps=10 ** 9,
                          update_sharding=True, grad_accum_steps=K)
        est = _dyadic_estimator(cfg, x, y)
        step = est._make_train_step()
        batch = est._to_global((x, y))
        compiled = step.lower(est.train_state, batch).compile()
        counts[K] = upd.collective_counts(compiled.as_text())
    assert counts[1] == counts[4], counts
    assert counts[4].get("reduce-scatter", 0) == 1, counts
    assert counts[4].get("all-gather", 0) >= 1, counts


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
    master = est.train_state["opt_state"].master
    assert master is not None and master.dtype == jnp.float32
    assert master.sharding.spec == P("dp")
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
def test_flat_mode_checkpoint_roundtrip(zoo_ctx, tmp_path):
    x, y = _dyadic_data(B=64)
    cfg = TrainConfig(shuffle=False, log_every_n_steps=10 ** 9,
                      update_sharding=True, checkpoint_dir=str(tmp_path))
    est = _dyadic_estimator(cfg, x, y, optimizer=Adam(1e-2))
    est.fit((x, y), batch_size=32, epochs=2)
    it = est.trainer_state.iteration
    # fresh estimator resumes from the flat-layout checkpoint
    cfg2 = TrainConfig(shuffle=False, log_every_n_steps=10 ** 9,
                       update_sharding=True, checkpoint_dir=str(tmp_path))
    model = Sequential([L.Dense(16, use_bias=False, input_shape=(8,)),
                        L.Dense(4, use_bias=False)])
    est2 = Estimator(model, optimizer=Adam(1e-2), loss="mse", config=cfg2)
    est2.load(str(tmp_path), sample_batch=(x, y))
    # the flat-layout state (FlatUpdateState + dp-sharded vectors) round-trips
    assert est2.trainer_state.iteration == it
    assert isinstance(est2.train_state["opt_state"], upd.FlatUpdateState)
    for a, b in zip(_leaves(est), _leaves(est2)):
        np.testing.assert_array_equal(a, b)
    est2.fit((x, y), batch_size=32, epochs=3)         # resumes, 1 more epoch
    assert est2.trainer_state.iteration == it + 2


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
    m = est2.train_state["opt_state"].master
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
