"""Parallelism tests on the faked 8-device mesh (SURVEY.md §7 stage 5 pattern):
ring/Ulysses attention vs full-attention oracle, tp/fsdp sharding rules, and the
full multi-axis training step (the driver's dryrun_multichip path).
"""

import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from analytics_zoo_tpu.ops.attention import full_attention, sharded_attention


@pytest.fixture(scope="module")
def mesh6():
    return Mesh(np.array(jax.devices()).reshape(2, 1, 1, 4, 1, 1),
                axis_names=("dp", "fsdp", "tp", "sp", "pp", "ep"))


@pytest.mark.parametrize("strategy", ["ring", "ulysses"])
@pytest.mark.parametrize("causal", [False, True])
def test_sequence_parallel_attention_matches_full(mesh6, strategy, causal):
    B, T, H, D = 4, 32, 4, 16
    rng = np.random.default_rng(0)
    q, k, v = (rng.normal(size=(B, T, H, D)).astype("float32") for _ in range(3))
    ref = full_attention(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                         causal=causal)
    spec = NamedSharding(mesh6, P(("dp", "fsdp"), "sp", "tp", None))
    qs, ks, vs = (jax.device_put(x, spec) for x in (q, k, v))
    out = jax.jit(lambda a, b, c: sharded_attention(
        a, b, c, mesh6, strategy=strategy, causal=causal))(qs, ks, vs)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-5)


def test_ring_attention_grad_matches_full(mesh6):
    B, T, H, D = 2, 16, 2, 8
    rng = np.random.default_rng(1)
    q, k, v = (rng.normal(size=(B, T, H, D)).astype("float32") for _ in range(3))

    def loss_ring(q, k, v):
        return jnp.sum(sharded_attention(q, k, v, mesh6, strategy="ring",
                                         causal=True) ** 2)

    def loss_full(q, k, v):
        return jnp.sum(full_attention(q, k, v, causal=True) ** 2)

    g_ring = jax.jit(jax.grad(loss_ring, argnums=(0, 1, 2)))(
        jnp.asarray(q), jnp.asarray(k), jnp.asarray(v))
    g_full = jax.grad(loss_full, argnums=(0, 1, 2))(
        jnp.asarray(q), jnp.asarray(k), jnp.asarray(v))
    for a, b in zip(g_ring, g_full):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-5)


def test_param_sharding_rules():
    from analytics_zoo_tpu.parallel import make_param_sharding

    mesh = Mesh(np.array(jax.devices()).reshape(2, 2, 2, 1, 1, 1),
                axis_names=("dp", "fsdp", "tp", "sp", "pp", "ep"))
    rule = make_param_sharding(mesh)

    class FakeKey:
        def __init__(self, key):
            self.key = key

    qkv = np.zeros((64, 3 * 64), dtype="float32")
    assert rule((FakeKey("block0"), FakeKey("attn"), FakeKey("qkv_kernel")),
                qkv) == P("fsdp", "tp")
    emb = np.zeros((100, 64), dtype="float32")
    assert rule((FakeKey("token_embeddings"),), emb) == P("tp", None)
    # non-divisible tp dim falls back to replicated on that axis
    odd = np.zeros((63, 64), dtype="float32")
    spec = rule((FakeKey("token_embeddings"),), odd)
    assert spec == P(None, None) or spec == P()
    bias = np.zeros((7,), dtype="float32")
    assert rule((FakeKey("block0"), FakeKey("qkv_bias")), bias) == P()


@pytest.mark.slow
def test_transformer_lm_trains_on_multi_axis_mesh(zoo_ctx, monkeypatch):
    """The full dryrun path: dp/fsdp/tp/sp sharded train step executes and the
    loss decreases over steps. GRAFT_DRYRUN_CHILD keeps it in-process (the
    driver-facing parent path re-execs a subprocess and is covered by the
    driver itself)."""
    spec = importlib.util.spec_from_file_location(
        "graft_entry", os.path.join(os.path.dirname(__file__), "..",
                                    "__graft_entry__.py"))
    ge = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(ge)
    monkeypatch.setenv("GRAFT_DRYRUN_CHILD", "1")
    ge.dryrun_multichip(8)


def test_transformer_lm_loss_decreases(zoo_ctx):
    from analytics_zoo_tpu.common import TrainConfig
    from analytics_zoo_tpu.engine import Estimator
    from analytics_zoo_tpu.models.transformer import TransformerLM, lm_loss
    from analytics_zoo_tpu.nn.optimizers import Adam

    model = TransformerLM(vocab=32, hidden_size=32, n_block=1, n_head=2,
                          seq_len=16, attn_strategy="full")
    est = Estimator(model, optimizer=Adam(lr=0.01), loss=lm_loss,
                    mesh=zoo_ctx.mesh)
    rng = np.random.default_rng(0)
    x = rng.integers(0, 32, size=(256, 16)).astype("int32")
    y = np.roll(x, -1, axis=1)  # learnable copy task
    est.fit((x, y), batch_size=64, epochs=1)
    first = est.trainer_state.last_loss
    est.fit((x, y), batch_size=64, epochs=6)
    assert est.trainer_state.last_loss < first


def _ring_local(mesh, use_flash, causal=True):
    import functools

    from analytics_zoo_tpu.ops.attention import ring_attention_local

    return shard_map(
        functools.partial(ring_attention_local, axis_name="sp", causal=causal,
                          use_flash=use_flash),
        mesh=mesh, in_specs=(P(None, "sp", None, None),) * 3,
        out_specs=P(None, "sp", None, None), check_vma=False)


@pytest.fixture(scope="module")
def mesh_sp8():
    return Mesh(np.array(jax.devices()).reshape(8), axis_names=("sp",))


@pytest.mark.parametrize("causal", [False, True])
def test_ring_flash_forced_matches_oracle_fwd_and_grad(mesh_sp8, causal):
    """VERDICT r3 #3: the pallas blockwise body (use_flash=True, interpret
    mode on CPU) must match the full-attention oracle — forward AND grads —
    not silently fall back to the jnp body."""
    B, T, H, D = 2, 64, 2, 16
    rng = np.random.default_rng(2)
    q, k, v = (jnp.asarray(rng.normal(size=(B, T, H, D)).astype("float32"))
               for _ in range(3))
    ref = full_attention(q, k, v, causal=causal)
    out = jax.jit(_ring_local(mesh_sp8, use_flash=True, causal=causal))(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-5)

    loss = lambda fn: lambda a, b, c: jnp.sum(fn(a, b, c) ** 2)
    g_ring = jax.jit(jax.grad(
        loss(_ring_local(mesh_sp8, use_flash=True, causal=causal)),
        argnums=(0, 1, 2)))(q, k, v)
    g_full = jax.grad(
        loss(lambda a, b, c: full_attention(a, b, c, causal=causal)),
        argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_ring, g_full):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-5)


def test_ring_flash_memory_is_linear_in_seq_not_quadratic(mesh_sp8):
    """The jnp ring body materializes (B,H,T_local,T_local) score blocks —
    temp memory grows ~4x per sequence doubling and a long-context run OOMs.
    The flash body is O(block) per step: temp grows ~2x (the O(T·D) operands),
    so sequences that would OOM the jnp body fit."""
    def temp_bytes(use_flash, t_local):
        x = jnp.zeros((1, 8 * t_local, 1, 64), jnp.float32)
        fn = jax.jit(_ring_local(mesh_sp8, use_flash=use_flash))
        return fn.lower(x, x, x).compile().memory_analysis().temp_size_in_bytes

    jnp_1k, jnp_2k = temp_bytes(False, 1024), temp_bytes(False, 2048)
    fl_1k, fl_2k = temp_bytes(True, 1024), temp_bytes(True, 2048)
    assert jnp_2k / jnp_1k > 3.0, (jnp_1k, jnp_2k)   # quadratic blowup
    assert fl_2k / fl_1k < 2.5, (fl_1k, fl_2k)       # linear in T
    assert jnp_2k > 4 * fl_2k, (jnp_2k, fl_2k)       # and already 4x smaller


def test_zigzag_ring_matches_oracle_fwd_and_grad(mesh6, monkeypatch):
    """Load-balanced causal ring (zigzag layout): device d holds chunks
    (d, 2n-1-d), so q_hi x k_lo is statically past and q_lo x k_hi statically
    future - per-step work equalizes at ~2 half-blocks per device. Must stay
    bitwise-comparable to the full-attention oracle."""
    monkeypatch.setenv("ZOO_FORCE_ZIGZAG", "1")   # off-TPU falls to ring
    B, T, H, D = 2, 64, 2, 16
    rng = np.random.default_rng(4)
    q, k, v = (jnp.asarray(rng.normal(size=(B, T, H, D)).astype("float32"))
               for _ in range(3))
    ref = full_attention(q, k, v, causal=True)
    out = jax.jit(lambda a, b, c: sharded_attention(
        a, b, c, mesh6, strategy="zigzag", causal=True))(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-5)

    loss = lambda fn: lambda a, b, c: jnp.sum(fn(a, b, c) ** 2)
    g_z = jax.jit(jax.grad(loss(lambda a, b, c: sharded_attention(
        a, b, c, mesh6, strategy="zigzag", causal=True)),
        argnums=(0, 1, 2)))(q, k, v)
    g_full = jax.grad(loss(lambda a, b, c: full_attention(a, b, c, causal=True)),
                      argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_z, g_full):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-4)


def test_zigzag_noncausal_falls_back_to_ring(mesh6):
    B, T, H, D = 2, 32, 2, 8
    rng = np.random.default_rng(5)
    q, k, v = (jnp.asarray(rng.normal(size=(B, T, H, D)).astype("float32"))
               for _ in range(3))
    ref = full_attention(q, k, v, causal=False)
    out = jax.jit(lambda a, b, c: sharded_attention(
        a, b, c, mesh6, strategy="zigzag", causal=False))(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-5)


def test_zigzag_permutation_validates_and_inverts():
    from analytics_zoo_tpu.ops.attention import zigzag_permutation

    with pytest.raises(ValueError, match="divisible"):
        zigzag_permutation(30, 4)
    perm = zigzag_permutation(32, 4)
    assert sorted(perm.tolist()) == list(range(32))
    # device 0's slice (first 8 entries) = chunks 0 and 7
    assert perm[:8].tolist() == [0, 1, 2, 3, 28, 29, 30, 31]


def test_zigzag_unsuitable_shapes_fall_back_to_ring(mesh6, monkeypatch):
    """Documented fallback: explicit strategy='zigzag' (and 'auto') must fall
    to ring when T doesn't divide by 2*sp or half-chunks don't tile —
    never raise at trace time."""
    monkeypatch.setenv("ZOO_FORCE_ZIGZAG", "1")
    B, T, H, D = 2, 40, 2, 8              # 40 % (2*4) = 0 but c=5 tiles fine;
    rng = np.random.default_rng(6)        # use T=36: 36 % 8 != 0 -> ring
    for T in (36, 40):
        q, k, v = (jnp.asarray(rng.normal(size=(B, T, H, D)).astype("f4"))
                   for _ in range(3))
        ref = full_attention(q, k, v, causal=True)
        for strat in ("zigzag", "auto"):
            out = jax.jit(lambda a, b, c_: sharded_attention(
                a, b, c_, mesh6, strategy=strat, causal=True))(q, k, v)
            np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                       rtol=2e-4, atol=2e-5)
