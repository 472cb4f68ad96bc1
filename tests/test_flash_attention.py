"""Flash-attention kernel tests (interpret mode on CPU) — differential vs the
reference full attention, causal masking, gradients through the custom VJP."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from analytics_zoo_tpu.ops.attention import full_attention
from analytics_zoo_tpu.ops.flash_attention import (_flash_fwd,
                                                   flash_attention,
                                                   forward_tile_plan)


def make_qkv(b=2, t=64, h=2, d=16, seed=0, dtype=jnp.float32, t_k=None):
    rng = np.random.default_rng(seed)
    mk = lambda t: jnp.asarray(rng.standard_normal((b, t, h, d)), dtype)
    return mk(t), mk(t_k or t), mk(t_k or t)


def reference_lse(q, k, causal):
    """Natural-log log-sum-exp of the scaled scores, (B, H, T_q), f32."""
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) / np.sqrt(q.shape[-1])
    if causal:
        t_q, t_k = s.shape[-2:]
        s = jnp.where(jnp.arange(t_q)[:, None] >= jnp.arange(t_k)[None, :],
                      s, -jnp.inf)
    return jax.scipy.special.logsumexp(s, axis=-1)


#: (causal, T_q, T_k, block_q, block_k): the 16 x 16 cases keep the
#: statistics one lane wide; at 1,024 tokens every kind of tile (skipped,
#: wholly below the diagonal, crossed by it; first of its q tile or not)
#: occurs in one call, with block_q == block_k and != both ways; T_q != T_k
#: is the ring's off-diagonal step
TILINGS = [(False, 64, 64, 16, 16), (True, 64, 64, 16, 16),
           (True, 1024, 1024, 256, 256), (True, 1024, 1024, 128, 256),
           (True, 1024, 1024, 256, 128), (False, 256, 512, 128, 256)]
_tiling_id = lambda c: "{}-q{}-k{}-{}x{}".format(
    "causal" if c[0] else "dense", *c[1:])


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("tiling", TILINGS, ids=_tiling_id)
def test_flash_matches_full(tiling, dtype):
    """The output against ``full_attention`` and the saved ``lse`` against
    the reference log-sum-exp (natural log, whatever base a tile works in)."""
    causal, t_q, t_k, bq, bk = tiling
    if t_q == 1024:     # skipped and working tiles, or the case tests nothing
        assert all(forward_tile_plan(t_q, t_k, bq, bk, causal))
    q, k, v = make_qkv(b=1, t=t_q, t_k=t_k, seed=1)
    want = full_attention(q, k, v, causal=causal)
    want_lse = reference_lse(q, k, causal)
    q, k, v = (a.astype(dtype) for a in (q, k, v))
    tol = 2e-5 if dtype == jnp.float32 else 3e-2
    got = flash_attention(q, k, v, causal, bq, bk, True)
    assert got.dtype == dtype
    np.testing.assert_allclose(np.asarray(got, np.float32), np.asarray(want),
                               atol=tol, rtol=tol)
    _, lse = _flash_fwd(q, k, v, causal=causal, block_q=bq, block_k=bk,
                        interpret=True)
    assert lse.dtype == jnp.float32 and lse.shape == want_lse.shape
    np.testing.assert_allclose(np.asarray(lse), np.asarray(want_lse),
                               atol=tol, rtol=tol)


def test_flash_single_tile_and_uneven_block_clamp():
    # T smaller than the default block: blocks clamp to T
    q, k, v = make_qkv(t=32)
    got = flash_attention(q, k, v, False, 128, 128, True)
    want = full_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5,
                               rtol=2e-5)


def test_flash_non_divisible_names_the_shape():
    # T=50 does not tile by 16: a kernel that was selected and cannot run is
    # an error, never a silent switch to full attention
    from analytics_zoo_tpu.ops.flash_attention import tiles_ok

    q, k, v = make_qkv(t=50)
    assert not tiles_ok(50, 50, 16, 16) and tiles_ok(64, 64, 16, 16)
    # compiled, Mosaic wants each q tile's lse store 128-lane aligned
    assert not tiles_ok(64, 64, interpret=False)
    assert tiles_ok(2048, 2048, interpret=False)
    with pytest.raises(ValueError, match=r"\(16, 16\).*T_q=50"):
        flash_attention(q, k, v, False, 16, 16, True)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_gradients_match_full(causal):
    q, k, v = make_qkv(t=32, d=8)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal, 16, 16, True) ** 2)

    def loss_full(q, k, v):
        return jnp.sum(full_attention(q, k, v, causal=causal) ** 2)

    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_full = jax.grad(loss_full, argnums=(0, 1, 2))(q, k, v)
    for gf, gr in zip(g_flash, g_full):
        np.testing.assert_allclose(np.asarray(gf), np.asarray(gr),
                                   atol=5e-4, rtol=5e-4)


def test_flash_under_jit_and_bf16():
    q, k, v = make_qkv(t=32, dtype=jnp.bfloat16)

    @jax.jit
    def f(q, k, v):
        return flash_attention(q, k, v, True, 16, 16, True)

    got = f(q, k, v)
    want = full_attention(q.astype(jnp.float32), k.astype(jnp.float32),
                          v.astype(jnp.float32), causal=True)
    assert got.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(got, dtype=np.float32),
                               np.asarray(want), atol=3e-2, rtol=3e-2)


def test_flash_strategy_dispatch():
    import jax.sharding as shd

    from analytics_zoo_tpu.ops.attention import sharded_attention

    devs = np.array(jax.devices()[:1]).reshape(1, 1, 1, 1, 1, 1)
    mesh = shd.Mesh(devs, ("dp", "fsdp", "tp", "sp", "pp", "ep"))
    q, k, v = make_qkv(t=32)
    got = sharded_attention(q, k, v, mesh, strategy="flash", causal=True)
    want = full_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5,
                               rtol=2e-5)


def test_flash_strategy_keeps_dp_sharding():
    """Under a dp-sharded mesh the flash output must stay sharded over dp
    (regression: unwrapped pallas_call let GSPMD replicate the whole batch)."""
    import jax.sharding as shd
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    from analytics_zoo_tpu.ops.attention import sharded_attention

    if len(jax.devices()) < 4:
        pytest.skip("needs the 8-device CPU mesh")
    devs = np.array(jax.devices()[:4]).reshape(4, 1, 1, 1, 1, 1)
    mesh = shd.Mesh(devs, ("dp", "fsdp", "tp", "sp", "pp", "ep"))
    q, k, v = make_qkv(b=8, t=32)
    spec = P(("dp", "fsdp"), None, "tp", None)
    qs, ks, vs = (jax.device_put(a, NamedSharding(mesh, spec))
                  for a in (q, k, v))

    @jax.jit
    def f(q, k, v):
        return sharded_attention(q, k, v, mesh, strategy="flash", causal=True)

    got = f(qs, ks, vs)
    assert got.sharding.spec == spec
    want = full_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5,
                               rtol=2e-5)


def test_flash_sp_mesh_rejected():
    import jax.sharding as shd

    from analytics_zoo_tpu.ops.attention import sharded_attention

    if len(jax.devices()) < 2:
        pytest.skip("needs multiple devices")
    devs = np.array(jax.devices()[:2]).reshape(1, 1, 1, 2, 1, 1)
    mesh = shd.Mesh(devs, ("dp", "fsdp", "tp", "sp", "pp", "ep"))
    q, k, v = make_qkv(t=32)
    with pytest.raises(ValueError, match="single-device kernel"):
        sharded_attention(q, k, v, mesh, strategy="flash")


def _max_intermediate_elems(fn, *args):
    """Largest intermediate (in elements) appearing in fn's jaxpr, recursing
    into sub-jaxprs EXCEPT pallas kernels (whose refs are VMEM tiles)."""
    jaxpr = jax.make_jaxpr(fn)(*args)

    def walk(jx):
        mx = 0
        for eqn in jx.eqns:
            if eqn.primitive.name == "pallas_call":
                for v in eqn.outvars:
                    mx = max(mx, int(np.prod(v.aval.shape)))
                continue
            for v in eqn.outvars:
                if hasattr(v.aval, "shape"):
                    mx = max(mx, int(np.prod(v.aval.shape)))
            for sub in eqn.params.values():
                if hasattr(sub, "jaxpr"):
                    mx = max(mx, walk(sub.jaxpr))
        return mx

    return walk(jaxpr.jaxpr)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_backward_no_quadratic_memory(causal):
    # The tiled pallas backward must not materialize any (B,H,T,T) tensor:
    # the largest intermediate in the whole grad jaxpr stays O(B*T*H*D),
    # far below T^2 scale.
    b, t, h, d = 1, 512, 2, 16

    def loss(q, k, v):
        return flash_attention(q, k, v, causal, 128, 128, True).sum()

    q, k, v = make_qkv(b=b, t=t, h=h, d=d)
    biggest = _max_intermediate_elems(jax.grad(loss, argnums=(0, 1, 2)), q, k, v)
    assert biggest <= 4 * b * t * h * d, (
        f"O(T^2)-scale intermediate found: {biggest} elems "
        f"(T^2 scale would be {b*h*t*t})")


@pytest.mark.parametrize("tiling", [(False, 128, 128, 32, 32),
                                    (True, 128, 128, 32, 32)] + TILINGS[2:],
                         ids=_tiling_id)
def test_flash_tiled_backward_matches_oracle_multi_tile(tiling):
    # multiple q AND k tiles so cross-tile accumulation paths are exercised;
    # the backward recomputes P from the lse the forward saved
    causal, t_q, t_k, bq, bk = tiling
    q, k, v = make_qkv(b=1, t=t_q, t_k=t_k, h=2, d=16, seed=3)

    def f_flash(q, k, v):
        return (flash_attention(q, k, v, causal, bq, bk, True) ** 2).sum()

    def f_full(q, k, v):
        return (full_attention(q, k, v, causal=causal) ** 2).sum()

    g1 = jax.grad(f_flash, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(f_full, argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   atol=5e-4, rtol=5e-4)


def test_flash_gradients_bf16_close_to_f32_oracle():
    """The bf16 backward path (p/ds downcast before the grad dots — the MXU
    full-rate pattern) must stay close to the f32 full-attention oracle;
    forward-only bf16 coverage would miss a broken gradient downcast."""
    q, k, v = make_qkv(t=32, d=8)
    qb, kb, vb = (x.astype(jnp.bfloat16) for x in (q, k, v))

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, True, 16, 16, True)
                       .astype(jnp.float32) ** 2)

    def loss_full(q, k, v):
        return jnp.sum(full_attention(q, k, v, causal=True) ** 2)

    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(qb, kb, vb)
    g_full = jax.grad(loss_full, argnums=(0, 1, 2))(q, k, v)
    for gf, gr in zip(g_flash, g_full):
        assert gf.dtype == jnp.bfloat16
        np.testing.assert_allclose(np.asarray(gf, dtype=np.float32),
                                   np.asarray(gr), atol=3e-2, rtol=5e-2)


@pytest.mark.parametrize("bq,bk", [(64, 128), (256, 64), (128, 256)])
def test_flash_nondefault_tile_sizes_match_oracle(bq, bk):
    """A caller may pass tiles other than ``default_blocks``' — every tiling
    must stay numerically identical to the oracle, fwd and dq."""
    rng = np.random.default_rng(3)
    q, k, v = (jnp.asarray(rng.normal(size=(2, 256, 2, 16)), jnp.float32)
               for _ in range(3))
    ref = full_attention(q, k, v, causal=True)
    out = flash_attention(q, k, v, True, bq, bk)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-5)
    g = jax.grad(lambda a: jnp.sum(flash_attention(a, k, v, True, bq, bk) ** 2))(q)
    gr = jax.grad(lambda a: jnp.sum(full_attention(a, k, v, causal=True) ** 2))(q)
    np.testing.assert_allclose(np.asarray(g), np.asarray(gr),
                               rtol=2e-4, atol=2e-4)


def test_default_blocks_adaptive():
    """The largest power-of-two ≤512 dividing the sequence; unknown or
    non-dividing lengths get 128 (``tiles_ok`` then tells an auto router to
    stay on full attention)."""
    from analytics_zoo_tpu.ops.flash_attention import default_blocks

    assert default_blocks() == (128, 128)
    assert default_blocks(2048, 2048) == (512, 512)
    assert default_blocks(512, 1024) == (512, 512)
    assert default_blocks(256, 384) == (256, 128)   # 384 = 3·128
    assert default_blocks(16384, None) == (512, 128)
    assert default_blocks(300, 300) == (128, 128)   # non-dividing


def test_prefer_flash_single_device_rule(monkeypatch):
    """Shared auto-dispatch rule (layer mesh-less path == sharded sp==1 path):
    flash on TPU from 2k tokens, full elsewhere."""
    import analytics_zoo_tpu.ops.attention as A

    monkeypatch.setattr(A.jax, "default_backend", lambda: "tpu")
    assert A.prefer_flash_single_device(2048)
    assert A.prefer_flash_single_device(65536)
    assert not A.prefer_flash_single_device(512)
    monkeypatch.setattr(A.jax, "default_backend", lambda: "cpu")
    assert not A.prefer_flash_single_device(65536)
