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
    flash on TPU from 2k tokens; when a backward follows at every multiple of
    512, if batch x heads make the (B, H, T, T) scores 2**26 elements or more;
    full elsewhere."""
    import analytics_zoo_tpu.ops.attention as A

    monkeypatch.setattr(A.jax, "default_backend", lambda: "tpu")
    assert (A.FLASH_FROM_TOKENS, A.FLASH_BACKWARD_TILE,
            A.FLASH_BACKWARD_SCORES) == (2048, 512, 2 ** 26)
    for backward in (False, True):
        assert A.prefer_flash_single_device(2048, backward)
        assert A.prefer_flash_single_device(65536, backward)
        assert not A.prefer_flash_single_device(256, backward, 4096)
    assert not A.prefer_flash_single_device(1024, False, 64)
    assert A.prefer_flash_single_device(1024, True, 64)
    assert not A.prefer_flash_single_device(1024, True, 32)
    assert not A.prefer_flash_single_device(1024, True)
    assert A.prefer_flash_single_device(1536, True, 32)
    assert A.prefer_flash_single_device(512, True, 256)
    assert not A.prefer_flash_single_device(512, True, 128)
    assert not A.prefer_flash_single_device(512, False, 256)
    assert not A.prefer_flash_single_device(768, True, 4096)    # tiles of 256
    assert not A.prefer_flash_single_device(896, True, 4096)    # tiles of 128
    monkeypatch.setattr(A.jax, "default_backend", lambda: "cpu")
    assert not A.prefer_flash_single_device(65536)
    assert not A.prefer_flash_single_device(65536, True, 64)


def _one_device_mesh():
    import jax.sharding as shd

    devs = np.array(jax.devices()[:1]).reshape(1, 1, 1, 1, 1, 1)
    return shd.Mesh(devs, ("dp", "fsdp", "tp", "sp", "pp", "ep"))


def _routes():
    """``zoo_attention_route_total`` as {(route, backward): count}."""
    from analytics_zoo_tpu.ops.attention import _ROUTES

    return {labels: child.value() for labels, child in _ROUTES.children()}


def _routes_since(before):
    return {k: n - before.get(k, 0) for k, n in _routes().items()
            if n != before.get(k, 0)}


#: (tokens, batch, backend, route forward alone, route when a backward
#: follows) at 8 heads; 1,100 is a length no flash tile divides
ROUTES = [(1024, 8, "tpu", "full", "flash"), (1024, 4, "tpu", "full", "full"),
          (2048, 1, "tpu", "flash", "flash"), (1, 8, "tpu", "full", "full"),
          (1100, 8, "tpu", "full", "full"), (512, 32, "tpu", "full", "flash"),
          (256, 128, "tpu", "full", "full"), (768, 128, "tpu", "full", "full"),
          (1024, 8, "cpu", "full", "full"),
          (4096, 1, "cpu", "full", "full")]


@pytest.mark.parametrize("t,batch,backend,forward,training", ROUTES,
                         ids=[f"{r[2]}-{r[0]}x{r[1]}" for r in ROUTES])
def test_auto_route_by_whether_a_backward_follows(monkeypatch, t, batch,
                                                  backend, forward, training):
    """The rule itself, ``MultiHeadAttention`` with no mesh and
    ``sharded_attention`` at ``sp == 1`` resolve alike, and each traced call
    is counted under the route it took."""
    import analytics_zoo_tpu.ops.attention as A
    from analytics_zoo_tpu.nn.layers.attention import MultiHeadAttention

    monkeypatch.setattr(A.jax, "default_backend", lambda: backend)
    mha = MultiHeadAttention(128, 8, causal=True, attn_strategy="auto")
    monkeypatch.setattr(mha, "_mesh", lambda: None)
    params = jax.eval_shape(
        lambda: mha.build(jax.random.PRNGKey(0), (None, t, 128))[0])
    x = jax.ShapeDtypeStruct((batch, t, 128), jnp.float32)
    qkv = jax.ShapeDtypeStruct((batch, t, 8, 16), jnp.float32)
    mesh = _one_device_mesh()
    for backward, want in ((False, forward), (True, training)):
        flash = want == "flash"
        assert A.prefer_flash_single_device(t, backward, batch * 8) is flash
        assert mha._flash_single_device(t, backward, batch * 8) is flash
        key = (want, "1" if backward else "0")
        before = _routes()
        jax.eval_shape(lambda p, a: mha.apply(p, {}, a, training=backward),
                       params, x)
        assert _routes_since(before) == {key: 1}
        before = _routes()
        jax.eval_shape(lambda q: A.sharded_attention(
            q, q, q, mesh, strategy="auto", causal=True, backward=backward),
            qkv)
        assert _routes_since(before) == {key: 1}


def test_auto_route_counts_the_rows_of_one_device(monkeypatch):
    """Under a dp mesh the rule sees a device's share of the batch, and
    inside a ``shard_map`` over dp (the ZeRO-1 flat path) the batch it is
    handed is that share already."""
    import jax.sharding as shd
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    import analytics_zoo_tpu.ops.attention as A

    if len(jax.devices()) < 4:
        pytest.skip("needs the 8-device CPU mesh")
    monkeypatch.setattr(A.jax, "default_backend", lambda: "tpu")
    devs = np.array(jax.devices()[:4]).reshape(4, 1, 1, 1, 1, 1)
    mesh = shd.Mesh(devs, ("dp", "fsdp", "tp", "sp", "pp", "ep"))

    def attend(q):
        return A.sharded_attention(q, q, q, mesh, strategy="auto",
                                   causal=True, backward=True)

    def routed(fn, batch):
        before = _routes()
        jax.eval_shape(fn, jax.ShapeDtypeStruct((batch, 1024, 8, 16),
                                                jnp.float32))
        (key, _), = _routes_since(before).items()
        return key

    assert routed(attend, 32) == ("flash", "1")     # 8 rows a device
    assert routed(attend, 16) == ("full", "1")      # 4 rows a device
    inside = shard_map(attend, mesh=mesh, in_specs=P("dp"), out_specs=P("dp"),
                       check_vma=False)
    assert routed(inside, 16) == ("full", "1")      # 4 rows a device
    assert routed(inside, 32) == ("flash", "1")     # 8 rows a device


@pytest.mark.parametrize("strategy", ["full", "flash"])
def test_explicit_strategy_ignores_the_backward(monkeypatch, strategy):
    """``attn_strategy="full"`` and ``"flash"`` mean what they say at every
    length, training or not."""
    import analytics_zoo_tpu.ops.attention as A
    from analytics_zoo_tpu.nn.layers.attention import MultiHeadAttention

    monkeypatch.setattr(A.jax, "default_backend", lambda: "tpu")
    mha = MultiHeadAttention(128, 2, causal=True, attn_strategy=strategy)
    for t in (512, 1024, 2048):
        for training in (False, True):
            assert mha._flash_single_device(t, training) is (
                strategy == "flash")


def test_training_step_counts_one_flash_route_a_block(monkeypatch):
    """Tracing a two-block ``TransformerLM`` training step at 1,024 tokens on
    a TPU backend takes the kernel in both blocks; ``apply`` without
    ``training`` takes XLA full attention."""
    import analytics_zoo_tpu.ops.attention as A
    from analytics_zoo_tpu.common import reset_zoo_context
    from analytics_zoo_tpu.models.transformer import TransformerLM, lm_loss

    reset_zoo_context()
    monkeypatch.setattr(A.jax, "default_backend", lambda: "tpu")
    model = TransformerLM(vocab=64, hidden_size=128, n_block=2, n_head=8,
                          seq_len=1024)
    params = jax.eval_shape(lambda: model.build(jax.random.PRNGKey(0))[0])
    ids = jax.ShapeDtypeStruct((8, 1024), jnp.int32)

    def loss(p, x, training):
        logits, _ = model.apply(p, {}, x, training=training)
        return lm_loss(x, logits)

    before = _routes()
    jax.eval_shape(jax.grad(lambda p, x: loss(p, x, True)), params, ids)
    assert _routes_since(before) == {("flash", "1"): 2}
    before = _routes()
    jax.eval_shape(lambda p, x: loss(p, x, False), params, ids)
    assert _routes_since(before) == {("full", "0"): 2}


def test_layer_gradients_flash_route_match_full_route():
    """``jax.grad`` of a ``MultiHeadAttention`` loss through the flash route
    (interpreted) against the ``full_attention`` route at the training cell's
    shape class: head 64, T a multiple of 128, causal."""
    from analytics_zoo_tpu.nn.layers.attention import MultiHeadAttention

    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.standard_normal((2, 256, 128)), jnp.float32)
    grads = {}
    for strategy in ("flash", "full"):
        mha = MultiHeadAttention(128, 2, causal=True, attn_strategy=strategy)
        mha._mesh = lambda: None
        params, _ = mha.build(jax.random.PRNGKey(1), (None, 256, 128))

        def loss(p, a):
            y, _ = mha.apply(p, {}, a, training=True)
            return jnp.sum(y ** 2)

        grads[strategy] = jax.grad(loss, argnums=(0, 1))(params, x)
    leaves = jax.tree_util.tree_leaves
    for got, want in zip(leaves(grads["flash"]), leaves(grads["full"])):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=5e-4, rtol=5e-4)


def test_flash_bf16_backward_no_farther_from_f32_than_full_bf16():
    """What a training call that ``auto`` now sends to the kernel gives up in
    exactness: nothing. At head 64, bf16, causal, the kernel's gradients are
    as close to the float32 oracle as XLA full attention's bf16 gradients
    (relative RMS 0.3-0.6% against 0.3-0.9%)."""
    rng = np.random.default_rng(5)
    q, k, v = (jnp.asarray(rng.standard_normal((2, 256, 2, 64)), jnp.float32)
               for _ in range(3))
    low = tuple(a.astype(jnp.bfloat16) for a in (q, k, v))

    def loss(attend):
        return lambda q, k, v: jnp.sum(attend(q, k, v).astype(jnp.float32)
                                       ** 2)

    flash = loss(lambda q, k, v: flash_attention(q, k, v, True, None, None,
                                                 True))
    full = loss(lambda q, k, v: full_attention(q, k, v, causal=True))
    want = jax.grad(full, argnums=(0, 1, 2))(q, k, v)
    got_flash = jax.grad(flash, argnums=(0, 1, 2))(*low)
    got_full = jax.grad(full, argnums=(0, 1, 2))(*low)

    def rel_rms(got, want):
        got, want = np.asarray(got, np.float32), np.asarray(want)
        return np.sqrt(np.mean((got - want) ** 2) / np.mean(want ** 2))

    for gf, gx, w in zip(got_flash, got_full, want):
        assert gf.dtype == jnp.bfloat16
        assert rel_rms(gf, w) < 1e-2
        assert rel_rms(gf, w) <= 1.1 * rel_rms(gx, w)
