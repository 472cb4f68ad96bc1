"""Fused-quantization pallas kernel tier tests (interpret mode on CPU).

Differential coverage: the fused int8 matmul/conv kernels
(ops/int8_fused.py) vs the unfused lax oracle (ops/int8.py) and vs f32;
the structural no-unfused-quantize-op invariant of the fused dispatch path
(the ``fused-int8-dispatch`` rule of the shared analysis engine that the
serving warm-up runs); and the serving-engine startup warmup that moved
int8 packing off the first request. All CPU-safe (pallas interpreter) —
these run in tier-1.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from analytics_zoo_tpu.ops import int8 as int8_ops
from analytics_zoo_tpu.ops import int8_fused
from analytics_zoo_tpu.ops.int8 import quantize_weight

pytestmark = pytest.mark.pallas


def _packed(w):
    return {k: jnp.asarray(v) for k, v in quantize_weight(w).items()}


@pytest.fixture()
def fused_interpret(monkeypatch):
    """Force the router onto the fused kernels (interpreter on CPU)."""
    monkeypatch.setenv("ZOO_INT8_FUSED", "interpret")


# ------------------------------------------------------------ matmul numerics


def test_fused_matmul_matches_unfused_and_f32(np_rng):
    x = (np_rng.normal(size=(16, 96)) * 3).astype(np.float32)
    w = np_rng.normal(size=(96, 48)).astype(np.float32)
    packed = _packed(w)
    ref = np.asarray(int8_ops.int8_matmul_unfused(jnp.asarray(x), packed))
    fused = int8_fused.int8_matmul_fused(
        jnp.asarray(x), packed, block_m=8, block_n=16, block_k=32,
        interpret=True)
    assert fused is not None and fused.shape == (16, 48)
    f32 = x @ w
    scale = np.max(np.abs(f32))
    # int8 quantization error bound vs exact f32 (per-K-tile scales are a
    # FINER granularity than the unfused per-row scheme, so the fused error
    # may differ from — but not exceed the class of — the unfused one)
    assert np.max(np.abs(np.asarray(fused) - f32)) / scale < 0.03
    assert np.max(np.abs(ref - f32)) / scale < 0.03
    # and the two int8 schemes agree with each other to quant-error scale
    assert np.max(np.abs(np.asarray(fused) - ref)) / scale < 0.03


def test_fused_matmul_bf16_activation(np_rng):
    x = np_rng.normal(size=(8, 64)).astype(np.float32)
    w = np_rng.normal(size=(64, 32)).astype(np.float32)
    packed = _packed(w)
    y = int8_fused.int8_matmul_fused(
        jnp.asarray(x, jnp.bfloat16), packed, block_m=8, block_n=16,
        block_k=32, out_dtype=jnp.bfloat16, interpret=True)
    assert y.dtype == jnp.bfloat16
    f32 = x @ w
    assert (np.max(np.abs(np.asarray(y, np.float32) - f32))
            / np.max(np.abs(f32)) < 0.05)


def test_fused_matmul_ragged_and_empty_batch(np_rng):
    """Shape-bucket edges: M smaller than a block (zero-pad rows) and the
    empty batch both go through without touching the lax fallback."""
    w = np_rng.normal(size=(64, 32)).astype(np.float32)
    packed = _packed(w)
    x = np_rng.normal(size=(3, 64)).astype(np.float32)
    y = int8_fused.int8_matmul_fused(
        jnp.asarray(x), packed, block_m=8, block_n=16, block_k=32,
        interpret=True)
    full = int8_fused.int8_matmul_fused(
        jnp.asarray(np.concatenate([x, np.zeros((5, 64), np.float32)])),
        packed, block_m=8, block_n=16, block_k=32, interpret=True)
    np.testing.assert_allclose(np.asarray(y), np.asarray(full)[:3],
                               rtol=0, atol=1e-5)
    empty = int8_fused.int8_matmul_fused(
        jnp.zeros((0, 64), jnp.float32), packed, interpret=True)
    assert empty.shape == (0, 32)


def test_fused_matmul_3d_leading_dims(np_rng):
    x = np_rng.normal(size=(2, 4, 64)).astype(np.float32)
    w = np_rng.normal(size=(64, 16)).astype(np.float32)
    packed = _packed(w)
    y = int8_fused.int8_matmul_fused(
        jnp.asarray(x), packed, block_m=8, block_n=16, block_k=32,
        interpret=True)
    assert y.shape == (2, 4, 16)
    f32 = x @ w
    assert np.max(np.abs(np.asarray(y) - f32)) / np.max(np.abs(f32)) < 0.03


def test_router_sends_untileable_to_lax(fused_interpret, np_rng):
    """K that no power-of-two tile divides → int8_matmul routes the shape to
    the lax path (identical results, no crash)."""
    x = np_rng.normal(size=(4, 33)).astype(np.float32)
    w = np_rng.normal(size=(33, 7)).astype(np.float32)
    packed = _packed(w)
    y = int8_ops.int8_matmul(jnp.asarray(x), packed)
    ref = int8_ops.int8_matmul_unfused(jnp.asarray(x), packed)
    np.testing.assert_allclose(np.asarray(y), np.asarray(ref), atol=1e-6)


def test_router_disabled_by_env(monkeypatch, np_rng):
    monkeypatch.setenv("ZOO_INT8_FUSED", "0")
    assert int8_fused.fused_mode() == "off"
    monkeypatch.setenv("ZOO_INT8_FUSED", "interpret")
    assert int8_fused.fused_mode() == "interpret"
    monkeypatch.delenv("ZOO_INT8_FUSED")
    # default on CPU: lax path (an interpreted kernel is not a speedup)
    assert int8_fused.fused_mode() == "off"


# -------------------------------------------------------------- conv numerics


@pytest.mark.parametrize("padding", ["VALID", "SAME"])
def test_fused_conv_matches_unfused_per_pixel(padding, np_rng):
    x = np_rng.normal(size=(2, 9, 9, 16)).astype(np.float32)
    w = np_rng.normal(size=(3, 3, 16, 24)).astype(np.float32)
    packed = _packed(w)
    ref = int8_ops.int8_conv2d_unfused(jnp.asarray(x), packed,
                                       strides=(1, 1), padding=padding)
    fused = int8_fused.int8_conv2d_fused(jnp.asarray(x), packed,
                                         strides=(1, 1), padding=padding,
                                         interpret=True)
    # same per-pixel scale scheme tap-for-tap: bit-near (f32 assoc. only)
    np.testing.assert_allclose(np.asarray(fused), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)


def test_fused_kernels_name_the_shape_they_cannot_run(np_rng):
    """A kernel that was selected and cannot run raises; the router asks
    ``conv_supported`` / ``resolve_blocks`` first and never gets here."""
    x = np_rng.normal(size=(1, 8, 8, 8)).astype(np.float32)
    packed = _packed(np_rng.normal(size=(3, 3, 8, 8)).astype(np.float32))
    assert not int8_fused.conv_supported((2, 2), (1, 1))
    with pytest.raises(ValueError, match=r"strides=\(2, 2\)"):
        int8_fused.int8_conv2d_fused(
            jnp.asarray(x), packed, strides=(2, 2), padding="VALID",
            interpret=True)
    w = _packed(np_rng.normal(size=(33, 7)).astype(np.float32))
    with pytest.raises(ValueError, match=r"\(4, 33\)"):
        int8_fused.int8_matmul_fused(jnp.zeros((4, 33), jnp.float32), w,
                                     interpret=True)


@pytest.mark.parametrize("strides,dilation", [((1, 1), (1, 1)),
                                              ((2, 2), (1, 1)),
                                              ((1, 1), (2, 2))])
def test_int8_conv2d_accuracy_vs_f32(strides, dilation, np_rng):
    """Satellite: per-pixel activation scales track f32 conv within int8
    quant error — including strided/dilated variants (lax fallback)."""
    x = np_rng.normal(size=(2, 12, 12, 8)).astype(np.float32)
    w = np_rng.normal(size=(3, 3, 8, 16)).astype(np.float32)
    packed = _packed(w)
    got = int8_ops.int8_conv2d(jnp.asarray(x), packed, strides=strides,
                               padding="SAME", dilation=dilation)
    want = jax.lax.conv_general_dilated(
        jnp.asarray(x), jnp.asarray(w), strides, "SAME",
        rhs_dilation=dilation, dimension_numbers=("NHWC", "HWIO", "NHWC"))
    assert got.shape == want.shape
    rel = (np.max(np.abs(np.asarray(got) - np.asarray(want)))
           / np.max(np.abs(np.asarray(want))))
    assert rel < 0.03, f"int8 conv rel err {rel} vs f32"


def test_per_pixel_scales_beat_per_image_on_hdr_input(np_rng):
    """The regression the granularity fix targets: one very bright pixel
    used to blow up EVERY pixel's quantization step (per-image abs-max).
    Per-pixel scales keep the rest of the image accurate."""
    x = np_rng.normal(size=(1, 8, 8, 8)).astype(np.float32)
    x[0, 0, 0, 0] = 500.0                      # high-dynamic-range outlier
    w = np_rng.normal(size=(3, 3, 8, 8)).astype(np.float32)
    packed = _packed(w)
    want = np.asarray(jax.lax.conv_general_dilated(
        jnp.asarray(x), jnp.asarray(w), (1, 1), "VALID",
        dimension_numbers=("NHWC", "HWIO", "NHWC")))

    # the old per-image scheme, inline for comparison
    amax = np.max(np.abs(x))
    s_img = max(amax, 1e-12) / 127.0
    xq = np.clip(np.round(x / s_img), -127, 127).astype(np.int8)
    per_image = np.asarray(jax.lax.conv_general_dilated(
        jnp.asarray(xq), packed["q"], (1, 1), "VALID",
        preferred_element_type=jnp.int32,
        dimension_numbers=("NHWC", "HWIO", "NHWC"))
    ).astype(np.float32) * s_img * np.asarray(packed["scale"]).reshape(-1)

    per_pixel = np.asarray(int8_ops.int8_conv2d_unfused(
        jnp.asarray(x), packed, strides=(1, 1), padding="VALID"))
    # compare away from the outlier's receptive field
    sl = (0, slice(3, None), slice(3, None))
    err_pix = np.max(np.abs(per_pixel[sl] - want[sl]))
    err_img = np.max(np.abs(per_image[sl] - want[sl]))
    assert err_pix < err_img / 5, (
        f"per-pixel {err_pix} not ≪ per-image {err_img}")


# ------------------------------------------------------- layer + model routes


def _fitted_mlp(np_rng, hidden=64, features=32, classes=8):
    from analytics_zoo_tpu.nn import Sequential
    from analytics_zoo_tpu.nn import layers as L

    m = Sequential([
        L.Dense(hidden, activation="relu", input_shape=(features,)),
        L.Dense(hidden, activation="relu"),
        L.Dense(classes, activation="softmax"),
    ])
    m.compile(optimizer="sgd", loss="mse")
    x = np_rng.normal(size=(32, features)).astype(np.float32)
    m.fit(x, np.zeros((32, classes), np.float32), batch_size=16, nb_epoch=1)
    return m


def test_quantized_model_fused_vs_lax_paths_agree(zoo_ctx, fused_interpret,
                                                  np_rng, monkeypatch):
    from analytics_zoo_tpu.inference import InferenceModel

    model = _fitted_mlp(np_rng)
    im = InferenceModel(max_batch_size=16).load(model)
    im.quantize_int8(min_elements=64)
    x = np_rng.normal(size=(8, 32)).astype(np.float32)
    fused_out = im.predict(x)
    monkeypatch.setenv("ZOO_INT8_FUSED", "0")
    im._compiled.clear()
    lax_out = im.predict(x)
    np.testing.assert_allclose(fused_out, lax_out, rtol=0.05, atol=0.01)
    assert float((fused_out.argmax(-1) == lax_out.argmax(-1)).mean()) == 1.0


def test_fused_dispatch_structure_invariants(zoo_ctx, fused_interpret,
                                             np_rng):
    """The ``fused-int8-dispatch`` rule the serving quick gate runs: with
    the fused tier on, the quantized dispatch path has pallas kernels and NO
    standalone quantize ops or int8 HBM intermediates (zero findings); with
    it off, the unfused ops are detected as findings (the rule is
    falsifiable)."""
    from analytics_zoo_tpu.analysis.rules.fused_int8 import (
        fused_dispatch_report)
    from analytics_zoo_tpu.inference import InferenceModel

    im = InferenceModel(max_batch_size=16).load(_fitted_mlp(np_rng))
    im.quantize_int8(min_elements=64)
    x = jnp.asarray(np_rng.normal(size=(8, 32)).astype(np.float32))
    st = fused_dispatch_report(im, x)
    assert st["fused_invariants_hold"], st
    assert st["findings"] == []
    assert st["pallas_calls"] == 3          # one per quantized Dense
    os.environ["ZOO_INT8_FUSED"] = "0"
    try:
        st_off = fused_dispatch_report(im, x)
    finally:
        os.environ["ZOO_INT8_FUSED"] = "interpret"
    assert not st_off["fused_invariants_hold"]
    assert st_off["quantize_ops_outside_kernels"] > 0
    assert st_off["int8_intermediates_outside_kernels"] > 0
    assert {f["rule"] for f in st_off["findings"]} == {"fused-int8-dispatch"}


# -------------------------------------------------------- engine warmup path


def test_engine_start_owns_quantize_cost(zoo_ctx, np_rng):
    """Satellite: int8 packing happens at engine warmup, not construction
    and not the first request; the cost is visible in stats()."""
    from analytics_zoo_tpu.inference import InferenceModel
    from analytics_zoo_tpu.serving import ServingConfig
    from analytics_zoo_tpu.serving.engine import ClusterServing

    im = InferenceModel(max_batch_size=8).load(_fitted_mlp(np_rng))
    cs = ClusterServing(model=im,
                        config=ServingConfig(int8=True, warmup_shape=(32,)))
    assert not im.is_quantized           # construction stays cheap
    cs._warm_model()                     # what start() runs before threads
    assert im.is_quantized
    stats = cs.stats()
    assert stats["quantize_seconds"] > 0
    # the warmup predict compiled the bucket ladder: first real request is
    # a cache hit, not a compile
    compiles_before = im.compile_stats()["compiles"]
    im.predict(np_rng.normal(size=(4, 32)).astype(np.float32))
    assert im.compile_stats()["compiles"] == compiles_before
