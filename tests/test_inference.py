"""Inference engine tests.

Mirrors the reference's inference specs (zoo/src/test/.../pipeline/inference/) —
load/predict correctness, the concurrency-bounded pool, int8 path, and
bundle loading.
"""

import threading

import numpy as np
import pytest

from analytics_zoo_tpu.inference import InferenceModel, InferenceSummary, timing
from analytics_zoo_tpu.inference.summary import reset_timing_stats, timing_stats
from analytics_zoo_tpu.nn import Sequential
from analytics_zoo_tpu.nn import layers as L


def _fitted_model(np_rng, in_dim=8, out_dim=3):
    model = Sequential([L.Dense(16, activation="relu", input_shape=(in_dim,)),
                        L.Dense(out_dim, activation="softmax")])
    model.compile(optimizer="adam", loss="categorical_crossentropy")
    x = np_rng.normal(size=(64, in_dim)).astype(np.float32)
    y = np.eye(out_dim, dtype=np.float32)[np_rng.integers(0, out_dim, 64)]
    model.fit(x, y, batch_size=16, nb_epoch=1)
    return model, x


def test_load_and_predict_matches_model(zoo_ctx, np_rng):
    model, x = _fitted_model(np_rng)
    im = InferenceModel(supported_concurrent_num=2, max_batch_size=32)
    im.load(model)
    got = im.predict(x)
    want = model.predict(x)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_ragged_batches_pad_and_slice(zoo_ctx, np_rng):
    model, x = _fitted_model(np_rng)
    im = InferenceModel(max_batch_size=16).load(model)
    for n in (1, 3, 16, 17, 50):
        out = im.predict(x[:n] if n <= len(x) else
                         np.tile(x, (2, 1))[:n])
        assert out.shape[0] == n
        # padded rows must not leak into real outputs
        np.testing.assert_allclose(out[:1], im.predict(x[:1]), rtol=1e-5)


def test_concurrent_predict_bounded(zoo_ctx, np_rng):
    model, x = _fitted_model(np_rng)
    im = InferenceModel(supported_concurrent_num=3, max_batch_size=32).load(model)
    errs = []

    def worker():
        try:
            for _ in range(5):
                out = im.predict(x[:8])
                assert out.shape == (8, 3)
        except Exception as e:  # pragma: no cover
            errs.append(e)

    threads = [threading.Thread(target=worker) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errs
    assert im.borrowed_peak <= 3  # semaphore bound respected


def test_int8_quantization_close_and_flagged(zoo_ctx, np_rng):
    model, x = _fitted_model(np_rng, in_dim=32)
    want = model.predict(x)
    im = InferenceModel().load(model)
    im.quantize_int8(min_elements=64)
    assert im.is_quantized
    got = im.predict(x)
    assert got.shape == want.shape
    # int8 weight quantization: outputs close but not identical
    assert np.max(np.abs(got - want)) < 0.05
    # softmax outputs still normalised
    np.testing.assert_allclose(got.sum(-1), 1.0, atol=1e-3)


def test_load_zoo_bundle(zoo_ctx, np_rng, tmp_path):
    from analytics_zoo_tpu.models.recommendation import NeuralCF

    ncf = NeuralCF(user_count=20, item_count=30, class_num=5)
    ncf.compile(optimizer="adam", loss="sparse_categorical_crossentropy")
    pairs = np.stack([np_rng.integers(1, 21, 64),
                      np_rng.integers(1, 31, 64)], axis=1).astype(np.int32)
    labels = np_rng.integers(0, 5, 64).astype(np.int32)
    ncf.fit(pairs, labels, batch_size=16, nb_epoch=1)
    want = ncf.predict(pairs)
    path = str(tmp_path / "ncf_bundle")
    ncf.save_model(path)

    im = InferenceModel().load_zoo(path)
    got = im.predict(pairs)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_warmup_compiles_ladder(zoo_ctx, np_rng):
    model, x = _fitted_model(np_rng)
    im = InferenceModel(max_batch_size=8).load(model)
    im.warm_up(x[:1])
    assert len(im._compiled) == 4  # buckets 1,2,4,8


def test_timing_and_summary(zoo_ctx, np_rng, tmp_path):
    reset_timing_stats()
    with timing("unit.block"):
        pass
    st = timing_stats()
    assert st["unit.block"]["count"] == 1

    model, x = _fitted_model(np_rng)
    summ = InferenceSummary(log_dir=str(tmp_path), name="svc")
    im = InferenceModel(summary=summ).load(model)
    im.predict(x[:4])
    im.predict(x[:4])
    snap = summ.snapshot()
    assert snap["records"] == 8 and snap["batches"] == 2
    assert snap["throughput"] > 0
    summ.close()


def test_predict_without_load_raises(zoo_ctx):
    with pytest.raises(RuntimeError, match="no model loaded"):
        InferenceModel().predict(np.zeros((1, 4), np.float32))


def test_int8_native_compute_packs_kernels(zoo_ctx, np_rng):
    """Native modules quantize to REAL int8 compute: the Dense kernels live as
    int8 in the params tree (not dequantized copies) and the layer forward
    takes the MXU int8 path (ops/int8.int8_matmul)."""
    model, x = _fitted_model(np_rng, in_dim=32)
    im = InferenceModel().load(model)
    im.quantize_int8(min_elements=64)
    kernels = [v["kernel"] for v in im._params.values()
               if isinstance(v, dict) and isinstance(v.get("kernel"), dict)]
    assert kernels, "no kernels packed"
    for k in kernels:
        assert np.asarray(k["q"]).dtype == np.int8
    out = im.predict(x[:16])
    assert np.isfinite(out).all()


def test_int8_conv2d_native_close_to_float(zoo_ctx, np_rng):
    from analytics_zoo_tpu.nn import Sequential
    from analytics_zoo_tpu.nn import layers as L

    model = Sequential([
        L.Convolution2D(16, 3, 3, border_mode="same", activation="relu",
                        input_shape=(8, 8, 3)),
        L.Flatten(),
        L.Dense(4, activation="softmax"),
    ])
    model.compile(optimizer="adam", loss="categorical_crossentropy")
    x = np_rng.normal(size=(32, 8, 8, 3)).astype("float32")
    y = np.eye(4, dtype="float32")[np_rng.integers(0, 4, 32)]
    model.fit(x, y, batch_size=16, nb_epoch=2)
    want = model.predict(x)
    im = InferenceModel().load(model)
    im.quantize_int8(min_elements=128)
    got = im.predict(x)
    # <0.1% classification disagreement is the reference's int8 bar
    # (wp-bigdl.md:192). On this toy undertrained net several samples sit on
    # sub-0.01 top-2 margins where argmax is a coin toss for ANY quantizer,
    # so demand identical argmax on every DECISIVE sample plus probs within
    # a bar 2.5x tighter than the old per-image scheme needed (the per-pixel
    # activation scales land ~0.004 max prob diff here)
    top2 = np.sort(want, axis=-1)
    decisive = (top2[:, -1] - top2[:, -2]) > 0.01
    assert decisive.sum() >= 16, "toy model degenerated to all-ties"
    assert (got.argmax(-1) == want.argmax(-1))[decisive].all()
    assert np.max(np.abs(got - want)) < 0.02


def test_int8_imported_graph_falls_back_to_weight_only(zoo_ctx, np_rng):
    w = np_rng.normal(size=(64, 8)).astype("float32") * 0.3

    def fn(p, s, x):
        import jax.numpy as jnp

        return jnp.asarray(x) @ p["w"]

    im = InferenceModel().load_fn(fn, params={"w": w})
    im.quantize_int8(min_elements=64)
    assert im.is_quantized
    x = np_rng.normal(size=(4, 64)).astype("float32")
    np.testing.assert_allclose(im.predict(x), x @ w, atol=0.05)


def test_device_apply_matches_predict_incl_int8(zoo_ctx, np_rng):
    """device_apply() is the public device-resident escape hatch (AOT export,
    the fused-dispatch rule, chip_smoke.py): it must expose exactly the predict
    computation, before AND after quantize_int8 rewires apply/params."""
    import jax.numpy as jnp

    from analytics_zoo_tpu.ops.int8 import is_quantized

    model, x = _fitted_model(np_rng)
    im = InferenceModel(max_batch_size=64).load(model)
    apply_fn, params, state = im.device_apply()
    got = np.asarray(apply_fn(params, state, jnp.asarray(x)))
    np.testing.assert_allclose(got, im.predict(x), rtol=1e-5, atol=1e-5)

    im.quantize_int8(min_elements=1)
    q_apply, q_params, q_state = im.device_apply()
    # really rewired: some leaf now carries the packed {'q','scale'} form
    import jax

    packed = jax.tree_util.tree_leaves(q_params, is_leaf=is_quantized)
    assert any(is_quantized(l) for l in packed)
    got_q = np.asarray(q_apply(q_params, q_state, jnp.asarray(x)))
    np.testing.assert_allclose(got_q, im.predict(x), rtol=1e-5, atol=1e-5)


def test_device_apply_requires_loaded_model(zoo_ctx):
    with pytest.raises(RuntimeError):
        InferenceModel().device_apply()
