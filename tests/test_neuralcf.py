"""NeuralCF end-to-end: the north-star workload on the 8-device mesh.

Mirrors /root/reference/pyzoo/test/zoo/models/recommendation/test_neuralcf.py:29-80:
forward/backward shapes, save/load round-trip, predict_user_item_pair /
recommend_for_user, and a real compile→fit integration run.
"""

import jax
import numpy as np
import pytest

from analytics_zoo_tpu.data.datasets import (leave_one_out_eval_sets,
                                             synthetic_movielens,
                                             train_test_split_by_user)
from analytics_zoo_tpu.models.recommendation import NeuralCF
from analytics_zoo_tpu.nn.metrics import HitRate
from analytics_zoo_tpu.nn.optimizers import Adam


@pytest.fixture()
def small_ncf(zoo_ctx):
    model = NeuralCF(user_count=50, item_count=30, class_num=5,
                     user_embed=8, item_embed=8, hidden_layers=(16, 8),
                     mf_embed=8)
    model.compile(optimizer=Adam(lr=0.01), loss="sparse_categorical_crossentropy",
                  metrics=["accuracy"])
    return model


def test_forward_shape(small_ncf):
    params, state = small_ncf.build(jax.random.PRNGKey(0))
    pairs = np.array([[1, 2], [3, 4], [49, 29]], dtype="int32")
    y, _ = small_ncf.apply(params, state, pairs)
    assert np.asarray(y).shape == (3, 5)
    np.testing.assert_allclose(np.asarray(y).sum(-1), 1.0, rtol=1e-4)


def test_no_mf_variant(zoo_ctx):
    model = NeuralCF(20, 10, 5, include_mf=False, hidden_layers=(8,))
    params, state = model.build(jax.random.PRNGKey(0))
    y, _ = model.apply(params, state, np.array([[1, 1]], dtype="int32"))
    assert np.asarray(y).shape == (1, 5)


def test_fit_and_recommend(small_ncf):
    pairs, ratings = synthetic_movielens(4000, n_users=50, n_items=30, seed=1)
    labels = (ratings - 1).astype("int32")  # 0-based classes
    (xtr, ytr), (xte, yte) = train_test_split_by_user(pairs, labels)
    small_ncf.fit(xtr, ytr, batch_size=256, nb_epoch=4)
    res = small_ncf.evaluate(xte, yte, batch_size=256)
    assert res["sparse_categorical_accuracy"] > 0.25  # 5 classes, latent structure

    preds = small_ncf.predict_user_item_pair(xte[:20])
    assert len(preds) == 20
    assert all(1 <= p.prediction <= 5 for p in preds)
    assert all(0.0 <= p.probability <= 1.0 for p in preds)

    # Recommender.scala:55 ranking: predicted rating desc, probability tiebreak
    recs = small_ncf.recommend_for_user(xte, max_items=3)
    by_user = {}
    for r in recs:
        by_user.setdefault(r.user_id, []).append((-r.prediction, -r.probability))
    for keys in by_user.values():
        assert len(keys) <= 3
        assert keys == sorted(keys)

    recs_i = small_ncf.recommend_for_item(xte, max_users=2)
    by_item = {}
    for r in recs_i:
        by_item.setdefault(r.item_id, []).append((-r.prediction, -r.probability))
    for keys in by_item.values():
        assert len(keys) <= 2
        assert keys == sorted(keys)


def test_hitrate_eval_layout(small_ncf):
    pairs, ratings = synthetic_movielens(3000, n_users=50, n_items=30, seed=2)
    small_ncf.fit(pairs, (ratings - 1).astype("int32"), batch_size=256, nb_epoch=2)
    eval_sets = leave_one_out_eval_sets(pairs, n_items=30, n_negatives=9,
                                        max_users=40)
    u, c, _ = eval_sets.shape
    flat = eval_sets.reshape(u * c, 2)
    probs = small_ncf.predict(flat, batch_size=512)
    classes = np.arange(1, probs.shape[-1] + 1, dtype="float32")
    scores = (probs * classes).sum(-1).reshape(u, c)
    m = HitRate(10)
    acc = m.update(m.init(), None, scores)
    hr = m.result(acc)
    assert 0.0 <= hr <= 1.0


def test_save_load_roundtrip(small_ncf, tmp_path):
    pairs, ratings = synthetic_movielens(1000, n_users=50, n_items=30, seed=3)
    small_ncf.fit(pairs, (ratings - 1).astype("int32"), batch_size=256, nb_epoch=1)
    probs_before = small_ncf.predict(pairs[:50])
    path = str(tmp_path / "ncf_bundle")
    small_ncf.save_model(path)

    loaded = NeuralCF.load_model(path)
    assert loaded.user_count == 50 and loaded.mf_embed == 8
    loaded.compile(optimizer="adam", loss="sparse_categorical_crossentropy")
    probs_after = loaded.predict(pairs[:50])
    np.testing.assert_allclose(probs_before, probs_after, rtol=1e-5, atol=1e-6)


def test_implicit_ncf_beats_random_ranking(zoo_ctx):
    """NCF-paper implicit protocol: on-device negative sampling + BCE lifts
    HR@10 well above the 0.10 random floor of the 1+99 candidate layout."""
    from analytics_zoo_tpu.common import TrainConfig
    from analytics_zoo_tpu.engine import Estimator
    from analytics_zoo_tpu.models.recommendation import (ImplicitNCF,
                                                         implicit_bce_loss)

    n_users, n_items = 300, 200
    pairs, _ = synthetic_movielens(30_000, n_users=n_users, n_items=n_items)
    ev = leave_one_out_eval_sets(pairs, n_items, n_negatives=99, max_users=200)
    # leave-one-out means LEAVE OUT: drop every held-out (user, positive) pair
    # from training so HR@10 measures ranking generalization, not memorization
    held = {(int(u), int(i)) for u, i in ev[:, 0]}
    mask = np.array([(int(u), int(i)) not in held for u, i in pairs])
    train = pairs[mask]
    model = ImplicitNCF(user_count=n_users, item_count=n_items, n_negatives=4,
                        user_embed=8, item_embed=8, hidden_layers=(16, 8),
                        mf_embed=8)
    # log (and so read the loss back) at every step: with no log point the
    # loop keeps several steps in flight, and XLA:CPU runs the 8 virtual
    # devices' executions on a pool as large as the host's cores, where a
    # later step can take the thread an earlier step's all-gather waits for
    # ("Expected 8 threads to join the rendezvous, but only 7 of them
    # arrived", abort after 40 s; seen on 8 cores whenever the executables
    # came out of the compile cache). One step in flight cannot deadlock
    est = Estimator(model, optimizer=Adam(lr=5e-3), loss=implicit_bce_loss,
                    mesh=zoo_ctx.mesh,
                    config=TrainConfig(log_every_n_steps=1))
    est.fit((train, np.zeros(len(train), "float32")), batch_size=2048, epochs=8)

    flat = ev.reshape(-1, 2).astype("int32")
    score = np.asarray(est.predict(flat, batch_size=4096)).reshape(
        ev.shape[0], ev.shape[1])
    rank = (score[:, 1:] > score[:, 0:1]).sum(axis=1) + 1
    hr10 = float((rank <= 10).mean())
    assert hr10 > 0.25, f"implicit HR@10 {hr10} not materially above random 0.10"


def test_implicit_ncf_training_block_shape(zoo_ctx):
    from analytics_zoo_tpu.models.recommendation import ImplicitNCF

    model = ImplicitNCF(user_count=20, item_count=30, n_negatives=3,
                        user_embed=4, item_embed=4, hidden_layers=(8,),
                        mf_embed=4)
    params, state = model.build(jax.random.PRNGKey(0))
    pos = np.array([[1, 2], [3, 4]], dtype="int32")
    block, _ = model.apply(params, state, pos, training=True,
                           rng=jax.random.PRNGKey(1))
    assert np.asarray(block).shape == (2, 4)  # [pos | 3 negatives]
    assert ((np.asarray(block) >= 0) & (np.asarray(block) <= 1)).all()
    # inference path: plain (B, 1) probabilities
    probs, _ = model.apply(params, state, pos)
    assert np.asarray(probs).shape == (2, 1)
