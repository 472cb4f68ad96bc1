"""NCF recommendation — the north-star workload
(apps/recommendation-ncf/ncf-explicit-feedback.ipynb parity): train NeuralCF on
MovieLens-1M (user, item) → rating, then evaluate leave-one-out HR@10 / NDCG
and per-user recs.

Real-data path: set ``ML1M_RATINGS=/path/to/ratings.dat`` (or pass it as
argv[1]) to train on the actual MovieLens-1M file; otherwise the
statistically-matched synthetic from ``data.datasets`` stands in with the same
pipeline end-to-end."""

import os
import sys

from _common import SMOKE

import numpy as np

from analytics_zoo_tpu.data.datasets import (ML1M_ITEMS, ML1M_USERS,
                                             leave_one_out_eval_sets,
                                             movielens_1m)
from analytics_zoo_tpu.models.recommendation import NeuralCF
from analytics_zoo_tpu.nn.optimizers import Adam


def main():
    path = (sys.argv[1] if len(sys.argv) > 1
            else os.environ.get("ML1M_RATINGS"))
    real = bool(path and os.path.exists(path))
    if path and not real:
        print(f"WARNING: {path!r} not found — using the synthetic stand-in")
    pairs, ratings = movielens_1m(
        path=path if real else None,
        n_ratings=20_000 if (SMOKE and not real) else None)
    n_users = int(pairs[:, 0].max())
    n_items = int(pairs[:, 1].max())
    print(f"dataset: {len(pairs)} ratings, {n_users} users, {n_items} items "
          f"({'real ' + path if real else 'synthetic stand-in'})")

    # leave-one-out protocol: negatives come from the ACTUAL catalog, and each
    # evaluated user's held-out positive (their last rating) is REMOVED from
    # the training pairs — otherwise the metric leaks
    eval_sets = leave_one_out_eval_sets(pairs, n_items, n_negatives=99,
                                        max_users=100 if SMOKE else 1000)
    users = pairs[:, 0]
    rev_first = np.unique(users[::-1], return_index=True)[1]
    last_row = len(users) - 1 - rev_first
    eval_users = set(int(u) for u in eval_sets[:, 0, 0])
    uniq = np.unique(users)
    drop = last_row[np.isin(uniq, list(eval_users))]
    mask = np.ones(len(users), dtype=bool)
    mask[drop] = False
    train_pairs = pairs[mask]
    train_labels = (ratings[mask] - 1).astype("int32")

    cut = int(0.95 * len(train_pairs))
    model = NeuralCF(user_count=max(n_users, ML1M_USERS),
                     item_count=max(n_items, ML1M_ITEMS), class_num=5,
                     user_embed=16, item_embed=16, hidden_layers=(32, 16),
                     mf_embed=16)
    model.compile(optimizer=Adam(lr=5e-3),
                  loss="sparse_categorical_crossentropy",
                  metrics=["accuracy"])
    model.fit(train_pairs[:cut], train_labels[:cut], batch_size=2048,
              nb_epoch=1 if SMOKE else 8,
              validation_data=(train_pairs[cut:], train_labels[cut:]))
    print("eval:", model.evaluate(train_pairs[cut:], train_labels[cut:],
                                  batch_size=4096))

    # leave-one-out HR@10: score = expected rating over the 5 classes
    flat = eval_sets.reshape(-1, 2).astype("int32")
    probs = np.asarray(model.predict(flat, batch_size=4096))
    score = probs @ np.arange(1, probs.shape[1] + 1, dtype=np.float32)
    score = score.reshape(eval_sets.shape[0], eval_sets.shape[1])
    rank = (score[:, 1:] > score[:, 0:1]).sum(axis=1) + 1
    print(f"HR@10: {float((rank <= 10).mean()):.4f}  "
          f"NDCG@10: {float(np.where(rank <= 10, 1 / np.log2(rank + 1), 0).mean()):.4f}")

    recs = model.recommend_for_user(train_pairs[cut:], max_items=3)
    print("top recommendations:", recs[:3])


if __name__ == "__main__":
    main()
