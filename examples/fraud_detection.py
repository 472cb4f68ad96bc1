"""Fraud detection — reference ``apps/fraud-detection`` (highly imbalanced
binary classification over transaction features; the notebook undersamples the
majority class and evaluates AUC/precision-recall)."""

from _common import SMOKE

import numpy as np

from analytics_zoo_tpu.nn import layers as L
from analytics_zoo_tpu.nn.topology import Sequential


def roc_auc(y_true, scores):
    """Exact AUC via the rank statistic (Mann-Whitney U)."""
    order = np.argsort(scores)
    ranks = np.empty(len(scores))
    ranks[order] = np.arange(1, len(scores) + 1)
    n_pos = int(y_true.sum())
    n_neg = len(y_true) - n_pos
    if n_pos == 0 or n_neg == 0:
        return float("nan")
    return (ranks[y_true == 1].sum() - n_pos * (n_pos + 1) / 2) / (
        n_pos * n_neg)


def synthetic_transactions(n, fraud_rate=0.02, dim=16, seed=0):
    rng = np.random.default_rng(seed)
    y = (rng.uniform(size=n) < fraud_rate).astype("int32")
    x = rng.standard_normal((n, dim)).astype("float32")
    # fraud shifts a few feature directions
    x[y == 1, :4] += 1.5
    x[y == 1, 4:8] -= 1.0
    return x, y


def undersample(x, y, ratio=2.0, seed=0):
    """Keep all positives + ratio× negatives (the notebook's rebalancing)."""
    rng = np.random.default_rng(seed)
    pos = np.flatnonzero(y == 1)
    neg = np.flatnonzero(y == 0)
    keep_neg = rng.choice(neg, size=min(len(neg), int(ratio * len(pos))),
                          replace=False)
    idx = rng.permutation(np.concatenate([pos, keep_neg]))
    return x[idx], y[idx]


def main():
    n = 2000 if SMOKE else 100_000
    x, y = synthetic_transactions(n)
    cut = int(0.8 * n)
    xb, yb = undersample(x[:cut], y[:cut])
    print(f"train: {len(xb)} rows after undersampling "
          f"({int(y[:cut].sum())} frauds of {cut})")

    model = Sequential([
        L.Dense(32, activation="relu", input_shape=(x.shape[1],)),
        L.Dropout(0.2),
        L.Dense(16, activation="relu"),
        L.Dense(2, activation="softmax"),
    ])
    model.compile(optimizer="adam", loss="sparse_categorical_crossentropy",
                  metrics=["accuracy"])
    model.fit(xb, yb, batch_size=64, nb_epoch=2 if SMOKE else 20)

    probs = np.asarray(model.predict(x[cut:], batch_size=512))[:, 1]
    auc = roc_auc(y[cut:], probs)
    top = np.argsort(-probs)[:100]
    precision_at_100 = float(y[cut:][top].mean())
    print(f"test AUC: {float(auc):.4f}; precision@100: {precision_at_100:.3f}")


if __name__ == "__main__":
    main()
