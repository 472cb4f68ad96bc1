"""Dogs-vs-cats transfer learning — reference ``apps/dogs-vs-cats``
(transfer-learning notebook) and the pytorch finetune examples
(``pyzoo/zoo/examples/pytorch`` mnist/resnet finetune): freeze a feature
extractor, train a new head, then unfreeze and fine-tune end-to-end.

Freezing is expressed the JAX way: ``jax.lax.stop_gradient`` via a Lambda in
the frozen phase — no per-layer ``trainable`` flags to mutate."""

import sys

from _common import SMOKE

import jax
import numpy as np

from analytics_zoo_tpu.nn import layers as L
from analytics_zoo_tpu.nn.topology import Sequential


def synthetic_pets(n, size, seed=0):
    """Dogs: warm blobs low in the frame. Cats: cool blobs high in the frame."""
    rng = np.random.default_rng(seed)
    y = rng.integers(0, 2, n).astype("int32")
    x = rng.uniform(0, 0.3, (n, size, size, 3)).astype("float32")
    for i, c in enumerate(y):
        r0 = size // 2 if c else size // 8
        x[i, r0:r0 + size // 3, size // 4:3 * size // 4, 0 if c else 2] = 0.9
    return x, y


def feature_extractor(size):
    return [
        L.InputLayer((size, size, 3)),
        L.Convolution2D(16, 3, 3, border_mode="same", activation="relu"),
        L.MaxPooling2D((2, 2)),
        L.Convolution2D(32, 3, 3, border_mode="same", activation="relu"),
        L.GlobalAveragePooling2D(),
    ]


def main():
    size = 32 if SMOKE else 96
    n = 96 if SMOKE else 2000
    data_dir = sys.argv[1] if len(sys.argv) > 1 else None
    if data_dir:
        from analytics_zoo_tpu.data.image import ImageResize, ImageSet

        iset = ImageSet.read(data_dir, with_label=True) \
            .transform(ImageResize(size, size))
        x, y = iset.to_arrays()
        x = x.astype("float32") / 255.0
        y = y.astype("int32")
    else:
        x, y = synthetic_pets(n, size)
    cut = int(0.8 * len(x))

    # phase 1: frozen features, train the head only
    feats = feature_extractor(size)
    frozen = Sequential(feats + [
        L.Lambda(jax.lax.stop_gradient),
        L.Dense(2, activation="softmax"),
    ])
    frozen.compile(optimizer="adam", loss="sparse_categorical_crossentropy",
                   metrics=["accuracy"])
    frozen.fit(x[:cut], y[:cut], batch_size=16, nb_epoch=2 if SMOKE else 8)
    print("frozen-phase eval:", frozen.evaluate(x[cut:], y[cut:]))

    # phase 2: unfreeze — same layers minus the stop_gradient, weights donated
    full = Sequential(feats + [L.Dense(2, activation="softmax",
                                       name="head2")])
    full.compile(optimizer="adam", loss="sparse_categorical_crossentropy",
                 metrics=["accuracy"])
    trained = frozen.estimator.train_state["params"]
    donated = {full.slot(l): trained[frozen.slot(l)]
               for l in feats if frozen.slot(l) in trained}
    full.set_initial_weights(donated, partial=True)  # head2 keeps fresh init
    full.fit(x[:cut], y[:cut], batch_size=16, nb_epoch=2 if SMOKE else 8)
    print("finetuned eval:", full.evaluate(x[cut:], y[cut:]))


if __name__ == "__main__":
    main()
