"""Inception-v1 ImageNet-style training — reference
``zoo/.../examples/inception`` (ImageNet training) and
``pyzoo/zoo/examples/inception``. Trains the backbone-zoo inception_v1 with a
FeatureSet pipeline (per-host sharded, deterministic shuffle); pass an
imagenet-layout directory (class subdirs) to train on real files, otherwise a
synthetic stand-in dataset is generated."""

import sys

from _common import SMOKE

import numpy as np

from analytics_zoo_tpu.data import FeatureSet
from analytics_zoo_tpu.models.image.backbones import inception_v1


def synthetic_imagenet(n, size, n_classes, seed=0):
    rng = np.random.default_rng(seed)
    y = rng.integers(0, n_classes, n).astype("int32")
    x = rng.uniform(0, 0.25, (n, size, size, 3)).astype("float32")
    # each class gets a bright patch at a class-specific location
    for i, c in enumerate(y):
        r = (c * 7) % (size - 8)
        x[i, r:r + 8, r:r + 8, :] = 0.9
    return x, y


def main():
    size = 64 if SMOKE else 224
    n_classes = 4 if SMOKE else 1000
    n = 64 if SMOKE else 4096

    data_dir = sys.argv[1] if len(sys.argv) > 1 else None
    if data_dir:
        from analytics_zoo_tpu.data.image import ImageResize, ImageSet

        iset = ImageSet.read(data_dir, with_label=True) \
            .transform(ImageResize(size, size))
        x, y = iset.to_arrays()
        x = x.astype("float32") / 255.0
    else:
        x, y = synthetic_imagenet(n, size, n_classes)

    model = inception_v1(input_shape=(size, size, 3), num_classes=n_classes)
    model.compile(optimizer="adam", loss="sparse_categorical_crossentropy",
                  metrics=["accuracy"])
    fs = FeatureSet.from_numpy(x, y)
    model.fit(fs, batch_size=16 if SMOKE else 256,
              nb_epoch=1 if SMOKE else 10)
    if SMOKE:
        # the eval step is a second full XLA compile of the backbone — the CI
        # smoke only needs to prove the train path runs
        print("smoke loss:", model.estimator.trainer_state.last_loss)
    else:
        print("eval:", model.evaluate(x[:32], y[:32], batch_size=16))


if __name__ == "__main__":
    main()
