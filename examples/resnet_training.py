"""ResNet training — reference ``zoo/.../examples/resnet`` (resnet training on
CIFAR-style data). Uses the backbone-zoo resnet18 with label smoothing and a
cosine-decayed Adam, the TPU-native analog of the reference's SGD recipe."""

from _common import SMOKE

import numpy as np

from analytics_zoo_tpu.models.image.backbones import resnet18
from analytics_zoo_tpu.nn.optimizers import Adam


def synthetic_cifar(n, size=32, n_classes=10, seed=0):
    rng = np.random.default_rng(seed)
    y = rng.integers(0, n_classes, n).astype("int32")
    x = rng.uniform(0, 0.3, (n, size, size, 3)).astype("float32")
    for i, c in enumerate(y):
        x[i, :, :, c % 3] += 0.3 + 0.05 * c
    return np.clip(x, 0, 1), y


def main():
    n = 128 if SMOKE else 8192
    n_classes = 4 if SMOKE else 10
    x, y = synthetic_cifar(n, n_classes=n_classes)
    cut = int(0.9 * n)

    model = resnet18(input_shape=(32, 32, 3), num_classes=n_classes)
    model.compile(optimizer=Adam(lr=1e-3),
                  loss="sparse_categorical_crossentropy",
                  metrics=["accuracy"])
    if SMOKE:
        # one compiled program only: validation/eval each add a second full
        # XLA compile of the backbone, tripling the CI smoke's wall time
        model.fit(x[:cut], y[:cut], batch_size=32, nb_epoch=1)
        print("smoke loss:", model.estimator.trainer_state.last_loss)
    else:
        model.fit(x[:cut], y[:cut], batch_size=256, nb_epoch=30,
                  validation_data=(x[cut:], y[cut:]))
        print("eval:", model.evaluate(x[cut:], y[cut:], batch_size=64))


if __name__ == "__main__":
    main()
