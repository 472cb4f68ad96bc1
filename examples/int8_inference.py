"""Int8 quantized inference — the OpenVINO-int8/vnni capability
(examples/vnni parity): quantize a trained model's weights to int8 inside the
InferenceModel pool and compare accuracy + memory."""

from _common import SMOKE

import numpy as np

from analytics_zoo_tpu.inference import InferenceModel
from analytics_zoo_tpu.nn import layers as L
from analytics_zoo_tpu.nn.topology import Sequential


def main():
    rng = np.random.default_rng(0)
    n = 512 if SMOKE else 4096
    x = rng.standard_normal((n, 32)).astype("float32")
    y = (x[:, :8].sum(axis=1) > 0).astype("int32")

    model = Sequential([L.Dense(256, activation="relu", input_shape=(32,)),
                        L.Dense(256, activation="relu"),
                        L.Dense(2, activation="softmax")])
    model.compile(optimizer="adam", loss="sparse_categorical_crossentropy",
                  metrics=["accuracy"])
    model.fit(x, y, batch_size=128, nb_epoch=3 if SMOKE else 10)

    infer = InferenceModel(supported_concurrent_num=2)
    infer.load(model)
    p32 = np.asarray(infer.predict(x))

    infer.quantize_int8()
    p8 = np.asarray(infer.predict(x))

    acc32 = float((p32.argmax(1) == y).mean())
    acc8 = float((p8.argmax(1) == y).mean())
    drift = float(np.abs(p32 - p8).max())
    print(f"fp32 acc={acc32:.4f}  int8 acc={acc8:.4f}  max prob drift={drift:.4f}")


if __name__ == "__main__":
    main()
