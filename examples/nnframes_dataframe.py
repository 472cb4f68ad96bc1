"""NNFrames — Spark-ML-style fit on a DataFrame of columns
(examples/nnframes parity)."""

from _common import SMOKE

import numpy as np
import pandas as pd

from analytics_zoo_tpu.nn import layers as L
from analytics_zoo_tpu.nn.topology import Sequential
from analytics_zoo_tpu.nnframes import NNClassifier


def main():
    rng = np.random.default_rng(0)
    n = 200 if SMOKE else 1000
    x = rng.standard_normal((n, 4)).astype("float32")
    df = pd.DataFrame({"features": list(x),
                       "label": (x.sum(axis=1) > 0).astype("int64")})

    net = Sequential()
    net.add(L.InputLayer((4,)))
    net.add(L.Dense(16, activation="relu"))
    net.add(L.Dense(2, activation="softmax"))

    model = (NNClassifier(net)
             .setFeaturesCol("features").setLabelCol("label")
             .setBatchSize(64).setMaxEpoch(5 if SMOKE else 20)
             .setLearningRate(0.05)
             .fit(df))
    out = model.transform(df)
    acc = float((out["prediction"].to_numpy() == df["label"].to_numpy()).mean())
    print(f"accuracy: {acc:.3f}")


if __name__ == "__main__":
    main()
