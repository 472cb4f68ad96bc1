"""Wide & Deep recommendation over feature columns
(examples/recommendation WND parity)."""

from _common import SMOKE

import numpy as np

from analytics_zoo_tpu.models.recommendation import (ColumnFeatureInfo,
                                                     WideAndDeep, rows_to_batch)


def main():
    rng = np.random.default_rng(0)
    n = 1000 if SMOKE else 5000
    info = ColumnFeatureInfo(
        wide_base_cols=["gender"], wide_base_dims=[3],
        wide_cross_cols=["gender_age"], wide_cross_dims=[50],
        indicator_cols=["occupation"], indicator_dims=[10],
        embed_cols=["user", "item"], embed_in_dims=[200, 100],
        embed_out_dims=[16, 16], continuous_cols=["age"])

    def rows():
        for _ in range(n):
            user = int(rng.integers(200))
            item = int(rng.integers(100))
            yield dict(gender=int(rng.integers(3)),
                       gender_age=int(rng.integers(50)),
                       occupation=int(rng.integers(10)),
                       user=user, item=item,
                       age=float(rng.uniform(18, 80)),
                       label=int((user * 13 + item * 7) % 5) + 1)

    xs, labels = rows_to_batch(rows(), info)
    model = WideAndDeep(5, info, model_type="wide_n_deep",
                        hidden_layers=(32, 16))
    model.compile(optimizer="adam", loss="sparse_categorical_crossentropy",
                  metrics=["accuracy"])
    model.fit(xs, labels - 1, batch_size=128, nb_epoch=2 if SMOKE else 8)
    print("metrics:", model.evaluate(xs, labels - 1))


if __name__ == "__main__":
    main()
