"""Zouwu AutoTS — automated time-series forecasting
(zouwu/autots parity: AutoTSTrainer.fit → TSPipeline predict/save/load)."""

from _common import SMOKE

import numpy as np
import pandas as pd

from analytics_zoo_tpu.automl.recipe import LSTMRandomGridRecipe, SmokeRecipe
from analytics_zoo_tpu.zouwu import AutoTSTrainer, TSPipeline


def main():
    n = 240 if SMOKE else 1000
    dt = pd.date_range("2024-01-01", periods=n, freq="1h")
    value = (np.sin(np.arange(n) / 12) + 0.3 * np.sin(np.arange(n) / 5)
             + 0.05 * np.random.default_rng(0).standard_normal(n))
    df = pd.DataFrame({"datetime": dt, "value": value})
    train, test = df.iloc[:int(n * 0.8)], df.iloc[int(n * 0.8):]

    recipe = SmokeRecipe() if SMOKE else LSTMRandomGridRecipe(
        num_rand_samples=1, epochs=3, lstm_1_units=(16, 32), lstm_2_units=(16,))
    trainer = AutoTSTrainer(horizon=1)
    ppl = trainer.fit(train, validation_df=test, metric="mse", recipe=recipe)
    mse, smape = ppl.evaluate(test, metrics=["mse", "smape"])
    print(f"test mse={mse:.4f} smape={smape:.2f}")
    print(ppl.predict(test).head())

    import tempfile

    with tempfile.TemporaryDirectory() as d:
        ppl.save(f"{d}/pipeline")
        reloaded = TSPipeline.load(f"{d}/pipeline")
        print("reloaded predict rows:", len(reloaded.predict(test)))


if __name__ == "__main__":
    main()
