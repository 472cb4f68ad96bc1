"""Anomaly detection — LSTM forecaster residuals flag anomalies
(apps/anomaly-detection + examples/anomalydetection parity)."""

from _common import SMOKE

import numpy as np

from analytics_zoo_tpu.models.anomalydetection import AnomalyDetector
from analytics_zoo_tpu.models.anomalydetection.anomaly_detector import (
    detect_anomalies, standard_scale, unroll)


def main():
    n = 400 if SMOKE else 2000
    t = np.arange(n)
    series = np.sin(t / 10) + 0.05 * np.random.default_rng(0).standard_normal(n)
    series[n // 2] += 4.0  # inject an anomaly

    scaled = standard_scale(series[:, None])
    x, y = unroll(scaled, unroll_length=24)
    (xtr, ytr), (xte, yte) = AnomalyDetector.train_test_split(x, y, n // 4)

    model = AnomalyDetector(feature_shape=(24, 1), hidden_layers=(8, 8),
                            dropouts=(0.2, 0.2))
    model.compile(optimizer="adam", loss="mse")
    model.fit(xtr, ytr, batch_size=64, nb_epoch=2 if SMOKE else 10)
    y_pred = model.predict(xte).reshape(-1)
    flagged = detect_anomalies(yte, y_pred, anomaly_size=3)
    anomalous_idx = np.nonzero(~np.isnan(flagged[:, 2]))[0]
    print("anomalous test indices:", anomalous_idx)


if __name__ == "__main__":
    main()
