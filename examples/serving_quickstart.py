"""Cluster serving quickstart — broker + serving job + InputQueue/OutputQueue
client (pyzoo/zoo/examples/serving + serving quick_start parity, one process)."""

from _common import SMOKE

import numpy as np

from analytics_zoo_tpu.nn import Sequential
from analytics_zoo_tpu.nn import layers as L
from analytics_zoo_tpu.serving import (ClusterServing, InputQueue, OutputQueue,
                                       ServingConfig, start_broker)


def main():
    # 1. a trained model
    model = Sequential([L.Dense(16, activation="relu", input_shape=(8,)),
                        L.Dense(4, activation="softmax")])
    model.compile(optimizer="adam", loss="categorical_crossentropy")
    rng = np.random.default_rng(0)
    x = rng.normal(size=(64, 8)).astype(np.float32)
    y = np.eye(4, dtype=np.float32)[rng.integers(0, 4, 64)]
    model.fit(x, y, batch_size=16, nb_epoch=1)

    # 2. broker (the Redis-stream equivalent) + serving job (the Flink map)
    broker = start_broker()
    job = ClusterServing(model, ServingConfig(batch_size=8, concurrent_num=2,
                                              queue_port=broker.port)).start()
    try:
        # 3. client: enqueue requests, await results
        iq = InputQueue(port=broker.port)
        oq = OutputQueue(port=broker.port)
        uris = [iq.enqueue(None, input=x[i]) for i in range(16)]
        results = []
        for u in uris:
            try:
                results.append(oq.query(u, timeout_s=30))
            except TimeoutError:
                results.append(None)
        ok = sum(1 for r in results if r is not None)
        first = next((r for r in results if r is not None), None)
        print(f"served {ok}/16 requests; first probs:",
              None if first is None else np.round(np.asarray(first), 3))
    finally:
        job.stop()
        broker.shutdown()


if __name__ == "__main__":
    main()
