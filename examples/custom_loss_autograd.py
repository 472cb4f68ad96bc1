"""Custom loss — autograd/CustomLoss parity: a loss is just a JAX function
(pyzoo/zoo/examples/autograd parity; the reference's Variable algebra collapses
to plain jnp under jax.grad)."""

from _common import SMOKE

import jax.numpy as jnp
import numpy as np

from analytics_zoo_tpu.nn import layers as L
from analytics_zoo_tpu.nn.topology import Sequential


def huber_loss(y_true, y_pred, delta: float = 1.0):
    err = jnp.abs(y_true - y_pred)
    return jnp.mean(jnp.where(err <= delta, 0.5 * err ** 2,
                              delta * (err - 0.5 * delta)))


def main():
    rng = np.random.default_rng(0)
    n = 256 if SMOKE else 1024
    x = rng.standard_normal((n, 3)).astype("float32")
    y = (x @ np.array([1.0, -2.0, 0.5], dtype="float32"))[:, None]
    y[::50] += 15.0  # outliers: huber should shrug these off

    model = Sequential()
    model.add(L.InputLayer((3,)))
    model.add(L.Dense(1))
    model.compile(optimizer="adam", loss=huber_loss)  # custom fn, no wrapper
    model.fit(x, y, batch_size=64, nb_epoch=5 if SMOKE else 30)
    w = np.asarray(model.estimator.train_state["params"]["1_dense"]["kernel"])
    print("learned weights (true [1, -2, 0.5]):", w.reshape(-1))


if __name__ == "__main__":
    main()
