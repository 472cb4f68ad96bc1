"""Variational autoencoder — reference ``apps/variational-autoencoder``
notebooks. Encoder → (mean, log_var) → GaussianSampler reparameterization →
decoder; loss = reconstruction + KL, written as a plain JAX custom loss
(the autograd-capability path)."""

from _common import SMOKE

import jax.numpy as jnp
import numpy as np

from analytics_zoo_tpu.nn import layers as L
from analytics_zoo_tpu.nn.graph import Input
from analytics_zoo_tpu.nn.topology import Model

LATENT = 4


def build_vae(input_dim):
    inp = Input((input_dim,))
    h = L.Dense(64, activation="relu")(inp)
    mean = L.Dense(LATENT)(h)
    log_var = L.Dense(LATENT)(h)
    z = L.GaussianSampler()([mean, log_var])
    dh = L.Dense(64, activation="relu")(z)
    out = L.Dense(input_dim, activation="sigmoid")(dh)
    # expose mean/log_var alongside the reconstruction for the KL term
    return Model(inp, [out, mean, log_var])


def vae_loss(y_true, y_pred):
    recon, mean, log_var = y_pred
    bce = -jnp.mean(jnp.sum(
        y_true * jnp.log(recon + 1e-7)
        + (1 - y_true) * jnp.log(1 - recon + 1e-7), axis=-1))
    kl = -0.5 * jnp.mean(jnp.sum(
        1 + log_var - mean ** 2 - jnp.exp(log_var), axis=-1))
    return bce + kl


def synthetic_digits(n, dim=64, seed=0):
    rng = np.random.default_rng(seed)
    protos = rng.uniform(0, 1, (8, dim)) > 0.6
    idx = rng.integers(0, 8, n)
    x = protos[idx].astype("float32")
    flip = rng.uniform(size=x.shape) < 0.05
    return np.where(flip, 1 - x, x).astype("float32")


def main():
    x = synthetic_digits(256 if SMOKE else 8192)
    vae = build_vae(x.shape[1])
    vae.compile(optimizer="adam", loss=vae_loss)
    vae.fit(x, x, batch_size=64, nb_epoch=2 if SMOKE else 30)
    recon, mean, log_var = vae.predict(x[:8])
    err = float(np.mean(np.abs(np.asarray(recon) - x[:8])))
    print(f"reconstruction L1: {err:.4f}; latent mean norm: "
          f"{float(np.abs(np.asarray(mean)).mean()):.4f}")


if __name__ == "__main__":
    main()
