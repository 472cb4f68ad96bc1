"""Image similarity — reference ``apps/image-similarity`` (semantic + visual
similarity ranking with backbone embeddings). A backbone's penultimate
features embed each image; cosine similarity ranks the gallery for a query."""

from _common import SMOKE

import numpy as np

from analytics_zoo_tpu.inference import InferenceModel
from analytics_zoo_tpu.nn import layers as L
from analytics_zoo_tpu.nn.topology import Sequential


def _render(rng, size, fam):
    img = rng.uniform(0, 0.2, (size, size, 3)).astype("float32")
    if fam == 0:                                    # stripes
        img[::4, :, 0] = 1.0
    elif fam == 1:                                  # square
        img[size // 4:3 * size // 4, size // 4:3 * size // 4, 1] = 1.0
    else:                                           # noise
        img = np.clip(img + rng.uniform(0, 0.8, img.shape), 0, 1)
    return img.astype("float32")


def synthetic_gallery(n, size, seed=0):
    """Three visual 'families' (stripes, squares, noise) — similar images
    should rank together."""
    rng = np.random.default_rng(seed)
    fams = np.asarray([i % 3 for i in range(n)])
    imgs = np.stack([_render(rng, size, f) for f in fams])
    return imgs, fams


def main():
    size = 32 if SMOKE else 96
    n = 24 if SMOKE else 200
    imgs, fams = synthetic_gallery(n, size)

    # embedding = CNN minus its classification head (the app uses a pretrained
    # GoogLeNet's penultimate layer; here a small net briefly shaped on the
    # gallery's families plays that role)
    backbone = Sequential([
        L.InputLayer((size, size, 3)),
        L.Convolution2D(16, 3, 3, border_mode="same", activation="relu"),
        L.MaxPooling2D((2, 2)),
        L.Convolution2D(32, 3, 3, border_mode="same", activation="relu"),
        L.GlobalAveragePooling2D(),
        L.Dense(16, activation="relu"),     # <- embedding layer
        L.Dense(3, activation="softmax"),
    ])
    backbone.compile(optimizer="adam", loss="sparse_categorical_crossentropy")
    backbone.fit(imgs, fams.astype("int32"), batch_size=8,
                 nb_epoch=3 if SMOKE else 15)
    embed = Sequential(backbone.layers[:-1])  # drop the softmax Dense
    embed.compile(optimizer="sgd", loss="mse")
    # donate the trained weights (minus the dropped head) to the embedder —
    # Sequential param keys are positional slots, identical for the shared
    # prefix of layers
    trained = backbone.estimator.train_state["params"]
    keep = {embed.slot(l) for l in embed.layers}
    embed.set_initial_weights(
        {k: v for k, v in trained.items() if k in keep}, partial=True)

    feats = np.asarray(embed.predict(imgs, batch_size=16))
    feats = feats.reshape(len(imgs), -1)
    feats /= np.linalg.norm(feats, axis=1, keepdims=True) + 1e-9

    query = 0
    sims = feats @ feats[query]
    order = np.argsort(-sims)[1:6]
    print(f"query family={fams[query]}; top-5 neighbour families:",
          fams[order].tolist())
    hit = (fams[order] == fams[query]).mean()
    print(f"same-family fraction in top-5: {hit:.2f}")

    # serve the embedder behind the inference pool (the app's deployment shape)
    im = InferenceModel().load(embed)
    v = np.asarray(im.predict(imgs[:2]))
    print("served embedding batch:", v.shape)


if __name__ == "__main__":
    main()
