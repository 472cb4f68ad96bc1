"""Image classification — backbone zoo + ImageSet predict
(examples/imageclassification parity; synthetic colored squares stand in for a
dataset directory — pass a dogs-vs-cats style dir layout to use real files)."""

import sys

from _common import SMOKE

import numpy as np

from analytics_zoo_tpu.data.image import ImageSet
from analytics_zoo_tpu.models.image import ImageClassifier


def synthetic_image_dir(root):
    import os

    from PIL import Image

    for label, color in (("red", (220, 40, 40)), ("green", (40, 220, 40))):
        os.makedirs(os.path.join(root, label), exist_ok=True)
        rng = np.random.default_rng(hash(label) % 2**32)
        for i in range(8):
            arr = np.full((40, 40, 3), color, dtype=np.uint8)
            arr = np.clip(arr + rng.integers(-30, 30, arr.shape), 0, 255)
            Image.fromarray(arr.astype("uint8")).save(
                os.path.join(root, label, f"{i}.png"))


def main():
    import tempfile

    data_dir = sys.argv[1] if len(sys.argv) > 1 else None
    with tempfile.TemporaryDirectory() as tmp:
        if data_dir is None:
            synthetic_image_dir(tmp)
            data_dir = tmp
        iset = ImageSet.read(data_dir, with_label=True)
        labels = sorted({f.get_uri().split("/")[0] for f in iset.features})
        clf = ImageClassifier("squeezenet", input_shape=(32, 32, 3),
                              num_classes=len(labels), label_map=labels)
        clf.compile()
        clf.fit_image_set(iset, batch_size=8, nb_epoch=3 if SMOKE else 10)
        preds = clf.set_top_n(1).predict_image_set(iset)
        correct = sum(p[0][0] == labels[l]
                      for p, l in zip(preds, iset.get_labels()))
        print(f"train accuracy: {correct}/{len(preds)}")


if __name__ == "__main__":
    main()
