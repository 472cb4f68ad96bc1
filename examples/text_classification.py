"""Text classification — TextSet pipeline → TextClassifier (CNN encoder)
(examples/textclassification parity)."""

from _common import SMOKE

import numpy as np

from analytics_zoo_tpu.data.text import TextSet
from analytics_zoo_tpu.models.textclassification import TextClassifier


def synthetic_corpus(n=300, seed=0):
    rng = np.random.default_rng(seed)
    pos_words = ["great", "excellent", "love", "wonderful", "best"]
    neg_words = ["terrible", "awful", "hate", "worst", "boring"]
    filler = ["the", "movie", "was", "a", "film", "it", "and", "really"]
    texts, labels = [], []
    for _ in range(n):
        label = int(rng.integers(2))
        words = list(rng.choice(filler, 6))
        words += list(rng.choice(pos_words if label else neg_words, 3))
        rng.shuffle(words)
        texts.append(" ".join(words))
        labels.append(label)
    return texts, labels


def main():
    texts, labels = synthetic_corpus(120 if SMOKE else 600)
    tset = (TextSet.from_texts(texts, labels)
            .tokenize().normalize().word2idx(max_words_num=200)
            .shape_sequence(len=12).generate_sample())
    x, y = tset.to_arrays()
    model = TextClassifier(class_num=2, sequence_length=12, encoder="cnn",
                           vocab_size=202, embed_dim=32,
                           encoder_output_dim=32)
    model.compile(optimizer="adam", loss="sparse_categorical_crossentropy",
                  metrics=["accuracy"])
    model.fit(x, y, batch_size=32, nb_epoch=2 if SMOKE else 8)
    print("train metrics:", model.evaluate(x, y))


if __name__ == "__main__":
    main()
