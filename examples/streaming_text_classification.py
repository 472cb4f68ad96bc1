"""Streaming text classification — reference
``zoo/.../examples/streaming/textclassification`` (streamed lines classified
by a trained TextClassifier): text flows through the serving stream as indexed
sequences; the engine batches and classifies, results stream back."""

from _common import SMOKE

import numpy as np

from analytics_zoo_tpu.data.text import TextSet
from analytics_zoo_tpu.models.textclassification import TextClassifier
from analytics_zoo_tpu.serving import (ClusterServing, InputQueue, OutputQueue,
                                       ServingConfig, start_broker)

SPORT = ["the team won the match", "a great goal in the game",
         "the player scored again", "championship final tonight"]
TECH = ["new chip doubles compute", "the compiler fuses kernels",
        "a faster network stack", "gpu and tpu benchmarks"]
SEQ_LEN = 10


def main():
    texts = (SPORT + TECH) * (2 if SMOKE else 16)
    labels = ([0] * len(SPORT) + [1] * len(TECH)) * (2 if SMOKE else 16)

    tset = (TextSet.from_texts(texts, labels)
            .tokenize().normalize().word2idx(max_words_num=200)
            .shape_sequence(len=SEQ_LEN).generate_sample())
    x, y = tset.to_arrays()

    clf = TextClassifier(class_num=2, sequence_length=SEQ_LEN, encoder="cnn",
                         vocab_size=202, embed_dim=16, encoder_output_dim=16)
    clf.compile(optimizer="adam", loss="sparse_categorical_crossentropy",
                metrics=["accuracy"])
    clf.fit(x, y, batch_size=8, nb_epoch=2 if SMOKE else 20)

    broker = start_broker()
    cfg = ServingConfig(batch_size=4, queue_port=broker.port)
    job = ClusterServing(clf, cfg, group="stream-text").start()
    try:
        iq = InputQueue(port=broker.port)
        oq = OutputQueue(port=broker.port)
        stream = ["the striker scored a goal", "benchmarks of the new chip"]
        # index the streamed lines with the TRAINING word index (the reference
        # broadcasts the word index to the streaming executors)
        from analytics_zoo_tpu.data.text import WordIndexer

        probe = (TextSet.from_texts(stream, [0, 0])
                 .tokenize().normalize()
                 .transform(WordIndexer(tset.get_word_index()))  # unseen drop
                 .shape_sequence(len=SEQ_LEN))
        px, _ = probe.to_arrays()
        uris = [iq.enqueue(None, tokens=row) for row in px]
        for line, uri in zip(stream, uris):
            probs = np.asarray(oq.query(uri, timeout_s=60))
            print(f"{line!r} -> class {int(probs.argmax())} "
                  f"(p={float(probs.max()):.2f})")
    finally:
        job.stop()
        broker.shutdown()


if __name__ == "__main__":
    main()
