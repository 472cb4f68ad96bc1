"""Seq2seq chatbot — encoder/decoder over token ids with greedy inference
(examples/chatbot parity; synthetic echo-ish corpus)."""

from _common import SMOKE

import numpy as np

from analytics_zoo_tpu.models.seq2seq import Bridge, RNNDecoder, RNNEncoder, Seq2seq
from analytics_zoo_tpu.nn import layers as L


def main():
    vocab, src_len, tgt_len = 40, 8, 6
    rng = np.random.default_rng(0)
    n = 256 if SMOKE else 2048
    # toy task: reply = reversed prefix of the prompt
    enc_in = rng.integers(2, vocab, (n, src_len)).astype("int32")
    target = enc_in[:, :tgt_len][:, ::-1].astype("int32")
    dec_in = np.concatenate([np.ones((n, 1), "int32"),  # BOS
                             target[:, :-1]], axis=1)

    enc = RNNEncoder.initialize("gru", 1, 32,
                                embedding=L.Embedding(vocab, 32))
    dec = RNNDecoder.initialize("gru", 1, 32,
                                embedding=L.Embedding(vocab, 32))
    model = Seq2seq(enc, dec, input_shape=(src_len,), output_shape=(tgt_len,),
                    bridge=Bridge.initialize("dense", 32),
                    generator=L.TimeDistributed(
                        L.Dense(vocab, activation="softmax")))
    model.compile(optimizer="adam", loss="sparse_categorical_crossentropy",
                  metrics=["accuracy"])
    model.fit([enc_in, dec_in], target, batch_size=64,
              nb_epoch=2 if SMOKE else 15)
    print("teacher-forced metrics:", model.evaluate([enc_in, dec_in], target))
    probs = model.predict([enc_in[:2], dec_in[:2]])
    print("sample decoded reply:", probs.argmax(-1)[0], "target:", target[0])


if __name__ == "__main__":
    main()
