"""Text sequence models: BERT fine-tune heads (NER / SQuAD spans) and the
BiLSTM-CRF taggers (NER, POS SequenceTagger, joint IntentEntity).

Parity workloads: the reference's TFPark text estimators and keras text models
(pyzoo/zoo/tfpark/text/) driven end to end on synthetic corpora — token tags
derivable from token ids, answer spans marked by a special token, intents from
the leading word. Everything here is one jittable program per model; the CRF
loss/decode are `lax.scan` dynamic programs (no dynamic shapes)."""

from _common import SMOKE

import numpy as np  # noqa: E402

from analytics_zoo_tpu.models.text import (NER, BERTNER, BERTSQuAD,  # noqa: E402
                                           IntentEntity, SequenceTagger)
from analytics_zoo_tpu.nn.optimizers import Adam  # noqa: E402

T, W = 8, 5
N = 64 if SMOKE else 256
EPOCHS = 2 if SMOKE else 8
rng = np.random.default_rng(0)


def bert_ner():
    ids = rng.integers(1, 50, size=(N, T)).astype("int32")
    tags = (ids % 3).astype("int32")
    model = BERTNER(num_entities=3, vocab=50, hidden_size=32, n_block=1,
                    n_head=2, seq_len=T)
    model.compile(optimizer=Adam(lr=0.01), loss=BERTNER.loss)
    model.fit(ids, tags, batch_size=32, nb_epoch=EPOCHS)
    acc = (model.predict_tags(ids[:32]) == tags[:32]).mean()
    print(f"BERTNER     token acc {acc:.2f}")


def bert_squad():
    ids = rng.integers(2, 50, size=(N, T)).astype("int32")
    ans = rng.integers(0, T, size=N)
    ids[np.arange(N), ans] = 1                      # answer marker token
    spans = np.stack([ans, ans], axis=1).astype("int32")
    model = BERTSQuAD(vocab=50, hidden_size=32, n_block=1, n_head=2, seq_len=T)
    model.compile(optimizer=Adam(lr=0.01), loss=BERTSQuAD.loss)
    model.fit(ids, spans, batch_size=32, nb_epoch=EPOCHS)
    start, _end = model.predict_spans(ids[:32])
    print(f"BERTSQuAD   start acc {(start == ans[:32]).mean():.2f}")


def ner_crf():
    words = rng.integers(1, 40, size=(N, T)).astype("int32")
    chars = rng.integers(1, 20, size=(N, T, W)).astype("int32")
    tags = (words % 4).astype("int32")
    model = NER(num_entities=4, word_vocab_size=40, char_vocab_size=20,
                word_length=W, word_emb_dim=24, char_emb_dim=8,
                tagger_lstm_dim=16)
    model.compile(optimizer=Adam(lr=0.02), loss=model.loss)
    model.fit([words, chars], tags, batch_size=32, nb_epoch=EPOCHS)
    acc = (model.predict_tags([words[:32], chars[:32]]) == tags[:32]).mean()
    print(f"NER (CRF)   viterbi acc {acc:.2f}")


def pos_tagger():
    words = rng.integers(1, 40, size=(N, T)).astype("int32")
    pos, chunk = (words % 3).astype("int32"), (words % 2).astype("int32")
    model = SequenceTagger(num_pos_labels=3, num_chunk_labels=2,
                           word_vocab_size=40, feature_size=16)
    model.compile(optimizer=Adam(lr=0.02), loss=SequenceTagger.loss)
    model.fit(words, (pos, chunk), batch_size=32, nb_epoch=EPOCHS)
    pos_p, _ = model.predict(words[:32])
    acc = (pos_p.argmax(-1) == pos[:32]).mean()
    print(f"POS tagger  pos acc {acc:.2f}")


def intent_entity():
    words = rng.integers(1, 40, size=(N, T)).astype("int32")
    chars = rng.integers(1, 20, size=(N, T, W)).astype("int32")
    intent = (words[:, 0] % 3).astype("int32")
    slots = (words % 4).astype("int32")
    model = IntentEntity(num_intents=3, num_entities=4, word_vocab_size=40,
                         char_vocab_size=20, word_length=W, word_emb_dim=24,
                         char_emb_dim=8, char_lstm_dim=8, tagger_lstm_dim=16)
    model.compile(optimizer=Adam(lr=0.02), loss=IntentEntity.loss)
    model.fit([words, chars], (intent, slots), batch_size=32, nb_epoch=EPOCHS)
    intent_p, slot_p = model.predict([words[:32], chars[:32]])
    print(f"IntentEntity intent acc "
          f"{(intent_p.argmax(-1) == intent[:32]).mean():.2f} "
          f"slot acc {(slot_p.argmax(-1) == slots[:32]).mean():.2f}")


if __name__ == "__main__":
    bert_ner()
    bert_squad()
    ner_crf()
    pos_tagger()
    intent_entity()
