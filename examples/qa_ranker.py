"""QA ranking with KNRM — rank-hinge training + NDCG/MAP evaluation
(examples/qaranker parity)."""

from _common import SMOKE

import numpy as np

from analytics_zoo_tpu.models.textmatching import KNRM


def main():
    rng = np.random.default_rng(0)
    q_len, a_len, vocab = 5, 10, 100
    n_pairs = 128 if SMOKE else 512

    # interleaved (pos, neg) pairs for rank hinge: answers containing the
    # query's tokens are relevant
    rows, labels = [], []
    for _ in range(n_pairs):
        q = rng.integers(2, vocab, q_len)
        pos = np.concatenate([q, rng.integers(2, vocab, a_len - q_len)])
        neg = rng.integers(2, vocab, a_len)
        rows += [np.concatenate([q, pos]), np.concatenate([q, neg])]
        labels += [1.0, 0.0]
    x = np.stack(rows).astype("int32")
    y = np.asarray(labels, "float32")[:, None]

    from analytics_zoo_tpu.common.config import TrainConfig

    model = KNRM(text1_length=q_len, text2_length=a_len, vocab_size=vocab,
                 embed_size=16, kernel_num=7, target_mode="ranking")
    # shuffle=False: rank_hinge consumes ADJACENT (pos, neg) rows — per-example
    # shuffling would pair arbitrary rows and train on noise
    model.compile(optimizer="adam", loss="rank_hinge",
                  config=TrainConfig(shuffle=False))
    model.fit(x, y, batch_size=64, nb_epoch=3 if SMOKE else 12)

    # group eval: 16 queries × 8 candidates
    groups = []
    for i in range(16):
        sl = slice(i * 8, (i + 1) * 8)
        groups.append((x[sl], y[sl, 0]))
    print(f"NDCG@3: {model.evaluate_ndcg(groups, k=3):.3f}  "
          f"MAP: {model.evaluate_map(groups):.3f}")


if __name__ == "__main__":
    main()
