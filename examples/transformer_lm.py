"""Flagship transformer LM — bf16 compute, optional remat, flash attention
(the model behind __graft_entry__; examples/attention parity)."""

from _common import SMOKE

import numpy as np

from analytics_zoo_tpu.models.transformer import TransformerLM, lm_loss


def main():
    vocab, seq = 256, 64
    model = TransformerLM(vocab=vocab, hidden_size=64, n_block=2, n_head=4,
                          seq_len=seq, remat=True)
    model.compile(optimizer="adam", loss=lm_loss)

    rng = np.random.default_rng(0)
    ids = rng.integers(0, vocab, (128 if SMOKE else 512, seq + 1))
    model.fit(ids[:, :-1], ids[:, 1:], batch_size=32,
              nb_epoch=1 if SMOKE else 3)
    logits = model.predict(ids[:4, :-1])
    print("logits:", logits.shape)  # (4, seq, vocab)

    # memory-constrained variant: train WITHOUT materializing (B, T, vocab)
    # logits — apply_features + the fused chunked cross-entropy
    # (ops/fused_ce.py; the LM-head analog of flash attention)
    import jax.numpy as jnp

    from analytics_zoo_tpu.ops.fused_ce import fused_softmax_xent

    params = model.estimator.train_state["params"]
    h = model.apply_features(params, jnp.asarray(ids[:4, :-1]))
    loss = fused_softmax_xent(h, params["logits_kernel"].astype(h.dtype),
                              jnp.asarray(ids[:4, 1:]), chunk=64)
    print("fused-CE loss (no logits tensor):", float(loss))


if __name__ == "__main__":
    main()
