"""Model FLOP/s utilisation of training: tokens per second times the
operations a token requires (forward and backward, nothing recomputed) over
chips times the published peak. An end-to-end utilisation, not a kernel's."""

from benchmark import flops


def read(obs, params):
    rate = obs["end_to_end"].get(params.get("rate", "train_tokens_per_s"))
    if rate is None or "peaks" not in obs:
        return None
    need = flops.train_flops_per_token(obs["config"],
                                       obs["traffic"]["seq_len"])
    return rate * need / (obs["chips"] * obs["peaks"]["flops_per_s_bf16"])
