"""Model FLOP/s utilisation of serving over the measured window: the
operations the tokens the clients saw served required (each prompt once, at
the instant its first token arrived; each generated token at the instant it
arrived; nothing padded, recomputed or speculated counts) over the window,
chips and the published peak. The whole step's share of the chip, beside the
kernels' rooflines: a kernel taken off the path leaves its roofline silent,
and this still bounds what the change can claim."""

from benchmark import flops


def read(obs, params):
    if "peaks" not in obs or not obs.get("records"):
        return None
    w0, w1 = obs["window"]
    need = 0.0
    for r in obs["records"]:
        if r["outcome"] != "ok" or not r["frames"]:
            continue
        n = r["prompt_len"]
        if w0 <= r["frames"][0][0] <= w1:
            need += flops.serve_flops(obs["config"], 0, n)
        for t, k in r["frames"]:
            if w0 <= t <= w1:
                # the k tokens of this frame came from contexts n ... n + k - 1
                need += flops.serve_flops(obs["config"], n, n + k)
            n += k
    if not need:
        return None
    return need / ((w1 - w0) * obs["chips"] * obs["peaks"]["flops_per_s_bf16"])
