"""``serve_mfu`` for a configuration of Olmo-Hybrid's shape: the operations
the tokens the clients saw served required (``benchmark.flops_hybrid``: each
prompt once, at the instant its first token arrived; each generated token at
the instant it arrived; nothing padded or recomputed counts) over the window,
chips and the published peak."""

from benchmark import flops_hybrid


def read(obs, params):
    if "peaks" not in obs or not obs.get("records"):
        return None
    config = obs["config"]
    w0, w1 = obs["window"]
    need = 0.0
    for r in obs["records"]:
        if r["outcome"] != "ok" or not r["frames"]:
            continue
        n = r["prompt_len"]
        if w0 <= r["frames"][0][0] <= w1:
            need += flops_hybrid.serve_flops(config, 0, n)
        for t, k in r["frames"]:
            if w0 <= t <= w1:
                need += flops_hybrid.serve_flops(config, n, n + k)
            n += k
    if not need:
        return None
    return need / ((w1 - w0) * obs["chips"] * obs["peaks"]["flops_per_s_bf16"])
