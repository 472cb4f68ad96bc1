"""``serve_mfu`` with the counts of a module the metric's file names: the
operations the tokens the clients saw served required (``serve_flops`` of
``benchmark.<counts>``: each prompt once, at the instant its first token
arrived; each generated token at the instant it arrived; nothing padded or
recomputed counts) over the window, chips and the published peak.

params: ``counts`` (a module of ``benchmark`` with ``serve_flops(config,
context_from, context_to)``)."""

import importlib


def read(obs, params):
    if "peaks" not in obs or not obs.get("records"):
        return None
    serve_flops = importlib.import_module(
        "benchmark." + params["counts"]).serve_flops
    config = obs["config"]
    w0, w1 = obs["window"]
    need = 0.0
    for r in obs["records"]:
        if r["outcome"] != "ok" or not r["frames"]:
            continue
        n = r["prompt_len"]
        if w0 <= r["frames"][0][0] <= w1:
            need += serve_flops(config, 0, n)
        for t, k in r["frames"]:
            if w0 <= t <= w1:
                need += serve_flops(config, n, n + k)
            n += k
    if not need:
        return None
    return need / ((w1 - w0) * obs["chips"] * obs["peaks"]["flops_per_s_bf16"])
