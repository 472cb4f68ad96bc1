"""Generation serving under a closed loop: a fixed number of clients, each
sending its next request when the last one ended, as an offline job over a
set of documents does.

End-to-end metric: ``serve_tokens_per_s``, the tokens the clients saw served
inside the window, over the window: of every request that ended ``ok``, its
prompt at the instant its first token arrived (the proof that the prompt is
processed) and each generated token at the instant it arrived. Counting a
request whole at its last token instead moves the work of the requests in
flight at the window's two edges in or out by chance (it read 0.6% under to
2.4% over this count in five runs on the v5e, PR 22).
"""

from __future__ import annotations

from benchmark import harness
from benchmark.serving_rig import ServingRig, serve_cell


def reduce(obs, seconds: float):
    w0, w1 = obs["window"]
    tokens = attempted = failed = 0
    for r in obs["records"]:
        sent = r["t_send"] if r["t_send"] is not None else r["t_due"]
        r["in_window"] = w0 <= sent < w1
        ok = r["outcome"] == "ok"
        attempted += r["in_window"]
        failed += r["in_window"] and not ok
        if not ok:
            continue
        if w0 <= r["frames"][0][0] <= w1:
            tokens += r["prompt_len"]
        tokens += sum(k for t, k in r["frames"] if w0 <= t <= w1)
    return attempted, failed, {"serve_tokens_per_s": tokens / seconds}


def jobs_for(rig: ServingRig, mix, seed: int):
    clients = int(mix["arrivals"]["clients"])
    n = int(mix.get("generator_processes", 4))
    return [dict(rig.base_job(), seed=seed, clients=list(range(i, clients, n)),
                 connections=len(range(i, clients, n))) for i in range(n)]


def run(run: harness.Run) -> harness.Outcome:
    return serve_cell(run, lambda rig, _lead_s: jobs_for(rig, run.traffic,
                                                         run.seed),
                      reduce, stop_at_end=True)
