"""Generation serving under an open loop: requests are sent on a schedule
drawn from the seed, whether or not earlier ones have finished, and timed at
the client from the instant each was due.

What the clients saw of the gaps between consecutive tokens of a stream that
ended inside the window (a frame of k tokens is k gaps of 1/k of its wait;
frames read back to back, within a millisecond, are one frame): the mean and
the 50th, 90th, 95th and 99th percentiles, as ``itl_mean_ms`` and
``itl_p<q>_ms``. Which of them a cell is judged on, and which it records as
per-layer metrics, its own file says (``workloads/<cell>.json``); PERF.md,
section 2, has the rule that chose and the runs it chose from. Also computed:
``ttft_p90_ms`` (due instant to first token, over the requests due in the
window; a request that failed or never got a token counts as the largest
value), recorded and not judged.

Where the statistics lie: a plain pass of the decode loop is the body of the
gaps; every arrival's prefill stalls each live stream once, so a few percent
of the gaps are a pass plus one prefill, and the 99th percentile sits on the
edge to "plus two" (it jumped by a third in one run of six on the v5e, PR 22).

A traced run sends on through the seconds the profiler runs after the window
(``serving_rig.ServingRig.measure``): a third part of the schedule, drawn
like the others, so that the window's own requests are the untraced run's.
"""

from __future__ import annotations

from benchmark import harness
from benchmark import traffic_gen as traffic
from benchmark.serving_rig import ServingRig, serve_cell

SAME_READ_S = 1e-3


def token_gaps(frames, w0: float, w1: float):
    """Gaps of one stream, in seconds, that ended in ``[w0, w1]``."""
    merged = []
    for t, k in frames:
        if merged and t - merged[-1][0] < SAME_READ_S:
            merged[-1][1] += k
        else:
            merged.append([t, k])
    gaps = []
    for (t_prev, _), (t, k) in zip(merged, merged[1:]):
        if w0 <= t <= w1:
            gaps.extend([(t - t_prev) / k] * k)
    return gaps


def reduce(obs, seconds: float):
    """Records of a run -> (attempted, failed, end-to-end metrics)."""
    w0, w1 = obs["window"]
    ttft, gaps, failed = [], [], 0
    for r in obs["records"]:
        r["in_window"] = w0 <= r["t_due"] < w1
        gaps.extend(token_gaps(r["frames"], w0, w1))
        if not r["in_window"]:
            continue
        failed += r["outcome"] != "ok"
        ttft.append(r["frames"][0][0] - r["t_due"]
                    if r["frames"] and r["outcome"] == "ok" else float("inf"))
    attempted = len(ttft)
    worst = max((t for t in ttft if t != float("inf")), default=seconds)
    ttft = [worst if t == float("inf") else t for t in ttft]
    metrics = {}
    if ttft:
        metrics["ttft_p90_ms"] = 1e3 * harness.percentile(ttft, 90)
    if gaps:
        metrics["itl_mean_ms"] = 1e3 * sum(gaps) / len(gaps)
        for q in (50, 90, 95, 99):
            metrics[f"itl_p{q}_ms"] = 1e3 * harness.percentile(gaps, q)
    obs["distribution_ms"] = {
        "ttft": {q: round(1e3 * harness.percentile(ttft, q), 1)
                 for q in (50, 90, 99)} if ttft else {},
        "n_gaps": len(gaps)}
    return attempted, failed, metrics


def jobs_for(rig: ServingRig, mix, seed: int, lead_s: float, seconds: float,
             after_s: float = 0.0):
    schedule = traffic.open_loop_schedule(mix, seed, lead_s, seconds, after_s)
    n = int(mix.get("generator_processes", 4))
    return [dict(rig.base_job(), requests=schedule[i::n]) for i in range(n)]


def run(run: harness.Run) -> harness.Outcome:
    return serve_cell(
        run, lambda rig, lead_s: jobs_for(rig, run.traffic, run.seed, lead_s,
                                          run.seconds, run.trace_seconds()),
        reduce, stop_at_end=False)
