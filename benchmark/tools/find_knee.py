#!/usr/bin/env python3
"""Find the knee of an open-loop serving cell: the highest of a few fixed
rates the system sustains. Run once, on the chip, when the cell is defined (or
when an optimisation has moved the knee); the cell then gets about four fifths
of it, written into its traffic file as a number.

    python benchmark/tools/find_knee.py --workload gen-chat-steady \
        --rates 4 6 8 10 12 --seconds 30 --seed 0

One process: the deployment (``serving_rig.ServingRig``: the broker in a
process of its own, as the cells run it) is set up and warmed once, then
offered each rate in turn, lowest first, each with its own lead-in, window and
drain, up to the first rate that is not sustained. A rate is
sustained when at least 99% of the requests due in its window ended ``ok``
and the backlog (requests due that have no first token yet: a request being
served is no backlog) at the end of the window is no larger than at its
middle. One JSON line per rate, and a last one
naming the knee.
"""

from __future__ import annotations

import time

T_PROCESS_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def backlog(records, t: float) -> int:
    n = 0
    for r in records:
        started = r["frames"][0][0] if r["frames"] else float("inf")
        n += r["t_due"] <= t < started
    return n


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=30)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rehearse-on-cpu", action="store_true")
    args = ap.parse_args(argv)

    from benchmark import harness
    from benchmark.drivers import gen_open_loop
    from benchmark.readers import generator_late
    from benchmark.serving_rig import ServingRig

    rehearse = args.rehearse_on_cpu
    cell, config, mix = harness.load_cell(args.workload, rehearse)

    import jax

    platform = jax.devices()[0].platform
    if (platform == "tpu") == rehearse:
        print(f"find_knee: platform={platform}, rehearse={rehearse}",
              file=sys.stderr)
        return 2
    from analytics_zoo_tpu.common.compile_cache import enable_compile_cache

    enable_compile_cache()
    run = harness.Run(cell=cell, config=config, traffic=mix, seed=args.seed,
                      seconds=args.seconds, trace=False, out_dir="",
                      t_process_start=T_PROCESS_START,
                      compiles=harness.CompileCounter())
    lead_s = float(mix.get("lead_in_s", 6))
    rig = ServingRig(run)
    knee = None
    try:
        rig.warm()
        for rate in sorted(args.rates):
            rig.mix = run.traffic = dict(
                mix, arrivals=dict(mix["arrivals"], rate_per_s=rate))
            rig.spawn(gen_open_loop.jobs_for(rig, rig.mix, args.seed, lead_s,
                                             args.seconds))
            obs = rig.measure(lead_s, args.seconds, stop_at_end=False)
            attempted, failed, metrics = gen_open_loop.reduce(
                obs, args.seconds)
            w0, w1 = obs["window"]
            mid, end = (backlog(obs["records"], (w0 + w1) / 2),
                        backlog(obs["records"], w1))
            ok_share = 1 - failed / max(attempted, 1)
            sustained = ok_share >= 0.99 and end <= mid
            steps = obs["stats1"]["steps"] - obs["stats0"]["steps"]
            print(json.dumps({
                "platform": platform, "rate_per_s": rate,
                "attempted": attempted, "failed": failed,
                "backlog_mid": mid, "backlog_end": end,
                "sustained": sustained, **metrics,
                "distribution_ms": obs["distribution_ms"],
                "decode_steps_per_s": steps / args.seconds,
                "compiles_in_window": obs["compiles_in_window"],
                "generator_late_p99_ms": generator_late.read(obs, {})}),
                flush=True)
            if not sustained:
                break       # its backlog would be offered to the next rate
            knee = rate
    finally:
        rig.close()
    print(json.dumps({"knee_rate_per_s": knee,
                      "four_fifths": None if knee is None else 0.8 * knee}),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
