#!/usr/bin/env python3
"""Where a serving cell's window went, by the decode loop's own accounting.

    python benchmark/tools/loop_phases.py --workload gen-docs-batch --seed 5
    python benchmark/tools/loop_phases.py --workload gen-chat-steady \
        --seed 7 --trace 1
    python benchmark/tools/loop_phases.py --region-cost

Runs one window of any serving cell file (listed in ``BENCHMARK.json`` or
not) through the cell's own driver, as ``run.py`` does, and prints one JSON
line: the window's seconds by phase of ``zoo_gen_loop_seconds_total`` and
their sum beside the window's length, every metric file read by
``counter_ratio`` (the loop's host share, a step and a prefill as the host
sees them, queue wait, ingress, egress), the driver's end-to-end numbers, the
clients' mean time from send to first frame, the longest silence (no frame at
any client) and, with ``--trace 1``, the device's busy and idle share of the traced part of
the window beside the loop's host share of the same part.

``--region-cost`` measures what the accounting itself costs, on this
machine's host: nanoseconds per ``telemetry.region`` and microseconds per
loop pass's worth of phases, with no profiler session and inside one.
"""

from __future__ import annotations

import time

T_PROCESS_START = time.monotonic()

import argparse  # noqa: E402
import glob  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, CHECKOUT)

from benchmark.readers import counter_ratio  # noqa: E402

PREFIX = "zoo_gen_loop_seconds_total{"


def phases(counters0, counters1):
    """Seconds by phase between two snapshots of ``harness.counters``."""
    return {name[len(PREFIX):-1]: value - counters0.get(name, 0.0)
            for name, value in counters1.items() if name.startswith(PREFIX)}


def ratio_metrics(obs):
    """Every ``metrics/*.json`` that ``counter_ratio`` reads, on ``obs``."""
    out = {}
    for path in sorted(glob.glob(os.path.join(CHECKOUT, "benchmark",
                                              "metrics", "*.json"))):
        with open(path) as f:
            spec = json.load(f)
        if spec["reader"] == "counter_ratio":
            out[spec["name"]] = counter_ratio.read(obs, spec["params"])
    return out


def region_cost(n: int = 200_000) -> dict:
    import jax

    from analytics_zoo_tpu.common import telemetry
    from analytics_zoo_tpu.serving.generation import _LoopClock

    child = telemetry.counter("zoo_tool_region_cost_seconds_total",
                              "loop_phases.py --region-cost",
                              labels=("phase",)).labels(phase="x")

    def per_region():
        t0 = time.perf_counter()
        for _ in range(n):
            with telemetry.region("tool.region.cost", child):
                pass
        return (time.perf_counter() - t0) / n * 1e9

    def per_pass():
        """The phases of one decode pass of ``ContinuousBatcher._loop``:
        admit, then a step (host, wait, host), then emit."""
        clock = _LoopClock()
        clock.begin()
        t0 = time.perf_counter()
        for _ in range(n // 10):
            with clock.phase("admit"):
                pass
            with clock.phase("decode_host"):
                with clock.phase("decode_wait"):
                    pass
            with clock.phase("emit"):
                pass
            clock.close_pass()
        return (time.perf_counter() - t0) / (n // 10) * 1e6

    out = {"platform": jax.devices()[0].platform,
           "region_ns_profiler_off": per_region(),
           "pass_us_profiler_off": per_pass()}
    with tempfile.TemporaryDirectory() as tmp:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(tmp, profiler_options=opts)
        try:
            n = n // 10                 # every region is an event now
            out["region_ns_profiler_on"] = per_region()
            out["pass_us_profiler_on"] = per_pass()
        finally:
            jax.profiler.stop_trace()
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse-on-cpu", action="store_true")
    ap.add_argument("--region-cost", action="store_true")
    args = ap.parse_args(argv)
    if args.region_cost:
        print(json.dumps(region_cost()), flush=True)
        return 0
    if not args.workload:
        ap.error("--workload or --region-cost")

    from benchmark import harness

    with open(os.path.join(CHECKOUT, "BENCHMARK.json")) as f:
        seconds = args.seconds if args.seconds is not None \
            else float(json.load(f)["run_seconds"])
    rehearse = args.rehearse_on_cpu
    cell, config, mix = harness.load_cell(args.workload, rehearse)

    import jax

    platform = jax.devices()[0].platform
    if (platform == "tpu") == rehearse:
        print(f"loop_phases: platform={platform}, rehearse={rehearse}",
              file=sys.stderr)
        return 2
    from analytics_zoo_tpu.common.compile_cache import enable_compile_cache

    enable_compile_cache()
    out_dir = os.path.join(CHECKOUT, "benchmark_out", cell["name"] + ".phases")
    os.makedirs(out_dir, exist_ok=True)
    run = harness.Run(cell=cell, config=config, traffic=mix, seed=args.seed,
                      seconds=seconds, trace=bool(args.trace),
                      out_dir=out_dir, t_process_start=T_PROCESS_START,
                      compiles=harness.CompileCounter())
    driver = importlib.import_module("benchmark.drivers." + cell["driver"])
    outcome = driver.run(run)
    obs = outcome.observations
    w0, w1 = obs["window"]
    by_phase = phases(obs["counters0"], obs["counters1"])
    steps = obs["stats1"]["steps"] - obs["stats0"]["steps"]
    # the client's own reading, to hold against the server's legs: from the
    # instant a request was sent to its first frame's arrival
    ttft = [r["frames"][0][0] - (r["t_send"] if r.get("t_send") is not None
                                 else r["t_due"])
            for r in obs["records"]
            if r["in_window"] and r["outcome"] == "ok" and r["frames"]]
    # a stall of the whole deployment shows as a silence: the longest stretch
    # of the window in which no frame reached any client, and where it lies
    arrivals = sorted(t for r in obs["records"] for t, _ in r["frames"]
                      if w0 <= t <= w1)
    silence = max(zip(arrivals[1:], arrivals), key=lambda p: p[0] - p[1],
                  default=None)
    result = {"workload": cell["name"], "seed": args.seed,
              "platform": platform, "correct": outcome.correct,
              "notes": outcome.notes, "attempted": outcome.attempted,
              "failed": outcome.failed, "end_to_end": outcome.end_to_end,
              "window_s": w1 - w0, "phase_s": by_phase,
              "phase_sum_s": sum(by_phase.values()), "steps": steps,
              "client_ttft_from_send_mean_ms":
                  1e3 * sum(ttft) / len(ttft) if ttft else None,
              "longest_silence": None if silence is None else {
                  "s": silence[0] - silence[1], "at_s": silence[1] - w0},
              "prefills": counter_ratio.increase(
                  obs, [r"zoo_gen_prefill_seconds\{.*\}:count"]),
              "metrics": ratio_metrics(obs)}
    if obs.get("trace_path"):
        from benchmark.readers import xplane

        trace = xplane.load(obs["trace_path"])
        shutil.rmtree(os.path.join(out_dir, "trace"))   # it is read
        t0, t1 = obs["trace_span"]
        traced = {"counters0": obs["trace_counters0"],
                  "counters1": obs["trace_counters1"], "window": (t0, t1)}
        result["traced"] = {
            "host_clock_s": t1 - t0,
            "phase_s": phases(traced["counters0"], traced["counters1"]),
            "metrics": ratio_metrics(traced)}
        if trace.devices:
            window = xplane.window_s(trace, t1 - t0)
            busy = xplane.busy_s(trace)
            result["traced"].update(
                window_s=window, busy_s=busy, idle_share=1 - busy / window,
                idle_gaps=xplane.idle_gaps(trace))
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
