#!/usr/bin/env python3
"""One run of one cell of the benchmark.

    python benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A run is a new process. It reads ``workloads/<cell>.json``, the configuration
and the traffic mix that file names, hands them to ``drivers/<driver>.py``,
and prints as the last line of standard output one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics with
``--trace 0``, its per-layer metrics, each read by ``readers/<reader>.py`` as
``metrics/<metric>.json`` says, with ``--trace 1``), ``device``, traced
``breakdown``, and last ``checks``: each number that decided ``correct``
beside its limit, which are also the last lines of standard error. It
measures on a TPU or not at all: off one it exits 2 and
prints no result. ``--rehearse-on-cpu`` is the development rehearsal (tiny
sizes from the files' ``rehearse`` entries, interpreted kernels): it proves
the control flow, says ``platform=cpu`` and reports no device metric.
"""

from __future__ import annotations

import time

T_PROCESS_START = time.monotonic()

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, CHECKOUT)

#: where a metric's number comes from decides whether a CPU run may print it
DEVICE_SOURCES = ("device_trace",)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse-on-cpu", action="store_true")
    ap.add_argument("--control", choices=("fp8", "int8"), default=None,
                    help="put the correctness check's control in the "
                         "program's place (the reference in this precision "
                         "below the configuration's): the run has to come "
                         "out not correct")
    ap.add_argument("--out", default=None,
                    help="directory for the run's files (default "
                         "<checkout>/benchmark_out/<cell>)")
    args = ap.parse_args(argv)

    if not os.path.isdir(os.path.join(CHECKOUT, "analytics_zoo_tpu")):
        print("benchmark/run.py: no analytics_zoo_tpu package beside "
              "benchmark/: there is no system here to measure",
              file=sys.stderr)
        return 2
    from benchmark import harness

    with open(os.path.join(CHECKOUT, "BENCHMARK.json")) as f:
        contract = json.load(f)
    listed = sorted(w["name"] for w in contract["workloads"])
    if args.workload not in listed:
        print(f"benchmark/run.py: BENCHMARK.json lists no cell "
              f"{args.workload!r}; it lists {listed}", file=sys.stderr)
        return 2
    seconds = args.seconds if args.seconds is not None \
        else float(contract["run_seconds"])
    rehearse = args.rehearse_on_cpu
    cell, config, mix = harness.load_cell(args.workload, rehearse)

    import jax

    devices = jax.devices()
    platform, kind = devices[0].platform, devices[0].device_kind
    if rehearse and platform != "cpu":
        print(f"benchmark/run.py: --rehearse-on-cpu on platform={platform}",
              file=sys.stderr)
        return 2
    if not rehearse and (platform != "tpu" or len(devices) < cell["chips"]):
        print(f"benchmark/run.py: cell {cell['name']} needs {cell['chips']} "
              f"TPU chip(s); JAX found platform={platform} kind={kind!r} "
              f"count={len(devices)}", file=sys.stderr)
        return 2
    peaks = None if rehearse else harness.peaks_for(kind)

    from analytics_zoo_tpu.common.compile_cache import enable_compile_cache

    out_dir = args.out or os.path.join(CHECKOUT, "benchmark_out", cell["name"])
    os.makedirs(out_dir, exist_ok=True)
    run = harness.Run(cell=cell, config=config, traffic=mix, seed=args.seed,
                      seconds=seconds, trace=bool(args.trace), out_dir=out_dir,
                      t_process_start=T_PROCESS_START,
                      compiles=harness.CompileCounter(), control=args.control)
    run.say("device", platform=platform, kind=repr(kind), count=len(devices),
            compile_cache=enable_compile_cache(), seed=args.seed,
            seconds=seconds, trace=args.trace)

    driver = importlib.import_module("benchmark.drivers." + cell["driver"])
    outcome = driver.run(run)
    for note in outcome.notes:
        run.say("FAULT", what=note)

    # a driver that runs a reference on the chip reads the peak before it
    peak_bytes = outcome.observations.get("memory_peak_bytes") or max(
        ((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
         for d in devices[:cell["chips"]]), default=0)
    device = {"platform": platform, "kind": kind, "count": len(devices),
              "memory_peak_bytes": peak_bytes}
    declared = {m["name"]: m for m in
                contract["end_to_end"] + contract["per_layer"]}
    result = {"correct": outcome.correct, "attempted": outcome.attempted,
              "failed": outcome.failed, "metrics": {}, "device": device}

    def put(name: str, value) -> None:
        if value is not None and math.isfinite(value):
            result["metrics"][name] = {"value": float(value),
                                       "unit": declared[name]["unit"]}

    if not args.trace:
        for name in cell["end_to_end"]:
            put(name, outcome.end_to_end.get(name))
    else:
        obs = outcome.observations
        obs.update(config=config, traffic=mix, chips=cell["chips"],
                   end_to_end=outcome.end_to_end,
                   memory_peak_bytes=peak_bytes)
        if peaks is not None:
            obs["peaks"] = peaks
        if obs.get("trace_path"):
            from benchmark.readers import xplane

            obs["trace"] = trace = xplane.load(obs["trace_path"])
            shutil.rmtree(os.path.join(out_dir, "trace"))   # it is read
            t0, t1 = obs["trace_span"]
            obs["window_s"] = xplane.window_s(trace, t1 - t0)
            if trace.devices and not rehearse:
                device["busy_s"] = xplane.busy_s(trace)
                device["window_s"] = obs["window_s"]
                result["breakdown"] = {
                    "device_ops": xplane.device_ops(trace),
                    "idle_gaps": xplane.idle_gaps(trace)}
        for name in cell["per_layer"]:
            spec = harness.load("metrics", name)
            if rehearse and spec["source"] in DEVICE_SOURCES:
                continue
            reader = importlib.import_module(
                "benchmark.readers." + spec["reader"])
            put(name, reader.read(obs, spec.get("params", {})))
    result["checks"] = {name: {"value": value, "limit": limit}
                        for name, (value, limit) in outcome.checks.items()}
    print(json.dumps(result), flush=True)
    for name, (value, limit) in outcome.checks.items():
        print(f"check {name} {value!r} limit {limit!r}", file=sys.stderr)
    sys.stderr.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
