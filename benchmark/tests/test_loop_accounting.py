"""Tests of what PR 23 added beside the benchmark: the ``counter_ratio``
reader, the six metric files that read the generation path's own accounting,
and ``tools/loop_phases.py``. ``python -m pytest benchmark/tests`` (not part
of tier-1); everything here runs on the CPU and nothing is a measurement.

The six metrics were files only until PR 34 listed them in
``workloads/gen-chat-steady.json`` and ``BENCHMARK.json`` (three of them also
for ``gen-docs-batch``, as ``docs_<name>``, moving that cell's throughput)."""

import json
import math
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
CHECKOUT = os.path.dirname(BENCH)
sys.path.insert(0, CHECKOUT)

from benchmark import harness  # noqa: E402
from benchmark.readers import counter_ratio  # noqa: E402
from benchmark.tests.test_benchmark import (CONTRACT, NAME, UNIT,  # noqa: E402
                                            rehearse)

CELL = "gen-chat-steady"
NEW = ["gen_loop_host_share", "gen_decode_step_host_ms", "gen_prefill_host_ms",
       "gen_queue_wait_ms", "gen_ingress_ms", "gen_egress_ms"]
LOOP = "zoo_gen_loop_seconds_total{%s}"


def test_counter_ratio_on_hand_made_observations():
    c0 = {LOOP % "idle": 1.0, LOOP % "admit": 0.5, LOOP % "decode_wait": 10.0,
          LOOP % "decode_host": 1.0, "zoo_gen_decode_steps_total": 100.0,
          "zoo_gen_prefill_seconds{64}:sum": 1.0,
          "zoo_gen_prefill_seconds{64}:count": 10.0}
    c1 = {LOOP % "idle": 3.0, LOOP % "admit": 1.0, LOOP % "decode_wait": 18.0,
          LOOP % "decode_host": 2.5, "zoo_gen_decode_steps_total": 200.0,
          "zoo_gen_prefill_seconds{64}:sum": 1.6,
          "zoo_gen_prefill_seconds{64}:count": 14.0,
          # a label value first seen inside the window counts from zero
          "zoo_gen_prefill_seconds{256}:sum": 0.4,
          "zoo_gen_prefill_seconds{256}:count": 1.0}
    obs = {"counters0": c0, "counters1": c1, "window": (100.0, 110.0)}

    def read(name):
        return counter_ratio.read(obs, harness.load("metrics", name)["params"])

    # host 0.5 + 1.5 of 0.5 + 8.0 + 1.5 not idle
    assert read("gen_loop_host_share") == pytest.approx(2.0 / 10.0)
    assert read("gen_decode_step_host_ms") == pytest.approx(95.0)
    assert read("gen_prefill_host_ms") == pytest.approx(200.0)
    assert read("gen_queue_wait_ms") is None        # no such counter: older
    assert read("gen_ingress_ms") is None           # program, nothing read
    # without "den": per second of the window
    idle = {"num": [r"zoo_gen_loop_seconds_total\{idle\}"]}
    assert counter_ratio.read(obs, idle) == pytest.approx(0.2)
    # nothing happened: no ratio, not a division by zero
    assert counter_ratio.read(
        {"counters0": c1, "counters1": c1, "window": (0.0, 1.0)},
        harness.load("metrics", "gen_prefill_host_ms")["params"]) is None


def test_the_six_loop_metrics_are_listed_and_move_the_judged_metric():
    listed = {m["name"]: m for m in CONTRACT["per_layer"]}
    cell = harness.load("workloads", CELL)
    (judged,) = [n for n in cell["end_to_end"] if n != "setup_s"]
    for name in NEW:
        spec = harness.load("metrics", name)
        assert spec["name"] == name and name in cell["per_layer"]
        assert NAME.match(name) and UNIT.match(spec["unit"])
        assert spec["source"] == "program_counter"
        assert spec["reader"] == "counter_ratio" and spec["better"] == "lower"
        assert spec["moves"] == judged and spec["workloads"] == [CELL]
        assert listed[name] == {k: spec[k] for k in listed[name]}
        assert "\n" not in spec["layer"] and len(spec["layer"]) <= 200
    docs = harness.load("workloads", "gen-docs-batch")
    for name in ("gen_loop_host_share", "gen_decode_step_host_ms",
                 "gen_egress_ms"):
        twin = harness.load("metrics", "docs_" + name)
        assert twin["params"] == harness.load("metrics", name)["params"]
        assert twin["moves"] == "serve_tokens_per_s"
        assert "docs_" + name in docs["per_layer"]


def test_the_cell_rehearses_with_all_six_loop_metrics():
    """They are counters over the whole measured window, which a traced run
    closes before its profiler starts: the trace's seconds do not decide
    them."""
    result, _ = rehearse(CELL, 1, seconds=3)
    assert result["correct"] is True and result["failed"] == 0
    for name in NEW:
        value = result["metrics"][name]["value"]
        assert math.isfinite(value) and value > 0, name
    assert result["metrics"]["gen_loop_host_share"]["value"] < 1
    print({n: result["metrics"][n]["value"] for n in NEW})
    # and untraced, the end-to-end metrics alone, as before
    result, _ = rehearse(CELL, 0, seconds=2)
    assert set(result["metrics"]) == set(
        harness.load("workloads", CELL)["end_to_end"])


@pytest.mark.parametrize("cell", [CELL, "gen-docs-batch"])
def test_loop_phases_tool_closes_the_window_of_any_serving_cell(cell):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "benchmark/tools/loop_phases.py", "--workload", cell,
         "--seed", "3", "--seconds", "3", "--trace", "1",
         "--rehearse-on-cpu"], cwd=CHECKOUT, env=env, capture_output=True,
        text=True, timeout=600)
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    result = json.loads(proc.stdout.strip().splitlines()[-1])
    assert result["correct"] is True and result["platform"] == "cpu"
    assert set(result["phase_s"]) == {
        "swap", "admit", "prefill_host", "prefill_wait", "decode_host",
        "decode_wait", "emit", "idle", "other"}
    # snapshots from another thread miss the phase under way at each end
    assert result["phase_sum_s"] == pytest.approx(result["window_s"],
                                                  abs=0.15)
    assert result["steps"] > 0 and result["prefills"] > 0
    for name in NEW:
        assert result["metrics"][name] > 0, name
        assert result["traced"]["metrics"][name] is None \
            or result["traced"]["metrics"][name] > 0
    assert "idle_share" not in result["traced"]     # no device on a CPU


def test_region_cost_prints_both_sides():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "benchmark/tools/loop_phases.py", "--region-cost"],
        cwd=CHECKOUT, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-3000:]
    cost = json.loads(proc.stdout.strip().splitlines()[-1])
    for key in ("region_ns_profiler_off", "region_ns_profiler_on",
                "pass_us_profiler_off", "pass_us_profiler_on"):
        assert cost[key] > 0
