"""Tests of what PR 23 added beside the benchmark: the ``counter_ratio``
reader, the six metric files that read the generation path's own accounting,
and ``tools/loop_phases.py``. ``python -m pytest benchmark/tests`` (not part
of tier-1); everything here runs on the CPU and nothing is a measurement.

The six metrics are files only: a cell's per-layer metrics are listed in its
``workloads/<cell>.json``, a file the benchmark already had, so listing them
is for a ``benchmark`` PR (PERF.md, section 7). The rehearsal below lists
them in a copy."""

import json
import math
import os
import shutil
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


def test_the_new_metric_files_are_ready_to_be_listed():
    contract_keys = ("name", "unit", "better", "source", "layer", "moves",
                     "workloads")
    listed = {m["name"] for m in CONTRACT["per_layer"]}
    layers = {m["layer"] for m in CONTRACT["per_layer"]}
    for name in NEW:
        spec = harness.load("metrics", name)
        assert spec["name"] == name and name not in listed
        assert NAME.match(name) and UNIT.match(spec["unit"])
        assert spec["source"] == "program_counter"
        assert spec["reader"] == "counter_ratio" and spec["better"] == "lower"
        assert spec["moves"] == "itl_p95_ms" and spec["workloads"] == [CELL]
        assert set(contract_keys) < set(spec) and "\n" not in spec["layer"]
        assert len(spec["layer"]) <= 200
    assert harness.load("metrics", NEW[0])["layer"] in layers


@pytest.fixture(scope="module")
def listed_copy(tmp_path_factory):
    """A copy of the benchmark in which the six are listed: their names at
    the end of the cell's ``per_layer`` and their entries at the end of the
    contract's, nothing else changed."""
    root = tmp_path_factory.mktemp("listed")
    shutil.copytree(BENCH, root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    os.symlink(os.path.join(CHECKOUT, "analytics_zoo_tpu"),
               root / "analytics_zoo_tpu")
    cell = harness.load("workloads", CELL)
    cell["per_layer"] = cell["per_layer"] + NEW
    (root / "benchmark" / "workloads" / (CELL + ".json")).write_text(
        json.dumps(cell))
    contract = json.loads(json.dumps(CONTRACT))
    for name in NEW:
        spec = harness.load("metrics", name)
        contract["per_layer"].append({k: spec[k] for k in (
            "name", "unit", "better", "source", "layer", "moves",
            "workloads")})
    (root / "BENCHMARK.json").write_text(json.dumps(contract))
    return str(root)


def test_the_cell_rehearses_with_all_six_new_metrics(listed_copy):
    result, _ = rehearse(CELL, 1, cwd=listed_copy, seconds=3)
    assert result["correct"] is True and result["failed"] == 0
    for name in NEW:
        value = result["metrics"][name]["value"]
        assert math.isfinite(value) and value > 0, name
    assert result["metrics"]["gen_loop_host_share"]["value"] < 1
    print({n: result["metrics"][n]["value"] for n in NEW})
    # and untraced, the end-to-end metrics alone, as before
    result, _ = rehearse(CELL, 0, cwd=listed_copy, seconds=2)
    assert set(result["metrics"]) == {"setup_s", "itl_p95_ms"}


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
