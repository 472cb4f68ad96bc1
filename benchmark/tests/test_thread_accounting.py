"""Tests of the thirteen metric files PR 41 added beside the benchmark: they
read, through ``counter_ratio`` alone, what the generation engine's sink and
source threads record about themselves (``zoo_gen_sink_seconds_total``,
``zoo_gen_source_seconds_total``, ``zoo_gen_cpu_seconds_total``,
``zoo_gen_egress_queued_seconds``) and PR 40's launch counter.
``python -m pytest benchmark/tests`` (not part of tier-1); everything here
runs on the CPU and nothing is a measurement.

They are files only, as PR 23's six were until PR 34 listed them: a cell
reports a metric that its workload file names, and appending to that file is
a ``benchmark`` PR's to do (``PERF.md`` section 7 has the lines). Until then
``tools/loop_phases.py`` prints them; once listed, the last test here holds
the listing to the files."""

import json
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
from benchmark.tests.test_benchmark import (CONTRACT, NAME,  # noqa: E402
                                            UNIT)

SINK_LAYER = "serving/broker + engine sink"
BATCHER = "serving/generation ContinuousBatcher"
#: name -> (unit, better, layer, the value the snapshots below give)
CHAT = {
    "gen_sink_busy_share": ("share", "lower", SINK_LAYER, 0.7),
    "gen_sink_frame_ms": ("ms", "lower", SINK_LAYER, 1.75),
    "gen_egress_queued_share": ("share", "lower", SINK_LAYER, 0.9),
    "gen_sink_cpu_share": ("share", "higher", SINK_LAYER, 0.25),
    "gen_first_frame_queued_ms": ("ms", "lower", SINK_LAYER, 40.0),
    "gen_source_busy_share": ("share", "lower",
                              "serving/broker + engine source", 0.02),
    "gen_decode_host_cpu_share": ("share", "higher", BATCHER, 0.8),
    "chat_launch_ahead_share": ("share", "higher", BATCHER, 0.985),
}
DOCS = {"docs_gen_sink_busy_share": "gen_sink_busy_share",
        "docs_gen_sink_frame_ms": "gen_sink_frame_ms",
        "docs_gen_egress_queued_share": "gen_egress_queued_share",
        "docs_gen_sink_cpu_share": "gen_sink_cpu_share",
        "docs_launch_ahead_share": "chat_launch_ahead_share"}


def snapshots():
    """A window of 10 s in which the sink wrote 4,000 frames in 7 busy
    seconds, flattened as ``harness.counters`` flattens: a label's value is
    part of the name, two labels are joined by a comma."""
    wall = {"sink": {"idle": 3.0, "build": 0.4, "xadd": 6.0, "ack": 0.1,
                     "other": 0.5},
            "source": {"poll": 9.8, "admit": 0.1, "stats": 0.06,
                       "other": 0.04},
            "loop": {"decode_host": 5.0, "decode_wait": 2.0, "idle": 3.0}}
    cpu = {"sink": {"idle": 0.01, "build": 0.35, "xadd": 1.0, "ack": 0.05,
                    "other": 0.35},
           "loop": {"decode_host": 4.0, "decode_wait": 0.1, "idle": 0.0}}
    c1 = {}
    for thread, phases in wall.items():
        for phase, s in phases.items():
            c1[f"zoo_gen_{thread}_seconds_total{{{phase}}}"] = s
    for thread, phases in cpu.items():
        for phase, s in phases.items():
            c1[f"zoo_gen_cpu_seconds_total{{{thread},{phase}}}"] = s
    c1.update({
        "zoo_gen_egress_seconds:sum": 200.0,
        "zoo_gen_egress_seconds:count": 4000.0,
        "zoo_gen_egress_queued_seconds{first}:sum": 1.2,
        "zoo_gen_egress_queued_seconds{first}:count": 30.0,
        "zoo_gen_egress_queued_seconds{next}:sum": 177.6,
        "zoo_gen_egress_queued_seconds{next}:count": 3940.0,
        "zoo_gen_egress_queued_seconds{final}:sum": 1.2,
        "zoo_gen_egress_queued_seconds{final}:count": 30.0,
        "zoo_gen_decode_launches_total{ahead,}": 1970.0,
        "zoo_gen_decode_launches_total{drained,admit}": 28.0,
        "zoo_gen_decode_launches_total{drained,first}": 2.0})
    # every counter stood at a tenth of that when the window opened; a
    # ratio of increases does not care
    c0 = {name: value / 10 for name, value in c1.items()}
    c1 = {name: value + c0[name] for name, value in c1.items()}
    return {"counters0": c0, "counters1": c1, "window": (100.0, 110.0)}


def test_there_are_thirteen_and_each_says_what_it_is():
    assert len(CHAT) + len(DOCS) == 13
    e2e = {m["name"] for m in CONTRACT["end_to_end"]}
    for name in list(CHAT) + list(DOCS):
        spec = harness.load("metrics", name)
        unit, better, layer, _ = CHAT[DOCS.get(name, name)]
        assert spec["name"] == name and NAME.match(name)
        assert spec["unit"] == unit and UNIT.match(unit)
        assert spec["better"] == better and spec["layer"] == layer
        assert spec["source"] == "program_counter"
        assert spec["reader"] == "counter_ratio" and spec["what"]
        cell, moves = (("gen-docs-batch", "serve_tokens_per_s")
                       if name in DOCS else ("gen-chat-steady", "itl_p50_ms"))
        assert spec["workloads"] == [cell] and spec["moves"] == moves
        assert moves in e2e
        assert moves in harness.load("workloads", cell)["end_to_end"]
        # a layer the contract already names, letter for letter
        assert layer in {m["layer"] for m in CONTRACT["per_layer"]}
    for twin, name in DOCS.items():
        assert harness.load("metrics", twin)["params"] \
            == harness.load("metrics", name)["params"]


@pytest.mark.parametrize("name", list(CHAT) + list(DOCS))
def test_each_file_reads_a_plausible_value_from_synthetic_counters(name):
    obs = snapshots()
    params = harness.load("metrics", name)["params"]
    expected = CHAT[DOCS.get(name, name)][3]
    assert counter_ratio.read(obs, params) == pytest.approx(expected)
    # a program older than the counters (the parent) gives nothing to read,
    # and raises nothing
    assert counter_ratio.read({"counters0": {}, "counters1": {
        "zoo_gen_egress_seconds:sum": 1.0,
        "zoo_gen_egress_seconds:count": 9.0}, "window": (0.0, 1.0)},
        params) is None


@pytest.mark.parametrize("cell,names", [
    ("gen-chat-steady", list(CHAT)), ("gen-docs-batch", list(DOCS))])
def test_loop_phases_prints_the_cells_new_metrics_from_a_served_window(
        cell, names):
    """``tools/loop_phases.py`` reads every ``counter_ratio`` file it finds,
    so it prints all thirteen with no change to it; a cell's own read a
    number over a rehearsed window, each share a share."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "benchmark/tools/loop_phases.py", "--workload", cell,
         "--seed", "3", "--seconds", "3", "--rehearse-on-cpu"], cwd=CHECKOUT,
        env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    result = json.loads(proc.stdout.strip().splitlines()[-1])
    assert result["correct"] is True
    assert set(CHAT) | set(DOCS) <= set(result["metrics"])
    for name in names:
        value = result["metrics"][name]
        if "_cpu_share" in name:
            # an estimate from one pass in seventeen: over the few dozen
            # passes of a rehearsal it is a number and little more
            assert value is not None and value >= 0, name
            continue
        assert value is not None and value > 0, name
        if harness.load("metrics", name)["unit"] == "share":
            assert value <= 1.0 + 1e-9, name
    print({n: result["metrics"][n] for n in names})


def test_where_the_contract_lists_one_it_agrees_with_the_file():
    """Today it lists none (a cell's workload file is not this PR's to
    append to). A ``benchmark`` PR that appends them is held by
    ``test_every_file_the_contract_names_is_there_and_agrees``; this one
    only says that a partial listing would not be a contradiction."""
    listed = {m["name"]: m for m in CONTRACT["per_layer"]}
    for name in list(CHAT) + list(DOCS):
        spec = harness.load("metrics", name)
        cell = harness.load("workloads", spec["workloads"][0])
        assert (name in listed) == (name in cell["per_layer"])
        if name in listed:
            assert listed[name] == {k: spec[k] for k in listed[name]}
