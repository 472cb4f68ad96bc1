"""Tests of the serving deployment the benchmark measures (PR 34): the broker
in a process of its own, the clients' statistics, the correctness check and
its control. ``python -m pytest benchmark/tests`` (not part of tier-1);
everything here runs on the CPU and nothing is a measurement."""

import json
import os
import re
import socket
import subprocess
import sys
import time

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
CHECKOUT = os.path.dirname(BENCH)
sys.path.insert(0, CHECKOUT)

from benchmark import harness, serving_rig  # noqa: E402
from benchmark.drivers import gen_open_loop  # noqa: E402

SERVING_CELLS = ["gen-chat-steady", "gen-docs-batch"]


def gone(pid: int) -> bool:
    try:
        os.kill(pid, 0)
    except ProcessLookupError:
        return True
    return False


def drive(cell, *more, before_main=""):
    """One CPU rehearsal of ``run.py`` in a process of its own, with
    ``before_main`` (python source) run first in that process: the way a test
    breaks the timed path underneath a whole run."""
    script = (f"import sys; sys.path.insert(0, {CHECKOUT!r})\n"
              f"from benchmark import run, serving_rig\n{before_main}\n"
              f"sys.exit(run.main(sys.argv[1:]))\n")
    proc = subprocess.run(
        [sys.executable, "-c", script, "--workload", cell, "--seed", "3",
         "--seconds", "3", "--trace", "0", "--rehearse-on-cpu", *more],
        cwd=CHECKOUT, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=600)
    pid = re.search(r"broker_pid=(\d+)", proc.stdout)
    return proc, int(pid.group(1)) if pid else None


def test_the_broker_runs_in_a_process_of_its_own_and_stop_ends_it():
    broker = serving_rig.BrokerProcess()
    try:
        broker.wait_until_it_answers()
        assert broker.proc.pid != os.getpid() and broker.proc.poll() is None
        socket.create_connection(("127.0.0.1", broker.port), 1.0).close()
    finally:
        broker.stop()
    assert broker.proc.poll() is not None and gone(broker.proc.pid)
    with socket.socket() as again:          # the port is free for the next run
        again.bind(("127.0.0.1", broker.port))
    broker.stop()                           # and stopping twice is harmless


def test_two_runs_side_by_side_get_a_broker_each():
    """The driver runs parent and change on one machine: each broker binds
    the port the kernel hands it and says which, so neither run can mistake
    the other's broker for its own."""
    both = [serving_rig.BrokerProcess() for _ in range(2)]
    try:
        for broker in both:
            broker.wait_until_it_answers()
        assert both[0].port != both[1].port
        assert all(b.proc.poll() is None for b in both)
    finally:
        for broker in both:
            broker.stop()
    assert all(gone(b.proc.pid) for b in both)


def test_a_broker_that_dies_before_it_answers_is_an_error_and_is_reaped(
        monkeypatch):
    monkeypatch.setattr(serving_rig.sys, "executable", "false")
    broker = serving_rig.BrokerProcess()
    with pytest.raises(RuntimeError, match="did not say its port"):
        broker.wait_until_it_answers(10.0)
    assert broker.proc.poll() is not None


def last_lines_are_the_checks(proc, checks):
    """Each number beside its limit, as the last lines of standard error."""
    last = proc.stderr.strip().splitlines()[-len(checks):]
    assert [line.split()[1] for line in last] == list(checks)


@pytest.mark.parametrize("cell", SERVING_CELLS)
def test_a_run_that_ends_well_is_correct_and_leaves_no_broker(cell):
    """Both serving cells through the broker's process."""
    proc, pid = drive(cell)
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    result = json.loads(proc.stdout.strip().splitlines()[-1])
    assert result["correct"] is True and result["failed"] == 0
    assert pid is not None and gone(pid)
    checks = result["checks"]
    assert list(result)[-1] == "checks"
    assert list(checks) == ["served_gap", "logit_rel_rms", "logit_max_abs",
                            "narrow_operands"]
    for check in checks.values():
        assert check["value"] <= check["limit"]
    assert checks["narrow_operands"] == {"value": 0, "limit": 0}
    last_lines_are_the_checks(proc, checks)


@pytest.mark.parametrize("control", ("fp8", "int8"))
def test_the_control_in_the_programs_place_comes_out_not_correct(control):
    """``--control``: the reference in the precision below the stated one
    takes the program's place and goes through the same comparison. At the
    rehearsal's toy size the limits that the chip's readings set need not
    part the two by their outputs (PERF.md, section 2, has the chip's), but
    8-bit operands are 8 bits wide at any size; float8 also has to lie clear
    of what the program itself serves."""
    proc, pid = drive("gen-chat-steady", "--control", control)
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    result = json.loads(proc.stdout.strip().splitlines()[-1])
    assert result["correct"] is False and result["failed"] == 0
    assert pid is not None and gone(pid)
    checks = result["checks"]
    assert checks["narrow_operands"]["value"] > 0
    over = [n for n, c in checks.items() if not c["value"] <= c["limit"]]
    assert "narrow_operands" in over
    assert [line for line in proc.stdout.splitlines() if "FAULT]" in line]
    # the program's own readings are printed beside the control's, and sound
    own = re.search(r"program\] served_gap=(\S+) narrow=\{\} rel_rms=(\S+) "
                    r"max_abs=(\S+)", proc.stdout)
    assert own and float(own.group(2)) <= serving_rig.LOGIT_REL_RMS_TOL
    if control == "fp8":
        assert checks["served_gap"]["value"] > max(
            3 * float(own.group(1)), 0.005)
        assert checks["logit_rel_rms"]["value"] > 3 * float(own.group(2))
    last_lines_are_the_checks(proc, checks)


def test_narrow_types_are_found_where_they_are_and_nowhere_else():
    import jax

    from benchmark.reference import gpt2_ref

    text = ("%0 = stablehlo.convert %a : (tensor<4x8xf32>) -> tensor<4x8xi8>\n"
            "%1 = stablehlo.compare LT, %b, %c : tensor<4xi1>\n"
            "%2 = stablehlo.convert %d : tensor<2x2xbf16> tensor<ui8> "
            "tensor<3xi16> tensor<3xi32> tensor<3xui32> tensor<2xf8E5M2>\n"
            "%3 = tensor<7xi4> tensor<7xf16> tensor<7xi64>")
    assert serving_rig.narrow_types(text) == {"i8": 1, "ui8": 1, "f8E5M2": 1,
                                              "i4": 1}
    params, _ = harness.build_model(harness.load_cell(
        "gen-chat-steady", rehearse=True)[1]).build(jax.random.PRNGKey(0))
    ids = np.ones((1, 8), np.int32)
    kwargs = harness.reference_of(harness.load_cell(
        "gen-chat-steady", rehearse=True)[1])[1]
    assert serving_rig.narrow_types(
        gpt2_ref.lowered_block(params, ids, **kwargs)) == {}
    assert set(serving_rig.narrow_types(gpt2_ref.lowered_block(
        params, ids, precision="int8", **kwargs))) == {"i8"}
    assert set(serving_rig.narrow_types(gpt2_ref.lowered_block(
        params, ids, precision="fp8", **kwargs))) == {"f8E4M3FN"}


def test_a_run_that_raises_leaves_no_broker():
    proc, pid = drive("gen-chat-steady", before_main=(
        "def boom(self): raise RuntimeError('warm-up broke')\n"
        "serving_rig.ServingRig.warm = boom"))
    assert proc.returncode != 0 and "warm-up broke" in proc.stderr
    assert "{" not in proc.stdout.strip().splitlines()[-1]
    assert pid is not None
    deadline = time.monotonic() + 10
    while not gone(pid) and time.monotonic() < deadline:
        time.sleep(0.1)
    assert gone(pid)


def test_a_token_altered_where_it_is_produced_makes_the_run_incorrect():
    """The whole run, with the decode step's sampled ids shifted by one
    underneath: the clients get a full stream of the wrong tokens, every
    request ends ``ok``, and only the reference can tell."""
    proc, pid = drive("gen-chat-steady", before_main=(
        "import numpy as np\n"
        "built = serving_rig.ServingRig.__init__\n"
        "class Shifted:\n"
        "    def __init__(self, step, vocab):\n"
        "        self.step, self.vocab = step, vocab\n"
        "    def __call__(self, *a, **k):\n"
        "        ids, logits, cache = self.step(*a, **k)\n"
        "        return (np.asarray(ids) + 1) % self.vocab, logits, cache\n"
        "    def __getattr__(self, name):\n"
        "        return getattr(self.step, name)\n"
        "def broken(self, run):\n"
        "    built(self, run)\n"
        "    self.batcher._decode = Shifted(self.batcher._decode,\n"
        "                                   self.model.vocab)\n"
        "serving_rig.ServingRig.__init__ = broken"))
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    result = json.loads(proc.stdout.strip().splitlines()[-1])
    assert result["correct"] is False and result["failed"] == 0
    gap = result["checks"]["served_gap"]
    assert gap["value"] > gap["limit"]
    assert result["checks"]["logit_rel_rms"]["value"] \
        <= result["checks"]["logit_rel_rms"]["limit"]
    assert "served_gap" in proc.stdout and gone(pid)


def test_reduce_gives_the_mean_and_the_percentiles_of_hand_made_records():
    # one stream of 101 frames of one token, 10 ms apart, but every tenth
    # gap 30 ms; a second stream whose frames carry two tokens each
    t, frames = 100.0, [[100.0, 1]]
    for i in range(1, 101):
        t += 0.030 if i % 10 == 0 else 0.010
        frames.append([t, 1])
    two = [[100.0, 1]] + [[100.0 + 0.040 * i, 2] for i in range(1, 11)]
    records = [
        {"t_due": 99.9, "frames": frames, "outcome": "ok"},
        {"t_due": 99.95, "frames": two, "outcome": "ok"},
        # due before the window: its gaps count, its first token does not
        {"t_due": 50.0, "frames": [[99.0, 1], [100.5, 1]], "outcome": "ok"},
        # failed: counted, with the worst time to first token
        {"t_due": 101.0, "frames": [], "outcome": "error:x"}]
    obs = {"window": (99.5, 110.0), "records": records}
    attempted, failed, m = gen_open_loop.reduce(obs, 10.5)
    assert (attempted, failed) == (3, 1)
    gaps = [0.030 if i % 10 == 0 else 0.010 for i in range(1, 101)] \
        + [0.020] * 20 + [1.5]
    assert m["itl_mean_ms"] == pytest.approx(1e3 * np.mean(gaps))
    assert m["itl_p50_ms"] == pytest.approx(10.0)
    assert m["itl_p90_ms"] == pytest.approx(20.0)
    assert m["itl_p95_ms"] == pytest.approx(30.0)
    assert m["itl_p99_ms"] == pytest.approx(30.0)
    assert m["ttft_p90_ms"] == pytest.approx(100.0)     # 100 ms, 50 ms, worst
    assert [r["in_window"] for r in records] == [True, True, False, True]
    assert obs["distribution_ms"]["n_gaps"] == len(gaps)
    # frames read back to back are one frame; a gap ending outside the window
    # is not counted
    assert gen_open_loop.token_gaps(
        [[1.0, 1], [1.0004, 1], [1.010, 1], [3.0, 1]], 0.0, 2.0) == [
        pytest.approx(0.010)]


def test_a_traced_run_sends_on_after_its_window_and_leaves_the_window_alone():
    from benchmark import traffic_gen as traffic

    mix = harness.load("traffic", "chat-steady")
    after_s = float(mix["trace"]["seconds"])
    plain = traffic.open_loop_schedule(mix, 9, 12.0, 51.0)
    traced = traffic.open_loop_schedule(mix, 9, 12.0, 51.0, after_s)
    assert traced[:len(plain)] == plain
    after = traced[len(plain):]
    assert len(after) == round(after_s * mix["arrivals"]["rate_per_s"])
    assert all(63.0 <= r["due_s"] < 63.0 + after_s for r in after)
    # the traced seconds hold ten prompts or more, one from each slice of
    # the lengths' distribution, whatever the seed
    assert len(after) >= 10
    sums = [sum(r["prompt_len"] for r in traffic.open_loop_schedule(
        mix, seed, 12.0, 51.0, after_s)[len(plain):]) for seed in range(6)]
    assert (max(sums) - min(sums)) / np.mean(sums) < 0.35


def test_the_served_sample_is_drawn_from_the_seed_and_holds_the_longest():
    rig = serving_rig.ServingRig.__new__(serving_rig.ServingRig)
    rig.run = harness.Run(cell={"name": "x"}, config={}, traffic={}, seed=5,
                          seconds=1, trace=False, out_dir="",
                          t_process_start=0.0)
    records = [{"in_window": i % 2 == 0, "outcome": "ok", "tokens": [1, 2],
                "token_seed": [5, 3, 1, i], "prompt_len": 10 + i,
                "output_len": 2} for i in range(40)]
    records[7]["outcome"] = "short:1"
    sample = rig.served_sample(records)
    assert len(sample) == serving_rig.SERVED_SAMPLE
    assert sample[0]["prompt_len"] == 48             # the longest in window
    assert all(r["in_window"] and r["outcome"] == "ok" for r in sample)
    assert sample == rig.served_sample(list(reversed(records)))
    rig.run.seed = 6
    assert sample != rig.served_sample(records)
    assert rig.served_sample([]) == []


#: the chat cell's two sets of six runs at 2.8/s (PERF.md, section 2): per
#: run the gaps' 95th and 90th percentile, mean and median, in ms
TWELVE = [(10.030, 9.156, 8.186, 7.894), (10.634, 9.110, 7.949, 7.593),
          (11.305, 9.136, 7.956, 7.560), (10.067, 9.108, 7.937, 7.578),
          (10.886, 8.862, 8.047, 7.716), (13.480, 10.512, 8.320, 7.640),
          (9.605, 8.858, 8.007, 7.744), (10.824, 9.190, 7.863, 7.501),
          (12.299, 9.533, 8.141, 7.728), (10.225, 9.300, 8.122, 7.788),
          (9.897, 8.433, 7.735, 7.472), (15.138, 12.015, 8.629, 7.761)]
ORDER = ["itl_p95_ms", "itl_p90_ms", "itl_mean_ms", "itl_p50_ms"]


def test_the_rule_that_picked_the_judged_statistic_picks_it_again():
    """``tools/spread.py --choose`` is the ISSUE's rule: the first statistic,
    in the order given, whose widest spread (each set, all runs) is under
    half of a bound of at most 0.10, with the smallest of 0.04, 0.06, 0.08,
    0.10 that is more than twice that spread. On the twelve runs the
    committed choice was made from it gives that choice."""
    sys.path.insert(0, os.path.join(BENCH, "tools"))
    import spread

    def log(p95, p90, mean, p50):
        return (f"[c +  1.0s window] attempted=9 failed=0 itl_p90_ms={p90} "
                f"itl_mean_ms={mean} itl_p95_ms={p95} steps=3 "
                f"requests={{'ok': 9}}\n"
                + json.dumps({"correct": True, "failed": 0, "metrics": {
                    "itl_p50_ms": {"value": p50, "unit": "ms"}}}) + "\n")

    runs = spread.runs_of("".join(log(*run) for run in TWELVE))
    assert len(runs) == 12 and runs[1]["numbers"] == {
        "attempted": 9.0, "failed": 0.0, "itl_p90_ms": 9.110,
        "itl_mean_ms": 7.949, "itl_p95_ms": 10.634, "steps": 3.0,
        "itl_p50_ms": 7.593}
    assert spread.spread([1, 2, 3, 4, 5, 6]) == pytest.approx(3.5 / 3.5)
    each, values = spread.spreads(runs, "itl_p95_ms", 2)
    assert len(each) == 3 and len(values) == 12
    assert each == pytest.approx([0.1664, 0.3026, 0.1875], abs=5e-4)
    name, bound, widest = spread.choose(runs, ORDER, 2)
    assert (name, bound) == ("itl_p50_ms", 0.08)
    assert widest == pytest.approx(0.0354, abs=5e-4)
    assert spread.choose(runs, ORDER[:3], 2) is None    # the mean: 5.4%
    # a statistic that repeats to under 2% gets the tightest bound
    steady = spread.runs_of("".join(
        log(10, 9, 8, 7.5 + 0.01 * i) for i in range(12)))
    assert spread.choose(steady, ["itl_p50_ms"], 2)[:2] == ("itl_p50_ms", 0.04)
