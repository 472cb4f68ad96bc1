"""The benchmark's own tests: ``python -m pytest benchmark/tests`` (not part of
tier-1). Everything here runs on the CPU; nothing here is a measurement."""

import hashlib
import json
import os
import re
import shutil
import subprocess
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
CHECKOUT = os.path.dirname(BENCH)
sys.path.insert(0, CHECKOUT)

from benchmark import flops, harness  # noqa: E402
from benchmark import traffic_gen as traffic  # noqa: E402
from benchmark.readers import xplane  # noqa: E402

with open(os.path.join(CHECKOUT, "BENCHMARK.json")) as f:
    CONTRACT = json.load(f)
CELLS = [w["name"] for w in CONTRACT["workloads"]]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def rehearse(cell, trace, cwd=CHECKOUT, seconds=2):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", cell, "--seed", "3",
         "--seconds", str(seconds), "--trace", str(trace),
         "--rehearse-on-cpu", "--out", os.path.join(cwd, "benchmark_out", cell)],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1]), proc.stdout


# ------------------------------------------------------------ the contract

def test_names_units_and_limits_of_the_contract():
    assert set(CONTRACT) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert 1 <= CONTRACT["run_seconds"] <= 51
    names = [m["name"] for m in CONTRACT["end_to_end"] + CONTRACT["per_layer"]]
    assert len(names) == len(set(names))
    for m in CONTRACT["end_to_end"] + CONTRACT["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    e2e = {m["name"]: m for m in CONTRACT["end_to_end"]}
    assert "setup_s" in e2e
    for m in CONTRACT["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    for m in CONTRACT["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["moves"] in e2e and "\n" not in m["layer"]
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"
    for w in CONTRACT["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200
    assert sum(w["chips"] == 4 for w in CONTRACT["workloads"]) <= max(
        1, len(CONTRACT["workloads"]) // 4)
    pairs = [(w["config"], w["traffic"]) for w in CONTRACT["workloads"]]
    assert len(pairs) == len(set(pairs))
    for c in CONTRACT["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["file"].startswith("benchmark/")
        assert any(w["config"] == c["name"] for w in CONTRACT["workloads"])
        with open(os.path.join(CHECKOUT, c["file"])) as f:
            assert json.load(f)["reduced"] == c["reduced"]
        for key in c["reduced"]:        # a depth, never a width
            assert not re.search(r"embd|inner|head|_dim|_rank|hidden", key)
    assert os.path.getsize(os.path.join(CHECKOUT, "BENCHMARK.json")) < 65536


def test_every_file_the_contract_names_is_there_and_agrees():
    e2e = {m["name"]: m for m in CONTRACT["end_to_end"]}
    layer = {m["name"]: m for m in CONTRACT["per_layer"]}
    for w in CONTRACT["workloads"]:
        cell = harness.load("workloads", w["name"])
        assert {k: cell[k] for k in w} == w
        harness.load("configs", cell["config"])
        harness.load("traffic", cell["traffic"])
        assert os.path.exists(os.path.join(
            BENCH, "drivers", cell["driver"] + ".py"))
        assert "setup_s" in cell["end_to_end"] and len(cell["end_to_end"]) > 1
        assert cell["per_layer"]
        for name in cell["end_to_end"]:
            assert w["name"] in e2e[name].get("workloads", [w["name"]])
        for name in cell["per_layer"]:
            assert w["name"] in layer[name].get("workloads", [w["name"]])
            assert layer[name]["moves"] in cell["end_to_end"]
    for name, m in layer.items():
        spec = harness.load("metrics", name)
        assert {k: spec[k] for k in m} == m
        assert os.path.exists(os.path.join(
            BENCH, "readers", spec["reader"] + ".py"))
    # and the other way round: a metric's ``workloads`` are the cells whose
    # files list it, no more and no fewer, so that no line lacks a metric the
    # contract promises there and no cell reads one it does not declare
    cells = {w["name"]: harness.load("workloads", w["name"])
             for w in CONTRACT["workloads"]}
    for kind, declared in (("end_to_end", e2e), ("per_layer", layer)):
        for name, m in declared.items():
            listing = sorted(c for c, cell in cells.items()
                             if name in cell[kind])
            assert listing, f"no cell lists {name}"
            assert sorted(m.get("workloads", cells)) == listing, name
        for c, cell in cells.items():
            assert set(cell[kind]) <= set(declared), c
            assert len(cell[kind]) == len(set(cell[kind])), c
    for c, cell in cells.items():           # no file says a thing twice
        assert not set(cell["end_to_end"]) & set(cell["per_layer"]), c
        assert "unlisted" not in cell, c
    for path, _, files in os.walk(BENCH):
        for name in files:
            if "__pycache__" not in path:
                assert re.match(r"^[A-Za-z0-9_.\-]+$", name), name


# ----------------------------------------------------------------- traffic

MIX = {"arrivals": {"process": "gamma", "rate_per_s": 20.0, "cv": 3},
       "prompt_len": {"dist": "mixture", "parts": [
           {"weight": 9, "dist": "lognormal", "median": 128, "sigma": 1.0,
            "min": 16, "max": 1024},
           {"weight": 1, "dist": "uniform", "min": 1025, "max": 1920}]},
       "output_len": {"dist": "uniform", "min": 16, "max": 64},
       "shared_prefix": {"tokens": 8, "pool": 2}}


def test_same_seed_same_traffic_and_another_seed_other_traffic():
    a = traffic.open_loop_schedule(MIX, 5, 5.0, 30.0)
    assert a == traffic.open_loop_schedule(MIX, 5, 5.0, 30.0)
    b = traffic.open_loop_schedule(MIX, 6, 5.0, 30.0)
    assert [r["due_s"] for r in a] != [r["due_s"] for r in b]
    assert [r["prompt_len"] for r in a] != [r["prompt_len"] for r in b]
    ids = traffic.prompt_tokens(MIX, a[0], 1000)
    assert (ids == traffic.prompt_tokens(MIX, a[0], 1000)).all()
    assert len(ids) == a[0]["prompt_len"] and ids.min() >= 1
    x, y = traffic.token_batches({"tokens": {"dist": "zipf", "exponent": 1.1}},
                                 5, 4, 32, 1000)
    x2, _ = traffic.token_batches({"tokens": {"dist": "zipf", "exponent": 1.1}},
                                  5, 4, 32, 1000)
    x3, _ = traffic.token_batches({"tokens": {"dist": "zipf", "exponent": 1.1}},
                                  6, 4, 32, 1000)
    assert (x == x2).all() and (x != x3).any() and (y[:, :-1] == x[:, 1:]).all()


def test_arrivals_and_lengths_have_the_parameters_asked_for():
    rng = np.random.default_rng(0)
    t = traffic.arrival_times(MIX["arrivals"], rng, 2000.0)
    gaps = np.diff(t)
    assert abs(len(t) / 2000.0 - 20.0) < 1.0
    assert abs(gaps.std() / gaps.mean() - 3.0) < 0.3
    n = traffic.lengths(MIX["prompt_len"], rng, 20000)
    assert n.min() >= 16 and n.max() <= 1920
    assert abs((n > 1024).mean() - 0.1) < 0.02
    chat = harness.load("traffic", "chat-steady")
    poisson = traffic.arrival_times(chat["arrivals"], rng, 50.0)
    assert len(poisson) == round(chat["arrivals"]["rate_per_s"] * 50.0)
    # stratified draws: every seed offers the same work, to a few percent
    work = [sum(r["output_len"] for r in traffic.open_loop_schedule(
        chat, seed, 0.0, 50.0)) for seed in range(8)]
    assert (max(work) - min(work)) / np.mean(work) < 0.05
    plain = traffic.lengths(chat["output_len"], rng, 100000)
    strat = traffic.lengths(chat["output_len"], rng, 100000, stratified=True)
    assert abs(np.median(plain) - 128) < 2 and abs(np.median(strat) - 128) < 2
    assert abs(plain.mean() - strat.mean()) < 1.5
    reqs = traffic.requests(MIX, 1, 64)
    heads = {tuple(traffic.prompt_tokens(MIX, r, 1000)[:8]) for r in reqs}
    assert len(heads) == 2              # the pool of shared prefixes
    clients = [traffic.requests(MIX, 1, 8, stream=s) for s in (0, 1)]
    assert clients[0] != clients[1]


def test_a_round_of_a_closed_loop_offers_the_same_work_in_every_seed():
    docs = harness.load("traffic", "docs-batch")
    n = docs["arrivals"]["clients"]

    def round_of(seed, i):
        return [traffic.closed_loop_request(docs, seed, c, i) for c in range(n)]

    assert round_of(3, 0) == round_of(3, 0)
    assert round_of(3, 0) != round_of(3, 1) != round_of(4, 1)
    sums = [sum(r["prompt_len"] + r["output_len"] for r in round_of(seed, i))
            for seed in range(6) for i in range(3)]
    independent = [int(traffic.lengths(docs["prompt_len"],
                                       np.random.default_rng(s), n).sum())
                   for s in range(18)]
    assert np.std(sums) < np.std(independent) / 8
    lo, hi = docs["prompt_len"]["min"], docs["prompt_len"]["max"]
    assert all(lo <= r["prompt_len"] <= hi for r in round_of(5, 2))
    ids = {tuple(traffic.prompt_tokens(docs, r, 1000)[:8])
           for i in range(2) for r in round_of(5, i)}
    assert len(ids) == 2 * n            # no two requests share their tokens


# ------------------------------------------------------------ trace reader

@pytest.fixture(scope="module")
def serve_trace():
    return xplane.load(os.path.join(HERE, "data", "serve_v5e.xplane.pb.gz"))


@pytest.fixture(scope="module")
def train_trace():
    return xplane.load(os.path.join(HERE, "data", "train_v5e.xplane.pb.gz"))


def test_interval_algebra():
    assert xplane.union([(3, 4), (0, 1), (0.5, 2)]) == [(0, 2), (3, 4)]
    assert xplane.total([(0, 2), (3, 4)]) == 3
    assert xplane.subtract([(0, 10)], [(1, 2), (4, 6), (9, 12)]) == [
        (0, 1), (2, 4), (6, 9)]
    assert xplane.subtract([(0, 1), (5, 6)], [(0, 1)]) == [(5, 6)]


def test_reader_on_the_recorded_serving_trace(serve_trace):
    """Two prompts (100 and 1500 tokens) and six decode steps of a 2-block
    toy with two heads of 128, recorded on a v5e."""
    (device,) = serve_trace.devices
    decode = xplane.runs(device, "jit__lambda", has_op="zoo_paged_attention")
    other = xplane.runs(device, "jit__lambda", lacks_op="zoo_paged_attention")
    assert len(decode) == 6 and len(other) == 4
    assert all(len(r.ops) == 190 for r in decode)
    paged = xplane.kernel_ops(device, ["zoo_paged_attention"])
    assert len(paged) == 12 and paged[0].shape == "bf16[4,1,2,128]"
    (flash_a, flash_b) = xplane.kernel_ops(device, ["zoo_flash_fwd"])
    assert flash_a.shape == "bf16[2,2048,128]"
    assert flops.flash_fwd_flops(flash_a.shape) == 4 * 2 * 2048 ** 2 * 128 / 2
    assert 1.5e-3 < xplane.busy_s(serve_trace) < 1.7e-3
    assert xplane.device_ops(serve_trace)[0][0] == "zoo_paged_attention"
    gaps = dict(xplane.idle_gaps(serve_trace))
    # the traced second pass compiled both prefill buckets again
    assert gaps["serving.gen.prefill/backend_compile_and_load"] > 1.0
    assert xplane.collective_exposed_s(device) == 0


def test_reader_on_the_recorded_training_trace(train_trace):
    (device,) = train_trace.devices
    steps = xplane.runs(device, "jit_step")
    assert len(steps) == 3
    busy = [xplane.total(xplane.union(xplane.spans(r.ops))) for r in steps]
    assert all(1.19e-3 < b < 1.20e-3 for b in busy)
    names = {o.name for o in device.ops}
    assert {"zoo_flash_fwd", "zoo_flash_bwd_dq", "zoo_flash_bwd_dkv"} <= names
    from benchmark.readers import kernel_roofline, module_device_ms

    obs = {"trace": train_trace, "peaks": harness.peaks_for("TPU v5 lite")}
    ms = module_device_ms.read(obs, {"module": "jit_step"})
    assert 1.19 < ms < 1.20
    share = kernel_roofline.read(obs, {
        "kernels": ["zoo_flash_bwd_dq", "zoo_flash_bwd_dkv"],
        "flops": "flash_bwd_flops", "count_on": "zoo_flash_bwd_dq"})
    assert 0 < share < 100
    assert module_device_ms.read({"trace": None}, {}) is None


def _renamed(trace_file, rename_line, rename_module):
    """The recorded trace with its host lines and its executables renamed,
    as another interpreter name and a later program would write it."""
    import gzip

    xplane_pb2 = pytest.importorskip(
        "tensorflow.tsl.profiler.protobuf.xplane_pb2")
    space = xplane_pb2.XSpace()
    with gzip.open(os.path.join(HERE, "data", trace_file), "rb") as f:
        space.ParseFromString(f.read())
    for plane in space.planes:
        for line in plane.lines:
            if plane.name == "/host:CPU" and line.name in rename_line:
                line.name = rename_line[line.name]
        for meta in plane.event_metadata.values():
            for old, new in rename_module.items():
                if meta.name.startswith(old + "("):
                    meta.name = new + meta.name[len(old):]
    return xplane.from_xspace(space.SerializeToString())


def test_host_lines_are_taken_by_what_they_hold_not_by_their_name(
        serve_trace, train_trace):
    """The contract runs ``python3``, and the profiler names a thread's line
    after the process: a reader that kept lines named ``python`` gave
    ``none/none`` for every idle gap of every ledger line (PRs 22-33)."""
    for recorded, file in ((serve_trace, "serve_v5e.xplane.pb.gz"),
                           (train_trace, "train_v5e.xplane.pb.gz")):
        as_python3 = _renamed(file, {"python": "python3"}, {})
        assert as_python3.host == recorded.host and recorded.host
        assert xplane.idle_gaps(as_python3) == xplane.idle_gaps(recorded)
    gaps = dict(xplane.idle_gaps(_renamed("serve_v5e.xplane.pb.gz",
                                          {"python": "python3"}, {})))
    assert gaps["serving.gen.prefill/backend_compile_and_load"] > 1.0
    assert gaps.get("none/none", 0.0) < 0.1 * sum(gaps.values())
    # the runtime's own threads (compile passes, transfers) stay out
    names = {name for _, _, name in serve_trace.host}
    assert "DCE" not in names and "XlaLinearize" not in names


def test_an_idle_gap_is_shared_out_by_time_not_handed_to_its_middle():
    """A decode loop's gap runs from the tail of the blocking read through
    ``emit`` into the next dispatch; whatever sat at its middle used to get
    all of it."""
    ops = [xplane.Op(0.0, 1.0, "a", ""), xplane.Op(1.010, 2.0, "b", ""),
           xplane.Op(2.00002, 3.0, "c", "")]
    host = sorted([(0.5, 1.004, "x.loop.wait"), (1.004, 1.0045, "x.loop.emit"),
                   (1.0045, 1.5, "x.loop.host"),
                   (1.003, 1.006, "PjitFunction(f)")])
    trace = xplane.Trace([xplane.Device("/device:TPU:0", [], ops, [])], host)
    gaps = dict(xplane.idle_gaps(trace))
    assert gaps == {
        "x.loop.wait/none": pytest.approx(0.003),
        "x.loop.wait/PjitFunction(f)": pytest.approx(0.001),
        "x.loop.emit/PjitFunction(f)": pytest.approx(0.0005),
        "x.loop.host/PjitFunction(f)": pytest.approx(0.0015),
        "x.loop.host/none": pytest.approx(0.004),
        "gaps_under_50us": pytest.approx(0.00002)}
    assert sum(gaps.values()) == pytest.approx(0.01002)
    assert xplane._innermost_at(host, [0.1, 0.6, 1.0035, 1.2, 9.0]) == [
        "none", "x.loop.wait", "PjitFunction(f)", "x.loop.host", "none"]


def test_executables_are_found_under_their_old_and_their_new_names(
        serve_trace, train_trace):
    """Four metric files select an executable by its HLO module's name; each
    takes an alternation of today's name and the one a later PR of the
    program's may give the jitted step, so that the rename breaks no line."""
    from benchmark.readers import module_device_ms

    for metric, file, recorded, old, new in (
            ("decode_step_device_ms", "serve_v5e.xplane.pb.gz", serve_trace,
             "jit__lambda", "jit_zoo_gen_decode_step"),
            ("chat_prefill_device_ms_per_ktok", "serve_v5e.xplane.pb.gz",
             serve_trace, "jit__lambda", "jit_zoo_gen_prefill"),
            ("prefill_device_ms_per_ktok", "serve_v5e.xplane.pb.gz",
             serve_trace, "jit__lambda", "jit_zoo_gen_first_token"),
            ("train_step_device_ms", "train_v5e.xplane.pb.gz", train_trace,
             "jit_step", "jit_zoo_train_step")):
        params = harness.load("metrics", metric)["params"]
        counters = {"trace_counters0": {params.get("per_1000_of"): 0.0},
                    "trace_counters1": {params.get("per_1000_of"): 1600.0}}
        before = module_device_ms.read(dict(counters, trace=recorded), params)
        renamed = _renamed(file, {}, {old: new})
        assert {r.name for d in renamed.devices for r in d.modules} == {new}
        assert before and before > 0
        assert module_device_ms.read(dict(counters, trace=renamed),
                                     params) == before
        other = _renamed(file, {}, {old: "jit_something_else"})
        assert module_device_ms.read(dict(counters, trace=other),
                                     params) is None


def test_busy_time_never_exceeds_the_traced_window(serve_trace, train_trace):
    """The profiler records from inside ``start_trace`` to inside
    ``stop_trace``: a host clock read between the two calls is short of that,
    and a training chip that never idles was busy for 3.0023 s of a 3.0012 s
    window (v5e, PR 22). The window is at least the span of the trace."""
    for trace in (serve_trace, train_trace):
        first, last = trace.span
        assert all(first <= o.start and o.end <= last
                   for d in trace.devices for o in d.ops + d.async_ops)
        assert all(first <= a and b <= last for a, b, _ in trace.host)
        for host_clock_s in (0.0, 1e-3, 10.0):
            window = xplane.window_s(trace, host_clock_s)
            assert 0 < xplane.busy_s(trace) <= window >= host_clock_s
    assert xplane.window_s(serve_trace) == pytest.approx(2.867, abs=1e-3)


def test_kernels_are_found_by_the_names_shard_map_gives_them(train_trace):
    """Inside ``shard_map`` the instructions are ``jvp_zoo_flash_fwd_`` and
    ``transpose_jvp_zoo_flash_bwd_dq__`` (the four-chip cell's compiled
    step): the kernel's name is held, not equalled."""
    from benchmark.readers import kernel_roofline

    (device,) = train_trace.devices
    renamed = [xplane.Op(o.start, o.end, {
        "zoo_flash_fwd": "jvp_zoo_flash_fwd_",
        "zoo_flash_bwd_dq": "transpose_jvp_zoo_flash_bwd_dq__",
        "zoo_flash_bwd_dkv": "transpose_jvp_zoo_flash_bwd_dkv__",
    }.get(o.name, o.name), o.shape) for o in device.ops]
    sharded = xplane.Trace([xplane.Device(device.name, [], renamed, [])], [])
    params = {"kernels": ["zoo_flash_bwd_dq", "zoo_flash_bwd_dkv"],
              "flops": "flash_bwd_flops", "count_on": "zoo_flash_bwd_dq"}
    peaks = harness.peaks_for("TPU v5 lite")
    assert kernel_roofline.read({"trace": sharded, "peaks": peaks}, params) \
        == kernel_roofline.read({"trace": train_trace, "peaks": peaks}, params)
    m = xplane._INSTRUCTION.match(
        "%transpose_jvp_zoo_flash_bwd_dq__.9 = bf16[32,2048,128]{2,1,0:T(8,128)"
        "(2,1)} custom-call(%a, %b)")
    assert m.group(1, 2) == ("transpose_jvp_zoo_flash_bwd_dq__",
                             "bf16[32,2048,128]")


def test_peaks_are_keyed_by_the_exact_kind():
    assert harness.peaks_for("TPU v5 lite")["flops_per_s_bf16"] == 197e12
    with pytest.raises(KeyError):
        harness.peaks_for("TPU v5")


# --------------------------------------------------------------- reference

def test_reference_agrees_with_the_program_at_a_tiny_size():
    import jax

    from analytics_zoo_tpu.models.transformer import TransformerLM, lm_loss
    from benchmark.reference import gpt2_ref

    model = TransformerLM(vocab=211, hidden_size=64, n_block=3, n_head=4,
                          seq_len=48, intermediate_size=200)
    params, _ = model.build(jax.random.PRNGKey(1))
    params = jax.tree_util.tree_map(            # biases and gains off zero
        lambda a: a + 0.01 * jax.random.normal(
            jax.random.PRNGKey(a.size % 97), a.shape), params)
    ids = np.random.default_rng(0).integers(0, 211, size=(2, 48))
    with jax.default_matmul_precision("highest"):
        got, _ = model.apply(params, {}, ids)
    want = gpt2_ref.logits(params, ids, n_head=4)
    assert np.abs(np.asarray(got) - np.asarray(want)).max() < 1e-4
    labels = np.roll(ids, -1, 1)
    assert abs(float(lm_loss(labels, got))
               - gpt2_ref.loss(params, ids, labels, n_head=4)) < 1e-5
    assert flops.train_flops_per_token(
        harness.load("configs", "gpt2-medium"), 1024) == pytest.approx(
        2.27e9, rel=0.01)


# --------------------------------------------------------------- rehearsal

@pytest.mark.parametrize("trace", (0, 1))
@pytest.mark.parametrize("cell", CELLS)
def test_cell_rehearses_end_to_end_on_the_cpu(cell, trace):
    result, out = rehearse(cell, trace)
    assert "platform=cpu" in out
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 0
    assert result["device"]["platform"] == "cpu"
    assert "busy_s" not in result["device"] and "breakdown" not in result
    spec = harness.load("workloads", cell)
    declared = {m["name"]: m for m in
                CONTRACT["end_to_end"] + CONTRACT["per_layer"]}
    if trace:
        assert set(result["metrics"]) <= set(spec["per_layer"])
        assert result["metrics"], "no per-layer metric could be read"
        for name in result["metrics"]:  # no device metric from a CPU run
            assert declared[name]["source"] != "device_trace"
            assert name not in ("mfu", "peak_hbm_gb")
    else:
        assert set(result["metrics"]) == set(spec["end_to_end"])
    for name, m in result["metrics"].items():
        assert m["unit"] == declared[name]["unit"] and m["value"] > 0


def test_run_refuses_without_a_tpu_and_without_the_program(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    cmd = [sys.executable, "benchmark/run.py", "--workload", CELLS[0],
           "--seed", "0", "--seconds", "1", "--trace", "0"]
    proc = subprocess.run(cmd, cwd=CHECKOUT, env=env, capture_output=True,
                          text=True, timeout=300)
    assert proc.returncode != 0 and "{" not in proc.stdout
    shutil.copytree(BENCH, tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(CHECKOUT, "BENCHMARK.json"), tmp_path)
    proc = subprocess.run(cmd, cwd=tmp_path, env=env, capture_output=True,
                          text=True, timeout=300)
    assert proc.returncode != 0 and "{" not in proc.stdout


# ---------------------------------------------------------- driven by data

def _digest(root):
    out = {}
    for path, _, files in os.walk(root):
        for name in files:
            if "__pycache__" in path or "benchmark_out" in path:
                continue
            full = os.path.join(path, name)
            with open(full, "rb") as f:
                out[os.path.relpath(full, root)] = hashlib.sha256(
                    f.read()).hexdigest()
    return out


def test_a_later_pr_adds_a_cell_a_configuration_and_a_metric_as_files(tmp_path):
    """New files and new entries only; no file that is there is edited."""
    shutil.copytree(BENCH, tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    os.symlink(os.path.join(CHECKOUT, "analytics_zoo_tpu"),
               tmp_path / "analytics_zoo_tpu")
    before = _digest(tmp_path / "benchmark")

    def add(kind, name, spec):
        path = tmp_path / "benchmark" / kind / (name + ".json")
        assert not path.exists()
        path.write_text(json.dumps(spec))

    config = harness.load("configs", "cerebras-gpt-1.3b")
    config["name"] = "new-config"
    config["rehearse"]["n_layer"] = 1
    add("configs", "new-config", config)
    mix = harness.load("traffic", "chat-steady")
    mix["name"] = "chat-bursty"
    mix["rehearse"]["arrivals"] = {"process": "gamma", "rate_per_s": 5.0,
                                   "cv": 3}
    add("traffic", "chat-bursty", mix)
    metric = dict(harness.load("metrics", "decode_steps_per_s"),
                  name="gen_requests_per_s", unit="requests/s",
                  workloads=["gen-chat-bursty"],
                  params={"counter": "zoo_gen_requests_total{ok}"})
    add("metrics", "gen_requests_per_s", metric)
    cell = dict(harness.load("workloads", "gen-chat-steady"),
                name="gen-chat-bursty", config="new-config",
                traffic="chat-bursty")
    cell["per_layer"] = cell["per_layer"] + ["gen_requests_per_s"]
    add("workloads", "gen-chat-bursty", cell)
    contract = json.loads(json.dumps(CONTRACT))
    contract["workloads"].append({k: cell[k] for k in (
        "name", "config", "traffic", "chips", "why")})
    contract["per_layer"].append({k: metric[k] for k in (
        "name", "unit", "better", "source", "layer", "moves", "workloads")})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(contract))

    result, _ = rehearse("gen-chat-bursty", 1, cwd=str(tmp_path), seconds=3)
    assert result["correct"] is True
    assert result["metrics"]["gen_requests_per_s"]["value"] > 0
    after = _digest(tmp_path / "benchmark")
    assert {k: after[k] for k in before} == before
    assert len(after) == len(before) + 4


@pytest.mark.parametrize("trace", (0, 1))
def test_a_cell_is_listed_by_its_entries_alone(tmp_path, trace):
    """``gen-docs-batch`` was measured from PR 22 on and listed by PR 34. Its
    files were there all along; what listed it are entries of
    ``BENCHMARK.json``. In a copy without them ``run.py`` refuses the cell in
    words, before it builds anything, and prints no result; with them (the
    tree as it is) it rehearses, above."""
    cell = harness.load("workloads", "gen-docs-batch")
    assert cell["name"] in CELLS and "unlisted" not in cell
    shutil.copytree(BENCH, tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    os.symlink(os.path.join(CHECKOUT, "analytics_zoo_tpu"),
               tmp_path / "analytics_zoo_tpu")
    contract = json.loads(json.dumps(CONTRACT))
    for section in ("workloads", "end_to_end", "per_layer"):
        contract[section] = [
            e for e in contract[section] if e["name"] != cell["name"]
            and e.get("workloads") != [cell["name"]]]
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(contract))
    proc = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", cell["name"],
         "--seed", "3", "--seconds", "2", "--trace", str(trace),
         "--rehearse-on-cpu"], cwd=tmp_path,
        env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True,
        text=True, timeout=600)
    assert proc.returncode == 2 and proc.stdout.strip() == ""
    assert "BENCHMARK.json lists no cell 'gen-docs-batch'" in proc.stderr
    assert "gen-chat-steady" in proc.stderr and "Traceback" not in proc.stderr
