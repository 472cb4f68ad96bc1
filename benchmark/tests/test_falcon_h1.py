"""Tests of what the Falcon-H1 configuration brought to the benchmark: its
plain reference against the program at a tiny size, the counts of
``flops_falcon_h1`` against the configuration's arithmetic, and the readers
of its per-layer metrics on a synthetic trace. ``python -m pytest
benchmark/tests`` (not part of tier-1); everything runs on the CPU."""

import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

from benchmark import flops_falcon_h1, harness  # noqa: E402
from benchmark.readers import (counted_roofline, counted_serve_mfu,  # noqa: E402
                               xplane)

CONFIG = harness.load("configs", "falcon-h1-34b")
COUNTS = {"counts": "flops_falcon_h1"}


def test_reference_agrees_with_the_program_at_a_tiny_size():
    import jax

    from benchmark.reference import falcon_h1_ref

    config = harness.rehearsal(CONFIG, True)
    model = harness.build_model(config)
    assert len(model.mixers) == 3 and all(len(pair) == 2
                                          for pair in model.mixers)
    params, _ = model.build(jax.random.PRNGKey(1))
    params = jax.tree_util.tree_map(            # norms' scales off one
        lambda a: a + 0.02 * jax.random.normal(
            jax.random.PRNGKey(a.size % 97), a.shape, a.dtype), params)
    ids = np.random.default_rng(0).integers(0, 512, size=(2, 48))
    _, kwargs = harness.reference_of(config)
    with jax.default_matmul_precision("highest"):
        got, _ = model.apply(params, {}, ids)
    want = falcon_h1_ref.logits(params, ids, **kwargs)
    # the logits' spread is some 5e-3 (lm_head_multiplier 1/128): relative
    assert np.abs(np.asarray(got) - want).max() < 2e-5 * want.std()
    # the controls round every weight product's operands, and it shows
    low = falcon_h1_ref.logits(params, ids, precision="fp8", **kwargs)
    assert np.abs(low - want).max() > 1e-2 * want.std()
    text = falcon_h1_ref.lowered_block(params, ids, precision="int8",
                                       **kwargs)
    assert "xi8>" in text


def test_the_configuration_holds_the_published_numbers():
    import json

    guide = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(guide):
        pytest.skip("no catalog beside the model-configs guide here")
    with open(guide) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Falcon-H1-34B-Instruct")
    assert CONFIG["source"].startswith(row["source_url"])
    for key, value in row["config"].items():
        if key in CONFIG["reduced"]:
            assert CONFIG[key] == 6 and CONFIG["published"][key] == value
        else:
            assert CONFIG[key] == value, key
    assert CONFIG["reduced"] == ["num_hidden_layers"]
    # every multiplier reaches the program and the reference by its key
    for kwargs in (CONFIG["build"]["kwargs"], CONFIG["reference"]["kwargs"]):
        for key in row["config"]:
            if key.endswith(("_multiplier", "_multipliers")):
                assert kwargs[key] == "@" + key


def test_counts_follow_the_configurations_arithmetic():
    # a layer: attention 31.46 M, the mixer's two projections 68.32 M, the
    # MLP 330.30 M; the head 1,336.9 M
    assert flops_falcon_h1.matmul_params(CONFIG) == pytest.approx(
        6 * 430.08e6 + 5120 * 261120, rel=1e-4)
    assert flops_falcon_h1.state_bytes_per_slot_step(CONFIG) == \
        6 * 2 * 32 * 128 * 256 * 4
    assert flops_falcon_h1.kv_bytes_per_token(CONFIG) == 12288
    flops, nbytes = flops_falcon_h1.chunk_pass(CONFIG, "f32[1,32,8,128,128]")
    assert flops == 32 * 8 * (2 * 128 * 128 * (256 + 128)
                              + 4 * 128 * 256 * 128)
    assert nbytes == 4 * (32 * (8 * 128 * (2 * 128 + 128) + 256 * 128)
                          + 2 * 8 * 2 * 128 * 256)
    # bytes bound it: under the chip's 240 flops a byte
    assert flops / nbytes < 197e12 / 819e9
    one = flops_falcon_h1.serve_flops(CONFIG, 100, 101)
    assert one == pytest.approx(
        2 * flops_falcon_h1.matmul_params(CONFIG)
        + 6 * 5 * 32 * 128 * 256 + 4 * 20 * 128 * 6 * 101, rel=1e-9)


def _obs(ops):
    device = xplane.Device("/device:TPU:0", [], ops, [])
    return {"trace": xplane.Trace([device], []), "config": CONFIG,
            "peaks": harness.peaks_for("TPU v5 lite"), "chips": 1,
            "trace_span": (0.0, 4.0), "window": (0.0, 4.0),
            "trace_counters0": {counted_roofline.SLOT_STEPS: 1000.0},
            "trace_counters1": {counted_roofline.SLOT_STEPS: 1380.0},
            "records": [{"outcome": "ok", "prompt_len": 200,
                         "frames": [[1.0, 1], [1.5, 1], [2.0, 2]]}]}


def test_rooflines_read_the_kernels_by_name_and_stay_under_the_peak():
    state = flops_falcon_h1.state_bytes_per_slot_step(CONFIG) * 380 / 819e9
    obs = _obs([
        xplane.Op(0.0, 2 * state, "zoo_ssd_decode", "f32[64,32,128]"),
        xplane.Op(3.0, 3.001, "zoo_ssd_chunk_fwd", "f32[1,32,8,128,128]"),
        xplane.Op(3.5, 3.5001, "zoo_paged_attention", "bf16[64,4,5,128]")])
    decode = counted_roofline.read(obs, dict(COUNTS, kernel="zoo_ssd_decode",
                                             need="state_bytes"))
    assert decode == pytest.approx(50.0)
    chunk = counted_roofline.read(obs, dict(
        COUNTS, kernel="zoo_ssd_chunk_fwd", need="chunk_pass"))
    assert chunk == pytest.approx(
        100 * flops_falcon_h1.chunk_pass(CONFIG, "f32[1,32,8,128,128]")[1]
        / 819e9 / 1e-3)
    paged = counted_roofline.read(obs, dict(
        COUNTS, kernel="zoo_paged_attention", need="kv_read"))
    # tokens 2, 3 and 4 of the stream read 201, 202 and 203 cached tokens
    assert paged == pytest.approx(100 * 606 * 12288 / 819e9 / 1e-4)
    assert 0 < counted_serve_mfu.read(obs, COUNTS) < 1


def test_a_program_without_the_kernels_or_the_counter_gives_nothing():
    """What the parent commit gives the new metrics: no such kernel in its
    trace. The readers return None and do not raise."""
    obs = _obs([xplane.Op(0.0, 1.0, "zoo_paged_attention", "bf16[1]")])
    for kernel, need in (("zoo_ssd_decode", "state_bytes"),
                         ("zoo_ssd_chunk_fwd", "chunk_pass")):
        assert counted_roofline.read(obs, dict(COUNTS, kernel=kernel,
                                               need=need)) is None
    obs = _obs([xplane.Op(0.0, 1.0, "zoo_ssd_decode", "f32[64,32,128]")])
    obs["trace_counters0"] = obs["trace_counters1"] = {}
    assert counted_roofline.read(obs, dict(COUNTS, kernel="zoo_ssd_decode",
                                           need="state_bytes")) is None
    assert counted_roofline.read({"trace": None}, COUNTS) is None
    assert counted_serve_mfu.read({"records": []}, COUNTS) is None


def test_the_cells_own_limits_lie_between_their_readings():
    cell = harness.load("workloads", "gen-falconh1-chat-steady")
    assert cell["driver"] == "gen_open_loop_limits"
    for name, spec in cell["limits"].items():
        assert spec["program"] < spec["limit"] < spec["control"], name
        assert spec["why"]
