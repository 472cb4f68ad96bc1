"""Tests of what the Olmo-Hybrid configuration brought to the benchmark: its
plain reference against the program at a tiny size, the counts of
``flops_hybrid`` against the configuration's arithmetic, and the readers of
its per-layer metrics on a synthetic trace. ``python -m pytest
benchmark/tests`` (not part of tier-1); everything runs on the CPU."""

import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

from benchmark import flops_hybrid, harness  # noqa: E402
from benchmark.readers import hyb_serve_mfu, hybrid_roofline, xplane  # noqa: E402

CONFIG = harness.load("configs", "olmo-hybrid-7b")


def test_reference_agrees_with_the_program_at_a_tiny_size():
    import jax

    from benchmark.reference import olmo_hybrid_ref

    config = harness.rehearsal(CONFIG, True)
    model = harness.build_model(config)
    assert model.layer_types == ["linear_attention"] * 3 + ["full_attention"]
    params, _ = model.build(jax.random.PRNGKey(1))
    params = jax.tree_util.tree_map(            # norms' scales off one
        lambda a: a + 0.02 * jax.random.normal(
            jax.random.PRNGKey(a.size % 97), a.shape, a.dtype), params)
    ids = np.random.default_rng(0).integers(0, 512, size=(2, 48))
    _, kwargs = harness.reference_of(config)
    with jax.default_matmul_precision("highest"):
        got, _ = model.apply(params, {}, ids)
    want = olmo_hybrid_ref.logits(params, ids, **kwargs)
    assert np.abs(np.asarray(got) - np.asarray(want)).max() < 1e-4
    # the controls round every weight product's operands, and it shows
    low = olmo_hybrid_ref.logits(params, ids, precision="fp8", **kwargs)
    assert np.abs(np.asarray(low) - np.asarray(want)).max() > 1e-2
    text = olmo_hybrid_ref.lowered_block(params, ids, precision="int8",
                                         **kwargs)
    assert "xi8>" in text


def test_the_configuration_holds_the_published_numbers():
    import json

    guide = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(guide):
        pytest.skip("no catalog beside the model-configs guide here")
    with open(guide) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Olmo-Hybrid-7B")
    assert CONFIG["source"].startswith(row["source_url"])
    for key, value in row["config"].items():
        if key in CONFIG["reduced"]:
            assert CONFIG[key] == 16 and CONFIG["published"][key] == value
        else:
            assert CONFIG[key] == value, key
    assert CONFIG["reduced"] == ["num_hidden_layers"]


def test_counts_follow_the_configurations_arithmetic():
    assert flops_hybrid.n_linear(CONFIG) == 12
    assert flops_hybrid.n_full(CONFIG) == 4
    # 12 x 215.5 M + 4 x 185.8 M + the 385.4 M of the head
    assert flops_hybrid.matmul_params(CONFIG) == pytest.approx(3.715e9,
                                                               rel=1e-3)
    assert flops_hybrid.state_bytes_per_slot_step(CONFIG) == \
        12 * 2 * 30 * 192 * 96 * 4
    assert flops_hybrid.kv_bytes_per_token(CONFIG) == 4 * 15360
    flops, nbytes = flops_hybrid.chunk_pass(CONFIG, "f32[1,30,16,64,192]")
    assert flops == 30 * 16 * (6 * 64 * 96 * 192 + 2 * 64 * 64 * 192)
    # bytes bound it: under the chip's 240 flops a byte
    assert flops / nbytes < 197e12 / 819e9
    one = flops_hybrid.serve_flops(CONFIG, 100, 101)
    assert one == pytest.approx(
        2 * 3.715e9 + 12 * 7 * 30 * 192 * 96 + 4 * 3840 * 4 * 101, rel=1e-3)


def _obs(ops):
    device = xplane.Device("/device:TPU:0", [], ops, [])
    return {"trace": xplane.Trace([device], []), "config": CONFIG,
            "peaks": harness.peaks_for("TPU v5 lite"), "chips": 1,
            "trace_span": (0.0, 4.0), "window": (0.0, 4.0),
            "trace_counters0": {hybrid_roofline.SLOT_STEPS: 1000.0},
            "trace_counters1": {hybrid_roofline.SLOT_STEPS: 1380.0},
            "records": [{"outcome": "ok", "prompt_len": 200,
                         "frames": [[1.0, 1], [1.5, 1], [2.0, 2]]}]}


def test_rooflines_read_the_kernels_by_name_and_stay_under_the_peak():
    state = flops_hybrid.state_bytes_per_slot_step(CONFIG) * 380 / 819e9
    obs = _obs([
        xplane.Op(0.0, 2 * state, "zoo_gdn_decode", "f32[48,1,5760]"),
        xplane.Op(3.0, 3.001, "zoo_gdn_chunk_fwd", "f32[1,30,16,64,192]"),
        xplane.Op(3.5, 3.5001, "zoo_paged_attention", "bf16[48,1,32,128]")])
    decode = hybrid_roofline.read(obs, {"kernel": "zoo_gdn_decode",
                                        "need": "state_bytes"})
    assert decode == pytest.approx(50.0)
    chunk = hybrid_roofline.read(obs, {"kernel": "zoo_gdn_chunk_fwd",
                                       "need": "chunk_pass"})
    assert chunk == pytest.approx(
        100 * flops_hybrid.chunk_pass(CONFIG, "f32[1,30,16,64,192]")[1]
        / 819e9 / 1e-3)
    paged = hybrid_roofline.read(obs, {"kernel": "zoo_paged_attention",
                                       "need": "kv_read"})
    # tokens 2, 3 and 4 of the stream read 201, 202 and 203 cached tokens
    assert paged == pytest.approx(100 * 606 * 61440 / 819e9 / 1e-4)
    assert 0 < hyb_serve_mfu.read(obs, {}) < 1


def test_a_program_without_the_kernels_or_the_counter_gives_nothing():
    """What the parent commit gives the new metrics: no such kernel in its
    trace, no such counter among its telemetry. The readers return None and
    do not raise."""
    obs = _obs([xplane.Op(0.0, 1.0, "zoo_paged_attention", "bf16[1]")])
    for kernel, need in (("zoo_gdn_decode", "state_bytes"),
                         ("zoo_gdn_chunk_fwd", "chunk_pass")):
        assert hybrid_roofline.read(obs, {"kernel": kernel,
                                          "need": need}) is None
    obs = _obs([xplane.Op(0.0, 1.0, "zoo_gdn_decode", "f32[48,1,5760]")])
    obs["trace_counters0"] = obs["trace_counters1"] = {}
    assert hybrid_roofline.read(obs, {"kernel": "zoo_gdn_decode",
                                      "need": "state_bytes"}) is None
    assert hybrid_roofline.read({"trace": None}, {}) is None


def test_the_cells_own_limits_lie_between_their_readings(monkeypatch):
    """``gen_open_loop_limits`` sets, for one run, the limits the cell's file
    states, and refuses one that does not part the program's reading from the
    control's."""
    from benchmark import serving_rig
    from benchmark.drivers import gen_open_loop, gen_open_loop_limits

    cell = harness.load("workloads", "gen-olmoh-reason-steady")
    assert cell["driver"] == "gen_open_loop_limits"
    for name, spec in cell["limits"].items():
        assert spec["program"] < spec["limit"] < spec["control"], name
        assert spec["why"]
    monkeypatch.setattr(gen_open_loop, "run", lambda run: "ran")
    for name in ("SERVED_GAP_TOL", "LOGIT_REL_RMS_TOL", "LOGIT_MAX_ABS_TOL"):
        monkeypatch.setattr(serving_rig, name, getattr(serving_rig, name))
    run = harness.Run(cell=cell, config={}, traffic={}, seed=0, seconds=1,
                      trace=False, out_dir="", t_process_start=0.0)
    assert gen_open_loop_limits.run(run) == "ran"
    assert serving_rig.SERVED_GAP_TOL == cell["limits"]["served_gap"]["limit"]
    assert serving_rig.LOGIT_MAX_ABS_TOL == cell["limits"]["logit_max_abs"][
        "limit"]
    only = dict(cell, limits={"served_gap": cell["limits"]["served_gap"]})
    monkeypatch.setattr(serving_rig, "LOGIT_REL_RMS_TOL", 0.05)
    gen_open_loop_limits.run(harness.Run(
        cell=only, config={}, traffic={}, seed=0, seconds=1, trace=False,
        out_dir="", t_process_start=0.0))
    assert serving_rig.LOGIT_REL_RMS_TOL == 0.05    # not stated: the rig's
    bad = dict(cell, limits={"served_gap": dict(
        cell["limits"]["served_gap"], limit=1.0)})
    with pytest.raises(ValueError, match="does not lie between"):
        gen_open_loop_limits.run(harness.Run(
            cell=bad, config={}, traffic={}, seed=0, seconds=1, trace=False,
            out_dir="", t_process_start=0.0))
