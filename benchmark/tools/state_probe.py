#!/usr/bin/env python3
"""How much of device 0 does ``Estimator._init_state`` take before it places
the state (ROADMAP D14a)? On one chip, for a training configuration at a few
depths, ascending: builds the seeded f32 parameters as the training driver
does, lets the Estimator build its state from them, and prints the device's
``peak_bytes_in_use`` after each depth. What placing the state over a mesh
adds on device 0 (its own shards, copied) comes on top.

    python benchmark/tools/state_probe.py --config cerebras-gpt-1.3b-zero1-x4 --layers 8 10 12
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--layers", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)

    import jax
    import numpy as np

    from analytics_zoo_tpu.common import TrainConfig
    from analytics_zoo_tpu.engine import Estimator
    from benchmark import harness

    config = harness.load("configs", args.config)
    ctx = harness.make_context(dict(config, mesh={"dp": 0}))
    device = jax.devices()[0]
    for depth in sorted(args.layers):
        cfg = dict(config, n_layer=depth)
        model = harness.build_model(cfg)
        training = cfg["training"]
        est = Estimator(model, optimizer=harness.construct(
            training["optimizer"], cfg), loss=harness.named(training["loss"]),
            mesh=ctx.mesh, config=TrainConfig(**training["TrainConfig"]))
        est.initial_weights = (harness.make_params(model, 0), {})
        sample = (np.zeros((1, cfg["n_positions"]), np.int32),) * 2
        state = None
        try:
            state = est._init_state(sample)
            jax.block_until_ready(state)
            outcome = "built"
        except Exception as e:
            outcome = "refused: " + str(e).splitlines()[0][:300]
        n = sum(int(np.prod(l.shape)) for l in jax.tree_util.tree_leaves(
            est.initial_weights[0]))
        stats = device.memory_stats() or {}
        print(json.dumps({
            "platform": device.platform, "kind": device.device_kind,
            "n_layer": depth, "parameters": n, "outcome": outcome,
            "peak_gb": stats.get("peak_bytes_in_use", 0) / 1e9,
            "in_use_gb": stats.get("bytes_in_use", 0) / 1e9,
            "limit_gb": stats.get("bytes_limit", 0) / 1e9}), flush=True)
        del state, est, model
    return 0


if __name__ == "__main__":
    sys.exit(main())
