#!/usr/bin/env python3
"""Where a decode step and a prefill of a hybrid configuration spend their
device time, without the serving stack: the model's own ``decode_step`` and
``prefill`` at the configuration's sizes, timed on the host clock around
``block_until_ready`` and profiled, the device's ops printed by total time.
A diagnostic for the chip (it refuses to run elsewhere); nothing it prints is
a benchmark metric.

    python benchmark/tools/hybrid_probe.py --config olmo-hybrid-7b \
        --live 48 36 --context 800 --prefill 256 1024
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", default="olmo-hybrid-7b")
    ap.add_argument("--live", type=int, nargs="+", default=[48])
    ap.add_argument("--context", type=int, default=800)
    ap.add_argument("--prefill", type=int, nargs="*", default=[256])
    ap.add_argument("--steps", type=int, default=10)
    ap.add_argument("--top", type=int, default=25)
    ap.add_argument("--solve", action="store_true",
                    help="also time a prefill's triangular systems alone")
    args = ap.parse_args(argv)

    import jax
    import numpy as np

    from benchmark import harness
    from benchmark.readers import xplane

    if jax.devices()[0].platform != "tpu":
        print("hybrid_probe: needs a TPU", file=sys.stderr)
        return 2
    config = harness.load("configs", args.config)
    sizes = config["serving"]["ServingConfig"]
    harness.make_context(config)
    model = harness.build_model(config)
    params = harness.make_params(model, 0)
    b, page = sizes["gen_slots"], sizes["gen_page_size"]
    cfg, cache = model.init_kv_cache(
        b, page_size=page, max_seq_len=sizes["gen_max_seq_len"],
        n_pages=sizes["gen_pages"])
    jax.block_until_ready((params, cache))
    decode = jax.jit(
        lambda p, c, ids, ln, tb, sd, ti, tp: model.decode_step(
            p, c, ids, ln, tb, sd, ti, tp, page_size=page),
        donate_argnums=(1,))
    prefill = jax.jit(
        lambda p, c, ids, ln, tb, sl: model.prefill(
            p, c, ids, ln, tb, slots=sl, page_size=page),
        donate_argnums=(1,))
    zeros = np.zeros(b, np.uint32)

    def profiled(what, fn, n):
        nonlocal cache
        cache = fn(cache)                           # compile, warm
        jax.block_until_ready(cache)
        t0 = time.monotonic()
        for _ in range(n):
            cache = fn(cache)
        jax.block_until_ready(cache)
        wall = (time.monotonic() - t0) / n
        out = tempfile.mkdtemp(prefix="hybrid_probe_")
        jax.profiler.start_trace(out)
        for _ in range(n):
            cache = fn(cache)
        jax.block_until_ready(cache)
        jax.profiler.stop_trace()
        path = max(glob.glob(os.path.join(out, "plugins", "profile", "*",
                                          "*.xplane.pb")),
                   key=os.path.getmtime)
        trace = xplane.load(path)
        ops = xplane.device_ops(trace, top=args.top)
        print(json.dumps({"what": what, "wall_ms_a_call": 1e3 * wall,
                          "device_busy_ms_a_call":
                          1e3 * xplane.busy_s(trace) / n,
                          "device_ops": ops}), flush=True)

    for n_live in args.live:
        pages = -(-(args.context + 64) // page)
        table = np.zeros((b, cfg.pages_per_slot), np.int32)
        for s in range(n_live):
            table[s, :pages] = 1 + s * pages + np.arange(pages)
        lengths = np.where(np.arange(b) < n_live, args.context, 0).astype(
            np.int32)
        ids = np.ones(b, np.int32)
        profiled(f"decode_step live={n_live} context={args.context}",
                 lambda c: decode(params, c, ids, lengths, table, zeros,
                                  zeros, np.zeros(b, np.float32))[2],
                 args.steps)
    for t in args.prefill:
        table = np.zeros((1, cfg.pages_per_slot), np.int32)
        table[0, :t // page] = 1 + np.arange(t // page)
        ids = np.ones((1, t), np.int32)
        profiled(f"prefill tokens={t}",
                 lambda c: prefill(params, c, ids,
                                   np.array([t - 3], np.int32), table,
                                   np.array([0], np.int32))[1], 3)
    if args.solve:
        # the unit-triangular systems of one 1,024-token prefill of one
        # layer, through XLA's triangular_solve and through the program's
        # block inverse (ops/gated_delta.py)
        import jax.numpy as jnp

        from analytics_zoo_tpu.ops.gated_delta import _unit_lower_inverse

        rng = np.random.default_rng(0)
        n = jnp.asarray(np.tril(rng.normal(size=(1, 30, 16, 64, 64)) * 0.2,
                                -1), jnp.float32)
        rhs = jnp.asarray(rng.normal(size=(1, 30, 16, 64, 288)), jnp.float32)
        ways = {
            "triangular_solve": jax.jit(
                lambda n, r: jax.scipy.linalg.solve_triangular(
                    jnp.eye(64) + n, r, lower=True, unit_diagonal=True)),
            "block_inverse": jax.jit(lambda n, r: jnp.matmul(
                _unit_lower_inverse(n), r,
                precision=jax.lax.Precision.HIGHEST))}
        got = {}
        for name, fn in ways.items():
            got[name] = jax.block_until_ready(fn(n, rhs))
            t0 = time.monotonic()
            for _ in range(10):
                out = fn(n, rhs)
            jax.block_until_ready(out)
            print(json.dumps({"what": name, "wall_ms_a_call":
                              100 * (time.monotonic() - t0)}), flush=True)
        print(json.dumps({"what": "largest difference of the two",
                          "value": float(jnp.abs(
                              got["triangular_solve"]
                              - got["block_inverse"]).max())}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
