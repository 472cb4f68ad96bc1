#!/usr/bin/env python3
"""The ZeRO-1 flat exchange alone, on a named configuration's parameter tree.

``python scripts/exchange_bench.py --config cerebras-gpt-1.3b-zero1-x4
--out chiprun_out/exchange.json`` builds the tree of that configuration of
``benchmark/configs`` by shape, lays it out with
``parallel.update_sharding.flat_meta`` over the configuration's ``dp`` and
runs ``flat_exchange`` with its optimizer: gradients in, parameters out,
no forward and no backward pass. Either way it prints the view (buckets,
their shape, ``own_rows_share``) and every leaf over 1% of the parameters with
how it enters the view: by its own rows, by its transpose's, or raveled.

* On the chips (as many as ``dp``): the device time of one exchange, the
  median of ``--runs`` traced runs, split into the reductions, the gathers
  that ran with nothing under them, the update of the shards, and everything
  else (the copies that lay leaves into buckets and cut them back out), with
  the ops that took most of it. The trace is read with the benchmark's reader
  (``benchmark/readers/xplane.py``).
* With no chip: the same program compiled ahead of time for a described
  ``v5e:2x2``. Nothing runs, so there is no time; the listing is what the
  program holds: the ops of its ``ENTRY`` computation by kind with the bytes
  they write (an op that updates a buffer in place counts the whole buffer),
  the collectives by kind, the count of each in the lowered text (what
  ``benchmark/drivers/train_fit.py`` counts), and the ``op_name`` of the
  largest fusions.
"""

from __future__ import annotations

import argparse
import collections
import glob
import json
import os
import re
import shutil
import statistics
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def build(config_name: str, devices):
    """The jitted exchange over ``devices`` and its arguments as shapes with
    their shardings: ``(step, (params, grads, opt_state), meta)``."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import shard_map
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from analytics_zoo_tpu.nn.optimizers import get_optimizer
    from analytics_zoo_tpu.parallel import update_sharding as upd
    from benchmark import harness

    config = harness.load("configs", config_name)
    model = harness.build_model(config)
    dtype = config.get("precision", {}).get("compute_dtype")
    tree = jax.eval_shape(lambda key: model.build(key)[0],
                          jax.random.PRNGKey(0))
    if dtype:
        tree = jax.tree_util.tree_map(
            lambda l: jax.ShapeDtypeStruct(l.shape, jnp.dtype(dtype)), tree)
    dp = int(config.get("mesh", {}).get("dp") or len(devices))
    if len(devices) < dp:
        raise SystemExit(f"{config_name} wants dp={dp}, found "
                         f"{len(devices)} devices")
    mesh = Mesh(np.array(devices[:dp]), ("dp",))
    tx = get_optimizer(harness.construct(config["training"]["optimizer"],
                                         config))
    meta = upd.flat_meta(tree, dp)
    opt = jax.eval_shape(lambda p: upd.flat_opt_init(
        tx, p, meta, keep_master=dtype is not None), tree)
    opt_specs = jax.tree_util.tree_map(
        lambda l: P(None, "dp") if l.shape == meta.bucket_shape else P(), opt)
    replicated = jax.tree_util.tree_map(lambda _: P(), tree)

    def exchange(params, grads, opt_state):
        return upd.flat_exchange(params, grads, opt_state, meta, tx)

    step = jax.jit(shard_map(
        exchange, mesh=mesh, in_specs=(replicated, replicated, opt_specs),
        out_specs=(replicated, opt_specs, P()), check_vma=False),
        donate_argnums=(0, 2))

    def place(avals, specs):
        return jax.tree_util.tree_map(
            lambda l, s: jax.ShapeDtypeStruct(
                l.shape, l.dtype, sharding=NamedSharding(mesh, s)),
            avals, specs)

    args = (place(tree, replicated), place(tree, replicated),
            place(opt, opt_specs))
    return step, args, meta, (tx, dtype)


def describe(meta) -> dict:
    return {"parameters": meta.n, "dp": meta.n_shards,
            "buckets": meta.n_buckets, "bucket_shape": list(meta.bucket_shape),
            "own_rows_share": getattr(meta, "own_rows_share", 0.0)}


def large_leaves(meta, tree) -> list:
    """Every leaf of ``tree`` over 1% of the parameters with how it enters
    the view, so that the listing names what is still raveled."""
    import jax

    names = [jax.tree_util.keystr(path) for path, _ in
             jax.tree_util.tree_leaves_with_path(tree)]
    blocks = getattr(meta, "col_blocks", (0,) * len(meta.sizes))
    return [[name, list(shape),
             "own rows" if k > 0 else "transpose" if k else "raveled"]
            for name, shape, size, k in zip(names, meta.shapes, meta.sizes,
                                            blocks) if 100 * size > meta.n]


def listing(config_name: str) -> dict:
    """No chip: compile for a described v5e 2x2 and list the program."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    from analytics_zoo_tpu.analysis.rules.collectives import (
        collective_counts, entry_ops, shape_bytes)

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    step, args, meta, _ = build(config_name, topo.devices)
    lowered = step.lower(*args)
    text = lowered.as_text()
    compiled = lowered.compile()
    hlo = compiled.as_text()
    entry = hlo[hlo.index("ENTRY"):]
    entry = entry[:entry.index("\n}")]
    fusions = collections.Counter()     # (result type, op_name) -> bytes
    for line in entry.splitlines():
        if " fusion(" in line:
            shape = line.split(" = ", 1)[1].split(" fusion(")[0]
            name = re.search(r'op_name="([^"]*)"', line)
            fusions[re.sub(r"\{[^}]*\}", "", shape)[:80],
                    name.group(1) if name else ""] += shape_bytes(shape)
    memory = compiled.memory_analysis()
    return {
        "compiled_for": "v5e:2x2 (described, nothing ran)",
        "layout": describe(meta),
        "large_leaves": large_leaves(meta, args[0]),
        "lowered_text": {k: text.count(f'stablehlo.{k}"')
                         for k in ("reduce_scatter", "all_gather",
                                   "all_reduce")},
        "collectives": collective_counts(entry),
        "entry_ops": dict(sorted(entry_ops(hlo).items(),
                                 key=lambda kv: -kv[1][1])),
        "cost_analysis_bytes": compiled.cost_analysis().get("bytes accessed"),
        "temp_bytes": getattr(memory, "temp_size_in_bytes", None),
        "largest_fusions": [[size, shape, name] for (shape, name), size
                            in fusions.most_common(12)],
    }


def measure(config_name: str, runs: int) -> dict:
    """On the chips: trace ``runs`` exchanges and split their device time."""
    import jax
    import jax.numpy as jnp

    from analytics_zoo_tpu.parallel import update_sharding as upd
    from benchmark.readers import xplane

    step, args, meta, (tx, dtype) = build(config_name, jax.devices())

    def randoms(avals, scale, seed):
        """Seeded leaves made on the devices, in the shardings asked for."""
        leaves, treedef = jax.tree_util.tree_flatten(avals)
        keys = jax.random.split(jax.random.PRNGKey(seed), len(leaves))
        return jax.tree_util.tree_unflatten(treedef, [
            jax.jit(lambda k, a=a: (scale * jax.random.normal(
                k, a.shape, jnp.float32)).astype(a.dtype),
                out_shardings=a.sharding)(k) for a, k in zip(leaves, keys)])

    params = randoms(args[0], 0.02, 1)
    grads = randoms(args[1], 1e-3, 2)
    opt = jax.jit(lambda p: upd.flat_opt_init(
        tx, p, meta, keep_master=dtype is not None),
        out_shardings=jax.tree_util.tree_map(lambda a: a.sharding,
                                             args[2]))(params)
    compiled = step.lower(*args).compile()
    for _ in range(3):
        params, opt, norm = compiled(params, grads, opt)
    jax.block_until_ready(params)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    tdir = tempfile.mkdtemp(prefix="exchange_")
    jax.profiler.start_trace(tdir, profiler_options=opts)
    try:
        for _ in range(runs):
            params, opt, norm = compiled(params, grads, opt)
        jax.block_until_ready(params)
    finally:
        jax.profiler.stop_trace()
    (pb,) = glob.glob(os.path.join(tdir, "plugins/profile/*/*.xplane.pb"))
    trace = xplane.load(pb)
    shutil.rmtree(tdir)

    shard = "[{},{}]".format(*meta.shard_shape)
    per_chip = []
    top = collections.defaultdict(float)
    for device in trace.devices:
        seen = [r for r in device.modules if r.ops]
        if len(seen) != runs:
            raise SystemExit(f"{device.name}: {len(seen)} runs in the trace, "
                             f"{runs} made")
        split = collections.defaultdict(list)
        for run in seen:
            parts = collections.defaultdict(list)
            for op in run.ops:
                # the compiler names what JAX asked for ``reduce_scatter.N``
                if re.match(r"(reduce[-_]scatter|all[-_]reduce)", op.name):
                    kind = "reductions"
                elif re.match(r"(all[-_]gather|async-collective)", op.name):
                    kind = "gathers"
                elif op.shape.endswith(shard):
                    kind = "update"
                else:
                    kind = "else"
                parts[kind].append((op.start, op.end))
                top[f"{op.name} {op.shape}"] += op.end - op.start
            for kind in ("reductions", "gathers", "update", "else"):
                split[kind].append(xplane.total(xplane.union(parts[kind])))
            split["busy"].append(xplane.total(xplane.union(
                xplane.spans(run.ops))))
            split["run"].append(run.end - run.start)
        per_chip.append({k: statistics.median(v) * 1e3
                         for k, v in split.items()})
    device = jax.devices()[0]
    stats = device.memory_stats() or {}
    return {
        "device_kind": device.device_kind, "platform": device.platform,
        "chips": len(trace.devices), "jax": jax.__version__, "runs": runs,
        "layout": describe(meta), "grad_norm": float(norm),
        "large_leaves": large_leaves(meta, args[0]),
        "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
        # medians over the runs, then the mean over the chips
        "ms": {k: statistics.fmean(c[k] for c in per_chip)
               for k in per_chip[0]},
        "top_ops_ms_per_run": [
            [name, seconds / runs / len(trace.devices) * 1e3]
            for name, seconds in sorted(top.items(),
                                        key=lambda kv: -kv[1])[:16]],
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", default="cerebras-gpt-1.3b-zero1-x4",
                    help="a configuration of benchmark/configs that trains")
    ap.add_argument("--out", help="write the readings here as JSON")
    ap.add_argument("--runs", type=int, default=12)
    args = ap.parse_args(argv)
    import jax

    result = (measure(args.config, args.runs)
              if jax.devices()[0].platform == "tpu"
              else listing(args.config))
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(result, f, indent=1)
    for key, value in result.items():
        if isinstance(value, (dict, list)) and len(value) > 6:
            print(f"{key}:")
            for item in (value.items() if isinstance(value, dict) else value):
                print("  " + json.dumps(item))
        else:
            print(f"{key}: {json.dumps(value)}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
