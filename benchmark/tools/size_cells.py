#!/usr/bin/env python3
"""Size the cells without the chip: compile their programs ahead of time for
a described v5e and read ``memory_analysis()``.

    JAX_PLATFORMS=cpu python benchmark/tools/size_cells.py [cell ...]

For a training cell it compiles the ``Estimator``'s own jitted step (one chip,
or the 2x2 mesh for a four-chip cell) at a ladder of batch sizes, and names
the largest power of two that leaves a gigabyte of the chip free. For a
serving cell it compiles the decode step and every prefill bucket of the mix
at the configured page pool, and says what is left.

Nothing runs, so nothing here is a time or a rate. ``jax.default_backend()``
says ``cpu`` in this process; the program asks it to choose its kernels, so it
is told ``tpu`` while the steps are traced (a scratch tool may steer such
code; the program gets no option for it). Each program is counted alone: what
else the process holds on the device (a second copy of the state while
``_init_state`` places it, the reference's temporaries) is not in these
numbers.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from unittest import mock

os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P  # noqa: E402

from benchmark import harness  # noqa: E402
from benchmark.serving_rig import prefill_buckets  # noqa: E402

GB = 1e9
HBM_BYTES = 16 * 2 ** 30 * 0.9846      # what a v5e reports as its limit
SPARE = 1e9


def memory(compiled) -> dict:
    m = compiled.memory_analysis()
    live = (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)
    return {"arguments_gb": m.argument_size_in_bytes / GB,
            "outputs_gb": m.output_size_in_bytes / GB,
            "aliased_gb": m.alias_size_in_bytes / GB,
            "temporaries_gb": m.temp_size_in_bytes / GB,
            "program_gb": m.generated_code_size_in_bytes / GB,
            "live_gb": live / GB}


def described_mesh(n_chips: int) -> Mesh:
    from jax.experimental import topologies

    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    devices = np.array(topo.devices[:n_chips]).reshape(n_chips, 1, 1, 1, 1, 1)
    return Mesh(devices, ("dp", "fsdp", "tp", "sp", "pp", "ep"))


def size_training(cell, config, mix, ladder=(1, 2, 4, 8, 16)) -> None:
    from analytics_zoo_tpu.common import TrainConfig
    from analytics_zoo_tpu.common.context import get_zoo_context
    from analytics_zoo_tpu.engine import Estimator

    n_chips = cell["chips"]
    mesh = described_mesh(n_chips)
    harness.make_context(dict(config, mesh={"dp": 1}))
    get_zoo_context().mesh = mesh       # the layers look their mesh up here
    model = harness.build_model(config)
    training = config["training"]
    seq_len = int(mix["seq_len"])
    fits = None
    for per_chip in ladder:
        batch = per_chip * n_chips
        est = Estimator(model, optimizer=harness.construct(
            training["optimizer"], config),
            loss=harness.named(training["loss"]), mesh=mesh,
            config=TrainConfig(**training["TrainConfig"]))
        sample = (np.zeros((batch, seq_len), np.int32),) * 2
        est._place_state = lambda state: state      # shapes only
        shapes = jax.eval_shape(lambda: est._init_state(sample))
        mode = est._update_mode()
        state = jax.tree_util.tree_map_with_path(
            lambda path, leaf: jax.ShapeDtypeStruct(
                leaf.shape, leaf.dtype, sharding=NamedSharding(
                    mesh, est._state_spec(path, leaf, mode, None))), shapes)
        batch_avals = tuple(jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=NamedSharding(mesh, P(("dp", "fsdp"))))
            for a in sample)
        est._make_train_step()
        try:
            with mock.patch("jax.default_backend", return_value="tpu"):
                lowered = est._with_policy(est._train_jit.lower)(
                    state, batch_avals)
            text = lowered.as_text()
            compiled = lowered.compile()
        except Exception as e:          # the compiler's refusal is the answer
            print(json.dumps({"cell": cell["name"], "global_batch": batch,
                              "refused": " | ".join(
                                  str(e).splitlines()[:6])[:1200]}),
                  flush=True)
            break
        mem = memory(compiled)
        n_params = sum(int(np.prod(l.shape)) for l in
                       jax.tree_util.tree_leaves(shapes["params"]))
        ok = mem["live_gb"] * GB + SPARE <= HBM_BYTES
        print(json.dumps({
            "cell": cell["name"], "chips": n_chips, "global_batch": batch,
            "per_chip_batch": per_chip, "seq_len": seq_len,
            "parameters": n_params, **{k: round(v, 3) for k, v in mem.items()},
            "fits_with_1gb_spare": ok,
            "mosaic_calls": text.count("tpu_custom_call"),
            "collectives": {n: text.count(f'stablehlo.{n}"') for n in
                            ("reduce_scatter", "all_gather", "all_reduce")}}),
            flush=True)
        if not ok:
            break
        fits = batch
    print(json.dumps({"cell": cell["name"], "largest_global_batch_that_fits":
                      fits}), flush=True)


def size_serving(cell, config, mix) -> None:
    from jax.sharding import SingleDeviceSharding

    mesh = described_mesh(1)
    chip = SingleDeviceSharding(mesh.devices.flat[0])
    from analytics_zoo_tpu.common.context import get_zoo_context

    harness.make_context(config)
    get_zoo_context().mesh = mesh       # the layers look their mesh up here
    model = harness.build_model(config)
    sizes = config["serving"]["ServingConfig"]
    page, slots = sizes["gen_page_size"], sizes["gen_slots"]

    def on_chip(tree):
        return jax.tree_util.tree_map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=chip), tree)

    params = on_chip(jax.eval_shape(
        lambda: model.build(jax.random.PRNGKey(0))[0]))
    cache = on_chip(jax.eval_shape(lambda: model.init_kv_cache(
        slots, page_size=page, max_seq_len=sizes["gen_max_seq_len"],
        n_pages=sizes["gen_pages"])[1]))
    pps = -(-sizes["gen_max_seq_len"] // page)
    weights = sum(int(np.prod(l.shape)) * l.dtype.itemsize
                  for l in jax.tree_util.tree_leaves(params))
    pool = sum(int(np.prod(l.shape)) * l.dtype.itemsize
               for l in jax.tree_util.tree_leaves(cache))
    print(json.dumps({"cell": cell["name"], "weights_gb": weights / GB,
                      "pool_gb": pool / GB, "pool_tokens":
                      sizes["gen_pages"] * page}), flush=True)

    def aval(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    # the jitted lambdas of ContinuousBatcher.__init__, cache donated
    decode = jax.jit(
        lambda p, c, ids, ln, tb, sd, ti, tp: model.decode_step(
            p, c, ids, ln, tb, sd, ti, tp, page_size=page, top_k=0),
        donate_argnums=(1,))
    prefill = jax.jit(
        lambda p, c, ids, ln, tb: model.prefill(p, c, ids, ln, tb,
                                                page_size=page),
        donate_argnums=(1,))
    programs = [("decode", decode, (
        params, cache, aval((slots,), jnp.int32), aval((slots,), jnp.int32),
        aval((slots, pps), jnp.int32), aval((slots,), jnp.uint32),
        aval((slots,), jnp.uint32), aval((slots,), jnp.float32)))]
    for bucket in prefill_buckets(mix, sizes):
        programs.append((f"prefill_{bucket}", prefill, (
            params, cache, aval((1, bucket), jnp.int32),
            aval((1,), jnp.int32), aval((1, pps), jnp.int32))))
    worst = 0.0
    for name, fn, args in programs:
        with mock.patch("jax.default_backend", return_value="tpu"):
            lowered = fn.lower(*args)
        text = lowered.as_text()
        try:
            mem = memory(lowered.compile())
        except Exception as e:          # the compiler's refusal is the answer
            print(json.dumps({"cell": cell["name"], "program": name,
                              "refused": " | ".join(
                                  str(e).splitlines()[:8])[:700]}),
                  flush=True)
            continue
        worst = max(worst, mem["live_gb"])
        print(json.dumps({"cell": cell["name"], "program": name,
                          **{k: round(v, 3) for k, v in mem.items()},
                          "mosaic_calls": text.count("tpu_custom_call")}),
              flush=True)
    print(json.dumps({"cell": cell["name"], "largest_program_live_gb":
                      round(worst, 3), "chip_gb": round(HBM_BYTES / GB, 3),
                      "share_of_chip": round(worst * GB / HBM_BYTES, 3)}),
          flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("cells", nargs="*")
    ap.add_argument("--per-chip", type=int, nargs="+", default=[1, 2, 4, 8, 16],
                    help="per-chip batch sizes to try, for training cells")
    args = ap.parse_args(argv)
    with open(os.path.join(harness.CHECKOUT, "BENCHMARK.json")) as f:
        names = args.cells or [w["name"] for w in json.load(f)["workloads"]]
    jax.config.update("jax_enable_compilation_cache", False)
    for name in names:
        cell = harness.load("workloads", name)
        config = harness.load("configs", cell["config"])
        mix = harness.load("traffic", cell["traffic"])
        if "training" in config:
            size_training(cell, config, mix, args.per_chip)
        else:
            size_serving(cell, config, mix)
    return 0


if __name__ == "__main__":
    sys.exit(main())
