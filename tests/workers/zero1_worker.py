"""Worker for the REAL 2-process flat ZeRO-1 training test (ISSUE 16 sat-3).

Each process: pick up the launcher-threaded backend config (cpu + gloo
collectives), join the jax.distributed job via init_zoo_context, then run
flat ZeRO-1 weight-update sharding (PR 5, parallel/update_sharding.py) as
genuine 2-process training: the optimizer state lives dp-sharded, every
step is one ``psum_scatter`` in + one tiled ``all_gather`` out per bucket
(this model fits one) across the two processes over gloo.

Before training, the worker runs the collective-budget lint on the jitted
step (jaxpr layer — trace only) and asserts the budget "one reduce-scatter
and one all-gather per bucket per step" holds; the finding count lands
in result-<rank>.json together with a post-training parameter digest so the
test can assert both ranks hold identical weights.
"""

import json
import os
import sys

# python puts the SCRIPT's dir (tests/workers) on sys.path, not the repo root
sys.path.insert(0, os.path.abspath(os.path.join(os.path.dirname(__file__),
                                                "..", "..")))

from analytics_zoo_tpu.common.cluster import configure_worker_jax

configure_worker_jax()       # platform + collectives BEFORE backend init

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import NamedSharding, PartitionSpec as P


def main():
    out_dir = sys.argv[1]
    rank = int(os.environ["ZOO_TPU_PROCESS_ID"])
    n_proc = int(os.environ["ZOO_TPU_NUM_PROCESSES"])

    from analytics_zoo_tpu.analysis import RuleContext, lint_traced
    from analytics_zoo_tpu.common import (MeshConfig, RuntimeConfig,
                                          init_zoo_context)
    from analytics_zoo_tpu.common.cluster import barrier
    from jax import shard_map
    from analytics_zoo_tpu.parallel import update_sharding as upd

    ctx = init_zoo_context(RuntimeConfig(platform="cpu",
                                         mesh=MeshConfig(dp=0)))
    assert ctx.process_count == n_proc, (ctx.process_count, n_proc)
    mesh = ctx.mesh
    n_dev = mesh.shape["dp"]

    # deterministic global problem; every rank derives the same params
    rng = np.random.default_rng(11)
    w0 = rng.normal(size=(6, 1)).astype("float32") * 0.1
    x_all = rng.normal(size=(64, 6)).astype("float32")
    w_true = rng.normal(size=(6, 1)).astype("float32")
    y_all = x_all @ w_true

    params = {"w": jnp.asarray(w0), "b": jnp.zeros((1,), jnp.float32)}
    tx = optax.adam(0.05)
    meta = upd.flat_meta(params, n_dev)
    opt_state = upd.flat_opt_init(tx, params, meta, keep_master=True)

    def step(params, opt_state, x, y):
        def loss_fn(p):
            pred = x @ p["w"] + p["b"]
            return jnp.mean((pred - y) ** 2)

        loss, grads = jax.value_and_grad(loss_fn)(params)
        new_params, new_opt, gnorm = upd.flat_exchange(
            params, grads, opt_state, meta, tx, axis="dp")
        return new_params, new_opt, jax.lax.pmean(loss, "dp"), gnorm

    # ZeRO-1 layout: each bucket's optimizer matrices (masters, adam
    # moments) live dp-sharded over their columns, scalars (step counts)
    # replicated — the same rule Estimator._state_spec applies in flat mode
    opt_specs = jax.tree_util.tree_map(
        lambda l: (P(None, "dp") if tuple(getattr(l, "shape", ()))
                   == meta.bucket_shape else P()), opt_state)
    sharded_step = shard_map(
        step, mesh=mesh,
        in_specs=(P(), opt_specs, P("dp"), P("dp")),
        out_specs=(P(), opt_specs, P(), P()), check_vma=False)

    # -- collective-budget lint: ONE reduce-scatter and ONE all-gather per
    # bucket per step (trace-only; the incidental scalar psums for the
    # loss/grad-norm are all-reduces and not part of the budget)
    lint_ctx = RuleContext(where="zero1_worker.step",
                           expect_collectives={
                               "reduce-scatter": meta.n_buckets,
                               "all-gather": meta.n_buckets})
    findings = lint_traced(
        sharded_step, params, opt_state,
        jax.ShapeDtypeStruct((64, 6), jnp.float32),
        jax.ShapeDtypeStruct((64, 1), jnp.float32),
        ctx=lint_ctx, rules=["collective-budget"])
    assert not findings, [str(f) for f in findings]

    step_jit = jax.jit(sharded_step)

    # lay the replicated params / dp-sharded optimizer state onto the
    # global mesh (every process computed identical values from the seed)
    params = jax.tree_util.tree_map(
        lambda l: jax.device_put(l, NamedSharding(mesh, P())), params)
    opt_state = jax.tree_util.tree_map(
        lambda l, s: jax.device_put(l, NamedSharding(mesh, s)),
        opt_state, opt_specs)

    def to_global(a, spec):
        return jax.make_array_from_process_local_data(
            NamedSharding(mesh, spec), np.asarray(a))

    # dp-sharded batch: this rank materializes ONLY its rows
    local = slice(rank * 64 // n_proc, (rank + 1) * 64 // n_proc)
    xg = to_global(x_all[local], P("dp"))
    yg = to_global(y_all[local], P("dp"))

    losses = []
    for _ in range(60):
        params, opt_state, loss, gnorm = step_jit(params, opt_state, xg, yg)
        losses.append(float(loss))
    barrier()

    digest = float(sum(np.abs(np.asarray(jax.device_get(v))).sum()
                       for v in jax.tree_util.tree_leaves(params)))
    with open(os.path.join(out_dir, f"result-{rank}.json"), "w") as f:
        json.dump({"rank": rank, "process_count": ctx.process_count,
                   "first_loss": losses[0], "last_loss": losses[-1],
                   "param_digest": digest,
                   "lint_findings": len(findings),
                   "devices": int(n_dev)}, f)
    barrier()


if __name__ == "__main__":
    main()
