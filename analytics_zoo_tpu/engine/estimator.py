"""Training engine: the ``Estimator`` / ``InternalDistriOptimizer`` replacement.

Parity map (reference → here):
* ``AbstractEstimator.train/evaluate`` (/root/reference/zoo/.../pipeline/estimator/
  Estimator.scala:33-46) → :class:`Estimator.fit/evaluate`.
* ``InternalDistriOptimizer.train`` (Topology.scala:1086-1269): per-iteration Spark
  job + AllReduceParameter block-manager gradient exchange → ONE jitted step over a
  ``jax.sharding.Mesh``; the batch is sharded over the ``dp``(+``fsdp``) axes, params
  are replicated (or fsdp-sharded), and XLA inserts the gradient ``psum`` over ICI.
  The whole hot loop (Topology.scala:1188-1207's optimizeModels) is a single
  device-side program — no driver round-trips.
* Failure retry from checkpoint (Topology.scala:1181-1263) → :meth:`Estimator.fit`'s
  retry loop (``retry_times`` = ``bigdl.failure.retryTimes`` default 5).
* Gradient clipping config (Topology.scala:161-194) → ``TrainConfig.gradient_clip_*``.
* TB summaries Loss/LearningRate/Throughput (Topology.scala:196-239) → TrainSummary.
"""

from __future__ import annotations

import logging
import signal
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import NamedSharding, PartitionSpec as P

from ..common import memwitness as _mw
from ..common import telemetry as _tm
from ..common.chaos import chaos_point
from ..common.config import TrainConfig
from ..common.context import get_zoo_context
from ..common.resilience import ResilienceError, RetryPolicy
from ..common.summary import TrainSummary, ValidationSummary
from ..common.triggers import (EveryEpoch, MaxEpoch, SeveralIteration, Trigger,
                               TrainerState)
from ..data.featureset import FeatureSet
from ..data.pipeline import PrefetchLoader
from ..nn.losses import get_loss
from ..nn.metrics import Metric, get_metric
from ..nn.module import Layer, cast_params, precision_policy
from ..nn.optimizers import get_optimizer, with_clipping
from ..parallel import update_sharding as upd
from . import checkpoint as ckpt

logger = logging.getLogger("analytics_zoo_tpu.estimator")

# per-step training breakdown (ISSUE 3): is the loop data-bound or
# device-bound? DataWait = time blocked on the host input pipeline;
# Compute = everything else in the step window (dispatch + device execution,
# synced at each log point by the loss transfer). The same numbers flush to
# TrainSummary (TensorBoard + metrics.jsonl) and land here for /metrics.
_STEPS = _tm.counter("zoo_train_steps_total", "Optimizer steps run")
_UPDATE_BUCKETS = _tm.gauge("zoo_train_update_buckets",
                            "Buckets of the ZeRO-1 flat exchange (one "
                            "reduce-scatter + one all-gather each per step)")
_UPDATE_OWN_ROWS = _tm.gauge("zoo_train_update_own_rows_share",
                             "Share of the parameters that enter the flat "
                             "exchange's buckets by their own rows or their "
                             "transpose's (cut into column blocks, not "
                             "raveled and re-cut)")
_DATA_WAIT = _tm.histogram("zoo_train_data_wait_seconds",
                           "Per-step host wait on the input pipeline")
_COMPUTE = _tm.histogram("zoo_train_compute_seconds",
                         "Per-step dispatch + device time (window mean, "
                         "synced at log points)")
_COMPILES = _tm.counter("zoo_train_compiles_total",
                        "Train-step executables built (first dispatch of a "
                        "jitted step/scan-block)")
_COMPILE_TIME = _tm.histogram("zoo_train_compile_seconds",
                              "Wall time of first-dispatch (compile) steps",
                              buckets=(0.1, 0.25, 0.5, 1, 2.5, 5, 10, 30,
                                       60, 120))
_ROLLBACKS = _tm.counter("zoo_train_rollbacks_total",
                         "Checkpoint rollbacks taken by fit's retry loop")
_CHECKPOINTS = _tm.counter("zoo_train_checkpoints_total",
                           "Checkpoints saved")
_SIGTERM_EXITS = _tm.counter("zoo_train_sigterm_exits_total",
                             "Graceful SIGTERM teardowns (final checkpoint "
                             "+ exit 143)")
_GRAD_NORM = _tm.histogram("zoo_train_grad_norm",
                           "f32 global (pre-clip) gradient L2 norm, observed "
                           "at log points",
                           buckets=(0.001, 0.01, 0.1, 0.5, 1, 2.5, 5, 10, 25,
                                    100, 1000))


class _GracefulStop(BaseException):
    """Raised inside the epoch loop when SIGTERM requested a clean exit.
    BaseException so the retry-from-checkpoint handler cannot absorb it."""


def _overlay(base: dict, donated: dict) -> dict:
    """Deep-merge donated weights over a fresh init (missing keys keep their
    fresh values — the transfer-learning partial-donor path)."""
    out = dict(base)
    for k, v in donated.items():
        if isinstance(v, dict) and isinstance(out.get(k), dict):
            out[k] = _overlay(out[k], v)
        else:
            out[k] = v
    return out


def _walk_layers(module):
    """Yield every layer reachable through nested containers (graph/sequential
    sub-modules expose ``.layers``)."""
    seen = set()
    stack = [module]
    while stack:
        m = stack.pop()
        if id(m) in seen:
            continue
        seen.add(id(m))
        yield m
        stack.extend(getattr(m, "layers", ()) or ())


def _as_featureset(data, batch_size=None) -> FeatureSet:
    if isinstance(data, FeatureSet):
        return data
    if isinstance(data, tuple) and len(data) == 2:
        return FeatureSet.from_numpy(data[0], data[1])
    raise TypeError(f"cannot build FeatureSet from {type(data)}")


class Estimator:
    """Drives a compiled train step over the global mesh."""

    def __init__(self, model: Layer, optimizer="adam", loss="mse",
                 mesh=None, config: Optional[TrainConfig] = None,
                 param_sharding: Optional[Callable] = None):
        self.model = model
        self.loss_fn = get_loss(loss)
        self.config = config or TrainConfig()
        self._base_tx = get_optimizer(optimizer)
        self._train_step = None
        self._step_shapes: set = set()
        self._rebuild_tx()
        # flat (BigDL AllReduceParameter-layout) update sharding: static
        # flattening meta, built by _init_state when the mode engages
        self._flat_meta = None
        self.mesh = mesh if mesh is not None else get_zoo_context().mesh
        # models that carry their own placement strategy (e.g.
        # PipelinedTransformerLM's stage-over-pp layout) expose
        # ``param_spec(path, leaf) -> PartitionSpec``; an explicit
        # param_sharding argument still wins
        if param_sharding is None:
            param_sharding = getattr(model, "param_spec", None)
        self.param_sharding = param_sharding
        self.train_state: Optional[Dict[str, Any]] = None
        self.trainer_state = TrainerState()
        # _step_shapes/_scan_shapes: compile-event detection keys on the
        # dispatched batch signature (jit re-traces per shape/dtype): a second
        # fit() with a new batch_size is a fresh compile that must be
        # attributed to zoo_train_compile_*, not silently smeared into that
        # window's ComputeMs (_step_shapes is created before _rebuild_tx above)
        self._scan_shapes: set = set()
        self.train_summary: Optional[TrainSummary] = None
        self.val_summary: Optional[ValidationSummary] = None
        self._eval_cache: Dict[Any, Callable] = {}
        # optional (params, model_state) replacing the fresh init — used by
        # model-bundle loading (ZooModel.loadModel); weights were already read
        # from disk eagerly by KerasNet.load_weights
        self.initial_weights: Optional[tuple] = None
        # set True when initial_weights holds only SOME layers' params
        # (transfer learning) — missing slots then keep a fresh init
        self.initial_weights_partial = False
        # at-most-one-in-flight async checkpoint writer (created lazily on
        # the first save when config.async_checkpoint)
        self._ckpt_writer: Optional[ckpt.CheckpointWriter] = None
        # recompilation-hazard tracker over step signatures (lazy; see
        # _note_step_signature)
        self._recompile_tracker = None

    def _rebuild_tx(self) -> "Estimator":
        """(Re)compose the optimizer chain from ``_base_tx``: clipping first,
        then — under mixed precision (TrainConfig.compute_dtype="bfloat16",
        where fwd/bwd run in the compute dtype against f32 master weights
        living ONLY in the possibly-dp-sharded optimizer state) — the
        ``with_master_weights`` wrapper whose "updates" ARE the new
        low-precision params. Invalidates the compiled step. The single
        authority for this wiring — __init__, set_gradient_clipping, and
        _refresh_precision all go through here."""
        self.tx = with_clipping(self._base_tx, self.config.gradient_clip_norm,
                                self.config.gradient_clip_value)
        self._mp_dtype = None
        if (self.config.compute_dtype is not None
                and jnp.dtype(self.config.compute_dtype) != jnp.float32):
            self._mp_dtype = jnp.dtype(self.config.compute_dtype)
            self.tx = upd.with_master_weights(self.tx)
        self._train_step = None
        self._step_shapes.clear()
        return self

    def set_gradient_clipping(self, clip_norm: Optional[float] = None,
                              clip_value: Optional[tuple] = None) -> "Estimator":
        """Re-wrap the optimizer with clipping after construction
        (setGradientClippingByL2Norm / setConstantGradientClipping parity).

        Must be called before the first fit step; it rebuilds the compiled step.
        """
        if self.train_state is not None:
            raise RuntimeError("set clipping before training starts: optimizer "
                               "state is already initialized")
        self.config.gradient_clip_norm = clip_norm
        self.config.gradient_clip_value = clip_value
        return self._rebuild_tx()

    def _refresh_precision(self) -> "Estimator":
        """Recompute the mixed-precision wiring after ``config.compute_dtype``
        changed post-construction (the orca facade's per-fit override). Must
        run before the first fit step — the state dtype layout is built once."""
        if self.train_state is not None:
            raise RuntimeError("compute_dtype must be set before training "
                               "starts: params/optimizer dtypes are already "
                               "laid out")
        return self._rebuild_tx()

    # ------------------------------------------------------------------ shardings
    def _batch_axes(self) -> Tuple[str, ...]:
        return ("dp", "fsdp")

    def _batch_sharding(self) -> NamedSharding:
        return NamedSharding(self.mesh, P(self._batch_axes()))

    def _replicated(self) -> NamedSharding:
        return NamedSharding(self.mesh, P())

    def _update_mode(self) -> Optional[str]:
        """Weight-update sharding mode: ``None`` (replicated update),
        ``"flat"`` (BigDL-layout reduce-scatter/shard-update/all-gather inside
        shard_map — pure-dp meshes), or ``"gspmd"`` (per-leaf dp-extended
        optimizer-state placement composed with the fsdp/tp rules)."""
        us = self.config.update_sharding
        if not us:
            return None
        dp = self.mesh.shape.get("dp", 1)
        if dp <= 1:
            return None
        pure_dp = all(size == 1 for name, size in self.mesh.shape.items()
                      if name != "dp")
        if us == "gspmd":
            return "gspmd"
        if pure_dp and self.param_sharding is None:
            return "flat"
        if us == "flat":
            logger.warning("update_sharding='flat' needs a pure-dp mesh and "
                           "no param_sharding rules; using gspmd placement")
        return "gspmd"

    def _state_spec(self, path, leaf, mode, upd_rule) -> P:
        """PartitionSpec for one train-state leaf: base param rule everywhere,
        with the opt_state subtree overridden by the update-sharding mode."""
        in_opt = bool(path) and str(getattr(path[0], "key", "")) == "opt_state"
        if mode == "flat":
            if (in_opt and self._flat_meta is not None
                    and tuple(getattr(leaf, "shape", ()))
                    == self._flat_meta.bucket_shape):
                return P(None, "dp")     # replica i owns column block i
            return P()       # flat mode implies no base rules (pure-dp mesh)
        if in_opt and upd_rule is not None:
            return upd_rule(path, leaf)
        return (self.param_sharding(path, leaf)
                if self.param_sharding is not None else P())

    def _place_state(self, state):
        """Lay train state onto the mesh: replicated by default, per
        ``param_sharding(path, leaf) -> PartitionSpec`` (fsdp/tp rules), and —
        under update sharding — the opt_state subtree dp-sharded congruent
        with the grad shards (ZeRO-1: each replica owns 1/dp of the optimizer
        state, master weights included)."""
        mode = self._update_mode()
        if self.param_sharding is None and mode is None:
            return jax.device_put(state, self._replicated())
        upd_rule = (upd.make_update_sharding(self.mesh, self.param_sharding)
                    if mode == "gspmd" else None)

        def put(path, leaf):
            spec = self._state_spec(path, leaf, mode, upd_rule)
            return jax.device_put(leaf, NamedSharding(self.mesh, spec))

        return jax.tree_util.tree_map_with_path(put, state)

    def _to_global(self, host_batch):
        """Host-local shard → global sharded jax.Array (multi-host safe).

        Partial trailing batches that don't divide the dp axes fall back to a
        replicated layout (evaluate/predict only; training drops remainders).
        """
        sharding = self._batch_sharding()
        n_shards = 1
        for ax in self._batch_axes():
            n_shards *= self.mesh.shape[ax]

        def put(a):
            a = np.asarray(a)
            local_ok = (a.shape[0] * get_zoo_context().process_count) % n_shards == 0
            s = sharding if local_ok else self._replicated()
            return jax.make_array_from_process_local_data(s, a)

        return jax.tree_util.tree_map(put, host_batch)

    # ------------------------------------------------------------------- build
    def _init_state(self, sample_batch, seed: int = 0):
        x = sample_batch[0]
        in_shape = (tuple(x[0].shape[1:]) if isinstance(x, (tuple, list))
                    else tuple(x.shape[1:]))
        if isinstance(x, (tuple, list)):
            in_shape = [tuple(xi.shape[1:]) for xi in x]
        rng = jax.random.PRNGKey(seed)
        k_init, k_train = jax.random.split(rng)
        if self.initial_weights is not None:
            params, mstate = self.initial_weights
            if self.initial_weights_partial and isinstance(params, dict):
                # partial donation (transfer learning: some layers donated,
                # new heads freshly initialized) — overlay on a fresh init.
                # Opt-in flag: the common full-donation/resume path must not
                # pay a throwaway fresh build.
                fresh_p, fresh_s = self.model.build(k_init, in_shape)
                params = _overlay(fresh_p, params)
                mstate = _overlay(fresh_s, mstate or {})
        else:
            params, mstate = self.model.build(k_init, in_shape)
        # params come out of build() in f32 (param_dtype policy); under mixed
        # precision the MODEL copy is cast down and the f32 values survive
        # only as master weights inside the optimizer state
        model_params = (cast_params(params, self._mp_dtype)
                        if self._mp_dtype is not None else params)
        mode = self._update_mode()
        if mode == "flat":
            meta = self._flat_meta = upd.flat_meta(model_params,
                                                   self.mesh.shape["dp"])
            # one executable for all buckets: built op by op, every bucket's
            # pieces compile (or load from the cache) on their own shapes
            opt_state = jax.jit(lambda p: upd.flat_opt_init(
                self._base_tx, p, meta,
                keep_master=self._mp_dtype is not None))(params)
            _UPDATE_BUCKETS.set(meta.n_buckets)
            _UPDATE_OWN_ROWS.set(meta.own_rows_share)
            logger.info("update sharding: flat over dp=%d, %d parameters in "
                        "%d bucket(s) of %d x %d, %.4f of them by their own "
                        "rows or their transpose's", meta.n_shards, meta.n, meta.n_buckets,
                        *meta.bucket_shape, meta.own_rows_share)
        else:
            opt_state = self.tx.init(params)
        state = {
            "params": model_params,
            "opt_state": opt_state,
            "model_state": mstate,
            "step": jnp.zeros((), jnp.int32),
            "rng": k_train,
        }
        return self._place_state(state)

    def _grads_fn(self, micro_constraint=None):
        """Build ``(params, mstate, rng, batch) -> (loss, new_mstate, grads)``.

        With ``config.grad_accum_steps == K > 1`` the batch is reshaped to K
        microbatches consumed by a ``lax.scan`` inside the jitted step (the
        grad accumulator rides the scan carry, which XLA updates in place —
        the donated-carry property): grads accumulate in f32 and are divided
        by K once, so the result is the global-batch mean gradient and any
        gradient collective pays once per GLOBAL step, amortizing comm K×.
        ``micro_constraint``: NamedSharding for the (K, micro, ...) layout on
        the GSPMD paths (None inside shard_map, where data is already local).
        """
        model, loss_fn = self.model, self.loss_fn
        K = max(1, int(self.config.grad_accum_steps))

        def loss_of(p, mstate, rng, x, y):
            y_hat, new_mstate = model.apply(p, mstate, x, training=True,
                                            rng=rng)
            total = loss_fn(y, y_hat)
            # 0.0 unless layers carry w/b regularizers
            reg_fn = getattr(model, "regularization", None)
            if reg_fn is not None:
                total = total + reg_fn(p)
            return total, new_mstate

        grad_of = jax.value_and_grad(loss_of, has_aux=True)

        def single(params, mstate, rng, batch):
            x, y = batch
            (loss, new_mstate), grads = grad_of(params, mstate, rng, x, y)
            return loss, new_mstate, grads

        if K == 1:
            return single

        def accum(params, mstate, rng, batch):
            def to_micro(a):
                return a.reshape((K, a.shape[0] // K) + a.shape[1:])

            micro = jax.tree_util.tree_map(to_micro, batch)
            if micro_constraint is not None:
                micro = jax.tree_util.tree_map(
                    lambda a: jax.lax.with_sharding_constraint(
                        a, micro_constraint), micro)

            def body(carry, mb):
                acc, mst, i = carry
                loss, mst2, g = single(params, mst,
                                       jax.random.fold_in(rng, i), mb)
                acc = jax.tree_util.tree_map(
                    lambda a, gg: a + gg.astype(a.dtype), acc, g)
                return (acc, mst2, i + jnp.int32(1)), loss

            zero = jax.tree_util.tree_map(
                lambda p: jnp.zeros(jnp.shape(p), jnp.float32), params)
            (acc, new_mstate, _), losses = jax.lax.scan(
                body, (zero, mstate, jnp.int32(0)), micro)
            grads = jax.tree_util.tree_map(lambda g: g / K, acc)
            return jnp.mean(losses), new_mstate, grads

        return accum

    def _step_fn(self):
        """The raw (state, batch) -> (state, (loss, grad_norm)) transition
        shared by the per-batch jitted step and the scanned device-cached
        epoch runner. Three update layouts (see parallel/update_sharding.py):
        replicated (classic), "gspmd" (grads constrained to dp-extended specs
        so the partitioner reduce-scatters into the sharded optimizer state
        and all-gathers params back), and "flat" (the shard_map BigDL-layout
        exchange, built by _flat_step_fn)."""
        mode = self._update_mode()
        if mode == "flat":
            return self._flat_step_fn()
        cfg = self.config
        mesh = self.mesh
        mp = self._mp_dtype is not None
        tx = self.tx
        base_rule = self.param_sharding
        micro_ns = (NamedSharding(mesh, P(None, self._batch_axes()))
                    if cfg.grad_accum_steps > 1 else None)
        grads_fn = self._grads_fn(micro_constraint=micro_ns)
        upd_rule = (upd.make_update_sharding(mesh, base_rule)
                    if mode == "gspmd" else None)

        def step(state, batch):
            rng = jax.random.fold_in(state["rng"], state["step"])
            loss, new_mstate, grads = grads_fn(
                state["params"], state["model_state"], rng, batch)
            # f32 grads from here on: the accumulation path already summed in
            # f32; the single-batch mixed-precision path casts up so clipping
            # and the update run against full-precision values
            grads = jax.tree_util.tree_map(
                lambda g: g.astype(jnp.float32), grads)
            gnorm = optax.global_norm(grads)
            if upd_rule is not None:
                # dp-sharded grad placement congruent with the optimizer
                # state: the partial→sharded transition is the reduce-scatter
                grads = jax.tree_util.tree_map_with_path(
                    lambda p, g: jax.lax.with_sharding_constraint(
                        g, NamedSharding(mesh, upd_rule(p, g))), grads)
            updates, new_opt = tx.update(grads, state["opt_state"],
                                         state["params"])
            if mp:
                # with_master_weights returns the NEW low-precision params
                new_params = updates
            else:
                new_params = optax.apply_updates(state["params"], updates)
            if upd_rule is not None:
                # back to the base (replicated / fsdp/tp) layout: the
                # sharded→base transition is the params all-gather
                def back(path, leaf):
                    spec = base_rule(path, leaf) if base_rule else P()
                    return jax.lax.with_sharding_constraint(
                        leaf, NamedSharding(mesh, spec))

                new_params = jax.tree_util.tree_map_with_path(back, new_params)
            new_state = {
                "params": new_params,
                "opt_state": new_opt,
                "model_state": new_mstate,
                "step": state["step"] + 1,
                "rng": state["rng"],
            }
            return new_state, (loss, gnorm)

        return step

    def _flat_step_fn(self):
        """Pure-dp weight-update sharding: the whole step runs inside
        ``shard_map`` (manual over the mesh), so per-replica grads stay local
        through the accumulation scan and the exchange is structurally one
        reduce-scatter + one params all-gather per BUCKET per global step —
        BigDL ``AllReduceParameter``'s slice-owner update, TPU-native."""
        from jax import shard_map

        cfg = self.config
        mesh = self.mesh
        base_tx = self._base_tx
        batch_axes = self._batch_axes()
        grads_fn = self._grads_fn()

        def step(state, batch):
            meta = self._flat_meta

            def body(st, bt):
                rng = jax.random.fold_in(st["rng"], st["step"])
                # decorrelate per-replica dropout/negative-sampling masks
                rng = jax.random.fold_in(rng, jax.lax.axis_index("dp"))
                loss, mstate2, grads = grads_fn(st["params"],
                                                st["model_state"], rng, bt)
                new_params, new_opt, gnorm = upd.flat_exchange(
                    st["params"], grads, st["opt_state"], meta, base_tx,
                    clip_norm=cfg.gradient_clip_norm,
                    clip_value=cfg.gradient_clip_value)
                loss = jax.lax.pmean(loss, "dp")
                # keep float model state (batchnorm EMAs computed from LOCAL
                # batch stats) replicated-consistent across replicas
                mstate2 = jax.tree_util.tree_map(
                    lambda a: (jax.lax.pmean(a, "dp")
                               if jnp.issubdtype(jnp.asarray(a).dtype,
                                                 jnp.floating) else a),
                    mstate2)
                new_state = {
                    "params": new_params,
                    "opt_state": new_opt,
                    "model_state": mstate2,
                    "step": st["step"] + 1,
                    "rng": st["rng"],
                }
                return new_state, (loss, gnorm)

            mode_rule = None  # flat mode: no per-leaf gspmd rule
            state_specs = jax.tree_util.tree_map_with_path(
                lambda p, l: self._state_spec(p, l, "flat", mode_rule), state)
            batch_specs = jax.tree_util.tree_map(
                lambda _: P(batch_axes), batch)
            fn = shard_map(body, mesh=mesh,
                           in_specs=(state_specs, batch_specs),
                           out_specs=(state_specs, (P(), P())),
                           check_vma=False)
            return fn(state, batch)

        return step

    def _with_policy(self, fn):
        """Engage TrainConfig.compute_dtype as the precision policy for the
        dynamic extent of each dispatch (policy is read at TRACE time by the
        layers' ``as_compute``; wrapping the call covers the trace)."""
        if self.config.compute_dtype is None:
            return fn
        dt = self.config.compute_dtype

        def wrapped(*args):
            with precision_policy(compute_dtype=dt):
                return fn(*args)

        return wrapped

    def _state_out_shardings(self):
        """``out_shardings`` that return the train state in the layout
        ``_place_state`` gave it. Left to the partitioner, a tensor-parallel
        step hands back leaves in layouts of its own choosing (a replicated
        bias comes back split over tp), so the second step sees new input
        shardings and compiles the whole program again."""
        return (jax.tree_util.tree_map(lambda l: l.sharding,
                                       self.train_state), None)

    def _make_train_step(self):
        donate = (0,) if self.config.donate_state else ()
        self._train_jit = jax.jit(self._step_fn(), donate_argnums=donate,
                                  out_shardings=self._state_out_shardings())
        return self._with_policy(self._train_jit)

    def lower_train_step(self, host_batch):
        """``jax.stages.Lowered`` of the jitted per-batch train step on the
        current state and one host batch: the program ``fit`` dispatches,
        for a caller that wants to read it (StableHLO text and the kernels
        in it, cost and memory analysis) rather than trust the routing."""
        if self._train_step is None:
            self._train_step = self._make_train_step()
        return self._with_policy(self._train_jit.lower)(
            self.train_state, self._to_global(host_batch))

    def _make_scan_block(self):
        """Device-cached mode: one jitted call running ``scan_block_steps``
        train steps via ``lax.scan``, gathering each batch from the
        HBM-resident dataset by index (TPU-first replacement for the
        reference's per-iteration Spark job — zero host work per step)."""
        step = self._step_fn()
        batch_sharding = self._batch_sharding()

        def block(state, data, idx_mat):
            def body(st, idxs):
                batch = jax.tree_util.tree_map(
                    lambda a: jax.lax.with_sharding_constraint(
                        jnp.take(a, idxs, axis=0), batch_sharding), data)
                return step(st, batch)

            return jax.lax.scan(body, state, idx_mat)

        donate = (0,) if self.config.donate_state else ()
        return self._with_policy(jax.jit(
            block, donate_argnums=donate,
            out_shardings=self._state_out_shardings()))

    # --------------------------------------------------------------------- fit
    def fit(self, data, batch_size: Optional[int] = None,
            epochs: Optional[int] = None, end_trigger: Optional[Trigger] = None,
            validation_data=None, validation_metrics: Sequence = (),
            checkpoint_trigger: Optional[Trigger] = None, seed: int = 0):
        """Train until ``end_trigger`` (default: MaxEpoch(config.max_epochs)).

        ``data``: FeatureSet or (x, y) arrays. ``batch_size`` is global.
        The loop structure mirrors InternalDistriOptimizer.train
        (Topology.scala:1086-1269) including retry-from-checkpoint — the
        retry budget is policy-driven (TrainConfig.retry_times /
        retry_backoff_s / retry_deadline_s through a
        :class:`~analytics_zoo_tpu.common.resilience.RetryPolicy`), and with
        ``config.graceful_shutdown`` a SIGTERM mid-fit saves one final
        checkpoint before exiting with status 143 — the preemption-safe
        teardown a supervisor (k8s, borg) expects.
        """
        cfg = self.config
        batch_size = batch_size or cfg.batch_size
        accum = max(1, int(cfg.grad_accum_steps))
        if accum > 1:
            n_shards = 1
            for ax in self._batch_axes():
                n_shards *= self.mesh.shape[ax]
            if batch_size % (accum * n_shards):
                raise ValueError(
                    f"batch_size={batch_size} must divide by "
                    f"grad_accum_steps={accum} x dp-shards={n_shards}: each "
                    f"of the {accum} microbatches is itself sharded over the "
                    f"dp axes — pick batch_size as a multiple of "
                    f"{accum * n_shards}")
        train_set = _as_featureset(data)
        end_trigger = end_trigger or MaxEpoch(epochs if epochs is not None
                                              else cfg.max_epochs)
        # Default cadence is the epoch-end save built into _run_epoch; a mid-epoch
        # trigger is only installed when explicitly requested (EveryEpoch parity).
        if checkpoint_trigger is None and cfg.checkpoint_every_n_iters:
            checkpoint_trigger = SeveralIteration(cfg.checkpoint_every_n_iters)

        # init or resume
        first = None
        if self.train_state is None:
            first = next(train_set.batches(batch_size, epoch=0, shuffle=False))
            self.train_state = self._init_state(first, seed=seed)
            if cfg.checkpoint_dir:
                latest = ckpt.latest_checkpoint(cfg.checkpoint_dir)
                if latest:
                    self._restore(latest)
                    logger.info("resumed from %s (iter %d)", latest,
                                self.trainer_state.iteration)
        if self._train_step is None:    # after the state: it fixes the layout
            self._train_step = self._make_train_step()

        # opt-in trace-time static analysis of the step about to train
        # (TrainConfig.graph_checks): a broken structural invariant —
        # collective budget, closure-captured weights, host round-trips,
        # dtype leaks — surfaces HERE, before the first (expensive) compile,
        # instead of at the next bench run
        if cfg.graph_checks and cfg.graph_checks != "off":
            if first is None:
                first = next(train_set.batches(batch_size, epoch=0,
                                               shuffle=False))
            self._run_graph_checks(first)

        # retry-from-checkpoint budget (Topology.scala:1181-1263), now policy-
        # driven: retry_times attempts with exponential backoff between
        # rollbacks and an optional overall deadline. The policy is the shared
        # resilience primitive; the rollback side effects stay here.
        retry_policy = RetryPolicy(
            max_attempts=cfg.retry_times + 1, base_delay_s=cfg.retry_backoff_s,
            max_delay_s=cfg.retry_max_backoff_s,
            deadline_s=cfg.retry_deadline_s, jitter=0.1, seed=seed)
        tracker = retry_policy.tracker()
        self._sigterm = False
        prev_handler = None
        handler_installed = (cfg.graceful_shutdown
                             and threading.current_thread()
                             is threading.main_thread())
        if handler_installed:
            prev_handler = signal.signal(
                signal.SIGTERM,
                lambda *_: setattr(self, "_sigterm", True))
        try:
            while not end_trigger(self.trainer_state):
                try:
                    self._run_epoch(train_set, batch_size, checkpoint_trigger)
                except (KeyboardInterrupt, ValueError, TypeError):
                    raise
                except Exception as e:  # retry-from-checkpoint
                    if not cfg.checkpoint_dir:
                        raise
                    # a rollback must never pick a checkpoint whose write is
                    # still in flight (half-written / about to be replaced by
                    # the newer snapshot): drain the async writer first. A
                    # FAILED in-flight write is logged and forfeited — the
                    # rollback falls back to the last durable snapshot.
                    self._drain_checkpoints(raise_errors=False)
                    latest = ckpt.latest_checkpoint(cfg.checkpoint_dir)
                    if latest is None:
                        raise
                    try:
                        delay = tracker.record_failure(e)
                    except ResilienceError:
                        # budget exhausted / deadline passed: surface the
                        # ORIGINAL failure (reference semantics — callers see
                        # what actually broke, with the policy error chained)
                        raise e
                    _ROLLBACKS.inc()
                    logger.warning("step failed (%s); retry %d/%d from %s "
                                   "in %.2fs", e, tracker.attempts,
                                   cfg.retry_times, latest, delay)
                    if delay > 0:
                        (retry_policy.sleep or time.sleep)(delay)
                    self._restore(latest)
                    continue

                if validation_data is not None and validation_metrics:
                    results = self.evaluate(validation_data, batch_size=batch_size,
                                            metrics=validation_metrics)
                    # the FIRST metric is the primary score (max() would pick an
                    # error metric like mse when mixed with accuracies)
                    self.trainer_state.last_score = next(iter(results.values()))
                    if self.val_summary:
                        self.val_summary.add_scalars(self.trainer_state.iteration,
                                                     results)
                    logger.info("epoch %d validation: %s",
                                self.trainer_state.epoch, results)
            # training finished: block once on the async writer so fit()
            # returning implies the newest checkpoint is DURABLE (and a
            # failed write surfaces here instead of dying silently)
            self._drain_checkpoints()
        except _GracefulStop:
            # SIGTERM: persist one final checkpoint so the replacement run
            # resumes exactly here, then exit 143 (128+SIGTERM) — the
            # conventional graceful-termination status
            jax.block_until_ready(self.train_state)
            _SIGTERM_EXITS.inc()
            if cfg.checkpoint_dir:
                # durable save: the supervisor's replacement run must find
                # this final snapshot on disk the moment exit(143) is seen.
                # A previously failed async write must not abort it — exit
                # 143 with the freshest possible snapshot beats a traceback.
                self._save(cfg.checkpoint_dir, durable=True,
                           raise_drain_errors=False)
                logger.warning("SIGTERM: final checkpoint saved at iter %d; "
                               "exiting", self.trainer_state.iteration)
            raise SystemExit(143)
        finally:
            if handler_installed:
                signal.signal(signal.SIGTERM, prev_handler)
            # thread hygiene on ANY exit: an in-flight write never outlives
            # fit(). During an exceptional unwind errors are logged, not
            # raised (the original failure must not be masked); the normal
            # path already drained with raise_errors=True above.
            self._drain_checkpoints(raise_errors=False)
        # fit() returning means training FINISHED: epochs only dispatch work
        # (epoch-final losses stay lazy device scalars), so block once here.
        # Otherwise a caller could observe fit() "done" while this rank's
        # collectives are still in flight — e.g. checkpointing or exiting the
        # process mid-psum, wedging every peer rank.
        jax.block_until_ready(self.train_state)
        return self

    def _run_epoch(self, train_set: FeatureSet, batch_size: int,
                   checkpoint_trigger: Trigger):
        cfg = self.config
        if (cfg.cache_on_device
                and get_zoo_context().process_count == 1
                and train_set.memory_type == "DRAM"
                # byte-record tiers decode at batch time: raw object arrays
                # can't live in HBM
                and getattr(train_set, "decoder", None) is None):
            return self._run_epoch_cached(train_set, batch_size,
                                          checkpoint_trigger)
        ts = self.trainer_state
        epoch = ts.epoch
        t0 = time.perf_counter()
        seen = 0
        loss = None

        # async input pipeline: gather → decode → sharded device_put run on a
        # background producer feeding a bounded queue (depth =
        # config.prefetch_depth; 0 = synchronous in-line production),
        # so the host work of batch N+1 overlaps the device step on batch N.
        # Batch ORDER is byte-identical to the sync path per (seed, epoch).
        loader = PrefetchLoader(train_set, batch_size, epoch=epoch,
                                shuffle=self.config.shuffle,
                                put_fn=self._to_global,
                                depth=self.config.prefetch_depth)
        # per-step breakdown window: data-wait accumulates per batch; compute
        # is the window remainder, synced by the float(loss) transfer at each
        # log point so dispatched-but-unfinished device work can't hide
        it = iter(loader)
        win_t0 = t0
        win_steps = 0
        win_data_wait = 0.0
        epoch_data_wait = 0.0
        epoch_compile = 0.0
        try:
            while True:
                td = time.perf_counter()
                try:
                    global_batch = next(it)
                except StopIteration:
                    break
                dw = time.perf_counter() - td
                win_data_wait += dw
                epoch_data_wait += dw
                _DATA_WAIT.observe(dw)
                self._check_interrupt()
                chaos_point("estimator.step")
                key = self._batch_signature(global_batch)
                t_step = time.perf_counter()
                self.train_state, (loss, gnorm) = self._train_step(
                    self.train_state, global_batch)
                if key not in self._step_shapes:
                    # first dispatch of this shape = compile event: sync so
                    # its cost is attributed to compilation, not smeared over
                    # the window — which requires restarting the window clock
                    # here, and excluding the cost from the epoch epilogue's
                    # ComputeMs
                    jax.block_until_ready(loss)
                    self._note_step_signature(key)
                    _COMPILES.inc()
                    compile_s = time.perf_counter() - t_step
                    _COMPILE_TIME.observe(compile_s)
                    epoch_compile += compile_s
                    win_t0 += compile_s
                _STEPS.inc()
                win_steps += 1
                ts.iteration += 1
                seen += batch_size
                if ts.iteration % cfg.log_every_n_steps == 0:
                    loss_val = float(loss)
                    gnorm_val = float(gnorm)
                    _GRAD_NORM.observe(gnorm_val)
                    ts.last_loss = loss_val
                    now = time.perf_counter()
                    throughput = seen / max(now - t0, 1e-9)
                    data_ms = win_data_wait / win_steps * 1e3
                    compute_ms = max(0.0, (now - win_t0 - win_data_wait)
                                     / win_steps) * 1e3
                    _COMPUTE.observe(compute_ms / 1e3)
                    _mw.sample("estimator.step")
                    if self.train_summary:
                        self.train_summary.add_scalars(ts.iteration, {
                            "Loss": loss_val, "Throughput": throughput,
                            "GradNorm": gnorm_val,
                            "DataWaitMs": data_ms, "ComputeMs": compute_ms})
                    logger.info("epoch %d iter %d loss %.4f gnorm %.3f "
                                "throughput %.1f rec/s (data %.2fms compute "
                                "%.2fms /step)",
                                epoch, ts.iteration, loss_val, gnorm_val,
                                throughput, data_ms, compute_ms)
                    # fresh clock: the summary writes and the log line ran
                    # after `now` and are not the NEXT window's ComputeMs
                    win_t0, win_steps, win_data_wait = (time.perf_counter(),
                                                        0, 0.0)
                if (checkpoint_trigger is not None and checkpoint_trigger(ts)
                        and cfg.checkpoint_dir):
                    self._save(cfg.checkpoint_dir)
        finally:
            # epoch end, step exception, or SIGTERM unwind: the producer
            # thread must not outlive the epoch
            loader.close()
        self._finish_epoch(t0, seen, loss, batch_size,
                           data_wait_s=epoch_data_wait,
                           compile_s=epoch_compile)

    def _finish_epoch(self, t0: float, seen: int, loss,
                      batch_size: Optional[int] = None,
                      data_wait_s: float = 0.0, compile_s: float = 0.0):
        """Epoch epilogue shared by both epoch runners: final-loss scalar,
        epoch/records bookkeeping, checkpoint save, summary flush."""
        cfg = self.config
        ts = self.trainer_state
        steps_this_epoch = max(1, seen // max(1, batch_size or cfg.batch_size))
        if loss is not None:
            # lazy: a 0-d device array; TrainerState materializes it on read.
            # Eagerly float()-ing here would stall the host on the device
            # once per epoch, which device-cached scanned epochs exist to
            # avoid.
            ts.last_loss = loss
            # always record the epoch-final loss so short runs still get scalars
            if self.train_summary:
                dt = time.perf_counter() - t0
                self.train_summary.add_scalars(ts.iteration, {
                    "Loss": ts.last_loss, "Throughput": seen / max(dt, 1e-9),
                    "DataWaitMs": data_wait_s / steps_this_epoch * 1e3,
                    # compile cost is reported separately
                    # (zoo_train_compile_seconds), not smeared over steps
                    "ComputeMs": max(0.0, dt - data_wait_s - compile_s)
                    / steps_this_epoch * 1e3})
        ts.epoch += 1
        ts.records_processed += seen
        # epoch boundary = a guaranteed witness point even when the epoch is
        # shorter than log_every_n_steps (the tests' usual shape)
        _mw.sample("estimator.step")
        if cfg.checkpoint_dir:
            # epoch boundary = durability barrier: the save is synchronous
            # (and drains any in-flight mid-epoch write), so a hard kill in
            # epoch N+1 can never lose epoch N's completion
            self._save(cfg.checkpoint_dir, durable=True)
        if self.train_summary:
            self.train_summary.flush()

    def _run_epoch_cached(self, train_set: FeatureSet, batch_size: int,
                          checkpoint_trigger: Trigger):
        """Epoch with the dataset resident in HBM and steps fused into
        ``lax.scan`` blocks (TrainConfig.cache_on_device).

        Triggers/logging fire at block granularity (``scan_block_steps``);
        trailing steps that don't fill a block run through the per-batch path
        so no samples are dropped beyond the usual remainder.
        """
        cfg = self.config
        ts = self.trainer_state
        epoch = ts.epoch
        t0 = time.perf_counter()

        # key the HBM-resident copy on the array objects, not the FeatureSet —
        # fit() wraps raw (x, y) into a fresh FeatureSet every call, and
        # re-uploading ~the whole dataset each epoch would dominate runtime.
        # The key holds STRONG references so object identity can't be recycled
        # by the allocator after a gc (id() alone would alias new datasets).
        leaves = jax.tree_util.tree_leaves(train_set.data)
        cached = getattr(self, "_device_data_key", None)
        if (cached is None or len(cached) != len(leaves)
                or any(a is not b for a, b in zip(cached, leaves))):
            self._device_data = jax.device_put(train_set.data, self._replicated())
            self._device_data_key = leaves
        if getattr(self, "_scan_block", None) is None:
            self._scan_block = self._make_scan_block()
        if self._train_step is None:
            self._train_step = self._make_train_step()

        # epoch permutation computed ON device (jax.random.permutation) so no
        # index upload happens per epoch; deterministic in (seed, epoch)
        n_total = len(train_set)
        if cfg.shuffle:
            if getattr(self, "_perm_n", None) != n_total:
                self._perm_fn = jax.jit(
                    lambda seed: jax.random.permutation(
                        jax.random.PRNGKey(seed),
                        jnp.arange(n_total, dtype=jnp.int32)))
                self._perm_n = n_total
            idx = self._perm_fn(train_set.seed + epoch * 1_000_003)
        else:
            idx = jnp.arange(n_total, dtype=jnp.int32)
        n_steps = n_total // batch_size
        block = max(1, min(cfg.scan_block_steps, n_steps))
        n_blocks = n_steps // block
        seen = 0
        loss = None
        epoch_compile = 0.0
        win_t0, win_steps = t0, 0          # reset at each log point, like
        for b in range(n_blocks):          # the streaming path's window
            self._check_interrupt()
            chaos_point("estimator.step")
            sel = idx[b * block * batch_size:(b + 1) * block * batch_size]
            idx_mat = sel.reshape(block, batch_size)
            t_blk = time.perf_counter()
            self.train_state, (losses, gnorms) = self._scan_block(
                self.train_state, self._device_data, idx_mat)
            scan_key = tuple(idx_mat.shape)
            if scan_key not in self._scan_shapes:
                jax.block_until_ready(losses)
                self._scan_shapes.add(scan_key)
                _COMPILES.inc()
                compile_s = time.perf_counter() - t_blk
                _COMPILE_TIME.observe(compile_s)
                epoch_compile += compile_s
                win_t0 += compile_s     # keep compile out of ComputeMs
            loss = losses[-1]
            # device-cached epochs: data wait is ~0 by construction (the
            # dataset lives in HBM; batches are gathers inside the scan), so
            # the whole block window is compute
            _STEPS.inc(block)
            win_steps += block
            ts.iteration += block
            seen += block * batch_size
            if cfg.log_every_n_steps and (b + 1) * block >= cfg.log_every_n_steps \
                    and ((b + 1) * block) // cfg.log_every_n_steps \
                    > (b * block) // cfg.log_every_n_steps:
                loss_val = float(loss)          # device sync closes the window
                gnorm_val = float(gnorms[-1])
                _GRAD_NORM.observe(gnorm_val)
                ts.last_loss = loss_val
                now = time.perf_counter()
                throughput = seen / max(now - t0, 1e-9)
                compute_ms = (now - win_t0) / max(1, win_steps) * 1e3
                _COMPUTE.observe(compute_ms / 1e3)
                _mw.sample("estimator.step")
                if self.train_summary:
                    self.train_summary.add_scalars(ts.iteration, {
                        "Loss": loss_val, "Throughput": throughput,
                        "GradNorm": gnorm_val,
                        "DataWaitMs": 0.0, "ComputeMs": compute_ms})
                logger.info("epoch %d iter %d loss %.4f throughput %.1f rec/s",
                            epoch, ts.iteration, loss_val, throughput)
                # fresh clock: keep the log point out of the next window
                win_t0, win_steps = time.perf_counter(), 0
            if (checkpoint_trigger is not None and cfg.checkpoint_dir
                    and self._trigger_crossed(checkpoint_trigger, ts, block)):
                self._save(cfg.checkpoint_dir)
        # trailing steps (< one block): per-batch path, gathering on device
        for s in range(n_blocks * block, n_steps):
            self._check_interrupt()
            chaos_point("estimator.step")
            sel = idx[s * batch_size:(s + 1) * batch_size]
            db = jax.tree_util.tree_map(lambda a: jnp.take(a, sel, axis=0),
                                        self._device_data)
            key = self._batch_signature(db)
            t_step = time.perf_counter()
            self.train_state, (loss, _gn) = self._train_step(self.train_state,
                                                             db)
            if key not in self._step_shapes:
                jax.block_until_ready(loss)
                self._note_step_signature(key)
                _COMPILES.inc()
                compile_s = time.perf_counter() - t_step
                _COMPILE_TIME.observe(compile_s)
                epoch_compile += compile_s
            _STEPS.inc()
            ts.iteration += 1
            seen += batch_size
            if (checkpoint_trigger is not None and checkpoint_trigger(ts)
                    and cfg.checkpoint_dir):
                self._save(cfg.checkpoint_dir)
        self._finish_epoch(t0, seen, loss, batch_size,
                           compile_s=epoch_compile)

    def _run_graph_checks(self, sample_batch):
        """Trace the train step (``jax.make_jaxpr`` — no compile) and run the
        graph-layer lint rules against it per ``TrainConfig.graph_checks``.

        Expectations are derived from the config: the flat update-sharding
        path must show exactly one reduce-scatter + one all-gather per bucket
        of the flat meta per global step (and none inside the accumulation
        scan); a declared bf16 policy must actually reach the contraction
        ops; no host callbacks or large closure-captured constants may ride
        the step. The memory tier rides
        the same trace: the train state is rebound every step, so an
        un-donated state (``donate_state=False``) is ``donation-missed``; a
        declared ``hbm_budget_mb`` bounds the static live-range peak; and
        outsized temporaries warn (``peak-temporary``)."""
        from ..analysis import RuleContext, enforce, lint_jaxpr, profile_jaxpr
        from ..analysis.rules.memory import lint_memory

        expect = None
        if self._update_mode() == "flat":
            n_buckets = self._flat_meta.n_buckets
            expect = {"reduce-scatter": n_buckets, "all-gather": n_buckets}
        cfg = self.config
        n_state = len(jax.tree_util.tree_leaves(self.train_state))
        batch = self._to_global(sample_batch)
        n_batch = len(jax.tree_util.tree_leaves(batch))
        budget = (int(cfg.hbm_budget_mb * 2 ** 20)
                  if cfg.hbm_budget_mb else None)
        ctx = RuleContext(where="estimator.fit",
                          expect_collectives=expect,
                          compute_dtype=cfg.compute_dtype,
                          hbm_budget_bytes=budget,
                          donated_invars=[cfg.donate_state] * n_state
                          + [False] * n_batch,
                          dead_invars=[True] * n_state + [False] * n_batch)
        step = self._with_policy(self._step_fn())
        closed = jax.make_jaxpr(step)(self.train_state, batch)
        findings = lint_jaxpr(closed, ctx=ctx,
                              rules=["collective-budget", "host-transfer",
                                     "large-constant", "dtype-discipline"])
        findings += lint_memory(closed, ctx=ctx)
        if _mw.enabled():
            # the runtime witness cross-checks measured bytes against this
            prof = profile_jaxpr(closed, donated_invars=ctx.donated_invars)
            _mw.note_static("estimator.step", prof.peak_live_bytes, budget)
        enforce(findings, cfg.graph_checks, logger)

    def _note_step_signature(self, key) -> None:
        """Record a newly-compiled step signature: add it to ``_step_shapes``
        (the compile-event membership set) AND the recompilation-hazard
        tracker — one add-path so the two can't desynchronize. A train step
        re-tracing beyond a handful of distinct batch signatures is compiling
        mid-run (unbucketed ragged batches, drifting dtypes)."""
        self._step_shapes.add(key)
        if self._recompile_tracker is None:
            from ..analysis.graphlint import SignatureTracker

            self._recompile_tracker = SignatureTracker("estimator.step",
                                                       max_distinct=4)
        self._recompile_tracker.add(key)

    @staticmethod
    def _batch_signature(batch) -> Tuple:
        """Shape/dtype key of a dispatched batch — the thing jit re-traces
        on."""
        return tuple((tuple(l.shape), str(getattr(l, "dtype", type(l))))
                     for l in jax.tree_util.tree_leaves(batch))

    def _check_interrupt(self):
        """SIGTERM lands between device steps (a step is never torn mid-
        collective; peers on other ranks don't wedge mid-psum)."""
        if getattr(self, "_sigterm", False):
            raise _GracefulStop()

    @staticmethod
    def _trigger_crossed(trigger: Trigger, ts: TrainerState, block: int) -> bool:
        """Block-granular trigger test: when iteration jumps by ``block``, an
        interval trigger fires if any multiple of its interval was CROSSED in
        the block (exact modulo equality would almost never hold)."""
        if isinstance(trigger, SeveralIteration):
            return (ts.iteration // trigger.interval
                    > (ts.iteration - block) // trigger.interval)
        return trigger(ts)

    def _save(self, directory: str, durable: bool = False,
              raise_drain_errors: bool = True):
        """``durable=False`` (trigger-based mid-epoch saves — the hot-path
        cost async checkpointing removes): snapshot-then-write; this returns
        after the device→host snapshot and the serialization/fsync/rename run
        on the writer thread (at most one in flight — submit drains the
        previous write first). ``durable=True`` (epoch boundaries, SIGTERM
        finals): drain any in-flight write, then write synchronously — the
        caller's contract is "this state is on disk when I return", which a
        hard kill right after the save must not be able to violate.
        ``raise_drain_errors=False``: a previously FAILED async write is
        logged and forfeited instead of aborting this save (the SIGTERM
        path, where writing the final snapshot beats error propagation)."""
        if get_zoo_context().process_index == 0:
            _CHECKPOINTS.inc()
            writer = None
            if self.config.async_checkpoint and not durable:
                if self._ckpt_writer is None:
                    self._ckpt_writer = ckpt.CheckpointWriter()
                writer = self._ckpt_writer
            else:
                self._drain_checkpoints(raise_errors=raise_drain_errors)
            pub = getattr(self, "_model_publisher", None)
            ckpt.save_checkpoint(directory, self.train_state,
                                 iteration=self.trainer_state.iteration,
                                 epoch=self.trainer_state.epoch,
                                 writer=writer,
                                 on_durable=(pub.on_durable if pub is not None
                                             else None))

    def set_model_publisher(self, publisher) -> "Estimator":
        """Attach a :class:`~..serving.hotswap.ModelPublisher`: every durable
        checkpoint this estimator saves (async writer-thread AND synchronous
        epoch/SIGTERM saves) is announced on the serving fleet's publish
        stream — the trainer half of the continuous-deployment loop."""
        self._model_publisher = publisher
        return self

    def _drain_checkpoints(self, raise_errors: bool = True):
        """Block until the in-flight async checkpoint write (if any) is
        durable. With ``raise_errors=False`` a failed write is logged and
        forfeited (teardown/rollback paths that must not mask the original
        failure)."""
        w = self._ckpt_writer
        if w is None:
            return
        try:
            w.drain()
        except BaseException:
            if raise_errors:
                raise
            logger.exception("async checkpoint write failed; continuing "
                             "with the last durable snapshot")

    # ---------------------------------------------------------------- evaluate
    def evaluate(self, data, batch_size: int = 256,
                 metrics: Sequence = ("accuracy",)) -> Dict[str, float]:
        """Streaming metric evaluation under jit (Estimator.evaluate parity)."""
        eval_set = _as_featureset(data)
        if self.train_state is None:
            first = next(eval_set.batches(batch_size, shuffle=False,
                                          drop_remainder=False))
            self.train_state = self._init_state(first)
        metric_objs: List[Metric] = [get_metric(m) for m in metrics]
        # cache key includes each metric's full scalar config so e.g. AUC(100)
        # and AUC(200) don't collide on one compiled closure
        key = tuple(
            (type(m).__name__, m.name,
             tuple(sorted((k, v) for k, v in vars(m).items()
                          if isinstance(v, (int, float, str, bool)))))
            for m in metric_objs)
        if key not in self._eval_cache:
            model = self.model

            def eval_step(params, mstate, accs, batch):
                x, y = batch
                y_hat, _ = model.apply(params, mstate, x, training=False)
                return [m.update(a, y, y_hat) for m, a in zip(metric_objs, accs)]

            # the accumulator is rebound to the step's output every batch —
            # donating it keeps one accumulator buffer live instead of two
            # (the donation-missed rule's evaluate-jit class)
            self._eval_cache[key] = self._with_policy(
                jax.jit(eval_step, donate_argnums=(2,)))
        eval_step = self._eval_cache[key]
        accs = [m.init() for m in metric_objs]
        # same async loader as the train path: gather/decode + device upload
        # of batch N+1 overlap the eval step on batch N, and every host batch
        # is produced (and counted) through the one FeatureSet iterator
        loader = PrefetchLoader(eval_set, batch_size, epoch=0, shuffle=False,
                                drop_remainder=False, put_fn=self._to_global,
                                depth=self.config.prefetch_depth)
        try:
            for global_batch in loader:
                accs = eval_step(self.train_state["params"],
                                 self.train_state["model_state"],
                                 accs, global_batch)
        finally:
            loader.close()
        return {m.name: m.result(a) for m, a in zip(metric_objs, accs)}

    # ----------------------------------------------------------------- predict
    def predict(self, x, batch_size: int = 256) -> np.ndarray:
        model = self.model
        if not hasattr(self, "_predict_step"):
            self._predict_step = self._with_policy(jax.jit(
                lambda p, s, x: model.apply(p, s, x, training=False)[0]))
        data = (x,) if not isinstance(x, (tuple, list)) else tuple(x)
        fs = FeatureSet(data)
        if self.train_state is None:
            first = next(fs.batches(batch_size, shuffle=False, drop_remainder=False))
            xb = first[0] if len(first) == 1 else list(first)
            self.train_state = self._init_state((xb, None))
        outs = []
        # prefetch host-side production (gather/decode); the jit dispatch
        # handles the transfer, so put_fn stays None here
        loader = PrefetchLoader(fs, batch_size, epoch=0, shuffle=False,
                                drop_remainder=False,
                                depth=self.config.prefetch_depth)
        try:
            for host_batch in loader:
                xb = host_batch[0] if len(host_batch) == 1 else list(host_batch)
                y = self._predict_step(self.train_state["params"],
                                       self.train_state["model_state"], xb)
                outs.append(jax.device_get(y))
        finally:
            loader.close()
        if isinstance(outs[0], (tuple, list)):
            # multi-output model (functional Model with several outputs):
            # concatenate each output head across batches
            return [np.concatenate([np.asarray(o[i]) for o in outs], axis=0)
                    for i in range(len(outs[0]))]
        return np.concatenate([np.asarray(o) for o in outs], axis=0)

    # --------------------------------------------------- batchnorm recalibration
    def recalibrate_batchnorm(self, x, batch_size: int = 32, passes: int = 2,
                              momentum: float = 0.5):
        """Re-estimate BatchNorm moving statistics under the FINAL weights.

        During short trainings the 0.99-momentum EMA lags the fast-moving
        weights, so eval-mode (moving-stat) forward passes diverge from
        train-mode (batch-stat) ones. This runs forward-only passes over ``x``
        with a low-momentum override — the functional equivalent of
        ``torch.optim.swa_utils.update_bn`` — and keeps only the state.
        Dropout-family layers are silenced so the statistics match the
        serving-time distribution.
        """
        from ..nn.layers.normalization import BatchNormalization

        if self.train_state is None:
            return self
        bns = [l for l in _walk_layers(self.model)
               if isinstance(l, BatchNormalization)]
        if not bns:
            return self
        from ..nn.layers.advanced_activations import _SpatialDropout
        from ..nn.layers.core import Dropout, GaussianDropout, GaussianNoise

        # exact class match, NOT hasattr(l, "rate"): atrous convs store their
        # dilation in .rate and zeroing it would break the traced forward
        noisy = []
        for l in _walk_layers(self.model):
            if isinstance(l, (Dropout, GaussianDropout, _SpatialDropout)):
                noisy.append((l, "rate"))
            elif isinstance(l, GaussianNoise):
                noisy.append((l, "sigma"))
        saved = ([(l, "momentum", l.momentum) for l in bns]
                 + [(l, attr, getattr(l, attr)) for l, attr in noisy])
        for l in bns:
            l.momentum = float(momentum)
        for l, attr in noisy:
            setattr(l, attr, 0.0)
        try:
            model = self.model
            # fresh trace every call: momentum/rate are captured at trace time
            fwd = jax.jit(lambda p, s, xb: model.apply(
                p, s, xb, training=True, rng=jax.random.PRNGKey(0))[1])
            data = (x,) if not isinstance(x, (tuple, list)) else tuple(x)
            fs = x if isinstance(x, FeatureSet) else FeatureSet(data)
            # keep only the model's inputs: a labeled FeatureSet (or a fit-style
            # (x, y) tuple) carries targets as trailing components that must not
            # reach model.apply
            n_in = len(getattr(self.model, "input_nodes", ()) or ()) or 1
            mstate = self.train_state["model_state"]
            for _ in range(max(1, passes)):
                for hb in fs.batches(batch_size, shuffle=False,
                                     drop_remainder=False):
                    if isinstance(hb, dict):
                        # dict-tree batches (from_generator/from_xshards): only
                        # models whose apply takes the mapping whole can eat
                        # them — positional multi-input graphs cannot tell
                        # inputs from labels in an unordered mapping
                        if getattr(self.model, "input_nodes", None):
                            raise ValueError(
                                "recalibrate_batchnorm got a dict-tree batch "
                                "but the model takes positional graph inputs; "
                                "pass x as an array/tuple FeatureSet instead")
                        xb = hb
                    else:
                        hb = hb[:n_in]
                        xb = hb[0] if len(hb) == 1 else list(hb)
                    # donation is illegal here: the first iteration's mstate
                    # IS the live train_state["model_state"] — donating would
                    # delete the training state's buffers if a later batch
                    # raises before the reassignment below lands
                    # zoo-lint: disable=donation-missed
                    mstate = fwd(self.train_state["params"], mstate, xb)
            self.train_state["model_state"] = mstate
        finally:
            for l, attr, v in saved:
                setattr(l, attr, v)
        return self

    # ------------------------------------------------------------- summaries
    def set_tensorboard(self, log_dir: str, app_name: str):
        """Topology.scala:207-214 parity."""
        self.train_summary = TrainSummary(log_dir, app_name)
        self.val_summary = ValidationSummary(log_dir, app_name)
        return self

    # --------------------------------------------------------------- weights
    @property
    def params(self):
        return self.train_state["params"] if self.train_state else None

    def save(self, directory: str):
        assert self.train_state is not None
        # public save is SYNCHRONOUS: callers expect a durable file on
        # return; drain first so it can't interleave with an async write
        self._drain_checkpoints()
        return ckpt.save_checkpoint(directory, self.train_state,
                                    iteration=self.trainer_state.iteration,
                                    epoch=self.trainer_state.epoch)

    def load(self, directory: str, sample_batch=None):
        self._drain_checkpoints()
        if self.train_state is None:
            assert sample_batch is not None, "need sample_batch to build state"
            self.train_state = self._init_state(sample_batch)
        self._restore(ckpt.latest_checkpoint(directory) or directory)
        return self

    def _restore(self, path: str):
        """Load the snapshot at ``path`` into the current state's structure
        and placement, and take its iteration/epoch. Under flat update
        sharding the optimizer state must be in this build's bucket layout:
        a one-bucket state in the older ``(npad,)`` layout is re-padded, any
        other layout (other buckets, or a view in which other leaves enter
        by their own rows or their transpose's) is refused rather than read
        as this one."""
        flat = self._update_mode() == "flat"
        try:
            restored, meta = ckpt.load_checkpoint(path, self.train_state)
            if flat:
                restored["opt_state"] = upd.adopt_flat_layout(
                    restored["opt_state"], self.train_state["opt_state"],
                    self._flat_meta)
        except ValueError as e:
            if not flat:
                raise
            own = ("" if self._flat_meta.layout is None
                   else ", matrices by their own rows")
            raise ValueError(
                f"{path} does not fit the flat update-sharding state "
                f"({self._flat_meta.n_buckets} bucket(s) of "
                f"{self._flat_meta.bucket_shape}{own}): {e}. Resume it with "
                f"the version that wrote it, or load the params alone.") from e
        self.train_state = self._place_state(restored)
        self.trainer_state.iteration = meta["iteration"]
        self.trainer_state.epoch = meta["epoch"]
