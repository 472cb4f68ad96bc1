"""North-star benchmark: NCF MovieLens-1M training throughput + HR@10 parity.

Reference workload: apps/recommendation-ncf/ncf-explicit-feedback.ipynb (pyzoo
KerasModel NCF on local Spark, MKL CPU). BASELINE.json publishes no absolute
number (``published: {}``), so the CPU baseline is measured LIVE each run: a
subprocess executes the *identical* recipe (same model, data, batch, epochs,
device-cached scanned train loop) on this host's CPU backend and reports its
samples/sec and HR@10. ``vs_baseline`` is TPU/CPU throughput; HR@10 parity is
TPU HR@10 vs the CPU-trained HR@10 of the same recipe.

Recipe: MovieLens-1M explicit feedback (real ``ratings.dat`` when present,
else the statistically-matched synthetic from ``data.datasets``), leave-one-out
split (each evaluated user's final rating held out of training), NeuralCF
(GMF+MLP, class_num=5), Adam, global batch 8192, fixed epoch count; HR@10 over
1 positive + 99 unseen negatives per user, scored by expected rating.

Also reports a flagship TransformerLM single-chip entry: tokens/sec and %MFU
(fwd+bwd, bf16, seq 2048) — see ``run_transformer_mfu``.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", "hr@10", ...}.
"""

from __future__ import annotations

import functools
import json
import os
import re
import subprocess
import sys
import time
from typing import Optional

import numpy as np

from analytics_zoo_tpu.common.compile_cache import enable_compile_cache

BATCH = 8192
TRAIN_EPOCHS = 16          # fixed recipe, identical on TPU and CPU-reference
MEASURE_FROM_EPOCH = 2     # epoch 1 pays compile; measure 2..TRAIN_EPOCHS
EVAL_USERS = 1000
# recorded --cpu-reference throughput on this host (1 core), used only if the
# live CPU subprocess fails
CPU_FALLBACK_SAMPLES_PER_SEC = 561_000.0
# rolling record of live CPU-baseline measurements; vs_baseline is computed
# against the MAX of (live run, recent history) so a live baseline depressed
# by host-CPU contention (the reference subprocess shares one core with the
# TPU host loop) can only make the reported ratio SMALLER, never inflate it
BASELINE_HISTORY_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                     "BASELINE_HISTORY.json")
BASELINE_HISTORY_MAX_AGE_S = 14 * 24 * 3600


def _baseline_history_load() -> list[dict]:
    try:
        with open(BASELINE_HISTORY_PATH) as f:
            return [e for e in json.load(f)
                    if time.time() - e.get("t", 0) < BASELINE_HISTORY_MAX_AGE_S]
    except (OSError, ValueError):
        return []


def _baseline_history_append(samples_per_sec: float) -> None:
    hist = _baseline_history_load()
    hist.append({"t": time.time(), "samples_per_sec": samples_per_sec})
    try:  # atomic replace: a kill mid-write must not destroy the history
        tmp = BASELINE_HISTORY_PATH + ".tmp"
        with open(tmp, "w") as f:
            json.dump(hist[-50:], f)
        os.replace(tmp, BASELINE_HISTORY_PATH)
    except OSError:
        pass


# peak bf16 FLOP/s per chip by device kind (public TPU specs)
_PEAK_FLOPS = {
    "v2": 45e12, "v3": 123e12, "v4": 275e12,
    "v5lite": 197e12, "v5e": 197e12, "v5p": 459e12,
    "v6e": 918e12, "v6 lite": 918e12,
}


def _peak_flops(device) -> tuple[float, str]:
    kind = getattr(device, "device_kind", "unknown").lower().replace(" ", "")
    for key, val in _PEAK_FLOPS.items():
        if key.replace(" ", "") in kind:
            return val, kind
    raise ValueError(f"no peak FLOP/s on record for device kind {kind!r}; "
                     f"add it to _PEAK_FLOPS with its source")


# migrated to the analysis subsystem's memory tier (ISSUE 12) so library
# code (ops/tuning.py, the OOM handler's callers) stops importing from the
# bench script; the alias keeps older callers and artifacts working
from analytics_zoo_tpu.analysis.memory import (  # noqa: E402
    parse_xla_memory_analysis)


def _movielens_leave_one_out():
    """(train_pairs, train_labels, eval_sets): last rating of each evaluated
    user held out of training (NCF-paper leave-one-out protocol)."""
    from analytics_zoo_tpu.data.datasets import (ML1M_ITEMS, movielens_1m,
                                                 leave_one_out_eval_sets)

    pairs, ratings = movielens_1m(path=os.environ.get("ML1M_RATINGS"))
    eval_sets = leave_one_out_eval_sets(pairs, ML1M_ITEMS, n_negatives=99,
                                        max_users=EVAL_USERS)
    # row index of each user's LAST rating (what eval_sets holds out)
    users = pairs[:, 0]
    rev_first = np.unique(users[::-1], return_index=True)[1]
    last_row = len(users) - 1 - rev_first  # aligned with np.unique's sorted users
    eval_user_set = set(int(u) for u in eval_sets[:, 0, 0])
    uniq = np.unique(users)
    drop = last_row[np.isin(uniq, list(eval_user_set))]
    mask = np.ones(len(users), dtype=bool)
    mask[drop] = False
    train_pairs = np.ascontiguousarray(pairs[mask])
    train_labels = np.ascontiguousarray((ratings[mask] - 1).astype("int32"))
    return train_pairs, train_labels, eval_sets


def _hr_at_10(est, eval_sets) -> float:
    """Score = expected rating; HR@10 over [positive | 99 negatives] groups."""
    flat = eval_sets.reshape(-1, 2).astype("int32")
    probs = est.predict(flat, batch_size=BATCH)
    score = probs @ np.arange(1, probs.shape[1] + 1, dtype=np.float32)
    score = score.reshape(eval_sets.shape[0], eval_sets.shape[1])
    rank = (score[:, 1:] > score[:, 0:1]).sum(axis=1) + 1
    return float((rank <= 10).mean())


def run_ncf_implicit(platform: str | None = None, train_epochs: int = 8,
                     n_negatives: int = 4) -> dict:
    """NCF-paper implicit-feedback recipe: binary interactions, ``n_negatives``
    random negatives per positive sampled ON DEVICE inside the jitted step
    (fresh every step), BCE, leave-one-out HR@10 over 1+99 candidates. This is
    the falsifiable accuracy recipe — random ranking gives 0.10, the paper's
    NeuMF lands 0.6-0.7 on real ML-1M."""
    import jax

    if platform:
        jax.config.update("jax_platforms", platform)
    enable_compile_cache()

    from analytics_zoo_tpu.common import (MeshConfig, PrecisionConfig,
                                          RuntimeConfig, TrainConfig,
                                          init_zoo_context, reset_zoo_context)
    from analytics_zoo_tpu.data import FeatureSet
    from analytics_zoo_tpu.engine import Estimator
    from analytics_zoo_tpu.models.recommendation import (ImplicitNCF,
                                                         implicit_bce_loss)
    from analytics_zoo_tpu.nn.optimizers import Adam

    reset_zoo_context()
    ctx = init_zoo_context(RuntimeConfig(
        mesh=MeshConfig(dp=0),
        precision=PrecisionConfig(compute_dtype="bfloat16")))

    train_pairs, _labels, eval_sets = _movielens_leave_one_out()
    fs = FeatureSet.from_numpy(train_pairs,
                               np.zeros(len(train_pairs), "float32"))
    n_steps = len(fs) // BATCH

    model = ImplicitNCF(user_count=6040, item_count=3706,
                        n_negatives=n_negatives)
    est = Estimator(model, optimizer=Adam(lr=2.5e-3), loss=implicit_bce_loss,
                    mesh=ctx.mesh,
                    config=TrainConfig(log_every_n_steps=10**9,
                                       cache_on_device=True,
                                       scan_block_steps=n_steps))
    est.fit(fs, batch_size=BATCH, epochs=train_epochs)

    flat = eval_sets.reshape(-1, 2).astype("int32")
    probs = est.predict(flat, batch_size=BATCH)
    score = np.asarray(probs).reshape(eval_sets.shape[0], eval_sets.shape[1])
    rank = (score[:, 1:] > score[:, 0:1]).sum(axis=1) + 1
    return {
        "hr@10": round(float((rank <= 10).mean()), 4),
        "ndcg@10": round(float(np.where(rank <= 10,
                                        1.0 / np.log2(rank + 1), 0.0).mean()), 4),
        "n_negatives": n_negatives,
        "epochs": train_epochs,
        "final_loss": float(est.trainer_state.last_loss),
        "platform": str(jax.devices()[0].platform),
    }


def run_ncf(platform: str | None = None, train_epochs: int = TRAIN_EPOCHS) -> dict:
    import jax

    if platform:
        jax.config.update("jax_platforms", platform)
    enable_compile_cache()

    from analytics_zoo_tpu.common import (MeshConfig, PrecisionConfig,
                                          RuntimeConfig, TrainConfig,
                                          init_zoo_context, reset_zoo_context)
    from analytics_zoo_tpu.data import FeatureSet
    from analytics_zoo_tpu.engine import Estimator
    from analytics_zoo_tpu.models.recommendation import NeuralCF
    from analytics_zoo_tpu.nn.optimizers import Adam

    reset_zoo_context()
    ctx = init_zoo_context(RuntimeConfig(
        mesh=MeshConfig(dp=0),  # all chips on the dp axis
        precision=PrecisionConfig(compute_dtype="bfloat16")))
    n_chips = ctx.num_devices

    train_pairs, train_labels, eval_sets = _movielens_leave_one_out()
    fs = FeatureSet.from_numpy(train_pairs, train_labels)
    n_steps = len(fs) // BATCH

    model = NeuralCF(user_count=6040, item_count=3706, class_num=5)
    est = Estimator(model, optimizer=Adam(lr=1e-3),
                    loss="sparse_categorical_crossentropy", mesh=ctx.mesh,
                    config=TrainConfig(log_every_n_steps=10**9,
                                       cache_on_device=True,
                                       scan_block_steps=n_steps))

    est.fit(fs, batch_size=BATCH, epochs=1)  # compile + epoch 1 (warmup)
    jax.tree_util.tree_leaves(est.train_state["params"])[0].block_until_ready()

    t0 = time.perf_counter()
    est.fit(fs, batch_size=BATCH, epochs=train_epochs)   # fit() blocks
    dt = time.perf_counter() - t0

    measured_steps = (train_epochs - MEASURE_FROM_EPOCH + 1) * n_steps
    hr10 = _hr_at_10(est, eval_sets)
    samples_per_sec = measured_steps * BATCH / dt
    return {
        "samples_per_sec": round(samples_per_sec, 1),
        "samples_per_sec_per_chip": round(samples_per_sec / n_chips, 1),
        "n_chips": n_chips,
        "measured_steps": measured_steps,
        "measured_seconds": round(dt, 3),
        "epochs": train_epochs,
        "hr@10": round(hr10, 4),
        "final_loss": float(est.trainer_state.last_loss),
        "platform": str(jax.devices()[0].platform),
    }


def run_transformer_mfu(seq_len: int = 2048, batch: Optional[int] = None,
                        hidden: int = 1024, n_block: int = 8,
                        n_head: int = 8, vocab: int = 32768) -> dict:
    """Flagship TransformerLM fwd+bwd step: tokens/sec + %MFU on one chip.

    bf16 compute policy, bf16 Adam moments, d_head=128 (full MXU lane),
    flash-attention pallas kernels fwd+bwd. ``batch=None`` auto-tunes over a
    small ladder (the per-step token count is the main MFU lever on one chip)
    and reports the best; a candidate that OOMs is skipped. FLOP accounting
    (per step, fwd+bwd = 3x fwd):
      * block matmuls: 6 * 12*H^2 * tokens   (qkv+proj 4H^2, MLP 8H^2)
      * attention scores/values: 6 * L * B * S^2 * H  (causal: half of 12LBS^2H)
      * LM head: 6 * tokens * H * V

    Timing: each timed chunk of dispatches is closed with ``float(loss)``,
    which waits for the device, before the clock is read.
    """
    import jax
    import jax.numpy as jnp
    import optax

    enable_compile_cache()

    from analytics_zoo_tpu.models.transformer import TransformerLM, lm_loss
    from analytics_zoo_tpu.nn.module import compute_dtype, set_policy

    def measure(b: int, budget_s: float, remat: bool = False) -> dict:
        model = TransformerLM(vocab=vocab, hidden_size=hidden, n_block=n_block,
                              n_head=n_head, seq_len=seq_len,
                              attn_strategy="flash", remat=remat)
        params, _ = model.build(jax.random.PRNGKey(0))
        tx = optax.adam(1e-3, mu_dtype=jnp.bfloat16)
        opt_state = tx.init(params)

        @functools.partial(jax.jit, donate_argnums=(0, 1))
        def step(params, opt_state, ids, labels):
            def loss_of(p):
                logits, _ = model.apply(p, {}, ids)
                return lm_loss(labels, logits)

            loss, grads = jax.value_and_grad(loss_of)(params)
            updates, opt_state = tx.update(grads, opt_state, params)
            return optax.apply_updates(params, updates), opt_state, loss

        rng = np.random.default_rng(0)
        ids = jnp.asarray(rng.integers(0, vocab, (b, seq_len)), jnp.int32)
        labels = jnp.roll(ids, -1, axis=1)

        for _ in range(3):  # warmup/compile
            params, opt_state, loss = step(params, opt_state, ids, labels)
        float(loss)

        n_steps, t0 = 0, time.perf_counter()
        while time.perf_counter() - t0 < budget_s or n_steps < 10:
            for _ in range(10):
                params, opt_state, loss = step(params, opt_state, ids, labels)
            float(loss)  # waits for the device (see docstring)
            n_steps += 10
        dt = time.perf_counter() - t0

        tokens = b * seq_len
        flops_per_step = (6 * 12 * hidden * hidden * n_block * tokens
                          + 6 * n_block * b * seq_len * seq_len * hidden
                          + 6 * tokens * hidden * vocab)
        peak, kind = _peak_flops(jax.devices()[0])
        return {
            "model": "transformer_lm",
            "tokens_per_sec": round(n_steps * tokens / dt, 1),
            "mfu": round(flops_per_step * n_steps / dt / peak, 4),
            "device_kind": kind,
            "peak_flops_assumed": peak,
            "seq_len": seq_len, "batch": b, "hidden": hidden,
            "n_block": n_block, "remat": remat, "final_loss": float(loss),
        }

    prev_compute = compute_dtype()
    set_policy(compute_dtype="bfloat16")
    try:
        # (batch, remat) ladder: remat rows only run when their plain sibling
        # hit an OOM — recompute trades FLOPs for HBM, so it can only win
        # when the plain variant doesn't fit at all
        def is_oom(e: Exception) -> bool:
            msg = str(e).lower()
            return "resource_exhausted" in msg or "out of memory" in msg

        candidates = ([(batch, False)] if batch
                      else [(4, False), (8, False), (16, False), (32, False)])
        budget = 3.0 if len(candidates) > 1 else 6.0
        best, tried, oomed, oom_reports = None, [], [], []
        for b, remat in candidates:
            try:
                res = measure(b, remat=remat, budget_s=budget)
            except Exception as e:  # OOM on a large candidate: skip it
                print(f"[bench] transformer_lm batch={b} failed: {e}",
                      file=sys.stderr)
                if is_oom(e):   # only an OOM earns a remat retry
                    oomed.append(b)
                    # the RESOURCE_EXHAUSTED text carries the XLA buffer
                    # table: keep it structured, not as a raw-text blob
                    parsed = parse_xla_memory_analysis(str(e))
                    if parsed:
                        oom_reports.append({"batch": b, "remat": remat,
                                            **parsed})
                continue
            tried.append({"batch": b, "remat": remat, "mfu": res["mfu"]})
            if best is None or res["mfu"] > best["mfu"]:
                best = res
        for b in oomed:           # second chance under rematerialization
            try:
                res = measure(b, remat=True, budget_s=budget)
            except Exception as e:
                print(f"[bench] transformer_lm batch={b} remat failed: {e}",
                      file=sys.stderr)
                continue
            tried.append({"batch": b, "remat": True, "mfu": res["mfu"]})
            if best is None or res["mfu"] > best["mfu"]:
                best = res
        if best is None:
            raise RuntimeError("every transformer_lm batch candidate failed")
        if len(candidates) > 1:   # re-measure the winner over a full window
            best = measure(best["batch"], remat=best["remat"], budget_s=6.0)
            best["batch_sweep"] = tried
        if oom_reports:
            best["oom_memory_analysis"] = oom_reports
        return best
    finally:
        set_policy(compute_dtype=prev_compute)


def run_data_pipeline(platform: str | None = None, n_records: int = 1024,
                      record_floats: int = 8192, batch: int = 128,
                      epochs: int = 3, hidden: int = 768) -> dict:
    """Input-pipeline micro-bench: sync vs async DataWaitMs on a decode-heavy
    ``BytesFeatureSet`` (ISSUE 4 acceptance).

    Each record is ``record_floats`` float32 bytes; the decoder does real
    numpy work per record (sort + matmul — the JPEG-decode stand-in; releases
    the GIL) so host-side production costs milliseconds per batch. The SAME
    recipe trains twice — ``prefetch_depth=0`` (fully synchronous in-line
    production, the control arm) and ``prefetch_depth=2`` (the async
    producer pipeline) — and the
    per-step DataWaitMs means come from the shared telemetry registry's
    ``zoo_train_data_wait_seconds`` deltas, i.e. exactly the numbers the
    train loop logs. Also asserts the async batch stream is byte-identical
    to the sync one, and reports the async-checkpoint snapshot-vs-write
    split (``zoo_train_checkpoint_{snapshot,write}_seconds``).
    """
    import tempfile

    import jax

    if platform:
        jax.config.update("jax_platforms", platform)

    from analytics_zoo_tpu.common import telemetry as _tm
    from analytics_zoo_tpu.common import (TrainConfig, init_zoo_context,
                                          reset_zoo_context)
    from analytics_zoo_tpu.data import PrefetchLoader
    from analytics_zoo_tpu.data.featureset import FeatureSet
    from analytics_zoo_tpu.engine import Estimator
    from analytics_zoo_tpu.nn import Sequential
    from analytics_zoo_tpu.nn import layers as L

    reset_zoo_context()
    init_zoo_context()

    rng = np.random.default_rng(0)
    side = int(np.sqrt(record_floats))
    records = [rng.normal(size=record_floats).astype(np.float32).tobytes()
               for _ in range(n_records)]

    def decoder(r: bytes):
        a = np.frombuffer(r, np.float32)
        a = np.sort(a)                          # GIL-releasing numpy work
        m = a[:side * side].reshape(side, side)
        v = (m @ m[:64].T).mean(axis=1)[:64]    # decode-heavy stand-in
        return v.astype(np.float32), np.float32(v[0] > 0)

    def featureset():
        return FeatureSet.from_bytes(records, decoder, seed=7)

    def hist_delta(snap0, snap1, name):
        s0 = snap0.get(name, {}).get("samples", {}).get("", {"sum": 0.0,
                                                            "count": 0})
        s1 = snap1.get(name, {}).get("samples", {}).get("", {"sum": 0.0,
                                                            "count": 0})
        n = s1["count"] - s0["count"]
        return ((s1["sum"] - s0["sum"]) / n if n else 0.0), n

    def run_mode(depth: int) -> dict:
        fs = featureset()
        # the device step must be heavy enough that a well-overlapped host
        # pipeline can hide its decode cost inside the compute window —
        # i.e. the normal compute-bound training regime
        model = Sequential([L.Dense(hidden, activation="relu",
                                    input_shape=(64,)),
                            L.Dense(hidden, activation="relu"),
                            L.Dense(hidden, activation="relu"),
                            L.Dense(1)])
        ckdir = tempfile.mkdtemp(prefix=f"bench_ckpt_d{depth}_")
        # checkpoint_every_n_iters puts trigger-based MID-EPOCH saves on the
        # hot path — the saves async checkpointing moves to the writer
        # thread; without it only durable-synchronous epoch-boundary saves
        # would run and the snapshot-vs-write split would never exercise the
        # async writer
        est = Estimator(model, optimizer="sgd", loss="mse",
                        config=TrainConfig(log_every_n_steps=1,
                                           prefetch_depth=depth,
                                           checkpoint_dir=ckdir,
                                           checkpoint_every_n_iters=4))
        est.fit(fs, batch_size=batch, epochs=1)      # compile + warmup epoch
        snap0 = _tm.snapshot()
        t0 = time.perf_counter()
        est.fit(fs, batch_size=batch, epochs=1 + epochs)
        dt = time.perf_counter() - t0
        snap1 = _tm.snapshot()
        dw_mean, n_steps = hist_delta(snap0, snap1,
                                      "zoo_train_data_wait_seconds")
        snap_mean, _ = hist_delta(snap0, snap1,
                                  "zoo_train_checkpoint_snapshot_seconds")
        write_mean, _ = hist_delta(snap0, snap1,
                                   "zoo_train_checkpoint_write_seconds")
        return {
            "prefetch_depth": depth,
            "data_wait_ms_mean": round(dw_mean * 1e3, 3),
            "samples_per_sec": round(n_steps * batch / max(dt, 1e-9), 1),
            "measured_steps": n_steps,
            "ckpt_snapshot_ms_mean": round(snap_mean * 1e3, 3),
            "ckpt_write_ms_mean": round(write_mean * 1e3, 3),
        }

    # byte-identity of the async stream vs the sync iterator (the loader's
    # determinism contract), checked on the exact bench featureset
    fs = featureset()
    sync_stream = [b for b in fs.batches(batch, epoch=1, shuffle=True)]
    loader = PrefetchLoader(featureset(), batch, epoch=1, shuffle=True,
                            depth=2)
    try:
        async_stream = list(loader)
    finally:
        loader.close()
    identical = len(sync_stream) == len(async_stream) and all(
        all(np.array_equal(np.asarray(u), np.asarray(v))
            for u, v in zip(sb, ab))
        for sb, ab in zip(sync_stream, async_stream))

    sync = run_mode(0)
    async_ = run_mode(2)
    ratio = (async_["data_wait_ms_mean"] / sync["data_wait_ms_mean"]
             if sync["data_wait_ms_mean"] else None)
    return {
        "metric": "input-pipeline DataWaitMs, sync vs async",
        "batch": batch,
        "record_bytes": record_floats * 4,
        "n_records": n_records,
        "byte_identical": bool(identical),
        "sync": sync,
        "async": async_,
        "data_wait_ratio_async_vs_sync": (round(ratio, 4)
                                          if ratio is not None else None),
        "platform": str(jax.devices()[0].platform),
    }


def run_int8_dispatch(hidden: Optional[int] = None,
                      batch: Optional[int] = None,
                      iters: Optional[int] = None) -> dict:
    """Raw-matmul vs through-dispatch int8/bf16 ratios (ISSUE 6 acceptance).

    The regression this guards: int8 measured 1.53× on a bare matmul but
    0.72× through the serving dispatch path — the unfused activation
    quantize/rescale ran as separate HBM round-trips around each dot. With
    the fused kernel tier the through-dispatch ratio must stay within 0.85×
    of the raw ratio. Three measurements, identical timing discipline:

    * ``raw``: device-resident chained matmul loop, bf16 vs int8;
    * ``dispatch``: ``InferenceModel.predict`` end-to-end (pad + executable
      lookup + transfers), bf16 vs quantized;
    * ``structure``: the ``fused-int8-dispatch`` rule of the shared
      static-analysis engine (``analysis.rules.fused_int8``) run over the
      jaxpr of the exact computation predict compiles, with the fused tier
      forced on (the CPU-checkable invariant; quick mode gates on its
      findings being empty).

    On TPU the fused tier is autotuned first (``ops.tuning``) so dispatch
    runs tuned blocks; the sweep winner rides the artifact.
    """
    import jax
    import jax.numpy as jnp

    enable_compile_cache()

    from analytics_zoo_tpu.inference import InferenceModel
    from analytics_zoo_tpu.nn import Sequential
    from analytics_zoo_tpu.nn import layers as L
    from analytics_zoo_tpu.nn.module import compute_dtype, set_policy
    from analytics_zoo_tpu.ops import int8 as int8_ops
    from analytics_zoo_tpu.ops import tuning

    on_tpu = jax.default_backend() == "tpu"
    hidden = hidden or (8192 if on_tpu else 512)
    batch = batch or (8192 if on_tpu else 256)
    iters = iters or (30 if on_tpu else 5)
    from analytics_zoo_tpu.ops.int8_fused import fused_mode

    rng = np.random.default_rng(3)
    out: dict = {"metric": "int8 dispatch vs raw-matmul ratio",
                 "hidden": hidden, "batch": batch, "iters": iters,
                 "platform": jax.default_backend(),
                 # the routing mode the raw/dispatch TIMINGS run under (the
                 # structural audit below forces its own, recorded separately)
                 "fused_mode": fused_mode(), "tuning": None}

    # --- autotune the fused schedule for this shape bucket (TPU) ----------
    if on_tpu:
        try:
            out["tuning"] = tuning.tune_int8_matmul(
                batch, hidden, hidden, dtype=jnp.bfloat16)
        except Exception as e:
            print(f"[bench] int8 tuning sweep failed: {e}", file=sys.stderr)
    else:
        out["tuning"] = {"skipped": "tuned on TPU only (interpreter probe "
                                    "timing carries no signal)"}

    # --- raw matmul: device-resident chained loop -------------------------
    x_np = rng.normal(size=(batch, hidden)).astype(np.float32)
    w_np = rng.normal(size=(hidden, hidden)).astype(np.float32)
    packed = int8_ops.quantize_weight(w_np)
    packed = {"q": jax.device_put(packed["q"]),
              "scale": jax.device_put(packed["scale"])}
    x_bf = jax.device_put(jnp.asarray(x_np, jnp.bfloat16))
    w_bf = jax.device_put(jnp.asarray(w_np, jnp.bfloat16))

    def timed_loop(step_fn, *args) -> float:
        def loop(*a):
            def body(_, carry):
                xc, acc = carry
                y = step_fn(xc, *a[1:])
                # serialize iterations: next input depends on this output by
                # an amount too small to change values but opaque to DCE
                eps = jnp.max(y.astype(jnp.float32)) * 1e-30
                return (a[0] + eps.astype(a[0].dtype), acc + eps)

            _, acc = jax.lax.fori_loop(0, iters, body,
                                       (a[0], jnp.float32(0)))
            return acc

        compiled = jax.jit(loop).lower(*args).compile()
        float(compiled(*args))              # warm, device-resident
        t0 = time.perf_counter()
        float(compiled(*args))
        return (time.perf_counter() - t0) / iters

    raw_bf16_s = timed_loop(
        lambda xc, w: jax.lax.dot(xc, w,
                                  preferred_element_type=jnp.float32),
        x_bf, w_bf)
    raw_int8_s = timed_loop(
        lambda xc: int8_ops.int8_matmul(xc, packed, out_dtype=jnp.bfloat16),
        x_bf)
    out["raw"] = {"bf16_ms": round(raw_bf16_s * 1e3, 3),
                  "int8_ms": round(raw_int8_s * 1e3, 3),
                  "int8_over_bf16": round(raw_bf16_s / raw_int8_s, 3)}

    # --- through-dispatch: the InferenceModel predict path ----------------
    def build_im():
        m = Sequential([
            L.Dense(hidden, activation="relu", input_shape=(hidden,)),
            L.Dense(hidden, activation="relu"),
            L.Dense(128, activation="softmax"),
        ])
        m.compile(optimizer="sgd", loss="mse")
        xw = rng.normal(size=(32, hidden)).astype(np.float32)
        m.fit(xw, np.zeros((32, 128), np.float32), batch_size=32, nb_epoch=1)
        return InferenceModel(max_batch_size=batch).load(m)

    def measure_dispatch(im):
        n = max(2, min(iters, 5)) if x_np.nbytes > 2 ** 26 else iters
        im.predict(x_np)                    # compile + warm
        t0 = time.perf_counter()
        for _ in range(n):
            y = im.predict(x_np)
        return (time.perf_counter() - t0) / n, y

    prev = compute_dtype()
    set_policy(compute_dtype="bfloat16")
    try:
        im_f = build_im()
        disp_bf16_s, y_f = measure_dispatch(im_f)
        im_q = build_im().quantize_int8()
        disp_int8_s, y_q = measure_dispatch(im_q)
    finally:
        set_policy(compute_dtype=prev)
    y_f = np.asarray(y_f, np.float32)
    y_q = np.asarray(y_q, np.float32)
    out["dispatch"] = {
        "bf16_ms": round(disp_bf16_s * 1e3, 3),
        "int8_ms": round(disp_int8_s * 1e3, 3),
        "int8_over_bf16": round(disp_bf16_s / disp_int8_s, 3),
        "argmax_agreement": float((y_f.argmax(-1) == y_q.argmax(-1)).mean()),
        "max_prob_diff": round(float(np.max(np.abs(y_f - y_q))), 5),
        "quantize_seconds": im_q.compile_stats()["quantize_seconds"],
    }
    out["dispatch_over_raw"] = round(
        out["dispatch"]["int8_over_bf16"] / out["raw"]["int8_over_bf16"], 3)

    # --- structural audit: fused tier forced on (CPU-checkable) ----------
    from analytics_zoo_tpu.analysis.rules.fused_int8 import (
        fused_dispatch_report)

    env_prev = os.environ.get("ZOO_INT8_FUSED")
    os.environ["ZOO_INT8_FUSED"] = "1" if on_tpu else "interpret"
    try:
        out["structure_mode"] = fused_mode()
        out["structure"] = fused_dispatch_report(
            im_q, jnp.asarray(x_np[: min(batch, 8)]))
    finally:
        if env_prev is None:
            os.environ.pop("ZOO_INT8_FUSED", None)
        else:
            os.environ["ZOO_INT8_FUSED"] = env_prev
    return out


def run_mfu_batch_sweep(batches=(4, 16), seq_len: int = 2048,
                        hidden: int = 1024, n_block: int = 8) -> dict:
    """MFU at the production batch points {4, 16} with TUNED flash blocks
    (ISSUE 6: MFU collapsed 0.53→0.18 going batch 4→16 under the fixed
    block schedule). Tunes the flash (block_q, block_k) schedule for this
    sequence shape first (persisted in the ops.tuning cache, so the model
    layer's ``default_blocks`` picks it up at trace time), then measures
    each batch via ``run_transformer_mfu`` — whose OOM ladder already
    retries under ``FLASH_REMAT_POLICY`` when the plain variant doesn't
    fit. Requires an accelerator: interpret-mode MFU carries no signal."""
    import jax

    from analytics_zoo_tpu.ops import tuning

    if jax.default_backend() == "cpu":
        return {"skipped": "requires accelerator (interpret-mode MFU "
                           "carries no signal)"}
    out: dict = {"seq_len": seq_len, "hidden": hidden, "n_block": n_block,
                 "entries": {}}
    try:
        out["flash_tuning"] = tuning.tune_flash_blocks(
            seq_len, seq_len, batch=2, heads=8, d=hidden // 8)
    except Exception as e:
        print(f"[bench] flash tuning sweep failed: {e}", file=sys.stderr)
        out["flash_tuning"] = None
    for b in batches:
        try:
            out["entries"][str(b)] = run_transformer_mfu(
                seq_len=seq_len, batch=b, hidden=hidden, n_block=n_block)
        except Exception as e:
            print(f"[bench] mfu batch={b} failed: {e}", file=sys.stderr)
            out["entries"][str(b)] = {"error": str(e)[:500]}
    return out


def run_update_sharding(dp_sizes=(2, 4, 8), accum_steps=(1, 4),
                        steps: int = 20) -> dict:
    """ZeRO-1 weight-update-sharding micro-bench (ISSUE 5 acceptance):
    replicated vs dp-sharded (flat reduce-scatter/all-gather) optimizer
    update on a small TransformerLM, at dp ∈ ``dp_sizes``.

    Per dp it records tokens/sec, per-device optimizer-state bytes (the
    ZeRO-1 memory claim: sharded ≈ replicated/dp within padding), compiled
    memory-analysis numbers (``hbm_peak_bytes`` = arguments + temp — the
    machine-readable baseline the memory gate compares), and the collective-
    instruction counts of the compiled step at ``grad_accum_steps`` ∈
    ``accum_steps`` — the flat path must show the SAME collective counts for
    K=1 and K=4 with exactly one grad-sized reduce-scatter (one gradient
    collective per GLOBAL step).

    Always runs on a virtual CPU mesh: re-execs itself in a child pinned to
    ``--xla_force_host_platform_device_count=max(dp)`` (the parent process
    may already hold a different backend).
    """
    need = max(dp_sizes)
    if os.environ.get("_ZOO_UPDATE_SHARDING_CHILD") != "1":
        env = dict(os.environ)
        env["_ZOO_UPDATE_SHARDING_CHILD"] = "1"
        flags = [f for f in env.get("XLA_FLAGS", "").split()
                 if not f.startswith("--xla_force_host_platform_device_count")]
        env["XLA_FLAGS"] = " ".join(
            flags + [f"--xla_force_host_platform_device_count={need}"])
        env["JAX_PLATFORMS"] = "cpu"
        r = subprocess.run(
            [sys.executable, os.path.abspath(__file__),
             "--update-sharding-child"],
            env=env, capture_output=True, text=True, timeout=1800)
        if r.returncode != 0:
            raise RuntimeError(
                f"update-sharding child failed rc={r.returncode}:\n"
                f"{r.stdout[-2000:]}\n{r.stderr[-2000:]}")
        return json.loads(r.stdout.strip().splitlines()[-1])

    import jax
    from jax.sharding import Mesh

    from analytics_zoo_tpu.common import TrainConfig
    from analytics_zoo_tpu.models.transformer import TransformerLM, lm_loss
    from analytics_zoo_tpu.nn.optimizers import Adam
    from analytics_zoo_tpu.parallel import update_sharding as upd
    from analytics_zoo_tpu.engine import Estimator

    axes = ("dp", "fsdp", "tp", "sp", "pp", "ep")
    rng = np.random.default_rng(0)

    def mem_fields(compiled) -> dict:
        try:
            ma = compiled.memory_analysis()
        except Exception:
            return {}
        fields = {}
        for k in ("temp_size_in_bytes", "argument_size_in_bytes",
                  "output_size_in_bytes", "generated_code_size_in_bytes"):
            v = getattr(ma, k, None)
            if v is not None:
                fields[k] = int(v)
        if "temp_size_in_bytes" in fields and "argument_size_in_bytes" in fields:
            fields["hbm_peak_bytes"] = (fields["temp_size_in_bytes"]
                                        + fields["argument_size_in_bytes"])
        return fields

    def opt_bytes_per_device(state) -> int:
        total = 0
        for l in jax.tree_util.tree_leaves(state["opt_state"]):
            shards = getattr(l, "addressable_shards", None)
            total += (shards[0].data.nbytes if shards
                      else np.asarray(l).nbytes)
        return total

    def arm(dp: int, cfg: TrainConfig, batch_np, measure_tps: bool,
            hlo: bool = True) -> dict:
        mesh = Mesh(np.array(jax.devices()[:dp]).reshape((dp,) + (1,) * 5),
                    axes)
        model = TransformerLM(vocab=2048, hidden_size=128, n_block=2,
                              n_head=4, seq_len=128, attn_strategy="full")
        est = Estimator(model, optimizer=Adam(lr=1e-3), loss=lm_loss,
                        mesh=mesh, config=cfg)
        state = est._init_state(batch_np)
        batch = est._to_global(batch_np)
        step = est._make_train_step()
        out = {
            "mode": est._update_mode() or "replicated",
            "grad_accum_steps": cfg.grad_accum_steps,
            "opt_state_bytes_per_device": opt_bytes_per_device(state),
        }
        if hlo:     # the mixed-precision arm's step is policy-wrapped (no
            # .lower); it is measured for state bytes only
            compiled = step.lower(state, batch).compile()
            hlo_text = compiled.as_text()
            out["collectives"] = upd.collective_counts(hlo_text)
            out["_hlo"] = hlo_text        # popped by the caller (lint input,
            out["hbm"] = mem_fields(compiled)  # never lands in the artifact)
            # drive the AOT executable directly below: jit dispatch would
            # compile the identical program a second time
            step = compiled
        if measure_tps:
            state, (loss, _) = step(state, batch)      # warmup dispatch
            jax.block_until_ready(loss)
            t0 = time.perf_counter()
            for _ in range(steps):
                state, (loss, _) = step(state, batch)
            jax.block_until_ready(loss)
            dt = time.perf_counter() - t0
            tokens = batch_np[0].shape[0] * batch_np[0].shape[1]
            out["tokens_per_sec"] = round(steps * tokens / dt, 1)
            out["final_loss"] = float(loss)
        return out

    entries = []
    for dp in dp_sizes:
        if dp > len(jax.devices()):
            continue
        B = 16 * dp                    # scale the global batch with the mesh
        x = rng.integers(0, 2048, size=(B, 128)).astype("int32")
        y = np.roll(x, -1, axis=1)
        batch_np = (x, y)
        quiet = dict(log_every_n_steps=10 ** 9, shuffle=False)
        repl = arm(dp, TrainConfig(update_sharding=False, **quiet),
                   batch_np, measure_tps=True)
        repl.pop("_hlo", None)
        shard = arm(dp, TrainConfig(update_sharding=True, **quiet),
                    batch_np, measure_tps=True)
        shard_hlo = shard.pop("_hlo", "")
        accum_arms = {k: arm(dp, TrainConfig(update_sharding=True,
                                             grad_accum_steps=k, **quiet),
                             batch_np, measure_tps=False)
                      for k in accum_steps}
        accum_hlos = {k: a.pop("_hlo", "") for k, a in accum_arms.items()}
        accum = {str(k): a["collectives"] for k, a in accum_arms.items()}
        mp = arm(dp, TrainConfig(update_sharding=True,
                                 compute_dtype="bfloat16", **quiet),
                 batch_np, measure_tps=False, hlo=False)
        entry = {
            "dp": dp,
            "batch": B,
            "replicated": repl,
            "sharded": shard,
            "sharded_accum_collectives": accum,
            "sharded_mp_opt_bytes_per_device":
                mp["opt_state_bytes_per_device"],
            "opt_state_ratio": round(
                shard["opt_state_bytes_per_device"]
                / max(1, repl["opt_state_bytes_per_device"]), 4),
        }
        # the ZeRO-1 structural gates now run through the shared rule
        # engine (analysis "collective-budget-hlo"): the sharded step must
        # budget exactly one grad reduce-scatter + one params all-gather,
        # and every accumulation variant must show the K=1 arm's exact
        # collective counts (constant in K). Findings ride the artifact.
        from analytics_zoo_tpu.analysis import RuleContext, lint_hlo

        entry["sharded_lint"] = [f.as_dict() for f in lint_hlo(
            shard_hlo, ctx=RuleContext(
                where=f"update-sharding.dp{dp}",
                expect_collectives={"reduce-scatter": 1, "all-gather": 1}))]
        base = accum[str(accum_steps[0])]
        # the base accum arm is gated against the ABSOLUTE ZeRO-1 budget
        # (one reduce-scatter + one all-gather); the K>1 arms are then
        # gated against the base's exact counts, so a violation shared by
        # every arm equally cannot slip through the constancy comparison
        accum_lint = [f.as_dict() for f in lint_hlo(
            accum_hlos[accum_steps[0]], ctx=RuleContext(
                where=f"update-sharding.dp{dp}.k{accum_steps[0]}",
                expect_collectives={"reduce-scatter": 1, "all-gather": 1}))]
        for k in accum_steps[1:]:
            # expectation covers the UNION of collective kinds seen at K=1
            # and at this K: a kind that only appears under accumulation
            # (expected 0, found n) must trip the rule, not slip past it
            kinds = set(base) | set(accum[str(k)])
            accum_lint += [f.as_dict() for f in lint_hlo(
                accum_hlos[k], ctx=RuleContext(
                    where=f"update-sharding.dp{dp}.k{k}",
                    expect_collectives={c: base.get(c, 0) for c in kinds}))]
        entry["accum_lint"] = accum_lint
        ks = [accum[str(k)] for k in accum_steps]
        entry["grad_collectives_constant_in_k"] = all(k == ks[0] for k in ks)
        entry["one_reduce_scatter"] = all(
            k.get("reduce-scatter", 0) == 1 for k in ks)
        entries.append(entry)
    return {
        "metric": "weight-update sharding: replicated vs dp-sharded (flat)",
        "model": "transformer_lm(vocab=2048,hidden=128,n_block=2,seq=128)",
        "accum_steps": list(accum_steps),
        "entries": entries,
        "platform": str(jax.devices()[0].platform),
    }


def run_embedding(quick: bool = False) -> dict:
    """Million-user embedding-scale bench (ISSUE 19) → EMBEDDING_BENCH.

    Trains a NeuralCF-style fused-pair embedding whose table is 4× the
    per-device HBM budget — only possible because the table is row-sharded
    ``P("dp", None)`` over the mesh (each device holds rows/8) with the
    model-parallel sharded gather moving ids to the owner shards. Records:

    * ``train``: tokens(ids)/sec through the full sharded train step, the
      table's per-device bytes (gated ≈ 1/8 of the full table), the
      shard-local Adam moment bytes, and the compiled step's collective
      counts — the all-gather(ids)/reduce-scatter(rows) pair must be
      present in the HLO;
    * ``gather_lint``: findings from the ``lint_sharded_gather`` memory
      gate — the shard-LOCAL gather block traced and checked against the
      per-device budget (must be empty: the sharded working set fits where
      the dense table cannot);
    * ``serving``: the host hot-row cache over the trained table under a
      skewed id stream — lookups/sec, per-tier hit rate, host bytes;
    * ``delta``: incremental row publishing — bytes of a 1%-rows-touched
      ``save_row_delta`` vs the full checkpoint (gated ≤5%).

    Always runs on a virtual 8-device CPU mesh: re-execs itself pinned via
    ``--xla_force_host_platform_device_count`` like the update-sharding
    bench (the parent may hold a different backend).
    """
    n = 8
    if os.environ.get("_ZOO_EMBEDDING_CHILD") != "1":
        env = dict(os.environ)
        env["_ZOO_EMBEDDING_CHILD"] = "1"
        flags = [f for f in env.get("XLA_FLAGS", "").split()
                 if not f.startswith("--xla_force_host_platform_device_count")]
        env["XLA_FLAGS"] = " ".join(
            flags + [f"--xla_force_host_platform_device_count={n}"])
        env["JAX_PLATFORMS"] = "cpu"
        r = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--embedding-child"]
            + (["--quick"] if quick else []),
            env=env, capture_output=True, text=True, timeout=1800)
        if r.returncode != 0:
            raise RuntimeError(
                f"embedding child failed rc={r.returncode}:\n"
                f"{r.stdout[-2000:]}\n{r.stderr[-2000:]}")
        return json.loads(r.stdout.strip().splitlines()[-1])

    import tempfile

    import jax
    from jax.sharding import Mesh

    from analytics_zoo_tpu.analysis.rules import lint_sharded_gather
    from analytics_zoo_tpu.common import TrainConfig
    from analytics_zoo_tpu.engine import Estimator
    from analytics_zoo_tpu.engine.checkpoint import (save_checkpoint,
                                                     save_row_delta)
    from analytics_zoo_tpu.nn import Sequential
    from analytics_zoo_tpu.nn import layers as L
    from analytics_zoo_tpu.nn.layers.embedding import FusedPairEmbedding
    from analytics_zoo_tpu.parallel import (collective_counts,
                                            embedding_sharding as es)
    from analytics_zoo_tpu.serving.rowcache import HostRowCache

    if quick:
        # 131072 rows: big enough that the batch's gather temporaries sit
        # well inside the table/8 headroom the memory gate leaves
        users, items, dim, mf = 98304, 32768, 16, 8
        B, steps, serve_batches = 1024, 6, 48
    else:
        users, items, dim, mf = 786432, 262144, 32, 16   # 1,048,576 rows
        B, steps, serve_batches = 4096, 15, 128

    axes = ("dp", "fsdp", "tp", "sp", "pp", "ep")
    mesh = Mesh(np.array(jax.devices()[:n]).reshape((n,) + (1,) * 5), axes)
    model = Sequential([
        FusedPairEmbedding(users, items, dim, dim, mf_dim=mf,
                           input_shape=(2,)),
        L.Dense(16, activation="relu"), L.Dense(1)])
    rule = es.shard_embedding_tables(model, mesh)
    cfg = TrainConfig(shuffle=False, log_every_n_steps=10 ** 9,
                      update_sharding=True)
    est = Estimator(model, optimizer="adam", loss="mse", config=cfg,
                    mesh=mesh, param_sharding=rule)

    rng = np.random.default_rng(0)
    x = np.stack([rng.integers(0, users, B), rng.integers(0, items, B)],
                 axis=1).astype(np.int32)
    y = rng.integers(0, 2, (B, 1)).astype(np.float32)
    batch_np = (x, y)
    est.fit(batch_np, batch_size=B, epochs=1)       # placement + compile
    t0 = time.perf_counter()
    est.fit(batch_np, batch_size=B, epochs=1 + steps)   # `steps` more steps
    dt = time.perf_counter() - t0
    state = est.train_state

    emb = state["params"]["0_fusedpairembedding"]["embeddings"]
    rows, width = int(emb.shape[0]), int(emb.shape[1])
    table_bytes = int(emb.nbytes)
    # the scale claim: the FULL table is 4x what one device may hold, so a
    # replicated table cannot train — only rows/8 per device fits
    hbm_budget_bytes = table_bytes // 4
    hlo = est._train_step.lower(state,
                                est._to_global(batch_np)).compile().as_text()

    def leaf_bytes(tree, match):
        per_dev = full = 0
        for p, l in jax.tree_util.tree_flatten_with_path(tree)[0]:
            if match in jax.tree_util.keystr(p) and getattr(l, "ndim", 0) == 2:
                shards = getattr(l, "addressable_shards", None)
                per_dev += (shards[0].data.nbytes if shards
                            else np.asarray(l).nbytes)
                full += l.nbytes
        return per_dev, full

    table_per_dev, table_full = leaf_bytes(state["params"], "embeddings")
    moment_per_dev, moment_full = leaf_bytes(state["opt_state"],
                                             "embeddings")
    out = {
        "metric": "mesh-sharded embedding scale: train + serve + row delta",
        "rows": rows, "width": width, "batch": B, "shards": n,
        "table_bytes": table_bytes,
        "hbm_budget_bytes": hbm_budget_bytes,
        "table_over_budget": round(table_bytes / hbm_budget_bytes, 2),
        "platform": str(jax.devices()[0].platform),
        "train": {
            "tokens_per_sec": round(steps * B * 2 / dt, 1),
            "table_bytes_per_device": table_per_dev,
            "table_shard_ratio": round(table_per_dev / max(1, table_full), 5),
            "moment_bytes_per_device": moment_per_dev,
            "moment_shard_ratio": round(
                moment_per_dev / max(1, moment_full), 5),
            "collectives": collective_counts(hlo),
        },
        "gather_lint": [f.as_dict() for f in lint_sharded_gather(
            rows, width, B * 2, n, hbm_budget_bytes=hbm_budget_bytes,
            where="embedding-bench.gather")],
    }

    # ---- serving arm: host hot-row cache over the trained table ----------
    table_host = np.asarray(jax.device_get(
        state["params"]["0_fusedpairembedding"]["embeddings"]))
    cache = HostRowCache(table_host, hot_rows=max(256, rows // 64),
                         budget_bytes=2 * table_bytes, name="bench")
    # skewed traffic: a small hot head + a zipf-ish tail, the
    # recommendation-serving shape the frequency-keyed admission targets
    hot_head = rng.permutation(rows)[:max(64, rows // 256)]
    serve_B = 256
    t0 = time.perf_counter()
    for i in range(serve_batches):
        if i % 2 == 0:
            ids = rng.choice(hot_head, serve_B)
        else:
            ids = rng.integers(0, rows, serve_B)
        np.asarray(cache.gather(ids))
    dt = time.perf_counter() - t0
    s = cache.stats()
    out["serving"] = {"lookups_per_sec": round(serve_batches * serve_B / dt,
                                               1),
                      **{k: s[k] for k in ("hit_rate", "hits", "misses",
                                           "evictions", "hot_rows",
                                           "hot_bytes", "host_bytes")}}

    # ---- incremental publish: 1% of rows touched -------------------------
    with tempfile.TemporaryDirectory() as d:
        host_params = jax.device_get(state["params"])
        base = save_checkpoint(d, host_params, iteration=1, epoch=0)
        touched = rng.permutation(rows)[:max(1, rows // 100)]
        host_params["0_fusedpairembedding"]["embeddings"] = \
            table_host.copy()
        host_params["0_fusedpairembedding"]["embeddings"][touched] += 0.1
        delta = save_row_delta(d, host_params, base, iteration=2,
                               n_shards=n)
        full_b = os.path.getsize(os.path.join(base, "state.npz"))
        delta_b = os.path.getsize(os.path.join(delta, "state.npz"))
        out["delta"] = {"rows_touched": int(touched.size),
                        "touched_fraction": round(touched.size / rows, 4),
                        "full_bytes": full_b, "delta_bytes": delta_b,
                        "bytes_ratio": round(delta_b / full_b, 4)}
    return out


def run_generation_bench(quick: bool = False) -> dict:
    """Autoregressive generation serving bench (ISSUE 8) → GENERATION_BENCH.

    Measures the continuous-batching decode path (serving/generation.py +
    ops/kv_cache.py) end to end, in-process (no HTTP — the wire numbers live
    in SERVING_BENCH.json; this isolates the decode engine):

    * ``streams``: aggregate tokens/sec + p50/p95 inter-token latency at
      N ∈ {1, 8, 32} concurrent streams (quick: N=8 only), zero-failure
      gated;
    * ``continuous_vs_rtc``: the same mixed-length workload (bursty shorts +
      a few longs, the chat-traffic shape) under continuous admission vs the
      run-to-completion baseline (``admit_policy="batch"`` — the reference's
      Flink-style batch semantics); the ≥1.5× aggregate-tokens/sec claim;
    * ``flat_decode``: per-token decode latency early vs late in a long
      generation — flat (ratio ≈ 1) is the KV-cache-working signal, O(T²)
      recompute would grow linearly;
    * ``decode_lint``: the decode-shape-stability rule findings (must be
      empty) + the bucket invariant (ONE compiled decode shape, prefill
      buckets within the pow2 ladder).
    """
    import threading as _threading

    import jax

    from analytics_zoo_tpu.models.transformer import TransformerLM
    from analytics_zoo_tpu.serving.generation import ContinuousBatcher

    if quick:
        vocab, hidden, n_block, n_head = 128, 64, 2, 2
        max_seq, slots = 128, 8
        stream_counts, tokens_per_stream = (8,), 24
        # 3 full RTC waves of 8 with a long in each wave: enough steps that
        # thread-scheduling jitter can't push the measured ratio near the
        # 1.5x gate (ideal ~144 RTC steps vs ~60 continuous)
        long_tok, short_tok, n_reqs = 48, 4, 24
        flat_tokens = 96
    else:
        vocab, hidden, n_block, n_head = 512, 256, 4, 4
        max_seq, slots = 256, 8
        stream_counts, tokens_per_stream = (1, 8, 32), 48
        long_tok, short_tok, n_reqs = 64, 4, 32
        flat_tokens = 192
    page_size = 16
    model = TransformerLM(vocab=vocab, hidden_size=hidden, n_block=n_block,
                          n_head=n_head, seq_len=max_seq)
    params, _ = model.build(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)

    def make(policy="continuous", n_pages=None):
        b = ContinuousBatcher(model, params, n_slots=slots,
                              page_size=page_size, max_seq_len=max_seq,
                              n_pages=n_pages, admit_policy=policy)
        # warm every prefill bucket the workload can hit + the decode
        # executable, so XLA compiles stay out of the measured windows
        for bucket in (4, 8, 16):
            b.generate(rng.integers(1, vocab, size=bucket - 1).tolist(),
                       max_new_tokens=2)
        return b

    def drive(b, n_streams, max_new, prompt_lens, repeat=1):
        """N concurrent client threads, each consuming its stream frame by
        frame; returns (wall_s, tokens, itl_ms list, failures, records) —
        ``records`` carries per-stream (submit, first-frame, end) stamps so
        queue wait and admitted-time decode rate report SEPARATELY (at
        N >> slots, wall-clock per-stream tokens/s conflates the two), plus
        the first frame's engine-side ``chunks``/``prefill_wait_ms`` meta
        (chunked-prefill accounting; 0 chunks = whole-prompt mode)."""
        itls, fails, records = [], [], []
        lock = _threading.Lock()
        total = [0]

        def client(i):
            for r in range(repeat):
                try:
                    n_p = prompt_lens[(i + r) % len(prompt_lens)]
                    t_sub = time.perf_counter()
                    h = b.submit(rng.integers(1, vocab, size=n_p).tolist(),
                                 max_new_tokens=max_new[(i + r)
                                                        % len(max_new)],
                                 temperature=0.7, seed=i * 97 + r)
                    last = time.perf_counter()
                    got = 0
                    t_first = None
                    first_meta: dict = {}
                    for chunk, final, meta in h.frames(timeout_s=300):
                        now = time.perf_counter()
                        if final and (meta.get("error")
                                      or meta.get("outcome") == "shed"):
                            raise RuntimeError(
                                f"stream failed: "
                                f"{meta.get('error', 'shed')}")
                        if not chunk:
                            continue
                        if t_first is None:
                            t_first = now
                            first_meta = meta
                        with lock:
                            if got:     # first token latency != ITL
                                itls.append((now - last) * 1e3)
                            total[0] += len(chunk)
                        got += len(chunk)
                        last = now
                    with lock:
                        records.append({
                            "submit": t_sub, "first": t_first,
                            "end": last, "tokens": got,
                            "chunks": first_meta.get("chunks", 0),
                            "prefill_wait_ms":
                                first_meta.get("prefill_wait_ms")})
                except Exception as e:
                    with lock:
                        fails.append(repr(e))

        threads = [_threading.Thread(target=client, args=(i,))
                   for i in range(n_streams)]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        return time.perf_counter() - t0, total[0], itls, fails, records

    out: dict = {"metric": "generation serving (continuous batching)",
                 "unit": "tokens/sec",
                 "model": f"transformer_lm(vocab={vocab},hidden={hidden},"
                          f"n_block={n_block},seq={max_seq})",
                 "slots": slots, "page_size": page_size}

    # --- tokens/sec + inter-token latency at N concurrent streams ---------
    streams_out = {}
    for n in stream_counts:
        b = make()
        try:
            wall, tokens, itls, fails, recs = drive(
                b, n, max_new=[tokens_per_stream], prompt_lens=[7, 11, 15],
                repeat=2 if n == 1 else 1)
            # admitted-time accounting (ISSUE 14): at N streams over S < N
            # slots, tokens/(wall*N) mixes queue wait into the decode rate
            # (the 517-vs-627 per-stream artifact at N=32 vs N=8). Report
            # the two separately: queue_wait = submit -> first frame
            # (admission + prefill), admitted rate = tokens over the
            # stream's OWN decode window only.
            qw = [(r["first"] - r["submit"]) * 1e3 for r in recs]
            adm = [(r["tokens"] - 1) / max(r["end"] - r["first"], 1e-9)
                   for r in recs if r["tokens"] > 1]
            pw = [r["prefill_wait_ms"] for r in recs
                  if r.get("prefill_wait_ms") is not None]
            streams_out[str(n)] = {
                "tokens_per_s": round(tokens / wall, 1),
                "tokens": tokens, "wall_s": round(wall, 3),
                "p50_itl_ms": round(float(np.percentile(itls, 50)), 3),
                "p95_itl_ms": round(float(np.percentile(itls, 95)), 3),
                "queue_wait_ms_p50": round(float(np.percentile(qw, 50)), 3),
                "queue_wait_ms_p95": round(float(np.percentile(qw, 95)), 3),
                "admitted_tokens_per_s_per_stream_p50": round(
                    float(np.percentile(adm, 50)), 1),
                "prefill_wait_ms_p50": round(
                    float(np.percentile(pw, 50)), 3) if pw else None,
                "prefill_chunks_mean": round(
                    float(np.mean([r["chunks"] for r in recs])), 2),
                "failed_streams": len(fails),
                "first_failure": fails[0] if fails else None,
            }
            stats = b.stats()
            streams_out[str(n)]["slot_occupancy"] = stats["slot_occupancy"]
            streams_out[str(n)]["distinct_decode_shapes"] = \
                stats["distinct_decode_shapes"]
            streams_out[str(n)]["prefill_buckets"] = stats["prefill_buckets"]
        finally:
            b.close()
    out["streams"] = streams_out

    # --- continuous vs run-to-completion on mixed-length traffic ----------
    def policy_run(policy, repeats=3):
        """Median of ``repeats`` trials per arm: one trial's wall is ~0.1s
        in quick mode, and on a shared 1-core host a single-shot ratio of
        two such walls swings 1.1x-2.3x run to run (measured RTC spread
        within one process: 1357-2641 tok/s for identical work) — the gate
        was flaking on scheduler jitter, not on the property it checks."""
        trials = []
        for _ in range(repeats):
            b = make(policy)
            try:
                # bursty mix, longs interleaved 1-in-4 (chat-traffic shape):
                # RTC waves are each gated by their slowest member;
                # continuous admission backfills retired slots immediately
                wall, tokens, _itls, fails, _recs = drive(
                    b, n_reqs, max_new=[long_tok, short_tok, short_tok,
                                        short_tok],
                    prompt_lens=[7])
                trials.append({"tokens_per_s": round(tokens / wall, 1),
                               "tokens": tokens, "wall_s": round(wall, 3),
                               "steps": b.stats()["steps"],
                               "failed_streams": len(fails)})
            finally:
                b.close()
        mid = sorted(trials, key=lambda t: t["tokens_per_s"])[len(trials) // 2]
        out = dict(mid)
        out["trials_tokens_per_s"] = [t["tokens_per_s"] for t in trials]
        out["failed_streams"] = sum(t["failed_streams"] for t in trials)
        return out

    cont = policy_run("continuous")
    rtc = policy_run("batch")
    out["continuous_vs_rtc"] = {
        "continuous": cont, "run_to_completion": rtc,
        "speedup": round(cont["tokens_per_s"] / rtc["tokens_per_s"], 2),
    }

    # --- decode cost flat in generated length ------------------------------
    from analytics_zoo_tpu.common import memwitness as _mw

    b = make()
    try:
        if _mw.enabled():
            # scope the serving.decode witness window to THIS batcher's long
            # generation: earlier arms' batchers (and their freed caches)
            # would otherwise smear the min/max the flatness gate reads
            _mw.reset_witness()
        h = b.submit(rng.integers(1, vocab, size=7).tolist(),
                     max_new_tokens=flat_tokens, temperature=0.5, seed=5)
        stamps = [time.perf_counter()]
        for _chunk in h.tokens(timeout_s=300):
            stamps.append(time.perf_counter())
        itl = np.diff(stamps)[1:] * 1e3         # drop first-token latency
        k = max(8, len(itl) // 4)
        early, late = float(np.mean(itl[:k])), float(np.mean(itl[-k:]))
        out["flat_decode"] = {
            "tokens": int(len(itl)),
            "early_ms_per_token": round(early, 3),
            "late_ms_per_token": round(late, 3),
            "late_over_early": round(late / early, 3),
        }
        # --- decode lint + bucket invariant -------------------------------
        out["decode_lint"] = {"findings": [
            f.as_dict() for f in b.check_decode_stability("warn")]}
        # --- decode-executable memory picture (ISSUE 12) ------------------
        # the donated KV pool must show as an input->output alias in the
        # compiled buffer table, and the donation-aware static peak must be
        # one pool smaller than the undonated estimate — the cache-alias
        # invariant, measured
        out["memory"] = b.decode_memory()
    finally:
        b.close()
    # --- runtime allocation witness (ZOO_TPU_MEM_WITNESS): device bytes
    # sampled at every decode step must be FLAT — per-token growth means the
    # paged cache is leaking or re-materializing
    if _mw.enabled():
        decode_site = _mw.witness_samples().get("serving.decode")
        if decode_site:
            out["memory"]["witness"] = decode_site
    out["platform"] = str(jax.devices()[0].platform)
    return out


def run_spec_generation_bench(quick: bool = False) -> dict:
    """Speculative decode + fused paged-attention bench (ISSUE 14) — the
    ``--generation --spec`` arm, merged into GENERATION_BENCH.json as the
    ``speculative`` section.

    * ``kernel_parity``: the fused paged-attention pallas kernel (interpret
      mode on CPU) vs the gather + masked-dot reference at q_len ∈ {1, k},
      f32 and bf16;
    * ``baseline`` / ``speculative``: N=8 greedy streams, identical
      prompts/seeds, plain decode vs k-gram self-draft + k-token verify —
      tokens/sec, acceptance rate, tokens/step, and the token-identity
      check (speculation must change COST, never CONTENT);
    * ``lint_findings``: decode-shape-stability + cache-alias over the
      VERIFY executable (must be empty), and the per-(k, slot-count)
      one-executable invariant.

    CPU quick gates: parity (f32 1e-4 / bf16 2e-2), greedy acceptance ≥
    0.10, advance-per-dispatch ≥ 1.3 (the host-speed-independent proxy —
    tokens advanced per occupied slot-dispatch; plain decode is 1.0 by
    construction), token identity, one executable, findings empty. The
    wall-clock ≥2× tokens/sec gate applies on TPU-platform runs only —
    interpret-mode kernels and a 1-core host can't represent the
    dispatch/HBM-bandwidth economics the speedup comes from.
    """
    import threading as _threading

    import jax
    import jax.numpy as jnp

    from analytics_zoo_tpu.models.transformer import TransformerLM
    from analytics_zoo_tpu.ops.kv_cache import (decode_attention_multi,
                                                paged_read)
    from analytics_zoo_tpu.ops.paged_attention import (paged_attention,
                                                       synthetic_paged_case)
    from analytics_zoo_tpu.serving.generation import ContinuousBatcher

    if quick:
        vocab, hidden, n_block, n_head = 128, 64, 2, 2
        max_seq, slots, n_streams, max_new = 128, 8, 8, 24
    else:
        vocab, hidden, n_block, n_head = 512, 256, 4, 4
        max_seq, slots, n_streams, max_new = 256, 8, 8, 48
    spec_k, page_size = 4, 16
    out: dict = {"metric": "speculative decode + fused paged attention",
                 "spec_k": spec_k, "slots": slots,
                 "model": f"transformer_lm(vocab={vocab},hidden={hidden},"
                          f"n_block={n_block},seq={max_seq})"}

    # --- fused kernel vs reference numerics (interpret mode on CPU) -------
    parity: dict = {}
    prng = np.random.default_rng(7)
    h_, d_, pps_, ps_ = 4, 32, 6, 8
    for dtype, label in ((np.float32, "float32"),
                         (jnp.bfloat16, "bfloat16")):
        entry = {}
        for q_len in (1, spec_k):
            q, kp, vp, table, lengths = synthetic_paged_case(
                4, pps_, ps_, h_, d_, q_len=q_len, dtype=dtype,
                lengths=np.maximum(q_len,
                                   np.array([5, 17, 30, q_len])),
                rng=prng)
            got = paged_attention(q, kp, vp, table, lengths,
                                  page_size=ps_)
            ref = decode_attention_multi(
                q, paged_read(kp, table).astype(q.dtype),
                paged_read(vp, table).astype(q.dtype), lengths)
            entry[f"q{q_len}_max_err"] = float(
                np.max(np.abs(np.asarray(got, np.float32)
                              - np.asarray(ref, np.float32))))
        parity[label] = entry
    out["kernel_parity"] = parity

    # --- spec vs plain decode arms (greedy, identical traffic) ------------
    model = TransformerLM(vocab=vocab, hidden_size=hidden, n_block=n_block,
                          n_head=n_head, seq_len=max_seq)
    params, _ = model.build(jax.random.PRNGKey(0))

    def arm(k: int) -> dict:
        b = ContinuousBatcher(model, params, n_slots=slots,
                              page_size=page_size, max_seq_len=max_seq,
                              spec_k=k)
        try:
            rng = np.random.default_rng(0)
            # warm the prefill bucket + the decode/verify executable
            b.generate(rng.integers(1, vocab, size=7).tolist(),
                       max_new_tokens=2)
            streams: list = [None] * n_streams
            fails: list = []
            lock = _threading.Lock()

            def client(i):
                r = np.random.default_rng(100 + i)
                try:
                    toks = b.generate(
                        r.integers(1, vocab, size=7).tolist(),
                        max_new_tokens=max_new, temperature=0.0,
                        seed=i * 13, timeout_s=300)
                    with lock:
                        streams[i] = toks
                except Exception as e:
                    with lock:
                        fails.append(repr(e))

            threads = [_threading.Thread(target=client, args=(i,))
                       for i in range(n_streams)]
            t0 = time.perf_counter()
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            wall = time.perf_counter() - t0
            stats = b.stats()
            findings = [f.as_dict()
                        for f in b.check_decode_stability("warn")]
            total = sum(len(s) for s in streams if s)
            entry = {
                "tokens_per_s": round(total / wall, 1),
                "tokens": total, "wall_s": round(wall, 3),
                "steps": stats["steps"],
                "tokens_per_step": round(total / max(stats["steps"], 1), 3),
                "tokens_per_slot_step": stats["tokens_per_slot_step"],
                "failed_streams": len(fails),
                "first_failure": fails[0] if fails else None,
                "distinct_decode_shapes": stats["distinct_decode_shapes"],
                "findings": findings,
            }
            if k >= 2:
                entry["acceptance_rate"] = stats["spec"]["acceptance_rate"]
            return entry, streams
        finally:
            b.close()

    base, base_streams = arm(0)
    spec, spec_streams = arm(spec_k)
    out["baseline"] = base
    out["speculative"] = spec
    out["speedup"] = round(spec["tokens_per_s"]
                           / max(base["tokens_per_s"], 1e-9), 2)
    # the host-speed-independent win: decode tokens advanced per occupied
    # slot-dispatch (1.0 for single-token decode by construction) — what a
    # dispatch/HBM-bound backend converts into the wall-clock speedup
    out["advance_per_dispatch"] = round(
        spec["tokens_per_slot_step"]
        / max(base["tokens_per_slot_step"], 1e-9), 2)
    out["greedy_token_identical"] = bool(
        all(a == b_ for a, b_ in zip(base_streams, spec_streams)))
    out["platform"] = str(jax.devices()[0].platform)
    return out


def run_prefix_generation_bench(quick: bool = False) -> dict:
    """Shared-prefix KV cache bench (ISSUE 17) — the ``--generation
    --prefix`` arm, merged into GENERATION_BENCH.json as the
    ``prefix_cache`` section.

    Synthetic multi-tenant trace: N tenants, each with a page-aligned
    system-prompt prefix, × M user requests per tenant carrying a short
    unique suffix (>=50% of every prompt's tokens are the shared prefix).

    * ``warm`` vs ``cold``: per-request prefill-dominated latency
      (``max_new_tokens=1``) for the SAME trace against a sharing-enabled
      batcher (tenant prefixes published by a priming pass) and a
      sharing-disabled one — the warm path prefills only the suffix from
      the divergence point;
    * ``occupancy``: S concurrent same-tenant streams — peak pool pages
      with sharing (prefix pages counted once + per-stream suffix pages)
      vs without (every stream carries its own full-prompt copy);
    * ``token_identical``: the warm trace's tokens vs the cold trace's.

    Quick gates: warm prefill >=5x faster than cold at >=50% reuse; shared
    peak occupancy <=0.6x the disabled baseline (sublinear in concurrent
    prefix-sharing streams); hit rate 1.0 on the measured trace; token
    identity; zero failed streams.
    """
    import threading as _threading

    import jax

    from analytics_zoo_tpu.models.transformer import TransformerLM
    from analytics_zoo_tpu.serving.generation import ContinuousBatcher

    # hidden/prefix sized so the COLD full-prompt prefill is compute-bound
    # even on a CPU host — the 5x warm gate measures prefill work saved,
    # not thread-handoff overhead (identical in both arms)
    if quick:
        vocab, hidden, n_block, n_head = 128, 512, 2, 4
        tenants, users = 2, 4
    else:
        vocab, hidden, n_block, n_head = 512, 512, 2, 4
        tenants, users = 4, 8
    page_size, max_seq, slots = 8, 512, 8
    prefix_tokens = 480                      # 60 pages, block-aligned
    model = TransformerLM(vocab=vocab, hidden_size=hidden, n_block=n_block,
                          n_head=n_head, seq_len=max_seq)
    params, _ = model.build(jax.random.PRNGKey(0))

    rng = np.random.default_rng(17)
    prefixes = [rng.integers(1, vocab, size=prefix_tokens).tolist()
                for _ in range(tenants)]
    # M user turns per tenant: unique 4..8-token suffixes => reuse >= 92%
    trace = []
    for t in range(tenants):
        for u in range(users):
            suffix = rng.integers(1, vocab,
                                  size=int(rng.integers(4, 9))).tolist()
            trace.append((t, prefixes[t] + suffix))
    reuse = prefix_tokens / max(len(p) for _, p in trace)
    out: dict = {
        "metric": "shared-prefix KV cache: warm vs cold prefill + occupancy",
        "tenants": tenants, "users_per_tenant": users,
        "prefix_tokens": prefix_tokens, "page_size": page_size,
        "reuse_fraction": round(reuse, 3),
        "model": f"transformer_lm(vocab={vocab},hidden={hidden},"
                 f"n_block={n_block},seq={max_seq})"}

    def timed_trace(b) -> dict:
        # prime every executable OUT of the measurement: pass 1 publishes
        # each tenant's prefix (cold full-prompt bucket compiles), pass 2
        # hits it (warm suffix bucket compiles). In the sharing-disabled
        # batcher both passes are plain full prefills of the same bucket.
        for seed, suf in ((0, [1, 2, 3]), (1, [4, 5, 6])):
            for t in range(tenants):
                b.generate(prefixes[t] + suf, max_new_tokens=1, seed=seed)
        h0 = b.prefix_cache.hits if b.prefix_cache is not None else 0
        s0 = b.prefix_tokens_saved
        # submit the whole trace at once and drain: the loop admits
        # back-to-back, so the per-request figure is prefill WORK, not M
        # copies of the submit->wake->frame round-trip latency (a constant
        # identical in both arms that would flatter neither)
        t0 = time.perf_counter()
        handles = [b.submit(prompt, max_new_tokens=1, temperature=0.0,
                            seed=i * 7)
                   for i, (t, prompt) in enumerate(trace)]
        streams = [h.result(timeout_s=300) for h in handles]
        wall = time.perf_counter() - t0
        entry = {"wall_s": round(wall, 4),
                 "prefill_s_per_request": round(wall / len(trace), 5),
                 "requests": len(trace)}
        if b.prefix_cache is not None:
            entry["hit_rate"] = round(
                (b.prefix_cache.hits - h0) / len(trace), 3)
            entry["tokens_saved"] = b.prefix_tokens_saved - s0
            entry["cache_held_pages"] = b.prefix_cache.held_pages()
        return entry, streams

    # the timed arms use a small-slot batcher: every prefill dispatch
    # carries a page-POOL-sized write-through (the scatter update rewrites
    # the pool buffer), a floor identical in both arms that scales with
    # n_slots — at 2 slots the floor is small enough that the measurement
    # is the prefill compute being saved, which is the claim under test
    timed_slots = 2
    cache_pages = tenants * (prefix_tokens // page_size) + 8
    cold_b = ContinuousBatcher(model, params, n_slots=timed_slots,
                               page_size=page_size, max_seq_len=max_seq)
    try:
        cold, cold_streams = timed_trace(cold_b)
    finally:
        cold_b.close()
    warm_b = ContinuousBatcher(model, params, n_slots=timed_slots,
                               page_size=page_size, max_seq_len=max_seq,
                               prefix_cache_pages=cache_pages)
    try:
        warm, warm_streams = timed_trace(warm_b)
    finally:
        warm_b.close()
    out["cold"] = cold
    out["warm"] = warm
    out["warm_speedup"] = round(cold["prefill_s_per_request"]
                                / max(warm["prefill_s_per_request"], 1e-9),
                                2)
    out["token_identical"] = bool(cold_streams == warm_streams)

    # --- occupancy: S concurrent same-tenant streams, shared vs not ------
    def occupancy_arm(cache_pages: int) -> dict:
        b = ContinuousBatcher(model, params, n_slots=slots,
                              page_size=page_size, max_seq_len=max_seq,
                              prefix_cache_pages=cache_pages)
        try:
            if cache_pages:
                b.generate(prefixes[0] + [1], max_new_tokens=1, seed=0)
            fails: list = []
            lock = _threading.Lock()

            def client(i):
                try:
                    b.generate(prefixes[0] + [9, 9 + i],
                               max_new_tokens=4, temperature=0.0,
                               seed=i, timeout_s=300)
                except Exception as e:
                    with lock:
                        fails.append(repr(e))

            threads = [_threading.Thread(target=client, args=(i,))
                       for i in range(slots)]
            for th in threads:
                th.start()
            for th in threads:
                th.join()
            return {"streams": slots, "failed_streams": len(fails),
                    "first_failure": fails[0] if fails else None,
                    "peak_pages_in_use": b.stats()["peak_pages_in_use"]}
        finally:
            b.close()

    shared = occupancy_arm(cache_pages)
    alone = occupancy_arm(0)
    out["occupancy"] = {
        "shared": shared, "disabled": alone,
        "peak_ratio": round(shared["peak_pages_in_use"]
                            / max(alone["peak_pages_in_use"], 1), 3)}
    out["platform"] = str(jax.devices()[0].platform)
    return out


def run_longprompt_generation_bench(quick: bool = False) -> dict:
    """Chunked prefill bench (ISSUE 20) — the ``--generation --longprompt``
    arm, merged into GENERATION_BENCH.json as the ``longprompt`` section.

    The scenario the tentpole exists for: a multi-thousand-token prompt
    lands in a batcher with 8 short streams mid-decode. Whole-prompt
    prefill blocks the loop for the entire prompt (every running stream
    stalls one prefill-sized ITL); chunked prefill spends a token budget
    per loop pass, so running streams keep emitting.

    * ``baseline``: 8 short streams on the chunked batcher, no long prompt
      — the undisturbed ITL distribution;
    * ``interleave``: the same 8 streams with the long prompt injected once
      every stream is decoding — short-stream ITL p95 vs baseline is THE
      gate (<=1.5x), plus the long stream's chunk count / prefill wait from
      its first-frame meta;
    * ``whole_prompt``: the same injection against a whole-prompt batcher —
      the stall being avoided, reported as max short-stream ITL;
    * ``throughput``: idle time-to-first-token for the long prompt, chunked
      vs whole (chunking must not tank raw prefill throughput: >=0.8x);
    * ``kill_drill``: chaos kill at the 3rd ``prefill.chunk`` dispatch —
      the respawned loop re-runs that chunk; token identity + zero leaked
      pages.

    Token identity is asserted across ALL arms: whole idle == chunked idle
    == chunked under load == chunked through the kill.
    """
    import threading as _threading

    import jax

    from analytics_zoo_tpu.common.chaos import ChaosSchedule
    from analytics_zoo_tpu.models.transformer import TransformerLM
    from analytics_zoo_tpu.serving.generation import ContinuousBatcher

    # hidden sized so the whole-prompt stall is visible on any host while
    # the per-chunk cost stays under half a decode step (the ITL-inflation
    # gate's headroom). The prompt is deliberately NOT a power of two: the
    # whole-prompt path pays the pow2 bucket ceiling for it (that padding
    # is real production cost, and chunking — which pays only chunk-size
    # granularity — is exactly how you stop paying it)
    vocab, hidden, n_block, n_head = 128, 64, 2, 2
    if quick:
        prompt_len, chunk_tokens, max_new_short = 1550, 48, 96
    else:
        prompt_len, chunk_tokens, max_new_short = 10000, 128, 224
    page_size, slots, n_short = 16, 9, 8
    # headroom past the next pow2 so the whole-prompt bucket is NOT clamped
    # to max_seq_len — the ceiling it would pay in a long-context config
    max_seq = 2112 if quick else 10496
    model = TransformerLM(vocab=vocab, hidden_size=hidden, n_block=n_block,
                          n_head=n_head, seq_len=max_seq)
    params, _ = model.build(jax.random.PRNGKey(0))
    rng = np.random.default_rng(29)
    long_prompt = rng.integers(1, vocab, size=prompt_len).tolist()
    short_prompts = [rng.integers(1, vocab, size=7).tolist()
                     for _ in range(n_short)]
    long_kw = dict(max_new_tokens=4, temperature=0.7, seed=101)

    def make(chunked: bool):
        kw = (dict(prefill_chunk_tokens=chunk_tokens) if chunked else {})
        b = ContinuousBatcher(model, params, n_slots=slots,
                              page_size=page_size, max_seq_len=max_seq,
                              **kw)
        # prime every executable OUT of the measured windows: the short
        # bucket + decode step, and the chunk shape / whole-prompt bucket
        b.generate(short_prompts[0], max_new_tokens=2, seed=0)
        b.generate(long_prompt, max_new_tokens=1, seed=0)
        return b

    def shorts_run(b, inject_long: bool):
        """8 short client threads; optionally inject the long prompt once
        EVERY short stream has emitted its first token (all are decoding,
        none still in its own prefill). Returns (itl_ms, streams, fails,
        long_info)."""
        itls: list = []
        streams: list = [None] * n_short
        fails: list = []
        lock = _threading.Lock()
        all_decoding = _threading.Event()
        n_first = [0]

        def client(i):
            try:
                h = b.submit(short_prompts[i],
                             max_new_tokens=max_new_short,
                             temperature=0.7, seed=500 + i)
                got: list = []
                last = None
                for chunk, final, meta in h.frames(timeout_s=600):
                    now = time.perf_counter()
                    if final and (meta.get("error")
                                  or meta.get("outcome") == "shed"):
                        raise RuntimeError(
                            f"stream failed: {meta.get('error', 'shed')}")
                    if not chunk:
                        continue
                    if last is not None:
                        with lock:
                            itls.append((now - last) * 1e3)
                    elif not got:
                        with lock:
                            n_first[0] += 1
                            if n_first[0] == n_short:
                                all_decoding.set()
                    last = now
                    got.extend(chunk)
                streams[i] = got
            except Exception as e:
                with lock:
                    fails.append(repr(e))

        threads = [_threading.Thread(target=client, args=(i,))
                   for i in range(n_short)]
        for t in threads:
            t.start()
        long_info = None
        if inject_long:
            all_decoding.wait(timeout=600)
            h = b.submit(long_prompt, **long_kw)
            frames = list(h.frames(timeout_s=600))
            meta0 = frames[0][2]
            long_info = {
                "tokens": [t for chunk, _f, _m in frames for t in chunk],
                "chunks": meta0.get("chunks"),
                "prefill_wait_ms": meta0.get("prefill_wait_ms"),
                "ttft_s": meta0.get("ttft_s")}
        for t in threads:
            t.join()
        return itls, streams, fails, long_info

    def idle_ttft(b):
        """Time-to-first-token for the long prompt on an idle batcher —
        raw prefill throughput, engine-side stamp (no client scheduling)."""
        frames = list(b.submit(long_prompt, **long_kw).frames(timeout_s=600))
        meta = frames[0][2]
        return (float(meta["ttft_s"]),
                [t for chunk, _f, _m in frames for t in chunk])

    def pctl(xs, q):
        return round(float(np.percentile(xs, q)), 3)

    out: dict = {
        "metric": "chunked prefill: long-prompt interleave vs whole-prompt",
        "prompt_tokens": prompt_len, "chunk_tokens": chunk_tokens,
        "short_streams": n_short, "page_size": page_size, "slots": slots,
        "model": f"transformer_lm(vocab={vocab},hidden={hidden},"
                 f"n_block={n_block},seq={max_seq})"}

    chunked_b = make(chunked=True)
    try:
        # alternate baseline/interleave trials and pool the ITL samples:
        # a single trial's p95 on a shared CPU host swings with scheduler
        # noise; alternation keeps both arms in the same noise regime
        base_itls, il_itls, il_fails, base_fails = [], [], [], []
        base_streams = il_streams = il_long = None
        for _trial in range(2):
            itls, base_streams, fails, _ = shorts_run(
                chunked_b, inject_long=False)
            base_itls += itls
            base_fails += fails
            itls, il_streams, fails, il_long = shorts_run(
                chunked_b, inject_long=True)
            il_itls += itls
            il_fails += fails
        chunked_ttft, chunked_idle_tokens = idle_ttft(chunked_b)
        st = chunked_b.stats()
        out["baseline"] = {
            "p50_itl_ms": pctl(base_itls, 50),
            "p95_itl_ms": pctl(base_itls, 95),
            "failed_streams": len(base_fails),
            "first_failure": base_fails[0] if base_fails else None}
        out["interleave"] = {
            "p50_itl_ms": pctl(il_itls, 50),
            "p95_itl_ms": pctl(il_itls, 95),
            "itl_p95_ratio": round(pctl(il_itls, 95)
                                   / max(pctl(base_itls, 95), 1e-9), 3),
            "long_chunks": il_long["chunks"],
            "long_prefill_wait_ms": il_long["prefill_wait_ms"],
            "short_tokens_identical": bool(il_streams == base_streams),
            "failed_streams": len(il_fails),
            "first_failure": il_fails[0] if il_fails else None}
        out["prefill_stats"] = dict(st["prefill"],
                                    budget=st["prefill"]["budget"])
        # chaos: kill the loop at the 3rd chunk dispatch of a fresh long
        # stream — slot state is untouched (the site fires BEFORE dispatch),
        # so the respawned loop re-runs exactly that chunk
        respawns0 = chunked_b.loop_respawns
        sched = ChaosSchedule(seed=11).kill("prefill.chunk", at=3)
        with sched:
            kill_tokens = chunked_b.generate(long_prompt, timeout_s=600,
                                             **long_kw)
        out["kill_drill"] = {
            "token_identical": bool(kill_tokens == chunked_idle_tokens),
            "loop_respawns": chunked_b.loop_respawns - respawns0,
            "chunk_occurrences": sched.occurrences("prefill.chunk")}
    finally:
        chunked_b.close()
    chunked_b.pool.check_conservation()
    out["kill_drill"]["pool_conserved"] = bool(
        chunked_b.pool.free_count() == chunked_b.pool.capacity)

    whole_b = make(chunked=False)
    try:
        wh_itls, _wh_streams, wh_fails, wh_long = shorts_run(
            whole_b, inject_long=True)
        whole_ttft, whole_idle_tokens = idle_ttft(whole_b)
        out["whole_prompt"] = {
            "p95_itl_ms": pctl(wh_itls, 95),
            "max_itl_ms": pctl(wh_itls, 100),
            "stall_over_baseline": round(
                pctl(wh_itls, 100) / max(pctl(base_itls, 95), 1e-9), 1),
            "failed_streams": len(wh_fails)}
    finally:
        whole_b.close()

    out["throughput"] = {
        "whole_ttft_s": round(whole_ttft, 4),
        "chunked_ttft_s": round(chunked_ttft, 4),
        # chunked prefill throughput as a fraction of whole-prompt (>1 =
        # chunking is faster; the causal chunks skip the padded-bucket
        # attention the whole prefill computes and masks)
        "ratio": round(whole_ttft / max(chunked_ttft, 1e-9), 3)}
    out["token_identical"] = bool(
        whole_idle_tokens == chunked_idle_tokens
        == il_long["tokens"])
    out["platform"] = str(jax.devices()[0].platform)
    return out


# --------------------------------------------------------------------------
# serving replica-fleet bench (ISSUE 9): router scaling + chaos-kill drill
# --------------------------------------------------------------------------

FLEET_SERVICE_MS = float(os.environ.get("ZOO_FLEET_BENCH_SERVICE_MS", "40"))
FLEET_BATCH = int(os.environ.get("ZOO_FLEET_BENCH_BATCH", "4"))


def _fleet_stub_model(service_time_s: float):
    """A device-bound stand-in model: ``predict`` blocks (GIL released) for a
    fixed service time per micro-batch, exactly like an XLA execute on a
    replica's own accelerator. The fleet bench measures the ROUTING TIER —
    dispatch, queue-depth balancing, failover requeue — on a 1-core CI host
    where N real compute-bound replicas could never overlap; a real
    deployment pins one replica per chip and the host CPU is not the
    bottleneck. The artifact records the stub's service time explicitly."""
    import numpy as np

    from analytics_zoo_tpu.inference import InferenceModel

    class _Stub(InferenceModel):
        def predict(self, inputs, batch_first=True):
            time.sleep(service_time_s)
            x = np.asarray(inputs)
            return x.sum(axis=tuple(range(1, x.ndim)), keepdims=True)

    return _Stub()


def _fleet_run_phase(broker_port: int, n_replicas: int, n_requests: int,
                     service_s: float, *, kill_rid=None,
                     submit_threads: int = 4) -> dict:
    """One fleet phase: N replicas behind the router, ``n_requests`` streamed
    in from ``submit_threads`` producers, every uri fetched exactly once.
    ``kill_rid`` hard-kills that replica once ~1/3 of the requests are in
    (the chaos drill) and asserts reconvergence."""
    import threading

    import numpy as np

    from analytics_zoo_tpu.serving import (FleetSupervisor, InputQueue,
                                           OutputQueue, ServingConfig)

    cfg = ServingConfig(queue_port=broker_port, batch_size=FLEET_BATCH,
                        batch_timeout_ms=2, replicas=n_replicas,
                        fleet_heartbeat_s=0.1, fleet_failover_timeout_s=0.8,
                        fleet_spawn_grace_s=10.0, breaker_reset_timeout_s=0.5)
    fleet = FleetSupervisor(
        cfg, model_factory=lambda: _fleet_stub_model(service_s))
    fleet.start()
    try:
        assert fleet.wait_eligible(n_replicas, timeout_s=15), \
            f"fleet never reached {n_replicas} eligible: {fleet.router.stats()}"
        uris: list = []
        uris_lock = threading.Lock()
        t0 = time.perf_counter()

        def submit(idx: int):
            iq = InputQueue(port=broker_port)
            try:
                for i in range(idx, n_requests, submit_threads):
                    u = iq.enqueue(None, input=np.full((4,), float(i),
                                                       np.float32))
                    with uris_lock:
                        uris.append((i, u))
            finally:
                iq.close()

        threads = [threading.Thread(target=submit, args=(i,), daemon=True)
                   for i in range(submit_threads)]
        for t in threads:
            t.start()
        killed_at = None
        if kill_rid is not None:
            while True:
                with uris_lock:
                    n_in = len(uris)
                if n_in >= n_requests // 3:
                    break
                time.sleep(0.005)
            fleet.kill_replica(kill_rid)
            killed_at = time.perf_counter() - t0
        for t in threads:
            t.join()
        oq = OutputQueue(port=broker_port)
        failed = []
        try:
            for i, u in sorted(uris):
                try:
                    v = oq.query(u, timeout_s=60)
                    # response-count accounting: the answer must be THIS
                    # request's (sum of its filled input), exactly once
                    if abs(float(np.asarray(v).ravel()[0]) - 4.0 * i) > 1e-5:
                        failed.append((u, "wrong value"))
                except Exception as e:
                    failed.append((u, repr(e)))
        finally:
            oq.close()
        wall = time.perf_counter() - t0
        reconverged = fleet.wait_eligible(n_replicas, timeout_s=15)
        events_audit = None
        if kill_rid is not None:
            # decision-event audit (ISSUE 15): the kill's failover must be
            # on the event stream, its trace must export whole (containing
            # the fleet.failover span), and /debug/events must serve valid
            # JSON over HTTP while the fleet is still up
            import urllib.request

            from analytics_zoo_tpu.observability import events as _events
            from analytics_zoo_tpu.observability import export_trace
            from analytics_zoo_tpu.serving.http_frontend import FrontEndApp

            failovers = [e for e in _events.events(kind="fleet.failover")
                         if e.fields.get("replica") == kill_rid]
            traces_ok = bool(failovers) and all(
                e.trace_id and any(
                    s["name"] == "fleet.failover"
                    for s in (export_trace(e.trace_id)
                              or {"traceEvents": []})["traceEvents"])
                for e in failovers)
            app = FrontEndApp(cfg, port=0).start()
            try:
                with urllib.request.urlopen(
                        f"http://127.0.0.1:{app.port}/debug/events",
                        timeout=10) as r:
                    page = json.loads(r.read())
                scrape_ok = any(ev["kind"] == "fleet.failover"
                                for ev in page["events"])
            except Exception:
                scrape_ok = False
            finally:
                app.stop()
            events_audit = {"failover_events": len(failovers),
                            "traces_complete": traces_ok,
                            "debug_scrape_ok": scrape_ok}
        out = {
            "replicas": n_replicas,
            "requests": n_requests,
            "failed_requests": len(failed),
            "first_failure": failed[0] if failed else None,
            "wall_seconds": round(wall, 3),
            "req_per_s": round(n_requests / wall, 1),
            "requeued": fleet.requeued,
            "respawns": fleet.respawns,
            "failover_s": ([round(f, 3) for f in fleet.failovers] or None),
            "eligible_at_end": len(fleet.router.eligible_ids()),
            "reconverged": reconverged,
            "dispatch": {rid: s["dispatched"] for rid, s in
                         fleet.router.stats()["replicas"].items()},
        }
        if killed_at is not None:
            out["killed_replica"] = kill_rid
            out["killed_at_s"] = round(killed_at, 3)
        if events_audit is not None:
            out["events"] = events_audit
        return out
    finally:
        fleet.stop(drain_s=2.0)


def run_fleet_bench(quick: bool = False) -> dict:
    """Replica-fleet scaling + failover artifact (FLEET_BENCH.json).

    Scaling arms run 1 → (2) → 4 stub replicas (fixed per-batch service
    time, see _fleet_stub_model) over a fresh broker each and record closed-
    set req/s; the drill arm runs 4 replicas under sustained submission,
    hard-kills one mid-burst, and verifies ZERO lost requests (every uri
    answered exactly once — duplicates are dropped broker-side by HSETNX and
    counted), plus reconvergence to 4 eligible replicas."""
    from analytics_zoo_tpu.serving import start_broker

    service_s = FLEET_SERVICE_MS / 1e3
    # enough requests that steady-state routing dominates the ramp/tail
    # (short runs understate the 4-replica arm: partial first/last batches
    # and the eligibility ramp are a fixed cost)
    n_requests = 360 if quick else 720
    arms = (1, 4) if quick else (1, 2, 4)
    out: dict = {
        "metric": "serving fleet scaling (routed replicas, stub model)",
        "unit": "req/s",
        "service_time_ms": FLEET_SERVICE_MS,
        "batch_size": FLEET_BATCH,
        "model": "device-bound stub (sleep(service_time) per micro-batch; "
                 "measures the routing tier, not XLA)",
        "scaling": {},
    }
    for n in arms:
        broker = start_broker()
        try:
            out["scaling"][str(n)] = _fleet_run_phase(
                broker.port, n, n_requests, service_s)
        finally:
            broker.shutdown()
    r1 = out["scaling"]["1"]["req_per_s"]
    r4 = out["scaling"]["4"]["req_per_s"]
    out["value"] = r4
    out["speedup_4_vs_1"] = round(r4 / r1, 2)

    from analytics_zoo_tpu.serving.broker import _DUP_DROPPED

    dups_before = _DUP_DROPPED.value()
    broker = start_broker()
    try:
        drill = _fleet_run_phase(broker.port, 4,
                                 180 if quick else 400, service_s,
                                 kill_rid="r1")
    finally:
        broker.shutdown()
    drill["duplicates_dropped"] = int(_DUP_DROPPED.value() - dups_before)
    out["chaos_drill"] = drill
    return out


def run_host_fleet_bench(quick: bool = False, n_hosts: int = 2) -> dict:
    """Cross-host fleet arm (ISSUE 16): host-level failure domains.

    Topology: ``n_hosts`` in-process HostAgents, replicas spread across them
    by the placement policy. The drill hard-kills ONE ENTIRE HOST mid-burst
    (agent.kill() — every replica dies at once, no goodbye heartbeat) and
    verifies the whole-host failover contract: every request answered
    exactly once, ONE ``fleet.host_failed`` decision whose exported trace
    stitches spans from both hosts, survivors absorb the respawns, and a
    dial to the dead host fails fast through the per-host breaker with a
    computed Retry-After."""
    import threading

    import numpy as np

    from analytics_zoo_tpu.common import resilience as _res
    from analytics_zoo_tpu.observability import ObservabilityPlane
    from analytics_zoo_tpu.observability import events as _events
    from analytics_zoo_tpu.observability import export_trace
    from analytics_zoo_tpu.serving import (FleetSupervisor, InputQueue,
                                           OutputQueue, ServingConfig,
                                           start_broker)

    service_s = FLEET_SERVICE_MS / 1e3
    n_replicas = 2 * n_hosts
    n_requests = 120 if quick else 400
    broker = start_broker()
    cfg = ServingConfig(queue_port=broker.port, batch_size=FLEET_BATCH,
                        batch_timeout_ms=2, replicas=n_replicas,
                        fleet_hosts=n_hosts, fleet_heartbeat_s=0.1,
                        fleet_failover_timeout_s=0.8,
                        fleet_spawn_grace_s=10.0,
                        breaker_reset_timeout_s=0.5,
                        # the SLO verdict the drill gates on: the critical
                        # class must ride out the whole-host kill without
                        # its latency objective ever firing (requeued
                        # requests wait one failover detection, well under
                        # the threshold)
                        slo_objectives=(
                            {"name": "critical-latency", "type": "latency",
                             "priority": "critical",
                             "threshold_ms": 2500.0, "target": 0.9},),
                        slo_fast_window_s=2.0, slo_slow_window_s=8.0,
                        slo_burn_factor=4.0)
    plane = ObservabilityPlane.from_config(cfg).start()
    fleet = FleetSupervisor(
        cfg, model_factory=lambda: _fleet_stub_model(service_s))
    fleet.start()
    try:
        assert fleet.wait_eligible(n_replicas, timeout_s=15), \
            f"host fleet never reached {n_replicas}: {fleet.router.stats()}"
        topology = {hid: sorted(s.replicas)
                    for hid, s in fleet._hosts.items()}
        uris: list = []
        uris_lock = threading.Lock()
        t0 = time.perf_counter()

        def submit(idx: int, threads: int = 4):
            iq = InputQueue(port=broker.port)
            try:
                for i in range(idx, n_requests, threads):
                    u = iq.enqueue(None, priority="critical",
                                   input=np.full((4,), float(i),
                                                 np.float32))
                    with uris_lock:
                        uris.append((i, u))
            finally:
                iq.close()

        threads = [threading.Thread(target=submit, args=(i,), daemon=True)
                   for i in range(4)]
        for t in threads:
            t.start()
        while True:
            with uris_lock:
                if len(uris) >= n_requests // 3:
                    break
            time.sleep(0.005)
        victim = "h0"
        fleet.kill_host(victim)
        killed_at = time.perf_counter() - t0
        for t in threads:
            t.join()

        oq = OutputQueue(port=broker.port)
        failed = []
        try:
            for i, u in sorted(uris):
                try:
                    v = oq.query(u, timeout_s=60)
                    if abs(float(np.asarray(v).ravel()[0]) - 4.0 * i) > 1e-5:
                        failed.append((u, "wrong value"))
                except Exception as e:
                    failed.append((u, repr(e)))
        finally:
            oq.close()
        wall = time.perf_counter() - t0

        # let the SLO evaluator tick past the fast window before reading
        # the verdict — a breach during the kill would fire within it
        time.sleep(2.5)
        slo_fired = [e for e in _events.events(kind="slo.firing")
                     if e.fields.get("objective") == "critical-latency"]

        host_events = [e for e in _events.events(kind="fleet.host_failed")
                       if e.fields.get("host") == victim]
        trace_hosts: list = []
        if host_events:
            tr = export_trace(host_events[-1].trace_id) or {}
            trace_hosts = sorted(tr.get("otherData", {}).get("hosts", ()))
        # fail-fast contract: the breaker answers without touching the
        # network, with an honest Retry-After. A first dial may land in the
        # half-open window (the drain outlasts breaker_reset_timeout_s) —
        # its probe judges the heartbeat stale and re-opens, so the SECOND
        # dial must be the fast path either way.
        dial = {"fast_failed": False, "retry_after_s": None}
        for _ in range(2):
            t_dial = time.perf_counter()
            try:
                fleet.dial_host(victim)
                break
            except _res.CircuitOpenError as e:
                dial = {"fast_failed": True,
                        "retry_after_s": round(e.retry_after_s, 3)}
                break
            except ConnectionError:
                continue            # half-open probe: breaker re-opened
        dial["dial_seconds"] = round(time.perf_counter() - t_dial, 4)

        return {
            "hosts": n_hosts,
            "replicas": n_replicas,
            "requests": n_requests,
            "topology_before_kill": topology,
            "killed_host": victim,
            "killed_at_s": round(killed_at, 3),
            "failed_requests": len(failed),
            "first_failure": failed[0] if failed else None,
            "wall_seconds": round(wall, 3),
            "req_per_s": round(n_requests / wall, 1),
            "requeued": fleet.requeued,
            "host_failovers": fleet.host_failovers,
            "host_failed_events": len(host_events),
            "respawned": (host_events[-1].fields.get("respawned")
                          if host_events else None),
            "trace_hosts": trace_hosts,
            "dial_dead_host": dial,
            "critical_slo_fired": len(slo_fired),
            "eligible_at_end": len(fleet.router.eligible_ids()),
        }
    finally:
        fleet.stop(drain_s=2.0)
        plane.stop()
        broker.shutdown()


# --------------------------------------------------------------------------
# adaptive-serving-under-overload bench (ISSUE 13): bimodal traffic at 2x
# capacity (high-priority p99 holds its SLO while bulk sheds with computed
# Retry-After) + the autoscale 1->4->1 zero-loss drill
# --------------------------------------------------------------------------

OVERLOAD_SERVICE_MS = float(os.environ.get("ZOO_OVERLOAD_BENCH_SERVICE_MS",
                                           "80"))


def _overload_bimodal_phase(broker_port: int, *, n_replicas: int,
                            service_s: float, duration_s: float,
                            crit_deadline_ms: float,
                            bulk_deadline_ms: float) -> dict:
    """Bimodal traffic against a fixed fleet: a few CLOSED-loop critical
    clients (per-request latency measured end to end, tight deadline) ride
    alongside an OPEN-loop bulk flood offered at ~2x the fleet's nominal
    capacity. Without QoS this queues everything to timeout; with it the
    critical class holds its SLO while bulk degrades to shed-with-honest-
    Retry-After."""
    import threading

    import numpy as np

    from analytics_zoo_tpu.serving import (FleetSupervisor, InputQueue,
                                           OutputQueue, ServingConfig,
                                           ShedError)

    from urllib.request import urlopen

    from analytics_zoo_tpu.observability import ObservabilityPlane
    from analytics_zoo_tpu.serving.http_frontend import FrontEndApp

    capacity = n_replicas * FLEET_BATCH / service_s      # req/s, nominal
    bulk_rate = 2.2 * capacity      # the overload (margin over the 2x
                                    # gate: sleep jitter on a loaded 1-core
                                    # host only ever LOWERS the real rate)
    cfg = ServingConfig(queue_port=broker_port, batch_size=FLEET_BATCH,
                        batch_timeout_ms=2, replicas=n_replicas,
                        fleet_heartbeat_s=0.1, fleet_failover_timeout_s=1.5,
                        fleet_spawn_grace_s=10.0,
                        # SLO verdicts for the drill (ISSUE 15): the
                        # critical latency objective must NEVER fire while
                        # the bulk availability alert fires under overload
                        # and resolves after the load drops. Windows are
                        # drill-scaled; burn math is the production path.
                        slo_objectives=(
                            {"name": "critical-latency", "type": "latency",
                             "priority": "critical",
                             "threshold_ms": crit_deadline_ms,
                             "target": 0.9},
                            {"name": "bulk-availability",
                             "type": "availability", "priority": "bulk",
                             "target": 0.9}),
                        slo_fast_window_s=2.0, slo_slow_window_s=8.0,
                        slo_burn_factor=4.0)
    plane = ObservabilityPlane.from_config(cfg).start()
    app = FrontEndApp(cfg, port=0, plane=plane).start()
    fleet = FleetSupervisor(
        cfg, model_factory=lambda: _fleet_stub_model(service_s))
    fleet.start()
    stop = threading.Event()
    crit_lat: list = []
    crit_fail: list = []
    crit_shed = [0]
    bulk_uris: list = []
    bulk_lock = threading.Lock()
    try:
        assert fleet.wait_eligible(n_replicas, timeout_s=15), \
            fleet.router.stats()

        def critical_client(idx: int):
            iq = InputQueue(port=broker_port)
            oq = OutputQueue(port=broker_port)
            i = 0
            try:
                while not stop.is_set():
                    i += 1
                    t0 = time.perf_counter()
                    try:
                        u = iq.enqueue(None, priority="critical",
                                       deadline_ms=crit_deadline_ms,
                                       input=np.full((4,), float(i),
                                                     np.float32))
                        v = oq.query(u, timeout_s=30)
                        if abs(float(np.asarray(v).ravel()[0])
                               - 4.0 * i) > 1e-5:
                            crit_fail.append((u, "wrong value"))
                        else:
                            crit_lat.append(time.perf_counter() - t0)
                    except ShedError:
                        crit_shed[0] += 1
                    except Exception as e:
                        crit_fail.append((f"c{idx}-{i}", repr(e)))
            finally:
                iq.close()
                oq.close()

        def bulk_flood(idx: int, n_threads: int):
            iq = InputQueue(port=broker_port)
            interval = n_threads / bulk_rate
            # schedule-based pacing: sleep overshoot (rampant on a loaded
            # 1-core host) must not accumulate into a lower offered rate —
            # a thread that fell behind its schedule catches up
            next_t = time.monotonic() + idx * interval / n_threads
            try:
                while not stop.is_set():
                    now = time.monotonic()
                    if now < next_t:
                        time.sleep(min(0.005, next_t - now))
                        continue
                    next_t += interval
                    u = iq.enqueue(None, priority="bulk",
                                   deadline_ms=bulk_deadline_ms,
                                   input=np.full((4,), 1.0, np.float32))
                    with bulk_lock:
                        bulk_uris.append(u)
            finally:
                iq.close()

        n_bulk_threads = 4
        threads = [threading.Thread(target=critical_client, args=(i,),
                                    daemon=True) for i in range(3)]
        threads += [threading.Thread(target=bulk_flood,
                                     args=(i, n_bulk_threads), daemon=True)
                    for i in range(n_bulk_threads)]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        # scrape the /debug ops surface DURING the overload (the CI gate:
        # valid JSON, and the bulk-class alert observed firing over HTTP)
        scrapes = {"slo_ok": 0, "slo_bad": 0, "events_ok": 0,
                   "events_bad": 0}
        fired_over_http: set = set()
        drill_end = time.monotonic() + duration_s
        while time.monotonic() < drill_end:
            time.sleep(min(0.5, max(0.05, drill_end - time.monotonic())))
            try:
                with urlopen(f"http://127.0.0.1:{app.port}/debug/slo",
                             timeout=5) as r:
                    slo_page = json.loads(r.read())
                scrapes["slo_ok"] += 1
                for o in slo_page.get("objectives", ()):
                    if o["state"] == "firing":
                        fired_over_http.add(o["name"])
            except Exception:
                scrapes["slo_bad"] += 1
            try:
                with urlopen(f"http://127.0.0.1:{app.port}/debug/events",
                             timeout=5) as r:
                    json.loads(r.read())
                scrapes["events_ok"] += 1
            except Exception:
                scrapes["events_bad"] += 1
        stop.set()
        for t in threads:
            t.join(timeout=30)
        wall = time.perf_counter() - t0
        # every bulk uri must be ANSWERED — served or shed with a computed
        # Retry-After — never silently queued to timeout
        served = shed = timeout = 0
        retry_afters: list = []
        oq = OutputQueue(port=broker_port)
        try:
            for u in bulk_uris:
                try:
                    oq.query(u, timeout_s=30)
                    served += 1
                except ShedError as e:
                    shed += 1
                    retry_afters.append(e.retry_after_s)
                except Exception:
                    timeout += 1
        finally:
            oq.close()
        # SLO verdicts: bulk-availability must have FIRED during overload
        # and must RESOLVE now that the load stopped (the fast window acts
        # as the resolver); critical-latency must never have fired
        engine = plane.slo
        resolve_deadline = time.monotonic() + 15.0
        while time.monotonic() < resolve_deadline and \
                engine.state_of("bulk-availability") == "firing":
            time.sleep(0.25)
        from analytics_zoo_tpu.observability import events as _events
        from analytics_zoo_tpu.observability import export_trace

        shed_events = _events.events(kind="shed")
        slo_events = _events.events(kind="slo")
        slo_verdict = {
            "critical_fired": engine.ever_fired("critical-latency"),
            "bulk_fired": engine.ever_fired("bulk-availability"),
            "bulk_fired_over_http": "bulk-availability" in fired_over_http,
            "bulk_resolved":
                engine.state_of("bulk-availability") == "ok",
            "scrapes": scrapes,
            "shed_events": len(shed_events),
            "slo_transition_events": len(slo_events),
            "event_traces_resolve": all(
                (export_trace(e.trace_id) or {}).get("traceEvents")
                for e in slo_events + shed_events if e.trace_id),
            "objectives": engine.objective_states(),
        }
        lat = sorted(crit_lat)

        def pct(q):
            return (round(lat[min(len(lat) - 1,
                                  int(q * len(lat)))] * 1e3, 1)
                    if lat else None)

        offered = (len(bulk_uris) + len(crit_lat) + crit_shed[0]
                   + len(crit_fail)) / wall
        return {
            "replicas": n_replicas,
            "capacity_req_per_s": round(capacity, 1),
            "offered_req_per_s": round(offered, 1),
            "offered_over_capacity": round(offered / capacity, 2),
            "duration_s": round(wall, 2),
            "critical": {
                "served": len(lat), "shed": crit_shed[0],
                "failed": len(crit_fail),
                "first_failure": crit_fail[0] if crit_fail else None,
                "deadline_ms": crit_deadline_ms,
                "p50_ms": pct(0.50), "p99_ms": pct(0.99),
            },
            "bulk": {
                "offered": len(bulk_uris), "served": served, "shed": shed,
                "unanswered": timeout,
                "shed_fraction": round(shed / max(1, len(bulk_uris)), 3),
                "deadline_ms": bulk_deadline_ms,
                "retry_after_s": {
                    "min": round(min(retry_afters), 4) if retry_afters
                    else None,
                    "max": round(max(retry_afters), 4) if retry_afters
                    else None,
                    "mean": round(sum(retry_afters) / len(retry_afters), 4)
                    if retry_afters else None,
                },
            },
            "router_shed": fleet.router.shed,
            "slo": slo_verdict,
        }
    finally:
        stop.set()
        fleet.stop(drain_s=2.0)
        plane.stop()
        app.stop()


def _overload_autoscale_phase(broker_port: int, *, service_s: float,
                              max_replicas: int, duration_s: float) -> dict:
    """The 1->max->1 drill: sustained load makes the supervisor spawn up to
    ``max_replicas`` on queue pressure; when the load stops it drains back
    down to 1 — and every submitted request is answered exactly once
    (graceful drain + straggler XTRANSFER make scale events zero-loss by
    construction; HSETNX dedup makes duplicates impossible to miss)."""
    import threading

    import numpy as np

    from analytics_zoo_tpu.serving import (FleetSupervisor, InputQueue,
                                           OutputQueue, ServingConfig)
    from analytics_zoo_tpu.serving.broker import _DUP_DROPPED

    cfg = ServingConfig(queue_port=broker_port, batch_size=FLEET_BATCH,
                        batch_timeout_ms=2, replicas=1,
                        autoscale=True, min_replicas=1,
                        max_replicas=max_replicas,
                        autoscale_up_depth=4.0, autoscale_sustain_s=0.25,
                        autoscale_idle_s=0.8, autoscale_cooldown_s=0.2,
                        fleet_heartbeat_s=0.1, fleet_failover_timeout_s=1.5,
                        fleet_spawn_grace_s=10.0)
    fleet = FleetSupervisor(
        cfg, model_factory=lambda: _fleet_stub_model(service_s))
    fleet.start()
    dups0 = _DUP_DROPPED.value()
    uris: list = []
    lock = threading.Lock()
    stop = threading.Event()
    replica_peak = [1]
    try:
        assert fleet.wait_eligible(1, timeout_s=15)
        rate = 1.6 * max_replicas * FLEET_BATCH / service_s / 2  # ~1.6x of
        # half the max fleet: enough pressure to scale, drainable by max

        def flood(idx: int, n_threads: int):
            iq = InputQueue(port=broker_port)
            interval = n_threads / rate
            i = idx
            try:
                while not stop.is_set():
                    u = iq.enqueue(None, input=np.full((4,), float(i),
                                                       np.float32))
                    with lock:
                        uris.append((i, u))
                    i += n_threads
                    time.sleep(interval)
            finally:
                iq.close()

        threads = [threading.Thread(target=flood, args=(i, 3), daemon=True)
                   for i in range(3)]
        for t in threads:
            t.start()
        # flood for duration_s; then keep the pressure on (up to 25s more)
        # until the fleet actually reaches max_replicas
        t_min = time.monotonic() + duration_s
        t_max = t_min + 25.0
        while time.monotonic() < t_max:
            replica_peak[0] = max(replica_peak[0],
                                  len(fleet.router.replica_ids()))
            if time.monotonic() >= t_min and replica_peak[0] >= max_replicas:
                break
            time.sleep(0.05)
        stop.set()
        for t in threads:
            t.join(timeout=30)
        scaled_up = replica_peak[0] >= max_replicas
        # fetch every uri exactly once, value-checked
        failed: list = []
        oq = OutputQueue(port=broker_port)
        try:
            for i, u in sorted(uris):
                try:
                    v = oq.query(u, timeout_s=60)
                    if abs(float(np.asarray(v).ravel()[0]) - 4.0 * i) > 1e-5:
                        failed.append((u, "wrong value"))
                except Exception as e:
                    failed.append((u, repr(e)))
        finally:
            oq.close()
        # idle: the fleet must drain back down to min_replicas
        shrink_deadline = time.monotonic() + 40
        while time.monotonic() < shrink_deadline and \
                len(fleet.router.replica_ids()) > 1:
            time.sleep(0.1)
        # decision-event audit (ISSUE 15): every scale action must appear on
        # the event stream with a trace that exports as a complete Perfetto
        # trace containing the fleet.autoscale span
        from analytics_zoo_tpu.observability import events as _events
        from analytics_zoo_tpu.observability import export_trace

        def _trace_complete(ev) -> bool:
            t = export_trace(ev.trace_id) if ev.trace_id else None
            return bool(t) and any(e["name"] == "fleet.autoscale"
                                   for e in t["traceEvents"])

        ups = _events.events(kind="autoscale.up")
        downs = _events.events(kind="autoscale.down")
        return {
            "requests": len(uris),
            "failed_requests": len(failed),
            "first_failure": failed[0] if failed else None,
            "duplicates_dropped": int(_DUP_DROPPED.value() - dups0),
            "replica_peak": replica_peak[0],
            "scaled_up_to_max": scaled_up,
            "scaled_back_to_min": len(fleet.router.replica_ids()) == 1,
            "scale_events": list(fleet.scale_events),
            "requeued": fleet.requeued,
            "events": {
                "autoscale_up": len(ups),
                "autoscale_down": len(downs),
                "matches_scale_events":
                    len(ups) + len(downs) >= len(fleet.scale_events),
                "traces_complete": bool(ups + downs) and all(
                    _trace_complete(e) for e in ups + downs),
            },
        }
    finally:
        stop.set()
        fleet.stop(drain_s=2.0)


def run_overload_bench(quick: bool = False) -> dict:
    """Adaptive-serving-under-overload artifact (OVERLOAD_BENCH.json)."""
    from analytics_zoo_tpu.serving import start_broker

    service_s = OVERLOAD_SERVICE_MS / 1e3
    out: dict = {
        "metric": "bimodal overload QoS (critical SLO at 2x capacity) + "
                  "autoscale 1->4->1 zero-loss drill",
        "service_time_ms": OVERLOAD_SERVICE_MS,
        "batch_size": FLEET_BATCH,
        "model": "device-bound stub (sleep(service_time) per micro-batch; "
                 "measures the QoS/routing tier, not XLA)",
        "slo_ms": 1500.0,
    }
    broker = start_broker()
    try:
        out["bimodal"] = _overload_bimodal_phase(
            broker.port, n_replicas=2, service_s=service_s,
            duration_s=2.5 if quick else 6.0,
            crit_deadline_ms=out["slo_ms"], bulk_deadline_ms=600.0)
    finally:
        broker.shutdown()
    broker = start_broker()
    try:
        out["autoscale"] = _overload_autoscale_phase(
            broker.port, service_s=0.05, max_replicas=4,
            duration_s=3.0 if quick else 5.0)
    finally:
        broker.shutdown()
    out["value"] = out["bimodal"]["critical"]["p99_ms"]
    out["unit"] = "ms (critical p99 at 2x capacity)"
    return out


# --------------------------------------------------------------------------
# flight-recorder replay bench (ISSUE 18): record an overload trace with the
# always-on flight recorder, then score two admission policies OFFLINE on
# the identical input stream — with the determinism gate that the incumbent
# replay reproduces the live decision sequence exactly
# --------------------------------------------------------------------------

def run_replay_bench(quick: bool = False) -> dict:
    """Replay-bench artifact (REPLAY_BENCH.json): bulk flood at ~2.2x fleet
    capacity with the flight recorder installed, dump the trace, then (a)
    verify the incumbent policy replays it bit-exactly, (b) replay a
    candidate watermark policy twice (must be deterministic) and diff it
    against the incumbent on the same recorded inputs."""
    import tempfile
    import threading

    import numpy as np

    from analytics_zoo_tpu.observability import recorder as _flight
    from analytics_zoo_tpu.observability import replay as _replay
    from analytics_zoo_tpu.serving import (FleetSupervisor, InputQueue,
                                           OutputQueue, ServingConfig,
                                           ShedError, start_broker)

    n_replicas = 2
    service_s = 0.04
    duration_s = 2.0 if quick else 5.0
    bulk_deadline_ms = 400.0
    capacity = n_replicas * FLEET_BATCH / service_s
    bulk_rate = 2.2 * capacity
    dump_dir = tempfile.mkdtemp(prefix="zoo-flight-bench-")
    rec = _flight.install(dump_dir=dump_dir, capacity=65536, signals=())
    broker = start_broker()
    stop = threading.Event()
    uris: list = []
    uris_lock = threading.Lock()
    dump_path = None
    try:
        cfg = ServingConfig(queue_port=broker.port, batch_size=FLEET_BATCH,
                            batch_timeout_ms=2, replicas=n_replicas,
                            fleet_heartbeat_s=0.1,
                            fleet_failover_timeout_s=1.5,
                            fleet_spawn_grace_s=10.0)
        fleet = FleetSupervisor(
            cfg, model_factory=lambda: _fleet_stub_model(service_s))
        fleet.start()
        try:
            assert fleet.wait_eligible(n_replicas, timeout_s=15), \
                fleet.router.stats()

            def flood(idx: int, n_threads: int):
                iq = InputQueue(port=broker.port)
                interval = n_threads / bulk_rate
                next_t = time.monotonic() + idx * interval / n_threads
                try:
                    while not stop.is_set():
                        now = time.monotonic()
                        if now < next_t:
                            time.sleep(min(0.005, next_t - now))
                            continue
                        next_t += interval
                        u = iq.enqueue(None, priority="bulk",
                                       deadline_ms=bulk_deadline_ms,
                                       input=np.full((4,), 1.0,
                                                     np.float32))
                        with uris_lock:
                            uris.append(u)
                finally:
                    iq.close()

            n_threads = 4
            threads = [threading.Thread(target=flood, args=(i, n_threads),
                                        daemon=True)
                       for i in range(n_threads)]
            for t in threads:
                t.start()
            time.sleep(duration_s)
            stop.set()
            for t in threads:
                t.join(timeout=30)
            served = shed = unanswered = 0
            oq = OutputQueue(port=broker.port)
            try:
                for u in uris:
                    try:
                        oq.query(u, timeout_s=30)
                        served += 1
                    except ShedError:
                        shed += 1
                    except Exception:
                        unanswered += 1
            finally:
                oq.close()
        finally:
            stop.set()
            fleet.stop(drain_s=2.0)
        dump_path = rec.dump(trigger="bench")
    finally:
        _flight.uninstall()
        broker.shutdown()

    records = _replay.load_records(dump_path)
    admission_records = [r for r in records
                         if r["site"].startswith("admission.")]
    # gate 1: the incumbent replays the recorded trace bit-exactly
    verify = _replay.verify_incumbent(records)
    incumbent = _replay.replay(records, _replay.IncumbentPolicy())
    # gate 2: a candidate policy is deterministic across replays of the
    # same recording (same virtual clock, same inputs -> same signature)
    cand_a = _replay.replay(
        records, _replay.WatermarkAdmissionPolicy(watermark_s=0.05))
    cand_b = _replay.replay(
        records, _replay.WatermarkAdmissionPolicy(watermark_s=0.05))
    deterministic = cand_a.signature() == cand_b.signature()
    divergences = _replay.diff_runs(incumbent, cand_a)
    out = {
        "metric": "offline policy bench on a recorded overload trace "
                  "(incumbent exact-replay + candidate watermark diff)",
        "service_time_ms": service_s * 1e3,
        "batch_size": FLEET_BATCH,
        "capacity_req_per_s": round(capacity, 1),
        "offered_over_capacity": 2.2,
        "duration_s": duration_s,
        "live": {"offered": len(uris), "served": served, "shed": shed,
                 "unanswered": unanswered},
        "dump": {"path": dump_path, "records": len(records),
                 "admission_records": len(admission_records)},
        "incumbent_exact": verify["exact"],
        "incumbent_divergences": verify["divergences"],
        "candidate_deterministic": deterministic,
        "policy_divergences": len(divergences),
        "scores": {
            "incumbent": _replay.score_admission(incumbent),
            "candidate": _replay.score_admission(cand_a),
        },
        "value": len(divergences),
        "unit": "decision divergences (incumbent vs watermark candidate)",
    }
    return out


# --------------------------------------------------------------------------
# model hot-swap bench (ISSUE 10): trainer→fleet checkpoint streaming with
# canary rollout, sustained load through consecutive swaps + chaos
# --------------------------------------------------------------------------

def _hotswap_model_factory():
    """A real (loaded, checkpoint-swappable) linear model: response =
    sum(input) + b, with b carrying the VERSION OFFSET — so every answer is
    attributable to exactly (request, model version), and a mixed-weights
    answer is arithmetically impossible to miss."""
    import numpy as np

    from analytics_zoo_tpu.inference import InferenceModel

    w = np.ones((4, 1), np.float32)
    im = InferenceModel(max_batch_size=8)
    im.load_fn(lambda p, s, x: x @ p["w"] + p["b"],
               params={"w": w, "b": np.zeros(1, np.float32)})
    return im


def run_hotswap_bench(quick: bool = False) -> dict:
    """Hot-swap drill artifact (HOTSWAP_BENCH.json): a 4-replica fleet under
    sustained closed-loop load takes >=3 consecutive canary-rolled version
    swaps, one canary hard-kill mid-rollout, and one NaN-poisoned publish.

    Measured: per-request RTT p50/p95 split into steady vs swap-window
    phases, zero-failed accounting with value↔version-tag cross-checks
    (offset b = 1000*version ⇒ a response's value proves which weights
    produced it), rollback/rejection counts, final fleet convergence."""
    import tempfile
    import threading

    import numpy as np

    from analytics_zoo_tpu.engine.checkpoint import save_checkpoint
    from analytics_zoo_tpu.serving import (FleetSupervisor, InputQueue,
                                           ModelPublisher, OutputQueue,
                                           ServingConfig, start_broker)

    n_clients = 4
    broker = start_broker()
    cfg = ServingConfig(queue_port=broker.port, batch_size=4,
                        batch_timeout_ms=2, replicas=4,
                        fleet_heartbeat_s=0.1, fleet_failover_timeout_s=0.8,
                        fleet_spawn_grace_s=10.0, warmup_shape=(4,),
                        rollout_window_s=0.5 if quick else 1.0,
                        rollout_min_requests=6,
                        rollout_canary_fraction=0.25, swap_timeout_s=15.0,
                        breaker_reset_timeout_s=0.5)
    fleet = FleetSupervisor(cfg, model_factory=_hotswap_model_factory)
    fleet.start()
    pub = ModelPublisher(port=broker.port)
    ckpt_dir = tempfile.mkdtemp(prefix="zoo-hotswap-bench-")
    w = np.ones((4, 1), np.float32)

    stop = threading.Event()
    lock = threading.Lock()
    results: list = []      # (i, value, version_tag, rtt_s, t_done)

    def client(idx: int):
        iq = InputQueue(port=broker.port)
        oq = OutputQueue(port=broker.port)
        i = idx
        try:
            while not stop.is_set():
                t0 = time.perf_counter()
                u = iq.enqueue(None, input=np.full((4,), float(i),
                                                   np.float32))
                try:
                    v = oq.query(u, timeout_s=30)
                    rec = (i, float(np.ravel(v)[0]), oq.last_model_version,
                           time.perf_counter() - t0, time.perf_counter())
                except Exception as e:
                    rec = (i, None, repr(e), time.perf_counter() - t0,
                           time.perf_counter())
                with lock:
                    results.append(rec)
                i += n_clients
        finally:
            iq.close()
            oq.close()

    def publish_version(v: int, poisoned: bool = False):
        b = np.array([np.nan if poisoned else 1000.0 * v], np.float32)
        path = save_checkpoint(ckpt_dir, {"w": w, "b": b}, iteration=v,
                               epoch=0)
        return pub.publish(path)

    def wait_converged(version: str, timeout_s: float = 30.0) -> bool:
        deadline = time.monotonic() + timeout_s
        while time.monotonic() < deadline:
            mv = fleet.model_versions()
            if mv and all(val == version for val in mv.values()) \
                    and fleet.rollout.state()["phase"] == "idle":
                return True
            time.sleep(0.1)
        return False

    def wait_rejected(version: str, timeout_s: float = 30.0) -> bool:
        deadline = time.monotonic() + timeout_s
        while time.monotonic() < deadline:
            if any(v == version for v, _ in fleet.rollout.outcomes):
                return True
            time.sleep(0.1)
        return False

    out: dict = {"metric": "zero-downtime hot-swap drill (4-replica fleet)",
                 "clients": n_clients}
    swap_windows: list = []     # (t_start, t_end) perf_counter spans
    threads: list = []
    try:
        assert fleet.wait_eligible(4, timeout_s=20), fleet.router.stats()
        threads = [threading.Thread(target=client, args=(i,), daemon=True)
                   for i in range(n_clients)]
        for t in threads:
            t.start()
        steady_s = 1.5 if quick else 3.0
        time.sleep(steady_s)                       # steady-state baseline
        t_steady_end = time.perf_counter()

        # --- three consecutive good swaps, one with a canary kill ---------
        records = {}
        for v in (1, 2, 3):
            t0 = time.perf_counter()
            rec = records[v] = publish_version(v)
            if v == 2:
                # chaos: hard-kill the canary replica mid-rollout — the
                # rollout must abort cleanly and the fleet re-converge on v1
                deadline = time.monotonic() + 20
                canary = None
                while time.monotonic() < deadline and canary is None:
                    st = fleet.rollout.state()
                    if st["target"] == rec["version"] and st["canary"] \
                            and st["phase"] in ("canary", "validating"):
                        canary = st["canary"]
                    else:
                        time.sleep(0.01)
                if canary is not None:
                    fleet.kill_replica(canary)
                    out["killed_canary"] = canary
                    ok = wait_rejected(rec["version"], timeout_s=30)
                    out["kill_rollout_aborted"] = ok
                    converged = wait_converged(records[1]["version"],
                                               timeout_s=30)
                    out["kill_reconverged_stable"] = converged
                else:   # rollout finished before the kill landed: note it
                    out["killed_canary"] = None
                    out["kill_rollout_aborted"] = False
                swap_windows.append((t0, time.perf_counter()))
                continue
            ok = wait_converged(rec["version"], timeout_s=40)
            swap_windows.append((t0, time.perf_counter()))
            assert ok, (f"fleet never converged on {rec['version']}: "
                        f"{fleet.model_versions()} "
                        f"{fleet.rollout.state()}")
        # --- one poisoned publish (NaN params): automatic rollback --------
        t0 = time.perf_counter()
        poison = publish_version(4, poisoned=True)
        assert wait_rejected(poison["version"], timeout_s=30), \
            fleet.rollout.state()
        swap_windows.append((t0, time.perf_counter()))
        # fleet must still be (or re-converge) on the last good version
        final_ok = wait_converged(records[3]["version"], timeout_s=30)
        time.sleep(0.5)
    finally:
        stop.set()
        for t in threads:
            t.join(timeout=15)
        rejections = []
        try:
            rejections = pub.check_rejections()
        except Exception:
            pass
        final_versions = fleet.model_versions()
        fleet_stats = {"respawns": fleet.respawns,
                       "requeued": fleet.requeued,
                       "eligible": len(fleet.router.eligible_ids()),
                       "outcomes": list(fleet.rollout.outcomes)}
        fleet.stop(drain_s=3.0)
        pub.close()
        broker.shutdown()

    # ---- accounting: zero failed, version-tag <-> value cross-check ------
    good_offsets = {"initial": 0.0,
                    records[1]["version"]: 1000.0,
                    records[2]["version"]: 2000.0,
                    records[3]["version"]: 3000.0}
    failed, mismatched = [], []
    for i, value, tag, rtt, t_done in results:
        if value is None or not np.isfinite(value):
            failed.append((i, value, tag))
            continue
        offset = value - 4.0 * i
        if tag not in good_offsets:
            failed.append((i, value, f"unknown version tag {tag!r}"))
        elif abs(offset - good_offsets[tag]) > 1e-4:
            mismatched.append((i, value, tag, offset))
    untagged = sum(1 for r in results if not r[2])

    def pctl(vals, q):
        if not vals:
            return None
        vals = sorted(vals)
        return round(vals[min(len(vals) - 1, int(q * len(vals)))] * 1e3, 2)

    steady = [r[3] for r in results if r[4] <= t_steady_end]
    in_swap = [r[3] for r in results
               if any(a <= r[4] <= b + 0.2 for a, b in swap_windows)]
    out.update({
        "requests": len(results),
        "failed_requests": len(failed),
        "first_failure": failed[0] if failed else None,
        "version_value_mismatches": len(mismatched),
        "first_mismatch": mismatched[0] if mismatched else None,
        "untagged_responses": untagged,
        "versions_swapped": [records[v]["version"] for v in (1, 2, 3)],
        "poisoned_version": poison["version"],
        "final_converged_last_good": final_ok,
        "final_versions": final_versions,
        "rejections": rejections,
        "fleet": fleet_stats,
        "latency_ms": {
            "steady_p50": pctl(steady, 0.50),
            "steady_p95": pctl(steady, 0.95),
            "swap_p50": pctl(in_swap, 0.50),
            "swap_p95": pctl(in_swap, 0.95),
            "steady_n": len(steady), "swap_n": len(in_swap)},
    })
    return out


def _require_tpu(arm: str) -> None:
    """A full-mode arm reports device numbers: off a TPU it has none to
    report and exits non-zero (the ``--quick`` gates are the CPU path)."""
    import jax

    platform = jax.devices()[0].platform
    if platform != "tpu":
        sys.exit(f"[bench] {arm} reports device numbers and JAX found "
                 f"platform={platform}; run it on the chip")


def _cpu_reference_start(flag: str = "--cpu-reference") -> subprocess.Popen:
    """Launch the identical NCF recipe on the host CPU in a background
    subprocess (overlaps with the TPU runs — joined via _cpu_reference_join).
    The parent holds the chip and a chip admits one process, so the child
    is pinned to the CPU backend by its environment, before it imports JAX."""
    return subprocess.Popen(
        [sys.executable, os.path.abspath(__file__), flag],
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True)


def _cpu_reference_join(proc: subprocess.Popen,
                        timeout_s: int = 1200) -> dict | None:
    try:
        out, _err = proc.communicate(timeout=timeout_s)
        if proc.returncode == 0:
            return json.loads(out.strip().splitlines()[-1])
    except (subprocess.TimeoutExpired, json.JSONDecodeError, IndexError):
        proc.kill()
    return None


if __name__ == "__main__":
    if "--update-sharding-child" in sys.argv:
        # re-exec target of run_update_sharding: prints ONE JSON line
        print(json.dumps(run_update_sharding()))
        sys.exit(0)
    if "--update-sharding" in sys.argv:
        us = run_update_sharding()
        print(json.dumps(us))
        if "--quick" in sys.argv:
            assert us["entries"], "no dp size fit the available devices"
            for e in us["entries"]:
                dp = e["dp"]
                repl_b = e["replicated"]["opt_state_bytes_per_device"]
                shard_b = e["sharded"]["opt_state_bytes_per_device"]
                # ZeRO-1 memory claim: sharded opt state ≈ replicated/dp
                # (within padding + the replicated scalar count leaves)
                assert shard_b <= repl_b / dp * 1.35 + 4096, (
                    f"dp={dp}: sharded opt state {shard_b}B not ~1/{dp} of "
                    f"replicated {repl_b}B")
                # collective gates run through the shared rule engine: an
                # empty finding list IS the invariant (exactly one grad
                # reduce-scatter + one params all-gather; counts constant
                # in grad_accum_steps)
                assert not e["sharded_lint"], (
                    f"dp={dp}: collective-budget rule findings:\n" + "\n".join(
                        f"  {f['location']}: {f['message']}"
                        for f in e["sharded_lint"]))
                assert not e["accum_lint"], (
                    f"dp={dp}: collective counts vary with grad_accum_steps:"
                    "\n" + "\n".join(f"  {f['location']}: {f['message']}"
                                     for f in e["accum_lint"]))
                # memory gate: the sharded-update step must not cost more
                # HBM than the replicated one
                rh = e["replicated"]["hbm"].get("hbm_peak_bytes")
                sh = e["sharded"]["hbm"].get("hbm_peak_bytes")
                if rh and sh:
                    assert sh <= rh * 1.02, (
                        f"dp={dp}: sharded step HBM {sh} > replicated {rh}")
            print("[bench] update-sharding quick gate OK: "
                  + ", ".join(
                      f"dp={e['dp']} opt-ratio {e['opt_state_ratio']}"
                      for e in us["entries"]), file=sys.stderr)
        sys.exit(0)
    if "--embedding-child" in sys.argv:
        # re-exec target of run_embedding: prints ONE JSON line
        print(json.dumps(run_embedding(quick="--quick" in sys.argv)))
        sys.exit(0)
    if "--embedding" in sys.argv:
        quick = "--quick" in sys.argv
        eb = run_embedding(quick=quick)
        if not quick:
            # quick is the CI gate and never touches the committed artifact
            with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                   "EMBEDDING_BENCH.json"), "w") as f:
                json.dump(eb, f, indent=1)
        print(json.dumps(eb))
        if quick:
            tr = eb["train"]
            # the scale invariant: a table 4x the per-device budget holds
            # rows/8 per device (within sharding padding)
            assert eb["table_over_budget"] >= 4.0, eb
            assert tr["table_shard_ratio"] <= 1.0 / eb["shards"] * 1.05, (
                f"table not row-sharded: {tr['table_shard_ratio']} of the "
                f"full table per device (expected ~1/{eb['shards']})")
            assert tr["moment_shard_ratio"] <= 1.0 / eb["shards"] * 1.05, (
                f"Adam moments not shard-local: {tr['moment_shard_ratio']}")
            # the model-parallel gather's collective pair must be in the
            # compiled step: ids all-gathered to owner shards, rows returned
            # via reduce-scatter (psum_scatter lowers to reduce-scatter)
            cc = tr["collectives"]
            assert cc.get("all-gather", 0) >= 1 \
                and cc.get("reduce-scatter", 0) >= 1, (
                    f"sharded-gather collective pair missing from HLO: {cc}")
            # the shard-local gather block must fit the per-device budget
            # the dense table breaks (empty findings IS the invariant)
            assert not eb["gather_lint"], (
                "sharded-gather memory findings:\n" + "\n".join(
                    f"  {f['location']}: {f['message']}"
                    for f in eb["gather_lint"]))
            # serving tier works and actually caches
            assert eb["serving"]["hits"] > 0 \
                and eb["serving"]["hit_rate"] > 0.1, eb["serving"]
            # incremental publish: ~1% rows touched ships <=5% of the bytes
            assert eb["delta"]["touched_fraction"] <= 0.011
            assert eb["delta"]["bytes_ratio"] <= 0.05, (
                f"row delta not incremental: {eb['delta']}")
            print("[bench] embedding quick gate OK: "
                  f"{eb['rows']} rows x{eb['table_over_budget']} budget, "
                  f"shard ratio {tr['table_shard_ratio']}, "
                  f"delta ratio {eb['delta']['bytes_ratio']}",
                  file=sys.stderr)
        sys.exit(0)
    if "--int8-dispatch" in sys.argv:
        # fused-quantization kernel tier bench (ISSUE 6): raw vs dispatch
        # int8/bf16 ratios + structural audit + MFU at batch {4,16} with
        # tuned blocks; artifact -> KERNEL_BENCH.json. Quick mode is pinned
        # by the caller (run_serving_bench.sh exports JAX_PLATFORMS=cpu).
        if "--quick" not in sys.argv:
            _require_tpu("--int8-dispatch")
        kb = run_int8_dispatch()
        try:
            kb["mfu_sweep"] = run_mfu_batch_sweep()
        except Exception as e:   # additive entry; never break the gate run
            print(f"[bench] mfu sweep failed: {e}", file=sys.stderr)
            kb["mfu_sweep"] = {"error": str(e)[:500]}
        if "--quick" not in sys.argv:
            # quick mode is the CI gate and, like the serving quick gate,
            # never touches the committed artifact (a CPU quick run must not
            # clobber TPU-measured ratios/MFU)
            with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                   "KERNEL_BENCH.json"), "w") as f:
                json.dump(kb, f, indent=1)
        print(json.dumps(kb))
        if "--quick" in sys.argv:
            st = kb["structure"]
            # structural gate (CPU-checkable): the fused-int8-dispatch rule
            # of the shared analysis engine must come back clean — pallas
            # kernels present, NO standalone quantize ops / int8 HBM
            # intermediates (the shape of the 0.72x regression)
            assert not st["findings"], (
                "fused-dispatch rule findings:\n" + "\n".join(
                    f"  {f['location']}: {f['message']}"
                    for f in st["findings"]))
            assert st["fused_invariants_hold"], (
                f"fused-dispatch invariants violated: {st}")
            # the bench model is UNTRAINED (near-uniform 128-class softmax:
            # argmax sits on a knife's edge), so the accuracy gate here is
            # deliberately loose; the reference-grade <0.1% disagreement bar
            # lives in tests/test_inference.py on a shaped model
            assert kb["dispatch"]["argmax_agreement"] >= 0.95, (
                f"int8 dispatch disagrees with bf16: {kb['dispatch']}")
            if kb["platform"] == "tpu":
                # timing gates only where the MXU int8 path is real:
                # dispatch must keep >= 0.85x of the raw-matmul win, and
                # batch-16 MFU must beat the recorded 0.18 collapse
                assert kb["dispatch_over_raw"] >= 0.85, (
                    f"dispatch ratio {kb['dispatch']['int8_over_bf16']} < "
                    f"0.85x raw {kb['raw']['int8_over_bf16']}")
                m16 = (kb.get("mfu_sweep", {}).get("entries", {})
                       .get("16", {}).get("mfu"))
                assert m16 is None or m16 > 0.18, (
                    f"batch-16 MFU {m16} not above the recorded 0.18")
            print("[bench] int8-dispatch quick gate OK: "
                  f"pallas_calls={st['pallas_calls']}, dispatch/raw="
                  f"{kb['dispatch_over_raw']}", file=sys.stderr)
        sys.exit(0)
    if "--fleet" in sys.argv:
        # replica-fleet routing bench (ISSUE 9): scaling 1->4 + chaos-kill
        # drill. Host-side by construction (stub device-bound model), so it
        # pins the CPU backend like the data-pipeline bench.
        import jax as _jax

        _jax.config.update("jax_platforms", "cpu")
        quick = "--quick" in sys.argv
        fb = run_fleet_bench(quick=quick)
        if "--hosts" in sys.argv:
            # cross-host arm (ISSUE 16): spread placement + whole-host kill
            n_hosts = int(sys.argv[sys.argv.index("--hosts") + 1])
            fb["hosts"] = run_host_fleet_bench(quick=quick, n_hosts=n_hosts)
        if not quick:
            # quick is the CI gate and never touches the committed artifact
            with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                   "FLEET_BENCH.json"), "w") as f:
                json.dump(fb, f, indent=1)
        print(json.dumps(fb))
        drill = fb["chaos_drill"]
        assert drill["failed_requests"] == 0, (
            f"chaos drill lost requests: {drill['first_failure']}")
        assert drill["requeued"] > 0, (
            "kill drill requeued nothing — the dead replica held no claimed "
            "work; raise load or lower failover timeout")
        assert drill["reconverged"] and drill["eligible_at_end"] == 4, drill
        ev = drill["events"]
        assert ev["failover_events"] > 0, (
            "the chaos kill's failover never appeared on the decision-event "
            "stream")
        assert ev["traces_complete"], (
            f"a failover event's trace does not export whole: {ev}")
        assert ev["debug_scrape_ok"], (
            f"/debug/events scrape failed or missed the failover: {ev}")
        for arm in fb["scaling"].values():
            assert arm["failed_requests"] == 0, arm
        assert fb["speedup_4_vs_1"] >= 2.5, (
            f"fleet scaling 1->4 gave {fb['speedup_4_vs_1']}x < 2.5x "
            f"({fb['scaling']['1']['req_per_s']} -> "
            f"{fb['scaling']['4']['req_per_s']} req/s)")
        print(f"[bench] fleet gate OK: {fb['speedup_4_vs_1']}x at 4 "
              f"replicas, drill zero-loss (requeued="
              f"{drill['requeued']}, dups_dropped="
              f"{drill['duplicates_dropped']}, failover="
              f"{drill['failover_s']})", file=sys.stderr)
        if "hosts" in fb:
            hb = fb["hosts"]
            # whole-host contract: zero loss, ONE decision, a trace that
            # spans both machines, and a breaker that fails dials fast
            assert hb["failed_requests"] == 0, (
                f"host drill lost requests: {hb['first_failure']}")
            assert hb["host_failovers"] == 1, hb
            assert hb["host_failed_events"] == 1, (
                "host kill must surface as exactly ONE fleet.host_failed "
                f"decision: {hb['host_failed_events']}")
            assert len(hb["trace_hosts"]) >= 2, (
                f"host-failover trace spans one host only: "
                f"{hb['trace_hosts']}")
            assert hb["requeued"] > 0, (
                "host drill requeued nothing — the dead host held no "
                "claimed work; raise load or lower failover timeout")
            sizes = sorted(len(r) for r in
                           hb["topology_before_kill"].values())
            assert sizes[0] >= 1 and sizes[-1] - sizes[0] <= 1, (
                f"placement did not spread: {hb['topology_before_kill']}")
            assert hb["dial_dead_host"]["fast_failed"], hb["dial_dead_host"]
            assert hb["dial_dead_host"]["retry_after_s"] > 0
            assert hb["dial_dead_host"]["dial_seconds"] < 0.1
            assert hb["critical_slo_fired"] == 0, (
                "the critical-class latency SLO fired during the "
                "whole-host kill — failover is not transparent")
            print(f"[bench] host-fleet gate OK: {hb['hosts']} hosts, "
                  f"whole-host drill zero-loss (requeued={hb['requeued']}, "
                  f"trace_hosts={hb['trace_hosts']}, retry_after="
                  f"{hb['dial_dead_host']['retry_after_s']}s)",
                  file=sys.stderr)
        sys.exit(0)
    if "--overload" in sys.argv:
        # adaptive serving under overload (ISSUE 13): bimodal traffic at 2x
        # capacity — the critical class must hold its SLO while bulk sheds
        # with a COMPUTED Retry-After (not queued to timeout) — plus the
        # autoscale 1->4->1 zero-loss drill. Host-side by construction
        # (stub device-bound model), so it pins the CPU backend.
        import jax as _jax

        _jax.config.update("jax_platforms", "cpu")
        quick = "--quick" in sys.argv
        ob = run_overload_bench(quick=quick)
        if not quick:
            # quick is the CI gate and never touches the committed artifact
            with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                   "OVERLOAD_BENCH.json"), "w") as f:
                json.dump(ob, f, indent=1)
        print(json.dumps(ob))
        # gates (quick AND full): the acceptance criteria of the drill
        bi = ob["bimodal"]
        assert bi["offered_over_capacity"] >= 1.8, (
            f"offered load only {bi['offered_over_capacity']}x capacity — "
            f"the overload condition was not reached")
        crit = bi["critical"]
        assert crit["failed"] == 0, (
            f"critical requests failed: {crit['first_failure']}")
        # the critical class must be SERVED under overload; a stray shed
        # (scheduler stall past the whole 1.5s budget on the shared 1-core
        # host) is tolerated at <=2%, never more
        assert crit["served"] > 0 and \
            crit["shed"] <= 0.02 * (crit["served"] + crit["shed"]), crit
        assert crit["p99_ms"] is not None and \
            crit["p99_ms"] <= ob["slo_ms"], (
            f"critical p99 {crit['p99_ms']}ms blew the {ob['slo_ms']}ms "
            f"SLO at {bi['offered_over_capacity']}x capacity")
        bulk = bi["bulk"]
        assert bulk["unanswered"] == 0, (
            f"{bulk['unanswered']} bulk requests were queued to timeout "
            f"instead of served-or-shed")
        assert bulk["shed"] > 0, (
            "no bulk traffic was shed at 2x capacity — deadline shedding "
            "never engaged")
        assert bulk["retry_after_s"]["max"] > 0.05, (
            f"shed Retry-After never exceeded the floor — not computed "
            f"from queue state: {bulk['retry_after_s']}")
        # SLO verdicts (ISSUE 15): the judgment layer must agree with the
        # raw gates — critical never fires, bulk fires under overload and
        # resolves once the load drops, and the /debug surface stayed
        # valid JSON throughout
        slo = bi["slo"]
        assert not slo["critical_fired"], (
            f"critical-latency SLO fired during the drill: "
            f"{slo['objectives']}")
        assert slo["bulk_fired"], (
            f"bulk-availability alert never fired at "
            f"{bi['offered_over_capacity']}x capacity: {slo['objectives']}")
        assert slo["bulk_resolved"], (
            f"bulk-availability alert did not resolve after load dropped: "
            f"{slo['objectives']}")
        assert slo["scrapes"]["slo_bad"] == 0 \
            and slo["scrapes"]["events_bad"] == 0, (
            f"/debug scrape returned invalid JSON during the drill: "
            f"{slo['scrapes']}")
        assert slo["scrapes"]["slo_ok"] > 0, slo["scrapes"]
        assert slo["shed_events"] > 0, (
            "no shed decision events emitted under overload")
        assert slo["slo_transition_events"] >= 2, (
            f"expected firing+resolved slo events, got "
            f"{slo['slo_transition_events']}")
        assert slo["event_traces_resolve"], (
            "a decision event's trace_id no longer exports a trace")
        asc = ob["autoscale"]
        assert asc["failed_requests"] == 0, (
            f"autoscale drill lost requests: {asc['first_failure']}")
        assert asc["duplicates_dropped"] == 0, asc
        assert asc["scaled_up_to_max"], (
            f"fleet never reached max replicas: {asc['scale_events']}")
        assert asc["scaled_back_to_min"], (
            f"fleet never drained back to 1: {asc['scale_events']}")
        ev = asc["events"]
        assert ev["autoscale_up"] > 0 and ev["autoscale_down"] > 0, (
            f"autoscale actions missing from the decision-event stream: "
            f"{ev}")
        assert ev["traces_complete"], (
            f"an autoscale event's trace does not export whole: {ev}")
        print(f"[bench] overload gate OK: critical p99 "
              f"{crit['p99_ms']}ms (SLO {ob['slo_ms']}ms) at "
              f"{bi['offered_over_capacity']}x capacity, bulk shed "
              f"{bulk['shed_fraction'] * 100:.0f}% with Retry-After up to "
              f"{bulk['retry_after_s']['max']}s; autoscale 1->"
              f"{asc['replica_peak']}->1 over {asc['requests']} requests, "
              f"0 lost, 0 duplicated", file=sys.stderr)
        sys.exit(0)
    if "--replay" in sys.argv:
        # flight-recorder replay bench (ISSUE 18): record an overload trace,
        # then score two admission policies offline on the same recording.
        # THE determinism gate: the incumbent policy replayed against the
        # recorded control inputs must reproduce the live decision sequence
        # exactly (kinds, order, fields — timestamps excluded).
        import jax as _jax

        _jax.config.update("jax_platforms", "cpu")
        quick = "--quick" in sys.argv
        rb = run_replay_bench(quick=quick)
        if not quick:
            # quick is the CI gate and never touches the committed artifact
            with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                   "REPLAY_BENCH.json"), "w") as f:
                json.dump(rb, f, indent=1)
        print(json.dumps(rb))
        # gates (quick AND full)
        assert rb["dump"]["admission_records"] >= 50, (
            f"overload trace too thin to bench policies on: "
            f"{rb['dump']['admission_records']} admission records")
        assert rb["incumbent_exact"], (
            f"incumbent replay DIVERGED from the recorded decision "
            f"sequence: {rb['incumbent_divergences'][:3]}")
        assert rb["candidate_deterministic"], (
            "candidate policy produced different decisions across two "
            "replays of the same recording")
        assert rb["policy_divergences"] >= 1, (
            "watermark candidate never disagreed with the incumbent on an "
            "overload trace — the diff harness is not discriminating")
        sc = rb["scores"]
        assert sc["candidate"]["shed"] >= sc["incumbent"]["shed"], (
            f"tighter watermark shed LESS than the incumbent: {sc}")
        assert sc["incumbent"]["considered"] == \
            sc["candidate"]["considered"], sc
        print(f"[bench] replay gate OK: {rb['dump']['records']} records "
              f"({rb['dump']['admission_records']} admission), incumbent "
              f"replay exact, candidate deterministic, "
              f"{rb['policy_divergences']} divergences "
              f"(incumbent shed {sc['incumbent']['shed']} vs candidate "
              f"{sc['candidate']['shed']})", file=sys.stderr)
        sys.exit(0)
    if "--hotswap" in sys.argv:
        # model hot-swap drill (ISSUE 10): sustained load through >=3
        # consecutive canary-rolled swaps + one mid-rollout canary kill +
        # one NaN-poisoned publish. Host-side by construction (tiny linear
        # model, the routing/swap tier is what's measured), so it pins the
        # CPU backend.
        import jax as _jax

        _jax.config.update("jax_platforms", "cpu")
        quick = "--quick" in sys.argv
        hs = run_hotswap_bench(quick=quick)
        if not quick:
            # quick is the CI gate and never touches the committed artifact
            with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                   "HOTSWAP_BENCH.json"), "w") as f:
                json.dump(hs, f, indent=1)
        print(json.dumps(hs))
        # gates (quick AND full): the acceptance criteria of the drill
        assert hs["failed_requests"] == 0, (
            f"hot-swap drill failed requests: {hs['first_failure']}")
        assert hs["version_value_mismatches"] == 0, (
            f"response value does not match its version tag (mixed "
            f"weights): {hs['first_mismatch']}")
        assert hs["untagged_responses"] == 0, (
            f"{hs['untagged_responses']} responses carried no model version")
        assert hs["final_converged_last_good"], (
            f"fleet did not converge on the last good version: "
            f"{hs['final_versions']}")
        outcomes = dict((v, o) for v, o in hs["fleet"]["outcomes"])
        assert "rolled_back" in outcomes.values(), (
            f"poisoned publish was not rolled back: {outcomes}")
        assert hs["rejections"], "no rejection records reached the publisher"
        assert hs["kill_rollout_aborted"], (
            "canary kill did not abort the rollout: "
            f"{hs.get('killed_canary')}, {outcomes}")
        assert hs["fleet"]["eligible"] == 4, hs["fleet"]
        # decision-event audit (ISSUE 15): promotions AND the poisoned
        # publish's rollback must be on the event stream, each trace
        # exporting whole (containing the rollout span)
        from analytics_zoo_tpu.observability import events as _events
        from analytics_zoo_tpu.observability import export_trace

        promoted_evs = _events.events(kind="rollout.promoted")
        rejected_evs = _events.events(kind="rollout.rejected")
        assert promoted_evs, "no rollout.promoted decision events"
        assert any(e.fields.get("outcome") == "rolled_back"
                   for e in rejected_evs), (
            f"poisoned publish's rollback missing from the event stream: "
            f"{[e.fields for e in rejected_evs]}")
        for e in promoted_evs + rejected_evs:
            t = export_trace(e.trace_id) if e.trace_id else None
            assert t and any(s["name"] == "rollout"
                             for s in t["traceEvents"]), (
                f"rollout event {e.fields} trace does not export whole")
        # bounded p95 inflation during swap windows: generous (shared 1-core
        # CI host; staging/validation runs off the hot path, but respawn +
        # requeue after the deliberate canary kill is inside these windows)
        lat = hs["latency_ms"]
        if lat["steady_p95"] and lat["swap_p95"]:
            bound = max(5.0 * lat["steady_p95"], lat["steady_p95"] + 500.0)
            assert lat["swap_p95"] <= bound, (
                f"p95 during swap {lat['swap_p95']}ms exceeds bound "
                f"{bound}ms (steady {lat['steady_p95']}ms)")
        print(f"[bench] hotswap gate OK: {hs['requests']} requests through "
              f"3 swaps + kill + poison, 0 failed, p95 steady/"
              f"swap {lat['steady_p95']}/{lat['swap_p95']}ms, outcomes="
              f"{outcomes}", file=sys.stderr)
        sys.exit(0)
    if "--generation" in sys.argv:
        # generation decode-path bench (ISSUE 8). Quick mode is the CI gate
        # (CPU, pinned by run_serving_bench.sh); full mode runs on the chip
        # and writes GENERATION_BENCH.json
        quick = "--quick" in sys.argv
        if not quick:
            _require_tpu("--generation")
        gb = run_generation_bench(quick=quick)
        if "--spec" in sys.argv:
            gb["speculative_decode"] = run_spec_generation_bench(quick=quick)
        if "--prefix" in sys.argv:
            gb["prefix_cache"] = run_prefix_generation_bench(quick=quick)
        if "--longprompt" in sys.argv:
            gb["longprompt"] = run_longprompt_generation_bench(quick=quick)
        if not quick:
            # like the other quick gates: a CPU smoke run must never clobber
            # the committed (possibly TPU-measured) artifact
            with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                   "GENERATION_BENCH.json"), "w") as f:
                json.dump(gb, f, indent=1)
        print(json.dumps(gb))
        if quick:
            s8 = gb["streams"]["8"]
            assert s8["failed_streams"] == 0, (
                f"failed streams at N=8: {s8['first_failure']}")
            # bucket invariant: ONE compiled decode shape; prefill buckets
            # inside the pow2 ladder up to max_seq
            assert s8["distinct_decode_shapes"] == 1, s8
            assert all(b_ & (b_ - 1) == 0 for b_ in s8["prefill_buckets"]), \
                f"non-pow2 prefill bucket: {s8['prefill_buckets']}"
            assert len(s8["prefill_buckets"]) <= 10, s8
            assert not gb["decode_lint"]["findings"], (
                "decode-shape-stability findings:\n" + "\n".join(
                    f"  {f['location']}: {f['message']}"
                    for f in gb["decode_lint"]["findings"]))
            sp = gb["continuous_vs_rtc"]["speedup"]
            assert sp >= 1.5, (
                f"continuous batching speedup {sp} < 1.5x over "
                f"run-to-completion on mixed-length traffic")
            ratio = gb["flat_decode"]["late_over_early"]
            assert ratio < 2.0, (
                f"decode cost grew with generated length "
                f"(late/early {ratio}) — KV cache not flat")
            # memory gates (ISSUE 12): the KV pool is donated through the
            # decode dispatch, so (a) the static peak excludes the second
            # pool-sized buffer the undonated estimate carries, and (b) the
            # compiled executable aliases at least the pool input->output
            mem = gb["memory"]
            assert mem["donate_cache"], "decode dispatch lost cache donation"
            saved = (mem["static_peak_bytes_undonated"]
                     - mem["static_peak_bytes"])
            assert saved >= 0.4 * mem["cache_bytes"], (
                f"donation-aware static peak saves only {saved} bytes over "
                f"the undonated estimate (pool is {mem['cache_bytes']}) — "
                f"the second pool-sized buffer is back")
            alias = mem["compiled"].get("alias_size_in_bytes")
            if alias is not None:
                assert alias >= mem["cache_bytes"], (
                    f"compiled decode aliases only {alias} bytes; the "
                    f"donated pool is {mem['cache_bytes']} — XLA is copying "
                    f"the KV pool every step")
            # witness gate (active when run_serving_bench.sh exports
            # ZOO_TPU_MEM_WITNESS): device bytes flat across decode steps
            wit = mem.get("witness")
            if wit:
                grow = wit["max_live_bytes"] / max(1, wit["min_live_bytes"])
                assert grow <= 1.25, (
                    f"witnessed device bytes grew {grow:.2f}x across decode "
                    f"steps (min {wit['min_live_bytes']}, max "
                    f"{wit['max_live_bytes']}) — decode memory not flat")
            print(f"[bench] generation quick gate OK: "
                  f"{s8['tokens_per_s']} tok/s @8 streams, "
                  f"continuous/RTC {sp}x, flat-decode {ratio}, "
                  f"donation saves {saved}B static "
                  f"(pool {mem['cache_bytes']}B), witness="
                  f"{'on' if mem.get('witness') else 'off'}",
                  file=sys.stderr)
            sg = gb.get("speculative_decode")
            if sg is not None:
                # --spec quick gates (ISSUE 14)
                kp = sg["kernel_parity"]
                for lbl, atol in (("float32", 1e-4), ("bfloat16", 2e-2)):
                    for key, err in kp[lbl].items():
                        assert err <= atol, (
                            f"paged-attention kernel {lbl} {key} "
                            f"diverges from the plain-dot reference: "
                            f"{err} > {atol}")
                assert sg["greedy_token_identical"], (
                    "speculative greedy streams diverged from the "
                    "single-token baseline — the accept/reject rule is "
                    "changing CONTENT, not just cost")
                for arm_name in ("baseline", "speculative"):
                    a = sg[arm_name]
                    assert a["failed_streams"] == 0, (
                        f"{arm_name} arm failed streams: "
                        f"{a['first_failure']}")
                    assert a["distinct_decode_shapes"] == 1, (
                        f"{arm_name} arm compiled "
                        f"{a['distinct_decode_shapes']} decode shapes — "
                        f"the one-executable-per-(k, slot-count) "
                        f"invariant broke")
                    assert not a["findings"], (
                        f"{arm_name} decode lint findings:\n" + "\n".join(
                            f"  {f['location']}: {f['message']}"
                            for f in a["findings"]))
                acc = sg["speculative"]["acceptance_rate"]
                assert acc >= 0.10, (
                    f"greedy self-draft acceptance {acc} < 0.10 floor — "
                    f"the k-gram proposer is not tracking the target")
                # speedup gate, split by platform (ISSUE 14 acceptance
                # criteria): TPU gates the wall-clock >=2x claim; on CPU —
                # where the verify step's k-fold FLOPs are NOT hidden
                # behind dispatch/HBM latency — gate the host-speed-
                # independent advance-per-dispatch factor instead (what a
                # dispatch-bound backend converts into wall clock)
                if sg["platform"] == "tpu":
                    assert sg["speedup"] >= 2.0, (
                        f"speculative decode speedup {sg['speedup']}x < "
                        f"2.0x over single-token decode at N=8 greedy "
                        f"streams (TPU threshold)")
                adv = sg["advance_per_dispatch"]
                assert adv >= 1.3, (
                    f"speculative decode advances only {adv}x tokens per "
                    f"occupied slot-dispatch (need >=1.3x; plain decode "
                    f"is 1.0 by construction)")
                print(f"[bench] spec quick gate OK: "
                      f"{adv}x tokens/dispatch (wall {sg['speedup']}x on "
                      f"{sg['platform']}), acceptance {acc}, "
                      f"parity+identity+lint green", file=sys.stderr)
            pg = gb.get("prefix_cache")
            if pg is not None:
                # --prefix quick gates (ISSUE 17 acceptance criteria)
                assert pg["reuse_fraction"] >= 0.5, pg["reuse_fraction"]
                assert pg["token_identical"], (
                    "warm prefix-sharing streams diverged from the cold "
                    "baseline — sharing changed CONTENT, not just cost")
                assert pg["warm"]["hit_rate"] >= 1.0, (
                    f"measured trace hit rate {pg['warm']['hit_rate']} < "
                    f"1.0 — tenant prefixes not being matched")
                assert pg["warm_speedup"] >= 5.0, (
                    f"warm prefill only {pg['warm_speedup']}x faster than "
                    f"cold at {pg['reuse_fraction']} reuse (need >=5x) — "
                    f"suffix prefill is not starting from the divergence "
                    f"point")
                occ = pg["occupancy"]
                for arm_name in ("shared", "disabled"):
                    assert occ[arm_name]["failed_streams"] == 0, (
                        f"{arm_name} occupancy arm failed streams: "
                        f"{occ[arm_name]['first_failure']}")
                assert occ["peak_ratio"] <= 0.6, (
                    f"peak pool occupancy with sharing is "
                    f"{occ['peak_ratio']}x the disabled baseline across "
                    f"{occ['shared']['streams']} concurrent same-prefix "
                    f"streams (need <=0.6x — prefix pages must be mapped, "
                    f"not copied)")
                print(f"[bench] prefix quick gate OK: warm prefill "
                      f"{pg['warm_speedup']}x faster at "
                      f"{pg['reuse_fraction']} reuse, peak occupancy "
                      f"{occ['peak_ratio']}x of no-sharing "
                      f"({occ['shared']['peak_pages_in_use']} vs "
                      f"{occ['disabled']['peak_pages_in_use']} pages), "
                      f"tokens saved {pg['warm']['tokens_saved']}, "
                      f"identity green", file=sys.stderr)
            lp = gb.get("longprompt")
            if lp is not None:
                # --longprompt quick gates (ISSUE 20 acceptance criteria)
                for arm_name in ("baseline", "interleave", "whole_prompt"):
                    a = lp[arm_name]
                    assert a["failed_streams"] == 0, (
                        f"{arm_name} arm failed streams: "
                        f"{a.get('first_failure')}")
                assert lp["token_identical"], (
                    "chunked long-prompt streams diverged from the "
                    "whole-prompt baseline — chunking changed CONTENT, "
                    "not just scheduling")
                assert lp["interleave"]["short_tokens_identical"], (
                    "short streams' tokens changed when the long prompt "
                    "was injected — prefill chunks are perturbing "
                    "running streams")
                itl_ratio = lp["interleave"]["itl_p95_ratio"]
                assert itl_ratio <= 1.5, (
                    f"short-stream ITL p95 inflated {itl_ratio}x while a "
                    f"{lp['prompt_tokens']}-token prompt prefilled (need "
                    f"<=1.5x) — the chunk budget is not bounding the "
                    f"per-iteration prefill spend")
                tp_ratio = lp["throughput"]["ratio"]
                assert tp_ratio >= 0.8, (
                    f"chunked prefill throughput is only {tp_ratio}x the "
                    f"whole-prompt path on an idle batcher (need >=0.8x) "
                    f"— per-chunk dispatch overhead is eating the win")
                assert lp["prefill_stats"]["distinct_chunk_shapes"] == 1, (
                    f"compiled {lp['prefill_stats']['distinct_chunk_shapes']}"
                    f" chunk shapes — the one-executable-per-(chunk_tokens,"
                    f" slot) invariant broke")
                kd = lp["kill_drill"]
                assert kd["token_identical"], (
                    "post-kill long stream diverged — the re-dispatched "
                    "chunk is not idempotent")
                assert kd["loop_respawns"] >= 1, kd
                assert kd["pool_conserved"], (
                    "pages leaked through the kill-mid-chunk drill")
                print(f"[bench] longprompt quick gate OK: ITL p95 "
                      f"{itl_ratio}x baseline under a "
                      f"{lp['prompt_tokens']}-token prefill "
                      f"({lp['interleave']['long_chunks']} chunks of "
                      f"{lp['chunk_tokens']}), whole-prompt stall "
                      f"{lp['whole_prompt']['stall_over_baseline']}x, "
                      f"idle throughput {tp_ratio}x, kill drill "
                      f"identity+conservation green", file=sys.stderr)
        sys.exit(0)
    if "--data-pipeline" in sys.argv:
        # standalone input-pipeline micro-bench, ALWAYS on the CPU backend:
        # it gates host-side pipeline behavior (the 0.5x threshold is tuned
        # for it)
        dp = run_data_pipeline(platform="cpu")
        print(json.dumps(dp))
        if "--quick" in sys.argv:
            assert dp["byte_identical"], "async batch stream diverged from sync"
            sync_dw = dp["sync"]["data_wait_ms_mean"]
            async_dw = dp["async"]["data_wait_ms_mean"]
            assert async_dw < 0.5 * sync_dw, (
                f"async DataWaitMs {async_dw}ms not < 0.5x sync {sync_dw}ms")
            print(f"[bench] quick gate OK: async {async_dw}ms < 0.5x "
                  f"sync {sync_dw}ms", file=sys.stderr)
        sys.exit(0)
    if "--cpu-reference" in sys.argv:
        print(json.dumps(run_ncf(platform="cpu")))
        sys.exit(0)
    if "--cpu-reference-implicit" in sys.argv:
        print(json.dumps(run_ncf_implicit(platform="cpu")))
        sys.exit(0)

    _require_tpu("the default arm")
    # launch the CPU references up front so they overlap with the TPU runs
    ref_procs = (_cpu_reference_start("--cpu-reference"),
                 _cpu_reference_start("--cpu-reference-implicit"))

    main = run_ncf()

    cpu = _cpu_reference_join(ref_procs[0])
    # baseline policy: vs_baseline divides by the MAX of the live CPU run and
    # recent recorded live runs, so contention-depressed live baselines can
    # only shrink the reported ratio (see BASELINE_HISTORY_PATH comment)
    history_max = max((e["samples_per_sec"] for e in _baseline_history_load()),
                      default=0.0)
    if cpu is not None:
        live_sps = cpu["samples_per_sec"]
        _baseline_history_append(live_sps)
        hr_cpu = cpu.get("hr@10")
        baseline_sps = max(live_sps, history_max)
        baseline_src = ("live_cpu_subprocess" if live_sps >= history_max
                        else "max_recent_live_cpu_history")
    elif history_max > 0:
        baseline_sps = history_max
        hr_cpu = None
        baseline_src = "max_recent_live_cpu_history"
    else:
        baseline_sps = CPU_FALLBACK_SAMPLES_PER_SEC
        hr_cpu = None
        baseline_src = "recorded_fallback"

    # implicit-feedback accuracy recipe (falsifiable HR@10)
    implicit = run_ncf_implicit()
    implicit_cpu = _cpu_reference_join(ref_procs[1])
    implicit["hr@10_cpu_reference"] = (implicit_cpu or {}).get("hr@10")
    if implicit["hr@10_cpu_reference"] is not None:
        implicit["hr@10_gap"] = round(
            implicit["hr@10"] - implicit["hr@10_cpu_reference"], 4)

    tlm = run_transformer_mfu()
    # input-pipeline micro-bench (sync vs async DataWaitMs)
    data_pipeline = run_data_pipeline()

    result = {
        "metric": "NCF MovieLens-1M training throughput",
        "value": main["samples_per_sec_per_chip"],
        "unit": "samples/sec/chip",
        "vs_baseline": round(main["samples_per_sec_per_chip"] / baseline_sps,
                             3),
        "hr@10": main["hr@10"],
        "hr@10_cpu_reference": hr_cpu,
        "hr@10_gap": (round(main["hr@10"] - hr_cpu, 4)
                      if hr_cpu is not None else None),
        # the 16-epoch explicit recipe sits near the 0.10 random-ranking
        # floor by design (throughput recipe); the falsifiable ranking claim
        # is the "implicit" entry's HR@10 (paper recipe, 0.55+)
        "hr@10_role": "parity_check_only",
        "baseline_samples_per_sec": baseline_sps,
        "baseline_source": baseline_src,
        "total_samples_per_sec": main["samples_per_sec"],
        "n_chips": main["n_chips"],
        "measured_steps": main["measured_steps"],
        "measured_seconds": main["measured_seconds"],
        "final_loss": main["final_loss"],
        "platform": main["platform"],
        "implicit": implicit,
        "transformer_lm": tlm,
        "data_pipeline": data_pipeline,
    }
    print(json.dumps(result))
