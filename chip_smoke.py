#!/usr/bin/env python3
"""chip_smoke.py: does the main path still start on the TPU?

One process drives, through the entry points a user calls, at the full width
of the flagship model (depth is what it is: 8 blocks):

  0. device        jax.devices()[0] must be a TPU; versions and cache directory
  1. train-lm      init_zoo_context -> Estimator(loss=lm_loss).fit on
                   TransformerLM 32768 x 1024 x 8 x 2048, bf16, flash attention;
                   with four chips, once on dp=4 and once on dp=2 x tp=2
  2. serve-gen     start_broker + GenerationEngine + GenerationClient, the same
                   model, four concurrent greedy streams; logits against
                   model.apply on the whole sequence
  3. serve-int8    a quantize_int8() MLP behind ClusterServing, fused kernel
                   compiled, argmax against the float model
  4. train-ncf     NeuralCF at MovieLens-1M size, device-cached scanned epochs.
                   The chip machine has no network, so the ratings are the
                   seeded synthetic generator of data.datasets, not ratings.dat
  5. kernels       flash fwd+bwd, paged attention, fused int8 matmul and conv
                   against their references, on the hardware

Each phase prints one line that names it. A phase that raises, or whose check
fails, ends the run with a traceback and a non-zero exit code; nothing is caught
and turned into a value. The last line of standard output of a run that passed
is one JSON object naming the device as JAX reports it.

Off a TPU the script exits 2 before any work. ``--rehearse-on-cpu`` is the
development rehearsal the on-chip guide asks for: tiny shapes, kernels in the
Pallas interpreter, ``platform=cpu`` in every line. It proves the control flow,
never a number.
"""

from __future__ import annotations

import argparse
import collections
import importlib.metadata
import json
import os
import re
import sys
import tempfile
import time

import numpy as np

# logits of the bf16 serving path against model.apply in f32 at "highest"
# matmul precision: root-mean-square error relative to the reference's spread,
# and the largest single error. Five times what the v5e measured at full
# width (0.009 and 0.010); a wrong page or position is off by the spread.
LOGIT_REL_RMS_TOL = 0.05
LOGIT_MAX_ABS_TOL = 0.05
# kernels against their f32 references, as max |got - want| / (1 + |want|)
# (numpy's allclose with atol == rtol): what the CPU parity tests already use
F32_TOL, BF16_TOL = 1e-4, 2e-2
# two int8 schemes agree to quantization-error scale (tests/test_int8_fused.py)
INT8_REL_TOL = 0.03

FULL = dict(vocab=32768, hidden=1024, n_block=8, n_head=8, seq_len=2048,
            lm_batch=8, lm_steps=6, prompt_lens=(37, 64, 200, 250),
            new_tokens=32, gen_slots=8, gen_max_seq_len=2048,
            mlp=(256, 512, 128), mlp_requests=64, mlp_batch=16,
            ncf_ratings=None, ncf_batch=8192, ncf_epochs=2,
            flash_shape=(8, 2048, 8, 128),
            paged=dict(h=8, d=128, pps=128, q_lens=(1, 4, 512)),
            int8_mkn=(256, 1024, 1024), conv=(2, 28, 28, 128, 128))
TINY = dict(vocab=128, hidden=32, n_block=1, n_head=2, seq_len=128,
            lm_batch=8, lm_steps=2, prompt_lens=(5, 9, 20, 30),
            new_tokens=3, gen_slots=4, gen_max_seq_len=64,
            mlp=(32, 64, 16), mlp_requests=8, mlp_batch=4,
            ncf_ratings=16_384, ncf_batch=2048, ncf_epochs=2,
            flash_shape=(1, 128, 1, 16),
            paged=dict(h=2, d=16, pps=4, q_lens=(4,)),
            int8_mkn=(8, 32, 64), conv=(1, 6, 6, 8, 16))


class Smoke:
    def __init__(self, size: dict, platform: str):
        self.size = size
        self.platform = platform
        self.on_tpu = platform == "tpu"

    def say(self, phase: str, **fields) -> None:
        body = " ".join(f"{k}={v}" for k, v in fields.items())
        print(f"[{phase}] platform={self.platform} {body}", flush=True)

    # ------------------------------------------------------------- helpers

    def mosaic_kernels(self, lowered, *required) -> dict:
        """Names and counts of the Mosaic custom calls in a lowered program,
        read from its StableHLO text; on a TPU every ``required`` one must
        be there. Interpreted kernels lower to plain ops and leave none."""
        names = collections.Counter()
        for line in lowered.as_text().splitlines():
            if "tpu_custom_call" in line:
                m = re.search(r'kernel_name = "([^"]+)"', line)
                names[m.group(1) if m else "?"] += 1
        missing = [n for n in required if not names[n]]
        if self.on_tpu and missing:
            raise AssertionError(f"compiled program lacks Mosaic kernels "
                                 f"{missing}; it has {dict(names)}")
        return dict(names)

    def peak_bytes(self) -> list:
        import jax

        return [(d.memory_stats() or {}).get("peak_bytes_in_use")
                for d in jax.devices()]

    def lm(self, attn_strategy: str):
        """The flagship model. ``flash`` for training; serving keeps the
        default ``auto``, which sends the short prefill buckets to plain
        attention (the flash kernel starts at 128 tokens) and decode to the
        paged kernel; ``full`` is the reference."""
        from analytics_zoo_tpu.models.transformer import TransformerLM

        s = self.size
        return TransformerLM(vocab=s["vocab"], hidden_size=s["hidden"],
                             n_block=s["n_block"], n_head=s["n_head"],
                             seq_len=s["seq_len"],
                             attn_strategy=attn_strategy)

    def context(self, mesh_cfg=None):
        from analytics_zoo_tpu.common import (MeshConfig, PrecisionConfig,
                                              RuntimeConfig,
                                              init_zoo_context,
                                              reset_zoo_context)

        reset_zoo_context()
        return init_zoo_context(RuntimeConfig(
            mesh=mesh_cfg or MeshConfig(dp=0),
            precision=PrecisionConfig(compute_dtype="bfloat16")))

    # ------------------------------------------------------------- phase 1

    def train_lm(self) -> None:
        import jax

        from analytics_zoo_tpu.common import MeshConfig

        self.train_lm_on(MeshConfig(dp=0))          # every chip on dp
        if len(jax.devices()) == 4:
            self.train_lm_on(MeshConfig(dp=2, tp=2))

    def train_lm_on(self, mesh_cfg) -> None:
        import jax
        from jax.sharding import NamedSharding

        from analytics_zoo_tpu.common import TrainConfig
        from analytics_zoo_tpu.common import telemetry as tm
        from analytics_zoo_tpu.engine import Estimator
        from analytics_zoo_tpu.models.transformer import lm_loss
        from analytics_zoo_tpu.nn.optimizers import Adam
        from analytics_zoo_tpu.parallel import make_param_sharding

        s = self.size
        ctx = self.context(mesh_cfg)
        tensor_parallel = ctx.mesh.shape["tp"] > 1
        rule = make_param_sharding(ctx.mesh) if tensor_parallel else None
        est = Estimator(self.lm("flash"), optimizer=Adam(lr=1e-3),
                        loss=lm_loss,
                        mesh=ctx.mesh, param_sharding=rule,
                        config=TrainConfig(log_every_n_steps=1,
                                           shuffle=False))
        # a sixteenth of the vocabulary, so a handful of Adam steps visibly
        # lowers the loss from ln(vocab)
        rng = np.random.default_rng(0)
        batch, steps = s["lm_batch"], s["lm_steps"]
        x = rng.integers(0, s["vocab"] // 16,
                         size=(batch * steps, s["seq_len"])).astype("int32")
        y = np.roll(x, -1, axis=1)
        with tempfile.TemporaryDirectory() as logs:
            est.set_tensorboard(logs, "chip_smoke_lm")
            compile_before = _hist_sum(tm, "zoo_train_compile_seconds")
            est.fit((x, y), batch_size=batch, epochs=1)   # compiles
            compile_s = _hist_sum(tm, "zoo_train_compile_seconds") \
                - compile_before
            t0 = time.perf_counter()
            est.fit((x, y), batch_size=batch, epochs=2)   # fit() blocks
            step_s = (time.perf_counter() - t0) / steps
            losses = [v for _, v in est.train_summary.read_scalar("Loss")]
        first, last = losses[0], float(est.trainer_state.last_loss)
        if not (np.isfinite(losses).all() and np.isfinite(last)
                and last < first):
            raise AssertionError(f"LM loss did not fall: {losses} -> {last}")

        kernels = self.mosaic_kernels(
            est.lower_train_step((x[:batch], y[:batch])),
            "zoo_flash_fwd", "zoo_flash_bwd_dq", "zoo_flash_bwd_dkv")

        # where things sit: the batch over the dp axes, each parameter as
        # the rule says, on every device of the mesh
        n_dev = ctx.mesh.devices.size
        gx = est._to_global((x[:batch], y[:batch]))[0]
        shards = gx.addressable_shards
        dp = ctx.mesh.shape["dp"] * ctx.mesh.shape["fsdp"]
        if (len({sh.device for sh in shards}) != n_dev
                or any(sh.data.shape[0] != batch // dp for sh in shards)):
            raise AssertionError(f"batch not split {dp}-way over {n_dev} "
                                 f"devices: {[sh.data.shape for sh in shards]}")
        split = 0
        for path, leaf in jax.tree_util.tree_leaves_with_path(
                est.train_state["params"]):
            want = NamedSharding(ctx.mesh, rule(path, leaf) if rule
                                 else jax.sharding.PartitionSpec())
            held = {sh.device: sh.data.shape
                    for sh in leaf.addressable_shards}
            if (len(held) != n_dev or set(held.values())
                    != {want.shard_shape(leaf.shape)}):
                raise AssertionError(
                    f"{jax.tree_util.keystr(path)} {leaf.shape} should be "
                    f"{want.spec} in shards of {want.shard_shape(leaf.shape)}"
                    f" on {n_dev} devices; it is {held}")
            split += want.shard_shape(leaf.shape) != leaf.shape
        if tensor_parallel and not split:
            raise AssertionError("tp=2 split no parameter")
        self.say("1 train-lm",
                 mesh=f"dp={ctx.mesh.shape['dp']},tp={ctx.mesh.shape['tp']}",
                 devices=n_dev,
                 compile_s=f"{compile_s:.1f}", step_s=f"{step_s:.4f}",
                 tokens_per_step=batch * s["seq_len"],
                 loss=f"{first:.3f}->{last:.3f}", mosaic=kernels,
                 batch_shard=shards[0].data.shape, split_params=split,
                 peak_bytes_per_device=self.peak_bytes())

    # ------------------------------------------------------------- phase 2

    def serve_generation(self) -> None:
        import jax

        from analytics_zoo_tpu.nn.module import precision_policy
        from analytics_zoo_tpu.ops.kv_cache import SCRATCH_PAGE
        from analytics_zoo_tpu.serving import ServingConfig, start_broker
        from analytics_zoo_tpu.serving.generation import (GenerationClient,
                                                          GenerationEngine)

        s = self.size
        self.context()
        model = self.lm("auto")
        params, _ = model.build(jax.random.PRNGKey(0))
        rng = np.random.default_rng(1)
        prompts = [rng.integers(1, s["vocab"], size=n).astype(np.int32)
                   for n in s["prompt_lens"]]
        broker = start_broker()
        engine = GenerationEngine(model, params, config=ServingConfig(
            gen_slots=s["gen_slots"], gen_page_size=16,
            gen_max_seq_len=s["gen_max_seq_len"], queue_port=broker.port,
            graph_checks="raise")).start()
        client = GenerationClient(port=broker.port)
        try:
            t0 = time.perf_counter()
            uris = [client.submit(p, max_new_tokens=s["new_tokens"])
                    for p in prompts]                 # all in flight at once
            streams = [np.concatenate(list(client.stream(u, timeout_s=900)))
                       for u in uris]
            wall_s = time.perf_counter() - t0
        finally:
            client.close()
            engine.stop()
            broker.shutdown()
        batcher = engine.batcher
        stats = batcher.stats()
        if (stats["requests"] != {"ok": len(prompts)}
                or any(len(t) != s["new_tokens"] for t in streams)):
            raise AssertionError(f"streams did not all end ok with "
                                 f"{s['new_tokens']} tokens: {stats}")
        if stats["distinct_decode_shapes"] != 1:
            raise AssertionError(f"decode compiled more than one shape: "
                                 f"{batcher.decode_shapes}")
        kernels = self.mosaic_kernels(batcher.lower_decode(),
                                      "zoo_paged_attention")

        # logits, not tokens: prefill then teacher-forced decode steps
        # through the executables that just served, against the plain
        # forward over the whole sequence in f32
        cfg = batcher.cfg
        n_prefill, n_decode = s["prompt_lens"][0], 3
        seq = rng.integers(1, s["vocab"],
                           size=n_prefill + n_decode).astype(np.int32)
        with precision_policy(compute_dtype="float32"), \
                jax.default_matmul_precision("highest"):
            ref, _ = jax.jit(lambda p, ids: self.lm("full").apply(
                p, {}, ids))(params, seq[None])
        ref = np.asarray(ref, np.float32)[0]
        bucket = min(b for b in stats["prefill_buckets"] if b >= n_prefill)
        ids = np.zeros((1, bucket), np.int32)
        ids[0, :n_prefill] = seq[:n_prefill]
        n_pages = -(-len(seq) // cfg.page_size)
        table = np.full((cfg.n_slots, cfg.pages_per_slot), SCRATCH_PAGE,
                        np.int32)
        table[0, :n_pages] = 1 + np.arange(n_pages)
        logits, cache = batcher._prefill(
            batcher.params, batcher.cache, ids,
            np.array([n_prefill], np.int32), table[:1])
        got, want = [np.asarray(logits)[0]], [ref[n_prefill - 1]]
        zeros = np.zeros(cfg.n_slots, np.uint32)
        for pos in range(n_prefill, len(seq)):
            step_ids = np.zeros(cfg.n_slots, np.int32)
            lengths = np.zeros(cfg.n_slots, np.int32)
            step_ids[0], lengths[0] = seq[pos], pos
            _next, logits, cache = batcher._decode(
                batcher.params, cache, step_ids, lengths, table, zeros,
                zeros, np.zeros(cfg.n_slots, np.float32))
            got.append(np.asarray(logits)[0])
            want.append(ref[pos])
        got, want = np.stack(got), np.stack(want)
        if got.shape != (1 + n_decode, s["vocab"]) \
                or not np.isfinite(got).all():
            raise AssertionError(f"serving logits {got.shape} not finite")
        rel_rms = float(np.sqrt(np.mean((got - want) ** 2)) / want.std())
        max_abs = float(np.abs(got - want).max())
        if rel_rms > LOGIT_REL_RMS_TOL or max_abs > LOGIT_MAX_ABS_TOL:
            raise AssertionError(
                f"serving logits off the f32 reference: rel_rms={rel_rms} "
                f"(tol {LOGIT_REL_RMS_TOL}) max_abs={max_abs} "
                f"(tol {LOGIT_MAX_ABS_TOL})")
        device = next(iter(jax.tree_util.tree_leaves(
            batcher.params)[0].devices()))
        self.say("2 serve-gen", streams=len(prompts),
                 prompt_lens=list(s["prompt_lens"]),
                 new_tokens=s["new_tokens"], outcomes=stats["requests"],
                 wall_s_incl_compile=f"{wall_s:.1f}",
                 decode_step_ema_s=stats["step_ema_s"],
                 prefill_buckets=stats["prefill_buckets"],
                 decode_shapes=stats["distinct_decode_shapes"],
                 mosaic=kernels, logit_rel_rms=f"{rel_rms:.4f}",
                 logit_max_abs=f"{max_abs:.4f}",
                 tol=f"{LOGIT_REL_RMS_TOL}/{LOGIT_MAX_ABS_TOL}",
                 device=device)

    # ------------------------------------------------------------- phase 3

    def serve_int8(self) -> None:
        import jax

        from analytics_zoo_tpu.inference import InferenceModel
        from analytics_zoo_tpu.nn import Sequential
        from analytics_zoo_tpu.nn import layers as L
        from analytics_zoo_tpu.serving import (ClusterServing, InputQueue,
                                               OutputQueue, ServingConfig,
                                               start_broker)

        self.context()
        d_in, hidden, classes = self.size["mlp"]
        n = self.size["mlp_requests"]
        # one noisy prototype per class: a short fit separates them, so the
        # float model's argmax has a margin for int8 to keep
        rng = np.random.default_rng(2)
        protos = rng.normal(size=(classes, d_in)).astype(np.float32)
        labels = rng.integers(0, classes, size=32 * classes)
        x = protos[labels] + 0.1 * rng.normal(
            size=(len(labels), d_in)).astype(np.float32)
        model = Sequential([
            L.Dense(hidden, activation="relu", input_shape=(d_in,)),
            L.Dense(hidden, activation="relu"),
            L.Dense(classes, activation="softmax")])
        model.compile(optimizer="adam",
                      loss="sparse_categorical_crossentropy")
        model.fit(x, labels.astype(np.int32), batch_size=64, nb_epoch=8)
        sample = x[:n]
        want = np.asarray(model.predict(sample)).argmax(-1)
        if (want == labels[:n]).mean() < 0.9:
            raise AssertionError("the float MLP did not learn its prototypes")

        max_batch = self.size["mlp_batch"]
        im = InferenceModel(max_batch_size=max_batch).load(
            model).quantize_int8()
        im.check_fused_dispatch(sample, mode="raise")
        apply, q_params, state = im.device_apply()
        kernels = self.mosaic_kernels(
            jax.jit(apply).lower(q_params, state, sample[:max_batch]),
            "zoo_int8_matmul")

        broker = start_broker()
        job = ClusterServing(im, ServingConfig(
            batch_size=max_batch, queue_port=broker.port,
            warmup_shape=(d_in,),
            graph_checks="raise")).start()
        iq, oq = InputQueue(port=broker.port), OutputQueue(port=broker.port)
        try:
            uris = [iq.enqueue(None, input=row) for row in sample]
            got = np.stack([np.asarray(oq.query(u, timeout_s=300))
                            for u in uris])
        finally:
            iq.close()
            oq.close()
            job.stop()
            broker.shutdown()
        if got.shape != (n, classes) or not np.isfinite(got).all():
            raise AssertionError(f"int8 serving returned {got.shape}")
        agree = float((got.argmax(-1) == want).mean())
        if agree < 0.98:
            raise AssertionError(f"int8 argmax agrees with the float model "
                                 f"on {agree:.3f} of {n} requests")
        device = next(iter(jax.tree_util.tree_leaves(q_params)[0].devices()))
        self.say("3 serve-int8", requests=n, argmax_agreement=agree,
                 fused_dispatch="clean", mosaic=kernels, device=device)

    # ------------------------------------------------------------- phase 4

    def train_ncf(self) -> None:
        from analytics_zoo_tpu.common import TrainConfig
        from analytics_zoo_tpu.data import FeatureSet
        from analytics_zoo_tpu.data.datasets import (ML1M_ITEMS,
                                                     ML1M_RATINGS, ML1M_USERS,
                                                     synthetic_movielens)
        from analytics_zoo_tpu.engine import Estimator
        from analytics_zoo_tpu.models.recommendation import NeuralCF
        from analytics_zoo_tpu.native.lib import native_available
        from analytics_zoo_tpu.nn.optimizers import Adam

        s = self.size
        ctx = self.context()
        pairs, ratings = synthetic_movielens(
            s["ncf_ratings"] or ML1M_RATINGS, seed=0)
        fs = FeatureSet.from_numpy(pairs, (ratings - 1).astype("int32"))
        batch = s["ncf_batch"]
        n_steps = len(fs) // batch
        est = Estimator(
            NeuralCF(user_count=ML1M_USERS, item_count=ML1M_ITEMS,
                     class_num=5),
            optimizer=Adam(lr=1e-3), loss="sparse_categorical_crossentropy",
            mesh=ctx.mesh,
            config=TrainConfig(log_every_n_steps=10 ** 9,
                               cache_on_device=True,
                               scan_block_steps=n_steps))
        t0 = time.perf_counter()
        est.fit(fs, batch_size=batch, epochs=1)           # compiles
        first = float(est.trainer_state.last_loss)
        t1 = time.perf_counter()
        est.fit(fs, batch_size=batch, epochs=s["ncf_epochs"])
        epoch_s = (time.perf_counter() - t1) / (s["ncf_epochs"] - 1)
        last = float(est.trainer_state.last_loss)
        if not (np.isfinite(first) and np.isfinite(last)):
            raise AssertionError(f"NCF loss not finite: {first}, {last}")
        self.say("4 train-ncf", data="synthetic_movielens(seed=0)",
                 ratings=len(fs), batch=batch, steps_per_epoch=n_steps,
                 mesh=f"dp={ctx.mesh.shape['dp']}",
                 first_epoch_s_incl_compile=f"{t1 - t0:.1f}",
                 epoch_s=f"{epoch_s:.3f}", loss=f"{first:.4f}->{last:.4f}",
                 gather="native" if native_available() else "numpy")

    # ------------------------------------------------------------- phase 5

    def kernels(self) -> None:
        """Every kernel and every reference is one jitted call on bf16 (or
        f32) operands made on the host; the error arithmetic is numpy's."""
        import jax
        import jax.numpy as jnp

        from analytics_zoo_tpu.ops import int8 as int8_ops
        from analytics_zoo_tpu.ops import int8_fused
        from analytics_zoo_tpu.ops.attention import full_attention
        from analytics_zoo_tpu.ops.flash_attention import flash_attention
        from analytics_zoo_tpu.ops.kv_cache import (decode_attention_multi,
                                                    paged_read)
        from analytics_zoo_tpu.ops.paged_attention import (
            paged_attention, synthetic_paged_case)

        s = self.size
        rng = np.random.default_rng(5)
        errs, tol = {}, {}

        def host(*arrays):
            return [np.asarray(a, np.float32) for a in arrays]

        def err(got, want):
            got, want = host(got, want)
            return float(np.max(np.abs(got - want) / (1 + np.abs(want))))

        def share(got, want):
            got, want = host(got, want)
            return float(np.max(np.abs(got - want)) / np.max(np.abs(want)))

        def highest(fn):
            """``fn`` on f32 copies of its array arguments, at "highest"."""
            def run(*a):
                with jax.default_matmul_precision("highest"):
                    return jax.jit(fn)(*[
                        x.astype(jnp.float32)
                        if jnp.issubdtype(x.dtype, jnp.floating) else x
                        for x in a])
            return run

        # flash forward and backward in bf16 against full attention in f32
        q, k, v, w = (jnp.asarray(rng.normal(size=s["flash_shape"]),
                                  jnp.bfloat16) for _ in range(4))

        def through(attn):
            def loss(q, k, v, w):
                out = attn(q, k, v)
                return jnp.sum(out.astype(jnp.float32) * w), out
            return jax.grad(loss, argnums=(0, 1, 2), has_aux=True)

        grads, out = jax.jit(through(
            lambda q, k, v: flash_attention(q, k, v, True)))(q, k, v, w)
        ref_grads, ref = highest(through(
            lambda q, k, v: full_attention(q, k, v, causal=True)))(q, k, v, w)
        errs["flash_fwd"] = err(out, ref)
        errs["flash_bwd"] = max(map(err, grads, ref_grads))
        tol["flash_fwd"] = tol["flash_bwd"] = BF16_TOL

        # paged attention at decode, verify and a tiled prefill width
        pg = s["paged"]
        for dtype, name, t in ((np.float32, "f32", F32_TOL),
                               (jnp.bfloat16, "bf16", BF16_TOL)):
            for q_len in pg["q_lens"]:
                case = synthetic_paged_case(
                    4, pg["pps"], 16, pg["h"], pg["d"], q_len=q_len,
                    dtype=dtype, rng=rng)
                # f32 operands at "highest", the regime the CPU parity
                # tests run in: at the TPU's default precision an f32 dot
                # is one bf16 pass, in the kernel as in XLA (1e-3 here)
                with jax.default_matmul_precision(
                        "highest" if name == "f32" else "default"):
                    got = jax.jit(lambda *a: paged_attention(
                        *a, page_size=16))(*case)
                want = highest(lambda pq, kp, vp, table, lengths:
                               decode_attention_multi(
                                   pq, paged_read(kp, table),
                                   paged_read(vp, table), lengths))(*case)
                errs[f"paged_q{q_len}_{name}"] = err(got, want)
                tol[f"paged_q{q_len}_{name}"] = t

        # fused int8 matmul and conv against the unfused lax scheme, as a
        # share of the product's largest value
        def packed(shape):
            return {a: jnp.asarray(b) for a, b in int8_ops.quantize_weight(
                rng.normal(size=shape).astype(np.float32)).items()}

        m, kk, n = s["int8_mkn"]
        x, wq = jnp.asarray(rng.normal(size=(m, kk)), jnp.bfloat16), \
            packed((kk, n))
        errs["int8_matmul"] = share(
            jax.jit(int8_fused.int8_matmul_fused)(x, wq),
            jax.jit(int8_ops.int8_matmul_unfused)(x, wq))
        b, hh, ww, cin, cout = s["conv"]
        x, wq = jnp.asarray(rng.normal(size=(b, hh, ww, cin)),
                            jnp.bfloat16), packed((3, 3, cin, cout))
        errs["int8_conv"] = share(
            jax.jit(lambda x, wq: int8_fused.int8_conv2d_fused(
                x, wq, padding="SAME"))(x, wq),
            jax.jit(lambda x, wq: int8_ops.int8_conv2d_unfused(
                x, wq, strides=(1, 1), padding="SAME"))(x, wq))
        tol["int8_matmul"] = tol["int8_conv"] = INT8_REL_TOL

        bad = {name: (e, tol[name]) for name, e in errs.items()
               if not e <= tol[name]}
        if bad:
            raise AssertionError(f"kernels off their references "
                                 f"(error, tolerance): {bad}")
        self.say("5 kernels", interpret=not self.on_tpu,
                 **{name: f"{e:.2e}" for name, e in errs.items()},
                 tol=f"f32:{F32_TOL},bf16:{BF16_TOL},int8:{INT8_REL_TOL}")

    PHASES = ("train_lm", "serve_generation", "serve_int8", "train_ncf",
              "kernels")


def _hist_sum(tm, name: str) -> float:
    samples = tm.snapshot().get(name, {}).get("samples", {})
    return float(sum(s["sum"] for s in samples.values()))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rehearse-on-cpu", action="store_true",
                    help="development only: tiny shapes, interpreted "
                         "kernels, platform=cpu in every line")
    args = ap.parse_args(argv)

    import jax

    dev = jax.devices()[0]
    platform, kind, count = dev.platform, dev.device_kind, len(jax.devices())
    if platform != "tpu" and not args.rehearse_on_cpu:
        print(f"chip_smoke: platform={platform} kind={kind!r}: JAX found no "
              f"TPU, and this script measures nothing else",
              file=sys.stderr, flush=True)
        return 2
    if args.rehearse_on_cpu:
        if platform != "cpu":
            print(f"chip_smoke: --rehearse-on-cpu on platform={platform}",
                  file=sys.stderr, flush=True)
            return 2
        # the routes a TPU takes by default, here in the interpreter
        os.environ["ZOO_PAGED_ATTENTION"] = "on"
        os.environ["ZOO_INT8_FUSED"] = "on"

    from analytics_zoo_tpu.common.compile_cache import enable_compile_cache

    smoke = Smoke(TINY if args.rehearse_on_cpu else FULL, platform)
    smoke.say("0 device", device_kind=repr(kind), count=count,
              jax=jax.__version__,
              jaxlib=importlib.metadata.version("jaxlib"),
              libtpu=importlib.metadata.version("libtpu"),
              compile_cache=enable_compile_cache(),
              cache_env=os.environ.get("JAX_COMPILATION_CACHE_DIR"))
    t0 = time.perf_counter()
    for name in Smoke.PHASES:
        getattr(smoke, name)()
    smoke.say("done", phases="0-5", wall_s=f"{time.perf_counter() - t0:.1f}")
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": kind, "count": count}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
