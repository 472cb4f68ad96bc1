"""A generation-serving deployment under load, as the serving drivers and
``tools/find_knee.py`` set it up: broker, engine and scheduler in this process
(which holds the chip), the load generator in child processes.

The way context, engine and client are built, the Mosaic-call check and the
logit arithmetic follow ``chip_smoke.py`` phase 2.
"""

from __future__ import annotations

import collections
import json
import os
import re
import subprocess
import sys
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from . import harness

# logits of the bf16 serving path (f32 weights cast at use, bf16 activations
# and KV pages) against the float32 reference at "highest" precision, over
# prefill and three decode steps: root-mean-square error relative to the
# reference's spread, and the largest single error. The v5e measured 0.009
# and 0.010 at 8 blocks of 1024 (PR 21); the limits are five times that, and
# a wrong page, position or mask is off by the spread itself (rel_rms ~ 1).
LOGIT_REL_RMS_TOL = 0.05
LOGIT_MAX_ABS_TOL = 0.05
N_DECODE_CHECKED = 3


def _next_pow2(n: int) -> int:
    return 1 << max(0, int(n) - 1).bit_length()


def prefill_bucket(n_prompt: int, page_size: int, max_seq_len: int) -> int:
    """The prefill executable a prompt of this length runs through (the rule
    of ``ContinuousBatcher._prefill_into_slot``)."""
    return min(max(_next_pow2(n_prompt), page_size), max_seq_len)


def length_bounds(spec: Dict[str, Any]) -> Tuple[int, int]:
    if spec["dist"] == "mixture":
        bounds = [length_bounds(p) for p in spec["parts"]]
        return min(b[0] for b in bounds), max(b[1] for b in bounds)
    return int(spec["min"]), int(spec["max"])


def prefill_buckets(mix: Dict[str, Any], sizes: Dict[str, Any]) -> List[int]:
    """Every prefill bucket the mix's prompt lengths can reach."""
    lo, hi = length_bounds(mix["prompt_len"])
    page, cap = sizes["gen_page_size"], sizes["gen_max_seq_len"]
    first, last = prefill_bucket(lo, page, cap), prefill_bucket(hi, page, cap)
    return [b for b in (first << i for i in range(32)) if b <= last]


class ServingRig:
    def __init__(self, run: harness.Run):
        import jax

        from analytics_zoo_tpu.serving import ServingConfig, start_broker
        from analytics_zoo_tpu.serving.generation import (GenerationClient,
                                                          GenerationEngine)

        self.run = run
        self.mix = run.traffic
        self.sizes = run.config["serving"]["ServingConfig"]
        harness.make_context(run.config)
        self.model = harness.build_model(run.config)
        self.params = harness.make_params(self.model, run.seed)
        jax.block_until_ready(self.params)
        run.say("weights", leaves=len(jax.tree_util.tree_leaves(self.params)))
        self.broker = start_broker()
        self.engine = GenerationEngine(
            self.model, self.params, config=ServingConfig(
                queue_port=self.broker.port, **self.sizes)).start()
        self.batcher = self.engine.batcher
        self.client = GenerationClient(port=self.broker.port)
        self.children: List[subprocess.Popen] = []
        self.records: List[Dict[str, Any]] = []
        self._readers: List[threading.Thread] = []
        self._ready = threading.Semaphore(0)
        self._done = threading.Semaphore(0)
        self._stopped = False

    # ---------------------------------------------------------------- warm-up

    def warm(self) -> None:
        """Every prefill executable this mix can reach and the decode step,
        through the client, until a whole round builds nothing new: the
        second prefill of a bucket sees a cache that decode steps have
        written, and compiled again in a trace of the v5e (PR 22)."""
        _, hi = length_bounds(self.mix["prompt_len"])
        rng = np.random.default_rng([self.run.seed, 99])
        buckets = prefill_buckets(self.mix, self.sizes)
        for round_no in range(4):
            before = self.run.compiles.count
            for bucket in buckets:
                ids = rng.integers(1, self.model.vocab,
                                   size=min(bucket, hi)).astype(np.int32)
                uri = self.client.submit(ids, max_new_tokens=3)
                n = sum(c.size for c in self.client.stream(uri, timeout_s=1100))
                if n != 3:
                    raise RuntimeError(f"warm-up stream ended with {n} tokens")
            built = self.run.compiles.count - before
            self.run.say("warm", round=round_no, buckets=buckets,
                         executables_built=built)
            if not built:
                return
        raise RuntimeError("warm-up kept compiling after four rounds")

    # ------------------------------------------------------------- generators

    def spawn(self, jobs: List[Dict[str, Any]]) -> None:
        """One child per job. They import and connect while this process
        warms up; ``measure`` waits until each has said READY."""
        env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONUNBUFFERED="1")
        env.pop("XLA_FLAGS", None)
        script = os.path.join(harness.HERE, "loadgen.py")
        for job in jobs:
            child = subprocess.Popen(
                [sys.executable, script], stdin=subprocess.PIPE,
                stdout=subprocess.PIPE, env=env, text=True, bufsize=1,
                cwd=harness.CHECKOUT)
            child.stdin.write(json.dumps(job) + "\n")
            child.stdin.flush()
            reader = threading.Thread(target=self._read, args=(child,),
                                      daemon=True)
            reader.start()
            self.children.append(child)
            self._readers.append(reader)

    def _read(self, child: subprocess.Popen) -> None:
        for line in child.stdout:
            line = line.strip()
            if line == "READY":
                self._ready.release()
            elif line == "DONE":
                self._done.release()
            elif line.startswith("{"):
                self.records.append(json.loads(line))

    def tell(self, message: str) -> None:
        for child in self.children:
            try:
                child.stdin.write(message + "\n")
                child.stdin.flush()
            except (BrokenPipeError, ValueError):
                pass

    def reap(self, timeout_s: float) -> bool:
        """Wait until every child said DONE and ended; kill what did not.
        True when all ended by themselves."""
        deadline = time.monotonic() + timeout_s
        clean = True
        for _ in self.children:
            clean &= self._done.acquire(
                timeout=max(0.0, deadline - time.monotonic()))
        for child in self.children:
            try:
                child.stdin.close()
            except (BrokenPipeError, ValueError):
                pass
            try:
                child.wait(timeout=max(1.0, deadline - time.monotonic()))
            except subprocess.TimeoutExpired:
                clean = False
                child.kill()
                child.wait()
        for reader in self._readers:
            reader.join(timeout=5)
        self.children, self._readers = [], []
        return clean

    # ------------------------------------------------------------------ load

    def base_job(self) -> Dict[str, Any]:
        return {"port": self.broker.port, "mix": self.mix,
                "vocab": self.model.vocab,
                "timeout_s": float(self.mix.get("request_timeout_s", 60)),
                "connections": int(self.mix.get("connections_per_process", 4))}

    def measure(self, lead_s: float, seconds: float,
                stop_at_end: bool) -> Dict[str, Any]:
        """Run the spawned children's load and observe the window
        ``[zero + lead_s, zero + lead_s + seconds]``."""
        run = self.run
        self.records = []
        for _ in self.children:
            if not self._ready.acquire(timeout=120):
                raise RuntimeError("a load generator did not get ready")
        zero = time.monotonic() + 0.5
        self.tell(f"GO {zero!r}")
        w0, w1 = zero + lead_s, zero + lead_s + seconds
        time.sleep(max(0.0, w0 - time.monotonic()))
        obs: Dict[str, Any] = {"window": (w0, w1)}
        obs["counters0"] = harness.counters()
        obs["stats0"] = self.batcher.stats()
        compiles0 = run.compiles.count
        tracer = run.trace_window()
        time.sleep(max(0.0, w1 - time.monotonic()))
        obs["counters1"] = harness.counters()
        obs["stats1"] = self.batcher.stats()
        obs["compiles_in_window"] = run.compiles.count - compiles0
        if stop_at_end:
            self.tell("STOP")
        if tracer is not None:
            obs.update(tracer.finish())
        obs["generators_clean"] = self.reap(
            float(self.mix.get("drain_s", 45)))
        obs["records"] = self.records
        obs["stats_end"] = self.batcher.stats()
        return obs

    # ----------------------------------------------------------- correctness

    def stop_serving(self) -> None:
        if self._stopped:
            return
        self._stopped = True
        self.client.close()
        self.engine.stop()
        self.broker.shutdown()

    def mosaic_kernels(self) -> Dict[str, int]:
        """Mosaic custom calls in the lowered decode step, by kernel name."""
        names: collections.Counter = collections.Counter()
        for line in self.batcher.lower_decode().as_text().splitlines():
            if "tpu_custom_call" in line:
                m = re.search(r'kernel_name = "([^"]+)"', line)
                names[m.group(1) if m else "?"] += 1
        return dict(names)

    def check_logits(self) -> Dict[str, float]:
        """Prefill, then teacher-forced decode steps, through the executables
        that just served, against the plain reference over the whole
        sequence. Call after ``stop_serving``: it consumes the cache."""
        from analytics_zoo_tpu.ops.kv_cache import SCRATCH_PAGE

        batcher, cfg = self.batcher, self.batcher.cfg
        served = sorted(batcher.prefill_buckets)
        bucket = served[len(served) // 2]
        n_prefill = int(0.75 * bucket) + 1
        rng = np.random.default_rng([self.run.seed, 7])
        seq = rng.integers(1, self.model.vocab,
                           size=n_prefill + N_DECODE_CHECKED).astype(np.int32)
        reference, kwargs = harness.reference_of(self.run.config)
        want_all = np.asarray(reference.logits(self.params, seq[None],
                                               **kwargs))[0]
        ids = np.zeros((1, bucket), np.int32)
        ids[0, :n_prefill] = seq[:n_prefill]
        n_pages = -(-len(seq) // cfg.page_size)
        table = np.full((cfg.n_slots, cfg.pages_per_slot), SCRATCH_PAGE,
                        np.int32)
        table[0, :n_pages] = 1 + np.arange(n_pages)
        logits, cache = batcher._prefill(
            batcher.params, batcher.cache, ids,
            np.array([n_prefill], np.int32), table[:1])
        got, want = [np.asarray(logits)[0]], [want_all[n_prefill - 1]]
        zeros = np.zeros(cfg.n_slots, np.uint32)
        for pos in range(n_prefill, len(seq)):
            step_ids = np.zeros(cfg.n_slots, np.int32)
            lengths = np.zeros(cfg.n_slots, np.int32)
            step_ids[0], lengths[0] = seq[pos], pos
            _next, logits, cache = batcher._decode(
                batcher.params, cache, step_ids, lengths, table, zeros,
                zeros, np.zeros(cfg.n_slots, np.float32))
            got.append(np.asarray(logits)[0])
            want.append(want_all[pos])
        got, want = np.stack(got), np.stack(want)
        finite = bool(np.isfinite(got).all())
        return {"bucket": bucket, "n_prefill": n_prefill, "finite": finite,
                "rel_rms": float(np.sqrt(np.mean((got - want) ** 2))
                                 / want.std()) if finite else float("inf"),
                "max_abs": float(np.abs(got - want).max())
                if finite else float("inf")}

    def verdict(self, obs: Dict[str, Any], on_tpu: bool) -> List[str]:
        """Everything that makes this run's outputs wrong, in words; empty
        when it is correct. Ends serving."""
        faults = []
        stats = obs["stats_end"]
        self.stop_serving()
        if obs["compiles_in_window"]:
            faults.append(f"{obs['compiles_in_window']} executables were "
                          f"built inside the window")
        if stats["distinct_decode_shapes"] != 1:
            faults.append(f"decode ran {stats['distinct_decode_shapes']} shapes")
        if not obs["generators_clean"]:
            faults.append("a load generator had to be killed")
        kernels = self.mosaic_kernels()
        if on_tpu and not kernels.get("zoo_paged_attention"):
            faults.append(f"the decode step holds no zoo_paged_attention "
                          f"Mosaic call: {kernels}")
        check = self.check_logits()
        self.run.say("logits", **check, tol_rel_rms=LOGIT_REL_RMS_TOL,
                     tol_max_abs=LOGIT_MAX_ABS_TOL, mosaic=kernels)
        if not (check["rel_rms"] <= LOGIT_REL_RMS_TOL
                and check["max_abs"] <= LOGIT_MAX_ABS_TOL):
            faults.append(f"logits off the reference: {check}")
        return faults

    def close(self) -> None:
        """End every process and thread this rig started."""
        for child in self.children:
            child.kill()
            child.wait()
        self.stop_serving()


def serve_cell(run: harness.Run, make_jobs, reduce,
               stop_at_end: bool) -> harness.Outcome:
    """One run of a serving cell. ``make_jobs(rig, lead_s)`` gives the load
    generators' jobs; ``reduce(obs, seconds)`` turns the window's records into
    ``(attempted, failed, end-to-end metrics)`` and marks each record
    ``in_window``."""
    import jax

    lead_s = float(run.traffic.get("lead_in_s", 6))
    rig = ServingRig(run)
    try:
        rig.spawn(make_jobs(rig, lead_s))
        rig.warm()
        obs = rig.measure(lead_s, run.seconds, stop_at_end)
        attempted, failed, metrics = reduce(obs, run.seconds)
        run.say("window", attempted=attempted, failed=failed,
                **{k: round(v, 3) for k, v in metrics.items()},
                requests=obs["stats_end"]["requests"],
                steps=obs["stats1"]["steps"] - obs["stats0"]["steps"],
                **({"distribution_ms": obs["distribution_ms"]}
                   if "distribution_ms" in obs else {}))
        faults = rig.verdict(obs, jax.devices()[0].platform == "tpu")
        bad = [r["outcome"] for r in obs["records"] if r["outcome"] != "ok"]
        if bad:
            faults.append(f"{len(bad)} requests did not end ok: {bad[:5]}")
    finally:
        rig.close()
    metrics["setup_s"] = obs["window"][0] - run.t_process_start
    return harness.Outcome(correct=not faults, attempted=attempted,
                           failed=failed, end_to_end=metrics,
                           observations=obs, notes=faults)
