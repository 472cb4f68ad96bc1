"""A generation-serving deployment under load, as the serving drivers,
``tools/find_knee.py`` and ``tools/loop_phases.py`` set it up, which is how
``python -m analytics_zoo_tpu.serving.cli start`` deploys it: the broker in a
process of its own (``python -m analytics_zoo_tpu.serving.broker``, no chip),
engine, scheduler, source and sink in this process (which holds the chip), the
load generator in child processes. There is one way to deploy and no switch.

The way context, engine and client are built, the Mosaic-call check and the
logit arithmetic follow ``chip_smoke.py`` phase 2.
"""

from __future__ import annotations

import collections
import json
import os
import re
import select
import socket
import subprocess
import sys
import threading
import time
from typing import Any, Dict, List, Tuple

import numpy as np

from . import harness

# Four numbers decide ``correct``, each with a limit of its own (PERF.md,
# section 2, has the readings each limit was set from).
#
# ``served_gap``: over a sample, drawn from the seed, of the requests the
# window finished (the longest among them), the reference runs once over each
# prompt with its served tokens, and the number is the widest gap by which a
# served token's logit lies below the reference's best at its position. The
# traffic is greedy, so a sound deployment serves the reference's best token
# or one that bf16 rounding cannot tell from it. On the v5e at the cells'
# size it read at most 0.0154 over 27 seeds of the chat cell and 0.0101 over
# 23 of the docs cell, and the float8 control at least 0.0625 and 0.0701 over
# six seeds each (PR 34); the limit lies between, with more of the room
# above the lower reading.
#
# ``logit_rel_rms`` / ``logit_max_abs``: logits of the executables that just
# served (f32 weights cast once to bf16, bf16 activations and KV pages)
# against the float32 reference at "highest" precision, over one prefill and
# three teacher-forced decode steps at one slot: root-mean-square error
# relative to the reference's spread, and the largest single error. The v5e
# measured 0.009 and 0.010 at 8 blocks of 1024 (PR 21) and 0.0112-0.0124 and
# 0.0142-0.0182 at the cell's size (PRs 22-34); the float8 control over the
# same sequence at least 0.106 and 0.137, the int8 one 0.023-0.026 and
# 0.030-0.033 (PR 34); a wrong page, position or mask is off by the spread
# itself (rel_rms ~ 1).
#
# ``narrow_operands``: tensors narrower than 16 bits (int8, int4, float8; not
# booleans) among the served tree's and the page pool's leaves and in the
# lowered decode step. The configuration states bfloat16, so a sound
# deployment has none and the limit is 0, an exact comparison. It is there
# because int8 with a scale per row and column is within twice of bfloat16
# on these weights: no number read from outputs parts it from the stated
# precision by the three times a limit needs (PERF.md, section 2), and it is
# the step down that a v5e tempts.
#
# The control (``run.py --control fp8|int8``) is the reference computed in
# that precision and put in the program's place: its first token at each
# served position, its logits over the probe's sequence and its lowered block
# go through the same comparison, and the run has to come out not correct.
SERVED_GAP_TOL = 0.04
LOGIT_REL_RMS_TOL = 0.05
LOGIT_MAX_ABS_TOL = 0.05
NARROW_OPERANDS_TOL = 0
N_DECODE_CHECKED = 3
#: requests in the served sample, and the multiple the reference pads to
SERVED_SAMPLE = 6
REFERENCE_PAD = 512
#: a tensor type narrower than 16 bits in StableHLO text (``i1`` is a mask)
_NARROW = re.compile(r"[<x](u?i[248]|f[468]E\w+)>")


def narrow_types(lowered_text: str) -> Dict[str, int]:
    """Tensor element types narrower than 16 bits in a lowered program's
    text, each with how often it occurs."""
    return dict(collections.Counter(_NARROW.findall(lowered_text)))


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


def rel_errors(got: np.ndarray, want: np.ndarray) -> Dict[str, float]:
    """Root-mean-square error relative to the reference's spread, and the
    largest single error; infinite where ``got`` is not finite."""
    if not np.isfinite(got).all():
        return {"rel_rms": float("inf"), "max_abs": float("inf")}
    return {"rel_rms": float(np.sqrt(np.mean((got - want) ** 2)) / want.std()),
            "max_abs": float(np.abs(got - want).max())}


class BrokerProcess:
    """``python -m analytics_zoo_tpu.serving.broker`` on this machine, in a
    process of its own that never sees the chip: what ``serving/cli.py``
    ``do_start`` launches. It is given port 0, binds whichever the kernel
    hands it and says which on its first line, so no other run on the
    machine (the driver runs parent and change side by side) can take the
    port between a probe and the bind, and what answers there is this
    process and no other checkout's."""

    HOST = "127.0.0.1"

    def __init__(self):
        env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONUNBUFFERED="1")
        env.pop("XLA_FLAGS", None)
        self.port = None
        self.proc = subprocess.Popen(
            [sys.executable, "-m", "analytics_zoo_tpu.serving.broker",
             "--host", self.HOST, "--port", "0"],
            cwd=harness.CHECKOUT, env=env, stdout=subprocess.PIPE, text=True)

    def wait_until_it_answers(self, timeout_s: float = 60.0) -> None:
        """Read the port the broker bound from its first line, then connect
        to it once; the broker still has to be alive after that."""
        try:
            ready, _, _ = select.select([self.proc.stdout], [], [], timeout_s)
            line = self.proc.stdout.readline() if ready else ""
            said = re.search(r"listening on \S+:(\d+)\s*$", line)
            if not said:
                raise RuntimeError(
                    f"the broker did not say its port within {timeout_s} s "
                    f"(it said {line!r}, exit code {self.proc.poll()})")
            self.port = int(said.group(1))
            socket.create_connection((self.HOST, self.port), 5.0).close()
            if self.proc.poll() is not None:
                raise RuntimeError(f"the broker exited with code "
                                   f"{self.proc.returncode} after it answered")
        except BaseException:
            self.stop()
            raise

    def stop(self) -> None:
        """Kill and wait: a broker left behind is a process the driver has
        to end."""
        if self.proc.poll() is None:
            self.proc.kill()
        self.proc.wait()
        self.proc.stdout.close()


class ServingRig:
    def __init__(self, run: harness.Run):
        import jax

        from analytics_zoo_tpu.serving import ServingConfig
        from analytics_zoo_tpu.serving.generation import (GenerationClient,
                                                          GenerationEngine)

        self.run = run
        self.mix = run.traffic
        self.sizes = run.config["serving"]["ServingConfig"]
        self.children: List[subprocess.Popen] = []
        self.records: List[Dict[str, Any]] = []
        self._readers: List[threading.Thread] = []
        self._ready = threading.Semaphore(0)
        self._done = threading.Semaphore(0)
        self._stopped = False
        self.checks: Dict[str, List[float]] = {}
        self.engine = self.client = None
        self.broker = BrokerProcess()       # it imports while weights are made
        try:
            harness.make_context(run.config)
            self.model = harness.build_model(run.config)
            params = harness.make_params(self.model, run.seed)
            jax.block_until_ready(params)
            run.say("weights", leaves=len(jax.tree_util.tree_leaves(params)))
            self.broker.wait_until_it_answers()
            self.engine = GenerationEngine(
                self.model, params, config=ServingConfig(
                    queue_port=self.broker.port, **self.sizes)).start()
            # the engine serves from a tree of its own and keeps no reference
            # to this one: the rig lets go of it, as a deployment does, and
            # makes it again from the seed for the reference check
            del params
            self.batcher = self.engine.batcher
            self.client = GenerationClient(port=self.broker.port)
            run.say("deployed", broker_pid=self.broker.proc.pid,
                    broker_port=self.broker.port)
        except BaseException:
            self.close()
            raise

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
        ``[zero + lead_s, zero + lead_s + seconds]``. A traced run profiles
        the seconds that follow the window, under the same load: what the
        clients and the counters report is then read where no profiler runs
        (under one the sink falls behind and time to first token read
        thirtyfold, PRs 27-33), and the device metrics where one does."""
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
        time.sleep(max(0.0, w1 - time.monotonic()))
        obs["counters1"] = harness.counters()
        obs["stats1"] = self.batcher.stats()
        tracer = run.trace_window(start_after_s=0.0)
        if tracer is not None:
            obs.update(tracer.finish())
        obs["compiles_in_window"] = run.compiles.count - compiles0
        if stop_at_end:
            self.tell("STOP")
        obs["generators_clean"] = self.reap(
            float(self.mix.get("drain_s", 45)))
        obs["records"] = self.records
        obs["stats_end"] = self.batcher.stats()
        return obs

    # ----------------------------------------------------------- correctness

    def stop_serving(self) -> None:
        """End the engine and the broker's process (kill and wait)."""
        if self._stopped:
            return
        self._stopped = True
        try:
            if self.client is not None:
                self.client.close()
            if self.engine is not None:
                self.engine.stop()
        finally:
            self.broker.stop()

    @staticmethod
    def mosaic_kernels(lowered_text: str) -> Dict[str, int]:
        """Mosaic custom calls in a lowered program's text, by kernel name."""
        names: collections.Counter = collections.Counter()
        for line in lowered_text.splitlines():
            if "tpu_custom_call" in line:
                m = re.search(r'kernel_name = "([^"]+)"', line)
                names[m.group(1) if m else "?"] += 1
        return dict(names)

    def program_logits(self):
        """Prefill, then teacher-forced decode steps, through the executables
        that just served: ``(sequence, prefill length, bucket, logits)``.
        Call after ``stop_serving``: it consumes the cache."""
        from analytics_zoo_tpu.ops.kv_cache import SCRATCH_PAGE

        batcher, cfg = self.batcher, self.batcher.cfg
        served = sorted(batcher.prefill_buckets)
        bucket = served[len(served) // 2]
        n_prefill = int(0.75 * bucket) + 1
        rng = np.random.default_rng([self.run.seed, 7])
        seq = rng.integers(1, self.model.vocab,
                           size=n_prefill + N_DECODE_CHECKED).astype(np.int32)
        ids = np.zeros((1, bucket), np.int32)
        ids[0, :n_prefill] = seq[:n_prefill]
        n_pages = -(-len(seq) // cfg.page_size)
        table = np.full((cfg.n_slots, cfg.pages_per_slot), SCRATCH_PAGE,
                        np.int32)
        table[0, :n_pages] = 1 + np.arange(n_pages)
        logits, cache = batcher._prefill(
            batcher.params, batcher.cache, ids,
            np.array([n_prefill], np.int32), table[:1])
        got = [np.asarray(logits)[0]]
        zeros = np.zeros(cfg.n_slots, np.uint32)
        for pos in range(n_prefill, len(seq)):
            step_ids = np.zeros(cfg.n_slots, np.int32)
            lengths = np.zeros(cfg.n_slots, np.int32)
            step_ids[0], lengths[0] = seq[pos], pos
            _next, logits, cache = batcher._decode(
                batcher.params, cache, step_ids, lengths, table, zeros,
                zeros, np.zeros(cfg.n_slots, np.float32))
            got.append(np.asarray(logits)[0])
        batcher.cache = cache
        return seq, n_prefill, bucket, np.stack(got)

    def release_program_state(self) -> int:
        """Free what the engine held on the device (served tree, page pool),
        so that the reference runs beside nothing; peak bytes before that."""
        import jax

        chips = jax.devices()[:self.run.cell["chips"]]
        stats = [d.memory_stats() or {} for d in chips]
        self.run.say("memory", held_bytes=max(
            (m.get("bytes_in_use", 0) for m in stats), default=0))
        for leaf in jax.tree_util.tree_leaves((self.batcher.params,
                                               self.batcher.cache)):
            if hasattr(leaf, "delete") and not leaf.is_deleted():
                leaf.delete()
        return max((m.get("peak_bytes_in_use", 0) for m in stats), default=0)

    def served_sample(self, records) -> List[Dict[str, Any]]:
        """Requests the window finished, drawn from the seed, the longest
        among them first."""
        done = sorted((r for r in records if r.get("in_window")
                       and r["outcome"] == "ok" and r.get("tokens")),
                      key=lambda r: tuple(r["token_seed"]))
        if not done:
            return []
        longest = max(done, key=lambda r: r["prompt_len"] + r["output_len"])
        rng = np.random.default_rng([self.run.seed, 11])
        drawn = [done[i] for i in rng.permutation(len(done))
                 if done[i] is not longest]
        return [longest] + drawn[:SERVED_SAMPLE - 1]

    def narrow_operands(self, lowered_text: str) -> Dict[str, int]:
        """What the deployment holds or computes in under 16 bits: leaves of
        the served tree and the page pool by their type, and the tensor
        types of the lowered decode step. Call before the state is freed."""
        import jax

        found = collections.Counter(narrow_types(lowered_text))
        for leaf in jax.tree_util.tree_leaves((self.batcher.params,
                                               self.batcher.cache)):
            dtype = np.dtype(leaf.dtype)
            if dtype.itemsize < 2 and dtype != np.bool_:
                found[f"leaf:{dtype.name}"] += 1
        return dict(found)

    def served_gaps(self, sample, params, control) -> Dict[str, Any]:
        """The reference, once over each sampled prompt with its served
        tokens: the widest gap by which a served token's logit lies below the
        reference's best at its position. With a ``control`` also the same
        reading of the token that the reference computed in that lower
        precision puts first there."""
        from benchmark import traffic_gen as traffic

        reference, kwargs = harness.reference_of(self.run.config)
        cap = int(self.sizes["gen_max_seq_len"])
        out = {"requests": len(sample), "tokens": 0, "served_gap": 0.0}
        if control:
            out["control_gap"] = 0.0
        for r in sample:
            tokens = np.asarray(r["tokens"], np.int32)
            seq = np.concatenate([traffic.prompt_tokens(
                self.mix, r, self.model.vocab), tokens[:-1]])
            padded = min(-(-len(seq) // REFERENCE_PAD) * REFERENCE_PAD, cap)
            ids = np.zeros((1, padded), np.int32)
            ids[0, :len(seq)] = seq
            at = r["prompt_len"] - 1 + np.arange(len(tokens))
            rows = np.asarray(reference.logits(params, ids, **kwargs))[0][at]
            best, each = rows.max(-1), np.arange(len(at))
            out["tokens"] += len(tokens)
            out["served_gap"] = max(out["served_gap"], float(
                (best - rows[each, tokens]).max()))
            if control:
                low = np.asarray(reference.logits(
                    params, ids, precision=control, **kwargs))[0][at]
                out["control_gap"] = max(out["control_gap"], float(
                    (best - rows[each, low.argmax(-1)]).max()))
        return out

    def verdict(self, obs: Dict[str, Any], on_tpu: bool) -> List[str]:
        """Everything that makes this run's outputs wrong, in words; empty
        when it is correct. Ends serving, frees its state, and only then runs
        the reference. ``self.checks`` holds each number compared beside its
        limit."""
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
        decode_step = self.batcher.lower_decode().as_text()
        kernels = self.mosaic_kernels(decode_step)
        if on_tpu and not kernels.get("zoo_paged_attention"):
            faults.append(f"the decode step holds no zoo_paged_attention "
                          f"Mosaic call: {kernels}")
        seq, n_prefill, bucket, got = self.program_logits()
        narrow = self.narrow_operands(decode_step)
        obs["memory_peak_bytes"] = self.release_program_state()

        control = self.run.control
        params = harness.make_params(self.model, self.run.seed)
        reference, kwargs = harness.reference_of(self.run.config)
        want = np.asarray(reference.logits(params, seq[None], **kwargs))[
            0][n_prefill - 1:]
        served = self.served_gaps(self.served_sample(obs["records"]), params,
                                  control)
        gap = served["served_gap"] if served["requests"] else float("inf")
        if control:     # the reference in that precision, in the program's place
            self.run.say("program", served_gap=gap, narrow=narrow, **rel_errors(
                got, want))
            got = np.asarray(reference.logits(
                params, seq[None], precision=control, **kwargs))[
                0][n_prefill - 1:]
            narrow = narrow_types(reference.lowered_block(
                params, seq[None], precision=control, **kwargs))
            gap = served["control_gap"] if served["requests"] else gap
        del params
        errors = rel_errors(got, want)
        self.run.say("logits", bucket=bucket, n_prefill=n_prefill,
                     mosaic=kernels, narrow=narrow, **errors)
        self.run.say("served", **served)
        self.checks = {
            "served_gap": [gap, SERVED_GAP_TOL],
            "logit_rel_rms": [errors["rel_rms"], LOGIT_REL_RMS_TOL],
            "logit_max_abs": [errors["max_abs"], LOGIT_MAX_ABS_TOL],
            "narrow_operands": [sum(narrow.values()), NARROW_OPERANDS_TOL]}
        for name, (value, limit) in self.checks.items():
            if not value <= limit:
                faults.append(f"{name} {value} is over its limit {limit}")
        return faults

    def close(self) -> None:
        """End every process and thread this rig started, also when the run
        failed: the load generators, the engine, the broker."""
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
                           observations=obs, notes=faults, checks=rig.checks)
