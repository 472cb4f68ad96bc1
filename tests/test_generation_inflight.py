"""One decode step in flight (ISSUE 40): ``ContinuousBatcher`` launches step
n+1 from step n's ids on the device and reads step n while it runs. These
tests hold the order of a pass to what it promises: streams bit-identical to
the serial order, nothing of a stale step reaching a refilled row, one decode
executable for both forms of ``ids``, a loop killed between a launch and its
collect, a failing step, the launch counter and the step EMA. No test asserts
a time.
"""

import threading
import time

import numpy as np
import pytest

import jax

from analytics_zoo_tpu.common.chaos import (ChaosSchedule, install_chaos,
                                            uninstall_chaos)
from analytics_zoo_tpu.models.transformer import TransformerLM
from analytics_zoo_tpu.serving import generation as gen
from analytics_zoo_tpu.serving.generation import ContinuousBatcher

pytestmark = pytest.mark.generation

VOCAB, HIDDEN, BLOCKS, HEADS, SEQ = 64, 32, 2, 2, 64


@pytest.fixture(scope="module")
def model_and_params():
    m = TransformerLM(vocab=VOCAB, hidden_size=HIDDEN, n_block=BLOCKS,
                      n_head=HEADS, seq_len=SEQ)
    params, _ = m.build(jax.random.PRNGKey(0))
    return m, params


def _batcher(model_and_params, **kw):
    m, params = model_and_params
    kw.setdefault("n_slots", 2)
    kw.setdefault("page_size", 4)
    kw.setdefault("max_seq_len", 32)
    return ContinuousBatcher(m, params, **kw)


def _always_drained(b):
    """The serial order through the same two functions: every pass collects
    the step in flight before it launches (what an admission does)."""
    b._why_drain = lambda flight, rows: "admit"
    return b


def _launches(b):
    return b.stats()["decode_launches"]


# ------------------------------------------------- (a) bit-identical streams

def _schedule(np_rng, temperature):
    """Requests that end in every way a stream can: by ``max_new_tokens``,
    by ``max_seq_len`` (truncated), by EOS (filled in by the caller from a
    free run) and by a cancellation at a given token."""
    def prompt(n):
        return np_rng.integers(1, VOCAB, size=n).astype(np.int32)

    kinds = [("budget", prompt(4), 9), ("budget", prompt(7), 2),
             ("seq_len", prompt(20), 30), ("eos", prompt(5), 16),
             ("cancel", prompt(3), 25), ("budget", prompt(6), 14),
             ("eos", prompt(9), 12), ("budget", prompt(2), 1)]
    return [dict(kind=kind, prompt=p, max_new_tokens=n,
                 temperature=temperature, seed=500 + i)
            for i, (kind, p, n) in enumerate(kinds)]


def _run_schedule(b, reqs):
    """Submit the first half at once and the rest as streams end (three
    requests a free slot finds waiting, so admissions fall mid-stream);
    returns ``[(tokens, outcome, n_tokens)]`` in request order."""
    results = [None] * len(reqs)
    done = threading.Semaphore(0)

    def submit(i):
        r = reqs[i]
        got = []

        def on_chunk(tokens, final, meta, i=i, r=r, got=got):
            got.extend(tokens)
            if r["kind"] == "cancel" and len(got) == 5 and not final:
                handle.cancel()         # on the loop's thread: seen at once
            if final:
                results[i] = (list(got), meta["outcome"], meta["n_tokens"])
                done.release()

        handle = b.submit(r["prompt"], max_new_tokens=r["max_new_tokens"],
                          temperature=r["temperature"], seed=r["seed"],
                          eos_id=r.get("eos_id"), on_chunk=on_chunk)

    half = len(reqs) // 2
    for i in range(half):
        submit(i)
    for i in range(half, len(reqs)):
        assert done.acquire(timeout=60)
        submit(i)
    for _ in range(half):
        assert done.acquire(timeout=60)
    return results


@pytest.mark.parametrize("temperature", [0.0, 0.8],
                         ids=["greedy", "sampled"])
def test_streams_are_bit_identical_to_the_drained_order(model_and_params,
                                                        np_rng, temperature):
    reqs = _schedule(np_rng, temperature)
    free = _always_drained(_batcher(model_and_params))
    try:
        for r in reqs:                  # name a token of the free run as EOS
            if r["kind"] == "eos":
                run = free.generate(r["prompt"], temperature=temperature,
                                    max_new_tokens=r["max_new_tokens"],
                                    seed=r["seed"])
                r["eos_id"] = int(run[len(run) // 2])
        serial = _run_schedule(free, reqs)
        assert _launches(free)["ahead"] == 0
        assert free.pool.free_count() == free.pool.capacity
    finally:
        free.close()
    b = _batcher(model_and_params)
    try:
        ahead = _run_schedule(b, reqs)
        launches = _launches(b)
        assert b.pool.free_count() == b.pool.capacity
    finally:
        b.close()
    assert ahead == serial
    outcomes = {r["kind"]: out[1] for r, out in zip(reqs, serial)}
    assert outcomes == {"budget": "ok", "seq_len": "truncated", "eos": "ok",
                        "cancel": "cancelled"}
    assert all(len(out[0]) == out[2] for out in serial)
    assert serial[4][2] == 5            # the cancellation took hold at once
    # the mechanism ran: most launches took their ids from the device, and
    # the drained ones were the idle batcher's first and the admissions'
    assert launches["ahead"] > launches["drained"] > 0
    assert set(launches["drained_by"]) <= {"first", "admit"}


# ------------------------------- (b) a stale step and the row refilled at once

def test_a_refilled_row_gets_nothing_of_the_stale_step(model_and_params,
                                                       np_rng):
    prompt_a = np_rng.integers(1, VOCAB, size=5).astype(np.int32)
    prompt_b = np_rng.integers(1, VOCAB, size=6).astype(np.int32)
    b = _batcher(model_and_params, n_slots=1)
    try:
        cap = b.pool.capacity
        free_a = b.generate(prompt_a, max_new_tokens=12, temperature=0.7,
                            seed=3)
        ref_b = b.generate(prompt_b, max_new_tokens=10, temperature=0.7,
                           seed=4)
        eos = int(free_a[6])            # first met at a decode step's collect
        cut = free_a.index(eos)
        assert cut >= 2
        steps0, kept0 = b.steps, b._occupied_slot_steps
        ha = b.submit(prompt_a, max_new_tokens=12, temperature=0.7, seed=3,
                      eos_id=eos)
        hb = b.submit(prompt_b, max_new_tokens=10, temperature=0.7, seed=4)
        assert ha.result(timeout_s=60) == free_a[:cut + 1]
        assert hb.result(timeout_s=60) == ref_b
        deadline = time.time() + 10
        while b.active_slots() and time.time() < deadline:
            time.sleep(0.005)
        # the step launched before A's EOS was read stepped A's row and was
        # collected once B held it: a step that kept no row's token
        assert (b.steps - steps0) - (b._occupied_slot_steps - kept0) == 1
        assert b.pool.free_count() == cap
        assert b.stats()["requests"] == {"ok": 4}
    finally:
        b.close()


# ------------------------------------------------------------ (c) the counter

def _counter(order, reason=""):
    return gen._GEN_LAUNCHES.labels(order, reason).value()


def test_launch_counter_one_stream_alone(model_and_params, np_rng):
    b = _batcher(model_and_params)
    try:
        ahead0, first0 = _counter("ahead"), _counter("drained", "first")
        out = b.generate(np_rng.integers(1, VOCAB, size=4), max_new_tokens=16)
        assert len(out) == 16
        stats = b.stats()
        steps = stats["steps"]
        assert steps == 15              # token 0 is the prefill's
        assert stats["decode_launches"] == {
            "ahead": steps - 1, "drained": 1, "drained_by": {"first": 1}}
        assert _counter("ahead") - ahead0 == steps - 1
        assert _counter("drained", "first") - first0 == 1
    finally:
        b.close()


def test_each_admission_mid_stream_is_one_drained_launch(model_and_params,
                                                         np_rng):
    b = _batcher(model_and_params, n_slots=3)
    try:
        admitted = [threading.Event(), threading.Event()]
        n_a = [0]

        def on_chunk(tokens, final, meta):
            # the loop's thread waits here, mid-stream, until the next
            # request is in the submit queue: its admission is the next pass
            n_a[0] += len(tokens)
            if n_a[0] == 4:
                assert admitted[0].wait(30)
            if n_a[0] == 9:
                assert admitted[1].wait(30)

        before = _counter("drained", "admit")
        ha = b.submit(np_rng.integers(1, VOCAB, size=4), max_new_tokens=20,
                      on_chunk=on_chunk)
        others = []
        for ev in admitted:
            while n_a[0] < (4 if ev is admitted[0] else 9):
                time.sleep(0.001)
            others.append(b.submit(np_rng.integers(1, VOCAB, size=5),
                                   max_new_tokens=4))
            ev.set()
        assert len(ha.result(timeout_s=60)) == 20
        assert all(len(h.result(timeout_s=60)) == 4 for h in others)
        stats = b.stats()
        assert stats["decode_launches"]["drained_by"] == {"first": 1,
                                                          "admit": 2}
        assert (stats["decode_launches"]["ahead"]
                + stats["decode_launches"]["drained"]) == stats["steps"] == 19
        assert _counter("drained", "admit") - before == 2
    finally:
        b.close()


# ------------------------------------------------- (d) one decode executable

def test_both_forms_of_ids_are_one_executable(model_and_params, np_rng):
    b = _batcher(model_and_params)
    try:
        hs = [b.submit(np_rng.integers(1, VOCAB, size=3 + i),
                       max_new_tokens=6 + i, temperature=0.5, seed=i)
              for i in range(4)]
        assert [len(h.result(timeout_s=60)) for h in hs] == [6, 7, 8, 9]
        launches = _launches(b)
        assert launches["ahead"] and launches["drained"]    # both forms ran
        assert b.stats()["distinct_decode_shapes"] == 1
        # a step's output in the place of ids and ids put from the host are
        # one signature to the jitted function: one entry, one executable
        assert b._decode._cache_size() == 1
        # and host ids as NumPy, as the benchmark's logit probe passes them
        # once serving has stopped, run that executable too
        built = []
        jax.monitoring.register_event_duration_secs_listener(
            lambda event, _s, **_k: built.append(event)
            if event.endswith("backend_compile_duration") else None)
        n = b.n_slots
        zeros = np.zeros(n, np.uint32)
        while b.active_slots():
            time.sleep(0.005)
        table = np.full((n, b.cfg.pages_per_slot), 0, np.int32)
        next_ids, _logits, b.cache = b._decode(
            b.params, b.cache, np.zeros(n, np.int32), np.zeros(n, np.int32),
            table, zeros, zeros, np.zeros(n, np.float32))
        assert np.asarray(next_ids).shape == (n,) and not built
    finally:
        b.close()


# --------------------------- (e) killed between a launch and its collect

@pytest.mark.chaos
def test_loop_killed_with_a_step_in_flight_loses_and_repeats_no_token(
        model_and_params, np_rng):
    prompt = np_rng.integers(1, VOCAB, size=4).astype(np.int32)
    ref_b = _batcher(model_and_params)
    try:
        ref = ref_b.generate(prompt, max_new_tokens=14, temperature=0.6,
                             seed=21)
    finally:
        ref_b.close()
    b = _batcher(model_and_params)
    sched = ChaosSchedule(seed=3).kill("serving.generate", at=(2, 5))
    in_flight_at_respawn = []
    spawn = b._spawn_loop

    def spy():
        in_flight_at_respawn.append(b._flight is not None)
        return spawn()

    b._spawn_loop = spy
    frames = []

    def on_chunk(tokens, final, meta):
        if not frames:
            # from the first token on: the next pass launches, the one after
            # it is killed at the chaos site with that step in flight
            install_chaos(sched)
        frames.append(list(tokens))

    try:
        out = b.submit(prompt, max_new_tokens=14, temperature=0.6, seed=21,
                       on_chunk=on_chunk).result(timeout_s=60)
        assert out == ref
        assert [t for f in frames for t in f] == ref    # none twice, in order
        assert b.loop_respawns == 2
        assert in_flight_at_respawn == [True, True]
        assert b.steps == 13            # every step computed and read once
        assert b.pool.free_count() == b.pool.capacity
    finally:
        uninstall_chaos()
        b.close()


# ------------------------------------------- (f) a step that fails, once

class _Poisoned:
    """What a failed step returns: reading it raises."""

    def __array__(self, *a, **k):
        raise RuntimeError("xla died")


@pytest.mark.parametrize("where", ["launch", "collect"])
def test_a_failing_step_fails_its_streams_exactly_once(model_and_params,
                                                       np_rng, where):
    b = _batcher(model_and_params)
    try:
        real = b._decode
        calls = [0]

        def decode(params, cache, ids, *rest):
            calls[0] += 1
            if calls[0] < 3:
                return real(params, cache, ids, *rest)
            if where == "launch":
                raise RuntimeError("xla died")
            # the dispatch goes through; the failure surfaces at the read,
            # of this step and of the one chained on it
            return _Poisoned(), None, cache

        b._decode = decode
        finals = {0: [], 1: []}

        def on_chunk(i):
            def cb(tokens, final, meta):
                if final:
                    finals[i].append(meta)
            return cb

        hs = [b.submit(np_rng.integers(1, VOCAB, size=4), max_new_tokens=20,
                       on_chunk=on_chunk(i)) for i in range(2)]
        for h in hs:
            frames = list(h.frames(timeout_s=30))
            assert frames[-1][1] is True
        deadline = time.time() + 10
        while b._flight is not None and time.time() < deadline:
            time.sleep(0.005)
        assert all(len(f) == 1 and f[0]["outcome"] == "error"
                   and "decode step failed" in f[0]["error"]
                   for f in finals.values())
        assert b.stats()["requests"] == {"error": 2}
        assert b.pool.free_count() == b.pool.capacity
        assert b.loop_respawns == 0
        # the loop lives on and serves the next request from a clean state
        b._decode = real
        assert len(b.generate(np_rng.integers(1, VOCAB, size=4),
                              max_new_tokens=5)) == 5
    finally:
        b.close()


# ------------------------------------------------------------ (g) step_ema

class _SlowIds:
    """A step's ids that are ready ``step_s`` after the device, which runs
    its dispatches in order, got to the step."""

    ready_at = 0.0

    def __init__(self, ids, step_s):
        self.ids = ids
        cls = _SlowIds
        cls.ready_at = max(cls.ready_at, time.monotonic()) + step_s
        self.mine = cls.ready_at

    def __array__(self, *a, **k):
        time.sleep(max(0.0, self.mine - time.monotonic()))
        return np.asarray(self.ids)


def test_step_ema_observes_the_interval_between_collects(model_and_params,
                                                         np_rng):
    b = _batcher(model_and_params)
    try:
        real = b._decode

        def decode(params, cache, ids, *rest):
            if isinstance(ids, _SlowIds):
                ids = ids.ids
            next_ids, logits, cache = real(params, cache, ids, *rest)
            return _SlowIds(next_ids, 0.004), logits, cache

        b._decode = decode
        observed = []
        observe = b.step_ema.observe

        def spy(seconds):
            observed.append((seconds, time.monotonic()))
            observe(seconds)

        b.step_ema.observe = spy
        out = b.generate(np_rng.integers(1, VOCAB, size=4), max_new_tokens=25)
        assert len(out) == 25 and len(observed) == 24
        assert _launches(b)["ahead"] == 23
        # intervals between collects tile the time from the first collect to
        # the last; launch-to-read of steps that were in flight across two
        # passes would count every pass twice
        tiled = sum(s for s, _ in observed[1:])
        wall = observed[-1][1] - observed[0][1]
        assert 0.75 * wall < tiled < 1.25 * wall
    finally:
        b.close()


def test_one_decode_executable_where_a_prefill_returns_committed_arrays(
        model_and_params, np_rng):
    """The flash prefill holds a shard_map, and what such an executable
    returns is committed: the pool it hands on, and so the ids of every step
    after it. The pool is committed from the start, so ids put from the host
    and ids a step returned stay one signature (on the chip an uncommitted
    pool cost gen-docs-batch a second copy of the decode step in set-up)."""
    b = _batcher(model_and_params)
    try:
        assert all(leaf.committed
                   for leaf in jax.tree_util.tree_leaves(b.cache))
        prefill = b._prefill

        def committing(*args):
            logits, cache = prefill(*args)
            dev = next(iter(logits.devices()))
            return jax.device_put(logits, dev), jax.device_put(cache, dev)

        b._prefill = committing
        hs = [b.submit(np_rng.integers(1, VOCAB, size=6),     # one bucket
                       max_new_tokens=7, seed=i) for i in range(3)]
        assert [len(h.result(timeout_s=60)) for h in hs] == [7, 7, 7]
        launches = _launches(b)
        assert launches["ahead"] and launches["drained"]
        assert b._decode._cache_size() == 1
        assert prefill.__wrapped__._cache_size() == 1
    finally:
        b.close()
