"""The generation path accounts for its own time (ISSUE 23): the decode
loop's closed phase accounting, a request's server-side timeline and spans,
the broker hops, the fine latency ladder, and the loop's profiler regions;
and the engine's sink and source threads keep the same books (ISSUE 41):
phases that sum to each thread's wall time, CPU beside wall, a frame's wait
in the sink's queue by kind of frame, back-pressure where it happens.
Tiny model, CPU; every test carries a time limit of its own.
"""

import functools
import glob
import json
import os
import queue
import re
import signal
import threading
import time

import numpy as np
import pytest

import jax

from analytics_zoo_tpu.common import telemetry as tm
from analytics_zoo_tpu.models.transformer import TransformerLM
from analytics_zoo_tpu.serving import ServingConfig, start_broker
from analytics_zoo_tpu.serving import generation as gen
from analytics_zoo_tpu.serving.generation import (ContinuousBatcher,
                                                  GenerationClient,
                                                  GenerationEngine)

pytestmark = pytest.mark.generation

#: what the benchmark's trace reader takes for one of the program's spans
SPAN_PATTERN = re.compile(r"^[a-z_]+(\.[a-z_]+)+$")


def time_limit(seconds):
    """Fail the test, rather than hang the suite, after ``seconds``."""
    def wrap(fn):
        @functools.wraps(fn)
        def limited(*args, **kwargs):
            def expired(_signum, _frame):
                raise TimeoutError(f"{fn.__name__} ran over {seconds} s")
            previous = signal.signal(signal.SIGALRM, expired)
            signal.setitimer(signal.ITIMER_REAL, seconds)
            try:
                return fn(*args, **kwargs)
            finally:
                signal.setitimer(signal.ITIMER_REAL, 0)
                signal.signal(signal.SIGALRM, previous)
        return limited
    return wrap


@pytest.fixture(scope="module")
def model_and_params():
    m = TransformerLM(vocab=64, hidden_size=32, n_block=2, n_head=2,
                      seq_len=64)
    params, _ = m.build(jax.random.PRNGKey(0))
    return m, params


def _batcher(model_and_params, **kw):
    m, params = model_and_params
    return ContinuousBatcher(m, params, n_slots=2, page_size=4,
                             max_seq_len=32, **kw)


def _hist(name, key=""):
    sample = tm.snapshot().get(name, {}).get("samples", {}).get(key)
    return (sample["sum"], sample["count"]) if sample else (0.0, 0)


def _hist_all(name):
    samples = tm.snapshot().get(name, {}).get("samples", {})
    return (sum(s["sum"] for s in samples.values()),
            sum(s["count"] for s in samples.values()))


# ------------------------------------------------- closed phase accounting

@pytest.mark.parametrize("mode", [
    {}, {"prefill_chunk_tokens": 8}, {"spec_k": 2}],
    ids=["plain", "chunked", "spec"])
@time_limit(120)
def test_loop_phases_sum_to_the_loop_threads_wall_time(model_and_params,
                                                       mode):
    """Prefills, decode steps, emits and idle waits of one loop thread, from
    its start to its end: the exclusive phases add up to the wall time that
    passed, and the process-wide counter moved by this batcher's seconds."""
    fam = tm.snapshot()["zoo_gen_loop_seconds_total"]["samples"]
    b = _batcher(model_and_params, autostart=False, **mode)
    t0 = time.perf_counter()
    b.start()
    try:
        handles = [b.submit(list(range(1, 9 + i)), max_new_tokens=8, seed=i)
                   for i in range(5)]
        for h in handles:
            assert len(h.result(timeout_s=100)) == 8
        time.sleep(0.25)                # some idle passes too
    finally:
        b.close()
    wall = time.perf_counter() - t0
    assert not b._loop_thread.is_alive()
    phases = b.stats()["loop_seconds"]
    assert set(phases) == set(gen.LOOP_PHASES)
    assert sum(phases.values()) == pytest.approx(wall, rel=0.02)
    for name in ("admit", "prefill_host", "prefill_wait", "decode_host",
                 "decode_wait", "emit", "idle", "other"):
        assert phases[name] > 0, name
    assert phases["idle"] >= 0.2 and phases["swap"] == 0
    after = tm.snapshot()["zoo_gen_loop_seconds_total"]["samples"]
    for name, seconds in phases.items():
        # other batchers of this process may be feeding the family too
        assert after[name] - fam.get(name, 0.0) >= seconds - 1e-4


@time_limit(60)
def test_a_swap_is_a_phase_and_a_foreign_thread_is_not_clocked(
        model_and_params):
    m, params = model_and_params
    b = _batcher(model_and_params)
    try:
        b.generate(list(range(1, 8)), max_new_tokens=3)
        b.swap_params(params, version="v2")
        deadline = time.monotonic() + 30
        while b.swaps == 0 and time.monotonic() < deadline:
            time.sleep(0.01)
        assert b.swaps == 1 and b.stats()["loop_seconds"]["swap"] > 0
        # this thread is not the loop: its phases are no-ops and leave the
        # loop's stack alone (close() fails streams from the caller's thread)
        with b._clock.phase("emit"):
            assert b._clock._stack in ([], ["idle"])
    finally:
        b.close()


# ------------------------------------------------- a request's own timeline

@time_limit(120)
def test_ttft_is_queue_wait_plus_prefill_and_one_trace(model_and_params):
    """Per request the two legs add up to the TTFT, in the first frame's
    meta and in the histograms, and queue, prefill (and the engine's stream
    span) are children of the caller's span under one trace id."""
    before = {n: _hist_all(n) for n in (
        "zoo_gen_ttft_seconds", "zoo_gen_queue_wait_seconds",
        "zoo_gen_prefill_seconds")}
    b = _batcher(model_and_params)
    try:
        b.generate(list(range(1, 12)), max_new_tokens=2)     # compiled
        metas = []
        with tm.span("test.gen.caller") as parent:
            ctx = parent.wire_context()
            # three requests, two slots: the third waits in the backlog
            handles = [b.submit(list(range(1, 12)), max_new_tokens=12,
                                seed=i, ctx=ctx) for i in range(3)]
            for h in handles:
                frames = list(h.frames(timeout_s=100))
                metas.append((frames[0][2], frames[-1][2]))
    finally:
        b.close()
    for first, final in metas:
        assert first["ttft_s"] * 1e3 == pytest.approx(
            first["queue_wait_ms"] + first["prefill_wait_ms"], abs=1.0)
        line = final["timeline_s"]
        assert line["queue"] + line["prefill"] == pytest.approx(
            first["ttft_s"], abs=1e-3)
        assert line["total"] >= line["queue"] + line["prefill"] \
            + line["decode"]
    # the third request waited for a slot: for a decode run, not a moment.
    # It left the backlog after one of the two before it had ended, so its
    # wait is that stream's whole run less the instant between their submits
    # (held against the other streams' own clocks, not against a ratio of
    # two waits, which a busy machine decides)
    runs = [final["timeline_s"]["queue"] + final["timeline_s"]["prefill"]
            + final["timeline_s"]["decode"] for _, final in metas[:2]]
    assert metas[2][1]["timeline_s"]["queue"] > 0.5 * min(runs)
    ttft, queue, prefill = (
        tuple(np.subtract(_hist_all(n), before[n])) for n in (
            "zoo_gen_ttft_seconds", "zoo_gen_queue_wait_seconds",
            "zoo_gen_prefill_seconds"))
    assert ttft[1] == queue[1] == prefill[1] == 4
    assert ttft[0] == pytest.approx(queue[0] + prefill[0], abs=1e-3)
    spans = tm.spans(trace_id=parent.trace_id)
    by_name = {}
    for s in spans:
        by_name.setdefault(s.name, []).append(s)
    assert len(by_name["serving.gen.queue"]) == 3
    assert len(by_name["serving.gen.prefill"]) == 3
    for s in by_name["serving.gen.queue"] + by_name["serving.gen.prefill"]:
        assert s.parent_id == parent.span_id
    # a queue span ends where its request's prefill span starts
    for q in by_name["serving.gen.queue"]:
        (p,) = [p for p in by_name["serving.gen.prefill"]
                if p.tags["uri"] == q.tags["uri"]]
        assert q.start_wall + q.duration_s == pytest.approx(
            p.start_wall, abs=0.05)


@time_limit(120)
def test_loop_passes_record_no_spans_and_keep_request_traces(
        model_and_params):
    """Loop phases are regions, not spans: a thousand passes add nothing to
    the span recorder, whose eviction is by whole trace at 8,192 spans, so a
    request's trace from before them is still there."""
    b = _batcher(model_and_params, autostart=False)
    try:
        b.start()
        with tm.span("test.gen.caller") as parent:
            b.submit(list(range(1, 9)), max_new_tokens=4,
                     ctx=parent.wire_context()).result(timeout_s=100)
        b.close()                       # the loop's thread has ended
        kept = {s.name for s in tm.spans(trace_id=parent.trace_id)}
        assert {"serving.gen.queue", "serving.gen.prefill"} <= kept
        n_spans = len(tm.spans())
        seconds = sum(b._clock.seconds.values())
        b._clock.begin()                # this thread drives the passes now
        for _ in range(1000):
            b._wake.set()               # an idle pass that does not wait
            b._loop_pass()
            b._clock.close_pass()
        assert sum(b._clock.seconds.values()) > seconds
        assert b._clock.seconds["idle"] > 0
        assert len(tm.spans()) == n_spans
        assert not [s for s in tm.spans()
                    if s.name.startswith("serving.gen.loop")]
        assert {s.name for s in tm.spans(trace_id=parent.trace_id)} == kept
    finally:
        b.close()


# ------------------------------------------------------- the broker's hops

@time_limit(180)
def test_ingress_once_a_request_and_egress_once_a_frame(model_and_params,
                                                        capsys):
    m, params = model_and_params
    broker = start_broker()
    engine = GenerationEngine(m, params, config=ServingConfig(
        queue_port=broker.port, gen_slots=2, gen_page_size=4,
        gen_max_seq_len=32)).start()
    client = GenerationClient(port=broker.port)
    try:
        ingress0 = _hist("zoo_gen_ingress_seconds")
        egress0 = _hist("zoo_gen_egress_seconds")
        queued0 = {k: _hist("zoo_gen_egress_queued_seconds", k)
                   for k in ("first", "next", "final")}
        n_new = (3, 5, 4)
        uris = [client.submit(list(range(1, 10)), max_new_tokens=n, seed=i)
                for i, n in enumerate(n_new)]
        for uri, n in zip(uris, n_new):
            assert sum(c.size for c in client.stream(uri, timeout_s=120)) == n
        # an old client's payload carries no stamp: served, not observed
        client._conn.call("XADD", gen.GEN_STREAM, {
            "uri": "old-client", "prompt": np.arange(1, 6, dtype=np.int32),
            "max_new_tokens": 2})
        assert sum(c.size for c in client.stream("old-client",
                                                 timeout_s=120)) == 2
        # a malformed request is a stream of one frame, final and seq 0
        client._conn.call("XADD", gen.GEN_STREAM, {
            "uri": "one-frame", "prompt": np.arange(1, 6, dtype=np.int32),
            "max_new_tokens": "abc"})
        with pytest.raises(RuntimeError, match="malformed request"):
            list(client.stream("one-frame", timeout_s=120))
        ingress = np.subtract(_hist("zoo_gen_ingress_seconds"), ingress0)
        assert ingress[1] == len(uris)
        # a frame a token (plain decode) and a final frame a request; the
        # client may read the last frame before the sink has timed its XADD
        frames = sum(n_new) + len(uris) + 2 + 1 + 1
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline:
            egress = np.subtract(_hist("zoo_gen_egress_seconds"), egress0)
            if egress[1] >= frames:
                break
            time.sleep(0.01)
        assert egress[1] == frames
        assert 0 <= ingress[0] < 5 * len(uris) and 0 < egress[0] < 60
        # the wait in the sink's queue is observed with it, once a frame and
        # by kind: a first and a final frame a stream, a one-frame stream a
        # first alone, and the wait is a part of the whole
        queued = {k: np.subtract(_hist("zoo_gen_egress_queued_seconds", k),
                                 queued0[k]) for k in queued0}
        assert sum(q[1] for q in queued.values()) == frames
        assert queued["first"][1] == len(uris) + 2
        assert queued["final"][1] == len(uris) + 1
        assert 0 < sum(q[0] for q in queued.values()) < egress[0]
        sink = engine.stats()["sink"]
        assert sink["frames"] == frames and sink["emit_blocked_s"] == 0
        assert sink["seconds"]["xadd"] > 0 and sink["seconds"]["ack"] > 0
        assert engine.stats()["loop_seconds"]["decode_wait"] > 0
        # one request, one trace: the client's send span parents the
        # server's queue, prefill and stream spans, and the egress of its
        # first and of its final frame (no other frame's)
        (send,) = [sp for sp in tm.spans(name="serving.gen.send")
                   if sp.tags.get("uri") == uris[0]]
        legs = [sp for sp in tm.spans(trace_id=send.trace_id)
                if sp.name.startswith("serving.gen.") and sp is not send]
        assert sorted(sp.name for sp in legs) == [
            "serving.gen.egress", "serving.gen.egress", "serving.gen.prefill",
            "serving.gen.queue", "serving.gen.stream"]
        assert all(sp.parent_id == send.span_id for sp in legs)
        egress_legs = {sp.tags["frame"]: sp for sp in legs
                       if sp.name == "serving.gen.egress"}
        assert set(egress_legs) == {"first", "final"}
        for sp in egress_legs.values():
            assert sp.tags["uri"] == uris[0]
            assert 0 <= sp.tags["queued_s"] <= sp.duration_s
        # the source republishes stats() to the gen:stats: hash once a
        # second, and `cli info` prints the loop's accounting from it
        from analytics_zoo_tpu.serving.cli import main as cli_main

        deadline = time.monotonic() + 10
        while time.monotonic() < deadline:
            capsys.readouterr()
            assert cli_main(["info", "--port", str(broker.port)]) == 0
            shown = json.loads(capsys.readouterr().out).get("generation", {})
            # a snapshot taken mid-stream holds the first steps only
            if (shown.get("loop_seconds", {}).get("decode_wait", 0) > 0
                    and shown.get("steps", 0) >= max(n_new) - 1
                    and shown["sink"]["queue_depth"] == 0):
                break
            time.sleep(0.2)
        assert set(shown["loop_seconds"]) == set(gen.LOOP_PHASES)
        assert set(shown["sink"]["seconds"]) == set(gen.SINK_PHASES)
        assert shown["sink"]["queue_depth"] == 0
        assert set(shown["param_bytes"]) == {"float32"}
        assert shown["steps"] >= max(n_new) - 1
    finally:
        client.close()
        engine.stop()
        broker.shutdown()


# ------------------------------------------- the sink's and source's books

def _engine(model_and_params, broker, **kw):
    m, params = model_and_params
    return GenerationEngine(m, params, config=ServingConfig(
        queue_port=broker.port, gen_slots=2, gen_page_size=4,
        gen_max_seq_len=32, **kw))


def _stamped(clock):
    """``{"t0", "t1"}``: the instants ``clock``'s thread began and last
    closed a pass (its end, once the thread has ended)."""
    marks = {}
    begin, close_pass = clock.begin, clock.close_pass

    def stamped_begin():
        marks["t0"] = time.perf_counter()
        begin()

    def stamped_close_pass():
        close_pass()
        marks["t1"] = time.perf_counter()

    clock.begin, clock.close_pass = stamped_begin, stamped_close_pass
    return marks


@time_limit(180)
def test_sink_and_source_phases_sum_to_their_threads_wall_time(
        model_and_params):
    """Over a served run, from each thread's start to its end: the exclusive
    phases add up to the wall time that passed, every phase that had work
    holds some, and the thread's CPU seconds by phase stay under its wall
    seconds (a wait burns next to none)."""
    def family(name):
        return dict(tm.snapshot().get(name, {}).get("samples", {}))

    before = {n: family(n) for n in (
        "zoo_gen_sink_seconds_total", "zoo_gen_source_seconds_total",
        "zoo_gen_cpu_seconds_total")}
    broker = start_broker()
    engine = _engine(model_and_params, broker)
    marks = {"sink": _stamped(engine._sink_clock),
             "source": _stamped(engine._source_clock)}
    # every pass reads the thread's CPU clock, where serving reads it on
    # one in seventeen: the books are then exact, not an estimate
    for clock in (engine._sink_clock, engine._source_clock,
                  engine.batcher._clock):
        clock.CPU_EVERY = 1
    engine.start()
    client = GenerationClient(port=broker.port)
    try:
        uris = [client.submit(list(range(1, 10)), max_new_tokens=6, seed=i)
                for i in range(4)]
        client.cancel("nobody")         # an entry that is acknowledged only
        for uri in uris:
            assert sum(c.size for c in client.stream(uri, timeout_s=120)) == 6
        time.sleep(1.2)                 # idle turns, and a second stats tick
    finally:
        client.close()
        engine.stop()
        broker.shutdown()
    assert not any(t.is_alive() for t in engine._threads)
    clocks = {"sink": (engine._sink_clock, gen.SINK_PHASES),
              "source": (engine._source_clock, gen.SOURCE_PHASES)}
    for thread, (clock, phases) in clocks.items():
        wall = marks[thread]["t1"] - marks[thread]["t0"]
        assert set(clock.seconds) == set(phases)
        assert wall > 1.2
        assert sum(clock.seconds.values()) == pytest.approx(wall, rel=0.02)
        assert all(v > 0 for v in clock.seconds.values()), clock.seconds
        after = family(f"zoo_gen_{thread}_seconds_total")
        cpu = family("zoo_gen_cpu_seconds_total")
        for name, seconds in clock.seconds.items():
            # other engines of this process may be feeding the families too
            moved = after[name] - before[
                f"zoo_gen_{thread}_seconds_total"].get(name, 0.0)
            assert moved >= seconds - 1e-4
            burnt = cpu[f"{thread},{name}"] - before[
                "zoo_gen_cpu_seconds_total"].get(f"{thread},{name}", 0.0)
            assert 0 <= burnt <= moved + 0.01, (thread, name)
    assert engine.stats()["sink"]["seconds"]["idle"] >= 1.0
    assert engine._source_clock.seconds["poll"] >= 1.0
    # the loop's clock feeds the same CPU family under its own thread
    assert family("zoo_gen_cpu_seconds_total")["loop,decode_host"] > 0


@time_limit(30)
def test_the_cpu_clock_is_read_on_one_pass_in_seventeen_and_counted_so(
        monkeypatch):
    """``time.thread_time`` is a system call (5.7 us where the benchmark
    runs), so a clock reads it on one pass in ``CPU_EVERY`` and counts what
    it read that many times: an estimate of the thread's CPU seconds at a
    seventeenth of the cost."""
    reads = []
    thread_time = time.thread_time
    monkeypatch.setattr(time, "thread_time",
                        lambda: reads.append(1) or thread_time())
    fam = tm.counter("zoo_test_clock_seconds_total", "test",
                     labels=("phase",))
    clock = gen._LoopClock(fam, ("busy", "other"), "test.clock.", "test")
    every = clock.CPU_EVERY
    assert every == 17
    cpu = gen._GEN_CPU_SECONDS.labels(thread="test", phase="busy")
    clock.begin()
    burnt = 0.0
    for _ in range(2 * every):
        with clock.phase("busy"):
            c0 = thread_time()
            while thread_time() - c0 < 0.002:
                pass
            burnt += thread_time() - c0
        clock.close_pass()
    # begin; passes 0 and 17: a pair a phase and one at each end of the
    # pass; and the start of pass 34
    assert len(reads) == 1 + 3 + 4 + 1
    assert cpu.value() == pytest.approx(burnt, rel=0.25)
    assert clock.seconds["busy"] >= burnt - 1e-3


class _SlowXadd:
    """The sink's connection with a round trip of frames (``XADDM``) that
    takes ``delay_s``."""

    def __init__(self, conn, delay_s):
        self._conn, self._delay_s = conn, delay_s

    def call(self, verb, *args):
        if verb == "XADDM":
            time.sleep(self._delay_s)
        return self._conn.call(verb, *args)

    def close(self):
        self._conn.close()


@time_limit(180)
def test_a_slow_sink_blocks_emit_and_the_loop_holds_that_time(
        model_and_params):
    """Back-pressure where it happens: with a queue of two frames and a
    round trip of 50 ms the decode loop's ``emit`` stands blocked on the full
    queue; the counter, ``stats()["sink"]`` and the loop's own ``emit`` phase
    all say so."""
    broker = start_broker()
    engine = _engine(model_and_params, broker)
    engine._sink_q = queue.Queue(maxsize=2)
    connect = engine._connect
    engine._connect = lambda tag: (_SlowXadd(connect(tag), 0.05)
                                   if tag == "gen.sink" else connect(tag))
    blocked0 = tm.snapshot()["zoo_gen_emit_blocked_seconds_total"][
        "samples"].get("", 0.0)
    engine.start()
    client = GenerationClient(port=broker.port)
    try:
        assert engine.stats()["sink"]["queue_depth"] == 0
        uri = client.submit(list(range(1, 10)), max_new_tokens=16)
        depth, n = 0, 0
        for chunk in client.stream(uri, timeout_s=120):
            n += chunk.size
            depth = max(depth, engine.stats()["sink"]["queue_depth"],
                        int(tm.snapshot()["zoo_gen_sink_queue_depth"][
                            "samples"][""]))
        assert n == 16 and depth == 2
        stats = engine.stats()
        blocked = stats["sink"]["emit_blocked_s"]
        # 18 frames, two at most a round trip of 50 ms, through a queue of
        # two: the loop stood blocked for most of the sink's 0.45 s
        assert blocked > 0.1
        assert tm.snapshot()["zoo_gen_emit_blocked_seconds_total"][
            "samples"][""] - blocked0 == pytest.approx(blocked, abs=1e-3)
        assert stats["loop_seconds"]["emit"] >= blocked
        # a round trip carries what the queue held, two frames at most here
        assert stats["sink"]["seconds"]["xadd"] >= 9 * 0.05
    finally:
        client.close()
        engine.stop()
        broker.shutdown()


@time_limit(60)
def test_a_thousand_next_frames_record_no_span(model_and_params):
    """A stream's first and final frame close its trace; the frames between
    them feed counters alone, or a long stream would push every request's
    trace out of the recorder."""
    class Broker:
        port = 1                        # nothing connects to it

        def call(self, *_args):
            return None

    engine = _engine(model_and_params, Broker)
    try:
        engine._sink_clock.begin()      # this thread is the sink now
        n_spans = len(tm.spans())
        queued0 = _hist("zoo_gen_egress_queued_seconds", "next")
        ctx = {"t": "ab" * 16, "s": "cd" * 8}
        for seq in range(1, 1001):
            engine._write(Broker(), [("chunk", "1-0", "u", seq, [seq], {},
                                      False, ctx, time.perf_counter())])
            engine._sink_clock.close_pass()
        assert len(tm.spans()) == n_spans
        assert engine.stats()["sink"]["frames"] == 1000
        assert np.subtract(_hist("zoo_gen_egress_queued_seconds", "next"),
                           queued0)[1] == 1000
        engine._write(Broker(), [("chunk", "1-0", "u", 1001, [], {
            "outcome": "ok", "n_tokens": 1000}, True, ctx,
            time.perf_counter())])
        (span,) = tm.spans()[n_spans:]
        assert span.name == "serving.gen.egress"
        assert span.tags["frame"] == "final" and span.trace_id == "ab" * 16
        assert engine.served_streams == 1
    finally:
        engine.batcher.close()


@time_limit(60)
def test_a_pass_of_the_sink_is_one_round_trip_for_all_that_waits(
        model_and_params):
    """The frames that wait when the sink turns to its queue (a decode step
    hands over one a live stream) reach the broker in ONE ``XADDM``, in the
    order they were handed over; the finals among them and a cancel's
    acknowledgement share one ``XACK`` after it; each frame is observed
    once, with the round trip it rode as its egress."""
    calls = []

    class Conn:
        def call(self, verb, *args):
            calls.append((verb,) + args)

    class Broker:
        port = 1                        # nothing connects to it

    engine = _engine(model_and_params, Broker)
    try:
        engine._sink_clock.begin()      # this thread is the sink now
        egress0 = _hist("zoo_gen_egress_seconds")
        now = time.perf_counter()
        engine._write(Conn(), [
            ("chunk", "1-0", "a", 0, [5], {}, False, None, now),
            ("chunk", "2-0", "b", 7, [6, 7], {}, False, None, now),
            ("ack", "9-0", "c", 0, [], {}, False, None, None),
            ("chunk", "1-0", "a", 1, [8], {}, False, None, now),
            ("chunk", "2-0", "b", 8, [], {"outcome": "ok", "n_tokens": 9,
                                         "left": "out"}, True, None, now)])
        engine._sink_clock.close_pass()
        (xaddm, frames), (xack, stream, group, ids) = calls
        assert (xaddm, xack) == ("XADDM", "XACK")
        assert [(key, f["seq"], f["tokens"], f["final"])
                for key, f in frames] == [
            ("genout:a", 0, [5], False), ("genout:b", 7, [6, 7], False),
            ("genout:a", 1, [8], False), ("genout:b", 8, [], True)]
        assert frames[3][1]["n_tokens"] == 9 and "left" not in frames[3][1]
        # plain ints: the frames ride the wire as JSON
        json.dumps(frames)
        assert (stream, group, ids) == (engine.stream, engine.group,
                                        ["9-0", "2-0"])
        assert np.subtract(_hist("zoo_gen_egress_seconds"), egress0)[1] == 4
        assert engine.stats()["sink"]["frames"] == 4
        assert engine.served_streams == 1
        # nothing but acknowledgements: no empty XADDM
        del calls[:]
        engine._write(Conn(), [("ack", "3-0", "d", 0, [], {}, False, None,
                                None)])
        assert [c[0] for c in calls] == ["XACK"]
    finally:
        engine.batcher.close()


# -------------------------------------------------------------- the ladder

def _quantile(snapshot, q):
    """Prometheus' ``histogram_quantile``: linear inside the bucket."""
    rank = q * snapshot["count"]
    lo, below = 0.0, 0
    for le, cum in snapshot["buckets"]:
        if cum >= rank:
            return lo + (le - lo) * (rank - below) / max(cum - below, 1)
        lo, below = le, cum
    return lo


@time_limit(30)
def test_the_fine_ladder_reads_a_p95_within_15_percent():
    ladder = tm.LATENCY_LADDER
    assert 45 <= len(ladder) <= 60
    assert ladder[0] == 0.0005 and ladder[-1] == 60.0
    ratios = np.divide(ladder[1:], ladder[:-1])
    assert 1.2 < ratios.min() and ratios.max() < 1.3
    rng = np.random.default_rng(0)
    # a decode step of 64 ms, one gap in ten a step plus a 70 ms prefill
    gaps = np.where(rng.random(20000) < 0.1, 0.134, 0.064) \
        * rng.lognormal(0.0, 0.03, 20000)
    fine, coarse = tm.Histogram(ladder), tm.Histogram(
        (.001, .0025, .005, .01, .025, .05, .1, .25, .5, 1.0, 2.5))
    for g in gaps:
        fine.observe(g)
        coarse.observe(g)
    for q in (0.5, 0.95):
        true = float(np.quantile(gaps, q))
        assert _quantile(fine.snapshot(), q) == pytest.approx(true, rel=0.15)
    # the eleven-edge ladder this one replaced could not
    assert abs(_quantile(coarse.snapshot(), 0.95) / float(
        np.quantile(gaps, 0.95)) - 1) > 0.15
    for fam in (gen._GEN_TTFT, gen._GEN_ITL, gen._GEN_QUEUE_WAIT,
                gen._GEN_PREFILL, gen._GEN_INGRESS, gen._GEN_EGRESS,
                gen._GEN_EGRESS_QUEUED):
        assert fam.buckets == ladder


# ------------------------------------------------- regions in the profiler

@time_limit(30)
def test_a_region_times_into_its_child_and_records_no_span():
    child = tm.counter("zoo_test_region_seconds_total", "test",
                       labels=("phase",)).labels(phase="a")
    n_spans = len(tm.spans())
    with tm.region("test.region.a", child) as r:
        time.sleep(0.01)
    assert 0.009 < r.seconds < 0.5
    assert child.value() == pytest.approx(r.seconds)
    with pytest.raises(KeyError):
        with tm.region("test.region.a", child):
            raise KeyError("passes through, and is timed")
    assert child.value() > r.seconds
    assert len(tm.spans()) == n_spans and tm.current_span() is None


@time_limit(30)
def test_a_region_with_a_cpu_child_adds_the_cpu_the_thread_burnt():
    fam = tm.counter("zoo_test_region_seconds_total", "test",
                     labels=("phase",))
    wall, cpu = fam.labels(phase="wall"), fam.labels(phase="cpu")
    with tm.region("test.region.busy", wall, cpu_child=cpu) as r:
        c0 = time.thread_time()
        while time.thread_time() - c0 < 0.05:
            pass
    assert 0.05 <= r.cpu_seconds < 0.07 and r.cpu_seconds <= r.seconds + 1e-3
    assert cpu.value() == pytest.approx(r.cpu_seconds)
    assert wall.value() == pytest.approx(r.seconds)
    # a wait costs wall time and next to no CPU
    with tm.region("test.region.asleep", wall, cpu_child=cpu,
                   annotate=False) as r:
        time.sleep(0.05)
    assert r.seconds >= 0.05 and r.cpu_seconds < 0.005
    assert cpu.value() < 0.075
    # without a child the thread's clock is not read
    with tm.region("test.region.plain", wall) as r:
        pass
    assert r.cpu_seconds == 0.0


@time_limit(180)
def test_a_cpu_profile_holds_the_working_phases_of_sink_and_source_only(
        model_and_params, tmp_path):
    """``sink.xadd`` (and ``build``, ``ack``) on the sink thread's line and
    ``source.admit`` on the source's, flat and side by side; the threads'
    waits (``sink.idle``, ``source.poll``, the ``stats`` tick) nowhere: a
    reader that names a device's idle gap after the span that began last
    must go on reading ``serving.gen.loop.idle`` while there is no request."""
    broker = start_broker()
    engine = _engine(model_and_params, broker).start()
    client = GenerationClient(port=broker.port)
    try:
        client.generate(list(range(1, 9)), max_new_tokens=3)     # compiled
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
        try:
            for i in range(3):
                client.generate(list(range(1, 9 + i)), max_new_tokens=6,
                                seed=i)
            time.sleep(1.2)             # idle turns, polls, a stats tick
        finally:
            jax.profiler.stop_trace()
    finally:
        client.close()
        engine.stop()
        broker.shutdown()
    (path,) = glob.glob(os.path.join(str(tmp_path), "plugins", "profile",
                                     "*", "*.xplane.pb"))
    data = jax.profiler.ProfileData.from_file(path)
    lines = []                          # both may be called "python"
    for plane in data.planes:
        for line in plane.lines:
            events = sorted((e.start_ns, e.start_ns + e.duration_ns, e.name)
                            for e in line.events
                            if e.name.startswith(("serving.gen.sink.",
                                                  "serving.gen.source.")))
            if events:
                lines.append(events)
    assert len(lines) == 2
    by_thread = {events[0][2].split(".")[2]: events for events in lines}
    names = {t: {name for _, _, name in events}
             for t, events in by_thread.items()}
    assert names["sink"] == {"serving.gen.sink." + p
                             for p in ("build", "xadd", "ack")}
    assert names["source"] == {"serving.gen.source.admit"}
    assert all(SPAN_PATTERN.match(n) for t in names for n in names[t])
    for events in by_thread.values():
        for (_, end, a), (start, _, b_name) in zip(events, events[1:]):
            assert end <= start, (a, b_name)
    assert engine._sink_clock.seconds["idle"] > 1.0
    assert engine._source_clock.seconds["poll"] > 1.0
    assert engine._source_clock.seconds["stats"] > 0


@time_limit(180)
def test_a_cpu_profile_holds_the_loop_regions_on_the_loop_threads_line(
        model_and_params, tmp_path):
    """The regions enter the annotation a span enters, so a profile holds
    them on the loop thread's line (whatever the profiler calls it: it names
    a line after the thread's OS name), side by side and named like spans."""
    b = _batcher(model_and_params)
    try:
        b.generate(list(range(1, 9)), max_new_tokens=3)      # compiled
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
        try:
            for i in range(3):
                b.generate(list(range(1, 9 + i)), max_new_tokens=6, seed=i)
            time.sleep(0.12)            # idle passes
        finally:
            jax.profiler.stop_trace()
    finally:
        b.close()
    (path,) = glob.glob(os.path.join(str(tmp_path), "plugins", "profile",
                                     "*", "*.xplane.pb"))
    data = jax.profiler.ProfileData.from_file(path)
    holding = []
    for plane in data.planes:
        for line in plane.lines:
            events = [(e.start_ns, e.start_ns + e.duration_ns, e.name)
                      for e in line.events
                      if e.name.startswith("serving.gen.loop.")]
            if events:
                holding.append((plane.name, line.name, sorted(events)))
    assert len(holding) == 1, [(p, l) for p, l, _ in holding]
    plane_name, _line_name, events = holding[0]
    assert plane_name == "/host:CPU"
    names = {name for _, _, name in events}
    assert all(SPAN_PATTERN.match(n) for n in names)
    assert {"serving.gen.loop." + p for p in (
        "admit", "prefill_host", "prefill_wait", "decode_host",
        "decode_wait", "emit", "idle")} <= names
    assert "serving.gen.loop.other" not in names        # computed, no region
    for (_, end, a), (start, _, b_name) in zip(events, events[1:]):
        assert end <= start, (a, b_name)


def test_the_timed_tests_do_limit_themselves():
    @time_limit(0.05)
    def hangs():
        threading.Event().wait(5)

    with pytest.raises(TimeoutError):
        hangs()
