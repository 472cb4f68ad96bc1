"""Adaptive serving under overload (ISSUE 13): priority + deadline QoS
threaded end to end (wire header, payload schema, HTTP headers, client
kwargs), deadline-aware shedding with computed Retry-After at every tier
(frontend admission, ReplicaRouter, MicroBatcher, ContinuousBatcher incl.
bulk-slot preemption with pages intact, engine source gate), deadline
survival across AOF replay and XTRANSFER requeue, the RetryPolicy
Retry-After backoff floor, and queue-driven autoscaling (1→N→1, zero-loss
by construction via graceful drain + requeue).
"""

import json
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from analytics_zoo_tpu.common.resilience import RetryPolicy
from analytics_zoo_tpu.inference import InferenceModel
from analytics_zoo_tpu.observability import events as _ev
from analytics_zoo_tpu.observability import export_trace
from analytics_zoo_tpu.serving import (ClusterServing, FleetSupervisor,
                                       FrontEndApp, InputQueue, OutputQueue,
                                       ReplicaRouter, ServingConfig,
                                       ShedError, start_broker)
from analytics_zoo_tpu.serving import qos
from analytics_zoo_tpu.serving.batching import MicroBatcher
from analytics_zoo_tpu.serving.broker import _Store
from analytics_zoo_tpu.serving.client import _Conn
from analytics_zoo_tpu.serving.fleet import REPLICA_STREAM_PREFIX
from analytics_zoo_tpu.serving.schema import (DEADLINE_KEY, PRIORITY_KEY,
                                              payload_deadline,
                                              payload_priority)
from analytics_zoo_tpu.serving.wire import (received_qos, recv_msg, send_msg,
                                            set_wire_qos)

pytestmark = [pytest.mark.serving, pytest.mark.overload]


class StubModel(InferenceModel):
    """Device-bound stand-in: predict blocks for a fixed service time and
    returns per-row sums so every answer is attributable to its request."""

    def __init__(self, service_time_s: float = 0.0):
        super().__init__()
        self._service = service_time_s

    def predict(self, inputs, batch_first=True):
        if self._service:
            time.sleep(self._service)
        x = np.asarray(inputs)
        return x.sum(axis=tuple(range(1, x.ndim)), keepdims=True)


def _cfg(broker, **kw):
    base = dict(queue_port=broker.port, batch_size=4, batch_timeout_ms=2,
                fleet_heartbeat_s=0.1, fleet_failover_timeout_s=0.8,
                fleet_spawn_grace_s=10.0, breaker_reset_timeout_s=0.3)
    base.update(kw)
    return ServingConfig(**base)


# ---------------------------------------------------------------------------
# qos primitives
# ---------------------------------------------------------------------------

def test_priority_deadline_ordering_and_normalization():
    now = time.time()
    # critical before normal before bulk; earlier deadline first in-class;
    # deadline-less last in-class; seq breaks ties FIFO
    keys = [qos.order_key("bulk", None, 1),
            qos.order_key("critical", now + 9, 2),
            qos.order_key("normal", now + 1, 3),
            qos.order_key("normal", None, 4),
            qos.order_key("critical", now + 1, 5),
            qos.order_key(None, now + 1, 6)]
    ranked = sorted(range(len(keys)), key=lambda i: keys[i])
    assert ranked == [4, 1, 2, 5, 3, 0]
    assert qos.normalize_priority("CRITICAL ") == "critical"
    assert qos.normalize_priority("no-such-class") == "normal"
    assert qos.normalize_priority(None) == "normal"
    assert qos.normalize_deadline(-5) is None
    assert qos.normalize_deadline(True) is None
    assert qos.normalize_deadline(now) == now


def test_cannot_meet_and_retry_after():
    now = time.time()
    assert qos.cannot_meet(now - 0.1, 0.0, 0.0)          # expired
    assert not qos.cannot_meet(None, 1e9, 1e9)           # no deadline
    assert qos.cannot_meet(now + 0.5, 1.0, 0.1)          # wait overruns
    assert not qos.cannot_meet(now + 5.0, 1.0, 0.1)
    # honest Retry-After: depth x service / concurrency, floored
    assert qos.retry_after_s(10, 0.2, 2) == pytest.approx(1.0)
    assert qos.retry_after_s(0, 0.0) == qos.MIN_RETRY_AFTER_S
    err = qos.ShedError("x", retry_after_s=0.0, reason="deadline")
    assert err.retry_after_s == qos.MIN_RETRY_AFTER_S
    # payload round trip preserves the computed backoff
    back = qos.shed_error_from_payload(
        qos.shed_payload("busy", 2.5, reason="deadline"), "u1")
    assert isinstance(back, ShedError)
    assert back.retry_after_s == pytest.approx(2.5)
    assert back.reason == "deadline"


def test_retry_policy_honors_retry_after_floor():
    sleeps = []
    policy = RetryPolicy(max_attempts=5, base_delay_s=0.001,
                         max_delay_s=0.004, jitter=0.1, seed=3,
                         retryable=(ShedError,), sleep=sleeps.append)
    calls = {"n": 0}

    def fn():
        calls["n"] += 1
        if calls["n"] <= 2:
            raise ShedError("overloaded", retry_after_s=0.5)
        return "ok"

    assert policy.call(fn) == "ok"
    # the server's hint is the FLOOR (never retried earlier), jitter only up
    assert len(sleeps) == 2
    for d in sleeps:
        assert 0.5 <= d <= 0.5 * 1.1 + 1e-9
    # without a hint the ordinary (much smaller) backoff applies
    sleeps.clear()
    calls["n"] = 0

    def fn2():
        calls["n"] += 1
        if calls["n"] <= 2:
            raise ShedError("overloaded", retry_after_s=0.0)
        return "ok"

    assert policy.call(fn2) == "ok"
    assert all(d < 0.1 for d in sleeps)


# ---------------------------------------------------------------------------
# wire / schema / broker: QoS fields ride the frame header and the payload
# ---------------------------------------------------------------------------

def test_wire_header_qos_roundtrip_and_old_sender():
    import socket

    a, b = socket.socketpair()
    try:
        dl = time.time() + 2.5
        set_wire_qos("critical", dl)
        try:
            send_msg(a, {"x": np.ones(3, np.float32)})   # binary frame
        finally:
            set_wire_qos(None, None)
        recv_msg(b)
        assert received_qos() == ("critical", pytest.approx(dl))
        # old/untagged sender: header fields absent, receiver tolerates
        send_msg(a, {"x": np.ones(3, np.float32)})
        recv_msg(b)
        assert received_qos() == (None, None)
        # JSON control frames never carry the header pair
        send_msg(a, ["PING"])
        recv_msg(b)
        assert received_qos() == (None, None)
    finally:
        a.close()
        b.close()


def test_payload_qos_tolerant_readers():
    dl = time.time() + 1.0
    assert payload_priority({PRIORITY_KEY: "bulk"}) == "bulk"
    assert payload_priority({PRIORITY_KEY: 17}) == "normal"
    assert payload_priority({"uri": "u"}) == "normal"
    assert payload_priority("not-a-dict") == "normal"
    assert payload_deadline({DEADLINE_KEY: dl}) == dl
    assert payload_deadline({DEADLINE_KEY: "soon"}) is None
    assert payload_deadline({}) is None


def test_enqueue_carries_qos_and_broker_stamps_header_only_senders():
    broker = start_broker()
    try:
        iq = InputQueue(port=broker.port)
        dl = time.time() + 30.0
        iq.enqueue("u-qos", priority="bulk", deadline=dl,
                   input=np.ones(4, np.float32))
        iq.close()
        conn = _Conn("127.0.0.1", broker.port)
        try:
            conn.call("XGROUPCREATE", "serving_stream", "t", "0")
            ((_, payload),) = conn.call("XREADGROUP", "serving_stream",
                                        "t", 10, 200)
            assert payload[PRIORITY_KEY] == "bulk"
            assert payload[DEADLINE_KEY] == pytest.approx(dl)
            # header-only sender (no payload fields): the broker folds the
            # frame header's "p"/"dl" into the stored record, so the QoS
            # survives the stream + AOF even for minimal senders
            set_wire_qos("critical", dl + 1)
            try:
                conn.call("XADD", "bare_stream",
                          {"uri": "u2", "data": {"x": np.ones(2,
                                                             np.float32)}})
            finally:
                set_wire_qos(None, None)
            conn.call("XGROUPCREATE", "bare_stream", "t", "0")
            ((_, p2),) = conn.call("XREADGROUP", "bare_stream", "t", 10, 200)
            assert p2[PRIORITY_KEY] == "critical"
            assert p2[DEADLINE_KEY] == pytest.approx(dl + 1)
        finally:
            conn.close()
    finally:
        broker.shutdown()


def test_deadline_survives_aof_replay(tmp_path):
    aof = str(tmp_path / "broker.aof")
    dl = time.time() + 120.0
    store = _Store(aof_path=aof)
    store.xadd("s", {"uri": "u1", PRIORITY_KEY: "critical",
                     DEADLINE_KEY: dl, "data": {"x": 1}})
    # replay into a fresh store (broker restart): the ORIGINAL deadline
    # must come back bit-exact — a fresh one would let an expired request
    # be served after the restart instead of shed
    store2 = _Store(aof_path=aof)
    store2.xgroupcreate("s", "g", "0")
    ((_, payload),) = store2.xreadgroup("s", "g", 10, 0)
    assert payload[DEADLINE_KEY] == dl
    assert payload[PRIORITY_KEY] == "critical"


def test_deadline_survives_xtransfer_requeue():
    dl = time.time() + 60.0
    store = _Store()
    store.xadd("src", {"uri": "u1", DEADLINE_KEY: dl, PRIORITY_KEY: "bulk"})
    store.xgroupcreate("src", "g", "0")
    claimed = store.xreadgroup("src", "g", 10, 0)
    assert len(claimed) == 1                 # delivered-but-unacked
    res = store.xtransfer("src", "g", "dst")
    assert res["moved"] == 1
    store.xgroupcreate("dst", "g2", "0")
    ((_, payload),) = store.xreadgroup("dst", "g2", 10, 0)
    # the failover requeue must carry the ORIGINAL deadline, not mint one
    assert payload[DEADLINE_KEY] == dl
    assert payload[PRIORITY_KEY] == "bulk"


# ---------------------------------------------------------------------------
# micro-batcher: (priority, deadline) ordering + deadline shedding
# ---------------------------------------------------------------------------

def test_microbatcher_priority_deadline_ordering():
    order = []
    release = threading.Event()
    started = threading.Event()

    def predict(x):
        order.append(float(np.asarray(x).ravel()[0]))
        started.set()
        if len(order) == 1:
            release.wait(10)
        return np.asarray(x)

    mb = MicroBatcher(predict, max_batch=1, max_delay_ms=1.0)
    try:
        s0 = mb.submit_async({"x": np.array([0.0], np.float32)})
        assert started.wait(5)
        # while the batcher is busy, queue bulk FIRST, then critical/normal:
        # eligible work must run critical -> normal -> bulk (FIFO in-class)
        bulk = [mb.submit_async({"x": np.array([10.0 + i], np.float32)},
                                priority="bulk") for i in range(3)]
        crit = mb.submit_async({"x": np.array([1.0], np.float32)},
                               priority="critical")
        norm = mb.submit_async({"x": np.array([2.0], np.float32)},
                               priority="normal",
                               deadline=time.time() + 30)
        release.set()
        for s in [s0, crit, norm] + bulk:
            mb.wait(s, timeout_s=10)
        assert order == [0.0, 1.0, 2.0, 10.0, 11.0, 12.0]
    finally:
        mb.close()


def test_microbatcher_sheds_expired_deadline_with_retry_after():
    mb = MicroBatcher(lambda x: np.asarray(x), max_batch=4, max_delay_ms=1.0)
    try:
        dead = mb.submit_async({"x": np.ones(2, np.float32)},
                               deadline=time.time() - 0.5)
        live = mb.submit_async({"x": np.full(2, 7.0, np.float32)})
        with pytest.raises(ShedError) as ei:
            mb.wait(dead, timeout_s=10)
        assert ei.value.retry_after_s >= qos.MIN_RETRY_AFTER_S
        assert ei.value.reason == "deadline"
        np.testing.assert_allclose(mb.wait(live, timeout_s=10),
                                   np.full(2, 7.0, np.float32))
        assert mb.stats()["shed_records"] == 1
    finally:
        mb.close()


# ---------------------------------------------------------------------------
# HTTP frontend: QoS headers, computed Retry-After, old-client compat
# ---------------------------------------------------------------------------

def _post(port, path="/predict", body=None, headers=None):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}",
        data=json.dumps(body or {"instances": [{"x": [1.0, 2.0]}]}
                        ).encode(),
        headers={"Content-Type": "application/json", **(headers or {})})
    return urllib.request.urlopen(req, timeout=15)


def test_frontend_deadline_shed_computed_retry_after_and_compat():
    app = FrontEndApp(model=lambda x: np.asarray(x).sum(axis=1,
                                                        keepdims=True),
                      port=0, max_batch=4, max_delay_ms=1.0).start()
    try:
        # old client (no QoS headers): served exactly as before
        with _post(app.port) as r:
            assert r.status == 200
            assert json.loads(r.read())["predictions"] == [[3.0]]
        # expired latency budget: shed at ADMISSION (before any body read /
        # enqueue / batch work), 503 + Retry-After, reason = deadline
        with pytest.raises(urllib.error.HTTPError) as ei:
            _post(app.port, headers={"X-Zoo-Priority": "bulk",
                                     "X-Zoo-Deadline-Ms": "-200"})
        assert ei.value.code == 503
        assert int(ei.value.headers["Retry-After"]) >= 1
        body = json.loads(ei.value.read())
        assert body["shed_reason"] == "deadline"
        assert body["retry_after_s"] >= qos.MIN_RETRY_AFTER_S
        assert app.shed_requests == 1
        # a generous budget is admitted and served
        with _post(app.port, headers={"X-Zoo-Priority": "critical",
                                      "X-Zoo-Deadline-Ms": "30000"}) as r:
            assert r.status == 200
    finally:
        app.stop()


def test_frontend_bulk_watermark_keeps_headroom_for_critical():
    release = threading.Event()
    entered = threading.Event()

    def slow_predict(x):
        entered.set()
        release.wait(10)
        return np.asarray(x)

    cfg = ServingConfig(bulk_inflight_fraction=0.5)
    app = FrontEndApp(cfg, model=slow_predict, port=0, max_batch=1,
                      max_delay_ms=1.0, max_inflight=2).start()
    try:
        results = {}

        def bg(name, headers):
            try:
                with _post(app.port, headers=headers) as r:
                    results[name] = r.status
            except urllib.error.HTTPError as e:
                results[name] = e.code

        t1 = threading.Thread(target=bg, args=("first", {}), daemon=True)
        t1.start()
        assert entered.wait(5)      # one inflight; bulk watermark = 1
        # bulk is refused while the watermark is reached...
        with pytest.raises(urllib.error.HTTPError) as ei:
            _post(app.port, headers={"X-Zoo-Priority": "bulk"})
        assert ei.value.code == 503
        assert json.loads(ei.value.read())["shed_reason"] == "admission"
        # ...but critical still has headroom (second inflight slot)
        t2 = threading.Thread(target=bg, args=(
            "critical", {"X-Zoo-Priority": "critical"}), daemon=True)
        t2.start()
        time.sleep(0.2)
        release.set()
        t1.join(10)
        t2.join(10)
        assert results == {"first": 200, "critical": 200}
    finally:
        release.set()
        app.stop()


def test_frontend_queue_mode_relays_engine_shed(zoo_ctx):
    """End to end through the broker: an expired deadline is shed by the
    ENGINE's source gate, the shed record (with computed Retry-After) rides
    the result hash back, the client raises ShedError, and the frontend
    answers 503 + Retry-After with reason=deadline."""
    broker = start_broker()
    job = None
    app = None
    try:
        cfg = ServingConfig(batch_size=4, batch_timeout_ms=2,
                            queue_port=broker.port)
        job = ClusterServing(StubModel(), cfg, group="ov-http").start()
        app = FrontEndApp(cfg, port=0).start()
        with _post(app.port) as r:          # old client path still works
            assert r.status == 200
        with pytest.raises(urllib.error.HTTPError) as ei:
            _post(app.port, headers={"X-Zoo-Deadline-Ms": "-100"})
        assert ei.value.code == 503
        body = json.loads(ei.value.read())
        assert body["shed_reason"] == "deadline"
        assert int(ei.value.headers["Retry-After"]) >= 1
    finally:
        if app is not None:
            app.stop()
        if job is not None:
            job.stop()
        broker.shutdown()


# ---------------------------------------------------------------------------
# engine + router tiers: shed-not-serve for expired work
# ---------------------------------------------------------------------------

def test_engine_sheds_expired_deadline_instead_of_serving(zoo_ctx):
    broker = start_broker()
    try:
        cfg = ServingConfig(batch_size=4, batch_timeout_ms=2,
                            queue_port=broker.port)
        job = ClusterServing(StubModel(), cfg, group="ov-engine").start()
        try:
            iq = InputQueue(port=broker.port)
            oq = OutputQueue(port=broker.port)
            u_live = iq.enqueue(None, input=np.full(4, 2.0, np.float32))
            u_dead = iq.enqueue(None, deadline=time.time() - 1.0,
                                input=np.full(4, 3.0, np.float32))
            got = oq.query(u_live, timeout_s=30)
            assert abs(float(np.asarray(got).ravel()[0]) - 8.0) < 1e-5
            with pytest.raises(ShedError) as ei:
                oq.query(u_dead, timeout_s=30)
            assert ei.value.retry_after_s >= qos.MIN_RETRY_AFTER_S
            iq.close()
            oq.close()
        finally:
            job.stop()
    finally:
        broker.shutdown()


def test_router_sheds_expired_deadline_before_dispatch(zoo_ctx):
    broker = start_broker()
    try:
        cfg = _cfg(broker)
        engine = ClusterServing(StubModel(), config=cfg, group="fleet-a",
                                stream=REPLICA_STREAM_PREFIX + "a",
                                dedup_results=True).start()
        router = ReplicaRouter(cfg, ("a",), policy="round_robin").start()
        try:
            iq = InputQueue(port=broker.port)
            oq = OutputQueue(port=broker.port)
            u_dead = iq.enqueue(None, priority="bulk",
                                deadline=time.time() - 0.5,
                                input=np.ones(4, np.float32))
            u_live = iq.enqueue(None, input=np.full(4, 5.0, np.float32))
            got = oq.query(u_live, timeout_s=30)
            assert abs(float(np.asarray(got).ravel()[0]) - 20.0) < 1e-5
            with pytest.raises(ShedError):
                oq.query(u_dead, timeout_s=30)
            assert router.shed >= 1          # shed at the ROUTING tier
            assert router.stats()["shed"] == router.shed
            iq.close()
            oq.close()
        finally:
            router.stop()
            engine.stop()
    finally:
        broker.shutdown()


# ---------------------------------------------------------------------------
# continuous batcher: ordering, shedding, bulk-slot preemption
# ---------------------------------------------------------------------------

VOCAB, HIDDEN, BLOCKS, HEADS, SEQ = 64, 32, 2, 2, 64


@pytest.fixture(scope="module")
def gen_model():
    import jax

    from analytics_zoo_tpu.models.transformer import TransformerLM

    m = TransformerLM(vocab=VOCAB, hidden_size=HIDDEN, n_block=BLOCKS,
                      n_head=HEADS, seq_len=SEQ)
    params, _ = m.build(jax.random.PRNGKey(0))
    return m, params


@pytest.mark.generation
def test_generation_sheds_expired_deadline(gen_model):
    from analytics_zoo_tpu.serving.generation import ContinuousBatcher

    m, params = gen_model
    b = ContinuousBatcher(m, params, n_slots=2, page_size=4, max_seq_len=32)
    try:
        h_dead = b.submit([1, 2, 3], max_new_tokens=4,
                          deadline=time.time() - 1.0)
        frames = list(h_dead.frames(timeout_s=20))
        assert frames[-1][1] is True
        meta = frames[-1][2]
        assert meta["outcome"] == "shed"
        assert meta["retry_after_s"] >= qos.MIN_RETRY_AFTER_S
        # an undated request on the same batcher is unaffected
        out = b.generate([1, 2, 3], max_new_tokens=4, timeout_s=30)
        assert len(out) == 4
        assert b.requests_finished.get("shed") == 1
    finally:
        b.close()


@pytest.mark.generation
def test_generation_critical_preempts_bulk_with_pages_intact(gen_model):
    """A critical request lands on a FULL batcher: the bulk stream is
    preempted (slot freed, KV pages kept), the critical request decodes to
    completion first, and the bulk stream then resumes producing EXACTLY
    the tokens an uninterrupted run produces — nothing recomputed, nothing
    lost."""
    from analytics_zoo_tpu.serving.generation import ContinuousBatcher

    m, params = gen_model
    prompt_bulk = [5, 6, 7, 8]
    prompt_crit = [9, 10, 11]
    # reference: the same bulk request, uninterrupted, greedy
    ref = ContinuousBatcher(m, params, n_slots=1, page_size=4,
                            max_seq_len=32)
    try:
        want_bulk = ref.generate(prompt_bulk, max_new_tokens=10,
                                 timeout_s=60)
    finally:
        ref.close()

    b = ContinuousBatcher(m, params, n_slots=1, page_size=4, max_seq_len=32)
    try:
        done_order = []
        h_bulk = b.submit(prompt_bulk, max_new_tokens=10, priority="bulk",
                          on_chunk=lambda t, f, m_:
                          done_order.append("bulk") if f else None)
        # let the bulk stream actually start decoding (occupy the only slot)
        deadline = time.monotonic() + 10
        while b.active_slots() == 0 and time.monotonic() < deadline:
            time.sleep(0.01)
        assert b.active_slots() == 1
        h_crit = b.submit(prompt_crit, max_new_tokens=4,
                          priority="critical",
                          on_chunk=lambda t, f, m_:
                          done_order.append("critical") if f else None)
        got_crit = h_crit.result(timeout_s=60)
        got_bulk = h_bulk.result(timeout_s=60)
        assert len(got_crit) == 4
        assert got_bulk == want_bulk          # pages intact across preempt
        assert done_order == ["critical", "bulk"]
        assert b.stats()["preempted_parked"] == 0   # resumed, not stranded
        assert b.pool.free_count() == b.pool.capacity
    finally:
        b.close()


@pytest.mark.generation
def test_generation_client_qos_rides_broker(gen_model, zoo_ctx):
    from analytics_zoo_tpu.serving.generation import (ContinuousBatcher,
                                                      GenerationClient,
                                                      GenerationEngine)

    m, params = gen_model
    broker = start_broker()
    engine = None
    try:
        cfg = ServingConfig(queue_port=broker.port)
        batcher = ContinuousBatcher(m, params, n_slots=2, page_size=4,
                                    max_seq_len=32, autostart=False)
        engine = GenerationEngine(batcher, config=cfg).start()
        gc = GenerationClient(port=broker.port)
        # expired budget -> the decode tier sheds; the client sees ShedError
        # with the engine's computed backoff
        uri = gc.submit([1, 2, 3], max_new_tokens=4, priority="bulk",
                        deadline=time.time() - 1.0)
        with pytest.raises(ShedError) as ei:
            list(gc.stream(uri, timeout_s=30))
        assert ei.value.retry_after_s >= qos.MIN_RETRY_AFTER_S
        # an old-style submit (no QoS) on the same engine still streams
        out = gc.generate([1, 2, 3], max_new_tokens=4, timeout_s=60)
        assert len(out) == 4
        gc.close()
    finally:
        if engine is not None:
            engine.stop()
        broker.shutdown()


# ---------------------------------------------------------------------------
# autoscaling: 1 -> N -> 1 with zero lost requests
# ---------------------------------------------------------------------------

def _drive_fleet(broker, fleet, n_requests, service_check=True,
                 deadline_ms=None, kill_when_scaled=None):
    """Stream n_requests in, then fetch every uri exactly once; returns
    (answered, shed, failed) counts. ``kill_when_scaled`` kills the named
    replica id as soon as it joins the roster (the kill-during-scale-up
    drill)."""
    uris = []
    lock = threading.Lock()

    def submit(idx, step):
        iq = InputQueue(port=broker.port)
        try:
            for i in range(idx, n_requests, step):
                u = iq.enqueue(None, deadline_ms=deadline_ms,
                               input=np.full((4,), float(i), np.float32))
                with lock:
                    uris.append((i, u))
        finally:
            iq.close()

    threads = [threading.Thread(target=submit, args=(i, 3), daemon=True)
               for i in range(3)]
    for t in threads:
        t.start()
    if kill_when_scaled is not None:
        deadline = time.monotonic() + 20
        while time.monotonic() < deadline:
            if kill_when_scaled in fleet.router.replica_ids() and \
                    kill_when_scaled in fleet._handles:
                fleet.kill_replica(kill_when_scaled)
                break
            time.sleep(0.01)
    for t in threads:
        t.join()
    answered = shed = failed = 0
    oq = OutputQueue(port=broker.port)
    try:
        for i, u in sorted(uris):
            try:
                v = oq.query(u, timeout_s=60)
                if service_check and \
                        abs(float(np.asarray(v).ravel()[0]) - 4.0 * i) > 1e-5:
                    failed += 1
                else:
                    answered += 1
            except ShedError:
                shed += 1
            except Exception:
                failed += 1
    finally:
        oq.close()
    return answered, shed, failed


@pytest.mark.fleet
def test_autoscale_up_then_down_zero_loss(zoo_ctx):
    broker = start_broker()
    try:
        cfg = _cfg(broker, replicas=1, autoscale=True, min_replicas=1,
                   max_replicas=3, autoscale_up_depth=2.0,
                   autoscale_sustain_s=0.2, autoscale_idle_s=0.6,
                   autoscale_cooldown_s=0.1)
        fleet = FleetSupervisor(
            cfg, model_factory=lambda: StubModel(0.04))
        fleet.start()
        try:
            assert fleet.wait_eligible(1, timeout_s=15)
            answered, shed, failed = _drive_fleet(broker, fleet, 120)
            assert failed == 0
            assert shed == 0                 # no deadlines -> nothing shed
            assert answered == 120           # zero lost, zero duplicated
            ups = [e for e in fleet.scale_events if e[0] == "up"]
            assert ups, f"never scaled up: {fleet.scale_events}"
            assert len(fleet.router.replica_ids()) >= 2
            # idle: the autoscaler drains back down to min_replicas with
            # zero-loss machinery (drain + straggler XTRANSFER)
            deadline = time.monotonic() + 30
            while time.monotonic() < deadline and \
                    len(fleet._handles) > 1:
                time.sleep(0.05)
            assert len(fleet._handles) == 1, fleet.scale_events
            downs = [e for e in fleet.scale_events if e[0] == "down"]
            assert downs
            # both directions are decision events whose traces export whole
            for kind in ("autoscale.up", "autoscale.down"):
                evs = _ev.events(kind=kind)
                assert evs, kind
                assert all(export_trace(e.trace_id)["traceEvents"]
                           for e in evs), kind
            # the survivors still serve
            iq = InputQueue(port=broker.port)
            oq = OutputQueue(port=broker.port)
            u = iq.enqueue(None, input=np.full((4,), 2.0, np.float32))
            got = oq.query(u, timeout_s=30)
            assert abs(float(np.asarray(got).ravel()[0]) - 8.0) < 1e-5
            iq.close()
            oq.close()
        finally:
            fleet.stop(drain_s=2.0)
    finally:
        broker.shutdown()


@pytest.mark.fleet
@pytest.mark.chaos
def test_autoscale_kill_during_scale_up_zero_loss(zoo_ctx):
    """Chaos drill: the freshly autoscaled replica is hard-killed the
    moment it joins the roster. The supervisor's failover requeues its
    claimed work; every request is still answered exactly once."""
    broker = start_broker()
    try:
        cfg = _cfg(broker, replicas=1, autoscale=True, min_replicas=1,
                   max_replicas=2, autoscale_up_depth=2.0,
                   autoscale_sustain_s=0.2, autoscale_idle_s=30.0,
                   autoscale_cooldown_s=0.1)
        fleet = FleetSupervisor(
            cfg, model_factory=lambda: StubModel(0.04))
        fleet.start()
        try:
            assert fleet.wait_eligible(1, timeout_s=15)
            answered, shed, failed = _drive_fleet(
                broker, fleet, 100, kill_when_scaled="r1")
            assert failed == 0
            assert answered + shed == 100    # nothing lost or duplicated
            assert shed == 0
        finally:
            fleet.stop(drain_s=2.0)
    finally:
        broker.shutdown()


# ---------------------------------------------------------------------------
# config plumbing
# ---------------------------------------------------------------------------

def test_serving_config_yaml_overload_and_autoscale(tmp_path):
    p = tmp_path / "cfg.yaml"
    p.write_text("""
model_path: /m
overload:
  priority: bulk
  bulk_inflight_fraction: 0.25
autoscale:
  enabled: true
  min_replicas: 2
  max_replicas: 6
  up_depth: 12
  sustain_s: 3.5
  idle_s: 9
  cooldown_s: 4.5
""")
    cfg = ServingConfig.from_yaml(str(p))
    assert cfg.default_priority == "bulk"
    assert cfg.bulk_inflight_fraction == 0.25
    assert cfg.autoscale is True
    assert cfg.min_replicas == 2
    assert cfg.max_replicas == 6
    assert cfg.autoscale_up_depth == 12.0
    assert cfg.autoscale_sustain_s == 3.5
    assert cfg.autoscale_idle_s == 9.0
    assert cfg.autoscale_cooldown_s == 4.5

    # `autoscale:` is BOTH a flat field name and the section name: a
    # section with `enabled: false` must not be read as bool(dict)=True
    off = tmp_path / "off.yaml"
    off.write_text("autoscale:\n  enabled: false\n  max_replicas: 8\n")
    cfg_off = ServingConfig.from_yaml(str(off))
    assert cfg_off.autoscale is False
    assert cfg_off.max_replicas == 8

    bad = tmp_path / "bad.yaml"
    bad.write_text("overload:\n  priority: urgent\n")
    with pytest.raises(ValueError):
        ServingConfig.from_yaml(str(bad))
    bad2 = tmp_path / "bad2.yaml"
    bad2.write_text("autoscale:\n  min_replicas: 4\n  max_replicas: 2\n")
    with pytest.raises(ValueError):
        ServingConfig.from_yaml(str(bad2))
