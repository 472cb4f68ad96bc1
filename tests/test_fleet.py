"""Serving replica-fleet tests (ISSUE 9): health-routed multi-replica
dispatch, zero-loss failover (claim-transfer requeue + dedup-on-uri),
graceful drain / rolling restart, the /healthz vs /readyz split, ordered
stack shutdown, and the broker verbs the fleet rides on (XTRANSFER, HSETNX,
size-triggered AOF compaction).

Replicas here are thread-mode ClusterServing engines over a stub
device-bound model (predict sleeps, GIL released — the routing tier is what
is under test, not XLA); the subprocess replica path is exercised by the
stack entrypoint (`tests/test_serving_stack.py`).
"""

import json
import threading
import time
import urllib.request
import urllib.error

import os

import numpy as np
import pytest

from analytics_zoo_tpu.inference import InferenceModel
from analytics_zoo_tpu.observability import events as _ev
from analytics_zoo_tpu.observability import export_trace
from analytics_zoo_tpu.observability import recorder as _flight
from analytics_zoo_tpu.serving import (ClusterServing, FleetSupervisor,
                                       InputQueue, OutputQueue, ReplicaRouter,
                                       ServingConfig, start_broker)
from analytics_zoo_tpu.serving.broker import _Store
from analytics_zoo_tpu.serving.fleet import REPLICA_STREAM_PREFIX

pytestmark = [pytest.mark.serving, pytest.mark.fleet]


def _install_flight(tmp_path):
    """Kill drills run under an installed flight recorder (like the real
    stack): the failover event must auto-cut a complete dump. The chaos
    suite points ZOO_FLIGHT_DIR at a shared dir it verifies afterwards."""
    return _flight.install(
        dump_dir=os.environ.get("ZOO_FLIGHT_DIR") or str(tmp_path))


def _await_flight_dump(rec, timeout_s=10.0):
    """Wait for the auto-cut dump a kill drill must produce, then load it
    — missing or unloadable (torn) artifacts fail the drill."""
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline and rec.last_dump_path is None:
        time.sleep(0.05)
    assert rec.last_dump_path is not None, "kill drill auto-cut no dump"
    with open(rec.last_dump_path) as f:
        dump = json.load(f)
    assert dump["schema"] == "zoo-flight-v1"
    for section in ("records", "events", "metrics", "chaos"):
        assert section in dump
    return dump


class StubModel(InferenceModel):
    """Device-bound stand-in: predict blocks for a fixed service time (like
    an XLA execute on the replica's own chip) and returns per-row sums so a
    response is attributable to exactly one request."""

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


def _submit_and_check(broker, uris_values, timeout_s=30.0):
    """Query every uri and assert its answer is the submitted row sum."""
    oq = OutputQueue(port=broker.port)
    try:
        for uri, want in uris_values:
            got = oq.query(uri, timeout_s=timeout_s)
            assert abs(float(np.asarray(got).ravel()[0]) - want) < 1e-4
    finally:
        oq.close()


# ---------------------------------------------------------------------------
# router policies (no supervisor needed: static liveness)
# ---------------------------------------------------------------------------

def test_router_round_robin_dispatch():
    broker = start_broker()
    try:
        cfg = _cfg(broker)
        engines = [
            ClusterServing(StubModel(), config=cfg, group=f"fleet-{rid}",
                           stream=REPLICA_STREAM_PREFIX + rid,
                           dedup_results=True).start()
            for rid in ("a", "b")]
        router = ReplicaRouter(cfg, ("a", "b"),
                               policy="round_robin").start()
        try:
            iq = InputQueue(port=broker.port)
            subs = []
            for i in range(12):
                u = iq.enqueue(None, input=np.full((4,), float(i),
                                                   np.float32))
                subs.append((u, 4.0 * i))
            _submit_and_check(broker, subs)
            iq.close()
            stats = router.stats()["replicas"]
            # strict alternation over a 2-replica roster
            assert stats["a"]["dispatched"] == 6
            assert stats["b"]["dispatched"] == 6
        finally:
            router.stop()
            for e in engines:
                e.stop()
    finally:
        broker.shutdown()


def test_router_least_pending_prefers_unloaded_replica():
    broker = start_broker()
    try:
        cfg = _cfg(broker)
        slow = ClusterServing(StubModel(0.25), config=cfg,
                              group="fleet-slow",
                              stream=REPLICA_STREAM_PREFIX + "slow",
                              dedup_results=True).start()
        fast = ClusterServing(StubModel(0.002), config=cfg,
                              group="fleet-fast",
                              stream=REPLICA_STREAM_PREFIX + "fast",
                              dedup_results=True).start()
        router = ReplicaRouter(cfg, ("slow", "fast"),
                               policy="least_pending").start()
        try:
            iq = InputQueue(port=broker.port)
            subs = []
            for i in range(30):
                u = iq.enqueue(None, input=np.full((4,), float(i),
                                                   np.float32))
                subs.append((u, 4.0 * i))
                time.sleep(0.01)   # let depth signal develop
            _submit_and_check(broker, subs)
            iq.close()
            stats = router.stats()["replicas"]
            # the slow replica's queue backs up; depth-aware routing must
            # send the clear majority to the fast one
            assert stats["fast"]["dispatched"] > stats["slow"]["dispatched"]
        finally:
            router.stop()
            slow.stop()
            fast.stop()
    finally:
        broker.shutdown()


# ---------------------------------------------------------------------------
# failover drills
# ---------------------------------------------------------------------------

@pytest.mark.chaos
def test_kill_one_of_four_midburst_zero_loss(zoo_ctx, tmp_path):
    """The headline drill: 4 replicas under a burst, one hard-killed
    mid-run. Every submitted uri gets exactly one successful response (the
    dead replica's claimed work is claim-transferred back and re-served;
    duplicate answers are dropped broker-side), the fleet re-converges to 4
    eligible replicas, and the failover auto-cuts a complete, loadable
    flight dump (the black-box postmortem artifact)."""
    from analytics_zoo_tpu.serving.broker import _DUP_DROPPED

    broker = start_broker()
    fleet = None
    rec = _install_flight(tmp_path)
    try:
        cfg = _cfg(broker, replicas=4)
        fleet = FleetSupervisor(
            cfg, model_factory=lambda: StubModel(0.03)).start()
        assert fleet.wait_eligible(4, timeout_s=10)
        iq = InputQueue(port=broker.port)
        subs = []
        for i in range(80):
            u = iq.enqueue(None, input=np.full((4,), float(i), np.float32))
            subs.append((u, 4.0 * i))
            if i == 25:
                fleet.kill_replica("r1")
        iq.close()
        dups_before = _DUP_DROPPED.value()
        _submit_and_check(broker, subs)
        # response-count accounting: exactly one response per uri — after
        # the client consumed each result, no duplicate may have recreated
        # the hash (HSETNX tombstones; any late answer was counted+dropped)
        from analytics_zoo_tpu.serving.client import _Conn

        c = _Conn("127.0.0.1", broker.port)
        for uri, _ in subs[:10]:
            assert c.call("HGET", "result:" + uri, 0) is None
        c.close()
        assert fleet.requeued > 0, "kill drill requeued nothing"
        assert fleet.respawns == 1
        assert fleet.wait_eligible(4, timeout_s=10), fleet.router.stats()
        assert _DUP_DROPPED.value() >= dups_before  # counted, never served
        dump = _await_flight_dump(rec)
        assert dump["trigger"] == "failover"
        assert any(e["kind"] == "fleet.failover" for e in dump["events"])
        # the failover is ONE decision event for the killed replica, and the
        # trace it names exports whole (the fleet.failover span is in it)
        (failover,) = [e for e in _ev.events(kind="fleet.failover")
                       if e.fields.get("replica") == "r1"]
        trace = export_trace(failover.trace_id)
        assert any(s["name"] == "fleet.failover"
                   for s in trace["traceEvents"])
    finally:
        _flight.uninstall()
        if fleet is not None:
            fleet.stop(drain_s=2.0)
        broker.shutdown()


@pytest.mark.chaos
def test_kill_during_drain_requeues_without_respawn(zoo_ctx, tmp_path):
    """A replica killed while draining: its unfinished claimed work is still
    requeued (zero loss), but the supervisor honors the drain decision and
    does NOT bring it back. The kill still auto-cuts a loadable flight
    dump."""
    broker = start_broker()
    fleet = None
    rec = _install_flight(tmp_path)
    try:
        cfg = _cfg(broker, replicas=2)
        fleet = FleetSupervisor(
            cfg, model_factory=lambda: StubModel(0.15)).start()
        assert fleet.wait_eligible(2, timeout_s=10)
        iq = InputQueue(port=broker.port)
        subs = []
        for i in range(24):
            u = iq.enqueue(None, input=np.full((4,), float(i), np.float32))
            subs.append((u, 4.0 * i))
        time.sleep(0.1)           # let r0 claim work
        fleet.drain("r0")
        time.sleep(0.05)          # drain command lands mid-batch
        fleet.kill_replica("r0")
        _submit_and_check(broker, subs)
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline and "r0" in fleet.router.replica_ids():
            time.sleep(0.05)
        assert "r0" not in fleet.router.replica_ids()
        assert fleet.respawns == 0          # drained replicas stay down
        assert fleet.router.eligible_ids() == ["r1"]
        iq.close()
        _await_flight_dump(rec)
    finally:
        _flight.uninstall()
        if fleet is not None:
            fleet.stop(drain_s=2.0)
        broker.shutdown()


def test_breaker_evict_then_half_open_readmit(zoo_ctx):
    """Out-of-band eviction (breaker trip) takes a healthy-but-suspect
    replica out of rotation without killing it; after the reset timeout the
    router sends ONE probe request, and only when the replica demonstrably
    SERVES it (cumulative served advances) does the breaker close and
    traffic resume."""
    broker = start_broker()
    fleet = None
    try:
        cfg = _cfg(broker, replicas=2)
        fleet = FleetSupervisor(
            cfg, model_factory=lambda: StubModel(0.005)).start()
        assert fleet.wait_eligible(2, timeout_s=10)
        fleet.router.evict("r0")
        slot_breaker = fleet.router._slots["r0"].breaker
        assert slot_breaker.state == "open"
        assert fleet.router.eligible_ids() == ["r1"]
        # traffic while evicted all lands on r1
        iq = InputQueue(port=broker.port)
        subs = [(iq.enqueue(None, input=np.full((4,), float(i), np.float32)),
                 4.0 * i) for i in range(8)]
        _submit_and_check(broker, subs)
        assert fleet.router.stats()["replicas"]["r0"]["dispatched"] == 0
        time.sleep(cfg.breaker_reset_timeout_s + 0.1)   # open -> half-open
        # next dispatches include the probe; r0 serves it; breaker closes
        deadline = time.monotonic() + 10
        n = 100
        while time.monotonic() < deadline and slot_breaker.state != "closed":
            u = iq.enqueue(None, input=np.full((4,), float(n), np.float32))
            _submit_and_check(broker, [(u, 4.0 * n)])
            n += 1
            time.sleep(0.05)
        assert slot_breaker.state == "closed"
        assert fleet.router.stats()["replicas"]["r0"]["dispatched"] > 0
        assert sorted(fleet.router.eligible_ids()) == ["r0", "r1"]
        iq.close()
    finally:
        if fleet is not None:
            fleet.stop(drain_s=2.0)
        broker.shutdown()


def test_drain_via_control_hash_and_rolling_restart(zoo_ctx):
    """`cli drain` semantics (the control hash path) + a rolling restart:
    the drained replica reaches state `drained` and leaves the rotation;
    restart brings a fresh incarnation back to eligible; submissions during
    the roll all answer."""
    broker = start_broker()
    fleet = None
    try:
        cfg = _cfg(broker, replicas=2)
        fleet = FleetSupervisor(
            cfg, model_factory=lambda: StubModel(0.01)).start()
        assert fleet.wait_eligible(2, timeout_s=10)
        stop_flag = threading.Event()
        subs, lock = [], threading.Lock()

        def load():
            iq = InputQueue(port=broker.port)
            i = 0
            while not stop_flag.is_set():
                u = iq.enqueue(None, input=np.full((4,), float(i),
                                                   np.float32))
                with lock:
                    subs.append((u, 4.0 * i))
                i += 1
                time.sleep(0.01)
            iq.close()

        t = threading.Thread(target=load, daemon=True)
        t.start()
        try:
            assert fleet.restart_replica("r0", timeout_s=20)
        finally:
            stop_flag.set()
            t.join(timeout=5)
        assert fleet.wait_eligible(2, timeout_s=10)
        with lock:
            snapshot = list(subs)
        assert snapshot, "load generator produced nothing"
        _submit_and_check(broker, snapshot)      # zero downtime, zero loss
        # fresh incarnation: generation bumped
        assert fleet._handles["r0"].generation == 2
    finally:
        if fleet is not None:
            fleet.stop(drain_s=2.0)
        broker.shutdown()


def test_replica_spawn_race_predispatched_requests_not_lost():
    """Regression (review): slots are born eligible, so the router forwards
    to fleet:req:<rid> (and XACKs the origin entry) before a slow-starting
    replica registers its consumer group — the model-load/compile window on
    spawn, and the post-XTRANSFER respawn window. Tail ('$') group semantics
    silently skipped those entries; fleet groups must replay from '0'."""
    broker = start_broker()
    try:
        cfg = _cfg(broker)
        router = ReplicaRouter(cfg, ("r0",), policy="round_robin").start()
        engine = None
        try:
            iq = InputQueue(port=broker.port)
            subs = [(iq.enqueue(None, input=np.full((4,), float(i),
                                                    np.float32)), 4.0 * i)
                    for i in range(6)]
            deadline = time.monotonic() + 5
            while time.monotonic() < deadline and router.routed < 6:
                time.sleep(0.02)
            assert router.routed == 6, "router did not forward the burst"
            # the replica comes up only AFTER everything was dispatched
            engine = ClusterServing(StubModel(), config=cfg, group="fleet-r0",
                                    stream=REPLICA_STREAM_PREFIX + "r0",
                                    replica_id="r0",
                                    dedup_results=True).start()
            _submit_and_check(broker, subs, timeout_s=15)
            iq.close()
        finally:
            router.stop()
            if engine is not None:
                engine.stop()
    finally:
        broker.shutdown()


# ---------------------------------------------------------------------------
# generation engine behind the router (smoke)
# ---------------------------------------------------------------------------

@pytest.mark.generation
def test_generation_engine_behind_router_smoke(zoo_ctx):
    """The router is stream-agnostic: generation replicas consume routed
    per-replica streams while clients keep the plain GenerationClient API;
    streams come back intact from whichever replica served them."""
    import jax

    from analytics_zoo_tpu.models.transformer import TransformerLM
    from analytics_zoo_tpu.serving.generation import (GEN_STREAM,
                                                      GenerationClient,
                                                      GenerationEngine)

    m = TransformerLM(vocab=64, hidden_size=32, n_block=2, n_head=2,
                      seq_len=64)
    params, _ = m.build(jax.random.PRNGKey(0))
    broker = start_broker()
    try:
        cfg = ServingConfig(queue_port=broker.port, gen_slots=2,
                            gen_page_size=4, gen_max_seq_len=32,
                            graph_checks="off")
        engines = [
            GenerationEngine(m, params, config=cfg, group=f"genfleet-{rid}",
                             stream="fleet:gen:" + rid).start()
            for rid in ("g0", "g1")]
        router = ReplicaRouter(cfg, ("g0", "g1"), stream=GEN_STREAM,
                               prefix="fleet:gen:", group="gen-router",
                               policy="round_robin", name="genfleet").start()
        try:
            client = GenerationClient(port=broker.port)
            outs = []
            for seed in range(4):
                toks = client.generate([1, 2, 3], max_new_tokens=5,
                                       seed=seed, timeout_s=60)
                outs.append(toks)
                assert len(toks) == 5
            client.close()
            stats = router.stats()["replicas"]
            assert stats["g0"]["dispatched"] == 2
            assert stats["g1"]["dispatched"] == 2
        finally:
            router.stop()
            for e in engines:
                e.stop()
    finally:
        broker.shutdown()


# ---------------------------------------------------------------------------
# readiness split + ordered shutdown
# ---------------------------------------------------------------------------

def test_readyz_vs_healthz_split():
    """Liveness stays process-level; readiness reflects eligible replicas /
    draining and answers 503 + Retry-After BEFORE requests are accepted."""
    from analytics_zoo_tpu.serving.http_frontend import FrontEndApp

    state = {"ready": True, "detail": {"eligible": ["r0"]}}
    app = FrontEndApp(ServingConfig(), port=0, model=StubModel(),
                      ready_fn=lambda: (state["ready"], state["detail"]))
    app.start()
    url = f"http://127.0.0.1:{app.port}"
    try:
        assert json.loads(urllib.request.urlopen(
            url + "/readyz", timeout=5).read())["status"] == "ready"
        assert urllib.request.urlopen(
            url + "/healthz", timeout=5).status == 200
        state["ready"] = False        # fleet lost its last eligible replica
        state["detail"] = {"eligible": []}
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(url + "/readyz", timeout=5)
        assert ei.value.code == 503
        assert ei.value.headers["Retry-After"] is not None
        assert json.loads(ei.value.read())["reason"] == "no eligible replica"
        # liveness is NOT affected: the process is healthy, just unready
        assert urllib.request.urlopen(
            url + "/healthz", timeout=5).status == 200
        state["ready"] = True
        # draining beats everything: readiness 503 AND new work shed
        app.stop_accepting()
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(url + "/readyz", timeout=5)
        assert json.loads(ei.value.read())["reason"] == "draining"
        body = json.dumps({"instances": [{"x": [0.0] * 4}]}).encode()
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(urllib.request.Request(
                url + "/predict", body,
                {"Content-Type": "application/json"}), timeout=5)
        assert ei.value.code == 503
    finally:
        app.stop()


def test_stack_shutdown_ordering_inflight_request_survives(zoo_ctx):
    """Regression for the shutdown-ordering bug-class: a /predict accepted
    BEFORE SIGTERM must complete through the ordered drain (frontend stops
    accepting -> engine drains + writes result -> broker still up for the
    fetch -> frontend exits). Construction-order stops strand it."""
    from analytics_zoo_tpu.serving.http_frontend import FrontEndApp
    from analytics_zoo_tpu.serving.stack import shutdown_stack

    broker = start_broker()
    cfg = ServingConfig(queue_port=broker.port, batch_size=4,
                        batch_timeout_ms=2)
    serving = ClusterServing(StubModel(0.5), config=cfg).start()
    app = FrontEndApp(cfg, port=0).start()
    url = f"http://127.0.0.1:{app.port}"
    result = {}

    def inflight():
        body = json.dumps({"instances": [{"x": [1.0] * 4}]}).encode()
        try:
            r = urllib.request.urlopen(urllib.request.Request(
                url + "/predict", body,
                {"Content-Type": "application/json"}), timeout=30)
            result["status"] = r.status
            result["body"] = json.loads(r.read())
        except Exception as e:   # pragma: no cover - the failure mode
            result["error"] = repr(e)

    t = threading.Thread(target=inflight, daemon=True)
    t.start()
    time.sleep(0.25)             # request is claimed, predict mid-sleep
    shutdown_stack(app, serving, broker, drain_s=10.0)
    t.join(timeout=15)
    assert not t.is_alive()
    assert result.get("status") == 200, result
    assert abs(result["body"]["predictions"][0][0] - 4.0) < 1e-4


# ---------------------------------------------------------------------------
# broker verbs the fleet rides on
# ---------------------------------------------------------------------------

def test_store_xtransfer_moves_pending_and_undelivered_with_counts():
    s = _Store()
    for i in range(6):
        s.xadd("src", {"uri": f"u{i}", "v": i})
    # consumer claims 2 (now pending/unacked), 4 stay undelivered
    got = s.xreadgroup("src", "g", 2, 0)
    assert len(got) == 2
    res = s.xtransfer("src", "g", "dst")
    assert res["moved"] == 6
    # delivery counts: the claimed two were handed out once, the rest never
    assert sorted(n for _, n in res["entries"]) == [0, 0, 0, 0, 1, 1]
    assert s.slen("src") == 0
    moved = s.xreadgroup("dst", "g2", 10, 0)
    assert [p["uri"] for _, p in moved] == [f"u{i}" for i in range(6)]
    # dict payloads carry their delivery count for observability
    assert [p["__deliveries__"] for _, p in moved] == [1, 1, 0, 0, 0, 0]
    # acked entries do NOT transfer
    s2 = _Store()
    s2.xadd("a", {"uri": "x"})
    got = s2.xreadgroup("a", "g", 1, 0)
    s2.xack("a", "g", [got[0][0]])
    assert s2.xtransfer("a", "g", "b")["moved"] == 0
    with pytest.raises(ValueError):
        s2.xtransfer("a", "g", "a")


def test_store_group_slen_counts_owed_not_history():
    """Regression (review): the least_pending depth signal must be work
    OWED (undelivered + unacked), not the raw stream length — the stream
    retains delivered-and-acked entries until maxlen-trim, so counting it
    wholesale reports cumulative dispatch history and floods a freshly
    respawned (stream-reset) replica with all traffic."""
    s = _Store()
    for i in range(6):
        s.xadd("st", {"uri": f"u{i}"})
    assert s.slen("st", "g") == 6        # nothing delivered: all owed
    got = s.xreadgroup("st", "g", 4, 0)
    s.xack("st", "g", [i for i, _ in got[:3]])
    # 2 undelivered + 1 delivered-but-unacked; the 3 acked are history
    assert s.slen("st", "g") == 3
    s.xack("st", "g", [got[3][0]])
    assert s.slen("st", "g") == 2
    assert s.slen("st") == 6             # raw (group-less) depth unchanged


def test_store_group_slen_counts_crash_redelivery_once(tmp_path):
    """Entries queued for crash redelivery are also still pending; the owed
    count takes the union, not the sum."""
    aof = str(tmp_path / "owed.aof")
    s = _Store(aof_path=aof)
    for i in range(3):
        s.xadd("st", {"uri": f"u{i}"})
    s.xreadgroup("st", "g", 2, 0)        # 2 claimed, never acked
    s2 = _Store(aof_path=aof)            # broker crash restart
    assert s2.slen("st", "g") == 3       # 1 undelivered + 2 owed, no double


def test_store_hsetnx_first_write_wins_even_after_hdel():
    s = _Store()
    assert s.hsetnx("result:u1", {"value": 1}) == 1
    assert s.hsetnx("result:u1", {"value": 2}) == 0      # live duplicate
    assert s.hget("result:u1") == {"value": 1}
    s.hdel("result:u1")
    # the client consumed it; a late duplicate must NOT recreate the hash
    assert s.hsetnx("result:u1", {"value": 3}) == 0
    assert s.hget("result:u1") is None
    # plain HSET keeps overwrite semantics (heartbeats, control hashes)
    s.hset("fleet:hb:r0", {"ts": 1})
    s.hset("fleet:hb:r0", {"ts": 2})
    assert s.hget("fleet:hb:r0") == {"ts": 2}


def test_store_hsetnx_tombstones_survive_aof_replay(tmp_path):
    aof = str(tmp_path / "fleet.aof")
    s = _Store(aof_path=aof)
    assert s.hsetnx("result:u1", {"value": 1}) == 1
    s.hdel("result:u1")
    s2 = _Store(aof_path=aof)         # broker restart
    assert s2.hsetnx("result:u1", {"value": 9}) == 0


def test_aof_size_triggered_compaction(tmp_path):
    import os

    aof = str(tmp_path / "grow.aof")
    s = _Store(aof_path=aof, aof_rewrite_min_bytes=8 * 1024)
    # churn: add + consume + ack + delete — live state stays tiny, the log
    # would grow without bound
    for i in range(200):
        s.xadd("st", {"uri": f"u{i}", "pad": "x" * 64})
        got = s.xreadgroup("st", "g", 1, 0)
        s.xack("st", "g", [got[0][0]])
    assert s.compactions > 0
    assert os.path.getsize(aof) < 64 * 1024
    # compacted log still replays to correct state
    s.hset("k", {"v": 1})
    s2 = _Store(aof_path=aof, aof_rewrite_min_bytes=8 * 1024)
    assert s2.hget("k") == {"v": 1}
    assert s2.slen("st") == s.slen("st")


def test_aof_size_trigger_has_growth_floor(tmp_path):
    """Live state BIGGER than the size threshold must not make every
    subsequent op pay a full synchronous rewrite: the trigger is
    max(min_bytes, 2x post-rewrite snapshot size), Redis
    auto-aof-rewrite-percentage style."""
    aof = str(tmp_path / "big.aof")
    s = _Store(aof_path=aof, aof_rewrite_min_bytes=2048)
    s.hset("big", {"pad": "x" * 8192})       # snapshot alone > threshold
    base = s.compactions
    for i in range(50):
        s.hset(f"k{i}", {"v": i})            # small ops on top
    # the log must roughly DOUBLE past the snapshot before compacting again
    assert s.compactions - base <= 2, (
        f"{s.compactions - base} rewrites for 50 small ops — compaction "
        f"thrash (every op paying a full rewrite)")


def test_ctl_hash_drain_then_kill_not_respawned(zoo_ctx):
    """Finding-class: a drain commanded OUT-OF-BAND (`cli drain` writes the
    control hash; FleetSupervisor.drain() never runs) must still suppress
    the respawn when the replica dies mid-drain."""
    from analytics_zoo_tpu.serving.client import _Conn
    from analytics_zoo_tpu.serving.engine import FLEET_CTL_PREFIX

    broker = start_broker()
    fleet = None
    try:
        cfg = _cfg(broker, replicas=2)
        fleet = FleetSupervisor(
            cfg, model_factory=lambda: StubModel(0.15)).start()
        assert fleet.wait_eligible(2, timeout_s=10)
        iq = InputQueue(port=broker.port)
        subs = [(iq.enqueue(None, input=np.full((4,), float(i), np.float32)),
                 4.0 * i) for i in range(16)]
        time.sleep(0.1)
        # the cli path: HSET the control hash directly, no supervisor call
        c = _Conn("127.0.0.1", broker.port)
        c.call("HSET", FLEET_CTL_PREFIX + "r0", {"state": "drain"})
        c.close()
        deadline = time.monotonic() + 5
        while time.monotonic() < deadline and \
                not fleet._handles["r0"].drain_requested:
            time.sleep(0.05)
        assert fleet._handles["r0"].drain_requested
        fleet.kill_replica("r0")
        _submit_and_check(broker, subs)         # still zero loss
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline and \
                "r0" in fleet.router.replica_ids():
            time.sleep(0.05)
        assert fleet.respawns == 0
        assert "r0" not in fleet.router.replica_ids()
        iq.close()
    finally:
        if fleet is not None:
            fleet.stop(drain_s=2.0)
        broker.shutdown()


def test_broker_info_carries_compactions(tmp_path):
    from analytics_zoo_tpu.serving.client import _Conn

    broker = start_broker(aof_path=str(tmp_path / "b.aof"))
    try:
        broker.store.aof_rewrite_min_bytes = 2048
        c = _Conn("127.0.0.1", broker.port)
        for i in range(100):
            c.call("HSET", "k", {"pad": "y" * 64})
        info = c.call("INFO")
        assert info["aof_compactions"] > 0
        c.close()
    finally:
        broker.shutdown()


def test_supervisor_stats_folds_heartbeat_served_for_process_replicas():
    """Regression (review): process-mode replicas have no in-process engine
    (handle.engine is None); their served counters ride the fleet:hb:<rid>
    heartbeat hashes the supervisor already polls onto the router slots —
    stats()/metrics.json must fold those in instead of reporting 0."""
    from analytics_zoo_tpu.serving.fleet import _ReplicaHandle

    sup = FleetSupervisor(ServingConfig(), replica_ids=["r0", "r1"],
                          spawn="process", demo=True)
    sup._handles["r0"] = _ReplicaHandle("r0", "process")
    sup._handles["r1"] = _ReplicaHandle("r1", "process")
    sup.router.set_liveness("r0", True, state="up", served=7, inflight=0)
    sup.router.set_liveness("r1", True, state="up", served=5, inflight=0)
    assert sup.stats()["served"] == 12


# ---------------------------------------------------------------------------
# config plumbing
# ---------------------------------------------------------------------------

def test_config_yaml_fleet_section(tmp_path):
    p = tmp_path / "fleet.yaml"
    p.write_text("""
model:
  path: /models/m
fleet:
  replicas: 4
  policy: round_robin
  spawn: process
  heartbeat_s: 0.25
  failover_timeout_s: 1.5
""")
    cfg = ServingConfig.from_yaml(str(p))
    assert cfg.replicas == 4
    assert cfg.fleet_policy == "round_robin"
    assert cfg.fleet_spawn == "process"
    assert cfg.fleet_heartbeat_s == 0.25
    assert cfg.fleet_failover_timeout_s == 1.5

    bad = tmp_path / "bad.yaml"
    bad.write_text("fleet:\n  policy: fastest\n")
    with pytest.raises(ValueError, match="policy"):
        ServingConfig.from_yaml(str(bad))
