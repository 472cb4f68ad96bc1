"""Cross-host fleet tests (ISSUE 16): host-level failure domains.

Covers the host tier end to end with in-process :class:`HostAgent` stand-ins
(``agent.kill()`` is "the machine died" — every engine on it stops acking at
once, no goodbye heartbeat):

* spread placement over hosts + per-host capacity
* the kill-an-entire-host drill: zero loss, exactly-once, ONE
  ``fleet.host_failed`` decision event whose exported trace stitches spans
  from both hosts
* per-host circuit breaker: dials to a dead host fail fast with a computed
  Retry-After; fresh heartbeats close it again
* NTP-style clock-skew estimation from heartbeat round trips, feeding
  ``zoo_fleet_host_clock_skew_seconds`` and the QoS deadline tolerance
* shm host-identity negotiation: matching peer attaches, mismatching peer is
  denied and stays on TCP (both polarities)
* broker restart under live hosts: the host registry/ctl hashes survive AOF
  replay, agents re-register idempotently, results stay exactly-once
"""

import os
import time

import numpy as np
import pytest

from analytics_zoo_tpu.common import resilience as _res
from analytics_zoo_tpu.inference import InferenceModel
from analytics_zoo_tpu.observability import events as _ev
from analytics_zoo_tpu.observability import recorder as _flight
from analytics_zoo_tpu.observability import traces as _traces
from analytics_zoo_tpu.serving import (FleetSupervisor, InputQueue,
                                       OutputQueue, ServingConfig,
                                       start_broker)
from analytics_zoo_tpu.serving import qos as _qos
from analytics_zoo_tpu.serving.client import _Conn
from analytics_zoo_tpu.serving.hostagent import (HOST_CTL_PREFIX,
                                                 HOST_HB_PREFIX, HostAgent)
from analytics_zoo_tpu.serving.shm import host_identity

pytestmark = [pytest.mark.serving, pytest.mark.fleet]


class StubModel(InferenceModel):
    """Device-bound stand-in: per-row sums make every response attributable
    to exactly one request (the exactly-once check)."""

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
                replicas=4, fleet_hosts=2, fleet_heartbeat_s=0.1,
                fleet_failover_timeout_s=0.8, fleet_spawn_grace_s=10.0,
                breaker_reset_timeout_s=0.3)
    base.update(kw)
    return ServingConfig(**base)


def _submit(broker, n, start=0):
    port = broker if isinstance(broker, int) else broker.port
    iq = InputQueue(port=port)
    try:
        return [(iq.enqueue(None, input=np.full((4,), float(i), np.float32)),
                 4.0 * i) for i in range(start, start + n)]
    finally:
        iq.close()


def _check_exactly_once(broker, subs, timeout_s=60.0):
    port = broker if isinstance(broker, int) else broker.port
    oq = OutputQueue(port=port)
    try:
        for uri, want in subs:
            got = oq.query(uri, timeout_s=timeout_s)
            assert abs(float(np.asarray(got).ravel()[0]) - want) < 1e-4
    finally:
        oq.close()


# ---------------------------------------------------------------------------
# qos: skew tolerance
# ---------------------------------------------------------------------------

def test_cannot_meet_skew_tolerance_widens_admit_only():
    now = 1000.0
    dl = now + 1.0
    # would miss by 0.2s on a single clock...
    assert _qos.cannot_meet(dl, est_wait_s=0.9, service_ema_s=0.3, now=now)
    # ...but inside the fleet's clock-disagreement window it is admitted
    assert not _qos.cannot_meet(dl, est_wait_s=0.9, service_ema_s=0.3,
                                now=now, skew_tolerance_s=0.25)
    # tolerance only WIDENS the admit side — a clearly-missable deadline is
    # still refused
    assert _qos.cannot_meet(dl, est_wait_s=2.0, service_ema_s=0.3, now=now,
                            skew_tolerance_s=0.25)
    # and a comfortably-meetable one is never refused by it
    assert not _qos.cannot_meet(dl, est_wait_s=0.1, service_ema_s=0.1,
                                now=now, skew_tolerance_s=0.25)


# ---------------------------------------------------------------------------
# shm host-identity negotiation (both polarities)
# ---------------------------------------------------------------------------

def test_shmopen_same_host_token_attaches():
    broker = start_broker()
    try:
        c = _Conn("127.0.0.1", broker.port, shm_mode="off")
        try:
            from analytics_zoo_tpu.serving.shm import ShmChannel

            ch = ShmChannel.create()
            try:
                assert c.call("SHMOPEN", ch.name, ch.size,
                              host_identity()) == "OK"
            finally:
                ch.close()
        finally:
            c.close()
    finally:
        broker.shutdown()


def test_shmopen_cross_host_token_denied():
    broker = start_broker()
    try:
        c = _Conn("127.0.0.1", broker.port, shm_mode="off")
        try:
            from analytics_zoo_tpu.serving.shm import ShmChannel

            ch = ShmChannel.create()
            try:
                resp = c.call("SHMOPEN", ch.name, ch.size,
                              "some-other-machine/boot-id")
                assert resp != "OK"
                assert "denied" in str(resp.get("error", resp))
            finally:
                ch.close()
            # the denial is connection-scoped, not fatal: normal verbs keep
            # working over the socket
            c.call("HSET", "after-deny", {"v": 1})
            assert c.call("HGET", "after-deny", 0)["v"] == 1
        finally:
            c.close()
    finally:
        broker.shutdown()


def test_client_negotiation_falls_back_to_tcp_on_identity_mismatch(
        monkeypatch):
    """A client that resolves to loopback but lives in another kernel (the
    containerized/port-forwarded case) must settle on TCP and still work."""
    import analytics_zoo_tpu.serving.client as client_mod

    broker = start_broker()
    try:
        monkeypatch.setattr(client_mod, "host_identity",
                            lambda: "other-container/boot-id")
        c = _Conn("127.0.0.1", broker.port, shm_mode="eager")
        try:
            assert c._shm is None          # negotiation refused, no ring
            big = np.ones((1 << 16,), np.float32)
            c.call("HSET", "xhost-big", {"v": big})
            back = c.call("HGET", "xhost-big", 0)
            assert np.allclose(back["v"], big)    # payload rode the socket
            assert c._shm is None
        finally:
            c.close()
    finally:
        broker.shutdown()


# ---------------------------------------------------------------------------
# placement
# ---------------------------------------------------------------------------

def test_host_placement_spreads_and_respects_capacity():
    broker = start_broker()
    try:
        cfg = _cfg(broker, replicas=4, fleet_hosts=2, fleet_host_capacity=3)
        fleet = FleetSupervisor(cfg, model_factory=lambda: StubModel())
        try:
            fleet.start()
            assert fleet.wait_eligible(4, timeout_s=20)
            hosts = fleet.stats()["hosts"]
            sizes = sorted(len(h["replicas"]) for h in hosts.values())
            assert sizes == [2, 2], hosts          # spread, not packed
            # capacity is a hard per-host ceiling
            assert fleet._place_host() in ("h0", "h1")
            for s in fleet._hosts.values():
                s.replicas.update({f"x{i}{s.hid}" for i in range(3)})
            assert fleet._place_host() is None
        finally:
            fleet.stop(drain_s=1.0)
    finally:
        broker.shutdown()


# ---------------------------------------------------------------------------
# the whole-host kill drill
# ---------------------------------------------------------------------------

@pytest.mark.chaos
def test_whole_host_kill_zero_loss_single_decision(tmp_path):
    """SIGKILL-equivalent death of one entire host mid-burst: every request
    is answered exactly once, the failover is ONE ``fleet.host_failed``
    decision, its exported trace carries spans from both hosts, and the
    kill auto-cuts a complete, loadable flight dump whose control records
    capture the host-heartbeat-age inputs behind the verdict."""
    broker = start_broker()
    rec = _flight.install(
        dump_dir=os.environ.get("ZOO_FLIGHT_DIR") or str(tmp_path))
    try:
        cfg = _cfg(broker, replicas=4, fleet_hosts=2)
        fleet = FleetSupervisor(
            cfg, model_factory=lambda: StubModel(0.02))
        try:
            fleet.start()
            assert fleet.wait_eligible(4, timeout_s=20)
            before = fleet.host_failovers
            subs = _submit(broker, 24)
            fleet.kill_host("h0")           # whole machine, no goodbye
            subs += _submit(broker, 24, start=24)
            _check_exactly_once(broker, subs)
            deadline = time.monotonic() + 10
            while time.monotonic() < deadline and not fleet.host_failovers:
                time.sleep(0.05)
            assert fleet.host_failovers == before + 1
            assert fleet.requeued > 0, "the dead host held no claimed work"
            evs = [e for e in _ev.events(kind="fleet.host_failed")]
            assert len(evs) == 1
            ev = evs[-1]
            fields = ev.fields
            assert fields["host"] == "h0"
            assert sorted(fields["replicas"]) == sorted(
                r for r in fields["respawned"])
            # every evicted replica landed on the survivor
            assert set(fields["respawned"].values()) == {"h1"}
            # the trace stitches spans from BOTH machines: the supervisor's
            # own host identity on the parent, the failed host's id on the
            # per-replica evict children
            trace = _traces.export_trace(ev.trace_id)
            assert trace is not None
            hosts_in_trace = set(trace["otherData"].get("hosts", ()))
            assert "h0" in hosts_in_trace
            assert host_identity() in hosts_in_trace
            assert len(hosts_in_trace) >= 2
            names = {e["name"] for e in trace["traceEvents"]}
            assert "fleet.host_failover" in names
            assert "fleet.host_failover.evict" in names
            # clock-offset annotation rides the evict spans
            evict = [e for e in trace["traceEvents"]
                     if e["name"] == "fleet.host_failover.evict"]
            assert all("clock_offset_s" in e["args"] for e in evict)
            # survivors keep serving
            _check_exactly_once(broker, _submit(broker, 8, start=100))
            # the SIGKILL drill must leave a black box behind: one complete
            # versioned dump, auto-cut on the fleet.host_failed event
            import json as _json

            deadline = time.monotonic() + 10
            while time.monotonic() < deadline and rec.last_dump_path is None:
                time.sleep(0.05)
            assert rec.last_dump_path is not None, "host kill cut no dump"
            with open(rec.last_dump_path) as f:
                dump = _json.load(f)
            assert dump["schema"] == "zoo-flight-v1"
            assert any(e["kind"] == "fleet.host_failed"
                       for e in dump["events"])
            checks = [r for r in dump["records"]
                      if r["site"] == "fleet.host_check"]
            assert checks and checks[-1]["inputs"]["host"] == "h0"
            assert checks[-1]["inputs"]["hb_age_s"] >= 0.0
        finally:
            _flight.uninstall()
            fleet.stop(drain_s=1.0)
    finally:
        broker.shutdown()


@pytest.mark.chaos
def test_dial_dead_host_fails_fast_with_retry_after():
    broker = start_broker()
    try:
        cfg = _cfg(broker, replicas=2, fleet_hosts=2,
                   breaker_reset_timeout_s=30.0)
        fleet = FleetSupervisor(cfg, model_factory=lambda: StubModel())
        try:
            fleet.start()
            assert fleet.wait_eligible(2, timeout_s=20)
            assert fleet.dial_host("h1").get("state") == "up"
            fleet.kill_host("h1")
            deadline = time.monotonic() + 10
            while time.monotonic() < deadline and not fleet.host_failovers:
                time.sleep(0.05)
            t0 = time.perf_counter()
            with pytest.raises(_res.CircuitOpenError) as ei:
                fleet.dial_host("h1")
            assert time.perf_counter() - t0 < 0.1       # no network wait
            assert ei.value.retry_after_s > 0           # computed Retry-After
            # restart the agent: fresh heartbeats close the breaker again
            fleet._start_agent("h1")
            deadline = time.monotonic() + 15
            while time.monotonic() < deadline:
                try:
                    if fleet.dial_host("h1").get("state") == "up":
                        break
                except (_res.CircuitOpenError, ConnectionError):
                    time.sleep(0.1)
            else:
                pytest.fail("breaker never closed after host revival")
        finally:
            fleet.stop(drain_s=1.0)
    finally:
        broker.shutdown()


# ---------------------------------------------------------------------------
# clock skew
# ---------------------------------------------------------------------------

def test_host_clock_skew_estimated_and_feeds_qos_tolerance():
    """A host whose wall clock runs 5s ahead: the supervisor's NTP-style
    estimate converges on the offset, exports it, keeps treating the host's
    (future-stamped) heartbeats as fresh, and widens the router's deadline
    skew tolerance."""
    broker = start_broker()
    try:
        cfg = _cfg(broker, replicas=2, fleet_hosts=2,
                   fleet_host_skew_tolerance_s=0.25)
        fleet = FleetSupervisor(cfg, model_factory=lambda: StubModel(),
                                manage_agents=False)
        agents = []
        try:
            fleet.start()
            agents = [
                HostAgent("h0", _cfg(broker, replicas=2),
                          model_factory=lambda: StubModel()).start(),
                HostAgent("h1", _cfg(broker, replicas=2),
                          model_factory=lambda: StubModel(),
                          clock_offset_s=5.0).start()]
            assert fleet.wait_eligible(2, timeout_s=20)
            deadline = time.monotonic() + 10
            while (time.monotonic() < deadline
                   and fleet._hosts["h1"].skew_samples < 3):
                time.sleep(0.05)
            est = fleet._hosts["h1"].clock_offset_s
            assert abs(est - 5.0) < 0.5, est
            assert abs(fleet._hosts["h0"].clock_offset_s) < 0.5
            # the skewed-but-healthy host must NOT look stale
            assert fleet._hosts["h1"].alive
            # router tolerance = configured floor + worst live |offset|
            # (est keeps EMA-updating, so compare loosely)
            assert fleet.router.skew_s == pytest.approx(0.25 + abs(est),
                                                        abs=0.5)
            # ... and the gauge carries the per-host estimate
            from analytics_zoo_tpu.serving.fleet import _HOST_SKEW

            assert abs(_HOST_SKEW.labels(host="h1").value() - est) < 1e-6
            # requests still flow on a skewed fleet
            _check_exactly_once(broker, _submit(broker, 8))
        finally:
            for a in agents:
                a.stop(drain_s=1.0)
            fleet.stop(drain_s=1.0)
    finally:
        broker.shutdown()


# ---------------------------------------------------------------------------
# broker restart with live hosts (AOF)
# ---------------------------------------------------------------------------

def _free_port() -> int:
    import socket as _socket

    with _socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _spawn_broker(port, aof):
    import subprocess
    import sys as _sys

    proc = subprocess.Popen(
        [_sys.executable, "-m", "analytics_zoo_tpu.serving.broker",
         "--host", "127.0.0.1", "--port", str(port), "--aof", aof],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True)
    deadline = time.time() + 20
    while time.time() < deadline:
        try:
            c = _Conn("127.0.0.1", port, timeout=2.0)
            assert c.call("PING") == "PONG"
            c.close()
            return proc
        except (OSError, ConnectionError):
            if proc.poll() is not None:
                raise RuntimeError(f"broker died: {proc.stdout.read()}")
            time.sleep(0.05)
    proc.kill()
    raise RuntimeError("broker did not come up")


@pytest.mark.slow
def test_broker_restart_with_live_hosts_reconverges(tmp_path):
    """SIGKILL the broker under a live cross-host fleet and restart it on
    the same AOF: the host registry + ctl hashes replay, the agents'
    re-register is idempotent (no double-spawned engines — the nonce/
    generation reconcile sees nothing new), and post-restart traffic stays
    exactly-once (HSETNX two-writes-one-wins survives the replay)."""
    import signal

    aof = str(tmp_path / "fleet.aof")
    port = _free_port()
    proc = _spawn_broker(port, aof)
    cfg = ServingConfig(queue_port=port, batch_size=4, batch_timeout_ms=2,
                        replicas=2, fleet_hosts=2, fleet_heartbeat_s=0.1,
                        # generous: the broker restart window must NOT read
                        # as a host death (the hosts never went anywhere)
                        fleet_failover_timeout_s=5.0,
                        fleet_spawn_grace_s=10.0)
    fleet = FleetSupervisor(cfg, model_factory=lambda: StubModel())
    try:
        fleet.start()
        assert fleet.wait_eligible(2, timeout_s=20)
        _check_exactly_once(port, _submit(port, 8))
        engines_before = {
            hid: list(s.agent.replica_ids())
            for hid, s in fleet._hosts.items() if s.agent is not None}

        proc.send_signal(signal.SIGKILL)   # broker dies, hosts stay live
        proc.wait()
        proc = _spawn_broker(port, aof)    # same port + log

        # replayed host registry: members, hb, and ctl hashes are all back
        c = _Conn("127.0.0.1", port)
        try:
            members = c.call("HGET", "fleet:members", 0)
            assert sorted(members["hosts"]) == ["h0", "h1"]
            for hid in ("h0", "h1"):
                assert isinstance(
                    c.call("HGET", HOST_HB_PREFIX + hid, 0), dict)
                ctl = c.call("HGET", HOST_CTL_PREFIX + hid, 0)
                assert isinstance(ctl, dict) and "replicas" in ctl
            # HSETNX two-writes-one-wins still holds post-replay
            assert c.call("HSETNX", "already-answered", {"v": 1}) == 1
            assert c.call("HSETNX", "already-answered", {"v": 2}) == 0

            # agents reconnect and re-register: the hb freshens again
            deadline = time.monotonic() + 20
            while time.monotonic() < deadline:
                hb = c.call("HGET", HOST_HB_PREFIX + "h0", 0)
                if isinstance(hb, dict) and time.time() - float(
                        hb.get("ts", 0)) < 0.5:
                    break
                time.sleep(0.1)
            else:
                pytest.fail("agent never re-registered after restart")
        finally:
            c.close()

        # idempotent re-register: the SAME engines, nothing double-spawned
        engines_after = {
            hid: list(s.agent.replica_ids())
            for hid, s in fleet._hosts.items() if s.agent is not None}
        assert engines_after == engines_before

        # lanes reconverge: post-restart traffic answered exactly once
        assert fleet.wait_eligible(2, timeout_s=20)
        _check_exactly_once(port, _submit(port, 12, start=50))
    finally:
        fleet.stop(drain_s=1.0)
        proc.send_signal(signal.SIGKILL)
        proc.wait()


# ---------------------------------------------------------------------------
# host-scoped autoscale events
# ---------------------------------------------------------------------------

def test_scale_down_retires_whole_host_to_idle():
    broker = start_broker()
    try:
        cfg = _cfg(broker, replicas=4, fleet_hosts=2, min_replicas=1)
        fleet = FleetSupervisor(cfg, model_factory=lambda: StubModel())
        try:
            fleet.start()
            assert fleet.wait_eligible(4, timeout_s=20)
            _check_exactly_once(broker, _submit(broker, 8))
            fleet._scale_down_host()
            deadline = time.monotonic() + 30
            while time.monotonic() < deadline:
                sizes = sorted(len(s.replicas)
                               for s in fleet._hosts.values())
                if sizes == [0, 2] and not fleet._as_busy:
                    break
                time.sleep(0.1)
            sizes = sorted(len(s.replicas) for s in fleet._hosts.values())
            assert sizes == [0, 2], fleet.stats()["hosts"]
            evs = [e for e in _ev.events(kind="autoscale.down")]
            assert evs and evs[-1].fields.get("host") in ("h0", "h1")
            # the retired host is still registered and idle — exactly the
            # machine the next scale-up borrows first
            idle = [h for h, s in fleet._hosts.items() if not s.replicas][0]
            assert fleet._hosts[idle].alive
            assert fleet._place_host() == idle
            # remaining capacity still serves, zero-loss
            _check_exactly_once(broker, _submit(broker, 8, start=30))
        finally:
            fleet.stop(drain_s=1.0)
    finally:
        broker.shutdown()
