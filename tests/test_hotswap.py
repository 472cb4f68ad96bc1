"""Model hot-swap tests (ISSUE 10): checkpoint manifest durability,
trainer-side publishing, swap-side staging validation (checksum / signature /
NaN), the atomic no-mixed-weights flip, version tagging end to end (payload +
wire header + HTTP), canary rollout with automatic rollback, and the chaos
drills (kill the canary mid-rollout, kill the engine mid-swap, NaN-poisoned
publish under live load).

Replicas are thread-mode ClusterServing engines over a tiny REAL loaded
linear model (response = sum(input) + b, with b encoding the version offset),
so every response is arithmetically attributable to exactly one (request,
model version) pair — a mixed-weights or mis-tagged answer cannot hide.
"""

import json
import threading
import time
import urllib.request

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from analytics_zoo_tpu.engine.checkpoint import (CheckpointCorruptError,
                                                 CheckpointWriter,
                                                 load_checkpoint,
                                                 param_tree_signature,
                                                 read_manifest,
                                                 save_checkpoint,
                                                 verify_checkpoint)
from analytics_zoo_tpu.inference import InferenceModel
from analytics_zoo_tpu.observability import events as _ev
from analytics_zoo_tpu.observability import export_trace
from analytics_zoo_tpu.serving import (ClusterServing, FleetSupervisor,
                                       InputQueue, ModelPublisher,
                                       ModelSwapper, OutputQueue,
                                       ReplicaRouter, ServingConfig,
                                       SwapRejected, start_broker)
from analytics_zoo_tpu.serving.hotswap import (MODEL_STREAM, publish_record)

pytestmark = [pytest.mark.serving, pytest.mark.hotswap]

W = np.ones((4, 1), np.float32)


def _model(b=0.0):
    im = InferenceModel(max_batch_size=8)
    im.load_fn(lambda p, s, x: x @ p["w"] + p["b"],
               params={"w": W, "b": np.array([b], np.float32)})
    return im


def _params(b):
    return {"w": W, "b": np.array([b], np.float32)}


def _cfg(broker, **kw):
    base = dict(queue_port=broker.port, batch_size=4, batch_timeout_ms=2,
                fleet_heartbeat_s=0.1, fleet_failover_timeout_s=0.8,
                fleet_spawn_grace_s=10.0, breaker_reset_timeout_s=0.3,
                warmup_shape=(4,), rollout_window_s=0.3,
                rollout_min_requests=3, rollout_canary_fraction=0.34,
                swap_timeout_s=10.0)
    base.update(kw)
    return ServingConfig(**base)


def _wait(pred, timeout_s=20.0, interval=0.05):
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        if pred():
            return True
        time.sleep(interval)
    return pred()


class _Load:
    """Closed-loop background load recording (i, value, version) triples."""

    def __init__(self, port, n_threads=2):
        self.port, self.n = port, n_threads
        self.stop = threading.Event()
        self.lock = threading.Lock()
        self.results = []
        self.threads = []

    def _run(self, idx):
        iq, oq = InputQueue(port=self.port), OutputQueue(port=self.port)
        i = idx
        try:
            while not self.stop.is_set():
                u = iq.enqueue(None, input=np.full((4,), float(i),
                                                   np.float32))
                try:
                    v = oq.query(u, timeout_s=30)
                    rec = (i, float(np.ravel(v)[0]), oq.last_model_version)
                except Exception as e:  # recorded, asserted on by the test
                    rec = (i, None, repr(e))
                with self.lock:
                    self.results.append(rec)
                i += self.n
        finally:
            iq.close()
            oq.close()

    def __enter__(self):
        self.threads = [threading.Thread(target=self._run, args=(i,),
                                         daemon=True) for i in range(self.n)]
        for t in self.threads:
            t.start()
        return self

    def __exit__(self, *exc):
        self.stop.set()
        for t in self.threads:
            t.join(timeout=15)

    def check_zero_loss(self, good_offsets):
        """Every request answered once, finite, value == 4*i + a good
        offset, AND the version tag matches the offset that produced it."""
        with self.lock:
            snap = list(self.results)
        assert snap, "load generator produced nothing"
        for i, value, tag in snap:
            assert value is not None and np.isfinite(value), (i, value, tag)
            offset = value - 4.0 * i
            assert tag in good_offsets, (i, value, tag)
            assert abs(offset - good_offsets[tag]) < 1e-4, \
                (i, value, tag, offset)
        return len(snap)


# ---------------------------------------------------------------------------
# checkpoint manifest durability (satellite)
# ---------------------------------------------------------------------------

def test_manifest_written_and_verified(tmp_path):
    path = save_checkpoint(str(tmp_path), _params(7.0), iteration=3, epoch=1)
    m = read_manifest(path)
    assert m is not None
    assert m["iteration"] == 3 and m["n_leaves"] == 2
    assert m["version"].startswith("v3-")
    assert m["signature"] == param_tree_signature(
        jax.tree_util.tree_leaves(_params(7.0)))
    assert verify_checkpoint(path) == m
    state, meta = load_checkpoint(path, _params(0.0))
    assert float(np.ravel(state["b"])[0]) == 7.0


def test_truncated_checkpoint_rejected_at_load(tmp_path):
    import os

    path = save_checkpoint(str(tmp_path), _params(1.0), iteration=1, epoch=0)
    state = os.path.join(path, "state.npz")
    with open(state, "r+b") as f:        # torn write: chop the tail off
        f.truncate(os.path.getsize(state) // 2)
    with pytest.raises(CheckpointCorruptError, match="truncated|torn"):
        load_checkpoint(path, _params(0.0))
    # same-size bit rot is caught by the content checksum
    path2 = save_checkpoint(str(tmp_path), _params(2.0), iteration=2, epoch=0)
    state2 = os.path.join(path2, "state.npz")
    raw = bytearray(open(state2, "rb").read())
    raw[len(raw) // 2] ^= 0xFF
    open(state2, "wb").write(bytes(raw))
    with pytest.raises(CheckpointCorruptError, match="checksum"):
        load_checkpoint(path2, _params(0.0))


def test_pre_manifest_checkpoints_still_load(tmp_path):
    import os

    path = save_checkpoint(str(tmp_path), _params(5.0), iteration=1, epoch=0)
    os.remove(os.path.join(path, "manifest.json"))
    state, _ = load_checkpoint(path, _params(0.0))    # tolerated: no manifest
    assert float(np.ravel(state["b"])[0]) == 5.0
    with pytest.raises(ValueError, match="manifest"):
        publish_record(path)


# ---------------------------------------------------------------------------
# publisher (trainer side)
# ---------------------------------------------------------------------------

def test_publisher_announces_durable_checkpoints_via_writer(tmp_path):
    from analytics_zoo_tpu.serving.client import _Conn

    broker = start_broker()
    try:
        pub = ModelPublisher(port=broker.port)
        writer = CheckpointWriter(on_durable=pub.on_durable)
        save_checkpoint(str(tmp_path), _params(1.0), iteration=1, epoch=0,
                        writer=writer)
        writer.drain()
        assert len(pub.published) == 1
        rec = pub.published[0]
        m = read_manifest(rec["path"])
        assert rec["version"] == m["version"]
        assert rec["checksum"] == m["checksum"]
        assert rec["signature"] == m["signature"]
        assert rec["step"] == 1
        c = _Conn("127.0.0.1", broker.port)
        last = c.call("XLAST", MODEL_STREAM)
        assert last is not None and last[1]["version"] == rec["version"]
        c.close()
        pub.close()
    finally:
        broker.shutdown()


def test_estimator_save_publishes(tmp_path):
    """The training loop's own checkpoint saves announce on the stream once
    a publisher is attached (set_model_publisher) — the trainer half of the
    continuous-deployment loop, no bespoke plumbing per training script."""
    from analytics_zoo_tpu.common import TrainConfig
    from analytics_zoo_tpu.engine import Estimator
    from analytics_zoo_tpu.nn import layers as L
    from analytics_zoo_tpu.nn.graph import Input
    from analytics_zoo_tpu.nn.topology import Model

    broker = start_broker()
    try:
        pub = ModelPublisher(port=broker.port)
        x = Input((6,))
        out = L.Dense(3, activation="softmax")(L.Dense(8)(x))
        est = Estimator(Model(x, out), optimizer="sgd",
                        loss="sparse_categorical_crossentropy",
                        config=TrainConfig(checkpoint_dir=str(tmp_path),
                                           log_every_n_steps=1000))
        est.set_model_publisher(pub)
        rng = np.random.default_rng(0)
        xs = rng.normal(size=(128, 6)).astype("float32")
        ys = rng.integers(0, 3, 128).astype("int32")
        est.fit((xs, ys), batch_size=32, epochs=1)
        assert pub.published, "epoch-end checkpoint was not announced"
        assert pub.published[-1]["step"] == 4
        pub.close()
    finally:
        broker.shutdown()


# ---------------------------------------------------------------------------
# swapper staging validation + the atomic flip
# ---------------------------------------------------------------------------

def test_swap_params_flips_and_preserves_compiled_cache():
    im = _model(0.0)
    x = np.full((2, 4), 1.0, np.float32)
    np.testing.assert_allclose(np.ravel(im.predict(x)), [4.0, 4.0])
    compiles = im.compile_count
    im.swap_params(_params(100.0), version="v1")
    assert im.version == "v1"
    np.testing.assert_allclose(np.ravel(im.predict(x)), [104.0, 104.0])
    # same avals, same apply identity: the executable cache survived
    assert im.compile_count == compiles


def test_swapper_stages_and_rejects(tmp_path):
    im = _model(0.0)
    sw = ModelSwapper(im, probe_shape=(4,))
    good = save_checkpoint(str(tmp_path / "good"), _params(10.0),
                           iteration=1, epoch=0)
    rec = publish_record(good)
    assert sw.stage_and_swap(rec).startswith("v1-")
    x = np.full((1, 4), 1.0, np.float32)
    np.testing.assert_allclose(np.ravel(im.predict(x)), [14.0])

    # NaN-poisoned params
    bad = save_checkpoint(str(tmp_path / "nan"), _params(np.nan),
                          iteration=2, epoch=0)
    with pytest.raises(SwapRejected) as ei:
        sw.stage_and_swap(publish_record(bad))
    assert ei.value.reason == "nan"

    # checksum mismatch between published record and on-disk bytes
    stale = save_checkpoint(str(tmp_path / "stale"), _params(3.0),
                            iteration=3, epoch=0)
    rec3 = publish_record(stale)
    rec3["checksum"] = "0" * 64
    with pytest.raises(SwapRejected) as ei:
        sw.stage_and_swap(rec3)
    assert ei.value.reason == "checksum"

    # param-tree signature mismatch (different shapes)
    wrong = save_checkpoint(str(tmp_path / "wrong"),
                            {"w": np.ones((5, 1), np.float32),
                             "b": np.zeros(1, np.float32)},
                            iteration=4, epoch=0)
    with pytest.raises(SwapRejected) as ei:
        sw.stage_and_swap(publish_record(wrong))
    assert ei.value.reason in ("shape", "signature")

    # duplicate / out-of-order publishes are skipped, not applied
    assert sw.stage_and_swap(rec) == im.version       # same step: no-op
    # live model is still on the good version with its weights
    np.testing.assert_allclose(np.ravel(im.predict(x)), [14.0])

    # rollback restores the retained pre-swap params (boot state)
    sw.rollback()
    np.testing.assert_allclose(np.ravel(im.predict(x)), [4.0])


def test_trainer_train_state_checkpoint_swaps_params_subtree(tmp_path):
    """Regression (found by the verify drive): the Estimator checkpoints its
    WHOLE train_state (params + opt_state + model_state + counters), so a
    published trainer checkpoint has more leaves than the serving model —
    the swapper must select the ``params`` subtree via the manifest's
    per-leaf tree paths instead of rejecting every real trainer publish."""
    train_state = {
        "params": _params(42.0),
        "opt_state": {"m": np.zeros((4, 1), np.float32), "count": np.int32(7)},
        "model_state": {},
        "step": np.int32(9),
        "rng": np.zeros(2, np.uint32),
    }
    path = save_checkpoint(str(tmp_path), train_state, iteration=9, epoch=1)
    m = read_manifest(path)
    assert len(m["leaf_paths"]) == m["n_leaves"] > 2
    im = _model(0.0)
    sw = ModelSwapper(im, probe_shape=(4,))
    sw.stage_and_swap(publish_record(path))
    x = np.full((1, 4), 1.0, np.float32)
    np.testing.assert_allclose(np.ravel(im.predict(x)), [46.0])
    assert im.version.startswith("v9-")
    # a train_state whose params DON'T match the model is still rejected
    bad_state = dict(train_state)
    bad_state["params"] = {"w": np.ones((5, 1), np.float32),
                           "b": np.zeros(1, np.float32)}
    bad = save_checkpoint(str(tmp_path / "bad"), bad_state, iteration=10,
                          epoch=1)
    with pytest.raises(SwapRejected) as ei:
        sw.stage_and_swap(publish_record(bad))
    assert ei.value.reason in ("shape", "signature")


def test_swap_rejects_stale_step_but_force_applies(tmp_path):
    im = _model(0.0)
    sw = ModelSwapper(im, probe_shape=(4,))
    p5 = save_checkpoint(str(tmp_path / "a"), _params(50.0), iteration=5,
                         epoch=0)
    p2 = save_checkpoint(str(tmp_path / "b"), _params(20.0), iteration=2,
                         epoch=0)
    sw.stage_and_swap(publish_record(p5))
    v5 = im.version
    sw.stage_and_swap(publish_record(p2))             # out-of-order: ignored
    assert im.version == v5
    sw.stage_and_swap(publish_record(p2), force=True)  # rollback-style force
    assert im.version.startswith("v2-")


def test_quantized_model_swap_requantizes():
    """A swapped-in checkpoint must serve through the SAME int8 path the
    engine warmed up — re-packed, not silently float."""
    rng = np.random.default_rng(0)
    w = rng.normal(size=(64, 64)).astype(np.float32)
    im = InferenceModel(max_batch_size=4)
    im.load_fn(lambda p, s, x: x @ p["w"], params={"w": w})
    im.quantize_int8(min_elements=1)
    assert im.is_quantized
    x = rng.normal(size=(2, 64)).astype(np.float32)
    before = np.asarray(im.predict(x))
    im.swap_params({"w": 2.0 * w}, version="v2")
    assert im.is_quantized
    after = np.asarray(im.predict(x))
    # still int8-quantized (not exact), but clearly the NEW weights
    np.testing.assert_allclose(after, 2.0 * before, rtol=0.1, atol=0.5)
    assert im.version == "v2"


# ---------------------------------------------------------------------------
# single-engine stream swap + version tagging end to end
# ---------------------------------------------------------------------------

def test_single_engine_swaps_on_publish_and_tags_responses(tmp_path, zoo_ctx):
    broker = start_broker()
    eng = None
    try:
        cfg = _cfg(broker)
        eng = ClusterServing(_model(0.0), config=cfg).start()
        iq, oq = InputQueue(port=broker.port), OutputQueue(port=broker.port)
        u = iq.enqueue(None, input=np.full((4,), 2.0, np.float32))
        assert float(np.ravel(oq.query(u, timeout_s=15))[0]) == 8.0
        assert oq.last_model_version == "initial"

        pub = ModelPublisher(port=broker.port)
        path = save_checkpoint(str(tmp_path), _params(1000.0), iteration=1,
                               epoch=0)
        rec = pub.publish(path)
        assert _wait(lambda: eng.model_version == rec["version"]), \
            (eng.model_version, eng._swap_state, eng._swap_error)
        u = iq.enqueue(None, input=np.full((4,), 2.0, np.float32))
        assert float(np.ravel(oq.query(u, timeout_s=15))[0]) == 1008.0
        assert oq.last_model_version == rec["version"]

        # poisoned publish: rejected, rejection visible to the publisher,
        # engine keeps serving the good version
        poison = save_checkpoint(str(tmp_path), _params(np.inf), iteration=2,
                                 epoch=0)
        pub.publish(poison)
        assert _wait(lambda: eng._swap_state == "error")
        assert "nan" in eng._swap_error
        assert eng.model_version == rec["version"]
        u = iq.enqueue(None, input=np.full((4,), 2.0, np.float32))
        assert float(np.ravel(oq.query(u, timeout_s=15))[0]) == 1008.0
        rej = pub.check_rejections()
        assert rej and rej[0]["reason"].startswith("nan")
        iq.close()
        oq.close()
        pub.close()
    finally:
        if eng is not None:
            eng.stop()
        broker.shutdown()


def test_late_joining_engine_adopts_latest_published(tmp_path, zoo_ctx):
    """XLAST catch-up: an engine started AFTER the trainer published (e.g. a
    restarted stack) must come up on the newest version, not the boot
    params, and not replay the whole publish history."""
    broker = start_broker()
    eng = None
    try:
        pub = ModelPublisher(port=broker.port)
        for it, b in ((1, 100.0), (2, 200.0)):
            pub.publish(save_checkpoint(str(tmp_path), _params(b),
                                        iteration=it, epoch=0))
        latest = pub.published[-1]["version"]
        eng = ClusterServing(_model(0.0), config=_cfg(broker)).start()
        assert _wait(lambda: eng.model_version == latest), \
            (eng.model_version, eng._swap_state, eng._swap_error)
        iq, oq = InputQueue(port=broker.port), OutputQueue(port=broker.port)
        u = iq.enqueue(None, input=np.full((4,), 1.0, np.float32))
        assert float(np.ravel(oq.query(u, timeout_s=15))[0]) == 204.0
        iq.close()
        oq.close()
        pub.close()
    finally:
        if eng is not None:
            eng.stop()
        broker.shutdown()


def test_http_response_carries_model_version(tmp_path, zoo_ctx):
    from analytics_zoo_tpu.serving.http_frontend import FrontEndApp

    broker = start_broker()
    eng = app = None
    try:
        cfg = _cfg(broker)
        eng = ClusterServing(_model(0.0), config=cfg).start()
        app = FrontEndApp(cfg, port=0).start()
        pub = ModelPublisher(port=broker.port)
        rec = pub.publish(save_checkpoint(str(tmp_path), _params(500.0),
                                          iteration=1, epoch=0))
        assert _wait(lambda: eng.model_version == rec["version"])
        body = json.dumps({"instances": [{"input": [1.0] * 4}]}).encode()
        r = urllib.request.urlopen(urllib.request.Request(
            f"http://127.0.0.1:{app.port}/predict", body,
            {"Content-Type": "application/json"}), timeout=15)
        payload = json.loads(r.read())
        assert payload["model_version"] == rec["version"]
        assert r.headers["X-Zoo-Model-Version"] == rec["version"]
        assert abs(payload["predictions"][0][0] - 504.0) < 1e-4
        pub.close()
    finally:
        if app is not None:
            app.stop()
        if eng is not None:
            eng.stop()
        broker.shutdown()


# ---------------------------------------------------------------------------
# router canary weighting
# ---------------------------------------------------------------------------

def test_router_traffic_fraction_weights_canary(zoo_ctx):
    from analytics_zoo_tpu.serving.fleet import REPLICA_STREAM_PREFIX

    broker = start_broker()
    engines, router = [], None
    try:
        cfg = _cfg(broker)
        engines = [
            ClusterServing(_model(0.0), config=cfg, group=f"fleet-{rid}",
                           stream=REPLICA_STREAM_PREFIX + rid,
                           dedup_results=True).start()
            for rid in ("a", "b")]
        router = ReplicaRouter(cfg, ("a", "b"), policy="round_robin").start()
        router.set_traffic_fraction("b", 0.25)
        iq = InputQueue(port=broker.port)
        subs = []
        for i in range(40):
            u = iq.enqueue(None, input=np.full((4,), float(i), np.float32))
            subs.append((u, 4.0 * i))
        oq = OutputQueue(port=broker.port)
        for u, want in subs:
            got = oq.query(u, timeout_s=20)
            assert abs(float(np.ravel(got)[0]) - want) < 1e-4
        stats = router.stats()["replicas"]
        # canary admitted on ~every 4th pick: clear minority, never zero
        assert 0 < stats["b"]["dispatched"] < stats["a"]["dispatched"]
        assert stats["b"]["dispatched"] <= 40 * 0.4
        assert stats["b"]["weight"] == 0.25
        router.set_traffic_fraction("b", 1.0)
        assert router.stats()["replicas"]["b"]["weight"] == 1.0
        with pytest.raises(ValueError):
            router.set_traffic_fraction("a", 0.0)
        iq.close()
        oq.close()
    finally:
        if router is not None:
            router.stop()
        for e in engines:
            e.stop()
        broker.shutdown()


# ---------------------------------------------------------------------------
# fleet canary rollout + the chaos drills
# ---------------------------------------------------------------------------

def _publish(pub, tmp_path, it, b):
    return pub.publish(save_checkpoint(str(tmp_path), _params(b),
                                       iteration=it, epoch=0))


def _assert_rollout_event(kind, version):
    """The rollout's verdict on ``version`` is on the decision-event stream
    and the trace it names exports whole (the rollout span is in it)."""
    (ev,) = [e for e in _ev.events(kind=kind)
             if e.fields.get("version") == version]
    trace = export_trace(ev.trace_id)
    assert any(s["name"] == "rollout" for s in trace["traceEvents"])
    return ev


def _versions_converged(fleet, version):
    mv = fleet.model_versions()
    return (mv and all(v == version for v in mv.values())
            and fleet.rollout.state()["phase"] == "idle")


def test_rollout_canary_promotes_fleet_wide(tmp_path, zoo_ctx):
    broker = start_broker()
    fleet = None
    try:
        cfg = _cfg(broker, replicas=3)
        fleet = FleetSupervisor(cfg, model_factory=_model).start()
        assert fleet.wait_eligible(3, timeout_s=15)
        pub = ModelPublisher(port=broker.port)
        with _Load(broker.port) as load:
            time.sleep(0.2)
            rec = _publish(pub, tmp_path, 1, 1000.0)
            assert _wait(lambda: _versions_converged(fleet, rec["version"]),
                         timeout_s=30), (fleet.model_versions(),
                                         fleet.rollout.state())
            time.sleep(0.3)
        n = load.check_zero_loss({"initial": 0.0, rec["version"]: 1000.0})
        assert n > 10
        assert ((rec["version"], "promoted")
                in fleet.rollout.outcomes), fleet.rollout.outcomes
        _assert_rollout_event("rollout.promoted", rec["version"])
        # operator surfaces: readiness + stats carry versions & phase
        ready, detail = fleet.readiness()
        assert ready
        assert set(detail["model_versions"].values()) == {rec["version"]}
        assert detail["rollout"]["phase"] == "idle"
        assert detail["rollout"]["current"] == rec["version"]
        pub.close()
    finally:
        if fleet is not None:
            fleet.stop(drain_s=2.0)
        broker.shutdown()


@pytest.mark.chaos
def test_poisoned_publish_rolls_back_zero_loss(tmp_path, zoo_ctx):
    """NaN-poisoned checkpoint published under live load: automatic
    rollback, zero failed client requests throughout, trainer sees the
    rejection record."""
    broker = start_broker()
    fleet = None
    try:
        cfg = _cfg(broker, replicas=3)
        fleet = FleetSupervisor(cfg, model_factory=_model).start()
        assert fleet.wait_eligible(3, timeout_s=15)
        pub = ModelPublisher(port=broker.port)
        rec1 = _publish(pub, tmp_path, 1, 1000.0)
        assert _wait(lambda: _versions_converged(fleet, rec1["version"]),
                     timeout_s=30)
        with _Load(broker.port) as load:
            time.sleep(0.2)
            poison = _publish(pub, tmp_path, 2, np.nan)
            assert _wait(lambda: any(
                v == poison["version"] and o in ("rolled_back", "aborted")
                for v, o in fleet.rollout.outcomes), timeout_s=30), \
                fleet.rollout.state()
            # fleet still (or again) on the good version
            assert _wait(lambda: _versions_converged(fleet, rec1["version"]),
                         timeout_s=20), fleet.model_versions()
            time.sleep(0.3)
        load.check_zero_loss({"initial": 0.0, rec1["version"]: 1000.0})
        rej = pub.check_rejections()
        assert any(r["version"] == poison["version"] and "nan" in r["reason"]
                   for r in rej), rej
        ev = _assert_rollout_event("rollout.rejected", poison["version"])
        assert ev.fields["outcome"] in ("rolled_back", "aborted")
        pub.close()
    finally:
        if fleet is not None:
            fleet.stop(drain_s=2.0)
        broker.shutdown()


@pytest.mark.chaos
def test_good_publish_after_poisoned_still_deploys(tmp_path, zoo_ctx):
    """Regression (review): after a rejected swap the replica's heartbeat
    keeps carrying the old swap_error until it polls the NEXT command — the
    controller must scope errors to its own command nonce, or every good
    version after one poisoned publish is rejected on the stale error and
    permanently lost."""
    broker = start_broker()
    fleet = None
    try:
        cfg = _cfg(broker, replicas=2)
        fleet = FleetSupervisor(cfg, model_factory=_model).start()
        assert fleet.wait_eligible(2, timeout_s=15)
        pub = ModelPublisher(port=broker.port)
        poison = _publish(pub, tmp_path, 1, np.nan)
        assert _wait(lambda: any(v == poison["version"]
                                 for v, _ in fleet.rollout.outcomes),
                     timeout_s=30), fleet.rollout.state()
        # the very next good publish must still roll out fleet-wide
        rec2 = _publish(pub, tmp_path, 2, 2000.0)
        assert _wait(lambda: _versions_converged(fleet, rec2["version"]),
                     timeout_s=30), (fleet.model_versions(),
                                     fleet.rollout.state())
        assert ((rec2["version"], "promoted")
                in fleet.rollout.outcomes), fleet.rollout.outcomes
        pub.close()
    finally:
        if fleet is not None:
            fleet.stop(drain_s=2.0)
        broker.shutdown()


@pytest.mark.chaos
def test_kill_canary_mid_rollout_aborts_and_reconverges(tmp_path, zoo_ctx):
    """Canary hard-killed during its validation window: the rollout aborts
    cleanly, the respawned replica reconciles onto the STABLE version, the
    fleet re-converges, and no client request is lost."""
    broker = start_broker()
    fleet = None
    try:
        # window comfortably wider than kill-scheduling jitter + the 0.8s
        # failover staleness, so the death is CONFIRMED inside the window
        # (the controller's hb-freshness gate covers the tail either way)
        cfg = _cfg(broker, replicas=3, rollout_window_s=2.5)
        fleet = FleetSupervisor(cfg, model_factory=_model).start()
        assert fleet.wait_eligible(3, timeout_s=15)
        pub = ModelPublisher(port=broker.port)
        rec1 = _publish(pub, tmp_path, 1, 1000.0)
        assert _wait(lambda: _versions_converged(fleet, rec1["version"]),
                     timeout_s=30)
        with _Load(broker.port, n_threads=3) as load:
            time.sleep(0.2)
            rec2 = _publish(pub, tmp_path, 2, 2000.0)
            canary = {}

            def in_validation():
                st = fleet.rollout.state()
                if st["phase"] in ("canary", "validating") and st["canary"] \
                        and st["target"] == rec2["version"]:
                    canary["rid"] = st["canary"]
                    return st["phase"] == "validating"
                return False

            assert _wait(in_validation, timeout_s=15), fleet.rollout.state()
            fleet.kill_replica(canary["rid"])
            assert _wait(lambda: any(v == rec2["version"]
                                     for v, _ in fleet.rollout.outcomes),
                         timeout_s=30), fleet.rollout.state()
            # aborted (canary died), never promoted
            outcome = dict(fleet.rollout.outcomes)[rec2["version"]]
            assert outcome in ("aborted", "rolled_back")
            # reconverge: respawned canary reconciled back to the stable
            # version, all replicas eligible again
            assert _wait(lambda: _versions_converged(fleet, rec1["version"])
                         and len(fleet.router.eligible_ids()) == 3,
                         timeout_s=30), (fleet.model_versions(),
                                         fleet.router.stats())
            time.sleep(0.3)
        # canary legitimately served some rec2-weighted traffic pre-kill
        load.check_zero_loss({"initial": 0.0, rec1["version"]: 1000.0,
                              rec2["version"]: 2000.0})
        assert fleet.respawns >= 1
        pub.close()
    finally:
        if fleet is not None:
            fleet.stop(drain_s=2.0)
        broker.shutdown()


@pytest.mark.chaos
def test_kill_engine_mid_swap_respawns_on_correct_version(tmp_path, zoo_ctx):
    """Chaos kill INSIDE staging (the swap.stage site): the replica dies
    mid-swap, the supervisor respawns it, and the respawn converges on the
    CORRECT (stable) version via the reconciler — not the half-applied one,
    not the boot params."""
    from analytics_zoo_tpu.common.chaos import ChaosSchedule

    broker = start_broker()
    fleet = None
    try:
        cfg = _cfg(broker, replicas=2)
        fleet = FleetSupervisor(cfg, model_factory=_model).start()
        assert fleet.wait_eligible(2, timeout_s=15)
        pub = ModelPublisher(port=broker.port)
        rec1 = _publish(pub, tmp_path, 1, 1000.0)
        assert _wait(lambda: _versions_converged(fleet, rec1["version"]),
                     timeout_s=30)
        # occurrence counters start at schedule install (post-convergence),
        # so the canary's staging of v2 is the FIRST swap.stage hit; the
        # respawn's reconcile staging (occurrence 2+) must succeed
        sched = ChaosSchedule(seed=3).kill("swap.stage", at=1)
        with sched:
            rec2 = _publish(pub, tmp_path, 2, 2000.0)
            # the canary dies mid-swap -> rollout aborts -> respawn
            assert _wait(lambda: any(v == rec2["version"]
                                     for v, _ in fleet.rollout.outcomes),
                         timeout_s=30), fleet.rollout.state()
            assert _wait(lambda: fleet.respawns >= 1, timeout_s=20)
            # respawn comes back, reconciler re-issues the CURRENT version
            # (chaos rule is spent: occurrence 4+ stages fine)
            assert _wait(lambda: _versions_converged(fleet, rec1["version"])
                         and len(fleet.router.eligible_ids()) == 2,
                         timeout_s=30), (fleet.model_versions(),
                                         fleet.rollout.state())
        iq, oq = InputQueue(port=broker.port), OutputQueue(port=broker.port)
        u = iq.enqueue(None, input=np.full((4,), 1.0, np.float32))
        assert float(np.ravel(oq.query(u, timeout_s=20))[0]) == 1004.0
        assert oq.last_model_version == rec1["version"]
        iq.close()
        oq.close()
        pub.close()
    finally:
        if fleet is not None:
            fleet.stop(drain_s=2.0)
        broker.shutdown()


def test_replica_joining_mid_history_reconciles(tmp_path, zoo_ctx):
    """A replica respawned AFTER a promotion (its boot params are stale)
    converges on model:current without any new publish."""
    broker = start_broker()
    fleet = None
    try:
        cfg = _cfg(broker, replicas=2)
        fleet = FleetSupervisor(cfg, model_factory=_model).start()
        assert fleet.wait_eligible(2, timeout_s=15)
        pub = ModelPublisher(port=broker.port)
        rec = _publish(pub, tmp_path, 1, 1000.0)
        assert _wait(lambda: _versions_converged(fleet, rec["version"]),
                     timeout_s=30)
        fleet.kill_replica("r1")        # respawns on boot (b=0) params
        assert _wait(lambda: fleet.respawns >= 1, timeout_s=20)
        assert _wait(lambda: _versions_converged(fleet, rec["version"])
                     and len(fleet.router.eligible_ids()) == 2,
                     timeout_s=30), fleet.model_versions()
        pub.close()
    finally:
        if fleet is not None:
            fleet.stop(drain_s=2.0)
        broker.shutdown()


# ---------------------------------------------------------------------------
# config plumbing
# ---------------------------------------------------------------------------

def test_config_yaml_rollout_section(tmp_path):
    p = tmp_path / "rollout.yaml"
    p.write_text("""
model:
  path: /models/m
rollout:
  enabled: true
  canary_fraction: 0.1
  window_s: 5.0
  min_requests: 32
  max_error_delta: 0.01
  max_latency_ratio: 2.0
""")
    cfg = ServingConfig.from_yaml(str(p))
    assert cfg.hot_swap is True
    assert cfg.rollout_canary_fraction == 0.1
    assert cfg.rollout_window_s == 5.0
    assert cfg.rollout_min_requests == 32
    assert cfg.rollout_max_error_delta == 0.01
    assert cfg.rollout_max_latency_ratio == 2.0

    off = tmp_path / "off.yaml"
    off.write_text("model:\n  path: /m\nrollout:\n  enabled: false\n")
    assert ServingConfig.from_yaml(str(off)).hot_swap is False

    bad = tmp_path / "bad.yaml"
    bad.write_text("rollout:\n  canary_fraction: 1.5\n")
    with pytest.raises(ValueError, match="canary_fraction"):
        ServingConfig.from_yaml(str(bad))
