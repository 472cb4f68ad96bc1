"""Cluster-serving tests: broker primitives, client enqueue/dequeue round-trip,
the streaming engine end-to-end, topN post-processing, and the HTTP frontend.

Mirrors the reference serving specs (zoo/src/test/.../serving/) on a single box.
"""

import json
import threading
import urllib.request

import numpy as np
import pytest

from analytics_zoo_tpu.nn import Sequential
from analytics_zoo_tpu.nn import layers as L
from analytics_zoo_tpu.serving import (ClusterServing, FrontEndApp, InputQueue,
                                       OutputQueue, ServingConfig, start_broker)
from analytics_zoo_tpu.serving.schema import decode_payload, encode_payload

pytestmark = pytest.mark.serving


@pytest.fixture(scope="module")
def broker():
    b = start_broker()
    yield b
    b.shutdown()


@pytest.fixture(scope="module")
def fitted():
    model = Sequential([L.Dense(16, activation="relu", input_shape=(8,)),
                        L.Dense(4, activation="softmax")])
    model.compile(optimizer="adam", loss="categorical_crossentropy")
    rng = np.random.default_rng(0)
    x = rng.normal(size=(64, 8)).astype(np.float32)
    y = np.eye(4, dtype=np.float32)[rng.integers(0, 4, 64)]
    model.fit(x, y, batch_size=16, nb_epoch=1)
    return model, x


def test_payload_roundtrip():
    data = {"a": np.arange(6, dtype=np.float32).reshape(2, 3),
            "s": "hello", "n": 3}
    back = decode_payload(json.loads(json.dumps(encode_payload(data))))
    np.testing.assert_array_equal(back["a"], data["a"])
    assert back["s"] == "hello" and back["n"] == 3


def test_broker_stream_and_hash(broker):
    from analytics_zoo_tpu.serving.client import _Conn

    c = _Conn("127.0.0.1", broker.port)
    c.call("XADD", "s1", {"v": 1})
    c.call("XADD", "s1", {"v": 2})
    got = c.call("XREADGROUP", "s1", "g1", 10, 100)
    assert [p["v"] for _, p in got] == [1, 2]
    # consumer-group semantics: a second read from the same group gets nothing
    assert c.call("XREADGROUP", "s1", "g1", 10, 10) == []
    # ... but a different group replays from the start
    got2 = c.call("XREADGROUP", "s1", "g2", 10, 100)
    assert len(got2) == 2
    c.call("HSET", "k", {"x": 5})
    assert c.call("HGET", "k", 0) == {"x": 5}
    c.call("HDEL", "k")
    assert c.call("HGET", "k", 0) is None
    c.close()


def test_broker_xaddm_appends_in_order_with_one_wake_up(broker):
    """``XADDM``: every ``(stream, payload)`` appended in order under ids
    that rise, readers of each stream see their own entries, and a reader
    blocked in ``XREAD`` wakes for the batch that holds its entry."""
    from analytics_zoo_tpu.serving.client import _Conn

    c = _Conn("127.0.0.1", broker.port)
    woke = []

    def blocked():
        r = _Conn("127.0.0.1", broker.port)
        woke.append(r.call("XREAD", "m:b", 0, 64, 5000))
        r.close()

    t = threading.Thread(target=blocked)
    t.start()
    ids = c.call("XADDM", [["m:a", {"v": 1}], ["m:b", {"v": 2}],
                           ["m:a", {"v": 3}]])
    t.join(timeout=10)
    assert len(ids) == 3 and \
        [int(i.split("-")[0]) for i in ids] == sorted(
            int(i.split("-")[0]) for i in ids)
    assert woke == [[1, [[ids[1], {"v": 2}]]]]
    cursor, got = c.call("XREAD", "m:a", 0, 64, 0)
    assert cursor == 2 and [p["v"] for _, p in got] == [1, 3]
    assert c.call("XADDM", []) == []
    assert c.call("INFO")["commands"]["XADDM"] == 2
    c.close()


def test_serving_end_to_end(zoo_ctx, broker, fitted):
    model, x = fitted
    cfg = ServingConfig(batch_size=8, concurrent_num=2,
                        queue_port=broker.port)
    job = ClusterServing(model, cfg).start()
    try:
        iq = InputQueue(port=broker.port)
        oq = OutputQueue(port=broker.port)
        uris = [iq.enqueue(None, input=x[i]) for i in range(20)]
        want = model.predict(x[:20])
        for i, uri in enumerate(uris):
            got = oq.query(uri, timeout_s=30)
            np.testing.assert_allclose(got, want[i], rtol=1e-4, atol=1e-5)
        # sink increments `served` just after the HSET a query saw: poll briefly
        import time
        t0 = time.time()
        while job.served < 20 and time.time() - t0 < 5:
            time.sleep(0.01)
        assert job.served >= 20
        iq.close(); oq.close()
    finally:
        job.stop()


def test_serving_topn(zoo_ctx, broker, fitted):
    model, x = fitted
    cfg = ServingConfig(batch_size=4, queue_port=broker.port, top_n=2)
    job = ClusterServing(model, cfg, group="topn").start()
    try:
        iq = InputQueue(port=broker.port)
        oq = OutputQueue(port=broker.port)
        uri = iq.enqueue(None, input=x[0])
        res = oq.query(uri, timeout_s=30)
        assert res.shape == (2, 2)  # (index, value) pairs
        probs = model.predict(x[:1])[0]
        assert int(res[0, 0]) == int(np.argmax(probs))
        assert res[0, 1] >= res[1, 1]
        iq.close(); oq.close()
    finally:
        job.stop()


def test_serving_bad_record_reports_error(zoo_ctx, broker, fitted):
    model, _ = fitted
    cfg = ServingConfig(batch_size=4, queue_port=broker.port)
    job = ClusterServing(model, cfg, group="errs").start()
    try:
        iq = InputQueue(port=broker.port)
        oq = OutputQueue(port=broker.port)
        uri = iq.enqueue(None, input=np.zeros((3,), np.float32))  # wrong shape
        with pytest.raises(RuntimeError, match="serving error"):
            oq.query(uri, timeout_s=30)
        iq.close(); oq.close()
    finally:
        job.stop()


def test_dequeue_scan_and_malformed_record(zoo_ctx, broker, fitted):
    from analytics_zoo_tpu.serving.client import _Conn

    model, x = fitted
    cfg = ServingConfig(batch_size=4, queue_port=broker.port)
    job = ClusterServing(model, cfg, group="scan").start()
    try:
        iq = InputQueue(port=broker.port)
        oq = OutputQueue(port=broker.port)
        # a malformed record must not kill the source loop
        raw = _Conn("127.0.0.1", broker.port)
        raw.call("XADD", "serving_stream",
                 {"uri": "bad1", "data": {"input": {"__ndarray__": "!!notb64"}}})
        raw.close()
        good = [iq.enqueue(None, input=x[i]) for i in range(3)]
        for u in good:
            oq.register(u)
        oq.register("bad1")
        deadline = 30
        import time
        got = {}
        t0 = time.time()
        while len(got) < 4 and time.time() - t0 < deadline:
            got.update(oq.dequeue())   # non-blocking scan
            time.sleep(0.05)
        assert set(got) == set(good) | {"bad1"}
        assert isinstance(got["bad1"], dict) and "error" in got["bad1"]
        want = model.predict(x[:3])
        for i, u in enumerate(good):
            np.testing.assert_allclose(got[u], want[i], rtol=1e-4, atol=1e-5)
        iq.close(); oq.close()
    finally:
        job.stop()


def test_broker_stream_trimming():
    from analytics_zoo_tpu.serving.broker import _Store

    st = _Store(maxlen=10)
    for i in range(25):
        st.xadd("s", {"v": i})
    assert st.slen("s") == 10
    got = st.xreadgroup("s", "g", 100, 0)
    assert [p["v"] for _, p in got] == list(range(15, 25))


def test_http_frontend(zoo_ctx, broker, fitted):
    model, x = fitted
    cfg = ServingConfig(batch_size=8, queue_port=broker.port)
    job = ClusterServing(model, cfg, group="http").start()
    app = FrontEndApp(cfg, port=0).start()
    try:
        req = urllib.request.Request(
            f"http://127.0.0.1:{app.port}/predict",
            data=json.dumps({"instances": [
                {"input": x[0].tolist()}, {"input": x[1].tolist()}
            ]}).encode(), headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=30) as r:
            body = json.loads(r.read())
        preds = np.asarray(body["predictions"])
        np.testing.assert_allclose(preds, model.predict(x[:2]),
                                   rtol=1e-4, atol=1e-5)
        # liveness + metrics
        with urllib.request.urlopen(
                f"http://127.0.0.1:{app.port}/", timeout=10) as r:
            assert "welcome" in json.loads(r.read())["message"]
        with urllib.request.urlopen(
                f"http://127.0.0.1:{app.port}/metrics.json", timeout=10) as r:
            assert "http.predict" in json.loads(r.read())
        # the Prometheus twin parses and carries the same request span
        from analytics_zoo_tpu.common.telemetry import parse_prometheus
        with urllib.request.urlopen(
                f"http://127.0.0.1:{app.port}/metrics", timeout=10) as r:
            fams = parse_prometheus(r.read().decode())
        assert any(l.get("span") == "serving.http.predict"
                   for _n, l, _v
                   in fams["zoo_span_duration_seconds"]["samples"])
    finally:
        app.stop()
        job.stop()


def test_http_direct_mode_microbatches_across_requests(zoo_ctx, fitted):
    """Concurrent batch-1 HTTP requests must coalesce into shared predict
    batches (FrontEndApp actor-batching parity) — fewer model invocations than
    requests, same numerics as sequential predict."""
    model, x = fitted
    calls = {"n": 0, "sizes": []}
    real_predict = model.predict

    def counting_predict(batch):
        calls["n"] += 1
        calls["sizes"].append(np.asarray(batch).shape[0])
        return real_predict(batch)

    n_req = 24
    app = FrontEndApp(ServingConfig(), port=0, model=counting_predict,
                      max_batch=16, max_delay_ms=60.0).start()
    try:
        want = np.asarray(model.predict(x[:n_req]))
        results = [None] * n_req
        errors = []

        def client(i):
            try:
                req = urllib.request.Request(
                    f"http://127.0.0.1:{app.port}/predict",
                    data=json.dumps({"instances": [
                        {"input": x[i].tolist()}]}).encode(),
                    headers={"Content-Type": "application/json"})
                with urllib.request.urlopen(req, timeout=30) as r:
                    results[i] = np.asarray(
                        json.loads(r.read())["predictions"][0])
            except Exception as e:  # pragma: no cover
                errors.append(e)

        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(n_req)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert not errors, errors
        for i in range(n_req):
            np.testing.assert_allclose(results[i], want[i], rtol=1e-4,
                                       atol=1e-5)
        # the batching claim itself: far fewer predict calls than requests
        assert calls["n"] < n_req / 2, (calls, app._batcher.stats())
        assert max(calls["sizes"]) >= 4
        # /metrics.json surfaces batching stats in direct mode
        with urllib.request.urlopen(
                f"http://127.0.0.1:{app.port}/metrics.json", timeout=10) as r:
            stats = json.loads(r.read())
        assert stats["batching"]["records"] == n_req
        assert stats["batching"]["mean_batch_size"] > 1.0
    finally:
        app.stop()


def test_microbatcher_heterogeneous_shapes_and_errors(zoo_ctx):
    from analytics_zoo_tpu.serving.batching import MicroBatcher

    def predict(b):
        arr = np.asarray(b)
        if arr.shape[-1] == 3:
            raise RuntimeError("bad shape three")
        return arr * 2

    mb = MicroBatcher(predict, max_batch=8, max_delay_ms=30.0)
    try:
        s1 = mb.submit_async({"x": np.ones(2, np.float32)})
        s2 = mb.submit_async({"x": np.full(4, 3.0, np.float32)})
        s3 = mb.submit_async({"x": np.ones(3, np.float32)})  # will error
        np.testing.assert_allclose(mb.wait(s1), [2, 2])
        np.testing.assert_allclose(mb.wait(s2), [6, 6, 6, 6])
        with pytest.raises(RuntimeError, match="three"):
            mb.wait(s3)
    finally:
        mb.close()


def test_config_yaml_reference_layout(tmp_path):
    p = tmp_path / "config.yaml"
    p.write_text("""
model:
  path: /models/ncf
params:
  batchSize: 64
  coreNum: 8
redis:
  host: 1.2.3.4
  port: 9999
postprocessing:
  topN: 5
""")
    cfg = ServingConfig.from_yaml(str(p))
    assert cfg.model_path == "/models/ncf"
    assert cfg.batch_size == 64 and cfg.concurrent_num == 8
    assert cfg.queue_host == "1.2.3.4" and cfg.queue_port == 9999
    assert cfg.top_n == 5


def test_config_yaml_graph_checks_bare_off(tmp_path):
    # YAML 1.1 parses bare off/on as booleans; the policy string must
    # survive (an operator's explicit opt-out must actually disable)
    for raw, want in (("off", "off"), ("on", "warn"),
                      ("warn", "warn"), ("raise", "raise")):
        p = tmp_path / f"gc_{raw}.yaml"
        p.write_text(f"graph_checks: {raw}\n")
        assert ServingConfig.from_yaml(str(p)).graph_checks == want
    # a typo'd policy fails at parse time, not silently at warmup
    p = tmp_path / "gc_bad.yaml"
    p.write_text("graph_checks: enforce\n")
    with pytest.raises(ValueError, match="graph_checks"):
        ServingConfig.from_yaml(str(p))
