"""Serving throughput/latency benchmark — the serving-side analog of bench.py.

Measures the HTTP frontend in direct micro-batching mode (FrontEndApp +
MicroBatcher + InferenceModel bucketed-jit predict) under concurrent batch-1
clients — the reference's Cluster-Serving operating point
(docs ClusterServingGuide/ProgrammingGuide.md:259 batch-size guidance; no
absolute numbers are published, so this artifact records ours).

Prints ONE JSON line:
  {"metric": "serving throughput", "value": rps, "unit": "req/s",
   "p50_ms": ..., "p99_ms": ..., "mean_batch": ..., ...}
and writes the same object to SERVING_BENCH.json.
"""

from __future__ import annotations

import json
import os
import sys
import threading
import time
import numpy as np

N_CLIENTS = int(os.environ.get("ZOO_SERVING_BENCH_CLIENTS", "16"))
REQUESTS_PER_CLIENT = int(os.environ.get("ZOO_SERVING_BENCH_REQUESTS", "40"))
FEATURES = 256
HIDDEN = 1024
CLASSES = 128


def measure_dispatch_rtt_ms(n: int = 20) -> float:
    """Median latency of a trivial dispatch+sync (1-element add): the floor
    under every request. Recording it lets the artifact separate framework
    cost from dispatch cost: the HTTP closed-loop throughput is capped at
    ``mean_batch × in_flight / rtt`` regardless of model speed."""
    import jax
    import jax.numpy as jnp

    one = jnp.ones((1,), jnp.float32)
    f = jax.jit(lambda a: a + 1.0)
    float(f(one)[0])  # compile
    samples = []
    for _ in range(n):
        t0 = time.perf_counter()
        float(f(one)[0])
        samples.append((time.perf_counter() - t0) * 1e3)
    return round(float(np.median(samples)), 3)


def build_model():
    import jax

    from analytics_zoo_tpu.inference import InferenceModel
    from analytics_zoo_tpu.nn import Sequential
    from analytics_zoo_tpu.nn import layers as L

    model = Sequential([
        L.Dense(HIDDEN, activation="relu", input_shape=(FEATURES,)),
        L.Dense(HIDDEN, activation="relu"),
        L.Dense(CLASSES, activation="softmax"),
    ])
    model.compile(optimizer="adam", loss="categorical_crossentropy")
    rng = np.random.default_rng(0)
    x = rng.normal(size=(256, FEATURES)).astype(np.float32)
    y = np.eye(CLASSES, dtype=np.float32)[rng.integers(0, CLASSES, 256)]
    model.fit(x, y, batch_size=64, nb_epoch=1)
    # batch ceiling 256: headroom so the pipelined leg (and any env-raised
    # client count) coalesces its whole in-flight set into one dispatch —
    # predict() must never chunk a coalesced micro-batch
    return InferenceModel(max_batch_size=max(256, N_CLIENTS * 2)).load(model)


def run_bench(im=None, n_clients: int = N_CLIENTS,
              requests_per_client: int = REQUESTS_PER_CLIENT,
              max_delay_ms: float = 2.0) -> dict:
    from analytics_zoo_tpu.serving import FrontEndApp, ServingConfig

    if im is None:
        im = build_model()
    # never coalesce past the model's own batch ceiling — a bigger micro-batch
    # would be chunked into multiple serial dispatches inside predict(),
    # paying one dispatch per chunk and defeating the amortization
    coalesce = min(n_clients * 2, im.max_batch_size)
    app = FrontEndApp(ServingConfig(), port=0, model=im,
                      max_batch=coalesce, max_delay_ms=max_delay_ms).start()
    rng = np.random.default_rng(1)
    payloads = [json.dumps({"instances": [
        {"input": rng.normal(size=FEATURES).astype(np.float32).tolist()}
    ]}).encode() for _ in range(n_clients)]

    import http.client

    def one_request(conn, payload):
        t0 = time.perf_counter()
        conn.request("POST", "/predict", body=payload,
                     headers={"Content-Type": "application/json"})
        resp = conn.getresponse()
        body = resp.read()
        if resp.status != 200:
            raise RuntimeError(f"HTTP {resp.status}: {body[:200]!r}")
        json.loads(body)
        return (time.perf_counter() - t0) * 1000.0

    # warm every bucketed executable the micro-batcher can hit — otherwise
    # first-use XLA compiles land inside the measured window
    rng_w = np.random.default_rng(2)
    from analytics_zoo_tpu.inference.inference_model import _buckets
    for b in [b for b in _buckets(im.max_batch_size) if b <= coalesce] + [coalesce]:
        im.predict(rng_w.normal(size=(b, FEATURES)).astype(np.float32))
    warm = http.client.HTTPConnection("127.0.0.1", app.port, timeout=60)
    for p in payloads[:2]:
        one_request(warm, p)
    warm.close()

    latencies: list = []
    failures: list = []
    lock = threading.Lock()

    def client(idx):
        # persistent connection per client (HTTP/1.1 keep-alive) — the
        # realistic load-test shape; reconnect on error
        conn = http.client.HTTPConnection("127.0.0.1", app.port, timeout=60)
        for _ in range(requests_per_client):
            try:
                ms = one_request(conn, payloads[idx])
            except Exception as e:
                with lock:
                    failures.append(repr(e))
                conn.close()
                conn = http.client.HTTPConnection("127.0.0.1", app.port,
                                                  timeout=60)
                continue
            with lock:
                latencies.append(ms)
        conn.close()

    t0 = time.perf_counter()
    threads = [threading.Thread(target=client, args=(i,))
               for i in range(n_clients)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall = time.perf_counter() - t0
    # scrape /metrics while the app is still up: the quick gate asserts the
    # exposition parses as Prometheus text and carries the request-span
    # histogram (run_quick checks metrics_scrape below)
    metrics_scrape = {"valid": False, "families": 0,
                      "has_request_span_histogram": False}
    try:
        conn = http.client.HTTPConnection("127.0.0.1", app.port, timeout=10)
        conn.request("GET", "/metrics")
        resp = conn.getresponse()
        text = resp.read().decode("utf-8")
        conn.close()
        from analytics_zoo_tpu.common.telemetry import parse_prometheus

        families = parse_prometheus(text)
        hist = families.get("zoo_span_duration_seconds", {})
        metrics_scrape = {
            "valid": resp.status == 200,
            "families": len(families),
            "has_request_span_histogram": hist.get("type") == "histogram"
            and any(l.get("span") == "serving.http.predict"
                    for _n, l, _v in hist.get("samples", ())),
        }
    except Exception as e:
        metrics_scrape["error"] = repr(e)
    app.stop()

    stats = app._batcher.stats()
    if not latencies:
        return {"metric": "serving throughput (HTTP, micro-batched)",
                "value": 0.0, "unit": "req/s", "requests": 0,
                "failed_requests": len(failures),
                "first_failure": failures[0] if failures else None}
    lat = np.asarray(latencies)
    n = len(latencies)
    return {
        "metric": "serving throughput (HTTP, micro-batched)",
        "value": round(n / wall, 1),
        "unit": "req/s",
        "requests": n,
        "failed_requests": len(failures),
        "clients": n_clients,
        "wall_seconds": round(wall, 3),
        "p50_ms": round(float(np.percentile(lat, 50)), 2),
        "p95_ms": round(float(np.percentile(lat, 95)), 2),
        "p99_ms": round(float(np.percentile(lat, 99)), 2),
        "mean_batch": round(stats["mean_batch_size"], 2),
        "max_batch": stats["max_batch_size"],
        "predict_calls": stats["batches"],
        # shape-bucketing evidence: distinct batch shapes the batcher emitted
        # and executables the engine compiled — both bounded by the bucket
        # ladder under mixed-size traffic (no mid-stream XLA recompiles)
        "distinct_batch_shapes": stats["distinct_batch_shapes"],
        "padded_rows": stats["padded_rows"],
        "compiled_shapes": im.compile_stats()["compiled_shapes"],
        "metrics_scrape": metrics_scrape,
    }


def run_wire_bench(payload_mb: float = 1.0, iters: int = 15) -> dict:
    """Data-plane microbench: one ``payload_mb`` tensor HSET+HGET round trip
    through the broker under (a) the legacy base64-JSON envelope, (b) binary
    frames over the socket, (c) binary frames with the same-host shm ring —
    the artifact that shows the wire rebuild, independent of model/XLA time."""
    from analytics_zoo_tpu.serving import start_broker
    from analytics_zoo_tpu.serving.client import _Conn
    from analytics_zoo_tpu.serving.schema import decode_payload, encode_payload
    from analytics_zoo_tpu.serving.wire import wire_stats

    n_elem = int(payload_mb * (1 << 20)) // 4
    arr = np.random.default_rng(0).normal(size=(n_elem,)).astype(np.float32)
    broker = start_broker()

    def median_ms(fn):
        fn()                                  # warm (incl. shm negotiation)
        samples = []
        for _ in range(iters):
            t0 = time.perf_counter()
            fn()
            samples.append((time.perf_counter() - t0) * 1e3)
        return round(float(np.median(samples)), 3)

    try:
        cj = _Conn("127.0.0.1", broker.port)

        def legacy_json():
            cj.call("HSET", "wj", encode_payload({"v": arr}))
            decode_payload(cj.call("HGET", "wj", 0))

        json_ms = median_ms(legacy_json)
        cj.close()

        cs = _Conn("127.0.0.1", broker.port, shm_mode="off")

        def binary_socket():
            cs.call("HSET", "wb", {"v": arr})
            cs.call("HGET", "wb", 0)

        socket_ms = median_ms(binary_socket)
        cs.close()

        shm0 = wire_stats()["shm_bytes"]
        ch = _Conn("127.0.0.1", broker.port)

        def binary_shm():
            ch.call("HSET", "ws", {"v": arr})
            ch.call("HGET", "ws", 0)

        shm_ms = median_ms(binary_shm)
        shm_used = wire_stats()["shm_bytes"] - shm0
        ch.close()
    finally:
        broker.shutdown()
    return {
        "payload_mb": payload_mb, "iters": iters,
        "json_rtt_ms": json_ms,
        "binary_rtt_ms": socket_ms,
        "binary_shm_rtt_ms": shm_ms,
        "binary_speedup_vs_json": round(json_ms / socket_ms, 2),
        "shm_speedup_vs_json": round(json_ms / shm_ms, 2),
        "shm_ring_used": shm_used > 0,
    }


INT8_HIDDEN = int(os.environ.get("ZOO_INT8_BENCH_HIDDEN", "0"))  # 0 = auto
INT8_BATCH = int(os.environ.get("ZOO_INT8_BENCH_BATCH", "0"))
INT8_ITERS = max(1, int(os.environ.get("ZOO_INT8_BENCH_ITERS", "30")))


def _int8_bench_shape() -> tuple:
    """(hidden, batch): big enough that the matmuls dominate the device loop.

    At 2048×4096 the elementwise/quant overhead caps the int8 gain at ~1.08×
    on a v5e; at 8192×8192 the MXU path is the bulk of the time, which is
    what the reference's OpenVINO int8 claim is about. The CPU fallback keeps
    the small shape (8192³ matmuls would take hours on the 1-core box)."""
    if INT8_HIDDEN and INT8_BATCH:
        return INT8_HIDDEN, INT8_BATCH
    import jax

    big = jax.default_backend() != "cpu"
    return (INT8_HIDDEN or (8192 if big else 4096),
            INT8_BATCH or (8192 if big else 2048))


def run_int8_bench() -> dict:
    """Int8 MXU compute vs the float predict path (the reference's OpenVINO
    int8 "up to 2× speedup, <0.1% accuracy drop" claim — wp-bigdl.md:192).
    Compute-bound MLP so the matmul path dominates, not dispatch."""
    from analytics_zoo_tpu.inference import InferenceModel
    from analytics_zoo_tpu.nn import Sequential
    from analytics_zoo_tpu.nn import layers as L

    hidden, batch = _int8_bench_shape()

    def build():
        m = Sequential([
            L.Dense(hidden, activation="relu", input_shape=(hidden,)),
            L.Dense(hidden, activation="relu"),
            L.Dense(CLASSES, activation="softmax"),
        ])
        m.compile(optimizer="adam", loss="categorical_crossentropy")
        rng = np.random.default_rng(0)
        xw = rng.normal(size=(64, hidden)).astype(np.float32)
        yw = np.eye(CLASSES, dtype=np.float32)[rng.integers(0, CLASSES, 64)]
        m.fit(xw, yw, batch_size=64, nb_epoch=1)
        return m

    model = build()
    x = np.random.default_rng(3).normal(
        size=(batch, hidden)).astype(np.float32)

    def measure_dispatch(im):
        """Per-``predict`` wall time: includes host↔device transfer of the
        (B, H) input and (B, C) output every call, so this is the
        *serving-path* number, not the compute number. Few iterations
        suffice: at the TPU shape each call moves ~256 MB."""
        n = min(INT8_ITERS, 5) if x.nbytes > 2 ** 26 else INT8_ITERS
        im.predict(x)                       # compile + warm
        t0 = time.perf_counter()
        for _ in range(n):
            out = im.predict(x)
        return (time.perf_counter() - t0) / n, out, n

    def measure_device(im):
        """Device-resident compute time: the input lives in HBM and the
        iterations chain inside ONE compiled program (``fori_loop`` with a
        non-eliminable data dependency between steps), closed by a single
        host sync. This isolates the MXU int8-vs-bf16 question from
        dispatch and host↔device transfer — the number the reference's
        OpenVINO "up to 2× int8 speedup" claim is about."""
        import jax
        import jax.numpy as jnp

        apply, params, state = im.device_apply()
        xd = jax.device_put(jnp.asarray(x))

        def loop(params, state, x0):
            def body(_, carry):
                xc, acc = carry
                y = apply(params, state, xc)
                # serialize iterations: next input depends on this output by
                # an amount too small to change values but opaque to DCE
                eps = jnp.max(y).astype(jnp.float32) * 1e-30
                return (x0 + eps, acc + eps)

            _, acc = jax.lax.fori_loop(0, INT8_ITERS, body,
                                       (x0, jnp.float32(0)))
            return acc

        # AOT-compile so warmup doesn't execute the full loop, then one warm
        # run (device-resident, cheap) before the timed one
        compiled = jax.jit(loop).lower(params, state, xd).compile()
        float(compiled(params, state, xd))
        t0 = time.perf_counter()
        float(compiled(params, state, xd))
        return (time.perf_counter() - t0) / INT8_ITERS

    # the baseline is the bf16 MXU path — the honest comparison point
    # (f32 would flatter the int8 speedup 2×)
    from analytics_zoo_tpu.nn.module import compute_dtype, set_policy

    prev = compute_dtype()
    set_policy(compute_dtype="bfloat16")
    try:
        im_f = InferenceModel(max_batch_size=batch).load(model)
        t_float, out_f, n_disp = measure_dispatch(im_f)
        dev_float = measure_device(im_f)
        im_q = InferenceModel(max_batch_size=batch).load(model)
        im_q.quantize_int8()
        t_int8, out_q, _ = measure_dispatch(im_q)
        dev_int8 = measure_device(im_q)
    finally:
        set_policy(compute_dtype=prev)
    out_f = np.asarray(out_f, np.float32)
    out_q = np.asarray(out_q, np.float32)

    agree = float((out_f.argmax(-1) == out_q.argmax(-1)).mean())
    return {
        # headline = device compute (what int8-on-MXU is about); the
        # dispatch_* rows keep the end-to-end predict() cost incl. transfer
        "device_speedup_vs_bf16": round(dev_float / dev_int8, 3),
        # measurement note: through round 3 "speedup_vs_bf16" meant the
        # end-to-end predict() speedup at batch 4096 / hidden 2048; from
        # round 4 the headline is device-resident compute at 8192/8192 and
        # the old end-to-end quantity lives in dispatch_speedup_vs_bf16 —
        # don't compare this key across rounds without checking the schema
        "measurement": "device_resident_compute",
        "bf16_ms": round(dev_float * 1e3, 3),
        "int8_ms": round(dev_int8 * 1e3, 3),
        "dispatch_speedup_vs_bf16": round(t_float / t_int8, 3),
        "dispatch_bf16_ms": round(t_float * 1e3, 3),
        "dispatch_int8_ms": round(t_int8 * 1e3, 3),
        "batch": batch, "hidden": hidden, "iters": INT8_ITERS,
        "dispatch_iters": n_disp,
        "argmax_agreement": agree,
        "max_prob_diff": round(float(np.max(np.abs(out_f - out_q))), 5),
    }


QUICK_RTT_THRESHOLD_MS = float(os.environ.get("ZOO_SERVING_QUICK_RTT_MS",
                                              "15"))


def run_quick() -> int:
    """CI smoke mode (scripts/run_serving_bench.sh --quick): a small HTTP run
    plus the dispatch-RTT probe; asserts 0 failed requests, the dispatch RTT
    under threshold, and the bucket invariant (compiled shapes bounded by the
    bucket ladder). Never touches SERVING_BENCH.json."""
    import jax

    jax.config.update("jax_platforms", "cpu")
    im = build_model()
    result = run_bench(im, n_clients=4, requests_per_client=8)
    result["dispatch_rtt_ms"] = measure_dispatch_rtt_ms(n=10)
    result["wire"] = run_wire_bench(payload_mb=0.5, iters=5)
    print(json.dumps(result))
    from analytics_zoo_tpu.inference.inference_model import _buckets

    failures = []
    if result.get("failed_requests", 1):
        failures.append(f"failed_requests={result.get('failed_requests')}")
    rtt = result["dispatch_rtt_ms"]
    if rtt is None or rtt >= QUICK_RTT_THRESHOLD_MS:
        failures.append(f"dispatch_rtt_ms={rtt} >= {QUICK_RTT_THRESHOLD_MS}")
    if result["compiled_shapes"] > len(_buckets(im.max_batch_size)):
        failures.append(f"compiled_shapes={result['compiled_shapes']} exceeds "
                        f"the bucket ladder")
    scrape = result.get("metrics_scrape") or {}
    if not scrape.get("valid"):
        failures.append(f"/metrics scrape invalid: {scrape}")
    if not scrape.get("has_request_span_histogram"):
        failures.append("/metrics lacks the request-span histogram "
                        "(zoo_span_duration_seconds{span=serving.http."
                        "predict})")
    if failures:
        print(f"[serving_bench --quick] FAIL: {'; '.join(failures)}",
              file=sys.stderr)
        return 1
    print("[serving_bench --quick] OK", file=sys.stderr)
    return 0


if __name__ == "__main__":
    if "--quick" in sys.argv:
        raise SystemExit(run_quick())
    from bench import _require_tpu

    _require_tpu("serving_bench.py")
    im = build_model()
    result = run_bench(im)
    result["platform"] = "tpu"
    result["dispatch_rtt_ms"] = measure_dispatch_rtt_ms()
    # wire-protocol leg: legacy JSON vs binary vs binary+shm data plane
    result["wire"] = run_wire_bench()
    result["int8"] = run_int8_bench()
    with open("SERVING_BENCH.json", "w") as f:
        json.dump(result, f, indent=1)
    print(json.dumps(result))
