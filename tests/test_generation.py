"""Autoregressive generation serving tests: paged KV cache, prefill/decode
parity, continuous micro-batching, streaming frames over the broker, and the
decode-shape-stability lint — the tier-1 suite for serving/generation.py
(ISSUE 8). Chaos drills reuse the seeded fault harness.
"""

import json
import queue
import threading
import time

import numpy as np
import pytest

import jax

from analytics_zoo_tpu.models.transformer import TransformerLM
from analytics_zoo_tpu.ops.kv_cache import (KVCacheConfig, OutOfPages,
                                            PagePool, SCRATCH_PAGE)
from analytics_zoo_tpu.serving import ServingConfig, start_broker
from analytics_zoo_tpu.serving.generation import (ContinuousBatcher,
                                                  GenerationClient,
                                                  GenerationEngine)

pytestmark = pytest.mark.generation

VOCAB, HIDDEN, BLOCKS, HEADS, SEQ = 64, 32, 2, 2, 64


@pytest.fixture(scope="module")
def model_and_params():
    m = TransformerLM(vocab=VOCAB, hidden_size=HIDDEN, n_block=BLOCKS,
                      n_head=HEADS, seq_len=SEQ)
    params, _ = m.build(jax.random.PRNGKey(0))
    return m, params


@pytest.fixture()
def batcher(model_and_params):
    m, params = model_and_params
    b = ContinuousBatcher(m, params, n_slots=2, page_size=4, max_seq_len=32)
    yield b
    b.close()


def _teacher_forced_parity(m, params, seq, prefill_len, atol):
    """Prefill ``seq[:prefill_len]`` then teacher-force the rest through
    decode_step; every step's logits must match the one-shot full forward at
    the same position."""
    full, _ = m.apply(params, {}, seq[None])
    full = np.asarray(full, np.float32)
    cfg, cache = m.init_kv_cache(n_slots=2, page_size=4, max_seq_len=32)
    pool = PagePool(cfg)
    bucket = 16
    ids = np.zeros((2, bucket), np.int32)
    ids[0, :prefill_len] = seq[:prefill_len]
    table = np.full((2, cfg.pages_per_slot), SCRATCH_PAGE, np.int32)
    n_pg = -(-prefill_len // cfg.page_size)
    table[0, :n_pg] = pool.alloc(n_pg)
    logits, cache = m.prefill(params, cache, ids,
                              np.array([prefill_len, 0], np.int32), table,
                              page_size=cfg.page_size)
    np.testing.assert_allclose(np.asarray(logits)[0],
                               full[0, prefill_len - 1], atol=atol, rtol=0)
    zeros_u = np.zeros(2, np.uint32)
    for pos in range(prefill_len, len(seq)):
        p = pos // cfg.page_size
        if table[0, p] == SCRATCH_PAGE:
            table[0, p] = pool.alloc(1)[0]
        _next, logits, cache = m.decode_step(
            params, cache, np.array([seq[pos], 0], np.int32),
            np.array([pos, 0], np.int32), table, zeros_u, zeros_u,
            np.zeros(2, np.float32), page_size=cfg.page_size)
        np.testing.assert_allclose(np.asarray(logits)[0], full[0, pos],
                                   atol=atol, rtol=0)


@pytest.mark.parametrize("n_layers", [1, 3])
def test_prefill_decode_logit_parity_f32(n_layers, np_rng):
    # one pool per layer: at 3 layers a block that read or wrote another
    # layer's pool would see the wrong K/V and miss the full forward
    m = TransformerLM(vocab=VOCAB, hidden_size=HIDDEN, n_block=n_layers,
                      n_head=HEADS, seq_len=SEQ)
    params, _ = m.build(jax.random.PRNGKey(0))
    seq = np_rng.integers(1, VOCAB, size=20).astype(np.int32)
    # f32: the cached path reassociates reductions differently from the
    # one-shot forward, so "exact" means float-epsilon-scale, not bit-equal
    _teacher_forced_parity(m, params, seq, prefill_len=9, atol=1e-4)


def test_prefill_decode_logit_parity_bf16(np_rng):
    from analytics_zoo_tpu.nn.module import set_policy

    set_policy(compute_dtype="bfloat16")
    try:
        m = TransformerLM(vocab=VOCAB, hidden_size=HIDDEN, n_block=BLOCKS,
                          n_head=HEADS, seq_len=SEQ)
        params, _ = m.build(jax.random.PRNGKey(0))
        seq = np_rng.integers(1, VOCAB, size=16).astype(np.int32)
        _teacher_forced_parity(m, params, seq, prefill_len=7, atol=0.25)
    finally:
        set_policy(compute_dtype="float32")


# --------------------------------------------------------------------- pages

def test_page_pool_accounting():
    cfg = KVCacheConfig(n_layers=1, n_heads=1, head_dim=4, n_slots=2,
                        page_size=4, pages_per_slot=4)
    pool = PagePool(cfg)
    assert pool.capacity == cfg.total_pages - 1   # scratch never allocated
    pages = pool.alloc(3)
    assert SCRATCH_PAGE not in pages
    assert pool.free_count() == pool.capacity - 3
    pool.release(pages)
    assert pool.free_count() == pool.capacity
    with pytest.raises(ValueError, match="double free"):
        pool.release([pages[0], pages[0]] if False else pages[:1] * 2)
    with pytest.raises(OutOfPages):
        pool.alloc(pool.capacity + 1)


def test_no_page_leak_across_retirements(batcher, np_rng):
    cap = batcher.pool.capacity
    for wave in range(3):    # slots reused across waves; pages must recycle
        handles = [batcher.submit(np_rng.integers(1, VOCAB, size=5 + i),
                                  max_new_tokens=4 + i) for i in range(4)]
        for h in handles:
            h.result(timeout_s=60)
    assert batcher.pool.free_count() == cap
    assert batcher.active_slots() == 0
    stats = batcher.stats()
    assert stats["requests"].get("ok") == 12
    # bucket invariant: the multi-slot decode step compiled exactly one
    # shape, and prompts of 5-8 tokens prefilled in power-of-two buckets
    assert stats["distinct_decode_shapes"] == 1
    assert stats["prefill_buckets"] == [8]


def test_pool_exhaustion_truncates_not_deadlocks(model_and_params):
    m, params = model_and_params
    # 5 non-scratch pages: one 8-token prompt (2 pages) can grow ~3 pages
    b = ContinuousBatcher(m, params, n_slots=2, page_size=4, max_seq_len=32,
                          n_pages=6)
    try:
        h = b.submit(np.arange(1, 9, dtype=np.int32), max_new_tokens=24)
        frames = list(h.frames(timeout_s=60))
        assert frames[-1][1] is True
        assert frames[-1][2]["outcome"] in ("truncated", "ok")
        assert b.pool.free_count() == b.pool.capacity
    finally:
        b.close()


# ---------------------------------------------------------------- admission

@pytest.mark.parametrize("retired", [{"admit_policy": "batch"},
                                     {"admit_policy": "continuous"},
                                     {"batch_window_s": 0.05}])
def test_one_admission_policy(model_and_params, retired):
    """Admission is continuous (a free slot, or a critical request at the
    head of the backlog) and nothing selects another policy: the
    run-to-completion baseline and its window went with the bench that
    compared against it."""
    m, params = model_and_params
    with pytest.raises(TypeError, match="unexpected keyword"):
        ContinuousBatcher(m, params, n_slots=2, page_size=4, max_seq_len=32,
                          **retired)


# -------------------------------------------------------------- determinism

def test_continuous_schedule_determinism(model_and_params, np_rng):
    """More requests than slots, mixed lengths + sampled temperatures: the
    per-request (seed, token-ordinal) PRNG keys make every stream identical
    no matter how admission/retirement interleaves."""
    m, params = model_and_params
    prompts = [np_rng.integers(1, VOCAB, size=3 + (i % 5)).astype(np.int32)
               for i in range(7)]

    def run(order):
        b = ContinuousBatcher(m, params, n_slots=2, page_size=4,
                              max_seq_len=32)
        try:
            handles = [
                b.submit(prompts[i], max_new_tokens=3 + (i % 4),
                         temperature=0.8, seed=1000 + i)
                for i in order]
            return {h.uri: h.result(timeout_s=60) for h in handles}, \
                [h.uri for h in handles]
        finally:
            b.close()

    res_a, uris_a = run(range(7))
    res_b, uris_b = run(reversed(range(7)))   # reversed submit order
    by_idx_a = {i: res_a[u] for i, u in zip(range(7), uris_a)}
    by_idx_b = {i: res_b[u] for i, u in zip(reversed(range(7)), uris_b)}
    assert by_idx_a == by_idx_b


def test_cancel_mid_stream(batcher, np_rng):
    h = batcher.submit(np_rng.integers(1, VOCAB, size=4), max_new_tokens=30,
                       temperature=0.5, seed=3)
    got = []
    for tokens, final, meta in h.frames(timeout_s=60):
        got.extend(tokens)
        if len(got) >= 3 and not final:
            h.cancel()
        if final:
            assert meta["outcome"] == "cancelled"
            break
    assert len(got) < 30
    assert batcher.pool.free_count() == batcher.pool.capacity


def test_decode_failure_fails_streams_not_hot_loop(model_and_params, np_rng):
    """A deterministic decode-step failure must fail the in-flight streams
    (error final frame, pages reclaimed) — not kill the loop thread and let
    the supervisor respawn it into the same failure forever."""
    m, params = model_and_params
    b = ContinuousBatcher(m, params, n_slots=2, page_size=4, max_seq_len=32)
    try:
        def boom(*a, **k):
            raise RuntimeError("xla died")

        b._decode = boom
        h = b.submit(np_rng.integers(1, VOCAB, size=4), max_new_tokens=5)
        frames = list(h.frames(timeout_s=30))
        assert frames[-1][1] is True
        assert frames[-1][2]["outcome"] == "error"
        assert "decode step failed" in frames[-1][2]["error"]
        assert b.pool.free_count() == b.pool.capacity
        assert b.loop_respawns == 0          # the loop thread never died
    finally:
        b.close()


def test_eos_stops_stream(model_and_params, np_rng):
    m, params = model_and_params
    b = ContinuousBatcher(m, params, n_slots=1, page_size=4, max_seq_len=32)
    try:
        # greedy decode of one prompt repeats deterministically: name a token
        # of the free run as eos and the rerun must end exactly at its first
        # occurrence, eos included — at token 0 (sampled by prefill) and at
        # a later one (sampled by a decode step)
        prompt = np_rng.integers(1, VOCAB, size=4).tolist()
        free = b.generate(prompt, max_new_tokens=20)
        assert len(free) == 20
        for eos in (free[0], free[-1]):
            out = b.generate(prompt, max_new_tokens=20, eos_id=int(eos))
            assert out == free[:free.index(eos) + 1]
    finally:
        b.close()


# ------------------------------------------------------- broker streaming

@pytest.fixture(scope="module")
def broker():
    b = start_broker()
    yield b
    b.shutdown()


def test_broker_xread_cursor(broker):
    from analytics_zoo_tpu.serving.client import _Conn

    c = _Conn("127.0.0.1", broker.port)
    for i in range(3):
        c.call("XADD", "xr", {"i": i})
    cur, ents = c.call("XREAD", "xr", 0, 2, 0)
    assert [p["i"] for _, p in ents] == [0, 1] and cur == 2
    cur, ents = c.call("XREAD", "xr", cur, 10, 0)
    assert [p["i"] for _, p in ents] == [2] and cur == 3
    # blocking read times out empty without consuming anything
    cur2, ents = c.call("XREAD", "xr", cur, 10, 50)
    assert ents == [] and cur2 == 3
    c.close()


def test_streaming_reassembly_and_old_client_interop(model_and_params,
                                                     broker, np_rng):
    """Token frames reassemble in order through engine → broker → client,
    while a one-shot predict job (old client protocol) shares the SAME
    broker untouched."""
    from analytics_zoo_tpu.nn import Sequential
    from analytics_zoo_tpu.nn import layers as L
    from analytics_zoo_tpu.serving import (ClusterServing, InputQueue,
                                           OutputQueue)

    m, params = model_and_params
    cfg = ServingConfig(queue_port=broker.port, gen_slots=2, gen_page_size=4,
                        gen_max_seq_len=32)
    eng = GenerationEngine(m, params, config=cfg).start()
    one_shot = Sequential([L.Dense(4, activation="softmax",
                                   input_shape=(8,))])
    one_shot.compile(optimizer="sgd", loss="mse")
    one_shot.fit(np.zeros((8, 8), np.float32), np.zeros((8, 4), np.float32),
                 batch_size=8, nb_epoch=1)
    job = ClusterServing(one_shot, ServingConfig(queue_port=broker.port),
                         group="interop").start()
    try:
        cl = GenerationClient(port=broker.port)
        prompt = np_rng.integers(1, VOCAB, size=5).tolist()
        uri = cl.submit(prompt, max_new_tokens=6, temperature=0.6, seed=11)
        chunks = list(cl.stream(uri, timeout_s=60))
        assert all(isinstance(c, np.ndarray) for c in chunks)
        streamed = [t for c in chunks for t in c.tolist()]
        ref = eng.batcher.generate(prompt, max_new_tokens=6, temperature=0.6,
                                   seed=11)
        assert streamed == ref and len(streamed) == 6
        # interop: the classic enqueue/query flow on the same broker
        iq = InputQueue(port=broker.port)
        oq = OutputQueue(port=broker.port)
        x = np.zeros(8, np.float32)
        r = oq.query(iq.enqueue(None, input=x), timeout_s=30)
        assert np.asarray(r).shape[-1] == 4
        iq.close(), oq.close(), cl.close()
    finally:
        job.stop()
        eng.stop()


def test_stream_cleanup_and_remote_cancel(model_and_params, broker, np_rng):
    """Finished genout streams are deleted by their consumer (bounded broker
    state), and a client-sent cancel frame stops an in-flight stream early
    (abandoned-client protection)."""
    m, params = model_and_params
    cfg = ServingConfig(queue_port=broker.port, gen_slots=2, gen_page_size=4,
                        gen_max_seq_len=32)
    eng = GenerationEngine(m, params, config=cfg).start()
    try:
        cl = GenerationClient(port=broker.port)
        uri = cl.submit(np_rng.integers(1, VOCAB, size=4).tolist(),
                        max_new_tokens=4)
        assert len([t for c in cl.stream(uri, timeout_s=60)
                    for t in c.tolist()]) == 4
        # the client deleted the per-request stream after the final frame
        assert ("genout:" + uri) not in broker.store.streams
        # remote cancel: consume one chunk, cancel, stream ends "cancelled".
        # A seeded per-step delay slows the decode loop so the cancel frame
        # deterministically lands while the stream is still in flight.
        from analytics_zoo_tpu.common.chaos import ChaosSchedule

        with ChaosSchedule(seed=1).delay("serving.generate", seconds=0.05):
            uri2 = cl.submit(np_rng.integers(1, VOCAB, size=4).tolist(),
                             max_new_tokens=25, temperature=0.4, seed=2)
            got = []
            it = cl.stream(uri2, timeout_s=60)
            got.extend(next(it).tolist())
            cl.cancel(uri2)
            for c in it:
                got.extend(c.tolist())
        assert len(got) < 25
        deadline = time.time() + 5
        while eng.batcher.active_slots() and time.time() < deadline:
            time.sleep(0.01)
        assert eng.batcher.pool.free_count() == eng.batcher.pool.capacity
        cl.close()
    finally:
        eng.stop()


@pytest.mark.chaos
def test_chaos_kill_engine_mid_stream(model_and_params, broker, np_rng):
    """Kill the decode loop mid-stream (seeded chaos at the
    ``serving.generate`` site): the supervisor respawns it with slot/cache
    state intact, the step in flight included, and every stream still
    completes with its full token count: no token lost, none sent twice
    (tests/test_generation_inflight.py kills it with a step known to be in
    flight)."""
    from analytics_zoo_tpu.common.chaos import ChaosSchedule

    m, params = model_and_params
    cfg = ServingConfig(queue_port=broker.port, gen_slots=2, gen_page_size=4,
                        gen_max_seq_len=32)
    prompts = [np_rng.integers(1, VOCAB, size=4).tolist() for _ in range(3)]
    sched = ChaosSchedule(seed=7).kill("serving.generate", at=4)
    with sched:
        eng = GenerationEngine(m, params, config=cfg).start()
        try:
            cl = GenerationClient(port=broker.port)
            uris = [cl.submit(p, max_new_tokens=8, temperature=0.3,
                              seed=100 + i) for i, p in enumerate(prompts)]
            outs = [[t for c in cl.stream(u, timeout_s=60)
                     for t in c.tolist()] for u in uris]
            assert all(len(o) == 8 for o in outs)
            assert eng.batcher.loop_respawns >= 1
            assert sched.occurrences("serving.generate") >= 4
            # what a loop nobody killed streams for the same requests
            assert outs == [eng.batcher.generate(p, max_new_tokens=8,
                                                 temperature=0.3,
                                                 seed=100 + i)
                            for i, p in enumerate(prompts)]
            cl.close()
        finally:
            eng.stop()


# ---------------------------------------------------------------- frontend

def test_http_generate_chunked_stream(model_and_params, np_rng):
    import http.client

    from analytics_zoo_tpu.serving import FrontEndApp

    m, params = model_and_params
    gen = ContinuousBatcher(m, params, n_slots=2, page_size=4,
                            max_seq_len=32)
    app = FrontEndApp(ServingConfig(), port=0, generator=gen).start()
    try:
        conn = http.client.HTTPConnection("127.0.0.1", app.port, timeout=30)
        conn.request("POST", "/generate", body=json.dumps(
            {"prompt": np_rng.integers(1, VOCAB, size=4).tolist(),
             "max_new_tokens": 5, "temperature": 0.4, "seed": 5}))
        resp = conn.getresponse()
        assert resp.status == 200
        assert resp.getheader("Transfer-Encoding") == "chunked"
        frames = [json.loads(l) for l in
                  resp.read().decode().strip().splitlines()]
        assert frames[-1]["final"] is True
        assert frames[-1]["outcome"] == "ok"
        toks = [t for f in frames for t in f["tokens"]]
        assert len(toks) == 5
        # non-stream answer matches the stream reassembly (same seed)
        conn.request("POST", "/generate", body=json.dumps(
            {"prompt": frames and [1, 2, 3], "max_new_tokens": 4,
             "stream": False}))
        r2 = conn.getresponse()
        assert r2.status == 200
        assert len(json.loads(r2.read())["tokens"]) == 4
        conn.close()
    finally:
        app.stop()
        gen.close()


# -------------------------------------------------- satellites: micro-batch

def test_microbatcher_timeout_cancel_drops_slot():
    """A timed-out slot must NOT be computed into a later batch (the leak):
    it is dropped at drain time and counted."""
    from analytics_zoo_tpu.serving.batching import MicroBatcher

    gate = threading.Event()
    seen_rows = []

    def slow_predict(x):
        gate.wait(5.0)
        seen_rows.append(np.asarray(x)[:, 0].tolist())
        return np.asarray(x)

    mb = MicroBatcher(slow_predict, max_batch=4, max_delay_ms=1.0,
                      bucket_pad=False)
    try:
        # first record occupies the batcher thread (blocked on the gate)
        s1 = mb.submit_async({"x": np.array([1.0], np.float32)})
        time.sleep(0.1)
        # second record queues; its waiter times out before it ever runs
        s2 = mb.submit_async({"x": np.array([2.0], np.float32)})
        with pytest.raises(TimeoutError):
            mb.wait(s2, timeout_s=0.2)
        gate.set()
        assert np.asarray(mb.wait(s1, timeout_s=5.0))[0] == 1.0
        deadline = time.time() + 5.0
        while mb.cancelled_drops < 1 and time.time() < deadline:
            time.sleep(0.01)
        assert mb.cancelled_drops == 1
        assert mb.stats()["cancelled_drops"] == 1
        # the cancelled record's row value 2.0 never reached predict_fn
        assert all(2.0 not in rows for rows in seen_rows)
    finally:
        mb.close()


# ------------------------------------------- satellites: attention dispatch

def test_auto_routes_single_query_to_plain_dot(monkeypatch):
    from analytics_zoo_tpu.nn.layers.attention import MultiHeadAttention
    from analytics_zoo_tpu.ops import attention as attn_ops

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert attn_ops.prefer_flash_single_device(1) is False
    assert attn_ops.prefer_flash_single_device(1, backward=True) is False
    assert attn_ops.prefer_flash_single_device(4096) is True
    mha_auto = MultiHeadAttention(8, 2, attn_strategy="auto")
    mha_flash = MultiHeadAttention(8, 2, attn_strategy="flash")
    # decode step (T=1): plain dot regardless of strategy — flash tiling is
    # pure overhead at query length 1
    for training in (False, True):
        assert mha_auto._flash_single_device(1, training) is False
        assert mha_flash._flash_single_device(1, training) is False


def test_auto_prefill_still_prefers_flash_at_long_t(monkeypatch):
    """Regression guard: the T=1 fast path must not eat the long-T prefill
    dispatch — 'auto' on TPU still routes long sequences to the kernel."""
    from analytics_zoo_tpu.nn.layers.attention import MultiHeadAttention
    from analytics_zoo_tpu.ops import attention as attn_ops

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    mha = MultiHeadAttention(8, 2, attn_strategy="auto")
    assert mha._flash_single_device(4096) is True
    assert mha._flash_single_device(2048) is True
    assert mha._flash_single_device(512) is False      # below the threshold
    # a prefill is a forward alone: at 1,024 tokens it stays on XLA full
    # attention, where a training call takes the kernel
    assert mha._flash_single_device(1024, batch_heads=64) is False
    assert mha._flash_single_device(1024, training=True,
                                    batch_heads=64) is True
    monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
    assert attn_ops.prefer_flash_single_device(4096) is False
    assert attn_ops.prefer_flash_single_device(4096, backward=True) is False


# ------------------------------------------------ satellites: decode lint

def test_decode_shape_stability_rule_clean(model_and_params):
    m, params = model_and_params
    b = ContinuousBatcher(m, params, n_slots=2, page_size=4, max_seq_len=32,
                          autostart=False)
    try:
        assert b.check_decode_stability("raise") == []
    finally:
        b.close()


def test_decode_shape_stability_rule_flags_growth():
    """A concatenate-grown cache (the naive append implementation) and a
    host callback both trip the rule."""
    import jax.numpy as jnp

    from analytics_zoo_tpu.analysis import RuleContext
    from analytics_zoo_tpu.analysis.graphlint import lint_jaxpr

    cache = jnp.zeros((2, 8, 4))

    def grows(c, k):
        return jnp.concatenate([c, k[None]], axis=0)   # (3, 8, 4): grew!

    closed = jax.make_jaxpr(grows)(cache, jnp.zeros((8, 4)))
    ctx = RuleContext(where="test",
                      decode_cache_avals=[((2, 8, 4), "float32")])
    findings = lint_jaxpr(closed, ctx=ctx, rules=["decode-shape-stability"])
    assert any("does not reappear" in f.message for f in findings)
    assert any(f.severity == "error" for f in findings)

    def hosty(c):
        jax.debug.callback(lambda x: None, c.sum())
        return c

    closed2 = jax.make_jaxpr(hosty)(cache)
    findings2 = lint_jaxpr(closed2, ctx=ctx,
                           rules=["decode-shape-stability"])
    assert any("host round-trip" in f.message for f in findings2)


def test_decode_lint_passes_logits_that_outweigh_one_layers_pool():
    """One pool per layer made the largest cache LEAF a 1/n_layers-th of
    what it was; the growth limit stayed one K pool over all layers, so a
    small model with a large vocabulary still lints clean."""
    m = TransformerLM(vocab=512, hidden_size=HIDDEN, n_block=4, n_head=HEADS,
                      seq_len=SEQ)
    params, _ = m.build(jax.random.PRNGKey(0))
    b = ContinuousBatcher(m, params, n_slots=2, page_size=4, max_seq_len=8,
                          autostart=False)
    try:
        layer_pool = b.cache["k"][0].nbytes
        logits = b.n_slots * 512 * 4
        assert layer_pool < logits < 4 * layer_pool
        assert b.check_decode_stability("raise") == []
    finally:
        b.close()


@pytest.mark.parametrize("rows,flagged", [(2, False), (3, True)])
def test_decode_lint_growth_limit_is_half_the_leaves(rows, flagged):
    """Four (8, 4) leaves (two layers' K and V): an (rows*8, 4) intermediate
    may outweigh a leaf, up to one K pool over both layers (16 rows of 4),
    and is flagged beyond it."""
    import jax.numpy as jnp

    from analytics_zoo_tpu.analysis import RuleContext
    from analytics_zoo_tpu.analysis.graphlint import lint_jaxpr

    def step(k0, k1, v0, v1, x):
        big = jnp.tile(x, (rows, 1))                  # (rows * 8, 4)
        return k0 + big[:8], k1, v0, v1

    leaf = jnp.zeros((8, 4))
    closed = jax.make_jaxpr(step)(leaf, leaf, leaf, leaf, leaf)
    ctx = RuleContext(where="test",
                      decode_cache_avals=[((8, 4), "float32")] * 4)
    findings = lint_jaxpr(closed, ctx=ctx, rules=["decode-shape-stability"])
    assert any("one K pool over all layers" in f.message
               for f in findings) == flagged, [str(f) for f in findings]


def test_generation_engine_graph_checks_raise(model_and_params, broker,
                                              monkeypatch):
    """ServingConfig.graph_checks='raise' fails start() when the decode
    lint reports findings — the decode analog of the fused-int8 warmup
    gate."""
    from analytics_zoo_tpu.analysis import GraphLintError
    from analytics_zoo_tpu.analysis.core import finding
    from analytics_zoo_tpu.serving import generation as gen_mod

    m, params = model_and_params
    cfg = ServingConfig(queue_port=broker.port, gen_slots=2, gen_page_size=4,
                        gen_max_seq_len=32, graph_checks="raise")
    bad = [finding("decode-shape-stability", "error", "jaxpr:test",
                   "injected finding")]
    monkeypatch.setattr(gen_mod.ContinuousBatcher, "check_decode_stability",
                        lambda self, mode="warn": (_ for _ in ()).throw(
                            GraphLintError(bad)))
    eng = GenerationEngine(m, params, config=cfg)
    with pytest.raises(GraphLintError):
        eng.start()
    eng.batcher.close()


# ------------------------------------------------------------ config plumbing

def test_servingconfig_generation_yaml(tmp_path):
    p = tmp_path / "serving.yaml"
    p.write_text("generation:\n  slots: 4\n  page_size: 8\n"
                 "  max_seq_len: 128\n  top_k: 16\n")
    cfg = ServingConfig.from_yaml(str(p))
    assert (cfg.gen_slots, cfg.gen_page_size, cfg.gen_max_seq_len,
            cfg.gen_top_k) == (4, 8, 128, 16)
    p2 = tmp_path / "flat.yaml"
    p2.write_text("gen_slots: 2\ngen_pages: 9\n")
    cfg2 = ServingConfig.from_yaml(str(p2))
    assert cfg2.gen_slots == 2 and cfg2.gen_pages == 9
