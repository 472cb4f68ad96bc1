"""Chunked prefill tests (ISSUE 20): token-budgeted prefill/decode
interleaving. Pure-logic tiers (qos budget math, ServingConfig wiring,
replay exactness, the chunk-mode decode lint) and the model-level
chunk-vs-whole bit-identity run in tier-1; the compile-heavy live-batcher
matrices (identity across temperature x spec x prefix warmth, budget
starvation, preempt-while-prefilling, kill-mid-chunk, hot-swap-mid-prefill)
are marked `slow` + `prefix`/`chaos` and ride `scripts/run_chaos_suite.sh`.
"""

import threading
import time

import numpy as np
import pytest

import jax

from analytics_zoo_tpu.models.transformer import TransformerLM
from analytics_zoo_tpu.ops.kv_cache import SCRATCH_PAGE, PagePool
from analytics_zoo_tpu.serving import ServingConfig
from analytics_zoo_tpu.serving import qos
from analytics_zoo_tpu.serving.generation import ContinuousBatcher

pytestmark = pytest.mark.generation

VOCAB, HIDDEN, BLOCKS, HEADS, SEQ = 64, 32, 2, 2, 256


@pytest.fixture(scope="module")
def model_and_params():
    m = TransformerLM(vocab=VOCAB, hidden_size=HIDDEN, n_block=BLOCKS,
                      n_head=HEADS, seq_len=SEQ)
    params, _ = m.build(jax.random.PRNGKey(0))
    return m, params


def _mk(model_and_params, **kw):
    m, params = model_and_params
    kw.setdefault("n_slots", 4)
    kw.setdefault("page_size", 8)
    kw.setdefault("max_seq_len", 128)
    return ContinuousBatcher(m, params, **kw)


# ------------------------------------------------------------- budget math

def test_prefill_budget_from_slo():
    # cold (either EMA unobserved): the one-chunk progress floor
    assert qos.prefill_budget_from_slo(0.1, 0.0, 0.01, 16) == 16
    assert qos.prefill_budget_from_slo(0.1, 0.02, 0.0, 16) == 16
    # saturated (decode alone eats the target): still the floor
    assert qos.prefill_budget_from_slo(0.1, 0.2, 0.01, 16) == 16
    # headroom: (0.1 - 0.02) / 0.01 = 8 chunks worth
    assert qos.prefill_budget_from_slo(0.1, 0.02, 0.01, 16) == 8 * 16
    # tiny headroom still grants one chunk, and chunk_tokens floors at 1
    assert qos.prefill_budget_from_slo(0.03, 0.02, 1.0, 16) == 16
    assert qos.prefill_budget_from_slo(0.1, 0.02, 0.01, 0) == 8


def test_prefill_budget_decision_source_precedence():
    # SLO wins over a static budget when an ITL target is declared
    d = qos.prefill_budget_decision(
        {"chunk_tokens": 16, "static_budget": 160, "itl_target_s": 0.1,
         "decode_ema_s": 0.02, "chunk_ema_s": 0.01})
    assert d == {"budget_tokens": 128, "chunks": 8, "source": "slo"}
    # static when no target; floored at one chunk
    d = qos.prefill_budget_decision(
        {"chunk_tokens": 16, "static_budget": 40, "itl_target_s": None})
    assert d == {"budget_tokens": 40, "chunks": 2, "source": "static"}
    d = qos.prefill_budget_decision(
        {"chunk_tokens": 64, "static_budget": 16, "itl_target_s": None})
    assert d["budget_tokens"] == 64 and d["source"] == "static"
    # nothing declared: the floor
    d = qos.prefill_budget_decision({"chunk_tokens": 32, "static_budget": 0,
                                     "itl_target_s": None})
    assert d == {"budget_tokens": 32, "chunks": 1, "source": "floor"}


def test_replay_incumbent_reproduces_budget_decisions_exactly():
    from analytics_zoo_tpu.observability.replay import verify_incumbent

    inputs = [{"chunk_tokens": 16, "static_budget": 0, "itl_target_s": 0.05,
               "decode_ema_s": round(0.001 * i, 6),
               "chunk_ema_s": 0.002} for i in range(1, 8)]
    records = [{"seq": i, "mono": float(i), "site": "gen.prefill.budget",
                "inputs": inp, "decision": qos.prefill_budget_decision(inp)}
               for i, inp in enumerate(inputs)]
    out = verify_incumbent(records)
    assert out["exact"] and out["decisions"] == len(records)
    # a tampered decision must be flagged, not silently re-derived
    records[3] = dict(records[3],
                      decision=dict(records[3]["decision"],
                                    budget_tokens=999))
    out = verify_incumbent(records)
    assert not out["exact"] and len(out["divergences"]) == 1
    assert out["divergences"][0]["site"] == "gen.prefill.budget"


# ---------------------------------------------------------- config wiring

def test_serving_config_chunked_yaml_and_validation(tmp_path):
    good = tmp_path / "good.yaml"
    good.write_text("generation:\n  page_size: 16\n"
                    "  prefill_chunk_tokens: 64\n"
                    "  prefill_token_budget: 256\n")
    cfg = ServingConfig.from_yaml(str(good))
    assert cfg.gen_prefill_chunk_tokens == 64
    assert cfg.gen_prefill_token_budget == 256

    typo = tmp_path / "typo.yaml"
    typo.write_text("generation:\n  prefill_chunk_token: 64\n")
    with pytest.raises(ValueError, match="unknown generation key"):
        ServingConfig.from_yaml(str(typo))

    ragged = tmp_path / "ragged.yaml"
    ragged.write_text("generation:\n  page_size: 16\n"
                      "  prefill_chunk_tokens: 24\n")
    with pytest.raises(ValueError, match="prefill_chunk_tokens"):
        ServingConfig.from_yaml(str(ragged))

    orphan = tmp_path / "orphan.yaml"
    orphan.write_text("generation:\n  prefill_token_budget: 128\n")
    with pytest.raises(ValueError, match="prefill_token_budget requires"):
        ServingConfig.from_yaml(str(orphan))


def test_batcher_rejects_invalid_chunk_config(model_and_params):
    m, params = model_and_params
    with pytest.raises(ValueError, match="prefill_chunk_tokens"):
        ContinuousBatcher(m, params, n_slots=2, page_size=8, max_seq_len=64,
                          prefill_chunk_tokens=12, autostart=False)
    with pytest.raises(ValueError, match="prefill_token_budget"):
        ContinuousBatcher(m, params, n_slots=2, page_size=8, max_seq_len=64,
                          prefill_token_budget=-1, autostart=False)
    with pytest.raises(ValueError, match="requires"):
        ContinuousBatcher(m, params, n_slots=2, page_size=8, max_seq_len=64,
                          prefill_token_budget=64, autostart=False)


# ------------------------------------------------- model-level bit identity

def test_prefill_chunk_bit_identical_to_whole_prefill(model_and_params):
    """Chunked prefill writes the SAME K/V pages and produces the SAME
    final-position logits as the one-shot prefill: page 0 is scratch in
    both, every masked lane lands there, and the per-chunk positions and
    page indices reproduce the whole run. The two run different programs
    (one 16-row forward with plain attention; 8-row chunks through the
    gathered cache), whose f32 reductions XLA is free to order differently,
    so "same" is a few ulp at the values' scale, not bitwise. What the
    batcher promises bitwise, identical TOKENS chunked or whole, is
    ``test_chunked_bit_identical_to_whole_prompt`` below."""
    m, params = model_and_params
    rng = np.random.default_rng(3)
    L, ct, bucket = 14, 8, 16
    seq = rng.integers(1, VOCAB, size=L).astype(np.int32)

    cfg, cache_a = m.init_kv_cache(n_slots=2, page_size=4, max_seq_len=32)
    row = PagePool(cfg).alloc(-(-L // cfg.page_size))
    ids = np.zeros((1, bucket), np.int32)
    ids[0, :L] = seq
    table = np.full((1, cfg.pages_per_slot), SCRATCH_PAGE, np.int32)
    table[0, :len(row)] = row
    whole_logits, cache_a = m.prefill(params, cache_a, ids,
                                      np.array([L], np.int32), table,
                                      page_size=cfg.page_size)

    _, cache_b = m.init_kv_cache(n_slots=2, page_size=4, max_seq_len=32)
    wide = np.full((1, cfg.pages_per_slot + ct // cfg.page_size),
                   SCRATCH_PAGE, np.int32)
    wide[0, :len(row)] = row
    for n_done in range(0, L, ct):
        n_valid = min(ct, L - n_done)
        chunk = np.zeros((1, ct), np.int32)
        chunk[0, :n_valid] = seq[n_done:n_done + n_valid]
        chunk_logits, cache_b = m.prefill_chunk(
            params, cache_b, chunk, np.array([n_done], np.int32),
            np.array([n_valid], np.int32), wide, page_size=cfg.page_size)

    np.testing.assert_allclose(np.asarray(chunk_logits),
                               np.asarray(whole_logits), rtol=0, atol=2e-6)
    for leaf in ("k", "v"):
        a = np.asarray(cache_a[leaf])[:, row]
        b = np.asarray(cache_b[leaf])[:, row]
        np.testing.assert_allclose(b, a, rtol=0, atol=2e-6,
                                   err_msg=f"cache leaf {leaf} diverged")


# ---------------------------------------------------------------- lint

def test_lint_covers_chunk_executable_both_polarities(model_and_params):
    """``chunk_tokens > 0`` extends decode-shape-stability + cache-alias to
    the chunked-prefill executable: clean when the pool is donated, extra
    cache-alias findings (beyond the decode step's own) when not."""
    from analytics_zoo_tpu.analysis.rules.decode import lint_decode_stability

    m, params = model_and_params
    cfg, cache = m.init_kv_cache(2, page_size=4, max_seq_len=32)
    clean = lint_decode_stability(m, params, cfg, cache, chunk_tokens=8,
                                  donate_cache=True)
    assert clean == []
    base = lint_decode_stability(m, params, cfg, cache,
                                 donate_cache=False)
    with_chunk = lint_decode_stability(m, params, cfg, cache,
                                       chunk_tokens=8, donate_cache=False)
    assert any(f.rule == "cache-alias" for f in with_chunk)
    assert (sum(f.rule == "cache-alias" for f in with_chunk)
            > sum(f.rule == "cache-alias" for f in base))


def test_chunked_batcher_warmup_lint_clean(model_and_params):
    m, params = model_and_params
    b = ContinuousBatcher(m, params, n_slots=2, page_size=4, max_seq_len=32,
                          prefill_chunk_tokens=8, autostart=False)
    try:
        assert b.check_decode_stability("raise") == []
    finally:
        b.close()


# ------------------------------------------------ live wiring (one compile)

def test_chunked_stream_meta_budget_record_and_ttft(model_and_params):
    """End-to-end wiring on a tiny batcher: first-frame meta carries
    ttft_s/chunks/prefill_wait_ms, the budget decision is recorded at the
    ``gen.prefill.budget`` tap and replays exactly, stats reports one
    compiled chunk shape, and the TTFT histogram observed the stream."""
    from analytics_zoo_tpu.observability import recorder as flight
    from analytics_zoo_tpu.serving.generation import _GEN_TTFT
    from analytics_zoo_tpu.observability.replay import verify_incumbent

    m, params = model_and_params
    rec = flight.install()
    b = ContinuousBatcher(m, params, n_slots=2, page_size=4, max_seq_len=32,
                          prefill_chunk_tokens=8)
    try:
        h = b.submit(list(range(1, 21)), max_new_tokens=4, seed=1)
        frames = list(h.frames(timeout_s=120))
        meta = frames[0][2]
        assert meta["chunks"] == 3                 # 20 tokens / 8 per chunk
        assert meta["ttft_s"] > 0 and meta["prefill_wait_ms"] > 0
        st = b.stats()["prefill"]
        assert st["chunks"] == 3
        assert st["distinct_chunk_shapes"] == 1
        assert st["budget"]["source"] == "floor"
        budget_recs = rec.records("gen.prefill.budget")
        assert budget_recs and verify_incumbent(budget_recs)["exact"]
        snap = _GEN_TTFT.labels(priority="normal").snapshot()
        assert snap["count"] >= 1
    finally:
        b.close()
        flight.uninstall()
    b.pool.check_conservation()
    assert b.pool.free_count() == b.pool.capacity


# ------------------------------------------------------------ bit identity

PREFIX = list(range(1, 41))     # 40 tokens, page-aligned at page_size=8


@pytest.mark.slow
@pytest.mark.prefix
@pytest.mark.parametrize("spec_k", [0, 3])
def test_chunked_bit_identical_to_whole_prompt(model_and_params, spec_k):
    """Chunked prefill is a pure scheduling change: tokens identical to the
    whole-prompt batcher at both temperatures, spec decode on and off, cold
    and warm prefixes, including the whole-prompt-cached COW case — and the
    chunk executable compiled exactly once."""
    whole = _mk(model_and_params, spec_k=spec_k, prefix_cache_pages=32)
    chunked = _mk(model_and_params, spec_k=spec_k, prefix_cache_pages=32,
                  prefill_chunk_tokens=16)
    try:
        prompts = [PREFIX + [50 + u, 51 + u] for u in range(3)]
        prompts.append(PREFIX)              # block-aligned: COW boundary
        for temperature in (0.0, 0.8):
            w = [whole.generate(p, max_new_tokens=8,
                                temperature=temperature, seed=11 + i)
                 for i, p in enumerate(prompts)]
            c = [chunked.generate(p, max_new_tokens=8,
                                  temperature=temperature, seed=11 + i)
                 for i, p in enumerate(prompts)]
            assert w == c
        st = chunked.stats()
        assert st["prefill"]["distinct_chunk_shapes"] == 1
        assert st["prefill"]["chunks"] > 0
        assert st["prefix"]["hits"] >= 7    # warm suffix chunks still hit
    finally:
        whole.close()
        chunked.close()
    chunked.pool.check_conservation()
    held = chunked.prefix_cache.held_pages()
    assert chunked.pool.free_count() == chunked.pool.capacity - held


@pytest.mark.slow
@pytest.mark.prefix
def test_budget_floor_never_starves_decode(model_and_params):
    """A deep prefill backlog cannot stall RUNNING streams: a short stream
    already decoding when a 12-chunk prompt lands keeps advancing every
    loop pass (one floor chunk, then the decode step), finishes first, and
    stays token-identical to its solo run."""
    b = _mk(model_and_params, prefill_chunk_tokens=8)
    solo = _mk(model_and_params, prefill_chunk_tokens=8)
    try:
        short_prompt = [7, 8, 9]
        baseline = solo.generate(short_prompt, max_new_tokens=10, seed=5)
        long_prompt = list(np.random.default_rng(0).integers(1, VOCAB, 96))
        h_short = b.submit(short_prompt, max_new_tokens=10, seed=5)
        frames = h_short.frames(timeout_s=120)
        first_tokens, _, _ = next(frames)      # short stream is decoding
        results, done_t = {}, {}
        h_long = b.submit(long_prompt, max_new_tokens=2, seed=1)

        def _drain_long():
            results["long"] = h_long.result(timeout_s=120)
            done_t["long"] = time.monotonic()

        def _drain_short():
            got = list(first_tokens)
            for tokens, final, _meta in frames:
                got.extend(tokens)
            results["short"] = got
            done_t["short"] = time.monotonic()

        threads = [threading.Thread(target=_drain_long),
                   threading.Thread(target=_drain_short)]
        for t in threads:
            t.start()
        saw_prefilling = 0
        deadline = time.time() + 120
        while len(done_t) < 2 and time.time() < deadline:
            saw_prefilling = max(saw_prefilling, b.stats()["prefilling"])
            time.sleep(0.002)
        for t in threads:
            t.join(timeout=120)
        assert results["short"] == baseline
        assert results["long"]
        assert saw_prefilling >= 1
        assert done_t["short"] < done_t["long"]
    finally:
        b.close()
        solo.close()
    b.pool.check_conservation()


@pytest.mark.slow
@pytest.mark.prefix
def test_preempt_while_prefilling_token_exact(model_and_params):
    """A critical request preempts a BULK slot that is still mid-prefill:
    the victim parks with its pages and chunk progress intact, resumes, and
    finishes token-identical to an uncontended run."""
    solo = _mk(model_and_params, n_slots=1, prefill_chunk_tokens=8)
    b = _mk(model_and_params, n_slots=1, prefill_chunk_tokens=8)
    try:
        long_prompt = list(np.random.default_rng(1).integers(1, VOCAB, 96))
        baseline = solo.generate(long_prompt, max_new_tokens=6,
                                 temperature=0.8, seed=3, priority="bulk")
        h_bulk = b.submit(long_prompt, max_new_tokens=6, temperature=0.8,
                          seed=3, priority="bulk")
        deadline = time.time() + 60
        while b.stats()["prefilling"] == 0 and time.time() < deadline:
            time.sleep(0.001)
        h_crit = b.submit([5, 6], max_new_tokens=4, seed=9,
                          priority="critical")
        crit_out = []

        def _drain():
            crit_out.extend(h_crit.result(timeout_s=60))

        t = threading.Thread(target=_drain)
        t.start()
        saw_parked = 0
        while t.is_alive() and time.time() < deadline:
            saw_parked = max(saw_parked, b.stats()["preempted_parked"])
            time.sleep(0.002)
        t.join(timeout=60)
        assert len(crit_out) == 4
        assert saw_parked >= 1                  # the preempt really happened
        assert h_bulk.result(timeout_s=120) == baseline
    finally:
        solo.close()
        b.close()
    b.pool.check_conservation()
    assert b.pool.free_count() == b.pool.capacity


@pytest.mark.slow
@pytest.mark.prefix
def test_chunked_token_exact_through_hot_swap(model_and_params):
    """A same-weights hot-swap landing mid-prefill cannot perturb the
    stream: chunks computed before and after the swap see identical
    weights, so the output matches the no-swap run bit-for-bit."""
    m, params = model_and_params
    solo = _mk(model_and_params, prefill_chunk_tokens=8)
    b = _mk(model_and_params, prefill_chunk_tokens=8)
    try:
        long_prompt = list(np.random.default_rng(2).integers(1, VOCAB, 96))
        baseline = solo.generate(long_prompt, max_new_tokens=8,
                                 temperature=0.8, seed=7)
        h = b.submit(long_prompt, max_new_tokens=8, temperature=0.8, seed=7)
        deadline = time.time() + 60
        while b.stats()["prefilling"] == 0 and time.time() < deadline:
            time.sleep(0.001)
        b.swap_params(params, version="v2")     # same weights, new version
        assert h.result(timeout_s=120) == baseline
        deadline = time.time() + 5
        while b.swaps == 0 and time.time() < deadline:
            time.sleep(0.01)
        assert b.swaps == 1 and b.version == "v2"
    finally:
        solo.close()
        b.close()
    b.pool.check_conservation()
    assert b.pool.free_count() == b.pool.capacity


# ------------------------------------------------------------ chaos drill

@pytest.mark.slow
@pytest.mark.prefix
@pytest.mark.chaos
def test_chaos_kill_mid_chunk_idempotent_redispatch(model_and_params):
    """Kill the decode loop at the 3rd ``prefill.chunk`` occurrence: the
    slot's host state is untouched (the chaos point fires BEFORE dispatch),
    the respawned loop re-runs exactly that chunk into exclusively-owned
    pages, and the stream completes bit-identical to the no-kill run with
    zero pages leaked."""
    from analytics_zoo_tpu.common.chaos import ChaosSchedule

    long_prompt = list(np.random.default_rng(4).integers(1, VOCAB, 96))
    solo = _mk(model_and_params, prefill_chunk_tokens=8)
    try:
        baseline = solo.generate(long_prompt, max_new_tokens=6,
                                 temperature=0.8, seed=13)
    finally:
        solo.close()

    sched = ChaosSchedule(seed=3).kill("prefill.chunk", at=3)
    with sched:
        b = _mk(model_and_params, prefill_chunk_tokens=8)
        try:
            out = b.generate(long_prompt, max_new_tokens=6, temperature=0.8,
                             seed=13, timeout_s=120)
            assert out == baseline
            assert sched.occurrences("prefill.chunk") >= 3
            assert b.loop_respawns >= 1
            assert b.stats()["prefill"]["distinct_chunk_shapes"] == 1
        finally:
            b.close()
    b.pool.check_conservation()
    assert b.pool.free_count() == b.pool.capacity
