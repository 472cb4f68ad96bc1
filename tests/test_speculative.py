"""Speculative multi-token decode + fused paged-attention tests (ISSUE 14):
kernel-vs-reference parity at q_len ∈ {1, k}, the accept/reject rule's
token-identity guarantee, preemption with pending draft state, draft+target
hot-swap pairs, and the verify-executable lint extension.
"""

import threading
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from analytics_zoo_tpu.models.transformer import TransformerLM
from analytics_zoo_tpu.ops.kv_cache import (decode_attention_multi,
                                            paged_read, sample_tokens)
from analytics_zoo_tpu.ops.paged_attention import (paged_attention,
                                                   query_block,
                                                   synthetic_paged_case)
from analytics_zoo_tpu.ops.speculative import (SpecDecodeConfig,
                                               propose_kgram,
                                               verify_draft_tokens)
from analytics_zoo_tpu.serving.generation import ContinuousBatcher

pytestmark = pytest.mark.speculative

VOCAB, HIDDEN, BLOCKS, HEADS, SEQ = 64, 32, 2, 2, 64


@pytest.fixture(scope="module")
def model_and_params():
    m = TransformerLM(vocab=VOCAB, hidden_size=HIDDEN, n_block=BLOCKS,
                      n_head=HEADS, seq_len=SEQ)
    params, _ = m.build(jax.random.PRNGKey(0))
    return m, params


# --------------------------------------------------------- k-gram proposer

def test_propose_kgram_copies_continuation():
    # ... 7 8 9 [5 6] ... [5 6] -> the continuation after the last earlier
    # occurrence of the suffix bigram is 7 8 9
    hist = [1, 2, 5, 6, 7, 8, 9, 3, 5, 6]
    assert propose_kgram(hist, 3, max_ngram=3) == [7, 8, 9]
    # no repeated suffix anywhere: fall back to repeating the last token
    assert propose_kgram([1, 2, 3, 4], 3) == [4, 4, 4]
    # match whose continuation is shorter than n_draft pads with the last
    hist = [5, 1, 2, 9, 1, 2]
    assert propose_kgram(hist, 4) == [9, 1, 2, 2]
    assert propose_kgram([], 2) == [0, 0]


# ---------------------------------------------------- sample_tokens + probs

def test_sample_tokens_bit_identical_with_probs_option(np_rng):
    """The ``return_probs`` extension must not perturb the token path —
    existing streams stay bit-identical — and the returned distribution is
    the one the tokens were sampled from."""
    logits = jnp.asarray(np_rng.normal(size=(6, VOCAB)), jnp.float32)
    seeds = np.arange(6, dtype=np.uint32)
    idx = np.arange(6, dtype=np.uint32)
    temps = np.array([0.0, 0.5, 1.0, 0.0, 0.7, 1.3], np.float32)
    plain = np.asarray(sample_tokens(logits, seeds, idx, temps, top_k=8))
    toks, probs = sample_tokens(logits, seeds, idx, temps, top_k=8,
                                return_probs=True)
    assert np.array_equal(plain, np.asarray(toks))
    probs = np.asarray(probs)
    np.testing.assert_allclose(probs.sum(axis=-1), 1.0, atol=1e-5)
    # greedy rows: the floored-temperature softmax concentrates on argmax
    assert probs[0].argmax() == plain[0] and probs[0].max() > 0.99
    # top_k: mass only on the k highest-logit tokens
    row = np.asarray(logits)[2]
    kth = np.sort(row)[-8]
    assert probs[2][row < kth].max() < 1e-6


def test_verify_draft_tokens_accept_counts(np_rng):
    """Greedy handcrafted case: drafts matching m leading argmaxes accept
    exactly m; the emitted run is the target's own tokens; draft_probs is
    pi(draft)."""
    b, k = 3, 4
    logits = np.full((b, k, VOCAB), -10.0, np.float32)
    want = np_rng.integers(1, VOCAB, size=(b, k))
    for i in range(b):
        for j in range(k):
            logits[i, j, want[i, j]] = 10.0
    # draft j is verified against the target's token at position j
    drafts = want[:, : k - 1].copy()
    drafts[1, 1] = (want[1, 1] + 1) % VOCAB   # row 1: mismatch at j=1
    drafts[2, 0] = (want[2, 0] + 1) % VOCAB   # row 2: mismatch immediately
    acc, toks, dp = verify_draft_tokens(
        jnp.asarray(logits), jnp.asarray(drafts, np.int32),
        np.zeros(b, np.uint32), np.zeros(b, np.uint32),
        np.zeros(b, np.float32))
    acc, toks, dp = np.asarray(acc), np.asarray(toks), np.asarray(dp)
    assert list(acc) == [k - 1, 1, 0]
    for i in range(b):
        # emitted = confirmed drafts + correction/bonus, all target tokens
        assert list(toks[i, :acc[i] + 1]) == \
            [want[i, j] for j in range(acc[i] + 1)]
    assert dp.shape == (b, k - 1)
    assert dp[0].min() > 0.99            # matching drafts: pi(d) ~ 1
    assert dp[2, 0] < 1e-6               # mismatched draft: pi(d) ~ 0


# ------------------------------------------------------------ fused kernel

def _random_paged_case(np_rng, q_len, dtype, n_slots=4, h=HEADS * 2, d=16,
                       page_size=8, pps=6):
    lengths = np.maximum(q_len, np.asarray(
        np_rng.integers(0, pps * page_size, size=n_slots), np.int32))
    lengths[-1] = 0                      # one masked/inactive slot
    q, kp, vp, table, lengths = synthetic_paged_case(
        n_slots, pps, page_size, h, d, q_len=q_len, dtype=dtype,
        lengths=lengths, rng=np_rng)
    return q, kp, vp, table, lengths, page_size


# (16, 8): the query-tiled grid the prefill-chunk widths run with
@pytest.mark.parametrize("q_len,block_q", [(1, None), (4, None), (16, 8)])
@pytest.mark.parametrize("block_h", [None, 1, 2])
def test_kernel_parity_f32(np_rng, q_len, block_q, block_h):
    q, kp, vp, table, lengths, ps = _random_paged_case(
        np_rng, q_len, jnp.float32)
    got = paged_attention(q, kp, vp, table, lengths, page_size=ps,
                          block_h=block_h, block_q=block_q, interpret=True)
    ref = decode_attention_multi(q, paged_read(kp, table),
                                 paged_read(vp, table), lengths)
    # live rows match the reference; the fully-masked slot differs BY
    # DESIGN (all-NEG_INF softmax is uniform garbage in the reference,
    # exact zeros from the kernel) — both are invisible downstream
    np.testing.assert_allclose(np.asarray(got)[:-1], np.asarray(ref)[:-1],
                               atol=1e-4, rtol=0)
    assert np.all(np.asarray(got)[-1] == 0.0)


@pytest.mark.parametrize("q_len", [1, 4])
def test_kernel_parity_bf16(np_rng, q_len):
    q, kp, vp, table, lengths, ps = _random_paged_case(
        np_rng, q_len, jnp.bfloat16)
    got = paged_attention(q, kp, vp, table, lengths, page_size=ps,
                          interpret=True)
    ref = decode_attention_multi(q, paged_read(kp, table),
                                 paged_read(vp, table), lengths)
    np.testing.assert_allclose(np.asarray(got, np.float32)[:-1],
                               np.asarray(ref, np.float32)[:-1],
                               atol=2e-2, rtol=0)
    assert np.all(np.asarray(got, np.float32)[-1] == 0.0)


def test_query_block_fits_vmem_or_names_the_shape():
    """Decode and verify widths run untiled; prefill-chunk widths get the
    largest 8-multiple divisor whose scratch fits; a width with none is an
    error naming the shape, and so is a block that does not divide."""
    assert query_block(4, 8, 128, jnp.bfloat16) == 4
    assert query_block(2048, 8, 128, jnp.bfloat16) == 256
    assert query_block(2048, 8, 128, jnp.float32) == 128
    with pytest.raises(ValueError, match="q_len=1030"):
        query_block(1030, 8, 128, jnp.bfloat16)
    q, kp, vp, table, lengths = synthetic_paged_case(2, 4, 8, 4, 16, q_len=4)
    with pytest.raises(ValueError, match="block_h=3"):
        paged_attention(q, kp, vp, table, lengths, page_size=8, block_h=3,
                        interpret=True)


# ----------------------------------------------- batcher: spec decode mode

@pytest.mark.parametrize("temperature", [0.0, 0.8])
def test_spec_streams_identical_to_plain(model_and_params, np_rng,
                                         temperature):
    """Speculation changes COST, never CONTENT: spec-mode streams are
    bit-identical to the single-token baseline at any temperature (the
    accept rule replays the exact per-(seed, ordinal) categorical draws)."""
    m, params = model_and_params
    prompts = [np_rng.integers(1, VOCAB, size=3 + i).astype(np.int32)
               for i in range(4)]

    def run(spec_k):
        b = ContinuousBatcher(m, params, n_slots=2, page_size=4,
                              max_seq_len=48, spec_k=spec_k)
        try:
            hs = [b.submit(p, max_new_tokens=14, temperature=temperature,
                           seed=50 + i) for i, p in enumerate(prompts)]
            return [h.result(timeout_s=60) for h in hs], b.stats()
        finally:
            b.close()

    plain, _ = run(0)
    spec, stats = run(4)
    assert plain == spec
    assert stats["spec"]["steps"] > 0
    assert stats["free_pages"] == stats["page_capacity"]
    # ONE verify executable per (k, slot-count)
    assert stats["distinct_decode_shapes"] == 1


def test_spec_eos_and_budget_respected(model_and_params, np_rng):
    """An eos or max_new_tokens boundary landing INSIDE an accepted run
    must clip the emitted stream exactly like the single-token loop."""
    m, params = model_and_params
    b = ContinuousBatcher(m, params, n_slots=1, page_size=4, max_seq_len=48,
                          spec_k=4)
    try:
        prompt = np_rng.integers(1, VOCAB, size=4).tolist()
        ref = b.generate(prompt, max_new_tokens=12)
        # budget mid-run: every prefix length is honored exactly
        for n in (1, 5, 7):
            assert b.generate(prompt, max_new_tokens=n) == ref[:n]
        # eos mid-run: stream stops AT the eos token
        eos = ref[6]
        out = b.generate(prompt, max_new_tokens=12, eos_id=int(eos))
        assert out == ref[: ref.index(eos) + 1]
        assert b.pool.free_count() == b.pool.capacity
    finally:
        b.close()


def test_spec_identical_through_cache_cap(model_and_params, np_rng):
    """Identity holds through the cache cap: a stream that outgrows the
    cache truncates at EXACTLY the same point (tokens + outcome) as the
    plain loop — slots within k of the cap fall back to the single-token
    executable for their last positions instead of retiring early (also
    the safe path for in-flight streams a hot-swap raises k under)."""
    m, params = model_and_params
    prompt = np_rng.integers(1, VOCAB, size=5).tolist()

    def run(spec_k):
        b = ContinuousBatcher(m, params, n_slots=2, page_size=4,
                              max_seq_len=24, spec_k=spec_k)
        try:
            h = b.submit(prompt, max_new_tokens=64, temperature=0.5, seed=3)
            toks, outcome = [], None
            for tokens, final, meta in h.frames(timeout_s=60):
                toks.extend(tokens)
                if final:
                    outcome = meta["outcome"]
            return toks, outcome, b.stats()
        finally:
            b.close()

    p_toks, p_out, _ = run(0)
    s_toks, s_out, s_stats = run(4)
    assert p_out == "truncated"            # the stream DID hit the cap
    # cache holds max_seq_len tokens; the last sampled token is never cached
    assert len(p_toks) == 24 - 5 + 1
    assert (s_toks, s_out) == (p_toks, p_out)
    # the tail ran through the single-token executable: both shapes traced
    assert s_stats["distinct_decode_shapes"] == 2
    assert s_stats["free_pages"] == s_stats["page_capacity"]


def test_spec_identical_under_pool_pressure(model_and_params, np_rng):
    """A pool too dry for the k-page verify lookahead must NOT truncate
    streams plain decode completes: the squeezed slot takes the
    single-token path for that pass (it needs only the page plain decode
    would), so outcomes and tokens stay identical under page pressure."""
    m, params = model_and_params
    prompts = [np_rng.integers(1, VOCAB, size=5).tolist() for _ in range(2)]

    def run(spec_k):
        b = ContinuousBatcher(m, params, n_slots=2, page_size=4,
                              max_seq_len=48, n_pages=13, spec_k=spec_k)
        try:
            hs = [b.submit(p, max_new_tokens=20, seed=i)
                  for i, p in enumerate(prompts)]
            outs = []
            for h in hs:
                toks, outcome = [], None
                for tokens, final, meta in h.frames(timeout_s=60):
                    toks.extend(tokens)
                    if final:
                        outcome = meta["outcome"]
                outs.append((toks, outcome))
            return outs
        finally:
            b.close()

    plain = run(0)
    spec = run(4)
    assert spec == plain
    assert all(outcome == "ok" and len(toks) == 20 for toks, outcome in plain)


def test_preempt_parks_pending_drafts_and_resumes_exact(model_and_params,
                                                        np_rng):
    """PR-13 composition: preempting a bulk stream mid-generation parks its
    slot WITH its pending un-verified draft state; the resumed stream is
    token-exact vs an uninterrupted reference and the pool drains fully."""
    m, params = model_and_params
    prompt = np_rng.integers(1, VOCAB, size=4).tolist()

    ref_b = ContinuousBatcher(m, params, n_slots=1, page_size=4,
                              max_seq_len=64, n_pages=33, spec_k=4)
    try:
        ref = ref_b.generate(prompt, max_new_tokens=24, temperature=0.6,
                             seed=9)
    finally:
        ref_b.close()

    b = ContinuousBatcher(m, params, n_slots=1, page_size=4, max_seq_len=64,
                          n_pages=33, spec_k=4)
    try:
        first_chunk, critical_in = threading.Event(), threading.Event()
        parked, parked_drafts = threading.Event(), []

        def on_chunk(tokens, final, meta):
            # the loop's thread stops at the bulk stream's first token until
            # the critical request is queued: its next pass finds it at the
            # head of the backlog with the one slot taken, whatever the
            # order and the speed of the passes before
            first_chunk.set()
            assert critical_in.wait(30)

        preempt_for = b._preempt_for

        def parking(req):
            freed = preempt_for(req)
            if freed and b._preempted:
                parked_drafts.extend(b._preempted[0].pending_drafts or [])
                parked.set()
            return freed

        b._preempt_for = parking
        h = b.submit(prompt, max_new_tokens=24, temperature=0.6, seed=9,
                     priority="bulk", on_chunk=on_chunk)
        assert first_chunk.wait(30)
        hc = b.submit(np_rng.integers(1, VOCAB, size=3).tolist(),
                      max_new_tokens=4, priority="critical")
        critical_in.set()
        # the critical request must preempt the only slot; the parked bulk
        # slot carries its pending (drafted, un-verified) proposals
        assert parked.wait(30), "bulk slot never parked"
        assert parked_drafts, "bulk slot parked without pending drafts"
        assert hc.result(timeout_s=60)           # critical completes
        assert h.result(timeout_s=60) == ref     # bulk resumes token-exact
        assert b.stats()["free_pages"] == b.stats()["page_capacity"]
    finally:
        b.close()


@pytest.mark.chaos
def test_chaos_kill_mid_verify_pool_returned(model_and_params, np_rng):
    """Chaos-kill the decode loop between verify steps: the supervisor
    respawns it with slot/cache/draft state intact, every stream completes
    with its full token count, and the pool is fully returned."""
    from analytics_zoo_tpu.common.chaos import ChaosSchedule

    m, params = model_and_params
    sched = ChaosSchedule(seed=11).kill("serving.generate", at=3)
    with sched:
        b = ContinuousBatcher(m, params, n_slots=2, page_size=4,
                              max_seq_len=48, spec_k=4)
        try:
            hs = [b.submit(np_rng.integers(1, VOCAB, size=4),
                           max_new_tokens=10, temperature=0.4, seed=i)
                  for i in range(3)]
            outs = [h.result(timeout_s=60) for h in hs]
            assert all(len(o) == 10 for o in outs)
            assert b.loop_respawns >= 1
            assert b.pool.free_count() == b.pool.capacity
        finally:
            b.close()


# ------------------------------------------------------ hot-swap pair flip

def test_swap_params_flips_target_and_spec_as_one_pair(model_and_params):
    """The PR-10 composition: a mid-stream ``swap_params`` lands the new
    target weights AND the new draft schedule between decode steps as one
    pair — streams continue, pending proposals are re-drafted, and the new
    k compiles exactly one more verify executable."""
    m, params = model_and_params
    params2 = jax.tree_util.tree_map(lambda p: p * 1.01, params)
    b = ContinuousBatcher(m, params, n_slots=2, page_size=4, max_seq_len=64,
                          spec_k=4)
    try:
        seen = threading.Event()
        toks = []

        def on_chunk(tokens, final, meta):
            toks.extend(tokens)
            if len(toks) >= 3:
                seen.set()

        h = b.submit(np.arange(1, 6, dtype=np.int32), max_new_tokens=30,
                     temperature=0.7, seed=1, on_chunk=on_chunk)
        assert seen.wait(30)
        b.swap_params(params2, version="v2-pair",
                      spec={"k": 3, "max_ngram": 2})
        assert len(h.result(timeout_s=60)) == 30    # stream survived
        assert b.version == "v2-pair"
        assert b.spec_k == 3 and b.spec_ngram == 2
        assert b.swaps == 1
        # the swap added exactly the new k's executable, nothing else
        ks = {shape[3] for shape in b.decode_shapes}
        assert ks == {3, 4}
        assert b.generate([1, 2, 3], max_new_tokens=6)  # post-swap decode
    finally:
        b.close()

    with pytest.raises(TypeError):
        b.swap_params(params2, spec="k=3")
    with pytest.raises(ValueError):
        SpecDecodeConfig(k=0)


def test_model_swapper_hands_spec_through_one_call():
    """``ModelSwapper.swap`` forwards a record's ``spec`` field inside the
    SAME ``swap_params`` call for targets that accept it — the atomic
    manifest-pair contract — and omits it for one-shot models."""
    from analytics_zoo_tpu.serving.hotswap import ModelSwapper

    class PairTarget:
        version = None

        def __init__(self):
            self.calls = []

        def host_params(self):
            return {"w": np.zeros(2)}

        def swap_params(self, params, version=None, spec=None):
            self.calls.append((version, spec))

    class PlainTarget:
        version = None

        def __init__(self):
            self.calls = []

        def host_params(self):
            return {"w": np.zeros(2)}

        def swap_params(self, params, version=None):
            self.calls.append(version)

    pair = PairTarget()
    ModelSwapper(pair).swap({"w": np.ones(2)},
                            {"version": "v7", "step": 7,
                             "spec": {"k": 3, "max_ngram": 2}})
    assert pair.calls == [("v7", {"k": 3, "max_ngram": 2})]
    plain = PlainTarget()
    ModelSwapper(plain).swap({"w": np.ones(2)},
                             {"version": "v7", "step": 7,
                              "spec": {"k": 3}})
    assert plain.calls == ["v7"]


def test_model_swapper_drives_live_batcher(model_and_params):
    """The documented integration end to end: a ModelSwapper wrapped around
    a LIVE ContinuousBatcher swaps a (params, spec) pair and rolls back —
    host_params retention included — while the batcher keeps serving."""
    from analytics_zoo_tpu.serving.hotswap import ModelSwapper

    m, params = model_and_params
    params2 = jax.tree_util.tree_map(lambda p: p * 1.01, params)
    b = ContinuousBatcher(m, params, n_slots=2, page_size=4, max_seq_len=32,
                          spec_k=4)
    try:
        sw = ModelSwapper(b)
        assert sw.swap(params2, {"version": "v2", "step": 2,
                                 "spec": {"k": 3, "max_ngram": 2}}) == "v2"
        assert b.generate([1, 2, 3], max_new_tokens=4)  # swap applied, serves
        assert b.version == "v2" and b.spec_k == 3
        assert sw.rollback() == "initial"               # boot params retained
        assert b.generate([1, 2, 3], max_new_tokens=4)
        assert b.version is None
        assert b.spec_k == 3    # rollback restores WEIGHTS; spec rides publishes
    finally:
        b.close()


# ------------------------------------------------------------ lint + config

def test_lint_covers_verify_executable(model_and_params):
    """decode-shape-stability + cache-alias extend to the k-token verify
    executable: clean when the pool is donated, cache-alias finding when
    not — both polarities."""
    from analytics_zoo_tpu.analysis.rules.decode import lint_decode_stability

    m, params = model_and_params
    cfg, cache = m.init_kv_cache(2, page_size=4, max_seq_len=32)
    clean = lint_decode_stability(m, params, cfg, cache, spec_k=4,
                                  donate_cache=True)
    assert clean == []
    findings = lint_decode_stability(m, params, cfg, cache, spec_k=4,
                                     donate_cache=False)
    assert any(f.rule == "cache-alias" for f in findings)


def test_spec_batcher_warmup_lint_clean(model_and_params):
    m, params = model_and_params
    b = ContinuousBatcher(m, params, n_slots=2, page_size=4, max_seq_len=32,
                          spec_k=4, autostart=False)
    try:
        assert b.check_decode_stability("raise") == []
        mem = b.decode_memory()
        assert mem["donate_cache"]
        # the verify executable still aliases the donated pool in place
        saved = (mem["static_peak_bytes_undonated"]
                 - mem["static_peak_bytes"])
        assert saved >= 0.4 * mem["cache_bytes"]
    finally:
        b.close()


def test_servingconfig_spec_yaml(tmp_path):
    from analytics_zoo_tpu.serving import ServingConfig

    y = tmp_path / "s.yaml"
    y.write_text("generation:\n  slots: 4\n  spec_k: 4\n  spec_ngram: 2\n")
    cfg = ServingConfig.from_yaml(str(y))
    assert cfg.gen_slots == 4
    assert cfg.gen_spec_k == 4
    assert cfg.gen_spec_ngram == 2
