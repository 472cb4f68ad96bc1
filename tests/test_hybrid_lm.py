"""HybridLM (ISSUE 43): Gated DeltaNet layers with per-slot recurrent state
beside paged full-attention layers, through one cache description and one
``ContinuousBatcher``.

Everything runs on the CPU at tiny sizes with seeded weights; the kernels run
in the Pallas interpreter (``ops/backend.py``). The yardsticks are the token
recurrence (``gated_delta_recurrent``) and the benchmark's plain reference
(``benchmark/reference/olmo_hybrid_ref.py``, which imports nothing of the
program). No test asserts a time.
"""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from analytics_zoo_tpu.analysis import GraphLintError  # noqa: E402
from analytics_zoo_tpu.analysis.rules.decode import lint_decode_stability  # noqa: E402
from analytics_zoo_tpu.common import telemetry  # noqa: E402
from analytics_zoo_tpu.models.falcon_h1 import FalconH1LM  # noqa: E402
from analytics_zoo_tpu.models.hybrid_lm import HybridLM  # noqa: E402
from analytics_zoo_tpu.models.transformer import TransformerLM  # noqa: E402
from analytics_zoo_tpu.nn.module import precision_policy  # noqa: E402
from analytics_zoo_tpu.ops import gated_delta as gd  # noqa: E402
from analytics_zoo_tpu.ops.kv_cache import (PAGES, SLOT, KVCacheConfig,  # noqa: E402
                                            init_cache)
from analytics_zoo_tpu.serving.generation import ContinuousBatcher  # noqa: E402
from benchmark.reference import olmo_hybrid_ref  # noqa: E402

pytestmark = pytest.mark.generation

VOCAB, HIDDEN, INNER, HEADS, DK, DV = 97, 32, 48, 2, 8, 16
PATTERN = ["linear_attention"] * 3 + ["full_attention"]
REF = dict(n_head=HEADS, linear_heads=HEADS, key_dim=DK, value_dim=DV)


def _model(layer_types=PATTERN + PATTERN[:1], **kw):
    return HybridLM(vocab=VOCAB, hidden_size=HIDDEN, intermediate_size=INNER,
                    layer_types=layer_types, n_head=HEADS,
                    linear_num_heads=HEADS, linear_key_head_dim=DK,
                    linear_value_head_dim=DV, seq_len=128, **kw)


@pytest.fixture(scope="module")
def model_and_params():
    m = _model()
    params, _ = m.build(jax.random.PRNGKey(0))
    params = jax.tree_util.tree_map(        # norms' scales off one
        lambda a: a + 0.05 * jax.random.normal(
            jax.random.PRNGKey(a.size % 89), a.shape, a.dtype), params)
    return m, params


def _batcher(model_and_params, **kw):
    m, params = model_and_params
    kw = dict(dict(n_slots=2, page_size=4, max_seq_len=64), **kw)
    return ContinuousBatcher(m, params, **kw)


# ------------------------------------------------ the recurrence, three ways

def _gdn_inputs(rng, b, t):
    q = rng.normal(size=(b, t, HEADS, DK)).astype(np.float32)
    k = rng.normal(size=(b, t, HEADS, DK)).astype(np.float32)
    q /= np.linalg.norm(q, axis=-1, keepdims=True) * np.sqrt(DK)
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    v = rng.normal(size=(b, t, HEADS, DV)).astype(np.float32)
    log_alpha = (-0.3 * np.abs(rng.normal(size=(b, t, HEADS)))).astype(
        np.float32)
    beta = (2 / (1 + np.exp(-rng.normal(size=(b, t, HEADS))))).astype(
        np.float32)
    return q, k, v, log_alpha, beta


@pytest.mark.parametrize("kernel", [False, True], ids=["scan", "kernel"])
@pytest.mark.parametrize("t,chunk,true_len", [
    (48, 16, 48),       # a whole number of chunks
    (32, 64, 32),       # shorter than a chunk: one chunk of 32
    (32, 16, 21),       # a padded bucket: the state is that of 21 tokens
    (64, 16, 3),        # nearly all padding
], ids=["multiple", "short", "padded", "mostly_padding"])
def test_chunked_scan_equals_the_token_recurrence(np_rng, kernel, t, chunk,
                                                  true_len):
    q, k, v, log_alpha, beta = _gdn_inputs(np_rng, 2, t)
    valid = (np.arange(t) < true_len)[None, :, None]
    o, m = gd.gated_delta_chunked(q, k, v, np.where(valid, log_alpha, 0.0),
                                  np.where(valid, beta, 0.0), chunk=chunk,
                                  kernel=kernel)
    want_o, want_m = gd.gated_delta_recurrent(
        *(a[:, :true_len] for a in (q, k, v, log_alpha, beta)))
    assert np.abs(np.asarray(o)[:, :true_len] - want_o).max() < 2e-5
    assert np.abs(np.asarray(m) - want_m).max() < 2e-5


@pytest.mark.parametrize("c", [8, 16, 32, 48, 64])
def test_the_chunks_triangular_systems_are_inverted_exactly(np_rng, c):
    n = np.tril(np_rng.normal(size=(2, 3, c, c)).astype(np.float32), -1)
    inverse = np.asarray(gd._unit_lower_inverse(jnp.asarray(n)))
    residual = inverse @ (np.eye(c, dtype=np.float32) + n) - np.eye(c)
    assert np.abs(residual).max() < 1e-4 * max(1.0, np.abs(inverse).max())
    assert not np.triu(inverse, 1).any()


def test_keys_that_all_but_repeat_do_not_break_the_chunked_form(np_rng):
    """Neighbouring tokens of real text give nearly the same key; with beta
    near 2 and no decay the chunk's system is as stiff as it gets."""
    q, k, v, _, _ = _gdn_inputs(np_rng, 1, 128)
    k = k + 3 * k[:, :1]
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    log_alpha = np.zeros((1, 128, HEADS), np.float32)
    beta = np.full((1, 128, HEADS), 1.9, np.float32)
    o, m = gd.gated_delta_chunked(q, k, v, log_alpha, beta, chunk=64)
    want_o, want_m = gd.gated_delta_recurrent(q, k, v, log_alpha, beta)
    assert np.abs(np.asarray(o) - want_o).max() < 1e-4 * np.abs(want_o).max()
    assert np.abs(np.asarray(m) - want_m).max() < 1e-4 * np.abs(want_m).max()


def test_a_sequence_that_is_no_multiple_of_its_chunk_is_refused(np_rng):
    with pytest.raises(ValueError, match="no multiple"):
        gd.gated_delta_chunked(*_gdn_inputs(np_rng, 1, 40), chunk=16)


@pytest.mark.parametrize("live", [(1, 0, 1, 1, 0, 0), (0,) * 6, (1,) * 6],
                         ids=["some", "none", "all"])
def test_decode_update_steps_live_slots_and_leaves_the_rest(np_rng, live):
    live = np.array(live, bool)
    n = len(live)
    q, k, v, log_alpha, beta = (a[:, 0] for a in _gdn_inputs(np_rng, n, 1))
    state = jnp.asarray(np_rng.normal(size=(n, DK, HEADS * DV)), jnp.float32)
    o, new = jax.jit(gd.gdn_decode)(state, q, k, v, np.exp(log_alpha), beta,
                                    live)
    want_o, want = gd.gated_delta_recurrent(
        q[:, None], k[:, None], v[:, None], log_alpha[:, None], beta[:, None],
        state0=gd.lanes_to_state(state, HEADS))
    want = gd.state_to_lanes(want)
    assert (np.asarray(new)[~live] == np.asarray(state)[~live]).all()
    assert not np.asarray(o)[~live].any()
    if live.any():
        assert np.abs(np.asarray(new)[live] - want[live]).max() < 1e-5
        assert np.abs(np.asarray(o)[live] - want_o[:, 0][live]).max() < 1e-5


def test_state_layout_round_trips(np_rng):
    m = jnp.asarray(np_rng.normal(size=(3, HEADS, DK, DV)), jnp.float32)
    lanes = gd.state_to_lanes(m)
    assert lanes.shape == (3, DK, HEADS * DV)
    assert (gd.lanes_to_state(lanes, HEADS) == m).all()
    assert gd.head_group(30, 192) == 2 and gd.head_group(2, 16) == 2


# ------------------------------------------- the model against the reference

def test_apply_equals_the_plain_reference(model_and_params, np_rng):
    m, params = model_and_params
    ids = np_rng.integers(0, VOCAB, size=(2, 40)).astype(np.int32)
    got, _ = m.apply(params, {}, ids)
    want = olmo_hybrid_ref.logits(params, ids, **REF)
    assert np.abs(np.asarray(got) - np.asarray(want)).max() < 1e-4
    # and JAX differentiates the teacher-forced forward
    grads = jax.grad(lambda p: m.apply(p, {}, ids)[0].sum())(params)
    assert all(np.isfinite(np.asarray(g)).all()
               for g in jax.tree_util.tree_leaves(grads))


def _prefill_two_slots(m, params, ids, lengths, slots, bucket=32, n_slots=4):
    cfg, cache = m.init_kv_cache(n_slots, page_size=4, max_seq_len=64)
    table = np.zeros((n_slots, cfg.pages_per_slot), np.int32)
    for i, s in enumerate(slots):
        table[s, :12] = 1 + 12 * i + np.arange(12)
    padded = np.zeros((len(slots), bucket), np.int32)
    for i, n in enumerate(lengths):
        padded[i, :n] = ids[i, :n]
    logits, cache = m.prefill(params, cache, padded,
                              np.asarray(lengths, np.int32), table[slots],
                              page_size=4, slots=np.asarray(slots, np.int32))
    return logits, cache, table


def test_prefill_then_decode_through_both_caches_equals_the_reference(
        model_and_params, np_rng):
    """Two slots with different lengths in one padded bucket, filled out of
    order, then 8 teacher-forced decode steps: logits against the reference's
    full forward."""
    m, params = model_and_params
    ids = np_rng.integers(0, VOCAB, size=(2, 40)).astype(np.int32)
    want = np.asarray(olmo_hybrid_ref.logits(params, ids, **REF))
    slots, lengths = [2, 0], [13, 21]
    logits, cache, table = _prefill_two_slots(m, params, ids, lengths, slots)
    for row, n in enumerate(lengths):
        assert np.abs(np.asarray(logits[row]) - want[row, n - 1]).max() < 1e-4
    pos = dict(zip(slots, lengths))
    zeros = np.zeros(4, np.uint32)
    for _ in range(8):
        step_ids, at = np.zeros(4, np.int32), np.zeros(4, np.int32)
        for row, s in enumerate(slots):
            step_ids[s], at[s] = ids[row, pos[s]], pos[s]
        _, logits, cache = m.decode_step(
            params, cache, step_ids, at, table, zeros, zeros,
            np.zeros(4, np.float32), page_size=4)
        for row, s in enumerate(slots):
            assert np.abs(np.asarray(logits[s]) - want[row, pos[s]]).max() \
                < 1e-4
            pos[s] += 1


def test_a_prefill_given_no_slot_fills_slots_from_zero(model_and_params,
                                                       np_rng):
    """What the benchmark's logit probe calls: ``prefill(params, cache, ids,
    lengths, table[:1])`` and then a decode step at slot 0."""
    m, params = model_and_params
    ids = np_rng.integers(0, VOCAB, size=(1, 20)).astype(np.int32)
    cfg, cache = m.init_kv_cache(3, page_size=4, max_seq_len=64)
    table = np.zeros((3, cfg.pages_per_slot), np.int32)
    table[0, :8] = 1 + np.arange(8)
    padded = np.zeros((1, 32), np.int32)
    padded[0, :19] = ids[0, :19]
    _, cache = m.prefill(params, cache, padded, np.array([19], np.int32),
                         table[:1], page_size=4)
    zeros = np.zeros(3, np.uint32)
    at = np.array([19, 0, 0], np.int32)
    _, logits, _ = m.decode_step(
        params, cache, np.array([ids[0, 19], 0, 0], np.int32), at, table,
        zeros, zeros, np.zeros(3, np.float32), page_size=4)
    want = np.asarray(olmo_hybrid_ref.logits(params, ids, **REF))
    assert np.abs(np.asarray(logits[0]) - want[0, 19]).max() < 1e-4


def test_a_reused_slot_starts_from_its_prompt_alone(model_and_params, np_rng):
    """A second stream's prefill into a slot that decoded another stream
    gives, bit for bit, the logits and the state of a fresh cache."""
    m, params = model_and_params
    ids = np_rng.integers(0, VOCAB, size=(2, 40)).astype(np.int32)
    _, used, table = _prefill_two_slots(m, params, ids[:1], [17], [1])
    zeros = np.zeros(4, np.uint32)
    for pos in range(17, 22):           # the first stream decodes on
        step_ids, at = np.zeros(4, np.int32), np.zeros(4, np.int32)
        step_ids[1], at[1] = ids[0, pos], pos
        _, _, used = m.decode_step(params, used, step_ids, at, table, zeros,
                                   zeros, np.zeros(4, np.float32), page_size=4)
    padded = np.zeros((1, 32), np.int32)
    padded[0, :9] = ids[1, :9]
    args = (padded, np.array([9], np.int32), table[[1]])
    again, used = m.prefill(params, used, *args, page_size=4,
                            slots=np.array([1], np.int32))
    fresh_logits, fresh = m.prefill(
        params, m.init_kv_cache(4, page_size=4, max_seq_len=64)[1], *args,
        page_size=4, slots=np.array([1], np.int32))
    assert (np.asarray(again) == np.asarray(fresh_logits)).all()
    for name in ("recurrent", "conv"):
        for a, b in zip(used[name], fresh[name]):
            assert (np.asarray(a[1]) == np.asarray(b[1])).all()


# --------------------------------------------------- through the batcher

def _streams(b, prompts, n_new=10):
    handles = [b.submit(p, max_new_tokens=n_new) for p in prompts]
    return [h.result(timeout_s=300) for h in handles]


def test_streams_equal_the_full_forward_and_the_drained_order(
        model_and_params, np_rng):
    """Five greedy streams over two slots (so slots are reused while others
    decode, with a step in flight): each token is the full forward's argmax,
    and the same streams come out with launch-ahead drained every pass."""
    m, params = model_and_params
    prompts = [np_rng.integers(1, VOCAB, size=n).astype(np.int32)
               for n in (5, 11, 7, 20, 3)]
    ahead = _batcher(model_and_params)
    drained = _batcher(model_and_params)
    drained._why_drain = lambda flight, rows: "admit"
    try:
        got = _streams(ahead, prompts)
        assert got == _streams(drained, prompts)
        launches = ahead.stats()["decode_launches"]
        assert launches["ahead"] > launches["drained"]
        assert drained.stats()["decode_launches"]["ahead"] == 0
    finally:
        ahead.close()
        drained.close()
    forward = jax.jit(lambda ids: m.apply(params, {}, ids)[0])
    for prompt, out in zip(prompts, got):
        # causal: one forward over prompt + stream gives every step's logits
        seq = np.zeros((1, 32), np.int32)
        seq[0, :len(prompt) + len(out)] = list(prompt) + out
        best = np.asarray(jnp.argmax(forward(seq)[0], axis=-1))
        assert best[len(prompt) - 1:len(prompt) + len(out) - 1].tolist() == out


def test_a_stream_ended_by_eos_leaves_nothing_to_the_slots_next_stream(
        model_and_params, np_rng):
    """EOS is found only at the collect, so the step launched ahead has run
    on the row of a stream that has ended; the stream admitted into that
    slot next must equal the one a fresh batcher gives."""
    first = np_rng.integers(1, VOCAB, size=6).astype(np.int32)
    second = np_rng.integers(1, VOCAB, size=9).astype(np.int32)
    fresh = _batcher(model_and_params, n_slots=1)
    reused = _batcher(model_and_params, n_slots=1)
    try:
        free = fresh.generate(first, max_new_tokens=12)
        want = fresh.generate(second, max_new_tokens=12)
        fresh.close()
        fresh = _batcher(model_and_params, n_slots=1)
        want = fresh.generate(second, max_new_tokens=12)
        a = reused.submit(first, max_new_tokens=12, eos_id=int(free[4]))
        b = reused.submit(second, max_new_tokens=12)
        assert a.result(timeout_s=300) == free[:free.index(free[4]) + 1]
        assert b.result(timeout_s=300) == want
    finally:
        fresh.close()
        reused.close()


@pytest.mark.parametrize("which", ["hybrid", "falcon_h1"])
@pytest.mark.parametrize("option,words", [
    (dict(prefix_cache_pages=4), "prefix reuse needs a snapshot"),
    (dict(spec_k=3), "rejected draft would have to roll that state back"),
    (dict(prefill_chunk_tokens=8), "{model} has none"),
], ids=["prefix_cache", "speculation", "chunked_prefill"])
def test_what_assumes_the_cache_is_pages_is_refused_in_words(
        model_and_params, option, words, which):
    """The batcher decides by ``cfg.slot_state`` alone: a model whose every
    layer keeps pages beside its slot state is refused what a model with one
    linear layer is, in the same words."""
    served = model_and_params if which == "hybrid" else _falcon_and_params()
    with pytest.raises(ValueError, match=words.format(
            model=type(served[0]).__name__)):
        _batcher(served, autostart=False, **option)


# ------------------------------- pages alone: the shared entry points serve

FULL_ONLY = ["full_attention"] * 3


@pytest.fixture(scope="module")
def full_only():
    """A pattern of full attention alone: its cache is pages, so nothing
    that assumes pages has to be refused."""
    m = _model(FULL_ONLY)
    params, _ = m.build(jax.random.PRNGKey(1))
    return m, jax.tree_util.tree_map(
        lambda a: a + 0.05 * jax.random.normal(
            jax.random.PRNGKey(a.size % 89), a.shape, a.dtype), params)


def test_a_pattern_of_pages_alone_verifies_and_chunks_as_its_whole_forward(
        full_only, np_rng):
    """``verify_step`` and ``prefill_chunk`` are the base's, over
    ``MultiHeadAttention.decode`` at a wider query: a prompt fed in
    chunks (the third starting mid-page) ends at the forward's logits; a
    verify step over the forward's own greedy continuation accepts every
    draft and emits the k tokens that follow."""
    m, params = full_only
    assert m.init_kv_cache(2, page_size=4, max_seq_len=64)[0].slot_state == ()
    ids = np_rng.integers(1, VOCAB, size=(1, 40)).astype(np.int32)
    want = np.asarray(m.apply(params, {}, ids)[0])[0]           # (40, V)
    cfg, cache = m.init_kv_cache(2, page_size=4, max_seq_len=64)
    table = np.zeros((2, cfg.pages_per_slot + 2), np.int32)
    table[1, :12] = 1 + np.arange(12)
    done = 0
    for n in (8, 6, 7):                 # 21 tokens: 8, then 6 of 8, then 7
        chunk = np.zeros((1, 8), np.int32)
        chunk[0, :n] = ids[0, done:done + n]
        logits, cache = m.prefill_chunk(
            params, cache, chunk, np.array([done], np.int32),
            np.array([n], np.int32), table[[1]], page_size=4)
        done += n
        assert np.abs(np.asarray(logits[0]) - want[done - 1]).max() < 1e-4
    # the forward's own greedy continuation of the 21 tokens, as the draft
    k, seq = 4, ids[0, :done].tolist()
    forward = jax.jit(lambda x: m.apply(params, {}, x)[0])
    for _ in range(k + 1):
        padded = np.zeros((1, 32), np.int32)
        padded[0, :len(seq)] = seq
        seq.append(int(np.argmax(np.asarray(forward(padded))[0, len(seq) - 1])))
    step = np.zeros((2, k), np.int32)
    step[1] = seq[done:done + k]        # the certain token, then k-1 drafts
    zeros = np.zeros(2, np.uint32)
    accepted, tokens, _, cache = m.verify_step(
        params, cache, step, np.array([0, done], np.int32),
        table[:, :cfg.pages_per_slot], zeros, zeros,
        np.zeros(2, np.float32), page_size=4)
    assert int(accepted[1]) == k - 1
    assert np.asarray(tokens)[1].tolist() == seq[done + 1:done + k + 1]


def test_a_batcher_over_pages_alone_accepts_what_a_slot_state_refuses(
        full_only, np_rng):
    m, params = full_only
    prompts = [np_rng.integers(1, VOCAB, size=n).astype(np.int32)
               for n in (5, 23, 9)]
    plain = _batcher(full_only)
    both = _batcher(full_only, spec_k=3, prefill_chunk_tokens=16,
                    prefix_cache_pages=4)
    try:
        want = _streams(plain, prompts)
        assert _streams(both, prompts) == want
        stats = both.stats()
        assert stats["prefill"]["chunks"] >= 4
        assert both.spec_steps > 0
    finally:
        plain.close()
        both.close()
    forward = jax.jit(lambda ids: m.apply(params, {}, ids)[0])
    for prompt, out in zip(prompts, want):
        seq = np.zeros((1, 64), np.int32)
        seq[0, :len(prompt) + len(out)] = list(prompt) + out
        best = np.asarray(jnp.argmax(forward(seq)[0], axis=-1))
        assert best[len(prompt) - 1:len(prompt) + len(out) - 1].tolist() == out


def test_a_linear_layer_takes_one_token_a_row(model_and_params):
    """What the refusals rest on: the recurrence has no k-token step."""
    m, params = model_and_params
    cfg, cache = m.init_kv_cache(2, page_size=4, max_seq_len=64)
    zeros = np.zeros(2, np.uint32)
    with pytest.raises(ValueError, match="takes one token a row"):
        m.verify_step(params, cache, np.zeros((2, 3), np.int32),
                      np.zeros(2, np.int32),
                      np.zeros((2, cfg.pages_per_slot), np.int32), zeros,
                      zeros, np.zeros(2, np.float32), page_size=4)


# ------------------------------------------- one contract at the batcher

def _gpt():
    m = TransformerLM(vocab=VOCAB, hidden_size=HIDDEN, n_block=2, n_head=HEADS,
                      seq_len=128)
    return m, m.build(jax.random.PRNGKey(2))[0]


def test_a_model_of_pages_alone_ignores_the_slots_it_is_told(np_rng):
    m, params = _gpt()
    cfg, cache = m.init_kv_cache(4, page_size=4, max_seq_len=64)
    ids = np_rng.integers(1, VOCAB, size=(2, 16)).astype(np.int32)
    table = np.zeros((2, cfg.pages_per_slot), np.int32)
    table[0, :4], table[1, :4] = 1 + np.arange(4), 5 + np.arange(4)
    args = (params, cache, ids, np.array([13, 16], np.int32), table)
    logits, filled = m.prefill(*args, page_size=4)
    told, filled_told = m.prefill(*args, page_size=4,
                                  slots=np.array([3, 1], np.int32))
    assert (np.asarray(logits) == np.asarray(told)).all()
    for a, b in zip(jax.tree_util.tree_leaves(filled),
                    jax.tree_util.tree_leaves(filled_told)):
        assert (np.asarray(a) == np.asarray(b)).all()


def _falcon(**kw):
    """Falcon-H1's layer at a tiny size: every layer keeps pages AND a slot
    state."""
    return FalconH1LM(**dict(dict(
        vocab=VOCAB, hidden_size=HIDDEN, intermediate_size=INNER, n_layer=2,
        n_head=4, n_kv_head=2, head_dim=8, mamba_n_heads=4, mamba_d_head=8,
        mamba_d_state=8, mamba_n_groups=2, mamba_chunk_size=8, seq_len=128,
        key_multiplier=0.5, ssm_multipliers=(0.5, 0.25, 0.5, 0.5, 0.5),
        mlp_multipliers=(0.5, 0.25)), **kw))


def _falcon_and_params():
    m = _falcon()
    return m, m.build(jax.random.PRNGKey(3))[0]


@pytest.mark.parametrize("which", ["transformer", "hybrid", "falcon_h1"])
def test_one_prefill_executable_a_bucket_whatever_the_model(
        which, model_and_params, np_rng):
    """One jitted prefill serves every kind of model, told the slot or not
    (the benchmark's logit probe passes none): an executable a bucket."""
    b = _batcher({"transformer": _gpt, "falcon_h1": _falcon_and_params,
                  "hybrid": lambda: model_and_params}[which]())
    try:
        for n in (5, 11, 7, 20):                    # buckets 8, 16 and 32
            b.generate(np_rng.integers(1, VOCAB, size=n).astype(np.int32),
                       max_new_tokens=3)
        built = b._prefill.__wrapped__._cache_size
        assert built() == len(b.prefill_buckets) == 3
        table = np.zeros((1, b.cfg.pages_per_slot), np.int32)
        table[0, :2] = 1 + np.arange(2)
        _, b.cache = b._prefill(b.params, b.cache, np.ones((1, 8), np.int32),
                                np.array([6], np.int32), table)
        assert built() == 3
    finally:
        b.close()


# ------------------------------------------------- the trees checkpoints read

GOLDEN = {
    "transformer": (
        lambda: TransformerLM(vocab=61, hidden_size=16, n_block=2, n_head=2,
                              seq_len=24, intermediate_size=40), """
block0/attn/out_bias (16,) float32 0.000000
block0/attn/out_kernel (16, 16) float32 -4.764462
block0/attn/qkv_bias (48,) float32 0.000000
block0/attn/qkv_kernel (16, 48) float32 -5.345192
block0/ln1/beta (16,) float32 0.000000
block0/ln1/gamma (16,) float32 0.715328
block0/ln2/beta (16,) float32 0.000000
block0/ln2/gamma (16,) float32 0.715328
block0/mlp_down_bias (16,) float32 0.000000
block0/mlp_down_kernel (40, 16) float32 -4.120924
block0/mlp_up_bias (40,) float32 0.000000
block0/mlp_up_kernel (16, 40) float32 1.624763
block1/attn/out_bias (16,) float32 0.000000
block1/attn/out_kernel (16, 16) float32 0.291964
block1/attn/qkv_bias (48,) float32 0.000000
block1/attn/qkv_kernel (16, 48) float32 -5.327025
block1/ln1/beta (16,) float32 0.000000
block1/ln1/gamma (16,) float32 0.715328
block1/ln2/beta (16,) float32 0.000000
block1/ln2/gamma (16,) float32 0.715328
block1/mlp_down_bias (16,) float32 0.000000
block1/mlp_down_kernel (40, 16) float32 -2.776223
block1/mlp_up_bias (40,) float32 0.000000
block1/mlp_up_kernel (16, 40) float32 -1.288532
ln_f/beta (16,) float32 0.000000
ln_f/gamma (16,) float32 0.715328
logits_kernel (16, 61) float32 1.898156
pos_embeddings (24, 16) float32 0.166449
token_embeddings (61, 16) float32 -0.450775
"""),
    "hybrid": (
        lambda: HybridLM(vocab=61, hidden_size=16, intermediate_size=24,
                         layer_types=["linear_attention", "full_attention"],
                         n_head=2, linear_num_heads=2, linear_key_head_dim=4,
                         linear_value_head_dim=8, seq_len=64), """
final_norm (16,) float32 0.715328
layer0/mixer/A_log (2,) float32 3.828246
layer0/mixer/ba_kernel (16, 4) float32 -0.670827
layer0/mixer/conv_kernel (32, 4) float32 3.602679
layer0/mixer/dt_bias (2,) float32 -5.077539
layer0/mixer/gate_kernel (16, 16) float32 3.780363
layer0/mixer/norm_scale (8,) float32 1.478254
layer0/mixer/out_kernel (16, 16) float32 -0.063086
layer0/mixer/qkv_kernel (16, 32) float32 -0.511350
layer0/mixer_norm (16,) float32 0.715328
layer0/mlp/down_kernel (24, 16) float32 -0.831366
layer0/mlp/gate_kernel (16, 24) float32 -0.503372
layer0/mlp/up_kernel (16, 24) float32 -6.003270
layer0/mlp_norm (16,) float32 0.715328
layer1/mixer/k_norm (16,) float32 0.715328
layer1/mixer/out_kernel (16, 16) float32 0.800188
layer1/mixer/q_norm (16,) float32 0.715328
layer1/mixer/qkv_kernel (16, 48) float32 4.629661
layer1/mixer_norm (16,) float32 0.715328
layer1/mlp/down_kernel (24, 16) float32 0.317985
layer1/mlp/gate_kernel (16, 24) float32 4.045587
layer1/mlp/up_kernel (16, 24) float32 -2.971600
layer1/mlp_norm (16,) float32 0.715328
logits_kernel (16, 61) float32 0.697175
token_embeddings (61, 16) float32 -0.450775
"""),
}


GOLDEN["falcon_h1"] = (
    lambda: FalconH1LM(vocab=61, hidden_size=16, intermediate_size=24,
                       n_layer=1, n_head=4, n_kv_head=2, head_dim=4,
                       mamba_n_heads=2, mamba_d_head=8, mamba_d_state=4,
                       mamba_n_groups=2, seq_len=64, key_multiplier=0.5,
                       ssm_in_multiplier=0.25,
                       ssm_multipliers=(0.5, 0.25, 0.5, 2.0, 0.5),
                       mlp_multipliers=(0.5, 0.25)), """
final_norm (16,) float32 0.715328
layer0/attn/out_kernel (16, 16) float32 3.780363
layer0/attn/qkv_kernel (16, 32) float32 0.234265
layer0/input_norm (16,) float32 0.715328
layer0/mlp/down_kernel (24, 16) float32 1.852206
layer0/mlp/gate_kernel (16, 24) float32 9.368815
layer0/mlp/up_kernel (16, 24) float32 0.472813
layer0/mlp_norm (16,) float32 0.715328
layer0/ssm/A_log (2,) float32 2.557086
layer0/ssm/D (2,) float32 1.540302
layer0/ssm/conv_bias (32,) float32 0.000000
layer0/ssm/conv_kernel (32, 4) float32 -0.761026
layer0/ssm/dt_bias (2,) float32 -6.236320
layer0/ssm/in_kernel (16, 50) float32 -15.658224
layer0/ssm/norm_scale (16,) float32 0.715328
layer0/ssm/out_kernel (16, 16) float32 -2.022944
logits_kernel (16, 61) float32 0.697175
token_embeddings (61, 16) float32 -0.450775
""")


@pytest.mark.parametrize("which", sorted(GOLDEN))
def test_the_parameter_tree_is_the_one_checkpoints_read(which):
    """Leaf paths, shapes, dtypes, and the values ``build`` draws from
    ``PRNGKey(11)`` (each leaf's sum against ``cos(0), cos(1), ...``): the
    benchmark's references, the sharding rules and every checkpoint address
    the leaves by these names, and a seed's weights are what a run is
    reproduced from. A renamed leaf or a moved ``jax.random.split`` fails
    here."""
    make, golden = GOLDEN[which]
    params, _ = make().build(jax.random.PRNGKey(11))
    flat, _ = jax.tree_util.tree_flatten_with_path(params)
    got = {}
    for path, leaf in flat:
        a = np.asarray(leaf, np.float64)
        ramp = np.cos(np.arange(a.size, dtype=np.float64)).reshape(a.shape)
        got["/".join(str(k.key) for k in path)] = (
            str(tuple(leaf.shape)).replace(" ", ""), str(leaf.dtype),
            float((a * ramp).sum()))
    want = {}
    for line in golden.strip().splitlines():
        name, rest = line.split(" ", 1)
        shape, dtype, checksum = rest.rsplit(" ", 2)
        want[name] = (shape.replace(" ", ""), dtype, float(checksum))
    assert sorted(got) == sorted(want)
    for name, (shape, dtype, checksum) in want.items():
        assert got[name][:2] == (shape, dtype), name
        assert abs(got[name][2] - checksum) < 1e-4, name


def test_speculation_by_hot_swap_and_preemption_are_refused(
        model_and_params, np_rng):
    m, params = model_and_params
    b = _batcher(model_and_params, n_slots=1)
    try:
        with pytest.raises(ValueError, match="spec_k=4"):
            b.swap_params(params, spec={"k": 4})
        # a critical request does not park a bulk stream: it waits, and both
        # streams come out whole
        before = telemetry.snapshot().get(
            "zoo_gen_preemptions_total", {"samples": {}})["samples"]
        bulk = b.submit(np_rng.integers(1, VOCAB, size=5).astype(np.int32),
                        max_new_tokens=20, priority="bulk")
        first = next(bulk.tokens(timeout_s=300))
        critical = b.submit(np_rng.integers(1, VOCAB, size=4).astype(np.int32),
                            max_new_tokens=3, priority="critical")
        assert len(critical.result(timeout_s=300)) == 3
        assert len([first] + list(bulk.tokens(timeout_s=300))) == 20
        assert telemetry.snapshot().get(
            "zoo_gen_preemptions_total", {"samples": {}})["samples"] == before
        assert b._preempt_refused
    finally:
        b.close()


def test_a_bf16_tree_is_served_without_a_copy():
    m = _model(PATTERN)
    with precision_policy(param_dtype="bfloat16", compute_dtype="bfloat16"):
        params = jax.device_put(m.build(jax.random.PRNGKey(0))[0])
        b = ContinuousBatcher(m, params, n_slots=2, page_size=4,
                              max_seq_len=32, autostart=False)
    given = jax.tree_util.tree_leaves(params)
    served = jax.tree_util.tree_leaves(b.params)
    assert all(s is g for s, g in zip(served, given))
    flags = jax.tree_util.tree_leaves(m.cast_at_use(params))
    kinds = {(str(g.dtype), bool(f)) for g, f in zip(given, flags)}
    # every matmul kernel is bfloat16 and declared; the decay's two vectors
    # stay float32 and are not
    assert ("bfloat16", True) in kinds and ("float32", False) in kinds
    assert ("float32", True) not in kinds
    assert b.stats()["param_bytes"]["bfloat16"] > 0


def test_under_an_f32_tree_only_declared_leaves_are_cast(model_and_params):
    m, params = model_and_params
    with precision_policy(compute_dtype="bfloat16"):
        b = ContinuousBatcher(m, params, n_slots=2, page_size=4,
                              max_seq_len=32, autostart=False)
    served = b.params
    assert served["layer0"]["mixer"]["qkv_kernel"].dtype == jnp.bfloat16
    assert served["token_embeddings"].dtype == jnp.bfloat16
    for name in ("A_log", "dt_bias", "conv_kernel", "norm_scale"):
        assert served["layer0"]["mixer"][name] is params["layer0"]["mixer"][
            name]
    assert served["final_norm"] is params["final_norm"]


# ------------------------------------------------ the cache's description

def test_the_cache_names_each_layers_kind(model_and_params):
    m, _ = model_and_params
    cfg, cache = m.init_kv_cache(3, page_size=4, max_seq_len=64, n_pages=20)
    assert cfg.kinds == (SLOT, SLOT, SLOT, PAGES, SLOT)
    assert [cfg.index_in_kind(i) for i in range(5)] == [0, 1, 2, 0, 3]
    assert set(cache) == {"k", "v", "recurrent", "conv"}
    assert len(cache["k"]) == len(cache["v"]) == 1      # pools: 1, not 5
    assert cache["k"][0].shape == (20, 4, 8, HIDDEN // HEADS)
    assert len(cache["recurrent"]) == len(cache["conv"]) == 4
    assert cache["recurrent"][0].shape == (3, DK, HEADS * DV)
    assert cache["recurrent"][0].dtype == jnp.float32
    assert cache["conv"][0].shape == (3, 3, 2 * HEADS * DK + HEADS * DV)
    held = {name: sum(int(a.nbytes) for a in leaves)
            for name, leaves in cache.items()}
    assert cfg.bytes_by_kind() == {
        "pages": held["k"] + held["v"], "recurrent": held["recurrent"],
        "conv": held["conv"]}


def test_a_model_of_pages_alone_gets_the_cache_it_always_got():
    m = TransformerLM(vocab=64, hidden_size=32, n_block=3, n_head=2,
                      seq_len=64)
    cfg, cache = m.init_kv_cache(2, page_size=4, max_seq_len=32)
    assert cfg.layer_kinds == () and cfg.slot_state == ()
    assert cfg.kinds == (PAGES,) * 3 and set(cache) == {"k", "v"}
    assert len(cache["k"]) == 3 and cache["k"][0].shape == (17, 4, 2, 16)
    assert cfg.bytes_by_kind() == {"pages": sum(
        int(a.nbytes) for a in jax.tree_util.tree_leaves(cache))}


def test_a_layer_may_keep_both_kinds():
    """Pages alone, then both, then a slot state alone, then both: each kind
    counts the layers that keep it, and a layer of both has a place in
    each."""
    both = (PAGES, SLOT)
    leaves = (("ssm", (2, 4, 8), jnp.float32), ("conv", (3, 24), jnp.bfloat16))
    cfg = KVCacheConfig(n_layers=4, n_heads=2, head_dim=8, n_slots=3,
                        page_size=4, pages_per_slot=4, n_pages=10,
                        dtype=jnp.bfloat16, layer_kinds=(PAGES, both, SLOT,
                                                         both),
                        slot_state=leaves)
    assert (cfg.n_page_layers, cfg.n_slot_layers) == (3, 3)
    assert [cfg.index_in_kind(i, PAGES) for i in (0, 1, 3)] == [0, 1, 2]
    assert [cfg.index_in_kind(i, SLOT) for i in (1, 2, 3)] == [0, 1, 2]
    assert cfg.index_in_kind(0) == 0 and cfg.index_in_kind(2) == 1
    for layer, kind in ((1, None), (0, SLOT), (2, PAGES)):
        with pytest.raises(ValueError, match="keeps"):
            cfg.index_in_kind(layer, kind)
    cache = init_cache(cfg)
    assert {name: len(v) for name, v in cache.items()} == {
        "k": 3, "v": 3, "ssm": 3, "conv": 3}
    assert cache["k"][0].shape == (10, 4, 2, 8)
    assert cache["ssm"][0].shape == (3, 2, 4, 8)
    assert cache["conv"][0].dtype == jnp.bfloat16
    held = {name: sum(int(a.nbytes) for a in v) for name, v in cache.items()}
    assert cfg.bytes_by_kind() == {
        "pages": held["k"] + held["v"], "ssm": held["ssm"],
        "conv": held["conv"]}


def test_falcon_h1s_cache_is_both_kinds_in_every_layer():
    m = _falcon()
    cfg, cache = m.init_kv_cache(3, page_size=4, max_seq_len=64, n_pages=20)
    assert cfg.kinds == ((PAGES, SLOT),) * 2
    assert cfg.n_heads == 2 and cfg.head_dim == 8      # the KV heads' pools
    assert cache["k"][1].shape == (20, 4, 2, 8)
    assert cache["ssm"][1].shape == (3, 4, 8, 8)
    assert cache["ssm"][1].dtype == jnp.float32
    assert cache["conv"][1].shape == (3, 3, 4 * 8 + 2 * 2 * 8)
    held = {name: sum(int(a.nbytes) for a in v) for name, v in cache.items()}
    assert cfg.bytes_by_kind() == {
        "pages": held["k"] + held["v"], "ssm": held["ssm"],
        "conv": held["conv"]}


@pytest.mark.parametrize("bad", [
    dict(layer_kinds=(PAGES,)),                          # one of two layers
    dict(layer_kinds=(PAGES, "window")),                 # no such kind
    dict(layer_kinds=(PAGES, SLOT)),                     # no leaves named
    dict(slot_state=(("recurrent", (2, 2), jnp.float32),)),     # no layer
    dict(layer_kinds=(PAGES, (PAGES, SLOT))),            # no leaves named
    dict(layer_kinds=(PAGES, (SLOT, SLOT)),              # a kind twice
         slot_state=(("recurrent", (2, 2), jnp.float32),)),
    dict(layer_kinds=(PAGES, ())),                       # a layer of nothing
    dict(layer_kinds=(PAGES, (PAGES, "window"))),        # no such kind
], ids=["count", "kind", "no_leaves", "no_layer", "both_no_leaves",
        "kind_twice", "no_kind", "both_unknown_kind"])
def test_a_description_that_does_not_add_up_is_refused(bad):
    with pytest.raises(ValueError):
        KVCacheConfig(n_layers=2, n_heads=2, head_dim=4, n_slots=2, **bad)
    assert init_cache(KVCacheConfig(n_layers=2, n_heads=2, head_dim=4,
                                    n_slots=2))["k"][1].shape == (33, 16, 2, 4)


# -------------------------------------------------------- lints, accounting

def test_decode_lints_hold_for_the_state_leaves(model_and_params):
    m, params = model_and_params
    b = _batcher(model_and_params, autostart=False)
    assert b.check_decode_stability("raise") == []
    findings = lint_decode_stability(m, params, b.cfg, b.cache,
                                     donate_cache=False)
    # every leaf of the cache, state leaves among them, has to be donated
    assert sum(f.rule == "cache-alias" for f in findings) >= 1
    undonated = _batcher(model_and_params, autostart=False,
                         donate_cache=False)
    with pytest.raises(GraphLintError, match="cache-alias"):
        undonated.check_decode_stability("raise")


def test_the_hbm_budget_and_the_stats_count_state_bytes(model_and_params,
                                                        np_rng):
    b = _batcher(model_and_params, n_slots=8, n_pages=9)
    try:
        by_kind = b.stats()["cache_bytes"]
        assert by_kind == b.cfg.bytes_by_kind()
        assert by_kind["recurrent"] > 0 and by_kind["pages"] > 0
        params_bytes = sum(b.stats()["param_bytes"].values())
        # a budget that holds weights and pages but not the state is refused
        with pytest.raises(GraphLintError, match="hbm-budget"):
            b.check_decode_stability(
                "raise", hbm_budget_bytes=params_bytes + by_kind["pages"]
                + by_kind["recurrent"] // 2)
        gauge = telemetry.snapshot()["zoo_gen_cache_bytes"]["samples"]
        assert gauge["recurrent"] >= by_kind["recurrent"]

        def counter(name):
            return sum(telemetry.snapshot()[name]["samples"].values())

        steps0 = counter("zoo_gen_decode_slot_steps_total")
        tokens0 = counter("zoo_gen_linear_prefill_tokens_total")
        prompt = np_rng.integers(1, VOCAB, size=7).astype(np.int32)
        assert len(b.generate(prompt, max_new_tokens=6)) == 6
        assert counter("zoo_gen_linear_prefill_tokens_total") - tokens0 == 7
        assert counter("zoo_gen_decode_slot_steps_total") - steps0 >= 5
    finally:
        b.close()
