"""FalconH1LM (ISSUE 50): a Mamba-2 mixer and a grouped-KV rotary attention
side by side in every layer, two kinds of state a layer in one cache, through
the one walker and one ``ContinuousBatcher``.

Everything runs on the CPU in float32 at a tiny size with seeded weights; the
kernels run in the Pallas interpreter (``ops/backend.py``). The yardsticks
are the token recurrence (``ssd_recurrent``) and the benchmark's plain
reference (``benchmark/reference/falcon_h1_ref.py``, which imports nothing of
the program). No test asserts a time.

The tolerances. The logits of this model are small: the published
``lm_head_multiplier`` (1/128) over a Glorot head leaves them a spread of
some 5e-3 here, so every comparison of logits is RELATIVE to the
reference's spread. Float32 at ``highest`` on the CPU agrees to 1e-6 of it
(the chunked scan sums in another order than the token scan); 2e-5 of the
spread is the limit, and a multiplier off by a tenth moves the logits by
8e-3 of it (the B segment's) to four tenths of it (the head's): the last
test. That every multiplier shows at all is ``build``'s doing: the
projections into the mixers and the gate are drawn wider by what multiplies
them, as muP means them to be; under a plain Glorot draw the published
multipliers leave the recurrence a millionth of a layer's output and the
softmax flat (B, C, dt and the key's multiplier then moved the logits by
1e-6 to 4e-4 of their spread, which no comparison could hold a program to).
"""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from analytics_zoo_tpu.models.falcon_h1 import FalconH1LM  # noqa: E402
from analytics_zoo_tpu.ops import ssd  # noqa: E402
from analytics_zoo_tpu.ops.kv_cache import (PAGES, SLOT,  # noqa: E402
                                            decode_attention_multi,
                                            paged_read)
from analytics_zoo_tpu.ops.paged_attention import (paged_attention,  # noqa: E402
                                                   synthetic_paged_case)
from analytics_zoo_tpu.serving.generation import ContinuousBatcher  # noqa: E402
from benchmark.reference import falcon_h1_ref  # noqa: E402

pytestmark = pytest.mark.generation

VOCAB, HIDDEN, INNER, LAYERS = 512, 64, 96, 3
HEADS, KV_HEADS, HEAD_DIM = 4, 2, 16
SSM_HEADS, SSM_HEAD_DIM, STATE, GROUPS, CHUNK = 4, 16, 16, 2, 8
#: the published multipliers (Falcon-H1-34B-Instruct's config.json)
PUBLISHED = dict(
    embedding_multiplier=5.656854249492381, lm_head_multiplier=0.0078125,
    attention_in_multiplier=1.0, attention_out_multiplier=0.0375,
    key_multiplier=0.011048543456039804, ssm_in_multiplier=0.25,
    ssm_out_multiplier=0.08838834764831845,
    ssm_multipliers=(0.3535533905932738, 0.25, 0.1767766952966369, 0.5,
                     0.3535533905932738),
    mlp_multipliers=(0.1767766952966369, 0.011160714285714284))
REF = dict(n_head=HEADS, n_kv_head=KV_HEADS, head_dim=HEAD_DIM,
           ssm_heads=SSM_HEADS, ssm_head_dim=SSM_HEAD_DIM, state_dim=STATE,
           n_groups=GROUPS, rope_theta=1e11, epsilon=1e-5, **PUBLISHED)
#: of the reference's spread: float32 agreement (module docstring)
AGREE = 2e-5


def _model(**multipliers):
    return FalconH1LM(
        vocab=VOCAB, hidden_size=HIDDEN, intermediate_size=INNER,
        n_layer=LAYERS, n_head=HEADS, n_kv_head=KV_HEADS, head_dim=HEAD_DIM,
        mamba_n_heads=SSM_HEADS, mamba_d_head=SSM_HEAD_DIM,
        mamba_d_state=STATE, mamba_n_groups=GROUPS, mamba_d_conv=4,
        mamba_chunk_size=CHUNK, rope_theta=1e11, seq_len=256,
        **{**PUBLISHED, **multipliers})


def _params(m):
    params, _ = m.build(jax.random.PRNGKey(0))
    return jax.tree_util.tree_map(          # norms' scales and D off one,
        lambda a: a + 0.05 * jax.random.normal(     # the conv's bias off zero
            jax.random.PRNGKey(a.size % 89), a.shape, a.dtype), params)


@pytest.fixture(scope="module")
def model_and_params():
    m = _model()
    return m, _params(m)


@pytest.fixture(scope="module")
def ids_and_reference(model_and_params):
    _, params = model_and_params
    ids = np.random.default_rng(5).integers(0, VOCAB, size=(2, 40)).astype(
        np.int32)
    return ids, falcon_h1_ref.logits(params, ids, **REF)


def _off(got, want):
    """The largest error, as a share of the reference's spread."""
    return float(np.abs(np.asarray(got) - want).max() / want.std())


# ------------------------------------------------ the recurrence, three ways

def _ssd_inputs(rng, b, t):
    x = rng.normal(size=(b, t, SSM_HEADS, SSM_HEAD_DIM)).astype(np.float32)
    dt = np.log1p(np.exp(rng.normal(size=(b, t, SSM_HEADS)) - 2)).astype(
        np.float32)
    a = -np.exp(rng.uniform(0, np.log(16), size=SSM_HEADS)).astype(np.float32)
    bm = rng.normal(size=(b, t, GROUPS, STATE)).astype(np.float32)
    cm = rng.normal(size=(b, t, GROUPS, STATE)).astype(np.float32)
    return x, dt, a, bm, cm


@pytest.mark.parametrize("kernel", [False, True], ids=["scan", "kernel"])
@pytest.mark.parametrize("t,chunk,true_len", [
    (48, 8, 48),        # a whole number of chunks
    (32, 64, 32),       # shorter than a chunk: one chunk of 32
    (32, 8, 21),        # a padded bucket: no multiple of the chunk is true
    (64, 16, 3),        # nearly all padding
], ids=["multiple", "short", "padded", "mostly_padding"])
def test_chunked_scan_equals_the_token_recurrence(np_rng, kernel, t, chunk,
                                                  true_len):
    """Sums in another order: 2e-5 absolute on values of order 1-10."""
    x, dt, a, bm, cm = _ssd_inputs(np_rng, 2, t)
    valid = (np.arange(t) < true_len)[None, :, None]
    y, m = ssd.ssd_chunked(x, np.where(valid, dt, 0.0), a, bm, cm,
                           chunk=chunk, kernel=kernel)
    want_y, want_m = ssd.ssd_recurrent(
        x[:, :true_len], dt[:, :true_len], a, bm[:, :true_len],
        cm[:, :true_len])
    assert np.abs(np.asarray(y)[:, :true_len] - want_y).max() < 2e-5
    assert np.abs(np.asarray(m) - want_m).max() < 2e-5


def test_the_chunk_kernel_equals_its_jax_numpy_form(np_rng):
    """The same products in the same order: 1e-5."""
    x, dt, a, bm, cm = _ssd_inputs(np_rng, 2, 32)
    parts = ssd.chunk_prepare(x, dt, a, bm, cm, 8)
    y, m = ssd.ssd_chunk_fwd(*parts)
    want_y, want_m = ssd._chunk_pass_scan(*parts)
    assert np.abs(np.asarray(y) - want_y).max() < 1e-5
    assert np.abs(np.asarray(m) - want_m).max() < 1e-5


def test_a_sequence_that_is_no_multiple_of_its_chunk_is_refused(np_rng):
    with pytest.raises(ValueError, match="no multiple"):
        ssd.ssd_chunked(*_ssd_inputs(np_rng, 1, 40), chunk=16)


@pytest.mark.parametrize("live", [(1, 0, 1, 1, 0, 0), (0,) * 6, (1,) * 6],
                         ids=["some", "none", "all"])
def test_the_decode_kernel_steps_live_slots_and_leaves_the_rest(np_rng, live):
    """The kernel against its ``jax.numpy`` form (the same arithmetic a
    row: 1e-6); a row that is not live keeps its state bit for bit."""
    live = np.array(live, bool)
    n = len(live)
    x, dt, a, bm, cm = _ssd_inputs(np_rng, n, 1)
    state = jnp.asarray(np_rng.normal(
        size=(n, SSM_HEADS, STATE, SSM_HEAD_DIM)), jnp.float32)
    args = (x[:, 0], dt[:, 0], a, bm[:, 0], cm[:, 0], live)
    want_y, want = ssd.ssd_decode_step(state, *args)
    y, new = jax.jit(ssd.ssd_decode)(state, *args)
    assert (np.asarray(new)[~live] == np.asarray(state)[~live]).all()
    assert not np.asarray(y)[~live].any()
    assert np.abs(np.asarray(new) - want).max() < 1e-6
    assert np.abs(np.asarray(y) - want_y).max() < 1e-6


def test_the_state_after_a_prefill_is_that_of_as_many_decode_steps(np_rng):
    """T tokens through the chunked scan leave the state T steps of the
    decode kernel leave (5e-6: the chunk's decays are products of the
    steps')."""
    t = 21
    x, dt, a, bm, cm = _ssd_inputs(np_rng, 2, 24)
    valid = (np.arange(24) < t)[None, :, None]
    _, chunked = ssd.ssd_chunked(x, np.where(valid, dt, 0.0), a, bm, cm,
                                 chunk=8, kernel=True)
    state = jnp.zeros((2, SSM_HEADS, STATE, SSM_HEAD_DIM), jnp.float32)
    for i in range(t):
        _, state = ssd.ssd_decode(state, x[:, i], dt[:, i], a, bm[:, i],
                                  cm[:, i], np.ones(2, bool))
    assert np.abs(np.asarray(state) - np.asarray(chunked)).max() < 5e-6


# ------------------------------------------------- grouped paged attention

@pytest.mark.parametrize("q_len", [1, 4])
def test_grouped_paged_attention_equals_attention_on_repeated_heads(np_rng,
                                                                    q_len):
    """Three query heads a KV head as rows of the KV head's dots, against
    the plain path with K and V repeated a query head (float32: 1e-6); a slot
    that holds no stream reads zeros."""
    group, kv_heads, d = 3, 2, 16
    lengths = [q_len, 9, 0, 24, 13]
    _, k_pages, v_pages, table, lengths = synthetic_paged_case(
        5, 6, 4, kv_heads, d, q_len=q_len, rng=np_rng, lengths=lengths)
    q = jnp.asarray(np_rng.normal(size=(5, q_len, group * kv_heads, d)),
                    jnp.float32)
    got = np.asarray(paged_attention(q, k_pages, v_pages, table, lengths,
                                     page_size=4, interpret=True))
    ks, vs = paged_read(k_pages, table), paged_read(v_pages, table)
    want = np.asarray(decode_attention_multi(
        q, jnp.repeat(ks, group, axis=2), jnp.repeat(vs, group, axis=2),
        lengths))
    # and the plain path takes the pools' own heads
    plain = np.asarray(decode_attention_multi(q, ks, vs, lengths))
    live = np.asarray(lengths) > 0
    assert np.abs(got[live] - want[live]).max() < 1e-6
    assert np.abs(plain[live] - want[live]).max() < 1e-6
    assert not got[~live].any()
    with pytest.raises(ValueError, match="no multiple"):
        paged_attention(q[:, :, :3], k_pages, v_pages, table, lengths,
                        page_size=4, interpret=True)


# ------------------------------------------- the model against the reference

def test_apply_equals_the_plain_reference(model_and_params,
                                          ids_and_reference):
    m, params = model_and_params
    ids, want = ids_and_reference
    got, _ = m.apply(params, {}, ids)
    assert _off(got, want) < AGREE
    # and JAX differentiates the teacher-forced forward
    grads = jax.grad(lambda p: m.apply(p, {}, ids)[0].sum())(params)
    assert all(np.isfinite(np.asarray(g)).all()
               for g in jax.tree_util.tree_leaves(grads))


def _tables(cfg, slots, n_slots):
    table = np.zeros((n_slots, cfg.pages_per_slot), np.int32)
    for i, s in enumerate(slots):
        table[s, :12] = 1 + 12 * i + np.arange(12)
    return table


def _step(m, params, cache, table, ids, pos):
    """One decode step: ``pos`` maps a slot to (row of ``ids``, position)."""
    n = table.shape[0]
    step_ids, at = np.zeros(n, np.int32), np.zeros(n, np.int32)
    live = table.copy()
    for s, (row, p) in pos.items():
        step_ids[s], at[s] = ids[row, p], p
    for s in range(n):
        if s not in pos:
            live[s] = 0                     # the row sits this step out
    zeros = np.zeros(n, np.uint32)
    _, logits, cache = m.decode_step(params, cache, step_ids, at, live, zeros,
                                     zeros, np.zeros(n, np.float32),
                                     page_size=4)
    return logits, cache


def test_prefill_then_decode_through_both_caches_equals_the_reference(
        model_and_params, ids_and_reference):
    """Two slots with different lengths in one padded bucket, filled out of
    order, then teacher-forced decode steps: logits against the reference's
    full forward at every position. One row sits a step out and resumes from
    the state it had, bit for bit; then its slot is reused by a shorter
    prompt, which has to start from that prompt alone."""
    m, params = model_and_params
    ids, want = ids_and_reference
    slots, lengths = [2, 0], [13, 21]
    cfg, cache = m.init_kv_cache(4, page_size=4, max_seq_len=64)
    assert cfg.kinds == ((PAGES, SLOT),) * LAYERS
    table = _tables(cfg, slots, 4)
    padded = np.zeros((2, 32), np.int32)
    for i, n in enumerate(lengths):
        padded[i, :n] = ids[i, :n]
    logits, cache = m.prefill(params, cache, padded,
                              np.asarray(lengths, np.int32), table[slots],
                              page_size=4, slots=np.asarray(slots, np.int32))
    for row, n in enumerate(lengths):
        assert _off(logits[row], want[row, n - 1]) < AGREE
    pos = {s: [row, n] for row, (s, n) in enumerate(zip(slots, lengths))}
    for step in range(8):
        sat_out = {2} if step == 3 else set()
        before = [np.asarray(leaf[2]) for name in ("ssm", "conv")
                  for leaf in cache[name]]
        logits, cache = _step(m, params, cache, table, ids,
                              {s: tuple(v) for s, v in pos.items()
                               if s not in sat_out})
        if sat_out:
            after = [np.asarray(leaf[2]) for name in ("ssm", "conv")
                     for leaf in cache[name]]
            assert all((a == b).all() for a, b in zip(before, after))
        for s, (row, p) in pos.items():
            if s not in sat_out:
                assert _off(logits[s], want[row, p]) < AGREE
                pos[s][1] += 1
    # slot 2 again, by a shorter prompt (9 tokens of the other row)
    short = np.zeros((1, 32), np.int32)
    short[0, :9] = ids[1, :9]
    args = (short, np.array([9], np.int32), table[[2]])
    again, cache = m.prefill(params, cache, *args, page_size=4,
                             slots=np.array([2], np.int32))
    fresh_logits, fresh = m.prefill(
        params, m.init_kv_cache(4, page_size=4, max_seq_len=64)[1], *args,
        page_size=4, slots=np.array([2], np.int32))
    assert (np.asarray(again) == np.asarray(fresh_logits)).all()
    assert _off(again[0], want[1, 8]) < AGREE
    for name in ("ssm", "conv"):
        for a, b in zip(cache[name], fresh[name]):
            assert (np.asarray(a[2]) == np.asarray(b[2])).all()
    logits, cache = _step(m, params, cache, table, ids, {2: (1, 9)})
    assert _off(logits[2], want[1, 9]) < AGREE


def test_a_state_space_layer_takes_one_token_a_row(model_and_params):
    """What the batcher's refusals rest on: the recurrence has no k-token
    step."""
    m, params = model_and_params
    cfg, cache = m.init_kv_cache(2, page_size=4, max_seq_len=64)
    zeros = np.zeros(2, np.uint32)
    with pytest.raises(ValueError, match="takes one token a row"):
        m.verify_step(params, cache, np.zeros((2, 3), np.int32),
                      np.zeros(2, np.int32),
                      np.zeros((2, cfg.pages_per_slot), np.int32), zeros,
                      zeros, np.zeros(2, np.float32), page_size=4)


# --------------------------------------------------- through the batcher

def test_twelve_streams_over_four_slots_are_the_references_best(
        model_and_params, np_rng):
    """Greedy streams through ``ContinuousBatcher`` (slots reused while
    others decode, a step in flight): in float32 every served token is the
    reference's best at its position, ``served_gap`` 0 as the benchmark
    reads it."""
    m, params = model_and_params
    prompts = [np_rng.integers(1, VOCAB, size=n).astype(np.int32)
               for n in (5, 11, 7, 20, 3, 9, 14, 6, 17, 4, 12, 8)]
    b = ContinuousBatcher(m, params, n_slots=4, page_size=4, max_seq_len=64)
    try:
        handles = [b.submit(p, max_new_tokens=10) for p in prompts]
        streams = [h.result(timeout_s=300) for h in handles]
        assert b.stats()["cache_bytes"] == b.cfg.bytes_by_kind()
        assert set(b.stats()["cache_bytes"]) == {"pages", "ssm", "conv"}
    finally:
        b.close()
    for prompt, out in zip(prompts, streams):
        assert len(out) == 10
        seq = np.zeros((1, 32), np.int32)
        seq[0, :len(prompt) + len(out) - 1] = list(prompt) + out[:-1]
        rows = falcon_h1_ref.logits(params, seq, **REF)[0][
            len(prompt) - 1:len(prompt) + len(out) - 1]
        gap = rows.max(-1) - rows[np.arange(len(out)), out]
        assert gap.max() == 0.0


# ------------------------------------------- every multiplier is in its place

def _perturbed():
    for name, value in PUBLISHED.items():
        if isinstance(value, tuple):
            for i in range(len(value)):
                yield f"{name}[{i}]", {name: tuple(
                    v * (1.1 if j == i else 1.0) for j, v in enumerate(value))}
        else:
            yield name, {name: value * 1.1}


@pytest.mark.parametrize("name,changed", list(_perturbed()),
                         ids=[name for name, _ in _perturbed()])
def test_a_multiplier_off_by_a_tenth_fails_the_comparison(
        model_and_params, ids_and_reference, name, changed):
    """Each of the fourteen multipliers, a tenth larger in the program alone
    (the reference keeps the published value): the comparison of ``apply``
    with the reference has to fail, by a hundred times its limit or more, so
    that a dropped or misplaced term cannot hide inside the tolerance."""
    _, params = model_and_params
    ids, want = ids_and_reference
    got, _ = _model(**changed).apply(params, {}, ids)
    assert _off(got, want) > 100 * AGREE, name
