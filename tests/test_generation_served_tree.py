"""The generation batcher serves from weights cast once (ISSUE 30).

Under a bf16 policy ``ContinuousBatcher.params`` is the *served tree*: the
leaves the model declares as cast at use (``Layer.cast_at_use``) held in the
compute dtype, every other leaf the given array. These tests hold the three
things that makes safe: the arithmetic is the same bit for bit, the
declaration says what the forward does (read off the traced methods), and the
compute dtype is read once (a policy changed later cannot pair a bf16 tree
with an f32 trace).
"""

import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.extend.core import Literal

from analytics_zoo_tpu.analysis.graphlint import walk_eqns
from analytics_zoo_tpu.models.transformer import TransformerLM
from analytics_zoo_tpu.nn.layers.moe import MoE
from analytics_zoo_tpu.nn.module import (cast_params, compute_dtype,
                                         pinned_compute_dtype,
                                         precision_policy)
from analytics_zoo_tpu.ops.kv_cache import SCRATCH_PAGE
from analytics_zoo_tpu.serving.generation import ContinuousBatcher

pytestmark = pytest.mark.generation

VOCAB, HIDDEN, BLOCKS, HEADS, SEQ = 64, 32, 2, 2, 64
BF16, F32 = jnp.dtype("bfloat16"), jnp.dtype("float32")
PROMPTS = [np.arange(1, 8, dtype=np.int32), np.arange(20, 31, dtype=np.int32)]


def _model(cls=TransformerLM):
    return cls(vocab=VOCAB, hidden_size=HIDDEN, n_block=BLOCKS, n_head=HEADS,
               seq_len=SEQ)


@pytest.fixture(scope="module")
def model_and_params():
    m = _model()
    # tables at a scale where the sum of two rounded rows is not the rounded
    # sum of the rows: what (d) needs to tell a cast table from a kept one
    params, _ = m.build(jax.random.PRNGKey(0))
    return m, params


def _batcher(m, params, **kw):
    kw = dict(dict(n_slots=2, page_size=4, max_seq_len=32), **kw)
    return ContinuousBatcher(m, params, **kw)


def _bf16_batcher(m, params, **kw):
    with precision_policy(compute_dtype="bfloat16"):
        return _batcher(m, params, **kw)


def _streams(b):
    return [b.generate(p, max_new_tokens=10) for p in PROMPTS]


def _logits(b, params):
    """Prefill of 6 tokens and three decode steps through the batcher's own
    executables, given ``params``: what ``benchmark/serving_rig.py`` does
    with ``batcher.params``."""
    cfg = b.cfg
    seq = np.arange(3, 12, dtype=np.int32)
    n_prefill = 6
    ids = np.zeros((1, 8), np.int32)
    ids[0, :n_prefill] = seq[:n_prefill]
    table = np.full((cfg.n_slots, cfg.pages_per_slot), SCRATCH_PAGE, np.int32)
    table[0, :3] = 1 + np.arange(3)
    _, cache = b._pinned(b.model.init_kv_cache, cfg.n_slots,
                         page_size=cfg.page_size, max_seq_len=cfg.max_seq_len)
    logits, cache = b._prefill(params, cache, ids,
                               np.array([n_prefill], np.int32), table[:1])
    out = [np.asarray(logits)[0]]
    zeros = np.zeros(cfg.n_slots, np.uint32)
    for pos in range(n_prefill, len(seq)):
        step_ids = np.zeros(cfg.n_slots, np.int32)
        lengths = np.zeros(cfg.n_slots, np.int32)
        step_ids[0], lengths[0] = seq[pos], pos
        _next, logits, cache = b._decode(
            params, cache, step_ids, lengths, table, zeros, zeros,
            np.zeros(cfg.n_slots, np.float32))
        out.append(np.asarray(logits)[0])
    return np.stack(out)


def _split(m, params):
    """``{dtype: bytes}`` the declaration implies under a bf16 policy."""
    flags = jax.tree_util.tree_leaves(m.cast_at_use(params))
    leaves = jax.tree_util.tree_leaves(params)
    cast = sum(int(np.prod(l.shape)) for l, f in zip(leaves, flags) if f)
    kept = sum(int(np.prod(l.shape)) for l, f in zip(leaves, flags) if not f)
    return {"bfloat16": 2 * cast, "float32": 4 * kept}


# ---------------------------------------------------------------- (a), (d)

def test_served_tree_logits_and_streams_equal_cast_at_use(model_and_params):
    m, params = model_and_params
    b = _bf16_batcher(m, params, donate_cache=False)
    given = _bf16_batcher(m, params)
    try:
        assert b.params["logits_kernel"].dtype == BF16
        # the same executables, given the f32 tree: they cast at use
        np.testing.assert_array_equal(_logits(b, b.params),
                                      _logits(b, jax.device_put(params)))
        given.params = jax.device_put(params)
        assert _streams(b) == _streams(given)
    finally:
        b.close()
        given.close()


def test_norms_and_embedding_tables_keep_their_dtype(model_and_params):
    m, params = model_and_params
    b = _bf16_batcher(m, params, autostart=False)
    try:
        served = b.params
        for name in ("token_embeddings", "pos_embeddings"):
            assert served[name].dtype == F32
            assert served[name] is params[name]        # shared, not copied
        for blk in ("block0", "block1"):
            for ln in ("ln1", "ln2"):
                assert {l.dtype for l in jax.tree_util.tree_leaves(
                    served[blk][ln])} == {F32}
            assert served[blk]["attn"]["qkv_kernel"].dtype == BF16
            assert served[blk]["mlp_down_bias"].dtype == BF16
        assert {l.dtype for l in jax.tree_util.tree_leaves(
            served["ln_f"])} == {F32}
        assert b.stats()["param_bytes"] == _split(m, params)
    finally:
        b.close()


class _TablesCastToo(TransformerLM):
    """A wrong declaration: the tables are summed in f32 before the cast."""

    def cast_at_use(self, params):
        flags = super().cast_at_use(params)
        flags["token_embeddings"] = flags["pos_embeddings"] = True
        return flags


def test_a_wrong_declaration_is_caught_by_the_bit_comparison(model_and_params):
    _, params = model_and_params
    m = _model(_TablesCastToo)
    b = _bf16_batcher(m, params, donate_cache=False, autostart=False)
    try:
        assert b.params["token_embeddings"].dtype == BF16
        assert not np.array_equal(_logits(b, b.params),
                                  _logits(b, jax.device_put(params)))
    finally:
        b.close()


# --------------------------------------------------------------------- (b)

def _wide_down_casts(b, params):
    """f32 -> bf16 converts of hidden x hidden elements or more in the traced
    decode step, given ``params``."""
    args = (params,) + b._decode_args()[1:]
    closed = jax.make_jaxpr(b._decode)(*args)
    found = []
    for site in walk_eqns(closed.jaxpr):
        eqn = site.eqn
        if eqn.primitive.name != "convert_element_type" or site.in_kernel:
            continue
        src, dst = eqn.invars[0].aval, eqn.outvars[0].aval
        if (src.dtype == F32 and dst.dtype == BF16
                and int(np.prod(src.shape)) >= HIDDEN * HIDDEN):
            found.append(tuple(src.shape))
    return found


def test_traced_decode_step_holds_no_weight_cast(model_and_params):
    m, params = model_and_params
    b = _bf16_batcher(m, params, autostart=False)
    try:
        assert _wide_down_casts(b, b.params) == []
        # the given tree's step casts every block's four kernels and the head
        given = _wide_down_casts(b, jax.device_put(params))
        assert len(given) == 4 * BLOCKS + 1
        assert (HIDDEN, VOCAB) in given
    finally:
        b.close()


# --------------------------------------------------------------------- (c)

def test_without_a_policy_the_served_tree_is_the_given_tree(model_and_params):
    m, params = model_and_params
    b = _batcher(m, params, autostart=False)
    try:
        assert b.compute_dtype == F32
        given = jax.tree_util.tree_leaves(params)
        served = jax.tree_util.tree_leaves(b.params)
        assert all(s is g for s, g in zip(served, given))
        assert b.stats()["param_bytes"] == {
            "float32": sum(int(l.nbytes) for l in given)}
    finally:
        b.close()


def test_a_tree_already_in_the_compute_dtype_is_served_as_given(
        model_and_params):
    m, params = model_and_params
    half = cast_params(params, BF16)
    b = _bf16_batcher(m, half, autostart=False)
    wide = _batcher(m, half, autostart=False)     # f32 compute, bf16 leaves
    try:
        for bb in (b, wide):
            assert all(s is g for s, g in zip(
                jax.tree_util.tree_leaves(bb.params),
                jax.tree_util.tree_leaves(half)))
            assert set(bb.param_bytes) == {"bfloat16"}
    finally:
        b.close()
        wide.close()


def test_a_model_that_declares_nothing_is_served_as_given(model_and_params):
    m, params = model_and_params

    class Plain:                # the protocol alone, no cast_at_use
        vocab = VOCAB
        init_kv_cache = m.init_kv_cache
        prefill = m.prefill
        decode_step = m.decode_step

    b = _bf16_batcher(Plain(), params, autostart=False)
    try:
        assert b.params["logits_kernel"] is params["logits_kernel"]
        assert set(b.param_bytes) == {"float32"}
    finally:
        b.close()


# --------------------------------------------------------------------- (e)

def _await_swaps(b, n):
    deadline = time.time() + 30
    while b.swaps < n and time.time() < deadline:
        time.sleep(0.005)
    assert b.swaps == n


def _flip(b, params, version):
    swaps = b.swaps
    b.swap_params(params, version=version)
    _await_swaps(b, swaps + 1)
    assert b.version == version


def test_swap_then_rollback_is_stream_exact_and_param_bytes_follow(
        model_and_params):
    m, params = model_and_params
    params2 = jax.tree_util.tree_map(lambda p: p * 1.5 + 0.01, params)
    b = _bf16_batcher(m, params)
    fresh2 = _bf16_batcher(m, params2)
    try:
        first = _streams(b)
        assert b.stats()["param_bytes"] == _split(m, params)
        prev = b.host_params()      # what ModelSwapper retains for rollback
        assert prev["logits_kernel"].dtype == BF16      # the served tree
        _flip(b, params2, "v2")
        assert b.params["logits_kernel"].dtype == BF16
        assert b.stats()["param_bytes"] == _split(m, params2)
        second = _streams(b)
        assert second == _streams(fresh2) and second != first
        # a tree published in bf16 throughout: nothing is wider, all is kept
        _flip(b, cast_params(params2, BF16), "v2-half")
        total = sum(int(np.prod(l.shape))
                    for l in jax.tree_util.tree_leaves(params2))
        assert b.stats()["param_bytes"] == {"bfloat16": 2 * total}
        _flip(b, prev, "v1-again")                      # rollback()
        assert b.stats()["param_bytes"] == _split(m, params)
        assert _streams(b) == first
    finally:
        b.close()
        fresh2.close()


def test_model_swapper_rollback_through_the_batcher(model_and_params):
    from analytics_zoo_tpu.serving.hotswap import ModelSwapper

    m, params = model_and_params
    params2 = jax.tree_util.tree_map(lambda p: p * 1.5 + 0.01, params)
    b = _bf16_batcher(m, params)
    try:
        first = _streams(b)
        swapper = ModelSwapper(b)
        swapper.swap(jax.device_get(params2), {"version": "v2"})
        _await_swaps(b, 1)
        assert _streams(b) != first
        swapper.rollback()
        _await_swaps(b, 2)
        assert _streams(b) == first
    finally:
        b.close()


# --------------------------------------------------------------------- (f)

def test_a_policy_changed_after_construction_changes_no_stream(
        model_and_params):
    m, params = model_and_params
    with precision_policy(compute_dtype="bfloat16"):
        inside = _batcher(m, params)
        try:
            want = _streams(inside)     # built, traced and run under bf16
        finally:
            inside.close()
    b = _bf16_batcher(m, params)        # built under bf16 ...
    try:
        assert compute_dtype() == F32   # ... and traced under f32, later
        assert b.cfg.dtype == BF16
        assert _streams(b) == want
        with precision_policy(compute_dtype="float32"):
            # a bucket this batcher has not traced yet, under a third policy
            long_prompt = np.arange(1, 20, dtype=np.int32)
            got = b.generate(long_prompt, max_new_tokens=6)
        assert 32 in b.prefill_buckets
        b.check_decode_stability("raise")
    finally:
        b.close()
    with precision_policy(compute_dtype="bfloat16"):
        ref = _batcher(m, params)
        try:
            assert ref.generate(long_prompt, max_new_tokens=6) == got
        finally:
            ref.close()


def test_a_batcher_built_without_a_policy_stays_f32_under_a_later_one(
        model_and_params):
    m, params = model_and_params
    plain = _batcher(m, params)
    try:
        want = _streams(plain)
    finally:
        plain.close()
    b = _batcher(m, params)
    try:
        with precision_policy(compute_dtype="bfloat16"):
            assert _streams(b) == want
            assert b.params["logits_kernel"].dtype == F32
    finally:
        b.close()


def test_the_pin_is_this_threads_alone():
    seen = {}
    with pinned_compute_dtype("bfloat16"):
        t = threading.Thread(
            target=lambda: seen.setdefault("other", compute_dtype()))
        t.start()
        t.join()
        seen["here"] = compute_dtype()
        with pinned_compute_dtype("float16"):
            seen["nested"] = compute_dtype()
        seen["restored"] = compute_dtype()
    assert seen == {"other": F32, "here": BF16,
                    "nested": jnp.dtype("float16"), "restored": BF16}
    assert compute_dtype() == F32


# --------------------------------- the declaration against the traced forward

def _reads(fn, params, *args):
    """Per parameter leaf, what reads it at the top level of ``fn``'s trace:
    a list of ``(primitive, output dtype)``."""
    closed = jax.make_jaxpr(fn)(params, *args)
    n = len(jax.tree_util.tree_leaves(params))
    reads = {v: [] for v in closed.jaxpr.invars[:n]}
    for eqn in closed.jaxpr.eqns:
        for v in eqn.invars:
            if not isinstance(v, Literal) and v in reads:
                reads[v].append((eqn.primitive.name,
                                 eqn.outvars[0].aval.dtype))
    return [reads[v] for v in closed.jaxpr.invars[:n]]


def _declaration_matches(layer, fn, params, *args):
    """Under a bf16 policy: a leaf is declared cast at use exactly when every
    read of it in ``fn`` is a convert to bf16 (and it is read at all)."""
    with precision_policy(compute_dtype="bfloat16"):
        reads = _reads(fn, params, *args)
    flags = jax.tree_util.tree_leaves(layer.cast_at_use(params))
    paths = [jax.tree_util.keystr(p) for p, _ in
             jax.tree_util.tree_leaves_with_path(params)]
    for path, flag, read in zip(paths, flags, reads):
        only_casts = bool(read) and all(
            prim == "convert_element_type" and dt == BF16
            for prim, dt in read)
        assert only_casts == flag, (path, flag, read)


def _serving_calls(m):
    cfg, cache = m.init_kv_cache(2, page_size=4, max_seq_len=32)
    b, pps = cfg.n_slots, cfg.pages_per_slot
    i32 = lambda *shape: np.zeros(shape, np.int32)              # noqa: E731
    u32, f32 = np.zeros(b, np.uint32), np.zeros(b, np.float32)
    page = dict(page_size=cfg.page_size)
    return {
        "apply": (lambda p, x: m.apply(p, {}, x)[0], i32(1, 8)),
        "prefill": (lambda p, *a: m.prefill(p, *a, **page),
                    cache, i32(1, 8), i32(1), i32(1, pps)),
        "prefill_from": (lambda p, *a: m.prefill_from(p, *a, **page),
                         cache, i32(1, 8), i32(1), i32(1), i32(1, pps)),
        "prefill_chunk": (lambda p, *a: m.prefill_chunk(p, *a, **page),
                          cache, i32(1, 8), i32(1), i32(1), i32(1, pps + 2)),
        "decode_step": (lambda p, *a: m.decode_step(p, *a, **page),
                        cache, i32(b), i32(b), i32(b, pps), u32, u32, f32),
        "verify_step": (lambda p, *a: m.verify_step(p, *a, **page),
                        cache, i32(b, 3), i32(b), i32(b, pps), u32, u32, f32),
    }


@pytest.mark.parametrize("method", ["apply", "prefill", "prefill_from",
                                    "prefill_chunk", "decode_step",
                                    "verify_step"])
def test_transformer_declares_what_its_forward_casts(model_and_params,
                                                     method):
    m, params = model_and_params
    with precision_policy(compute_dtype="bfloat16"):
        fn, *args = _serving_calls(m)[method]
    _declaration_matches(m, fn, params, *args)


def test_moe_declares_what_its_forward_casts():
    layer = MoE(HIDDEN, n_experts=4, top_k=2)
    params, state = layer.build(jax.random.PRNGKey(1), (None, HIDDEN))
    _declaration_matches(
        layer, lambda p, x: layer.apply(p, state, x)[0], params,
        np.zeros((2, 4, HIDDEN), np.float32))


def test_cell_widths_serve_three_gigabytes_not_five_point_seven():
    # Cerebras-GPT-1.3B as gen-chat-steady runs it, shapes only
    m = TransformerLM(vocab=50257, hidden_size=2048, n_block=24, n_head=16,
                      seq_len=2048, intermediate_size=8192)
    shapes = jax.eval_shape(lambda: m.build(jax.random.PRNGKey(0))[0])
    split = _split(m, shapes)
    assert sum(int(np.prod(l.shape)) * 4 for l in
               jax.tree_util.tree_leaves(shapes)) == 5_674_598_400
    assert split == {"bfloat16": 2_622_656_512, "float32": 429_285_376}
