"""The fused paged-attention kernel against the gather + masked-dot reference
(ISSUE 27): one program a slot that walks only that slot's pages, several
pages a compute block. Lengths on every edge the walk has (an empty slot, one
token, a page edge, a compute-block edge and one past it, the full table),
page tables out of order, tables the block size does not divide, and pool
pages no live slot references filled with NaN.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from analytics_zoo_tpu.ops.kv_cache import (SCRATCH_PAGE,
                                            decode_attention_multi,
                                            paged_read)
from analytics_zoo_tpu.ops.paged_attention import (paged_attention,
                                                   pages_per_block,
                                                   query_block,
                                                   synthetic_paged_case)

pytestmark = pytest.mark.pallas

H, D = 4, 16
TOL = {jnp.float32: 1e-4, jnp.bfloat16: 2e-2}


def _edge_case(np_rng, q_len, dtype, *, page_size=8, pps=24, block_h=H):
    """Slots whose lengths sit on every edge of the walk, on pages out of
    order (``synthetic_paged_case`` draws them so). Returns the kernel's
    arguments and the mask of slots that hold a stream: slot 0 has length 0,
    slot 1 a length but an all-scratch table (what the decode step sends for
    a slot nobody occupies)."""
    bk = pages_per_block(pps, page_size, block_h, D, dtype) * page_size
    cap = pps * page_size
    edges = [0, q_len, 1, page_size, 2 * page_size + 3, bk - 1, bk, bk + 1,
             cap - page_size, cap]
    lengths = np.asarray([0 if n == 0 else min(cap, max(q_len, n))
                          for n in edges], np.int32)
    q, kp, vp, table, lengths = synthetic_paged_case(
        len(edges), pps, page_size, H, D, q_len=q_len, dtype=dtype,
        lengths=lengths, rng=np_rng)
    table = np.asarray(table).copy()
    table[1, :] = SCRATCH_PAGE
    live = np.arange(len(edges)) >= 2
    return (q, kp, vp, jnp.asarray(table), lengths), live


def _reference(q, kp, vp, table, lengths):
    return decode_attention_multi(q, paged_read(kp, table),
                                  paged_read(vp, table), lengths)


def _check(got, args, live, dtype):
    got = np.asarray(got, np.float32)
    ref = np.asarray(_reference(*args), np.float32)
    np.testing.assert_allclose(got[live], ref[live], atol=TOL[dtype], rtol=0)
    # a slot that holds nothing emits zeros (the reference attends the
    # scratch page there: both are invisible downstream)
    assert np.all(got[~live] == 0.0)


# (16, 8): the query-tiled grid the prefill-chunk widths run with
@pytest.mark.parametrize("q_len,block_q", [(1, None), (4, None), (16, 8)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("block_h", [H, 2])
def test_parity_on_every_edge_with_pages_out_of_order(np_rng, q_len, block_q,
                                                      dtype, block_h):
    args, live = _edge_case(np_rng, q_len, dtype, block_h=block_h)
    got = paged_attention(*args, page_size=8, block_h=block_h,
                          block_q=block_q, interpret=True)
    _check(got, args, live, dtype)


# tables the block does not divide (24 by 16, 6 by 4), one it does, one of a
# single block, and a page as long as a block
@pytest.mark.parametrize("page_size,pps,want", [(8, 24, 16), (16, 6, 4),
                                                (16, 16, 8), (16, 2, 2),
                                                (128, 3, 1)])
def test_parity_at_table_widths_the_block_does_not_divide(np_rng, page_size,
                                                          pps, want):
    assert pages_per_block(pps, page_size, H, D, jnp.float32) == want
    args, live = _edge_case(np_rng, 1, jnp.float32, page_size=page_size,
                            pps=pps)
    got = paged_attention(*args, page_size=page_size, interpret=True)
    _check(got, args, live, jnp.float32)


@pytest.mark.parametrize("q_len", [1, 4])
def test_pages_no_live_slot_references_are_never_read(np_rng, q_len):
    """NaN in the scratch page, in every unallocated page and in every page
    of the slot whose table is masked to scratch must not reach an output:
    the walk stops at a slot's last visible page, and what a partly fetched
    block's buffer still holds is zeroed before the PV dot."""
    args, live = _edge_case(np_rng, q_len, jnp.float32)
    q, kp, vp, table, lengths = args
    clean = np.asarray(paged_attention(*args, page_size=8, interpret=True))
    used = np.zeros(kp.shape[0], bool)
    for i in np.flatnonzero(live):
        used[np.asarray(table)[i, :-(-int(lengths[i]) // 8)]] = True
    assert not used[SCRATCH_PAGE] and used.sum() < kp.shape[0] - 1
    poison = jnp.where(used[:, None, None, None], 0.0, jnp.nan)
    got = np.asarray(paged_attention(q, kp + poison, vp + poison, table,
                                     lengths, page_size=8, interpret=True))
    assert np.all(np.isfinite(got))
    np.testing.assert_array_equal(got, clean)
    assert np.all(got[~live] == 0.0)


def test_a_row_that_sees_nothing_emits_zeros_beside_rows_that_do(np_rng):
    """A length under q_len leaves the first queries with no position to
    see (no caller sends one; the mask must still hold): they emit zeros,
    not the mean of a page, and the later queries are unaffected."""
    q, kp, vp, table, _ = synthetic_paged_case(
        2, 4, 8, H, D, q_len=4, lengths=[20, 20], rng=np_rng)
    lengths = jnp.asarray([2, 20], jnp.int32)
    got = np.asarray(paged_attention(q, kp, vp, table, lengths, page_size=8,
                                     interpret=True))
    ref = np.asarray(_reference(q, kp, vp, table, lengths))
    assert np.all(got[0, :2] == 0.0)
    np.testing.assert_allclose(got[0, 2:], ref[0, 2:], atol=1e-4, rtol=0)
    np.testing.assert_allclose(got[1], ref[1], atol=1e-4, rtol=0)


@pytest.mark.parametrize("q_len,slots,pps", [(1, 32, 128), (4, 8, 128),
                                             (2048, 1, 256)])
def test_the_grid_has_no_page_axis(q_len, slots, pps):
    """One program per (slot, head block, query tile) whatever the table's
    width: the walk over pages is a loop inside the program, bounded by the
    slot's own length."""
    h, d = 16, 128
    shapes = [jax.ShapeDtypeStruct(s, t) for s, t in (
        ((slots, q_len, h, d), jnp.bfloat16),
        ((slots * pps + 1, 16, h, d), jnp.bfloat16),
        ((slots * pps + 1, 16, h, d), jnp.bfloat16),
        ((slots, pps), jnp.int32), ((slots,), jnp.int32))]
    jaxpr = jax.make_jaxpr(lambda *a: paged_attention(
        *a, page_size=16, interpret=True))(*shapes)
    (call,) = [e for e in jaxpr.eqns if e.primitive.name == "pallas_call"]
    assert call.params["grid_mapping"].grid == (
        slots, 1, q_len // query_block(q_len, h, d, jnp.bfloat16))


def test_pages_per_block_follows_the_shapes():
    # the serving cell: 8 pages of 16 tokens, 128 columns a dot
    assert pages_per_block(128, 16, 16, 128, jnp.bfloat16) == 8
    # f32 pages are twice as large: half as many fit
    assert pages_per_block(128, 16, 16, 128, jnp.float32) == 4
    # fewer heads a program: the token cap binds, not the memory
    assert pages_per_block(128, 16, 8, 128, jnp.float32) == 8
    # never more than the table, never under one page
    assert pages_per_block(2, 16, 16, 128, jnp.bfloat16) == 2
    assert pages_per_block(8, 512, 16, 128, jnp.float32) == 1
