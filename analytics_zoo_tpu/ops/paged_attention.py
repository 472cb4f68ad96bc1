"""Fused paged-attention pallas kernel (ISSUE 14; one program a slot since
ISSUE 27).

``decode_attention`` (kv_cache.py) is a plain masked dot over a
*host-gathered contiguous view*: ``paged_read`` materializes the full
``(B, pages_per_slot * page_size, H, D)`` cache per layer per step in HBM,
so decode is bandwidth-bound on data it mostly re-reads — the gathered copy
is written once and read once, doubling cache traffic for zero FLOPs.

This kernel fuses the page-table gather INTO the attention loop. The grid is
``(slot, head-block, query-tile)`` and has no page axis: the pools stay in
HBM (``memory_space=pl.ANY``), the page table and the lengths are scalar-
prefetched into SMEM (``pltpu.PrefetchScalarGridSpec``), and each program
walks ITS OWN slot's pages in a ``fori_loop`` over *compute blocks* of
:func:`pages_per_block` pages, bounded by the last position its queries can
see. A block's pages are copied into VMEM one ``make_async_copy`` each,
straight from ``pool[table[slot, j]]``, into one of two buffers, so the next
block's pages fly while this block is folded: one QK dot as wide as the
block (128 tokens at 16-token pages), the masked online-softmax update in
f32, one PV dot. Nothing page-sized ever round-trips HBM. Supports query
length 1 (the classic decode step) AND ``q_len = k > 1`` — the speculative-
decode verify step that scores k draft tokens against the same paged cache
in one pass (:mod:`analytics_zoo_tpu.ops.speculative`) and the prefill-chunk
and prefix-suffix widths.

Block schedule: a function of the shapes alone. ``block_h`` (heads per
program) is all heads unless the caller passes another divisor. ``block_q``
(query rows per program) and the pages of a compute block: the whole
``q_len`` while its softmax scratch fits scoped VMEM (every decode and
verify step), else the largest tile that does (:func:`query_block` — the
prefill-chunk and prefix-suffix widths, where an untiled call is refused by
Mosaic); as many pages as make 128 tokens and fit beside it
(:func:`pages_per_block`). Routing: :func:`use_kernel` — ``auto`` (kernel on
TPU, reference path elsewhere: interpret-mode pallas is a correctness tool,
not a fast path), forced ``on`` (interpret on CPU — the parity gates), or
``off`` via ``ZOO_PAGED_ATTENTION``.

Semantics match :func:`~analytics_zoo_tpu.ops.kv_cache.decode_attention_multi`:
``lengths[b]`` counts VALID cache positions *including* the q_len new tokens
(already written by ``paged_write_multi``), and query ``i`` attends to
positions ``<= lengths[b] - q_len + i`` — causal within the step, full
prefix before it. Cost tracks each slot's true length, not the table
capacity: a program fetches only the pages that hold a position it can see
(the table entries past them point at scratch and are never read), so a
slot of 270 tokens is three blocks whatever ``pages_per_slot`` is, and a
slot that holds no stream (its first table entry is ``SCRATCH_PAGE``, which
a live slot's never is) fetches nothing and emits zeros. Measured on the
v5e at the serving cell's shape (32 slots, 16 heads of 128, table 128,
PERF.md section 6, PR 27): 11 us a call with every slot empty, 37 us with 4
live streams of 60-900 tokens, 414 us with 20 of 1,500 (three quarters of
the HBM roofline for the pages read).
"""

from __future__ import annotations

import functools
import os
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .backend import interpret_default
from .kv_cache import SCRATCH_PAGE

NEG_INF = -1e30

#: What the kernel sizes its query tile against: half of the 16 MiB scoped
#: VMEM limit Mosaic enforces on a v5e, the rest left to the K and V page
#: buffers (:func:`pages_per_block`) and to Mosaic's own temporaries.
_VMEM_BUDGET = 8 * 2 ** 20

#: Tokens of K and V one compute block folds: the MXU's width.
_BLOCK_TOKENS = 128


def paged_mode() -> str:
    """``ZOO_PAGED_ATTENTION``: ``auto`` (default — kernel on TPU only),
    ``on`` (force the kernel; interpret mode off-TPU — parity testing),
    ``off`` (always the gather + plain-dot reference path)."""
    mode = os.environ.get("ZOO_PAGED_ATTENTION", "auto").lower()
    if mode not in ("auto", "on", "off"):
        raise ValueError(f"ZOO_PAGED_ATTENTION must be auto/on/off, "
                         f"got {mode!r}")
    return mode


def use_kernel() -> bool:
    """Resolve routing at trace time (a jitted decode step bakes the
    answer)."""
    mode = paged_mode()
    if mode == "off":
        return False
    if mode == "on":
        return True
    return jax.default_backend() == "tpu"


def query_block(q_len: int, block_h: int, d: int, dtype) -> int:
    """Query rows per kernel program. Each (head, query) row costs f32
    running max / sum / accumulator scratch (128-lane padded), the
    double-buffered q and o tiles, and the f32 score temporaries of one
    compute block (128 columns); ``block_h * block_q`` rows must fit
    :data:`_VMEM_BUDGET`. Returns ``q_len`` when it fits whole, else its
    largest divisor that is a multiple of 8 (the sublane tile) and fits.
    Raises ``ValueError`` naming the shape when there is none — a width the
    batcher must not admit."""
    row_bytes = (4 * (128 + 128 + max(d, 128))
                 + 4 * d * np.dtype(dtype).itemsize + 3 * 4 * 128)
    cap = _VMEM_BUDGET // row_bytes // block_h
    if q_len <= cap:
        return q_len
    for bq in range(cap - cap % 8, 0, -8):
        if q_len % bq == 0:
            return bq
    raise ValueError(
        f"paged_attention: q_len={q_len} with block_h={block_h}, d={d}, "
        f"{np.dtype(dtype).name} needs a query tile of at most {cap} rows "
        f"that is a multiple of 8 and divides q_len; there is none")


def pages_per_block(pages_per_slot: int, page_size: int, block_h: int,
                    d: int, dtype) -> int:
    """Pages one compute block fetches and folds at once. Derived, not
    tuned: a power of two, at most :data:`_BLOCK_TOKENS` tokens (a block any
    longer makes a short context fetch mostly padding; this many fill the
    MXU's columns), at most the table, and small enough that the K and V
    page buffers (two of each: the next block's pages fly while this one is
    computed) and the head-major copies the dots read fit half of
    :data:`_VMEM_BUDGET`. The table need not be a multiple: the last block
    of a slot fetches only the pages it has."""
    page_bytes = page_size * block_h * d * np.dtype(dtype).itemsize
    fit = min(max(1, _BLOCK_TOKENS // page_size), pages_per_slot,
              max(1, _VMEM_BUDGET // 2 // (6 * page_bytes)))
    return 1 << (fit.bit_length() - 1)


def _paged_kernel(table_ref, lengths_ref, q_ref, k_hbm, v_hbm, o_ref,
                  k_buf, v_buf, sems, m_scr, l_scr, acc_scr, *, scale: float,
                  page_size: int, q_len: int, block_q: int, block_h: int,
                  d: int, n_block: int, group: int):
    b = pl.program_id(0)
    hb = pl.program_id(1)
    qi = pl.program_id(2)
    # a KV head's dots have a row for each query head that attends it
    # (grouped KV heads: ``group`` of them) and each query of the tile
    rows_q = group * block_q
    rows = block_h * rows_q
    bk = n_block * page_size
    pps = table_ref.shape[1]

    length = lengths_ref[b]
    # positions some query of this tile can see are [0, n_vis): query i sits
    # at absolute position length - q_len + i and sees itself and everything
    # before it. A slot that holds no stream (its table is all scratch: a
    # live slot's first page never is) sees nothing and fetches nothing.
    n_vis = jnp.where(table_ref[b, 0] == SCRATCH_PAGE, 0,
                      length - q_len + (qi + 1) * block_q)
    n_pages = jnp.clip(pl.cdiv(n_vis, page_size), 0, pps)
    n_blocks = pl.cdiv(n_pages, n_block)

    def page_copies(i, buf, p):
        """The K and the V copy of page ``p`` of block ``i`` into buffer
        ``buf``: this program's heads of pool page ``table[b, i*P + p]``."""
        page = table_ref[b, i * n_block + p]
        heads = pl.ds(hb * block_h, block_h)
        return (pltpu.make_async_copy(k_hbm.at[page, :, heads, :],
                                      k_buf.at[buf, p], sems.at[0, buf]),
                pltpu.make_async_copy(v_hbm.at[page, :, heads, :],
                                      v_buf.at[buf, p], sems.at[1, buf]))

    def for_pages(i, buf, act):
        # only the pages that hold a visible position: past them the table
        # points at scratch and the buffer keeps whatever it held
        def one(p, carry):
            for copy in page_copies(i, buf, p):
                act(copy)
            return carry
        jax.lax.fori_loop(0, jnp.minimum(n_block, n_pages - i * n_block),
                          one, None)

    m_scr[:] = jnp.full_like(m_scr, NEG_INF)
    l_scr[:] = jnp.zeros_like(l_scr)
    acc_scr[:] = jnp.zeros_like(acc_scr)

    @pl.when(n_blocks > 0)
    def _first():
        for_pages(0, 0, lambda c: c.start())

    # operands stay in storage dtype (bf16 MXU full-rate), statistics
    # accumulate in f32 — same discipline as the flash kernel
    # query i's last visible position: the whole prefix AND itself/earlier
    # drafts, never later drafts
    if group == 1:
        q = q_ref[0].transpose(1, 0, 2)             # (block_h, block_q, D)
        bound = length - q_len + qi * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_h, block_q, bk), 1)
    else:
        # laid out by the caller: row r of a KV head is query r // group
        q = q_ref[0]                                # (block_h, rows_q, D)
        bound = length - q_len + qi * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_h, rows_q, bk), 1) // group

    def block(i, carry):
        buf = i % 2

        @pl.when(i + 1 < n_blocks)
        def _next():
            for_pages(i + 1, 1 - buf, lambda c: c.start())

        for_pages(i, buf, lambda c: c.wait())
        k = k_buf[buf].reshape(bk, block_h, d)
        v = v_buf[buf].reshape(bk, block_h, d)
        # rows of pages this block did not fetch hold stale bits: their
        # scores are masked below, and 0 * NaN is NaN, so V's become zeros
        tok = i * bk + jax.lax.broadcasted_iota(jnp.int32, v.shape, 0)
        v = jnp.where(tok < n_vis, v, jnp.zeros_like(v))
        k = k.transpose(1, 0, 2)                    # (block_h, bk, D)
        v = v.transpose(1, 0, 2)
        s = jax.lax.dot_general(q, k, (((2,), (2,)), ((0,), (0,))),
                                preferred_element_type=jnp.float32) * scale
        seen = i * bk + jax.lax.broadcasted_iota(
            jnp.int32, (block_h, rows_q, bk), 2) <= bound
        s = jnp.where(seen, s, NEG_INF)
        m_prev = m_scr[:rows, 0:1].reshape(block_h, rows_q, 1)
        l_prev = l_scr[:rows, 0:1].reshape(block_h, rows_q, 1)
        m_new = jnp.maximum(m_prev, s.max(axis=2, keepdims=True))
        corr = jnp.exp(m_prev - m_new)
        # exact zeros where masked, also in a row that has seen nothing yet
        # (there s - m_new is 0, not -inf)
        p = jnp.where(seen, jnp.exp(s - m_new), 0.0)
        l_new = l_prev * corr + p.sum(axis=2, keepdims=True)
        pv = jax.lax.dot_general(p.astype(v.dtype), v,
                                 (((2,), (1,)), ((0,), (0,))),
                                 preferred_element_type=jnp.float32)
        acc_scr[:rows, :d] = (acc_scr[:rows, :d] * corr.reshape(rows, 1)
                              + pv.reshape(rows, d))
        m_scr[:rows, :] = jnp.broadcast_to(m_new.reshape(rows, 1),
                                           (rows, m_scr.shape[1]))
        l_scr[:rows, :] = jnp.broadcast_to(l_new.reshape(rows, 1),
                                           (rows, l_scr.shape[1]))
        return carry

    jax.lax.fori_loop(0, n_blocks, block, None)

    l = l_scr[:rows, 0:1]
    safe_l = jnp.where(l == 0, 1.0, l)      # rows that saw nothing emit zeros
    o = (acc_scr[:rows, :d] / safe_l).reshape(block_h, rows_q, d)
    if group == 1:
        o = o.transpose(1, 0, 2)
    o_ref[0] = o.astype(o_ref.dtype)


def paged_attention(q: jax.Array, k_pages: jax.Array, v_pages: jax.Array,
                    table: jax.Array, lengths: jax.Array, *,
                    page_size: int, block_h: Optional[int] = None,
                    block_q: Optional[int] = None,
                    interpret: Optional[bool] = None) -> jax.Array:
    """Fused page-gather attention.

    ``q``: (B, q_len, H, D); ``k_pages``/``v_pages``: (P, page_size, H_kv,
    D) — ONE layer's pool, ``H = g x H_kv`` (grouped KV heads: query head
    ``j`` attends KV head ``j // g``; ``g = 1`` is plain multi-head
    attention); ``table``: (B, pages_per_slot) int32; ``lengths``:
    (B,) int32 valid positions INCLUDING the q_len new tokens. Returns
    (B, q_len, H, D). ``block_h`` counts KV heads; one that does not divide
    them, or a ``q_len`` no query tile fits (:func:`query_block`), is a
    ``ValueError`` naming the shape; the gather + masked-dot path of
    :mod:`ops.kv_cache` is the route :func:`use_kernel` selects off TPU and
    the parity reference.

    With ``g > 1`` the ``g`` query heads of a KV head are rows of ITS dots,
    beside the tile's queries (a decode step's QK^T has ``g`` rows a KV head
    where plain heads have one), so a page's K and V are fetched and held
    once for all of them: the pools and the kernel's buffers have the KV
    heads' width, and ``q`` is handed over as ``(B, H_kv, q_len * g, D)``."""
    b, q_len, h_q, d = q.shape
    h = k_pages.shape[2]
    group = h_q // h
    if h_q != group * h:
        raise ValueError(f"paged_attention: the {h_q} heads of q{q.shape} "
                         f"are no multiple of the pool's {h}")
    pps = table.shape[1]
    if interpret is None:
        interpret = interpret_default()
    if block_h is None:
        # all heads in one program: decode's working set is small, and 8
        # heads a program ran 35-38% slower than 16 on the v5e (PERF.md
        # section 6, PR 27)
        block_h = h
    if h % block_h:
        raise ValueError(f"paged_attention: block_h={block_h} does not "
                         f"divide the {h} heads of q{q.shape}")
    if block_q is None:
        block_q = query_block(q_len, block_h * group, d, q.dtype)
    if q_len % block_q:
        raise ValueError(f"paged_attention: block_q={block_q} does not "
                         f"divide q_len of q{q.shape}")
    n_block = pages_per_block(pps, page_size, block_h, d, k_pages.dtype)
    scale = 1.0 / float(np.sqrt(d))
    rows = max(8, block_h * group * block_q)
    kern = functools.partial(_paged_kernel, scale=scale, page_size=page_size,
                             q_len=q_len, block_q=block_q, block_h=block_h,
                             d=d, n_block=n_block, group=group)
    if group == 1:
        q_spec = pl.BlockSpec((1, block_q, block_h, d),
                              lambda b, hb, qi, tbl, ln: (b, qi, hb, 0))
    else:
        q = q.reshape(b, q_len, h, group, d).transpose(0, 2, 1, 3, 4).reshape(
            b, h, q_len * group, d)
        q_spec = pl.BlockSpec((1, block_h, block_q * group, d),
                              lambda b, hb, qi, tbl, ln: (b, hb, qi, 0))
    kv_buf = pltpu.VMEM((2, n_block, page_size, block_h, d), k_pages.dtype)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        # no page axis: a program walks its own slot's pages, as many as
        # hold something its queries see
        grid=(b, h // block_h, q_len // block_q),
        in_specs=[
            q_spec,
            # THE fusion: the pools stay where they lie, and each program
            # copies in the pages its slot's row of the scalar-prefetched
            # table names — no contiguous copy ever exists
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=q_spec,
        scratch_shapes=[
            kv_buf, kv_buf,
            pltpu.SemaphoreType.DMA((2, 2)),        # (K | V, buffer)
            pltpu.VMEM((rows, 128), jnp.float32),
            pltpu.VMEM((rows, 128), jnp.float32),
            pltpu.VMEM((rows, max(d, 128)), jnp.float32),
        ],
    )
    o = pl.pallas_call(
        kern, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        # every program owns its output block and its copies end with it
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel")),
        interpret=interpret,
        name="zoo_paged_attention",
    )(jnp.asarray(table, jnp.int32), jnp.asarray(lengths, jnp.int32),
      q, k_pages, v_pages)
    if group == 1:
        return o
    return o.reshape(b, h, q_len, group, d).transpose(0, 2, 1, 3, 4).reshape(
        b, q_len, h_q, d)


def synthetic_paged_case(n_slots: int, pages_per_slot: int, page_size: int,
                         h: int, d: int, *, q_len: int = 1,
                         dtype=np.float32, lengths=None, rng=None):
    """Random ``(q, k_pages, v_pages, table, lengths)`` laid out exactly
    like the serving cache — page 0 scratch, each slot's valid prefix on
    pages drawn in random order (as a pool that has served and freed
    streams hands them out: neighbours in a table are not neighbours in
    the pool), unallocated entries scratch. The ONE
    fixture builder shared by ``chip_smoke.py``'s parity phase and the
    kernel tests, so neither can drift from the real
    :class:`~analytics_zoo_tpu.ops.kv_cache.PagePool` layout.

    ``lengths`` (optional, (n_slots,) int): valid positions per slot
    INCLUDING the q_len newest tokens; defaults to a half-full ladder
    (the steady serving regime). Rows at 0 get all-scratch tables
    (masked/inactive slots)."""
    rng = rng if rng is not None else np.random.default_rng(0)
    n_pages = n_slots * pages_per_slot + 1
    q = jnp.asarray(rng.normal(size=(n_slots, q_len, h, d)), dtype)
    k_pages = jnp.asarray(rng.normal(size=(n_pages, page_size, h, d)), dtype)
    v_pages = jnp.asarray(rng.normal(size=(n_pages, page_size, h, d)), dtype)
    max_len = pages_per_slot * page_size
    if lengths is None:
        lengths = np.maximum(q_len, (np.arange(n_slots) + 1)
                             * max_len // (2 * n_slots)).astype(np.int32)
    lengths = np.asarray(lengths, np.int32)
    table = np.zeros((n_slots, pages_per_slot), np.int32)
    free = rng.permutation(np.arange(1, n_pages))
    nxt = 0
    for i in range(n_slots):
        for j in range(-(-int(lengths[i]) // page_size)):
            table[i, j] = free[nxt]
            nxt += 1
    return q, k_pages, v_pages, jnp.asarray(table), jnp.asarray(lengths)


__all__ = ["paged_attention", "paged_mode",
           "pages_per_block", "query_block", "synthetic_paged_case",
           "use_kernel"]
