"""Blockwise (flash) attention — the pallas TPU kernel (SURVEY.md §7 hard parts:
"ring attention / SP pallas kernel").

Forward: tiled online-softmax. Grid (B·H, T_q/block_q, T_kv/block_k); each
program folds one K/V tile into fp32 VMEM accumulators (m, l, acc), writing the
normalized output on the last K tile. Q·Kᵀ and P·V hit the MXU per tile; scores
never materialize in HBM — peak memory O(block_q · block_k) per core instead of
O(T²). What a grid step does follows from the tile's position and the static
``causal`` alone (:func:`forward_tile_plan` counts the kinds): in a causal call
a K tile wholly in the future is skipped (an empty grid step; its K/V copy
hides under the working tiles) and every other tile builds the mask, as the
backward kernels do; a non-causal call (a ring's off-diagonal step) builds
none. Masking only the tiles the diagonal crosses, and index maps that fetch
nothing for a skipped tile, were measured on a v5e and refused (PR 42,
``PERF.md``: the tile is bound by its stores, the copies were hidden, neither
was faster). The first tile of a q tile sets the statistics, so nothing
resets them. ``m`` and ``l`` stay (block_q, 128) with the row's value in
every lane, the exponent is ``exp2`` with the softmax scale folded into its
one multiply, and the saved ``lse`` is converted back to the NATURAL log on
the last tile: the backward kernels and the ring merges read it.

Backward: tiled pallas kernels recomputing probabilities from the saved
log-sum-exp (standard flash recompute: P = exp(S − lse)). Two passes:
``_bwd_dq_kernel`` (grid over Q tiles, folding K/V tiles) and
``_bwd_dkv_kernel`` (grid over K tiles, folding Q tiles). Like the forward,
scores/probabilities live only in VMEM — peak HBM stays O(T·D), not O(T²),
for training as well as inference.

Layout: (B, T, H, D) like the other attention strategies. A sequence the tiles
do not divide is an error that names the shape (:func:`tiles_ok` lets a router
ask first); ``ops.attention.full_attention`` is the reference the parity tests
compare against. Off TPU the kernels run in the Pallas interpreter
(:func:`ops.backend.interpret_default`).
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .backend import interpret_default

NEG_INF = -1e30
_LOG2E = math.log2(math.e)
_LN2 = math.log(2.0)

#: Flash-aware rematerialization policy: under ``jax.checkpoint`` save ONLY the
#: flash kernel's output + log-sum-exp (tagged in ``_flash_attention_fwd_res``),
#: so the backward pass reuses the kernel's saved statistics — the attention
#: recompute (the expensive O(T^2) part of plain remat) disappears while the
#: cheap projections/layernorms/MLP still recompute for the memory win.
FLASH_REMAT_POLICY = jax.checkpoint_policies.save_only_these_names(
    "flash_out", "flash_lse")


def _reaches_diagonal(q_lo, k_lo, block_q):
    """A K tile some row of the q tile attends to; the others are skipped."""
    return k_lo <= q_lo + block_q - 1


def forward_tile_plan(t_q: int, t_k: int, block_q: int, block_k: int,
                      causal: bool) -> tuple:
    """``(skipped, working)`` (q tile, K tile) pairs of one head in the
    forward kernel, by the predicate the kernel itself branches on: a skipped
    pair runs an empty grid step, a working pair folds its scores (behind the
    causal mask when ``causal``)."""
    nq, nk = t_q // block_q, t_k // block_k
    working = nq * nk if not causal else sum(
        _reaches_diagonal(qi * block_q, kb * block_k, block_q)
        for qi in range(nq) for kb in range(nk))
    return nq * nk - working, working


def _lanes(x, n: int):
    """``x`` holds one value a row in each of its lanes; the same in ``n``
    lanes, by reusing registers where ``n`` is a multiple (no broadcast)."""
    w = x.shape[1]
    if n == w:
        return x
    if n < w:
        return x[:, :n]
    if w > 1 and n % w == 0:
        return jnp.tile(x, (1, n // w))
    return jnp.broadcast_to(x[:, :1], (x.shape[0], n))


def _lane_sums(p, w: int):
    """Row sums of ``p`` left spread over ``w`` lanes (lane j holds the sum
    of columns j, j + w, ...): elementwise adds of whole registers, the one
    cross-lane reduction is made once, on the last tile."""
    if w == 1:
        return jnp.sum(p, axis=1, keepdims=True)
    ps = p[:, :w]
    for c in range(w, p.shape[1], w):
        ps = ps + p[:, c:c + w]
    return ps


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref,
                m_scr, l_scr, acc_scr, *, scale: float, causal: bool,
                block_q: int, block_k: int):
    qi = pl.program_id(1)
    kb = pl.program_id(2)
    nk = pl.num_programs(2)
    w = m_scr.shape[1]
    # the exponent is taken in base 2 with the softmax scale folded into its
    # one multiply: exp(scale * s - m) = exp2(s * c2 - m2), m2 = m * log2(e)
    c2 = scale * _LOG2E

    def fold(first: bool):
        # operands STAY in their storage dtype: a bf16 x bf16 -> f32 dot runs
        # the MXU at full rate, an f32 upcast would halve it; scores,
        # statistics and accumulator are f32
        v = v_ref[0]
        s = jax.lax.dot_general(q_ref[0], k_ref[0], (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if causal:
            q_pos = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            k_pos = kb * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(q_pos >= k_pos, s, NEG_INF)
        # m and l live as (block_q, w) with the row's value in every lane
        # (l: spread over the lanes), so nothing below slices a one-lane
        # column out of them or broadcasts one back
        m_new = jnp.max(s, axis=1, keepdims=True) * c2
        if first:
            m_new = jnp.broadcast_to(m_new, (block_q, w))
        else:
            m_prev = m_scr[...]
            m_new = jnp.maximum(m_prev, m_new)
            alpha = jnp.exp2(m_prev - m_new)
        p = jnp.exp2(s * c2 - _lanes(m_new, block_k))   # (block_q, block_k)
        pv = jax.lax.dot_general(p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        if first:       # the first tile of a q tile sets the state: no reset
            l_scr[...] = _lane_sums(p, w)
            acc_scr[...] = pv
        else:
            l_scr[...] = alpha * l_scr[...] + _lane_sums(p, w)
            acc_scr[...] = acc_scr[...] * _lanes(alpha, pv.shape[1]) + pv
        m_scr[...] = m_new

    # one online softmax, two tile bodies: the first tile of a q tile (never
    # skipped: column 0 is in every row's past) and the working tiles after it
    first = kb == 0
    later = jnp.logical_not(first)
    if causal:
        later = jnp.logical_and(
            later, _reaches_diagonal(qi * block_q, kb * block_k, block_q))
    pl.when(first)(functools.partial(fold, True))
    pl.when(later)(functools.partial(fold, False))

    @pl.when(kb == nk - 1)
    def _finish():
        l = jnp.sum(l_scr[...], axis=1, keepdims=True)      # (block_q, 1)
        safe_l = jnp.where(l == 0, 1.0, l)
        o_ref[0] = (acc_scr[...] / safe_l).astype(o_ref.dtype)
        # the saved lse is the natural log whatever base the tiles used;
        # lse block spans the FULL row (TPU tiling: last-two block dims must
        # divide (8,128) or equal the array dims); each q-tile writes its
        # slice, moved from rows to lanes by one transpose
        lse = (m_scr[...] + jnp.log2(jnp.broadcast_to(safe_l, m_scr.shape))
               ) * _LN2
        lse_ref[0, :, pl.ds(qi * block_q, block_q)] = lse.T[0:1, :]


@functools.partial(jax.jit, static_argnames=("causal", "block_q", "block_k",
                                             "interpret"))
def _flash_fwd(q, k, v, *, causal: bool, block_q: int, block_k: int,
               interpret: bool):
    """``(out, lse)``: (B, T_q, H, D) in the storage dtype and the natural-log
    (B, H, T_q) f32. Jitted so that a model's blocks, which call it with the
    same shapes, trace and lower the kernel once between them and not once
    each (seconds of a 24-block prefill's set-up: PR 42, ``PERF.md``)."""
    b, t_q, h, d = q.shape
    t_k = k.shape[1]
    scale = 1.0 / math.sqrt(d)
    # (B, T, H, D) -> (B*H, T, D)
    qh = q.transpose(0, 2, 1, 3).reshape(b * h, t_q, d)
    kh = k.transpose(0, 2, 1, 3).reshape(b * h, t_k, d)
    vh = v.transpose(0, 2, 1, 3).reshape(b * h, t_k, d)
    nq = t_q // block_q
    nk = t_k // block_k
    # statistics in whole registers where a K tile is whole registers wide
    stat_lanes = 128 if block_k % 128 == 0 else 1

    kernel = functools.partial(_fwd_kernel, scale=scale, causal=causal,
                               block_q=block_q, block_k=block_k)
    out, lse = pl.pallas_call(
        kernel,
        grid=(b * h, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda bh, qi, kb: (bh, qi, 0)),
            pl.BlockSpec((1, block_k, d), lambda bh, qi, kb: (bh, kb, 0)),
            pl.BlockSpec((1, block_k, d), lambda bh, qi, kb: (bh, kb, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda bh, qi, kb: (bh, qi, 0)),
            pl.BlockSpec((1, 1, t_q), lambda bh, qi, kb: (bh, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * h, t_q, d), q.dtype),
            jax.ShapeDtypeStruct((b * h, 1, t_q), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, stat_lanes), jnp.float32),
            pltpu.VMEM((block_q, stat_lanes), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        # qi is NOT parallel: the lse out-block (one full row per bh) is
        # revisited by every qi step; parallel execution over qi would give
        # each core its own copy of the row and clobber other cores' slices
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        interpret=interpret,
        name="zoo_flash_fwd",
    )(qh, kh, vh)
    out4 = out.reshape(b, h, t_q, d).transpose(0, 2, 1, 3)
    lse4 = lse.reshape(b, h, t_q)
    return out4, lse4.astype(jnp.float32)


def _bwd_p_ds(q_ref, k_ref, v_ref, g_ref, lse_ref, delta_ref, qi, kb, *,
              scale: float, causal: bool, block_q: int, block_k: int):
    """Shared backward-tile recompute: (p, ds, q, k, g) for tile (qi, kb).

    P = exp(S − lse) from the saved log-sum-exp; dS = P ∘ (dP − δ) · scale —
    identical math in the dq and dk/dv kernels so the two passes can never
    desynchronize.
    """
    # storage dtype in, f32 accumulate out (bf16 MXU full-rate — see forward)
    q = q_ref[0]                                    # (block_q, D)
    k = k_ref[0]                                    # (block_k, D)
    v = v_ref[0]
    g = g_ref[0]                                    # (block_q, D)
    lse = lse_ref[0, 0, pl.ds(qi * block_q, block_q)]    # (block_q,)
    delta = delta_ref[0, 0, pl.ds(qi * block_q, block_q)]
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    if causal:
        q_pos = qi * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        k_pos = kb * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        s = jnp.where(q_pos >= k_pos, s, NEG_INF)
    p = jnp.exp(s - lse[:, None])                   # (block_q, block_k)
    dp = jax.lax.dot_general(g, v, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    ds = p * (dp - delta[:, None]) * scale
    return p, ds, q, k, g


def _bwd_dq_kernel(q_ref, k_ref, v_ref, g_ref, lse_ref, delta_ref, dq_ref,
                   dq_scr, *, scale: float, causal: bool,
                   block_q: int, block_k: int):
    qi = pl.program_id(1)
    kb = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(kb == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    def body():
        _, ds, _, k, _ = _bwd_p_ds(
            q_ref, k_ref, v_ref, g_ref, lse_ref, delta_ref, qi, kb,
            scale=scale, causal=causal, block_q=block_q, block_k=block_k)
        dq_scr[:] += jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    if causal:
        @pl.when(kb * block_k <= qi * block_q + block_q - 1)
        def _():
            body()
    else:
        body()

    @pl.when(kb == nk - 1)
    def _finish():
        dq_ref[0] = dq_scr[:].astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, g_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, dk_scr, dv_scr, *, scale: float,
                    causal: bool, block_q: int, block_k: int):
    kb = pl.program_id(1)
    qi = pl.program_id(2)
    nq = pl.num_programs(2)

    @pl.when(qi == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    def body():
        p, ds, q, _, g = _bwd_p_ds(
            q_ref, k_ref, v_ref, g_ref, lse_ref, delta_ref, qi, kb,
            scale=scale, causal=causal, block_q=block_q, block_k=block_k)
        # dV += Pᵀ · dO
        dv_scr[:] += jax.lax.dot_general(
            p.astype(g.dtype), g, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        # dK += dSᵀ · Q
        dk_scr[:] += jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    if causal:
        # skip Q tiles strictly before this K tile (their P block is all-masked)
        @pl.when(qi * block_q + block_q - 1 >= kb * block_k)
        def _():
            body()
    else:
        body()

    @pl.when(qi == nq - 1)
    def _finish():
        dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "block_q", "block_k",
                                             "interpret"))
def _flash_bwd(q, k, v, o, lse, g, *, causal: bool, block_q: int,
               block_k: int, interpret: bool):
    """Tiled flash backward: dq/dk/dv pallas kernels from the saved lse.
    Jitted as :func:`_flash_fwd` is, and for its reason: a 24-block model's
    training step held 48 copies of the two kernels and started 10 s later
    on a warm compile cache (PR 44, ``PERF.md``)."""
    b, t_q, h, d = q.shape
    t_k = k.shape[1]
    scale = 1.0 / math.sqrt(d)
    qh = q.transpose(0, 2, 1, 3).reshape(b * h, t_q, d)
    kh = k.transpose(0, 2, 1, 3).reshape(b * h, t_k, d)
    vh = v.transpose(0, 2, 1, 3).reshape(b * h, t_k, d)
    gh = g.transpose(0, 2, 1, 3).reshape(b * h, t_q, d)
    # delta_i = rowsum(dO_i ∘ O_i), computed once in plain XLA (O(T·D))
    delta = jnp.sum(g.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    delta = delta.transpose(0, 2, 1).reshape(b * h, 1, t_q)
    lse3 = lse.reshape(b * h, 1, t_q)
    nq = t_q // block_q
    nk = t_k // block_k

    row_spec = pl.BlockSpec((1, 1, t_q), lambda bh, i, j: (bh, 0, 0))
    # unlike the forward (whose lse OUT row is revisited by every qi), lse and
    # delta are read-only here and each middle-dim index owns a disjoint out
    # block, so only the innermost fold dim must stay sequential
    dims = None if interpret else pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"))

    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k),
        grid=(b * h, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda bh, qi, kb: (bh, qi, 0)),
            pl.BlockSpec((1, block_k, d), lambda bh, qi, kb: (bh, kb, 0)),
            pl.BlockSpec((1, block_k, d), lambda bh, qi, kb: (bh, kb, 0)),
            pl.BlockSpec((1, block_q, d), lambda bh, qi, kb: (bh, qi, 0)),
            row_spec, row_spec,
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda bh, qi, kb: (bh, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, t_q, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        compiler_params=dims,
        interpret=interpret,
        name="zoo_flash_bwd_dq",
    )(qh, kh, vh, gh, lse3, delta)

    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k),
        grid=(b * h, nk, nq),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda bh, kb, qi: (bh, qi, 0)),
            pl.BlockSpec((1, block_k, d), lambda bh, kb, qi: (bh, kb, 0)),
            pl.BlockSpec((1, block_k, d), lambda bh, kb, qi: (bh, kb, 0)),
            pl.BlockSpec((1, block_q, d), lambda bh, kb, qi: (bh, qi, 0)),
            row_spec, row_spec,
        ],
        out_specs=[
            pl.BlockSpec((1, block_k, d), lambda bh, kb, qi: (bh, kb, 0)),
            pl.BlockSpec((1, block_k, d), lambda bh, kb, qi: (bh, kb, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * h, t_k, d), k.dtype),
            jax.ShapeDtypeStruct((b * h, t_k, d), v.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((block_k, d), jnp.float32),
                        pltpu.VMEM((block_k, d), jnp.float32)],
        compiler_params=dims,
        interpret=interpret,
        name="zoo_flash_bwd_dkv",
    )(qh, kh, vh, gh, lse3, delta)

    to4 = lambda a, t: a.reshape(b, h, t, d).transpose(0, 2, 1, 3)
    return (to4(dq, t_q).astype(q.dtype), to4(dk, t_k).astype(k.dtype),
            to4(dv, t_k).astype(v.dtype))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def flash_attention(q, k, v, causal: bool = False,
                    block_q: Optional[int] = None,
                    block_k: Optional[int] = None,
                    interpret: Optional[bool] = None):
    """Blockwise attention, (B, T, H, D) → (B, T, H, D).

    ``block_q``/``block_k`` default to :func:`default_blocks` (adaptive:
    largest power-of-two ≤512 dividing the sequence, at EVERY call site:
    direct, sharded, ring and Ulysses). Raises ``ValueError`` naming the
    shape when the sequence does not tile evenly (the caller may pad, or
    ask :func:`tiles_ok` and route to full attention instead).
    """
    out, _ = _flash_attention_fwd_res(q, k, v, causal, block_q, block_k,
                                      interpret)
    return out


def default_blocks(t_q: Optional[int] = None,
                   t_k: Optional[int] = None) -> tuple:
    """Flash tile sizes, a function of the sequence lengths alone: the
    largest power-of-two tile ≤512 that divides the length, 128 when the
    length is unknown or nothing larger divides it. A caller that wants
    other tiles passes ``block_q`` / ``block_k``."""

    def auto(t: Optional[int]) -> int:
        if t is None:
            return 128
        b = 512
        while b > 128 and t % b:
            b //= 2
        return b

    return auto(t_q), auto(t_k)


def resolve_blocks(t_q: int, t_k: int, block_q: Optional[int] = None,
                   block_k: Optional[int] = None) -> tuple:
    """Tile sizes a call at these sequence lengths runs with: the explicit
    arguments, else :func:`default_blocks`, clamped to the sequence."""
    if block_q is None or block_k is None:
        auto_q, auto_k = default_blocks(t_q, t_k)
        block_q = auto_q if block_q is None else block_q
        block_k = auto_k if block_k is None else block_k
    return min(block_q, t_q), min(block_k, t_k)


def tiles_ok(t_q: int, t_k: int, block_q: Optional[int] = None,
             block_k: Optional[int] = None,
             interpret: Optional[bool] = None) -> bool:
    """Whether the kernel can run at these sequence lengths — what a router
    asks before selecting it. The resolved tiles must divide them, and a
    compiled kernel needs a q tile that is a multiple of 128: each q tile
    stores its log-sum-exp at a lane offset Mosaic must prove aligned
    ("cannot statically prove that index in dimension 2 is a multiple of
    128" at T=64 on a v5e), so sequences under 128 stay on full attention."""
    block_q, block_k = resolve_blocks(t_q, t_k, block_q, block_k)
    interpret = interpret_default() if interpret is None else interpret
    return (t_q % block_q == 0 and t_k % block_k == 0
            and (interpret or block_q % 128 == 0))


def _resolve(q, k, block_q, block_k, interpret):
    """Resolve tile sizes and interpret mode — shared by the forward and the
    VJP backward so both always use identical tiling."""
    t_q, t_k = q.shape[1], k.shape[1]
    block_q, block_k = resolve_blocks(t_q, t_k, block_q, block_k)
    interpret = interpret_default() if interpret is None else interpret
    if not tiles_ok(t_q, t_k, block_q, block_k, interpret):
        raise ValueError(
            f"flash_attention: q{q.shape} k{k.shape} cannot run with blocks "
            f"({block_q}, {block_k}): they must divide (T_q={t_q}, "
            f"T_k={t_k}), and block_q must be a multiple of 128 when the "
            f"kernel is compiled; pad the sequence or use full_attention")
    return block_q, block_k, interpret


def _flash_attention_fwd_res(q, k, v, causal, block_q, block_k, interpret):
    block_q, block_k, interpret = _resolve(q, k, block_q, block_k, interpret)
    out, lse = _flash_fwd(q, k, v, causal=causal, block_q=block_q,
                          block_k=block_k, interpret=interpret)
    # checkpoint_name is identity outside jax.checkpoint; under a
    # save_only_these_names policy (FLASH_REMAT_POLICY) these tags make the
    # kernel's output + log-sum-exp the SAVED residuals, so a rematerialized
    # backward reuses them instead of re-running the O(T^2) flash forward —
    # only the cheap projections/elementwise around it recompute.
    out = checkpoint_name(out, "flash_out")
    lse = checkpoint_name(lse, "flash_lse")
    return out, (q, k, v, out, lse)


def _flash_vjp_fwd(q, k, v, causal, block_q, block_k, interpret):
    return _flash_attention_fwd_res(q, k, v, causal, block_q, block_k,
                                    interpret)


def _flash_vjp_bwd(causal, block_q, block_k, interpret, res, g):
    q, k, v, out, lse = res
    block_q, block_k, interpret = _resolve(q, k, block_q, block_k, interpret)
    return _flash_bwd(q, k, v, out, lse, g, causal=causal,
                      block_q=block_q, block_k=block_k, interpret=interpret)


flash_attention.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)
