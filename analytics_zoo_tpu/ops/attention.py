"""Attention ops with selectable parallelism strategy.

The reference's attention is fixed-length single-node (TransformerLayer.scala,
BERT.scala — SURVEY.md §5.7: no ring attention, no sequence parallelism). Here
long-context is first-class: interchangeable strategies over the global mesh:

* ``full``    — plain batched attention; GSPMD shards it over dp/tp axes.
* ``ring``    — ring attention over the ``sp`` axis: K/V blocks rotate around the
                ring via ``lax.ppermute``. On TPU each ring step runs the pallas
                flash kernel (O(block) score memory); off TPU a plain-jnp
                online-softmax body runs. K/V transfers ride ICI neighbor links.
* ``zigzag``  — causal ring over the zigzag layout (device d holds the chunk
                pair (d, 2n−1−d)): the causal schedule is load-balanced — every
                device does ~2 half-blocks per step instead of the plain ring's
                tail-heavy triangle. Causal + TPU only; else falls to ``ring``.
* ``ulysses`` — DeepSpeed-Ulysses-style all-to-all: resharding from sequence-split
                to head-split, local (flash on TPU) attention over the full
                sequence, then the inverse all-to-all.

All strategies compute bitwise-comparable results (up to float reassociation) and
are differentiable (pure jnp/lax — JAX autodiff through collectives).

Shapes: q, k, v are (B, T, H, D) per-device LOCAL blocks inside shard_map, or
global arrays for ``full``.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.lax import axis_size
from jax.sharding import PartitionSpec as P

from ..common import telemetry as _tm
from .backend import interpret_default

NEG_INF = -1e30


def full_attention(q, k, v, *, causal: bool = False, q_offset=0, k_offset=0):
    """Reference attention: softmax(q k^T / sqrt(d)) v. (B, T, H, D) layout."""
    d = q.shape[-1]
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(
        jnp.asarray(d, q.dtype))
    if causal:
        q_pos = q_offset + jnp.arange(q.shape[1])
        k_pos = k_offset + jnp.arange(k.shape[1])
        mask = q_pos[:, None] >= k_pos[None, :]
        scores = jnp.where(mask[None, None], scores, NEG_INF)
    probs = jax.nn.softmax(scores.astype(jnp.float32), axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def _ring_body(q, k_blk, v_blk, o, m, l, *, scale, causal, q_pos, k_pos):
    """One ring step: fold k_blk/v_blk into the online-softmax accumulator."""
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k_blk).astype(jnp.float32) * scale
    if causal:
        mask = q_pos[:, None] >= k_pos[None, :]
        scores = jnp.where(mask[None, None], scores, NEG_INF)
    blk_max = jnp.max(scores, axis=-1)                       # (B,H,Tq)
    m_new = jnp.maximum(m, blk_max)
    corr = jnp.exp(m - m_new)
    p = jnp.exp(scores - m_new[..., None])                   # (B,H,Tq,Tk)
    l_new = l * corr + jnp.sum(p, axis=-1)
    pv = jnp.einsum("bhqk,bkhd->bqhd", p.astype(v_blk.dtype), v_blk)
    o_new = o * corr.transpose(0, 2, 1)[..., None] + pv.astype(jnp.float32)
    return o_new, m_new, l_new


def _ring_attention_jnp(q, k, v, *, axis_name: str = "sp", causal: bool = False):
    """Plain-jnp ring body (O(T_local²) score blocks) — the off-TPU route,
    and the one for local sequences the flash tiles do not divide."""
    n = axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    b, t_q, h, d = q.shape
    scale = 1.0 / jnp.sqrt(jnp.asarray(d, jnp.float32))
    q32 = q
    o = jnp.zeros((b, t_q, h, d), jnp.float32)
    m = jnp.full((b, h, t_q), NEG_INF, jnp.float32)
    l = jnp.zeros((b, h, t_q), jnp.float32)
    q_pos = idx * t_q + jnp.arange(t_q)
    perm = [(j, (j + 1) % n) for j in range(n)]

    def step(carry, i):
        o, m, l, k_blk, v_blk = carry
        src = (idx - i) % n                     # which global block we now hold
        k_pos = src * k_blk.shape[1] + jnp.arange(k_blk.shape[1])
        o, m, l = _ring_body(q32, k_blk, v_blk, o, m, l, scale=scale,
                             causal=causal, q_pos=q_pos, k_pos=k_pos)
        k_blk = jax.lax.ppermute(k_blk, axis_name, perm)
        v_blk = jax.lax.ppermute(v_blk, axis_name, perm)
        return (o, m, l, k_blk, v_blk), None

    (o, m, l, _, _), _ = jax.lax.scan(step, (o, m, l, k, v), jnp.arange(n))
    return (o / l.transpose(0, 2, 1)[..., None]).astype(q.dtype)


# --------------------------------------------------------------- flash-in-ring
def _block_cases(src, idx, causal, diag_fn, past_fn, future_fn, operand):
    """Dispatch one ring step on the visiting block's causal relation to the
    local Q block. ``src`` is traced (depends on axis_index), so the three
    cases are runtime ``lax.cond`` branches: src == idx → diagonal (causal
    mask), src < idx → strictly past (dense), src > idx → strictly future
    (fully masked, skipped)."""
    if not causal:
        return past_fn(operand)
    return jax.lax.cond(
        src == idx, diag_fn,
        lambda op: jax.lax.cond(src < idx, past_fn, future_fn, op),
        operand)


def _merge_blocks(o, lse, o_blk, lse_blk):
    """Fold one normalized block result into the running (o, lse) accumulator:
    U = o·e^lse is the unnormalized numerator, so the merged output is a
    stable convex combination weighted by e^(lse−lse_new). NEG_INF is finite,
    so empty blocks merge to weight 0 without NaNs."""
    m = jnp.maximum(lse, lse_blk)
    w_old = jnp.exp(lse - m)                        # (B, H, Tq)
    w_new = jnp.exp(lse_blk - m)
    lse_new = m + jnp.log(w_old + w_new)
    tr = lambda w: w.transpose(0, 2, 1)[..., None]  # -> (B, Tq, H, 1)
    denom = tr(w_old + w_new)
    o_new = (o * tr(w_old) + o_blk.astype(jnp.float32) * tr(w_new)) / denom
    return o_new, lse_new


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _ring_flash(q, k, v, axis_name, causal, block_q, block_k):
    """Ring attention whose per-step body is the pallas flash kernel —
    O(block_q·block_k) score memory inside each ring step instead of the jnp
    body's O(T_local²): flash within ring is the composition that makes
    long context real."""
    out, _ = _ring_flash_fwd_res(q, k, v, axis_name, causal, block_q, block_k)
    return out


def _ring_flash_fwd_res(q, k, v, axis_name, causal, block_q, block_k):
    from .flash_attention import _flash_fwd

    interpret = interpret_default()
    n = axis_size(axis_name)
    # non-causal rings never branch on block position — every visiting block
    # is dense. Emitting axis_index anyway leaves an (unused) PartitionId in
    # the shard_map body, which XLA's SPMD partitioner rejects outright
    # ("meaning is ambiguous"); only materialize it when causal needs it.
    idx = jax.lax.axis_index(axis_name) if causal else None
    b, t_q, h, d = q.shape
    o0 = jnp.zeros((b, t_q, h, d), jnp.float32)
    lse0 = jnp.full((b, h, t_q), NEG_INF, jnp.float32)
    perm = [(j, (j + 1) % n) for j in range(n)]

    def flash(causal_flag):
        def run(op):
            q_, k_, v_ = op
            return _flash_fwd(q_, k_, v_, causal=causal_flag, block_q=block_q,
                              block_k=block_k, interpret=interpret)
        return run

    def future(op):
        return (jnp.zeros((b, t_q, h, d), q.dtype),
                jnp.full((b, h, t_q), NEG_INF, jnp.float32))

    def step(carry, i):
        o, lse, k_blk, v_blk = carry
        src = (idx - i) % n if causal else None
        o_blk, lse_blk = _block_cases(src, idx, causal, flash(True),
                                      flash(False), future, (q, k_blk, v_blk))
        o, lse = _merge_blocks(o, lse, o_blk, lse_blk)
        k_blk = jax.lax.ppermute(k_blk, axis_name, perm)
        v_blk = jax.lax.ppermute(v_blk, axis_name, perm)
        return (o, lse, k_blk, v_blk), None

    (o, lse, _, _), _ = jax.lax.scan(step, (o0, lse0, k, v), jnp.arange(n))
    out = o.astype(q.dtype)
    return out, (q, k, v, out, lse)


def _ring_flash_vjp_fwd(q, k, v, axis_name, causal, block_q, block_k):
    return _ring_flash_fwd_res(q, k, v, axis_name, causal, block_q, block_k)


def _ring_flash_vjp_bwd(axis_name, causal, block_q, block_k, res, g):
    """Second ring pass: (k, v, dk, dv) rotate together; each device folds the
    visiting block's gradients through the tiled flash backward kernels using
    the saved GLOBAL lse (P = exp(S − lse) is exact for every block), so the
    backward is O(block) memory too. After n rotations every bundle is back on
    its home device with dk/dv fully accumulated; dq accumulates locally."""
    from .flash_attention import _flash_bwd

    q, k, v, out, lse = res
    interpret = interpret_default()
    n = axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name) if causal else None  # see fwd note
    perm = [(j, (j + 1) % n) for j in range(n)]

    def bwd(causal_flag):
        def run(op):
            k_blk, v_blk = op
            return _flash_bwd(q, k_blk, v_blk, out, lse, g, causal=causal_flag,
                              block_q=block_q, block_k=block_k,
                              interpret=interpret)
        return run

    def future(op):
        k_blk, v_blk = op
        return (jnp.zeros_like(q), jnp.zeros_like(k_blk),
                jnp.zeros_like(v_blk))

    def step(carry, i):
        dq, k_blk, v_blk, dk, dv = carry
        src = (idx - i) % n if causal else None
        dq_c, dk_c, dv_c = _block_cases(src, idx, causal, bwd(True),
                                        bwd(False), future, (k_blk, v_blk))
        dq = dq + dq_c.astype(jnp.float32)
        dk = dk + dk_c.astype(jnp.float32)
        dv = dv + dv_c.astype(jnp.float32)
        roll = lambda x: jax.lax.ppermute(x, axis_name, perm)
        return (dq, roll(k_blk), roll(v_blk), roll(dk), roll(dv)), None

    dq0 = jnp.zeros(q.shape, jnp.float32)
    dk0 = jnp.zeros(k.shape, jnp.float32)
    dv0 = jnp.zeros(v.shape, jnp.float32)
    (dq, _, _, dk, dv), _ = jax.lax.scan(
        step, (dq0, k, v, dk0, dv0), jnp.arange(n))
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


_ring_flash.defvjp(_ring_flash_vjp_fwd, _ring_flash_vjp_bwd)


# ----------------------------------------------------------- zigzag ring
def zigzag_permutation(t: int, n: int):
    """Sequence-axis permutation for load-balanced CAUSAL ring attention.

    Contiguous chunking starves the early devices: device d has only d+1
    non-future blocks of n, so the wall-clock is set by the last device while
    the first sits idle (~2× waste at large n). Zigzag gives device d the
    chunk PAIR (d, 2n−1−d) of 2n half-chunks — causal work per device becomes
    (d+1) + (2n−1−d − (n−1)) … = 2n+1 half-pairs, EQUAL for every d. Returns
    the permutation such that ``x[:, perm]`` sharded over ``n`` devices puts
    that pair on device d; invert with ``np.argsort(perm)``.
    """
    import numpy as np

    if t % (2 * n):
        raise ValueError(f"zigzag needs seq len divisible by 2*sp ({2 * n}); "
                         f"got {t}")
    c = t // (2 * n)
    order = []
    for d in range(n):
        order += [d, 2 * n - 1 - d]
    return np.concatenate([np.arange(c) + ch * c for ch in order])


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _zigzag_ring_flash(q, k, v, axis_name, block_q, block_k):
    """Causal ring attention over the zigzag layout; called INSIDE shard_map.

    The local sequence is [lo | hi] = chunks (idx, 2n−1−idx). Of the four
    (q-half × visiting-k-half) pairs, two are STATIC: q_lo×k_hi is always
    future (skipped at trace time) and q_hi×k_lo always strictly past (dense
    flash, no cond); only the two same-half pairs need runtime 3-way
    dispatch. Per-step work is therefore ~2 half-blocks on every device —
    the balanced schedule the plain causal ring lacks."""
    out, _ = _zigzag_fwd_res(q, k, v, axis_name, block_q, block_k)
    return out


def _zigzag_split(x, axis=1):
    c = x.shape[axis] // 2
    lo = jax.lax.slice_in_dim(x, 0, c, axis=axis)
    hi = jax.lax.slice_in_dim(x, c, 2 * c, axis=axis)
    return lo, hi


def _zigzag_fwd_res(q, k, v, axis_name, block_q, block_k):
    from .flash_attention import _flash_fwd

    interpret = interpret_default()
    n = axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    b, t_loc, h, d = q.shape
    c = t_loc // 2
    bq, bk = min(block_q, c), min(block_k, c)
    q_lo, q_hi = _zigzag_split(q)
    k_lo, k_hi = _zigzag_split(k)
    v_lo, v_hi = _zigzag_split(v)
    perm = [(j, (j + 1) % n) for j in range(n)]

    def fwd(causal_flag):
        def run(op):
            qh, kh, vh = op
            return _flash_fwd(qh, kh, vh, causal=causal_flag, block_q=bq,
                              block_k=bk, interpret=interpret)
        return run

    def future(op):
        return (jnp.zeros((b, c, h, d), q.dtype),
                jnp.full((b, h, c), NEG_INF, jnp.float32))

    def step(carry, i):
        o_lo, lse_lo, o_hi, lse_hi, kl, kh, vl, vh = carry
        src = (idx - i) % n
        # q_hi × k_lo: hi chunk (2n−1−idx) is ALWAYS past every lo chunk
        o_blk, lse_blk = fwd(False)((q_hi, kl, vl))
        o_hi, lse_hi = _merge_blocks(o_hi, lse_hi, o_blk, lse_blk)
        # q_lo × k_lo: past iff src < idx on lo chunk ids
        o_blk, lse_blk = _block_cases(src, idx, True, fwd(True), fwd(False),
                                      future, (q_lo, kl, vl))
        o_lo, lse_lo = _merge_blocks(o_lo, lse_lo, o_blk, lse_blk)
        # q_hi × k_hi: hi ids invert the order — past iff src > idx
        o_blk, lse_blk = _block_cases(idx, src, True, fwd(True), fwd(False),
                                      future, (q_hi, kh, vh))
        o_hi, lse_hi = _merge_blocks(o_hi, lse_hi, o_blk, lse_blk)
        roll = lambda x: jax.lax.ppermute(x, axis_name, perm)
        return (o_lo, lse_lo, o_hi, lse_hi,
                roll(kl), roll(kh), roll(vl), roll(vh)), None

    z_o = jnp.zeros((b, c, h, d), jnp.float32)
    z_l = jnp.full((b, h, c), NEG_INF, jnp.float32)
    (o_lo, lse_lo, o_hi, lse_hi, *_), _ = jax.lax.scan(
        step, (z_o, z_l, z_o, z_l, k_lo, k_hi, v_lo, v_hi), jnp.arange(n))
    out = jnp.concatenate([o_lo, o_hi], axis=1).astype(q.dtype)
    lse = jnp.concatenate([lse_lo, lse_hi], axis=2)
    return out, (q, k, v, out, lse)


def _zigzag_vjp_fwd(q, k, v, axis_name, block_q, block_k):
    return _zigzag_fwd_res(q, k, v, axis_name, block_q, block_k)


def _zigzag_vjp_bwd(axis_name, block_q, block_k, res, g):
    """Backward ring pass with the same 4-pair structure: (k, v, dk, dv)
    half-bundles rotate together and return home fully accumulated after n
    steps; dq halves accumulate locally. Every pair recomputes P from the
    saved global lse via the tiled flash backward kernels."""
    from .flash_attention import _flash_bwd

    q, k, v, out, lse = res
    interpret = interpret_default()
    n = axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    b, t_loc, h, d = q.shape
    c = t_loc // 2
    bq, bk = min(block_q, c), min(block_k, c)
    q_lo, q_hi = _zigzag_split(q)
    k_lo, k_hi = _zigzag_split(k)
    v_lo, v_hi = _zigzag_split(v)
    o_lo, o_hi = _zigzag_split(out)
    g_lo, g_hi = _zigzag_split(g)
    lse_lo, lse_hi = _zigzag_split(lse, axis=2)
    perm = [(j, (j + 1) % n) for j in range(n)]

    def bwd(qh, oh, lseh, gh, causal_flag):
        def run(op):
            kh, vh = op
            return _flash_bwd(qh, kh, vh, oh, lseh, gh, causal=causal_flag,
                              block_q=bq, block_k=bk, interpret=interpret)
        return run

    def future(op):
        kh, vh = op
        return (jnp.zeros((b, c, h, d), q.dtype), jnp.zeros_like(kh),
                jnp.zeros_like(vh))

    def step(carry, i):
        dq_lo, dq_hi, kl, kh, vl, vh, dkl, dkh, dvl, dvh = carry
        src = (idx - i) % n
        # q_hi × k_lo: always past (dense)
        dqc, dkc, dvc = bwd(q_hi, o_hi, lse_hi, g_hi, False)((kl, vl))
        dq_hi = dq_hi + dqc.astype(jnp.float32)
        dkl = dkl + dkc.astype(jnp.float32)
        dvl = dvl + dvc.astype(jnp.float32)
        # q_lo × k_lo
        dqc, dkc, dvc = _block_cases(
            src, idx, True, bwd(q_lo, o_lo, lse_lo, g_lo, True),
            bwd(q_lo, o_lo, lse_lo, g_lo, False), future, (kl, vl))
        dq_lo = dq_lo + dqc.astype(jnp.float32)
        dkl = dkl + dkc.astype(jnp.float32)
        dvl = dvl + dvc.astype(jnp.float32)
        # q_hi × k_hi (inverted order)
        dqc, dkc, dvc = _block_cases(
            idx, src, True, bwd(q_hi, o_hi, lse_hi, g_hi, True),
            bwd(q_hi, o_hi, lse_hi, g_hi, False), future, (kh, vh))
        dq_hi = dq_hi + dqc.astype(jnp.float32)
        dkh = dkh + dkc.astype(jnp.float32)
        dvh = dvh + dvc.astype(jnp.float32)
        roll = lambda x: jax.lax.ppermute(x, axis_name, perm)
        return (dq_lo, dq_hi, roll(kl), roll(kh), roll(vl), roll(vh),
                roll(dkl), roll(dkh), roll(dvl), roll(dvh)), None

    z = lambda: jnp.zeros((b, c, h, d), jnp.float32)
    (dq_lo, dq_hi, _, _, _, _, dkl, dkh, dvl, dvh), _ = jax.lax.scan(
        step, (z(), z(), k_lo, k_hi, v_lo, v_hi, z(), z(), z(), z()),
        jnp.arange(n))
    cat = lambda a, b_, dt: jnp.concatenate([a, b_], axis=1).astype(dt)
    return (cat(dq_lo, dq_hi, q.dtype), cat(dkl, dkh, k.dtype),
            cat(dvl, dvh, v.dtype))


_zigzag_ring_flash.defvjp(_zigzag_vjp_fwd, _zigzag_vjp_bwd)


def _zigzag_ok(t: int, sp: int) -> bool:
    """Whether the zigzag layout applies: global T divides into 2·sp chunks
    AND each half-chunk tiles by the (env-default) flash blocks — otherwise
    the caller should stay on the plain ring."""
    from .flash_attention import tiles_ok

    if t % (2 * sp):
        return False
    c = t // (2 * sp)
    return tiles_ok(c, c)


def zigzag_ring_attention_local(q, k, v, *, axis_name: str = "sp",
                                causal: bool = True,
                                block_q: Optional[int] = None,
                                block_k: Optional[int] = None):
    """Load-balanced causal ring attention; called INSIDE shard_map over the
    ZIGZAG layout (``zigzag_permutation``). Causal only — without masking the
    plain ring is already balanced."""
    from .flash_attention import resolve_blocks, tiles_ok

    if not causal:
        return ring_attention_local(q, k, v, axis_name=axis_name, causal=False,
                                    block_q=block_q, block_k=block_k)
    if q.shape[1] % 2:
        raise ValueError("zigzag local block needs an even sequence length")
    c = q.shape[1] // 2
    b_q, b_k = resolve_blocks(c, c, block_q, block_k)
    if not tiles_ok(c, c, b_q, b_k):
        raise ValueError(f"zigzag half-chunk {c} must tile by blocks "
                         f"({b_q}/{b_k})")
    return _zigzag_ring_flash(q, k, v, axis_name, b_q, b_k)


def ring_attention_local(q, k, v, *, axis_name: str = "sp", causal: bool = False,
                         use_flash: Optional[bool] = None,
                         block_q: Optional[int] = None,
                         block_k: Optional[int] = None):
    """Ring attention over ``axis_name``; called INSIDE shard_map.

    q/k/v: local blocks (B, T_local, H, D); global seq is sharded over the ring.
    The per-step body is the pallas flash kernel on a TPU whenever the local
    sequence tiles evenly (``use_flash=None`` auto-detects); otherwise the
    plain-jnp online-softmax body runs. Tile sizes default to
    ``default_blocks()`` (env-tunable, like every flash call site).
    """
    from .flash_attention import resolve_blocks, tiles_ok

    b_q, b_k = resolve_blocks(q.shape[1], k.shape[1], block_q, block_k)
    can_flash = tiles_ok(q.shape[1], k.shape[1], b_q, b_k)
    if use_flash is None:
        # auto only on real TPU: elsewhere the kernel runs in interpret mode
        # (correct but slow) — forcing use_flash=True still works for tests
        use_flash = can_flash and jax.default_backend() == "tpu"
    if use_flash and not can_flash:
        raise ValueError(
            f"use_flash=True needs a local sequence the flash tiles fit "
            f"(T_q={q.shape[1]}, T_k={k.shape[1]}, blocks {b_q}/{b_k})")
    if not use_flash:
        return _ring_attention_jnp(q, k, v, axis_name=axis_name, causal=causal)
    return _ring_flash(q, k, v, axis_name, causal, b_q, b_k)


def ulysses_attention_local(q, k, v, *, axis_name: str = "sp",
                            causal: bool = False):
    """Ulysses all-to-all attention; called INSIDE shard_map.

    Reshard (B, T/n, H, D) -> (B, T, H/n, D) with all_to_all, run full local
    attention over the complete sequence, reshard back. Head count must divide
    the ``sp`` axis size.
    """
    n = axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)

    def a2a(x, split, concat):
        return jax.lax.all_to_all(x, axis_name, split_axis=split,
                                  concat_axis=concat, tiled=True)

    # seq-sharded -> head-sharded (gather full sequence, scatter heads)
    q_h = a2a(q, 2, 1)
    k_h = a2a(k, 2, 1)
    v_h = a2a(v, 2, 1)
    from .flash_attention import flash_attention, tiles_ok

    if (jax.default_backend() == "tpu"
            and tiles_ok(q_h.shape[1], k_h.shape[1])):
        # blockwise kernel over the gathered sequence: O(block²) score memory
        # per core instead of full_attention's O(T²)
        o = flash_attention(q_h, k_h, v_h, causal)
    else:  # interpret-mode pallas is slow; off-TPU uses the fused XLA path
        o = full_attention(q_h, k_h, v_h, causal=causal)
    return a2a(o, 1, 2)


#: ``auto`` on one TPU device takes the pallas kernel from this many tokens,
#: whatever the batch and whether or not a backward pass follows ...
FLASH_FROM_TOKENS = 2048
#: ... and, when a backward pass follows, at a shorter length too if the
#: kernel's largest tile divides it and the (B, H, T, T) scores have at least
#: this many elements a device. XLA's form writes, keeps and re-reads that
#: tensor for the backward: under 2**26 elements (128 MiB in bf16, a v5e's
#: VMEM) it does so faster than the kernel recomputes the scores, from there
#: on slower by a fifth or more, at head 64 and at head 128 alike; on tiles
#: of 128 or 256 (256, 640, 768, 896 tokens) the kernel loses at any batch.
#: Read from ``scripts/attention_routes.py`` on a v5e (PERF.md
#: section 6, PR 44).
FLASH_BACKWARD_TILE = 512
FLASH_BACKWARD_SCORES = 2 ** 26

_ROUTES = _tm.counter(
    "zoo_attention_route_total",
    "Attention calls traced on one device (no mesh, or sp == 1), by the "
    "route taken and whether a backward pass follows",
    labels=("route", "backward"))


def prefer_flash_single_device(t: int, backward: bool = False,
                               batch_heads: int = 1) -> bool:
    """Auto-dispatch rule shared by the layer (mesh-less) and
    :func:`sharded_attention` (sp==1) paths, so both resolve identically.
    On TPU the pallas kernel from :data:`FLASH_FROM_TOKENS` tokens up; a call
    that will be differentiated (``backward``: the layer passes its
    ``training`` flag) takes it at any multiple of
    :data:`FLASH_BACKWARD_TILE` tokens if its ``batch_heads`` (batch x heads
    on one device) make the score tensor :data:`FLASH_BACKWARD_SCORES`
    elements or more. Past 16k the kernel is the only option, since the
    (H, T, T) score tensor would OOM. A length the flash tiles do not divide
    stays on full attention.

    Query length 1 — the KV-cache decode step — is excluded UNCONDITIONALLY
    (not just by the threshold): a single query row has nothing to tile, so
    the flash grid/VMEM machinery is pure overhead over one dot+softmax;
    plain attention is the fast path no matter how the threshold is tuned."""
    if t <= 1:
        return False
    from .flash_attention import tiles_ok

    if jax.default_backend() != "tpu" or not tiles_ok(t, t):
        return False
    return t >= FLASH_FROM_TOKENS or (
        backward and t % FLASH_BACKWARD_TILE == 0
        and batch_heads * t * t >= FLASH_BACKWARD_SCORES)


def count_route(flash: bool, backward: bool) -> None:
    """One traced single-device attention call took this route (the route is
    chosen while tracing, so this counts traces, not runs)."""
    _ROUTES.labels(route="flash" if flash else "full",
                   backward="1" if backward else "0").inc()


def _rows_a_device(n: int, mesh, axes) -> int:
    """How many of ``n`` rows split over the mesh ``axes`` one device holds.
    An axis that is Manual in the context (the caller is inside a
    ``shard_map`` over it) has split them already."""
    manual = jax.sharding.get_abstract_mesh().manual_axes
    for a in axes:
        if a not in manual:
            n = -(-n // mesh.shape[a])
    return n


def sharded_attention(q, k, v, mesh, *, strategy: str = "auto",
                      causal: bool = False, seq_axis: str = "sp",
                      batch_axes=("dp", "fsdp"), head_axis: str = "tp",
                      backward: bool = False):
    """Dispatch attention under the global mesh (called inside jit).

    With ``sp > 1`` wraps the chosen sequence-parallel kernel in a shard_map whose
    specs shard batch over dp/fsdp, sequence over sp, heads over tp — so tensor and
    sequence parallelism compose. ``backward`` says that the call will be
    differentiated: with ``sp == 1`` ``auto`` then takes the flash kernel from
    a shorter sequence (:func:`prefer_flash_single_device`).
    """
    if strategy not in ("auto", "full", "flash", "ring", "zigzag", "ulysses"):
        raise ValueError(f"unknown attention strategy {strategy!r}; "
                         "known: auto, full, flash, ring, zigzag, ulysses")
    sp = mesh.shape[seq_axis]
    if strategy == "auto":
        if sp > 1:
            # causal: the zigzag layout halves the causal ring's idle time
            # when the shape supports it (divisibility + flash tiling); the
            # zigzag branch additionally falls back to ring off TPU
            strategy = ("zigzag" if causal and _zigzag_ok(q.shape[1], sp)
                        else "ring")
        else:
            strategy = ("flash" if prefer_flash_single_device(
                q.shape[1], backward,
                _rows_a_device(q.shape[0], mesh, batch_axes)
                * _rows_a_device(q.shape[2], mesh, (head_axis,)))
                else "full")
    if sp == 1:
        count_route(strategy == "flash", backward)
    if strategy == "flash":
        if sp > 1:
            raise ValueError(
                "strategy='flash' is a single-device kernel; on a sequence-"
                "parallel mesh (sp>1) use 'ring' (blockwise over the sp ring) "
                "or 'ulysses'")
        from .flash_attention import flash_attention

        # batch/head parallelism is embarrassingly parallel for attention:
        # shard_map keeps each device's kernel on its OWN batch/head shard
        # (without it GSPMD would all-gather q/k/v and replicate the work).
        # shard_map needs exact divisibility; shapes that don't split fall
        # back to the unwrapped kernel (GSPMD handles them, possibly with
        # gathers — correct, just not maximally parallel). Inside a caller's
        # shard_map over these axes (the ZeRO-1 flat path) q/k/v are this
        # device's shard already, and a second shard_map does not trace.
        batch_div = 1
        for a in batch_axes:
            batch_div *= mesh.shape[a]
        manual = jax.sharding.get_abstract_mesh().manual_axes
        if (all(a in manual for a in (*batch_axes, head_axis))
                or q.shape[0] % batch_div or q.shape[2] % mesh.shape[head_axis]):
            return flash_attention(q, k, v, causal)
        spec = P(batch_axes, None, head_axis, None)
        wrapped = shard_map(
            lambda q_, k_, v_: flash_attention(q_, k_, v_, causal),
            mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
            check_vma=False)
        return wrapped(q, k, v)
    if strategy == "full" or sp == 1:
        return full_attention(q, k, v, causal=causal)

    spec = P(batch_axes, seq_axis, head_axis, None)
    if strategy == "zigzag":
        import os

        if not causal:
            strategy = "ring"         # balanced already; zigzag buys nothing
        elif not _zigzag_ok(q.shape[1], sp):
            strategy = "ring"         # documented fallback: shape unsuitable
        elif (jax.default_backend() != "tpu"
              and os.environ.get("ZOO_FORCE_ZIGZAG") != "1"):
            # interpret-mode pallas off TPU is orders slower than the jnp
            # ring body; tests force the kernel with ZOO_FORCE_ZIGZAG=1
            strategy = "ring"
        else:
            import numpy as np

            perm = zigzag_permutation(q.shape[1], sp)
            inv = np.argsort(perm)
            wrapped = shard_map(
                functools.partial(zigzag_ring_attention_local,
                                  axis_name=seq_axis, causal=True),
                mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
                check_vma=False)
            # constant-index gathers; GSPMD lowers them to ICI permutes
            o = wrapped(q[:, perm], k[:, perm], v[:, perm])
            return o[:, inv]
    fn = {"ring": ring_attention_local,
          "ulysses": ulysses_attention_local}[strategy]
    wrapped = shard_map(
        functools.partial(fn, axis_name=seq_axis, causal=causal),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False)
    return wrapped(q, k, v)
