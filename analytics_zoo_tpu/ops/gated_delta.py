"""The gated delta rule (Gated DeltaNet, arXiv:2412.06464): the recurrence of
a linear-attention layer, and the two Mosaic kernels serving runs it through.

Per head, with ``k_t`` of unit length, a decay ``alpha_t`` in (0, 1] and a
write strength ``beta_t`` in [0, 2], the layer keeps a matrix state and reads
it with the query:

    S_t = alpha_t S_{t-1} (I - beta_t k_t k_t^T) + beta_t v_t k_t^T,   o_t = S_t q_t

Everything here holds the state transposed, ``M = S^T`` of shape ``(dk, dv)``
(keys on sublanes, values on lanes), where one step is

    u_t = beta_t (v_t - alpha_t M_{t-1}^T k_t),   M_t = alpha_t M_{t-1} + k_t u_t^T

* :func:`gated_delta_recurrent`: that, token by token (``lax.scan``). The
  yardstick of the tests; never served.
* :func:`gated_delta_chunked`: the chunkwise form. Inside a chunk of ``C``
  tokens the ``u`` of all tokens solve one unit-triangular system that does
  not involve the state the chunk starts from (:func:`chunk_prepare`, plain
  XLA, every chunk at once); what is sequential is a pass over the chunks that
  carries ``M``: three small products a chunk. ``kernel=False`` runs the pass
  as a ``lax.scan`` (differentiable: the teacher-forced ``apply``),
  ``kernel=True`` as the Mosaic kernel ``zoo_gdn_chunk_fwd`` (the served
  prefill). A token whose ``beta`` is 0 and ``log_alpha`` is 0 leaves the
  state as it was, which is how a bucket's padding is kept out of it.
* :func:`gdn_decode`: one token for every live slot, the Mosaic kernel
  ``zoo_gdn_decode``. The state of all heads of a slot is one ``(dk, H * dv)``
  tile-aligned block (:func:`state_to_lanes`: heads side by side on lanes, so
  no lane is padding: 96 x 5,760 float32 = 2.21 MB at Olmo-Hybrid's widths);
  the kernel reads it, updates it and writes it where it lay (the state is
  aliased input to output), which is all the bytes the step needs. Slots that
  hold no stream are neither read nor written: the grid walks the live slots
  (scalar-prefetched) and idles over the rest.

Both kernels take ``interpret`` from :mod:`.backend`, so the CPU tests and the
benchmark's rehearsal run the same code through the Pallas interpreter.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .backend import interpret_default

F32 = jnp.float32
_HIGHEST = jax.lax.Precision.HIGHEST

#: tokens a chunk of the prefill scan holds, where the sequence has as many
DEFAULT_CHUNK = 64

#: scoped VMEM the decode kernel asks for: a slot's state block is double
#: buffered on its way in and on its way out (4 x 2.21 MB at Olmo-Hybrid's
#: widths), which with the temporaries is over Mosaic's default of 16 MiB
_DECODE_VMEM_BYTES = 64 * 2 ** 20


def state_to_lanes(m: jax.Array) -> jax.Array:
    """``(B, H, dk, dv)`` per-head states -> ``(B, dk, H * dv)``, the layout
    slots keep them in."""
    b, h, dk, dv = m.shape
    return m.transpose(0, 2, 1, 3).reshape(b, dk, h * dv)


def lanes_to_state(m: jax.Array, n_heads: int) -> jax.Array:
    """The inverse of :func:`state_to_lanes`."""
    b, dk, hv = m.shape
    return m.reshape(b, dk, n_heads, hv // n_heads).transpose(0, 2, 1, 3)


# ---------------------------------------------------------------------------
# the recurrence, token by token
# ---------------------------------------------------------------------------

def gated_delta_recurrent(q, k, v, log_alpha, beta, state0=None):
    """``q``, ``k``: (B, T, H, dk); ``v``: (B, T, H, dv); ``log_alpha``,
    ``beta``: (B, T, H). Returns ``(o (B, T, H, dv), M_T (B, H, dk, dv))`` in
    float32."""
    q, k, v, log_alpha, beta = (jnp.asarray(a, F32)
                                for a in (q, k, v, log_alpha, beta))
    b, _, h, dk = q.shape
    m0 = (jnp.zeros((b, h, dk, v.shape[-1]), F32) if state0 is None
          else jnp.asarray(state0, F32))

    def step(m, xs):
        q_t, k_t, v_t, la_t, b_t = xs
        a_t = jnp.exp(la_t)[..., None]
        r = jnp.einsum("bhk,bhkv->bhv", k_t, m, precision=_HIGHEST)
        u = b_t[..., None] * (v_t - a_t * r)
        m = a_t[..., None] * m + k_t[..., None] * u[..., None, :]
        return m, jnp.einsum("bhk,bhkv->bhv", q_t, m, precision=_HIGHEST)

    xs = tuple(jnp.moveaxis(a, 1, 0) for a in (q, k, v, log_alpha, beta))
    m, o = jax.lax.scan(step, m0, xs)
    return jnp.moveaxis(o, 0, 1), m


# ---------------------------------------------------------------------------
# the chunkwise form
# ---------------------------------------------------------------------------

def chunk_prepare(q, k, v, log_alpha, beta, chunk: int):
    """Everything of the chunkwise form that does not involve the carried
    state, for every chunk at once, in float32. With ``g_i`` the running sum
    of ``log_alpha`` inside a chunk and ``G_ij = exp(g_i - g_j)``:

    * ``wv``, ``wk``: ``T V`` and ``T diag(exp g) K`` with ``T = (I + diag(beta)
      tril(G * K K^T, -1))^{-1} diag(beta)`` (:func:`_unit_lower_inverse`), so
      that ``U = wv - wk M_0``;
    * ``qg = diag(exp g) Q`` and ``p = tril(G * Q K^T)``: ``O = qg M_0 + p U``;
    * ``kd^T`` with ``kd_j = exp(g_C - g_j) k_j`` and ``dec = exp(g_C)``:
      ``M_C = dec M_0 + kd^T U``.

    Shapes: inputs as :func:`gated_delta_recurrent`; outputs ``(B, H, N, C,
    .)`` with ``N = T / C`` chunks, ``kd^T`` as ``(B, H, N, dk, C)`` and
    ``dec`` as ``(B, H, N, 1, dv)`` (one number a chunk, spread over a row so
    that a kernel reads it as a tile)."""
    b, t, h, dk = q.shape
    dv = v.shape[-1]
    n = t // chunk

    def chunks(a):          # (B, T, H, x) -> (B, H, N, C, x)
        return jnp.moveaxis(a.reshape(b, n, chunk, h, -1), 3, 1)

    q, k, v = (chunks(jnp.asarray(a, F32)) for a in (q, k, v))
    g = jnp.cumsum(chunks(jnp.asarray(log_alpha, F32)[..., None])[..., 0], -1)
    beta = chunks(jnp.asarray(beta, F32)[..., None])
    i = jnp.arange(chunk)
    # exp only of differences that are <= 0: never an overflow
    decay = jnp.exp(jnp.where(i[:, None] >= i[None, :],
                              g[..., :, None] - g[..., None, :], -jnp.inf))
    kk = jnp.einsum("bhnik,bhnjk->bhnij", k, k, precision=_HIGHEST)
    qk = jnp.einsum("bhnik,bhnjk->bhnij", q, k, precision=_HIGHEST)
    strict = (i[:, None] > i[None, :]).astype(F32)
    eg = jnp.exp(g)[..., None]
    w = jnp.matmul(_unit_lower_inverse(beta * kk * decay * strict),
                   jnp.concatenate([beta * v, beta * eg * k], -1),
                   precision=_HIGHEST)
    wv, wk = w[..., :dv], w[..., dv:]
    g_last = g[..., -1:]
    kd = jnp.exp(g_last - g)[..., None] * k
    dec = jnp.broadcast_to(jnp.exp(g_last)[..., None], (b, h, n, 1, dv))
    return wv, wk, eg * q, qk * decay, jnp.swapaxes(kd, -1, -2), dec


def _unit_lower_inverse(n, block: int = 16):
    """``(I + n)^-1`` for strictly lower-triangular ``n`` (..., C, C), by
    forward substitution on the diagonal blocks of ``block`` rows (that many
    small steps, every block and every system at once) and then the block
    formula ``[[A, 0], [X, D]]^-1 = [[A^-1, 0], [-D^-1 X A^-1, D^-1]]``, which
    doubles the blocks with two products a level. It is the substitution's
    arithmetic regrouped, so it is as stable as a triangular solve, and it is
    a handful of batched products where XLA's ``triangular_solve`` goes
    through the system a column at a time."""
    c = n.shape[-1]
    mm = functools.partial(jnp.matmul, precision=_HIGHEST)
    lead = n.shape[:-2]
    if c % block or (c // block) & (c // block - 1):
        block = c                       # no power-of-two split: substitute
    parts = c // block
    blocks = n.reshape(lead + (parts, block, parts, block))
    diag = jnp.stack([blocks[..., p, :, p, :] for p in range(parts)], -3)
    eye = jnp.eye(block, dtype=n.dtype)
    rows = []                           # row i of the inverse, all blocks
    for i in range(block):
        row = jnp.broadcast_to(eye[i], diag.shape[:-2] + (block,))
        if i:
            row = row - jnp.einsum("...j,...jk->...k", diag[..., i, :i],
                                   jnp.stack(rows, -2), precision=_HIGHEST)
        rows.append(row)
    inverse = jnp.stack(rows, -2)       # (..., parts, block, block)
    size = block
    while size < c:
        pairs = c // (2 * size)
        m = n.reshape(lead + (pairs, 2, size, pairs, 2, size))
        below = jnp.stack([m[..., p, 1, :, p, 0, :] for p in range(pairs)], -3)
        upper, lower = inverse[..., 0::2, :, :], inverse[..., 1::2, :, :]
        corner = -mm(mm(lower, below), upper)
        inverse = jnp.concatenate([
            jnp.concatenate([upper, jnp.zeros_like(upper)], -1),
            jnp.concatenate([corner, lower], -1)], -2)
        size *= 2
    return inverse[..., 0, :, :]


def _chunk_pass_scan(wv, wk, qg, p, kdt, dec):
    """The pass over chunks as a ``lax.scan`` (JAX differentiates it)."""
    b, h, _, _, dv = wv.shape
    dk = wk.shape[-1]
    mm = functools.partial(jnp.einsum, precision=_HIGHEST)

    def step(m, xs):
        wv_c, wk_c, qg_c, p_c, kdt_c, dec_c = xs
        u = wv_c - mm("bhck,bhkv->bhcv", wk_c, m)
        o = mm("bhck,bhkv->bhcv", qg_c, m) + mm("bhcj,bhjv->bhcv", p_c, u)
        return dec_c * m + mm("bhkc,bhcv->bhkv", kdt_c, u), o

    xs = tuple(jnp.moveaxis(a, 2, 0) for a in (wv, wk, qg, p, kdt, dec))
    m, o = jax.lax.scan(step, jnp.zeros((b, h, dk, dv), F32), xs)
    return jnp.moveaxis(o, 0, 2), m


def _chunk_kernel(wv_ref, wk_ref, qg_ref, p_ref, kdt_ref, dec_ref,
                  o_ref, m_ref, m_scr, *, n_chunks: int):
    c = pl.program_id(2)

    @pl.when(c == 0)
    def _start():
        m_scr[...] = jnp.zeros_like(m_scr)

    dot = functools.partial(jnp.dot, preferred_element_type=F32,
                            precision=_HIGHEST)
    m = m_scr[...]
    u = wv_ref[0, 0, 0] - dot(wk_ref[0, 0, 0], m)
    o_ref[0, 0, 0] = dot(qg_ref[0, 0, 0], m) + dot(p_ref[0, 0, 0], u)
    m = dec_ref[0, 0, 0] * m + dot(kdt_ref[0, 0, 0], u)
    m_scr[...] = m

    @pl.when(c == n_chunks - 1)
    def _end():
        m_ref[0, 0] = m


def gdn_chunk_fwd(wv, wk, qg, p, kdt, dec, *, interpret: Optional[bool] = None):
    """The pass over chunks as the Mosaic kernel ``zoo_gdn_chunk_fwd``: grid
    ``(batch, head, chunk)``, the chunk axis sequential, ``M`` in VMEM scratch
    from the first chunk to the last. Float32 operands at ``highest`` (the
    pass is a hundredth of a prefill's operations: the projections around it
    are what a prefill costs). Returns ``(o (B, H, N, C, dv), M (B, H, dk,
    dv))``; ``o`` is the first output, so that a trace's event carries the
    call's shape."""
    if interpret is None:
        interpret = interpret_default()
    b, h, n, c, dv = wv.shape
    dk = wk.shape[-1]

    def block(*tail):
        return pl.BlockSpec((1, 1, 1) + tail, lambda i, j, l: (i, j, l, 0, 0))

    return pl.pallas_call(
        functools.partial(_chunk_kernel, n_chunks=n),
        grid=(b, h, n),
        in_specs=[block(c, dv), block(c, dk), block(c, dk), block(c, c),
                  block(dk, c), block(1, dv)],
        out_specs=[block(c, dv),
                   pl.BlockSpec((1, 1, dk, dv), lambda i, j, l: (i, j, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct((b, h, n, c, dv), F32),
                   jax.ShapeDtypeStruct((b, h, dk, dv), F32)],
        scratch_shapes=[pltpu.VMEM((dk, dv), F32)],
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="zoo_gdn_chunk_fwd",
    )(wv, wk, qg, p, kdt, dec)


def chunk_of(t: int, chunk: int = DEFAULT_CHUNK) -> int:
    """Tokens a chunk holds for a sequence of ``t``: ``chunk``, or the whole
    sequence where it is shorter. ``t`` has to be a multiple of it (serving's
    buckets are powers of two)."""
    c = min(chunk, t)
    if t % c:
        raise ValueError(f"gated delta rule: a sequence of {t} tokens is no "
                         f"multiple of its chunk of {c}")
    return c


def gated_delta_chunked(q, k, v, log_alpha, beta, *, chunk: int = DEFAULT_CHUNK,
                        kernel: bool = False,
                        interpret: Optional[bool] = None
                        ) -> Tuple[jax.Array, jax.Array]:
    """The chunkwise form from a zero state; arguments and results as
    :func:`gated_delta_recurrent`."""
    b, t, h, _ = q.shape
    parts = chunk_prepare(q, k, v, log_alpha, beta, chunk_of(t, chunk))
    o, m = (gdn_chunk_fwd(*parts, interpret=interpret) if kernel
            else _chunk_pass_scan(*parts))
    return jnp.moveaxis(o, 1, 3).reshape(b, t, h, -1), m


# ---------------------------------------------------------------------------
# one token for every live slot
# ---------------------------------------------------------------------------

def head_group(n_heads: int, dv: int) -> int:
    """Heads the decode kernel updates at once: as many as make their lanes
    whole tiles of 128 (two at a value width of 192), or all of them where no
    such group divides the heads (the tiny sizes of the tests)."""
    for g in range(1, n_heads + 1):
        if n_heads % g == 0 and (g * dv) % 128 == 0:
            return g
    return n_heads


def _decode_kernel(idx_ref, n_ref, s_ref, kq_ref, vab_ref, o_ref, s_out_ref,
                   *, n_heads: int, dv: int, group: int):
    del idx_ref                         # read by the index maps

    @pl.when(n_ref[0] == 0)
    def _none_live():
        # the one block the grid then stays on is written back all the same
        s_out_ref[...] = s_ref[...]
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(pl.program_id(0) < n_ref[0])
    def _live():
        width = group * dv
        head_of_lane = jax.lax.broadcasted_iota(
            jnp.int32, (1, width), 1) // dv
        for p in range(n_heads // group):
            lanes = slice(p * width, (p + 1) * width)

            def per_lane(first):
                """Column ``first + head`` of ``kq`` on the lanes of each of
                this group's heads: (dk, width)."""
                cols = None
                for j in range(group):
                    col = kq_ref[0, :, first + p * group + j:
                                 first + p * group + j + 1]
                    cols = col if cols is None else jnp.where(
                        head_of_lane == j, col, cols)
                return cols

            k = per_lane(0)
            q = per_lane(n_heads)
            m = s_ref[0, :, lanes]
            v = vab_ref[0, 0:1, lanes]
            alpha = vab_ref[0, 1:2, lanes]
            beta = vab_ref[0, 2:3, lanes]
            r = jnp.sum(k * m, axis=0, keepdims=True)
            u = beta * (v - alpha * r)
            m = alpha * m + k * u
            o_ref[0, :, lanes] = jnp.sum(q * m, axis=0, keepdims=True)
            s_out_ref[0, :, lanes] = m


def gdn_decode(state, q, k, v, alpha, beta, live, *,
               interpret: Optional[bool] = None):
    """One step of the recurrence for every live slot, the Mosaic kernel
    ``zoo_gdn_decode``.

    ``state``: (B, dk, H * dv) float32 (:func:`state_to_lanes`), to be
    donated by the caller's jit: it is aliased to the state returned.
    ``q``, ``k``: (B, H, dk), ``v``: (B, H, dv), ``alpha``, ``beta``: (B, H);
    ``live``: (B,) bool. Returns ``(o (B, H, dv) float32, state)``. A slot
    that is not live keeps its state, bit for bit, and reads ``o`` = 0: the
    grid's step ``i`` maps to the ``i``-th live slot, and the steps past the
    last stay on that slot's block and compute nothing, so nothing is
    fetched or written for them."""
    if interpret is None:
        interpret = interpret_default()
    b, dk, hv = state.shape
    h = q.shape[1]
    dv = hv // h
    live = jnp.asarray(live, bool)
    n_live = jnp.sum(live, dtype=jnp.int32)
    order = jnp.argsort(~live, stable=True).astype(jnp.int32)
    idx = jnp.where(jnp.arange(b) < n_live, order,
                    order[jnp.maximum(n_live - 1, 0)])
    kq = jnp.concatenate([jnp.swapaxes(jnp.asarray(k, F32), 1, 2),
                          jnp.swapaxes(jnp.asarray(q, F32), 1, 2)], -1)
    vab = jnp.stack([jnp.asarray(v, F32).reshape(b, hv),
                     jnp.repeat(jnp.asarray(alpha, F32), dv, axis=-1),
                     jnp.repeat(jnp.asarray(beta, F32), dv, axis=-1)], 1)

    def at_slot(*tail):
        return pl.BlockSpec((1,) + tail, lambda i, idx, n: (idx[i], 0, 0))

    o, state = pl.pallas_call(
        functools.partial(_decode_kernel, n_heads=h, dv=dv,
                          group=head_group(h, dv)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(b,),
            in_specs=[at_slot(dk, hv), at_slot(dk, 2 * h), at_slot(3, hv)],
            out_specs=[at_slot(1, hv), at_slot(dk, hv)]),
        out_shape=[jax.ShapeDtypeStruct((b, 1, hv), F32),
                   jax.ShapeDtypeStruct(state.shape, F32)],
        # operands count the two prefetched scalars: the state is the third
        input_output_aliases={2: 1},
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_DECODE_VMEM_BYTES),
        interpret=interpret,
        name="zoo_gdn_decode",
    )(idx, n_live[None], state, kq, vab)
    o = jnp.where(live[:, None, None], o.reshape(b, h, dv), 0.0)
    return o, state


__all__ = ["DEFAULT_CHUNK", "chunk_of", "chunk_prepare",
           "gated_delta_chunked", "gated_delta_recurrent", "gdn_chunk_fwd",
           "gdn_decode", "head_group", "lanes_to_state", "state_to_lanes"]
