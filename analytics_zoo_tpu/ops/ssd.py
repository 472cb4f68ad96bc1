"""The selective state-space recurrence of Mamba-2 (state-space duality,
arXiv:2405.21060): what a state-space layer keeps between tokens, and the two
Mosaic kernels serving runs it through.

Per head, with a decay ``a_t = exp(dt_t A)`` in (0, 1] (``A < 0`` one scalar a
head, ``dt_t > 0`` a step size a token) and ``B_t``, ``C_t`` of width ``N``
shared by the heads of a group, the layer keeps a matrix state and reads it:

    S_t = a_t S_{t-1} + dt_t x_t B_t^T        (P x N a head),   y_t = S_t C_t

Everything here holds the state transposed, ``M = S^T`` of shape ``(N, P)``
(state rows on sublanes, the head's width on lanes: at ``P = 128`` a row of
the state is a row of a vector register), where one step is

    M_t = a_t M_{t-1} + B_t (dt_t x_t)^T,   y_t = C_t^T M_t

The skip ``D x_t`` is the caller's (it involves no state).

* :func:`ssd_recurrent`: that, token by token (``lax.scan``). The yardstick
  of the tests; never served.
* :func:`ssd_chunked`: the dual form by chunks of ``C`` tokens. With ``g_i``
  the running sum of ``log a`` inside a chunk, a chunk's outputs are
  ``(L * (C B^T)) (dt x) + diag(exp g) C M_0`` with ``L_ij = exp(g_i - g_j)``
  for ``i >= j``, and its state ``exp(g_C) M_0 + B^T diag(exp(g_C - g) dt) x``:
  four products a chunk a head, ``2 C^2 (N + P) + 4 C N P`` operations.
  What is elementwise (``dt x``, ``L``, the two scalings) is made for every
  chunk at once by :func:`chunk_prepare`, plain XLA; the products and the
  pass over the chunks that carries ``M`` are ``kernel=False`` a ``lax.scan``
  (differentiable: the teacher-forced ``apply``) and ``kernel=True`` the
  Mosaic kernel ``zoo_ssd_chunk_fwd`` (the served prefill). A token whose
  ``dt`` is 0 leaves the state as it was and adds nothing to it, which is how
  a bucket's padding is kept out.
* :func:`ssd_decode`: one token for every live slot, the Mosaic kernel
  ``zoo_ssd_decode``. A program reads a block of heads of one slot's state,
  updates it and writes it where it lay (the state is aliased input to
  output): all the bytes the step needs. Slots that hold no stream are
  neither read nor written: the grid walks the live slots (scalar-prefetched)
  and idles over the rest. :func:`ssd_decode_step` is the same step in
  ``jax.numpy``, over every slot, for the tests to hold the kernel to.

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

#: lanes of the array that carries a chunk's two per-token scalings into the
#: kernel (column 0: ``exp g``, column 1: ``exp(g_C - g)``): one lane tile
_SCALE_LANES = 128


def per_head(bc: jax.Array, n_heads: int, axis: int = -2) -> jax.Array:
    """``B`` or ``C`` with its ``G`` groups on ``axis`` (``(..., G, N)``) as
    ``n_heads`` heads read it: head ``i`` reads group ``i // (n_heads / G)``."""
    return jnp.repeat(bc, n_heads // bc.shape[axis], axis=axis)


# ---------------------------------------------------------------------------
# the recurrence, token by token
# ---------------------------------------------------------------------------

def ssd_recurrent(x, dt, a, b, c, state0=None):
    """``x``: (B, T, H, P); ``dt``: (B, T, H), after its softplus; ``a``:
    (H,), negative; ``b``, ``c``: (B, T, G, N). Returns ``(y (B, T, H, P),
    M_T (B, H, N, P))`` in float32."""
    x, dt, a, b, c = (jnp.asarray(v, F32) for v in (x, dt, a, b, c))
    bsz, _, h, p = x.shape
    b, c = per_head(b, h), per_head(c, h)
    m0 = (jnp.zeros((bsz, h, b.shape[-1], p), F32) if state0 is None
          else jnp.asarray(state0, F32))

    def step(m, xs):
        x_t, dt_t, b_t, c_t = xs
        decay = jnp.exp(dt_t * a)[..., None, None]
        m = decay * m + b_t[..., :, None] * (dt_t[..., None] * x_t)[..., None, :]
        return m, jnp.einsum("bhn,bhnp->bhp", c_t, m, precision=_HIGHEST)

    m, y = jax.lax.scan(step, m0, tuple(jnp.moveaxis(v, 1, 0)
                                        for v in (x, dt, b, c)))
    return jnp.moveaxis(y, 0, 1), m


def ssd_decode_step(state, x, dt, a, b, c, live):
    """One step of :func:`ssd_recurrent` for every row, in ``jax.numpy``:
    ``state`` (B, H, N, P), ``x`` (B, H, P), ``dt`` (B, H), ``b``, ``c`` (B,
    G, N), ``live`` (B,) bool. What :func:`ssd_decode` computes; a row that
    is not live keeps its state and reads 0."""
    y, new = ssd_recurrent(x[:, None], dt[:, None], a, b[:, None], c[:, None],
                           state)
    keep = jnp.asarray(live, bool)[:, None, None]
    return (jnp.where(keep, y[:, 0], 0.0),
            jnp.where(keep[..., None], new, state))


# ---------------------------------------------------------------------------
# the dual form, by chunks
# ---------------------------------------------------------------------------

def chunk_of(t: int, chunk: int) -> int:
    """Tokens a chunk holds for a sequence of ``t``: ``chunk``, or the whole
    sequence where it is shorter. ``t`` has to be a multiple of it (serving's
    buckets are powers of two)."""
    size = min(chunk, t)
    if t % size:
        raise ValueError(f"state-space scan: a sequence of {t} tokens is no "
                         f"multiple of its chunk of {size}")
    return size


def chunk_prepare(x, dt, a, b, c, chunk: int):
    """What of the dual form is elementwise, for every chunk at once, in
    float32: ``xdt = dt x`` (B, H, n, C, P); ``decay`` (B, H, n, C, C), ``L``
    above; ``scale`` (B, H, n, C, 2), ``exp g`` and ``exp(g_C - g)``; ``whole``
    (B, H, n, 1, P), ``exp g_C`` (one number a chunk, spread over a row so
    that a kernel reads it as a tile); and ``b``, ``c`` by chunks, (B, G, n,
    C, N)."""
    bsz, t, h, p = x.shape
    n = t // chunk

    def chunks(v):          # (B, T, heads or groups, w) -> (B, ., n, C, w)
        return jnp.moveaxis(v.reshape(bsz, n, chunk, v.shape[2], -1), 3, 1)

    dt = jnp.asarray(dt, F32)
    g = jnp.cumsum(chunks((dt * jnp.asarray(a, F32))[..., None])[..., 0], -1)
    i = jnp.arange(chunk)
    # exp only of differences that are <= 0: never an overflow
    decay = jnp.exp(jnp.where(i[:, None] >= i[None, :],
                              g[..., :, None] - g[..., None, :], -jnp.inf))
    scale = jnp.stack([jnp.exp(g), jnp.exp(g[..., -1:] - g)], -1)
    whole = jnp.broadcast_to(jnp.exp(g[..., -1:])[..., None], (bsz, h, n, 1, p))
    return (chunks(jnp.asarray(x, F32) * dt[..., None]), decay, scale, whole,
            chunks(jnp.asarray(b, F32)), chunks(jnp.asarray(c, F32)))


def _chunk_pass_scan(xdt, decay, scale, whole, b, c):
    """The products and the pass over chunks as a ``lax.scan`` (JAX
    differentiates it)."""
    bsz, h, _, _, p = xdt.shape
    mm = functools.partial(jnp.einsum, precision=_HIGHEST)

    def step(m, xs):
        xdt_c, decay_c, scale_c, whole_c, b_c, c_c = xs
        b_c, c_c = per_head(b_c, h, axis=1), per_head(c_c, h, axis=1)
        y = mm("bhij,bhjp->bhip", mm("bhin,bhjn->bhij", c_c, b_c) * decay_c,
               xdt_c) + scale_c[..., 0:1] * mm("bhin,bhnp->bhip", c_c, m)
        m = whole_c * m + mm("bhjn,bhjp->bhnp", b_c, scale_c[..., 1:2] * xdt_c)
        return m, y

    xs = tuple(jnp.moveaxis(v, 2, 0)
               for v in (xdt, decay, scale, whole, b, c))
    m, y = jax.lax.scan(step, jnp.zeros((bsz, h, b.shape[-1], p), F32), xs)
    return jnp.moveaxis(y, 0, 2), m


def _chunk_kernel(xdt_ref, decay_ref, scale_ref, whole_ref, b_ref, c_ref,
                  y_ref, m_ref, m_scr, *, n_chunks: int):
    n = pl.program_id(2)

    @pl.when(n == 0)
    def _start():
        m_scr[...] = jnp.zeros_like(m_scr)

    dot = functools.partial(jax.lax.dot_general, preferred_element_type=F32,
                            precision=_HIGHEST)
    m = m_scr[...]                                  # (N, P)
    xdt, scale = xdt_ref[0, 0, 0], scale_ref[0, 0, 0]
    b_c, c_c = b_ref[0, 0, 0], c_ref[0, 0, 0]       # (C, N)
    cb = dot(c_c, b_c, (((1,), (1,)), ((), ())))    # C B^T: (C, C)
    y_ref[0, 0, 0] = (
        dot(cb * decay_ref[0, 0, 0], xdt, (((1,), (0,)), ((), ())))
        + scale[:, 0:1] * dot(c_c, m, (((1,), (0,)), ((), ()))))
    m = whole_ref[0, 0, 0] * m + dot(
        b_c, scale[:, 1:2] * xdt, (((0,), (0,)), ((), ())))     # B^T (w x)
    m_scr[...] = m

    @pl.when(n == n_chunks - 1)
    def _end():
        m_ref[0, 0] = m


def ssd_chunk_fwd(xdt, decay, scale, whole, b, c, *,
                  interpret: Optional[bool] = None):
    """The chunks' products and the pass over them as the Mosaic kernel
    ``zoo_ssd_chunk_fwd``: grid ``(batch, head, chunk)``, the chunk axis
    sequential, ``M`` in VMEM scratch from the first chunk to the last; a
    head reads its group's ``b`` and ``c``. Float32 operands at ``highest``
    (the scan is a hundredth of a prefill's operations: the projections
    around it are what a prefill costs). Returns ``(y (B, H, n, C, P), M (B,
    H, N, P))``; ``y`` is the first output, so that a trace's event carries
    the call's shape."""
    if interpret is None:
        interpret = interpret_default()
    bsz, h, n, size, p = xdt.shape
    groups, width = b.shape[1], b.shape[-1]
    scale = jnp.pad(scale, ((0, 0),) * 4 + ((0, _SCALE_LANES - 2),))

    def block(*tail, heads_a_group=1):
        return pl.BlockSpec((1, 1, 1) + tail,
                            lambda i, j, l: (i, j // heads_a_group, l, 0, 0))

    of_group = functools.partial(block, size, width,
                                 heads_a_group=h // groups)
    return pl.pallas_call(
        functools.partial(_chunk_kernel, n_chunks=n),
        grid=(bsz, h, n),
        in_specs=[block(size, p), block(size, size),
                  block(size, _SCALE_LANES), block(1, p), of_group(),
                  of_group()],
        out_specs=[block(size, p),
                   pl.BlockSpec((1, 1, width, p), lambda i, j, l: (i, j, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct((bsz, h, n, size, p), F32),
                   jax.ShapeDtypeStruct((bsz, h, width, p), F32)],
        scratch_shapes=[pltpu.VMEM((width, p), F32)],
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="zoo_ssd_chunk_fwd",
    )(xdt, decay, scale, whole, b, c)


def ssd_chunked(x, dt, a, b, c, *, chunk: int, kernel: bool = False,
                interpret: Optional[bool] = None
                ) -> Tuple[jax.Array, jax.Array]:
    """The dual form from a zero state; arguments and results as
    :func:`ssd_recurrent`."""
    bsz, t, h, p = x.shape
    parts = chunk_prepare(x, dt, a, b, c, chunk_of(t, chunk))
    y, m = (ssd_chunk_fwd(*parts, interpret=interpret) if kernel
            else _chunk_pass_scan(*parts))
    return jnp.moveaxis(y, 1, 3).reshape(bsz, t, h, p), m


# ---------------------------------------------------------------------------
# one token for every live slot
# ---------------------------------------------------------------------------

def head_block(n_heads: int) -> int:
    """Heads of one slot the decode kernel updates in a program: 8 (a tile
    of sublanes in the arrays that carry a row a head, and a megabyte of
    state at 256 x 128), or all of them where 8 does not divide them (the
    tiny sizes of the tests)."""
    return 8 if n_heads % 8 == 0 else n_heads


def _decode_kernel(idx_ref, n_ref, m_ref, xa_ref, bc_ref, y_ref, m_out_ref,
                   *, block: int, heads_a_group: int, n_groups: int):
    del idx_ref                         # read by the index maps
    first = pl.program_id(1) * block

    @pl.when(n_ref[0] == 0)
    def _none_live():
        # the one block the grid then stays on is written back all the same
        m_out_ref[...] = m_ref[...]
        y_ref[...] = jnp.zeros_like(y_ref)

    @pl.when(pl.program_id(0) < n_ref[0])
    def _live():
        for j in range(block):
            # this head's B and C: its group's columns of the slot's ``bc``
            group = (first + j) // heads_a_group
            b_col, c_col = bc_ref[0, :, 0:1], bc_ref[0, :, n_groups:n_groups + 1]
            for g in range(1, n_groups):
                b_col = jnp.where(group == g, bc_ref[0, :, g:g + 1], b_col)
                c_col = jnp.where(
                    group == g, bc_ref[0, :, n_groups + g:n_groups + g + 1],
                    c_col)
            xdt = xa_ref[0, 0, j:j + 1, :]              # (1, P)
            decay = xa_ref[0, 1, j:j + 1, :]
            m = decay * m_ref[0, j] + b_col * xdt       # (N, P)
            y_ref[0, j:j + 1, :] = jnp.sum(c_col * m, axis=0, keepdims=True)
            m_out_ref[0, j] = m


def ssd_decode(state, x, dt, a, b, c, live, *,
               interpret: Optional[bool] = None):
    """One step of the recurrence for every live slot, the Mosaic kernel
    ``zoo_ssd_decode``.

    ``state``: (B, H, N, P) float32, to be donated by the caller's jit: it
    is aliased to the state returned. ``x``: (B, H, P), ``dt``: (B, H),
    ``a``: (H,), ``b``, ``c``: (B, G, N); ``live``: (B,) bool. Returns ``(y
    (B, H, P) float32, state)``. A slot that is not live keeps its state, bit
    for bit, and reads ``y`` = 0: the grid's step ``i`` maps to the ``i``-th
    live slot, and the steps past the last stay on that slot's last block
    and compute nothing, so nothing is fetched or written for them."""
    if interpret is None:
        interpret = interpret_default()
    bsz, h, width, p = state.shape
    groups = b.shape[1]
    block = head_block(h)
    n_blocks = h // block
    live = jnp.asarray(live, bool)
    n_live = jnp.sum(live, dtype=jnp.int32)
    order = jnp.argsort(~live, stable=True).astype(jnp.int32)
    idx = jnp.where(jnp.arange(bsz) < n_live, order,
                    order[jnp.maximum(n_live - 1, 0)])
    dt = jnp.asarray(dt, F32)
    # a row a head, the head's width on lanes: dt x, and the decay spread
    xa = jnp.stack([jnp.asarray(x, F32) * dt[..., None],
                    jnp.broadcast_to(jnp.exp(dt * jnp.asarray(a, F32))[
                        ..., None], x.shape)], 1)            # (B, 2, H, P)
    # a column a group, the state's rows on sublanes: B's, then C's
    bc = jnp.swapaxes(jnp.concatenate([jnp.asarray(b, F32),
                                       jnp.asarray(c, F32)], 1), 1, 2)

    def at(i, j, idx, n):
        """Block ``j`` of the ``i``-th live slot; past the last live slot,
        where it already is."""
        return idx[i], jnp.where(i < n[0], j, n_blocks - 1)

    y, state = pl.pallas_call(
        functools.partial(_decode_kernel, block=block,
                          heads_a_group=h // groups, n_groups=groups),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(bsz, n_blocks),
            in_specs=[
                pl.BlockSpec((1, block, width, p),
                             lambda i, j, idx, n: at(i, j, idx, n) + (0, 0)),
                pl.BlockSpec((1, 2, block, p), lambda i, j, idx, n: (
                    idx[i], 0, at(i, j, idx, n)[1], 0)),
                pl.BlockSpec((1, width, 2 * groups),
                             lambda i, j, idx, n: (idx[i], 0, 0))],
            out_specs=[
                pl.BlockSpec((1, block, p),
                             lambda i, j, idx, n: at(i, j, idx, n) + (0,)),
                pl.BlockSpec((1, block, width, p),
                             lambda i, j, idx, n: at(i, j, idx, n) + (0, 0))]),
        out_shape=[jax.ShapeDtypeStruct((bsz, h, p), F32),
                   jax.ShapeDtypeStruct(state.shape, F32)],
        # operands count the two prefetched scalars: the state is the third
        input_output_aliases={2: 1},
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
        name="zoo_ssd_decode",
    )(idx, n_live[None], state, xa, bc)
    return jnp.where(live[:, None, None], y, 0.0), state


__all__ = ["chunk_of", "chunk_prepare", "head_block", "per_head",
           "ssd_chunk_fwd", "ssd_chunked", "ssd_decode", "ssd_decode_step",
           "ssd_recurrent"]
