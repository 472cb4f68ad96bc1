"""Fused-quantization pallas kernels — int8 matmul/conv with in-VMEM
activation quantization (ROADMAP "Pallas kernel tier").

Why this exists: the lax path in :mod:`ops.int8` is numerically right but
structurally wrong for serving — XLA materializes the quantized activations
(``round``/``clamp``/``convert`` → an int8 array the size of the input) and
the f32 rescale as separate HBM round-trips around each ``dot_general``.  On
a raw matmul int8 still wins (1.53×), but through the serving dispatch path
those extra HBM passes inverted the win to 0.72× vs bf16.  Here the whole
pipeline lives inside one kernel per layer:

* the activation tile is quantized **in VMEM** (per-row abs-max over the
  K-tile → int8 — finer granularity than the unfused per-full-row scheme, so
  accuracy can only improve),
* the MXU int8 dot runs per (M,N,K) tile with an int32 accumulator,
* the per-row × per-output-channel rescale is applied on the f32 VMEM
  accumulator, and only the final activation-dtype output block is written
  back — no int8 or dequantized-f32 intermediate ever touches HBM.

The conv variant folds the KH tap rows into the grid and unrolls the KW taps
of a row inside the kernel: each program owns one (batch, output-row) pair
and accumulates ``window @ W[kh,kw]`` per tap with per-output-pixel
activation scales (one abs-max over channels per pixel — the granularity the
unfused path in :mod:`ops.int8` now matches).

Block sizes are the caller's arguments or the fixed defaults, shrunk to
divisors of the shape (:func:`resolve_blocks`).  The router
(:func:`ops.int8.int8_matmul` / :func:`ops.int8.int8_conv2d`) asks
:func:`resolve_blocks` / :func:`conv_supported` first and sends shapes the
kernels do not cover to the lax path; a kernel that was selected and cannot
run raises.  On non-TPU backends the kernels run in interpreter mode for
tests; production CPU inference keeps the lax path (an interpreted kernel is
not a speedup).
"""

from __future__ import annotations

import functools
import os
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .backend import interpret_default

#: The schedule of a call that passes no blocks.
DEFAULT_BLOCK_M = 256
DEFAULT_BLOCK_N = 256
DEFAULT_BLOCK_K = 512

# int8 VMEM tiling floor is (32, 128); the M dim only feeds the MXU rows so
# 8 (the f32 sublane) is enough for the padded-M path. Interpreter mode has
# no hardware tiling constraint but keeps a floor of 8 on N/K so the
# tileable-vs-fallback decision CPU tests exercise mirrors the TPU one
# (scaled down), instead of degenerating to 1-wide tiles.
_MIN_M, _MIN_N, _MIN_K = 8, 128, 128
_MIN_INTERPRET = 8


def fused_mode() -> str:
    """Routing decision for the int8 entry points: ``'compiled'`` (TPU),
    ``'interpret'`` (forced kernels on CPU — tests/structural gates), or
    ``'off'`` (lax path).

    ``ZOO_INT8_FUSED``: ``0``/``off`` disables, ``1``/``on``/``interpret``
    enable. Whether an enabled kernel compiles or interprets is the
    backend's call alone (:func:`ops.backend.interpret_default`), so
    ``interpret`` on a TPU is refused rather than obeyed.
    Default: compiled on TPU, off elsewhere — an interpreted kernel is
    correctness-equal but orders of magnitude slower than the lax path.
    """
    env = os.environ.get("ZOO_INT8_FUSED", "").strip().lower()
    if env in ("0", "off", "false"):
        return "off"
    interpret = interpret_default()
    if env == "interpret" and not interpret:
        raise ValueError(
            "ZOO_INT8_FUSED=interpret asks for the Pallas interpreter on a "
            "TPU backend; kernels compile there (a test passes "
            "interpret=True to the kernel instead)")
    if env in ("1", "on", "true", "interpret"):
        return "interpret" if interpret else "compiled"
    return "off" if interpret else "compiled"


def _pow2_floor(v: int) -> int:
    return 1 << (int(v).bit_length() - 1)


def _pow2_ceil(v: int) -> int:
    return 1 << (int(v) - 1).bit_length() if v > 1 else 1


def _shrink_to_divisor(dim: int, block: int, floor: int) -> Optional[int]:
    """Largest power-of-two ≤ ``block`` that divides ``dim`` and is ≥
    ``floor`` — None when no such tile exists."""
    b = _pow2_floor(block)
    while b >= floor:
        if dim % b == 0:
            return b
        b //= 2
    return None


def resolve_blocks(m: int, n: int, k: int,
                   block_m: Optional[int] = None,
                   block_n: Optional[int] = None,
                   block_k: Optional[int] = None,
                   interpret: bool = False) -> Optional[Tuple[int, int, int]]:
    """Resolve the (block_m, block_n, block_k) schedule for an (M,K)×(K,N)
    fused matmul: explicit args, else the fixed defaults; every choice is
    shrunk to a power-of-two divisor of its dim. Returns None when N or K
    cannot tile (M is padded by the caller) — the router's test for sending
    the shape to the lax path."""
    block_m = block_m or DEFAULT_BLOCK_M
    block_n = block_n or DEFAULT_BLOCK_N
    block_k = block_k or DEFAULT_BLOCK_K
    # M need not divide: the caller zero-pads the rows up to a block multiple
    # (ragged shape-bucket edges); clamp near M so a tiny batch doesn't pay a
    # full 256-row tile of padding compute
    bm = max(min(_pow2_floor(block_m), _pow2_ceil(max(m, 1))),
             1 if interpret else _MIN_M)
    bn = _shrink_to_divisor(n, min(block_n, n),
                            _MIN_INTERPRET if interpret else _MIN_N)
    bk = _shrink_to_divisor(k, min(block_k, k),
                            _MIN_INTERPRET if interpret else _MIN_K)
    if bn is None or bk is None:
        return None
    return bm, bn, bk


# --------------------------------------------------------------- fused matmul


def _int8_matmul_kernel(x_ref, wq_ref, ws_ref, o_ref, acc_scr):
    """One (block_m, block_n) output tile; grid dim 2 folds the K tiles.

    Quantize the activation K-tile in VMEM (per-row abs-max), int8 MXU dot,
    rescale the int32 partial by the per-row scale into the f32 accumulator;
    the per-channel weight scale lands once on writeback."""
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        acc_scr[:] = jnp.zeros_like(acc_scr)

    x = x_ref[...].astype(jnp.float32)                      # (bm, bk)
    amax = jnp.max(jnp.abs(x), axis=1, keepdims=True)
    scale = jnp.maximum(amax, 1e-12) * (1.0 / 127.0)        # (bm, 1)
    xq = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
    part = jax.lax.dot_general(xq, wq_ref[...], (((1,), (0,)), ((), ())),
                               preferred_element_type=jnp.int32)
    acc_scr[:] += part.astype(jnp.float32) * scale

    @pl.when(ki == nk - 1)
    def _finish():
        o_ref[...] = (acc_scr[:] * ws_ref[...]).astype(o_ref.dtype)


def _fused_matmul_2d(x2, wq, ws_row, out_dtype, bm: int, bn: int, bk: int,
                     interpret: bool):
    m, k = x2.shape
    n = wq.shape[1]
    return pl.pallas_call(
        _int8_matmul_kernel,
        grid=(m // bm, n // bn, k // bk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda mi, ni, ki: (mi, ki)),
            pl.BlockSpec((bk, bn), lambda mi, ni, ki: (ki, ni)),
            pl.BlockSpec((1, bn), lambda mi, ni, ki: (0, ni)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda mi, ni, ki: (mi, ni)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        # the (mi, ni) dims each own a disjoint output block; only the K fold
        # must stay sequential (it revisits the accumulator)
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="zoo_int8_matmul",
    )(x2, wq, ws_row)


def int8_matmul_fused(x: jnp.ndarray, packed: Dict[str, Any], *,
                      block_m: Optional[int] = None,
                      block_n: Optional[int] = None,
                      block_k: Optional[int] = None,
                      out_dtype=None,
                      interpret: Optional[bool] = None) -> jnp.ndarray:
    """``x @ W`` on the int8 MXU path with quantize+rescale fused into the
    kernel. ``packed`` is ``ops.int8.quantize_weight`` of an (in, out)
    kernel. Returns ``x.shape[:-1] + (out,)`` in ``out_dtype`` (default f32,
    matching the unfused path). Raises ``ValueError`` naming the shape when
    N or K cannot tile (:func:`resolve_blocks` is the router's test)."""
    interpret = interpret_default() if interpret is None else interpret
    wq = packed["q"]
    k, n = wq.shape
    lead = x.shape[:-1]
    m = int(np.prod(lead)) if lead else 1
    out_dtype = jnp.float32 if out_dtype is None else out_dtype
    if m == 0:
        return jnp.zeros(lead + (n,), out_dtype)
    blocks = resolve_blocks(m, n, k, block_m, block_n, block_k,
                            interpret=interpret)
    if blocks is None:
        raise ValueError(f"int8_matmul_fused: x{x.shape} @ w{wq.shape} does "
                         f"not tile (N and K need a power-of-two divisor of "
                         f"at least {_MIN_INTERPRET if interpret else _MIN_N})")
    bm, bn, bk = blocks
    x2 = x.reshape(m, k)
    pad = (-m) % bm
    if pad:     # ragged M (shape-bucket edges): zero rows quantize to zeros
        x2 = jnp.concatenate(
            [x2, jnp.zeros((pad, k), x2.dtype)], axis=0)
    ws_row = packed["scale"].reshape(1, n).astype(jnp.float32)
    y = _fused_matmul_2d(x2, wq, ws_row, out_dtype, bm, bn, bk, interpret)
    if pad:
        y = y[:m]
    return y.reshape(lead + (n,))


# ----------------------------------------------------------------- fused conv


def _int8_conv_kernel(x_ref, wq_ref, ws_ref, o_ref, acc_scr, *,
                      kw_total: int, wo: int):
    """One (batch, output-row) pair; grid dim 2 folds the KH tap rows.

    Step kh reads input row ``ho + kh`` (via the x BlockSpec index map) and,
    for each of the KW taps of that row, its stride-1 window
    ``[kw : kw+Wo]``. The KW loop is unrolled at trace time so every window
    offset is static: Mosaic refuses a sublane offset it cannot prove a
    multiple of 8, which a tap index read from the grid is not. Each output
    pixel's window row is quantized with its own channel-abs-max scale
    (per-pixel granularity), dotted against the tap's (Cin, Cout) int8 slice
    on the MXU, and accumulated in f32 VMEM."""
    kh = pl.program_id(2)
    nkh = pl.num_programs(2)

    @pl.when(kh == 0)
    def _init():
        acc_scr[:] = jnp.zeros_like(acc_scr)

    for kw in range(kw_total):
        win = x_ref[0, 0, kw:kw + wo, :].astype(jnp.float32)    # (Wo, Cin)
        amax = jnp.max(jnp.abs(win), axis=1, keepdims=True)
        scale = jnp.maximum(amax, 1e-12) * (1.0 / 127.0)        # (Wo, 1)
        xq = jnp.clip(jnp.round(win / scale), -127, 127).astype(jnp.int8)
        part = jax.lax.dot_general(xq, wq_ref[0, kw],
                                   (((1,), (0,)), ((), ())),
                                   preferred_element_type=jnp.int32)
        acc_scr[:] += part.astype(jnp.float32) * scale

    @pl.when(kh == nkh - 1)
    def _finish():
        o_ref[0, 0] = (acc_scr[:] * ws_ref[...]).astype(o_ref.dtype)


def conv_supported(strides, dilation) -> bool:
    """The fused conv covers stride (1, 1) / dilation (1, 1) (the serving
    conv shapes) — the router's test for sending a conv to the lax taps."""
    return tuple(strides) == (1, 1) and tuple(dilation) == (1, 1)


def int8_conv2d_fused(x: jnp.ndarray, packed: Dict[str, Any], *,
                      strides=(1, 1), padding="VALID", dilation=(1, 1),
                      out_dtype=None,
                      interpret: Optional[bool] = None) -> jnp.ndarray:
    """NHWC × HWIO int8 conv with per-pixel activation quantization fused
    into the kernel. Covers :func:`conv_supported` strides and dilations;
    anything else is a ``ValueError`` (the router sends those to the lax
    tap-decomposition in :mod:`ops.int8` — same per-pixel math)."""
    if not conv_supported(strides, dilation):
        raise ValueError(f"int8_conv2d_fused: strides={tuple(strides)} "
                         f"dilation={tuple(dilation)} on x{x.shape}: only "
                         f"(1, 1)/(1, 1) is fused")
    interpret = interpret_default() if interpret is None else interpret
    wq = packed["q"]
    kh, kw, cin, cout = wq.shape
    out_dtype = jnp.float32 if out_dtype is None else out_dtype
    if isinstance(padding, str) and padding.upper() == "SAME":
        pads = jax.lax.padtype_to_pads(x.shape[1:3], (kh, kw), (1, 1),
                                       "SAME")
        x = jnp.pad(x, ((0, 0),) + tuple(pads) + ((0, 0),))
    elif not isinstance(padding, str):
        x = jnp.pad(x, ((0, 0),) + tuple(tuple(p) for p in padding)
                    + ((0, 0),))
    b, h, w, _ = x.shape
    ho, wo = h - kh + 1, w - kw + 1
    if b == 0 or ho <= 0 or wo <= 0:
        raise ValueError(f"int8_conv2d_fused: empty output for padded "
                         f"x{x.shape} and kernel {wq.shape}")
    ws_row = packed["scale"].reshape(1, cout).astype(jnp.float32)
    kernel = functools.partial(_int8_conv_kernel, kw_total=kw, wo=wo)
    y = pl.pallas_call(
        kernel,
        grid=(b, ho, kh),
        in_specs=[
            # one full input row per program; the tap row selects which
            # (block-size-1 ⇒ index == element offset along H)
            pl.BlockSpec((1, 1, w, cin),
                         lambda bi, hi, t: (bi, hi + t, 0, 0)),
            pl.BlockSpec((1, kw, cin, cout),
                         lambda bi, hi, t: (t, 0, 0, 0)),
            pl.BlockSpec((1, cout), lambda bi, hi, t: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, wo, cout),
                               lambda bi, hi, t: (bi, hi, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, ho, wo, cout), out_dtype),
        scratch_shapes=[pltpu.VMEM((wo, cout), jnp.float32)],
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="zoo_int8_conv",
    )(x, wq, ws_row)
    return y
