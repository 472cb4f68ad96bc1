"""Int8 compute kernels — the OpenVINO-Int8 capability, TPU-native.

The reference's int8 path runs calibrated int8 inference inside OpenVINO
(`OpenVinoInferenceSupportive.scala:32-55`; "up to 2× speedup, 4× model-size
reduction, <0.1% accuracy drop" — docs/docs/wp-bigdl.md:192). On TPU the MXU
multiplies int8 operands natively at twice the bf16 rate: `lax.dot_general`
with int8 inputs and ``preferred_element_type=int32`` compiles to the int8
systolic-array path, no custom kernel needed.

Scheme (AQT-style dynamic quantization):
* weights: symmetric per-output-channel int8, packed once at
  ``InferenceModel.quantize_int8`` time ({"q": int8, "scale": f32[out]});
* activations: symmetric per-row (matmul) / per-pixel (conv) int8, quantized
  dynamically inside the compiled program;
* accumulate in int32, rescale with ``row_scale × channel_scale`` in f32.

Two execution tiers share this scheme:

* **fused** (:mod:`ops.int8_fused`) — pallas kernels that quantize the
  activation tile in VMEM and rescale on the f32 accumulator before
  writeback, so no int8/f32 intermediate ever round-trips HBM. This is the
  TPU dispatch path (the unfused HBM round-trips inverted the raw 1.53×
  matmul win into 0.72× end-to-end through serving).
* **unfused** (this module) — plain lax ops; XLA materializes the quantized
  activations, but every backend runs it. This is the interpreter/CPU
  fallback and the numerics oracle the fused kernels are tested against.

:func:`int8_matmul` / :func:`int8_conv2d` route between the tiers via
``int8_fused.fused_mode()`` (``ZOO_INT8_FUSED`` env; default: fused on TPU,
lax elsewhere) and, per shape, by what the kernels cover
(``int8_fused.resolve_blocks`` / ``conv_supported``). A kernel that was
selected and then cannot run raises; nothing falls back after the fact.
"""

from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

from . import int8_fused


def quantize_weight(w: np.ndarray, axis: int = -1) -> Dict[str, Any]:
    """Symmetric per-channel int8 packing along ``axis`` (the output-channel
    axis: last for (in, out) matmul kernels and HWIO conv kernels)."""
    w = np.asarray(w, np.float32)
    axis = axis % w.ndim
    reduce_axes = tuple(i for i in range(w.ndim) if i != axis)
    amax = np.max(np.abs(w), axis=reduce_axes, keepdims=True)
    scale = np.maximum(amax, 1e-12) / 127.0
    q = np.clip(np.round(w / scale), -127, 127).astype(np.int8)
    return {"q": q, "scale": scale.astype(np.float32)}


def is_quantized(leaf) -> bool:
    return isinstance(leaf, dict) and "q" in leaf and "scale" in leaf


def dequantize(packed) -> jnp.ndarray:
    return packed["q"].astype(jnp.float32) * packed["scale"]


def _quant_activations(x: jnp.ndarray, axes=(-1,)):
    """Dynamic symmetric quantization: one abs-max scale per slice along
    ``axes`` (default: per-row over the contraction dim)."""
    xf = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=axes, keepdims=True)
    xscale = jnp.maximum(amax, 1e-12) / 127.0
    xq = jnp.clip(jnp.round(xf / xscale), -127, 127).astype(jnp.int8)
    return xq, xscale


def int8_matmul_unfused(x: jnp.ndarray, packed: Dict[str, Any]) -> jnp.ndarray:
    """``x @ W`` with the MXU int8 path, quantize/rescale as separate lax
    ops (XLA materializes the int8 activations — see module docstring)."""
    xq, xscale = _quant_activations(x)
    acc = jax.lax.dot_general(
        xq, packed["q"],
        dimension_numbers=(((x.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32)
    # scale: (..., 1) row scales × (1, out)→(out,) channel scales
    ch = packed["scale"].reshape(-1)
    return acc.astype(jnp.float32) * xscale * ch


def int8_matmul(x: jnp.ndarray, packed: Dict[str, Any],
                out_dtype=None) -> jnp.ndarray:
    """``x @ W`` over a ``quantize_weight``-packed (in, out) kernel; returns
    ``x.shape[:-1] + (out,)`` in ``out_dtype`` (default f32).

    Routes to the fused pallas kernel (:func:`int8_fused.int8_matmul_fused`)
    when the mode is on and N and K tile, else the unfused lax path."""
    mode = int8_fused.fused_mode()
    if mode != "off":
        interpret = mode == "interpret"
        k, n = packed["q"].shape
        blocks = int8_fused.resolve_blocks(
            int(np.prod(x.shape[:-1])), n, k, interpret=interpret)
        if blocks is not None:
            bm, bn, bk = blocks
            return int8_fused.int8_matmul_fused(
                x, packed, block_m=bm, block_n=bn, block_k=bk,
                out_dtype=out_dtype, interpret=interpret)
    y = int8_matmul_unfused(x, packed)
    return y.astype(out_dtype) if out_dtype is not None else y


def int8_conv2d_unfused(x: jnp.ndarray, packed: Dict[str, Any], *, strides,
                        padding, dilation=(1, 1)) -> jnp.ndarray:
    """NHWC × HWIO conv on the int8 MXU path, **per-pixel** activation
    scales (one abs-max over channels per (n, h, w) pixel).

    A single ``lax.conv`` cannot rescale per-pixel post-hoc (each output
    pixel mixes window pixels with different scales), so the conv is
    decomposed into its KH·KW taps: per tap, a shifted/strided slice of the
    quantized input contracts with the tap's (Cin, Cout) int8 weight slice
    on the MXU, and the int32 partial is rescaled by that slice's own pixel
    scales before the f32 accumulate — identical math to the fused kernel
    (which folds the taps into its grid), and strictly finer granularity
    than the old per-image scheme that lost accuracy on high-dynamic-range
    inputs. XLA fuses the tap loop into one program under jit.
    """
    kh, kw, _cin, _cout = packed["q"].shape
    sh, sw = tuple(strides)
    dh, dw = tuple(dilation)
    xq, xscale = _quant_activations(x, axes=(3,))        # per-pixel scales
    if isinstance(padding, str):
        eff = ((kh - 1) * dh + 1, (kw - 1) * dw + 1)
        pads = jax.lax.padtype_to_pads(x.shape[1:3], eff, (sh, sw),
                                       padding.upper())
    else:
        pads = tuple(tuple(p) for p in padding)
    full = ((0, 0),) + tuple(pads) + ((0, 0),)
    # padded zeros contribute nothing regardless of scale; pad scales with 1
    # so the rescale multiply never sees a 0-scale
    xq = jnp.pad(xq, full)
    xscale = jnp.pad(xscale, full, constant_values=1.0)
    h, w = xq.shape[1:3]
    ho = (h - ((kh - 1) * dh + 1)) // sh + 1
    wo = (w - ((kw - 1) * dw + 1)) // sw + 1
    ch = packed["scale"].reshape(-1)
    acc = jnp.zeros(x.shape[:1] + (ho, wo) + ch.shape, jnp.float32)
    for i in range(kh):
        for j in range(kw):
            lo = (0, i * dh, j * dw, 0)
            hi = (x.shape[0], i * dh + (ho - 1) * sh + 1,
                  j * dw + (wo - 1) * sw + 1, xq.shape[3])
            x_tap = jax.lax.slice(xq, lo, hi, (1, sh, sw, 1))
            s_tap = jax.lax.slice(xscale, lo, hi[:3] + (1,), (1, sh, sw, 1))
            part = jax.lax.dot_general(
                x_tap, packed["q"][i, j],
                dimension_numbers=(((3,), (0,)), ((), ())),
                preferred_element_type=jnp.int32)
            acc = acc + part.astype(jnp.float32) * s_tap
    return acc * ch


def int8_conv2d(x: jnp.ndarray, packed: Dict[str, Any], *, strides, padding,
                dilation=(1, 1), out_dtype=None) -> jnp.ndarray:
    """NHWC × HWIO conv on the int8 MXU path; per-output-channel weight
    scales × per-pixel activation scales.

    Routes to the fused pallas kernel (:func:`int8_fused.int8_conv2d_fused`)
    when the mode is on and the kernel covers the strides and dilation, else
    the unfused tap-decomposed lax path — both compute the same per-pixel
    scheme."""
    mode = int8_fused.fused_mode()
    if (mode != "off" and x.shape[0]
            and int8_fused.conv_supported(strides, dilation)):
        return int8_fused.int8_conv2d_fused(
            x, packed, strides=strides, padding=padding, dilation=dilation,
            out_dtype=out_dtype, interpret=(mode == "interpret"))
    y = int8_conv2d_unfused(x, packed, strides=strides, padding=padding,
                            dilation=dilation)
    return y.astype(out_dtype) if out_dtype is not None else y
