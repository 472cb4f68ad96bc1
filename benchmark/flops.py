"""Operations and bytes the algorithms need, from shapes alone.

Kept with the benchmark so that no PR that claims a gain can change the
yardstick. Recomputed operations never count; padding a kernel is handed
counts, because it is in the shape the call was made with.
"""

from __future__ import annotations

import re
from typing import Any, Dict

_DIMS = re.compile(r"\[([\d,]+)\]")
_BYTES = {"bf16": 2, "f16": 2, "f32": 4}


def _dims(shape: str):
    return [int(d) for d in _DIMS.search(shape).group(1).split(",")]


def train_flops_per_token(config: Dict[str, Any], seq_len: int) -> float:
    """Forward plus backward of the GPT-2 block stack and head, per token:
    6 x (parameters in the block matmuls and the head) plus causal attention
    (this file is the formula's home: the pre-chip ``bench.py`` it was copied
    from was deleted in PR 28; the MLP width is the published one)."""
    h, layers = config["n_embd"], config["n_layer"]
    block = 4 * h * h + 2 * h * config["n_inner"]
    return (6.0 * block * layers + 6.0 * layers * seq_len * h
            + 6.0 * h * config["vocab_size"])


def serve_flops(config: Dict[str, Any], context_from: int,
                context_to: int) -> float:
    """Forward of the block stack and head for the tokens at positions
    ``context_from`` ... ``context_to - 1`` of one sequence, as serving has to
    compute them once (a prefill, or decode steps): 2 x (parameters in the
    block matmuls and the head) a token, plus QK^T and PV over the tokens
    before it, 4 x hidden a cached token a layer."""
    h, layers = config["n_embd"], config["n_layer"]
    block = 4 * h * h + 2 * h * config["n_inner"]
    n = context_to - context_from
    attended = (context_to * (context_to + 1)
                - context_from * (context_from + 1)) / 2
    return (n * (2.0 * block * layers + 2.0 * h * config["vocab_size"])
            + 4.0 * h * layers * attended)


def flash_fwd_flops(shape: str) -> float:
    """One ``zoo_flash_fwd`` call whose first output is (batch x heads, T,
    head_dim): QK^T and PV, 2 flops a multiply-add, halved by causality."""
    bh, t, d = _dims(shape)
    return 4.0 * bh * t * t * d / 2


def flash_bwd_flops(shape: str) -> float:
    """Backward of the same call, counted once for the ``dq`` kernel's event
    and covering both kernels: S recomputed, dP, dV, dK and dQ are five
    matmuls of the forward's two (the second recomputation of S and dP in the
    ``dkv`` kernel is not required work)."""
    bh, t, d = _dims(shape)
    return 10.0 * bh * t * t * d / 2


def kv_bytes_per_token(config: Dict[str, Any], dtype: str = "bf16") -> float:
    """Keys and values of one cached token in one layer."""
    return 2.0 * config["n_embd"] * _BYTES[dtype]
