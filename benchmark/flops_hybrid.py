"""Operations and bytes Olmo-Hybrid's algorithms need, from shapes alone: the
counterpart of ``flops.py`` for a configuration whose layers are of two kinds
(``configs/olmo-hybrid-7b.json``; the keys are the published config's).

Kept with the benchmark so that no PR that claims a gain can change the
yardstick. Recomputed operations never count; padding a kernel is handed
counts, because it is in the shape the call was made with.
"""

from __future__ import annotations

from typing import Any, Dict

from benchmark.flops import _dims

F32_BYTES, BF16_BYTES = 4, 2


def layer_kinds(config: Dict[str, Any]):
    """The kinds of the layers that are run: the first ``num_hidden_layers``
    of the published pattern."""
    return config["layer_types"][:config["num_hidden_layers"]]


def n_linear(config) -> int:
    return layer_kinds(config).count("linear_attention")


def n_full(config) -> int:
    return layer_kinds(config).count("full_attention")


def linear_widths(config):
    """(heads, key width, value width) of a linear layer."""
    return (config["linear_num_value_heads"], config["linear_key_head_dim"],
            config["linear_value_head_dim"])


def matmul_params(config: Dict[str, Any]) -> float:
    """Parameters in the matmuls a token passes through: the block matmuls of
    every layer run and the head (not the embedding, a gather; not the
    norms)."""
    d, inner = config["hidden_size"], config["intermediate_size"]
    h, dk, dv = linear_widths(config)
    mlp = 3 * d * inner
    full = 4 * d * d
    linear = d * (2 * h * dk + 2 * h * dv + 2 * h) + h * dv * d
    return (n_full(config) * (full + mlp) + n_linear(config) * (linear + mlp)
            + d * config["vocab_size"])


def state_flops_per_token(config) -> float:
    """The delta rule for one token in one linear layer: S k, the rank-one
    correction, the decay and S q over a ``dv x dk`` state a head: 7 flops an
    element of the state."""
    h, dk, dv = linear_widths(config)
    return 7.0 * h * dv * dk


def serve_flops(config: Dict[str, Any], context_from: int,
                context_to: int) -> float:
    """Forward for the tokens at positions ``context_from`` ...
    ``context_to - 1`` of one sequence, as serving has to compute them once:
    2 x matmul parameters a token, the state update a linear layer, and QK^T
    and PV over the tokens before it, 4 x hidden a cached token a full
    layer."""
    n = context_to - context_from
    attended = (context_to * (context_to + 1)
                - context_from * (context_from + 1)) / 2
    return (n * (2.0 * matmul_params(config)
                 + n_linear(config) * state_flops_per_token(config))
            + 4.0 * config["hidden_size"] * n_full(config) * attended)


def state_bytes_per_slot_step(config) -> float:
    """Recurrent state one live slot's decode step reads and writes, all
    linear layers: 2 x heads x dv x dk float32 a layer."""
    h, dk, dv = linear_widths(config)
    return 2.0 * n_linear(config) * h * dv * dk * F32_BYTES


def kv_bytes_per_token(config) -> float:
    """Keys and values of one cached token, all full layers, bfloat16."""
    return 2.0 * n_full(config) * config["hidden_size"] * BF16_BYTES


def chunk_pass(config, shape: str):
    """(flops, bytes) of one ``zoo_gdn_chunk_fwd`` call whose first output is
    float32 (batch, heads, chunks, chunk, dv): a chunk is U = wv - wk M,
    O = qg M + p U and M = dec M + kd^T U, three products of chunk x dk x dv
    and one of chunk x chunk x dv; it reads wv, wk, qg, kd (chunk x dk or dv
    each), p (chunk x chunk) and writes O, in float32, and the state once a
    head."""
    b, h, n, c, dv = _dims(shape)
    dk = config["linear_key_head_dim"]
    flops = b * h * n * (6.0 * c * dk * dv + 2.0 * c * c * dv)
    nbytes = F32_BYTES * b * h * (n * c * (2 * dv + 3 * dk + c) + dk * dv)
    return flops, nbytes
