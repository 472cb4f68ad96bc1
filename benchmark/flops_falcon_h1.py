"""Operations and bytes Falcon-H1's algorithms need, from shapes alone: the
counterpart of ``flops.py`` for a configuration whose every layer runs a
grouped-query attention and a Mamba-2 mixer side by side
(``configs/falcon-h1-34b.json``; the keys are the published config's).

Kept with the benchmark so that no PR that claims a gain can change the
yardstick. Recomputed operations never count; what is counted is what the
algorithm must do and move, not what a kernel does and moves.
"""

from __future__ import annotations

from typing import Any, Dict

from benchmark.flops import _dims

F32_BYTES, BF16_BYTES = 4, 2


def n_layers(config: Dict[str, Any]) -> int:
    return config["num_hidden_layers"]


def ssm_widths(config):
    """(heads, head width, state width, groups) of the state-space mixer."""
    return (config["mamba_n_heads"], config["mamba_d_head"],
            config["mamba_d_state"], config["mamba_n_groups"])


def matmul_params(config: Dict[str, Any]) -> float:
    """Parameters in the matmuls a token passes through: the block matmuls of
    every layer run and the head (not the embedding, a gather; not the norms,
    the convolution or the scalars)."""
    d, inner = config["hidden_size"], config["intermediate_size"]
    q = config["num_attention_heads"] * config["head_dim"]
    kv = config["num_key_value_heads"] * config["head_dim"]
    h, p, n, g = ssm_widths(config)
    attention = d * (q + 2 * kv) + q * d
    mixer = d * (2 * h * p + 2 * g * n + h) + h * p * d
    return (n_layers(config) * (attention + mixer + 3 * d * inner)
            + d * config["vocab_size"])


def state_flops_per_token(config) -> float:
    """The recurrence for one token in one layer: the decay, the rank-one
    update (a multiply and an add) and the read ``S C`` (a multiply and an
    add) over a ``head width x state width`` state a head: 5 flops an element
    of the state."""
    h, p, n, _ = ssm_widths(config)
    return 5.0 * h * p * n


def serve_flops(config: Dict[str, Any], context_from: int,
                context_to: int) -> float:
    """Forward for the tokens at positions ``context_from`` ...
    ``context_to - 1`` of one sequence, as serving has to compute them once:
    2 x matmul parameters a token, the state update a layer, and QK^T and PV
    over the tokens before it at the QUERY heads' width (20 x 128), 4 flops a
    cached token a query channel a layer."""
    n = context_to - context_from
    attended = (context_to * (context_to + 1)
                - context_from * (context_from + 1)) / 2
    q = config["num_attention_heads"] * config["head_dim"]
    return (n * (2.0 * matmul_params(config)
                 + n_layers(config) * state_flops_per_token(config))
            + 4.0 * q * n_layers(config) * attended)


def state_bytes_per_slot_step(config) -> float:
    """State one live slot's decode step reads and writes, all layers: 2 x
    heads x head width x state width float32 a layer."""
    h, p, n, _ = ssm_widths(config)
    return 2.0 * n_layers(config) * h * p * n * F32_BYTES


def kv_bytes_per_token(config) -> float:
    """Keys and values of one cached token, all layers, at the KV heads'
    width (4 x 128), bfloat16."""
    return (2.0 * n_layers(config) * config["num_key_value_heads"]
            * config["head_dim"] * BF16_BYTES)


def chunk_pass(config, shape: str):
    """(flops, bytes) of one ``zoo_ssd_chunk_fwd`` call whose first output is
    float32 (batch, heads, chunks, chunk, head width): a chunk a head is
    ``C B^T`` (2 c^2 N), ``(L * C B^T) (dt x)`` (2 c^2 P), ``C M`` and ``B^T
    (w dt x)`` (2 c N P each); it has to read ``dt x`` (c x P) and the decays
    (c x c) a head, ``B`` and ``C`` (c x N each) once a group, and to write
    the outputs (c x P) and once a head the state (N x P), in float32."""
    b, h, chunks, c, p = _dims(shape)
    _, _, n, g = ssm_widths(config)
    flops = b * h * chunks * (2.0 * c * c * (n + p) + 4.0 * c * n * p)
    nbytes = F32_BYTES * b * (h * (chunks * c * (2 * p + c) + n * p)
                              + g * chunks * 2 * c * n)
    return flops, nbytes
