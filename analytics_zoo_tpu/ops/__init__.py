"""TPU compute ops: attention strategies (full/ring/zigzag/Ulysses), pallas
kernels (flash attention, paged attention, fused-quantization int8)."""

from .attention import (full_attention, ring_attention_local, sharded_attention,
                        ulysses_attention_local, zigzag_permutation,
                        zigzag_ring_attention_local)
from .int8 import (int8_conv2d, int8_matmul, is_quantized, quantize_weight)
from .int8_fused import (fused_mode, int8_conv2d_fused, int8_matmul_fused)

__all__ = ["full_attention", "ring_attention_local", "sharded_attention",
           "ulysses_attention_local", "zigzag_permutation",
           "zigzag_ring_attention_local",
           "int8_matmul", "int8_conv2d", "int8_matmul_fused",
           "int8_conv2d_fused", "fused_mode", "is_quantized",
           "quantize_weight"]
