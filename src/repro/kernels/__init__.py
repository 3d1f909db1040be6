"""Pallas TPU kernels for the paper's compute hot spots (quantized MACs).

- ``qmatmul`` — fused dequant(int8/int4-packed) × bf16 matmul with optional
  fused fixed-point requant of the output (``ops.qmatmul`` is the wrapper).
- ``qkv_attention`` — decode attention over an int8-quantized KV cache.

``ref.py`` holds the pure-jnp oracles; kernels are validated in interpret
mode on CPU (TPU v5e is the deployment target).
"""
from .ops import qmatmul, qmatmul_qt
from .qmatmul import qmatmul_pallas, DEFAULT_BLOCKS
from .qkv_attention import qkv_attention_pallas
from .paged_attention import paged_attention_pallas
from .aquant import aquant_pallas

__all__ = ["qmatmul", "qmatmul_qt", "qmatmul_pallas", "qkv_attention_pallas",
           "paged_attention_pallas", "aquant_pallas", "DEFAULT_BLOCKS"]
