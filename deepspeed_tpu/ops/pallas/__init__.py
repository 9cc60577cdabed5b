"""Pallas TPU kernels (reference analogue: ``csrc/`` CUDA kernels)."""

from .flash_attention import flash_attention
from .gelu import bias_gelu, gelu
from .layer_norm import layer_norm
from .softmax import fused_softmax, masked_softmax
from ._utils import KernelUnsupported
