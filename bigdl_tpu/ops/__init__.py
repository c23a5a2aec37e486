"""Custom TPU kernels (Pallas/Mosaic) — the native-acceleration layer.

This package is the TPU-native counterpart of the reference's ``bigdl-core``
JNI libraries (SURVEY.md §2.6: MKL gemm/vml, MKL-DNN primitives): where BigDL
ships hand-tuned C/C++ kernels behind JNI, this framework ships Pallas kernels
that compile through Mosaic to TPU machine code. XLA fusion covers most of what
MKL-DNN's primitive zoo provided; kernels live here only where a hand schedule
beats the compiler (flash attention's O(T) memory online softmax).
"""

from .flash_attention import flash_attention
from .fused_epilogue import fused_bias_act
from .fused_norm import fused_layer_norm, fused_rms_norm

__all__ = [
    "flash_attention",
    "fused_bias_act",
    "fused_layer_norm",
    "fused_rms_norm",
]
