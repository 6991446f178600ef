"""apex_tpu.ops — the kernel layer.

TPU-native replacement for the reference's ``csrc/`` CUDA kernel tier
(SURVEY.md §2.2): every op is a jittable function with a Pallas TPU fast
path and a pure-XLA fallback sharing one ``custom_vjp``, so numerics are
identical across backends (the reference's L1 "ext vs python path"
bitwise test philosophy, reference: tests/L1/common/run_test.sh:118-137).
"""

from apex_tpu.ops.layer_norm import (  # noqa: F401
    fused_layer_norm,
    fused_layer_norm_affine,
    fused_rms_norm,
    fused_rms_norm_affine,
    mixed_dtype_fused_layer_norm_affine,
)
from apex_tpu.ops.softmax import (  # noqa: F401
    scaled_softmax,
    scaled_masked_softmax,
    scaled_upper_triang_masked_softmax,
)
from apex_tpu.ops.attention import (  # noqa: F401
    flash_attention,
    mha_reference,
)
from apex_tpu.ops.attention_short import (  # noqa: F401
    fmha_short,
)
from apex_tpu.ops.attention_latent import (  # noqa: F401
    mla_absorbed,
    mla_expanded,
)
from apex_tpu.ops.sparse_index import (  # noqa: F401
    index_scores,
    topk_indices,
    topk_mask,
)
from apex_tpu.ops.attention_mid import (  # noqa: F401
    fmha_mid,
)
from apex_tpu.ops.quantization import (  # noqa: F401
    CompressionConfig,
    dequantize_blockwise,
    quantize_blockwise,
    quantized_psum,
)
from apex_tpu.ops.dequant_matmul import (  # noqa: F401
    dequant_matmul,
    quantize_weight,
)

__all__ = [
    "CompressionConfig",
    "dequantize_blockwise",
    "quantize_blockwise",
    "quantized_psum",
    "dequant_matmul",
    "quantize_weight",
    "fmha_mid",
    "fmha_short",
    "index_scores",
    "mla_absorbed",
    "mla_expanded",
    "topk_indices",
    "topk_mask",
    "fused_layer_norm",
    "fused_layer_norm_affine",
    "fused_rms_norm",
    "fused_rms_norm_affine",
    "mixed_dtype_fused_layer_norm_affine",
    "scaled_softmax",
    "scaled_masked_softmax",
    "scaled_upper_triang_masked_softmax",
    "flash_attention",
    "mha_reference",
]
