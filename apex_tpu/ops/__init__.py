"""apex_tpu.ops — fused NN ops (Pallas/XLA).

TPU-native replacements for the reference's fused CUDA op layer
(SURVEY.md §2.6): ``fused_layer_norm_cuda`` / ``fast_layer_norm``,
``scaled_(upper_triang_)masked_softmax_cuda``, ``xentropy_cuda``,
``fused_dense_cuda``, ``mlp_cuda``.  Each module documents the exact
reference contract it mirrors.
"""

from apex_tpu.ops.attention import (  # noqa: F401
    flash_attention,
    flash_attention_qkv,
    flash_attention_qkv_route,
    flash_attention_route,
    flash_attention_varlen,
    flash_decode,
    flash_decode_latent,
    flash_decode_latent_route,
    flash_decode_route,
    latent_walk_tiles,
    ring_attention,
    routing_override,
)
from apex_tpu.ops.fused_dense import (  # noqa: F401
    FusedDense,
    FusedDenseGeluDense,
    fused_dense,
    fused_dense_gelu_dense,
)
from apex_tpu.ops.fused_layer_norm import (  # noqa: F401
    FastLayerNorm,
    FusedLayerNorm,
    MixedFusedLayerNorm,
    fast_layer_norm,
    layer_norm,
    rms_norm,
)
from apex_tpu.ops.fused_softmax import (  # noqa: F401
    AttnMaskType,
    FusedScaleMaskSoftmax,
    scaled_masked_softmax,
    scaled_softmax,
    scaled_upper_triang_masked_softmax,
)
from apex_tpu.ops.mlp import MLP, mlp  # noqa: F401
from apex_tpu.ops.ssm import (  # noqa: F401
    causal_conv,
    short_conv_update,
    ssd_chunk_scan,
    ssm_decode_route,
    ssm_decode_update,
)
from apex_tpu.ops.fused_linear_xent import (  # noqa: F401
    fused_linear_cross_entropy,
)
from apex_tpu.ops.xentropy import (  # noqa: F401
    SoftmaxCrossEntropyLoss,
    softmax_cross_entropy_loss,
)
