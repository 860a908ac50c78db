"""paddle.incubate.nn: fused op functional parity."""
from . import functional
from .layer import FusedBiasDropoutResidualLayerNorm
from .functional import (fused_linear, fused_feedforward,
                         fused_multi_head_attention, fused_rms_norm,
                         fused_layer_norm, fused_rotary_position_embedding,
                         fused_bias_act, swiglu, top_p_sampling)
