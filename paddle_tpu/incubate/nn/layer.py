"""Fused layers (incubate.nn parity).

Reference parity: `python/paddle/incubate/nn/layer/fused_transformer.py`
[UNVERIFIED — empty reference mount].
"""
from __future__ import annotations

from ...nn import initializer as I
from ...nn.layer.layers import Layer
from . import functional as F

__all__ = ["FusedBiasDropoutResidualLayerNorm"]


class FusedBiasDropoutResidualLayerNorm(Layer):
    """layer_norm(residual + dropout(x + linear_bias)) over the last
    axis of width ``embed_dim``."""

    def __init__(self, embed_dim, dropout_rate=0.5, weight_attr=None,
                 bias_attr=None, epsilon=1e-5, name=None):
        super().__init__()
        if embed_dim <= 0:
            raise ValueError(
                f"embed_dim must be positive, got {embed_dim}")
        self._dropout_rate = dropout_rate
        self._epsilon = epsilon
        self.linear_bias = self.create_parameter(
            shape=[embed_dim], attr=bias_attr, is_bias=True)
        self.ln_scale = self.create_parameter(
            shape=[embed_dim], attr=weight_attr,
            default_initializer=I.Constant(1.0))
        self.ln_bias = self.create_parameter(
            shape=[embed_dim], attr=bias_attr, is_bias=True)

    def forward(self, x, residual):
        return F.fused_bias_dropout_residual_layer_norm(
            x, residual, self.linear_bias, self.ln_scale, self.ln_bias,
            self._dropout_rate, self._epsilon, training=self.training)
