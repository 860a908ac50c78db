"""Norm layers.

Reference parity: `python/paddle/nn/layer/norm.py` [UNVERIFIED — empty
reference mount].
"""
from __future__ import annotations

from ...core.tensor import Tensor
from .. import functional as F
from .. import initializer as I
from .layers import Layer

__all__ = ["LayerNorm", "BatchNorm", "BatchNorm1D", "BatchNorm2D",
           "BatchNorm3D", "SyncBatchNorm", "GroupNorm", "InstanceNorm1D",
           "InstanceNorm2D", "InstanceNorm3D", "LocalResponseNorm",
           "RMSNorm", "SpectralNorm"]


class LayerNorm(Layer):
    def __init__(self, normalized_shape, epsilon=1e-05, weight_attr=None,
                 bias_attr=None, name=None):
        super().__init__()
        if isinstance(normalized_shape, int):
            normalized_shape = [normalized_shape]
        self._normalized_shape = list(normalized_shape)
        self._epsilon = epsilon
        if weight_attr is False:
            self.weight = None
        else:
            self.weight = self.create_parameter(
                shape=self._normalized_shape, attr=weight_attr,
                default_initializer=I.Constant(1.0))
        if bias_attr is False:
            self.bias = None
        else:
            self.bias = self.create_parameter(
                shape=self._normalized_shape, attr=bias_attr, is_bias=True,
                default_initializer=I.Constant(0.0))

    def forward(self, x):
        return F.layer_norm(x, self._normalized_shape, self.weight,
                            self.bias, self._epsilon)

    def forward_fused(self, x, residual, dropout_p=0.0):
        """layer_norm(dropout(x) + residual) — the post-norm transformer
        sublayer epilogue, with the residual add and, while training,
        the dropout at rate ``dropout_p`` fused into the norm kernel on
        TPU (``layer_norm_residual`` gate)."""
        return F.fused_residual_layer_norm(
            x, residual, self._normalized_shape, self.weight, self.bias,
            self._epsilon, dropout_p=dropout_p, training=self.training)


class RMSNorm(Layer):
    def __init__(self, hidden_size, epsilon=1e-6, weight_attr=None,
                 name=None):
        super().__init__()
        self._epsilon = epsilon
        self.weight = self.create_parameter(
            shape=[hidden_size], attr=weight_attr,
            default_initializer=I.Constant(1.0))

    def forward(self, x):
        return F.rms_norm(x, self.weight, self._epsilon)


class _BatchNormBase(Layer):
    def __init__(self, num_features, momentum=0.9, epsilon=1e-05,
                 weight_attr=None, bias_attr=None, data_format="NCHW",
                 use_global_stats=None, name=None):
        super().__init__()
        self._num_features = num_features
        self._momentum = momentum
        self._epsilon = epsilon
        self._data_format = data_format
        self._use_global_stats = use_global_stats
        if weight_attr is False:
            self.weight = None
        else:
            self.weight = self.create_parameter(
                shape=[num_features], attr=weight_attr,
                default_initializer=I.Constant(1.0))
        if bias_attr is False:
            self.bias = None
        else:
            self.bias = self.create_parameter(
                shape=[num_features], attr=bias_attr, is_bias=True,
                default_initializer=I.Constant(0.0))
        from ...ops.creation import zeros, ones
        self.register_buffer("_mean", zeros([num_features]))
        self.register_buffer("_variance", ones([num_features]))

    def forward(self, x):
        return F.batch_norm(
            x, self._mean, self._variance, self.weight, self.bias,
            training=self.training, momentum=self._momentum,
            epsilon=self._epsilon, data_format=self._data_format,
            use_global_stats=self._use_global_stats)


class BatchNorm(_BatchNormBase):
    def __init__(self, num_channels, act=None, momentum=0.9, epsilon=1e-05,
                 param_attr=None, bias_attr=None, data_layout="NCHW",
                 **kwargs):
        super().__init__(num_channels, momentum, epsilon, param_attr,
                         bias_attr, data_layout)
        self._act = act

    def forward(self, x):
        out = super().forward(x)
        if self._act == "relu":
            out = F.relu(out)
        return out


class BatchNorm1D(_BatchNormBase):
    def __init__(self, num_features, momentum=0.9, epsilon=1e-05,
                 weight_attr=None, bias_attr=None, data_format="NCL",
                 use_global_stats=None, name=None):
        super().__init__(num_features, momentum, epsilon, weight_attr,
                         bias_attr, "NCW" if data_format == "NCL" else
                         data_format, use_global_stats)


class BatchNorm2D(_BatchNormBase):
    pass


class BatchNorm3D(_BatchNormBase):
    def __init__(self, num_features, momentum=0.9, epsilon=1e-05,
                 weight_attr=None, bias_attr=None, data_format="NCDHW",
                 use_global_stats=None, name=None):
        super().__init__(num_features, momentum, epsilon, weight_attr,
                         bias_attr, data_format, use_global_stats)


class SyncBatchNorm(_BatchNormBase):
    """Cross-replica batch norm.

    TPU-native: under pjit/shard_map the batch axis is sharded; stats are
    synced with a psum over the data-parallel mesh axis when inside a
    shard_map region; under plain pjit, XLA's global reduction over the
    sharded batch already yields synced stats (the TPU idiom — no
    ProcessGroup broadcast needed as in reference `sync_batch_norm_op`).
    """

    @classmethod
    def convert_sync_batchnorm(cls, layer):
        out = layer
        if isinstance(layer, _BatchNormBase) and not isinstance(
                layer, SyncBatchNorm):
            out = SyncBatchNorm(layer._num_features, layer._momentum,
                                layer._epsilon, None, None,
                                layer._data_format)
            if layer.weight is not None:
                out.weight.set_value(layer.weight)
            if layer.bias is not None:
                out.bias.set_value(layer.bias)
            out._mean.set_value(layer._mean)
            out._variance.set_value(layer._variance)
        for name, sub in list(layer._sub_layers.items()):
            converted = cls.convert_sync_batchnorm(sub)
            if converted is not sub:
                layer.add_sublayer(name, converted)
        return out


class GroupNorm(Layer):
    def __init__(self, num_groups, num_channels, epsilon=1e-05,
                 weight_attr=None, bias_attr=None, data_format="NCHW",
                 name=None):
        super().__init__()
        self._num_groups = num_groups
        self._epsilon = epsilon
        self._data_format = data_format
        if weight_attr is False:
            self.weight = None
        else:
            self.weight = self.create_parameter(
                shape=[num_channels], attr=weight_attr,
                default_initializer=I.Constant(1.0))
        if bias_attr is False:
            self.bias = None
        else:
            self.bias = self.create_parameter(
                shape=[num_channels], attr=bias_attr, is_bias=True,
                default_initializer=I.Constant(0.0))

    def forward(self, x):
        return F.group_norm(x, self._num_groups, self._epsilon, self.weight,
                            self.bias, self._data_format)


class _InstanceNormBase(Layer):
    def __init__(self, num_features, epsilon=1e-05, momentum=0.9,
                 weight_attr=None, bias_attr=None, data_format="NCHW",
                 name=None):
        super().__init__()
        self._epsilon = epsilon
        self._data_format = data_format
        if weight_attr is False:
            self.weight = None
        else:
            self.weight = self.create_parameter(
                shape=[num_features], attr=weight_attr,
                default_initializer=I.Constant(1.0))
        if bias_attr is False:
            self.bias = None
        else:
            self.bias = self.create_parameter(
                shape=[num_features], attr=bias_attr, is_bias=True,
                default_initializer=I.Constant(0.0))

    def forward(self, x):
        return F.instance_norm(x, weight=self.weight, bias=self.bias,
                               epsilon=self._epsilon,
                               data_format=self._data_format)


class InstanceNorm1D(_InstanceNormBase):
    pass


class InstanceNorm2D(_InstanceNormBase):
    pass


class InstanceNorm3D(_InstanceNormBase):
    pass


class LocalResponseNorm(Layer):
    def __init__(self, size, alpha=1e-4, beta=0.75, k=1.0,
                 data_format="NCHW", name=None):
        super().__init__()
        self.args = (size, alpha, beta, k, data_format)

    def forward(self, x):
        return F.local_response_norm(x, *self.args)


class SpectralNorm(Layer):
    def __init__(self, weight_shape, dim=0, power_iters=1, epsilon=1e-12,
                 dtype="float32"):
        super().__init__()
        self._dim = dim
        self._power_iters = power_iters
        self._epsilon = epsilon
        import numpy as np
        h = weight_shape[dim]
        w = int(np.prod(weight_shape)) // h
        self.weight_u = self.create_parameter(
            shape=[h], default_initializer=I.Normal(0.0, 1.0))
        self.weight_u.stop_gradient = True
        self.weight_v = self.create_parameter(
            shape=[w], default_initializer=I.Normal(0.0, 1.0))
        self.weight_v.stop_gradient = True

    def forward(self, weight):
        import jax.numpy as jnp
        from ...core.dispatch import dispatch

        dim, eps, iters = self._dim, self._epsilon, self._power_iters

        def impl(w, u, v, *, dim, eps, iters):
            perm = [dim] + [i for i in range(w.ndim) if i != dim]
            wm = jnp.transpose(w, perm).reshape(w.shape[dim], -1)
            for _ in range(iters):
                v = wm.T @ u
                v = v / (jnp.linalg.norm(v) + eps)
                u = wm @ v
                u = u / (jnp.linalg.norm(u) + eps)
            sigma = u @ wm @ v
            return w / sigma

        return dispatch("spectral_norm", impl,
                        (weight, self.weight_u, self.weight_v),
                        dict(dim=dim, eps=eps, iters=iters))
