"""Transformer layers: MultiHeadAttention, encoder/decoder stacks.

Reference parity: `python/paddle/nn/layer/transformer.py` [UNVERIFIED —
empty reference mount].  Attention dispatches to
F.scaled_dot_product_attention (Pallas flash kernel on TPU).
"""
from __future__ import annotations

import collections

from ...core.tensor import Tensor
from .. import functional as F
from .common import Linear, Dropout
from .norm import LayerNorm
from .layers import Layer, LayerList

__all__ = ["MultiHeadAttention", "TransformerEncoderLayer",
           "TransformerEncoder", "TransformerDecoderLayer",
           "TransformerDecoder", "Transformer"]


def _post_norm(norm, dropout, x, residual):
    """norm(residual + dropout(x)), the post-norm sublayer epilogue.
    A plain Dropout layer rides in the norm kernel as its rate; one over
    an axis, in another mode or in another training state than the norm
    runs first, as its own op."""
    if (dropout.axis is None and dropout.mode == "upscale_in_train"
            and dropout.training == norm.training):
        return norm.forward_fused(x, residual, dropout.p)
    return norm.forward_fused(dropout(x), residual)


class MultiHeadAttention(Layer):
    Cache = collections.namedtuple("Cache", ["k", "v"])
    StaticCache = collections.namedtuple("StaticCache", ["k", "v"])

    def __init__(self, embed_dim, num_heads, dropout=0.0, kdim=None,
                 vdim=None, need_weights=False, weight_attr=None,
                 bias_attr=None):
        super().__init__()
        self.embed_dim = embed_dim
        self.num_heads = num_heads
        self.head_dim = embed_dim // num_heads
        self.dropout = dropout
        self.need_weights = need_weights
        kdim = kdim or embed_dim
        vdim = vdim or embed_dim
        self.q_proj = Linear(embed_dim, embed_dim, weight_attr, bias_attr)
        self.k_proj = Linear(kdim, embed_dim, weight_attr, bias_attr)
        self.v_proj = Linear(vdim, embed_dim, weight_attr, bias_attr)
        self.out_proj = Linear(embed_dim, embed_dim, weight_attr, bias_attr)

    def _shape(self, x):
        from ...ops.manipulation import reshape
        b, s = x.shape[0], x.shape[1]
        return reshape(x, [b, s, self.num_heads, self.head_dim])

    def forward(self, query, key=None, value=None, attn_mask=None,
                cache=None):
        key = query if key is None else key
        value = query if value is None else value
        q = self._shape(self.q_proj(query))
        if isinstance(cache, MultiHeadAttention.StaticCache):
            # pre-projected encoder memory (cross-attention): reuse as
            # is — re-projecting (or concatenating) would be wrong
            k, v = cache.k, cache.v
            new_cache = cache
        else:
            k = self._shape(self.k_proj(key))
            v = self._shape(self.v_proj(value))
            if cache is not None:
                from ...ops.manipulation import concat
                k = concat([cache.k, k], axis=1)
                v = concat([cache.v, v], axis=1)
                new_cache = MultiHeadAttention.Cache(k, v)
        out = F.scaled_dot_product_attention(
            q, k, v, attn_mask=attn_mask,
            dropout_p=self.dropout if self.training else 0.0,
            is_causal=False, training=self.training)
        from ...ops.manipulation import reshape
        b, s = out.shape[0], out.shape[1]
        out = reshape(out, [b, s, self.embed_dim])
        out = self.out_proj(out)
        if cache is not None:
            return out, new_cache
        return out

    def gen_cache(self, key, value=None, type=None):
        """paddle semantics: type=StaticCache projects k/v from the
        memory; the default (Cache) seeds an incremental cache — empty
        when value is None, else Cache(key, value) VERBATIM (resuming
        from previously produced k/v)."""
        if type is MultiHeadAttention.StaticCache:
            value = key if value is None else value
            return MultiHeadAttention.StaticCache(
                self._shape(self.k_proj(key)),
                self._shape(self.v_proj(value)))
        if value is not None:
            return MultiHeadAttention.Cache(key, value)
        from ...ops.creation import zeros
        b = key.shape[0]
        k = zeros([b, 0, self.num_heads, self.head_dim],
                  dtype=key.dtype)
        v = zeros([b, 0, self.num_heads, self.head_dim],
                  dtype=key.dtype)
        return MultiHeadAttention.Cache(k, v)


def _get_activation(name):
    return {"relu": F.relu, "gelu": F.gelu}[name]


class TransformerEncoderLayer(Layer):
    def __init__(self, d_model, nhead, dim_feedforward, dropout=0.1,
                 activation="relu", attn_dropout=None, act_dropout=None,
                 normalize_before=False, weight_attr=None, bias_attr=None,
                 layer_norm_eps=1e-5):
        super().__init__()
        attn_dropout = dropout if attn_dropout is None else attn_dropout
        act_dropout = dropout if act_dropout is None else act_dropout
        self.normalize_before = normalize_before
        self.self_attn = MultiHeadAttention(d_model, nhead, attn_dropout,
                                            weight_attr=weight_attr,
                                            bias_attr=bias_attr)
        self.linear1 = Linear(d_model, dim_feedforward, weight_attr,
                              bias_attr)
        self.dropout = Dropout(act_dropout)
        self.linear2 = Linear(dim_feedforward, d_model, weight_attr,
                              bias_attr)
        self.norm1 = LayerNorm(d_model, layer_norm_eps)
        self.norm2 = LayerNorm(d_model, layer_norm_eps)
        self.dropout1 = Dropout(dropout)
        self.dropout2 = Dropout(dropout)
        self.activation = _get_activation(activation)
        self._act_name = activation

    def _ffn(self, src):
        # bias + activation fold into the first matmul's epilogue on
        # TPU (matmul_epilogue gate); XLA fallback is the composite
        if self.linear1.bias is not None:
            h = F.linear_act(src, self.linear1.weight, self.linear1.bias,
                             act=self._act_name)
        else:
            h = self.activation(self.linear1(src))
        return self.linear2(self.dropout(h))

    def forward(self, src, src_mask=None, cache=None):
        residual = src
        if self.normalize_before:
            src = self.norm1(src)
        if cache is None:
            src = self.self_attn(src, src, src, src_mask)
        else:  # incremental encoding (paddle cache protocol)
            src, new_cache = self.self_attn(src, src, src, src_mask,
                                            cache=cache)
        if self.normalize_before:
            src = residual + self.dropout1(src)
        else:  # post-norm: dropout and residual add fused into the norm
            src = _post_norm(self.norm1, self.dropout1, src, residual)
        residual = src
        if self.normalize_before:
            src = self.norm2(src)
        src = self._ffn(src)
        if self.normalize_before:
            src = residual + self.dropout2(src)
        else:
            src = _post_norm(self.norm2, self.dropout2, src, residual)
        return src if cache is None else (src, new_cache)

    def gen_cache(self, src):
        return self.self_attn.gen_cache(src)


class TransformerEncoder(Layer):
    def __init__(self, encoder_layer, num_layers, norm=None):
        super().__init__()
        import copy
        self.layers = LayerList(
            [encoder_layer if i == 0 else
             _clone_layer(encoder_layer) for i in range(num_layers)])
        self.num_layers = num_layers
        self.norm = norm
        # per-instance recompute opt-in; the memory guard's global
        # remat hook (memory.set_remat) overrides it on OOM degradation
        self.enable_recompute = False

    def forward(self, src, src_mask=None, cache=None):
        from ...memory.guard import remat_enabled
        use_remat = self.enable_recompute or remat_enabled()
        out = src
        new_caches = []
        for i, layer in enumerate(self.layers):
            if cache is None:
                if use_remat:
                    from ...distributed.fleet.recompute import recompute
                    out = recompute(layer, out, src_mask)
                else:
                    out = layer(out, src_mask)
            else:
                out, c = layer(out, src_mask, cache=cache[i])
                new_caches.append(c)
        if self.norm is not None:
            out = self.norm(out)
        if cache is None:
            return out
        return out, new_caches

    def gen_cache(self, src):
        return [layer.gen_cache(src) for layer in self.layers]


def _clone_layer(layer):
    """Structural clone with fresh parameters (paddle deep-copies)."""
    import copy
    new = copy.copy(layer)
    new.__dict__ = dict(layer.__dict__)
    new._parameters = collections.OrderedDict()
    new._sub_layers = collections.OrderedDict()
    new._buffers = collections.OrderedDict()
    for name, sub in layer._sub_layers.items():
        new.add_sublayer(name, _clone_layer(sub))
    for name, p in layer._parameters.items():
        if p is None:
            new.add_parameter(name, None)
            continue
        from .layers import Parameter
        import jax.numpy as jnp
        from ...framework.random import default_generator
        import jax
        # re-initialize: fresh params (matching paddle's deepcopy of spec,
        # though paddle clones values; for stacks, fresh init is standard)
        key = default_generator().next_key()
        newp = Parameter(p._value + 0 * p._value, _internal=True,
                         trainable=p.trainable)
        new.add_parameter(name, newp)
    for name, b in layer._buffers.items():
        from ...core.tensor import Tensor
        new.register_buffer(name, Tensor(b._value, _internal=True))
    return new


class TransformerDecoderLayer(Layer):
    def __init__(self, d_model, nhead, dim_feedforward, dropout=0.1,
                 activation="relu", attn_dropout=None, act_dropout=None,
                 normalize_before=False, weight_attr=None, bias_attr=None,
                 layer_norm_eps=1e-5):
        super().__init__()
        attn_dropout = dropout if attn_dropout is None else attn_dropout
        act_dropout = dropout if act_dropout is None else act_dropout
        self.normalize_before = normalize_before
        self.self_attn = MultiHeadAttention(d_model, nhead, attn_dropout,
                                            weight_attr=weight_attr,
                                            bias_attr=bias_attr)
        self.cross_attn = MultiHeadAttention(d_model, nhead, attn_dropout,
                                             weight_attr=weight_attr,
                                             bias_attr=bias_attr)
        self.linear1 = Linear(d_model, dim_feedforward, weight_attr,
                              bias_attr)
        self.dropout = Dropout(act_dropout)
        self.linear2 = Linear(dim_feedforward, d_model, weight_attr,
                              bias_attr)
        self.norm1 = LayerNorm(d_model, layer_norm_eps)
        self.norm2 = LayerNorm(d_model, layer_norm_eps)
        self.norm3 = LayerNorm(d_model, layer_norm_eps)
        self.dropout1 = Dropout(dropout)
        self.dropout2 = Dropout(dropout)
        self.dropout3 = Dropout(dropout)
        self.activation = _get_activation(activation)
        self._act_name = activation

    _ffn = TransformerEncoderLayer._ffn

    def forward(self, tgt, memory, tgt_mask=None, memory_mask=None,
                cache=None):
        # paddle cache protocol: cache = (incremental Cache for
        # self-attn, StaticCache of projected memory for cross-attn)
        residual = tgt
        if self.normalize_before:
            tgt = self.norm1(tgt)
        if cache is None:
            tgt = self.self_attn(tgt, tgt, tgt, tgt_mask)
        else:
            tgt, inc_cache = self.self_attn(tgt, tgt, tgt, tgt_mask,
                                            cache=cache[0])
        if self.normalize_before:
            tgt = residual + self.dropout1(tgt)
        else:  # post-norm: dropout and residual add fused into the norm
            tgt = _post_norm(self.norm1, self.dropout1, tgt, residual)
        residual = tgt
        if self.normalize_before:
            tgt = self.norm2(tgt)
        if cache is None:
            tgt = self.cross_attn(tgt, memory, memory, memory_mask)
        else:
            tgt, static_cache = self.cross_attn(
                tgt, memory, memory, memory_mask, cache=cache[1])
        if self.normalize_before:
            tgt = residual + self.dropout2(tgt)
        else:
            tgt = _post_norm(self.norm2, self.dropout2, tgt, residual)
        residual = tgt
        if self.normalize_before:
            tgt = self.norm3(tgt)
        tgt = self._ffn(tgt)
        if self.normalize_before:
            tgt = residual + self.dropout3(tgt)
        else:
            tgt = _post_norm(self.norm3, self.dropout3, tgt, residual)
        if cache is None:
            return tgt
        return tgt, (inc_cache, static_cache)

    def gen_cache(self, memory):
        return (self.self_attn.gen_cache(memory),
                self.cross_attn.gen_cache(
                    memory, type=MultiHeadAttention.StaticCache))


class TransformerDecoder(Layer):
    def __init__(self, decoder_layer, num_layers, norm=None):
        super().__init__()
        self.layers = LayerList(
            [decoder_layer if i == 0 else _clone_layer(decoder_layer)
             for i in range(num_layers)])
        self.num_layers = num_layers
        self.norm = norm
        self.enable_recompute = False

    def forward(self, tgt, memory, tgt_mask=None, memory_mask=None,
                cache=None):
        from ...memory.guard import remat_enabled
        use_remat = self.enable_recompute or remat_enabled()
        out = tgt
        new_caches = []
        for i, layer in enumerate(self.layers):
            if cache is None:
                if use_remat:
                    from ...distributed.fleet.recompute import recompute
                    out = recompute(layer, out, memory, tgt_mask,
                                    memory_mask)
                else:
                    out = layer(out, memory, tgt_mask, memory_mask)
            else:
                out, c = layer(out, memory, tgt_mask, memory_mask,
                               cache=cache[i])
                new_caches.append(c)
        if self.norm is not None:
            out = self.norm(out)
        if cache is None:
            return out
        return out, new_caches

    def gen_cache(self, memory, do_zip=False):
        caches = [layer.gen_cache(memory) for layer in self.layers]
        if do_zip:
            return list(zip(*caches))
        return caches


class Transformer(Layer):
    def __init__(self, d_model=512, nhead=8, num_encoder_layers=6,
                 num_decoder_layers=6, dim_feedforward=2048, dropout=0.1,
                 activation="relu", attn_dropout=None, act_dropout=None,
                 normalize_before=False, weight_attr=None, bias_attr=None,
                 custom_encoder=None, custom_decoder=None):
        super().__init__()
        if custom_encoder is not None:
            self.encoder = custom_encoder
        else:
            enc_layer = TransformerEncoderLayer(
                d_model, nhead, dim_feedforward, dropout, activation,
                attn_dropout, act_dropout, normalize_before, weight_attr,
                bias_attr)
            enc_norm = LayerNorm(d_model) if normalize_before else None
            self.encoder = TransformerEncoder(enc_layer, num_encoder_layers,
                                              enc_norm)
        if custom_decoder is not None:
            self.decoder = custom_decoder
        else:
            dec_layer = TransformerDecoderLayer(
                d_model, nhead, dim_feedforward, dropout, activation,
                attn_dropout, act_dropout, normalize_before, weight_attr,
                bias_attr)
            dec_norm = LayerNorm(d_model) if normalize_before else None
            self.decoder = TransformerDecoder(dec_layer, num_decoder_layers,
                                              dec_norm)
        self.d_model = d_model
        self.nhead = nhead

    def forward(self, src, tgt, src_mask=None, tgt_mask=None,
                memory_mask=None):
        memory = self.encoder(src, src_mask)
        return self.decoder(tgt, memory, tgt_mask, memory_mask)

    @staticmethod
    def generate_square_subsequent_mask(length):
        from ...ops.creation import full, triu
        import numpy as np
        from ...core.tensor import to_tensor
        m = np.full((length, length), 0.0, np.float32)
        m[np.triu_indices(length, 1)] = -np.inf
        return to_tensor(m)
