"""Qwen3-Next: Gated DeltaNet layers and gated softmax attention layers
mixed, routed experts beside a gated shared expert (``model_type:
qwen3_next``).

Built from the published config's own keys:

  * every ``full_attention_interval``-th layer is **gated attention**:
    ``q_proj`` gives each head its query and an output gate of the same
    width, q and k pass a zero-centred RMSNorm over the head's lanes,
    rotate-half RoPE turns the first ``partial_rotary_factor`` of them,
    grouped KV heads, ``o_proj(attn * sigmoid(gate))``;
  * the others are **Gated DeltaNet** (arXiv:2412.06464): ``[q; k; v]``
    through a causal depthwise convolution (``linear_conv_kernel_dim``
    taps) and a SiLU, ``beta = sigmoid(b)``, ``g = -exp(A_log)
    softplus(a + dt_bias)``, the gated delta rule on a float32 state a
    value head (``ops/pallas_gated_delta.py``), an RMSNorm over each
    head's output times ``silu(z)``, ``out_proj``;
  * every layer's FFN routes each token to ``num_experts_per_tok`` of
    ``num_experts`` SwiGLU experts by a softmax router with renormalised
    weights, beside one shared expert scaled by ``sigmoid(x w_s)``;
  * RMSNorms are zero-centred (``1 + w``) but the delta layers' output
    norm; two a block, pre-norm; an untied head.

``expert_shard = (chips, index)`` is this chip's share of a deployment
in which ``chips`` chips share each layer: it holds experts ``[index E /
chips, (index + 1) E / chips)`` of every layer, routes over all ``E``
and computes the part of the result that its own experts give, with the
shared expert whole.  What the absent experts would add is left out: on
one chip the layer runs without its exchange.

The routed experts' weights are stored as the grouped kernel reads them
(``ops/pallas_grouped.py``): ``[held, hidden, 2 * width]`` and ``[held,
width, hidden]``.

With no cache the model is the dense forward (a whole prompt at once).
With the engine's cache view an attention layer asks its layer cache
``cache.attend(q, k, v)`` and a delta layer ``cache.delta_update(...)``;
``cache_spec()`` tells the engine which kind of state each layer keeps.
"""
from __future__ import annotations

import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from .. import nn
from ..core.dispatch import dispatch
from ..nn import functional as F
from ..nn import initializer as I
from ..observability import block
from ..ops import pallas_gated_delta as pgd
from .afmoe import AfmoeMLP, _dense_attention_impl, _linear, _normal
from .generation import GenerationMixin


@dataclass
class Qwen3NextConfig:
    vocab_size: int = 151936
    hidden_size: int = 2048
    num_hidden_layers: int = 48
    full_attention_interval: int = 4
    num_attention_heads: int = 16
    num_key_value_heads: int = 2
    head_dim: int = 256
    partial_rotary_factor: float = 0.25
    rope_theta: float = 10000000.0
    linear_conv_kernel_dim: int = 4
    linear_key_head_dim: int = 128
    linear_num_key_heads: int = 16
    linear_num_value_heads: int = 32
    linear_value_head_dim: int = 128
    moe_intermediate_size: int = 512
    shared_expert_intermediate_size: int = 512
    #: experts the router chooses among (the published count)
    num_experts: int = 512
    num_experts_per_tok: int = 10
    norm_topk_prob: bool = True
    #: ``(chips, index)``: the chips that share a layer and which of
    #: them this is; it holds ``num_experts / chips`` experts a layer
    expert_shard: tuple = (1, 0)
    max_position_embeddings: int = 262144
    rms_norm_eps: float = 1e-6
    #: std of every projection's and table's normal draw
    initializer_range: float = 0.02
    #: std of the zero-centred norm weights' draw (zeros in a fresh one)
    norm_weight_std: float = 0.0
    #: ``A_log = log U(a_range)``; ``softplus(dt_bias)`` log-uniform in
    #: ``dt_range`` (the convention of Mamba-2's reference code)
    a_range: tuple = (1.0, 16.0)
    dt_range: tuple = (0.001, 0.1)
    #: tokens a KV block holds in the serving cache
    kv_block_size: int = 64
    #: the type the parameters are held in; each layer is cast as it is
    #: built (``minicpm_sala.py``)
    dtype: str = "float32"

    def __post_init__(self):
        chips, index = self.expert_shard = tuple(self.expert_shard)
        if self.num_experts % chips or not 0 <= index < chips:
            raise ValueError(f"{self.num_experts} experts over {chips} "
                             f"chips, of which this is number {index}")
        if self.linear_num_value_heads % self.linear_num_key_heads:
            raise ValueError("each key head serves a whole number of "
                             "value heads")

    @property
    def held_experts(self):
        """``(lo, hi)``: the experts of each layer that live here."""
        chips, index = self.expert_shard
        share = self.num_experts // chips
        return index * share, (index + 1) * share

    @property
    def rotary_dim(self):
        return int(self.head_dim * self.partial_rotary_factor)

    def is_attention(self, index):
        return (index + 1) % self.full_attention_interval == 0


class _LogUniform(I.Initializer):
    """``transform(exp(U(log lo, log hi)))`` in float32."""

    def __init__(self, lo, hi, transform):
        self.lo, self.hi, self.transform = lo, hi, transform

    def generate(self, shape, dtype):
        from ..framework.random import default_generator
        x = jnp.exp(jax.random.uniform(
            default_generator().next_key(), shape, jnp.float32,
            math.log(self.lo), math.log(self.hi)))
        return self.transform(x).astype(dtype)


def _softplus_inverse(x):
    return x + jnp.log(-jnp.expm1(-x))


# ---------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------
def _zero_centred_norm_impl(x, w, *, eps):
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt((xf * xf).mean(-1, keepdims=True) + eps)
    return (y * (1.0 + w.astype(jnp.float32))).astype(x.dtype)


class Qwen3NextRMSNorm(nn.Layer):
    """``x rsqrt(mean(x^2) + eps) (1 + w)`` in float32."""

    def __init__(self, cfg: Qwen3NextConfig, size):
        super().__init__()
        self._eps = cfg.rms_norm_eps
        self.weight = self.create_parameter(
            shape=[size], default_initializer=I.Normal(
                0.0, cfg.norm_weight_std))

    def forward(self, x):
        return dispatch("zero_centred_rms_norm", _zero_centred_norm_impl,
                        (x, self.weight), dict(eps=float(self._eps)),
                        differentiable=False)


def _gated_norm_impl(o, z, w, *, eps):
    of = o.astype(jnp.float32)
    y = of * jax.lax.rsqrt((of * of).mean(-1, keepdims=True) + eps)
    y = y * w.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
    return y.astype(o.dtype)


# ---------------------------------------------------------------------
# gated attention
# ---------------------------------------------------------------------
def _partial_rope_impl(q, k, positions, *, rotary_dim, theta):
    """Rotate-half RoPE on lanes ``[0, rotary_dim)`` of ``q`` and ``k``
    [b, s, heads, d] at absolute ``positions``; the other lanes pass."""
    half = rotary_dim // 2
    inv = 1.0 / theta ** (jnp.arange(0, rotary_dim, 2, dtype=jnp.float32)
                          / rotary_dim)
    ang = positions.reshape(-1).astype(jnp.float32)[:, None] * inv[None, :]
    cos = jnp.cos(ang)[None, :, None, :]
    sin = jnp.sin(ang)[None, :, None, :]

    def turn(x):
        xf = x.astype(jnp.float32)
        a, b = xf[..., :half], xf[..., half:rotary_dim]
        return jnp.concatenate(
            [a * cos - b * sin, b * cos + a * sin, xf[..., rotary_dim:]],
            -1).astype(x.dtype)

    return turn(q), turn(k)


class Qwen3NextAttention(nn.Layer):
    def __init__(self, cfg: Qwen3NextConfig):
        super().__init__()
        self.num_heads, self.head_dim = cfg.num_attention_heads, cfg.head_dim
        self.num_kv_heads = cfg.num_key_value_heads
        self.rotary_dim, self.theta = cfg.rotary_dim, cfg.rope_theta
        inner = self.num_heads * self.head_dim
        kv = self.num_kv_heads * self.head_dim
        # a head's query and its output gate side by side
        self.q_proj = _linear(cfg, cfg.hidden_size, 2 * inner)
        self.k_proj = _linear(cfg, cfg.hidden_size, kv)
        self.v_proj = _linear(cfg, cfg.hidden_size, kv)
        self.o_proj = _linear(cfg, inner, cfg.hidden_size)
        self.q_norm = Qwen3NextRMSNorm(cfg, self.head_dim)
        self.k_norm = Qwen3NextRMSNorm(cfg, self.head_dim)

    def forward(self, u, positions, cache=None):
        b, s, _ = u.shape
        q, gate = paddle.split(paddle.reshape(
            self.q_proj(u), [b, s, self.num_heads, 2 * self.head_dim]),
            2, axis=-1)
        q = self.q_norm(q)
        k = self.k_norm(paddle.reshape(
            self.k_proj(u), [b, s, self.num_kv_heads, self.head_dim]))
        v = paddle.reshape(self.v_proj(u),
                           [b, s, self.num_kv_heads, self.head_dim])
        q, k = dispatch("partial_rope", _partial_rope_impl,
                        (q, k, positions),
                        dict(rotary_dim=self.rotary_dim,
                             theta=float(self.theta)),
                        differentiable=False)
        if cache is not None:
            o = cache.attend(q, k, v)
        else:
            o = dispatch("qwen3_next_attention_dense",
                         _dense_attention_impl, (q, k, v),
                         dict(window=None), differentiable=False)
        o = paddle.reshape(o, [b, s, -1])
        return self.o_proj(o * F.sigmoid(paddle.reshape(gate, [b, s, -1])))


# ---------------------------------------------------------------------
# Gated DeltaNet
# ---------------------------------------------------------------------
def _decay_and_beta_impl(ba, a_log, dt_bias):
    """``(g, beta)`` float32 [.., Hv] from ``in_proj_ba``'s output
    (``b`` then ``a``)."""
    b, a = jnp.split(ba.astype(jnp.float32), 2, -1)
    g = -jnp.exp(a_log.astype(jnp.float32)) * jax.nn.softplus(
        a + dt_bias.astype(jnp.float32))
    return g, jax.nn.sigmoid(b)


def _delta_dense_impl(x, g, beta, conv_w, *, key_heads, value_heads,
                      key_dim, value_dim):
    """No cache: every sequence of ``x`` [b, s, channels] convolves
    after nothing and runs the rule from a zero state."""
    prev = jnp.zeros((conv_w.shape[0] - 1, x.shape[-1]), x.dtype)
    y = jax.vmap(lambda row: pgd.causal_conv(row, prev, conv_w)[0])(x)
    return pgd.gated_delta_dense(*pgd.split_heads(
        y, key_heads, value_heads, key_dim, value_dim), g, beta)


class GatedDeltaNet(nn.Layer):
    def __init__(self, cfg: Qwen3NextConfig):
        super().__init__()
        self.dims = dict(key_heads=cfg.linear_num_key_heads,
                         value_heads=cfg.linear_num_value_heads,
                         key_dim=cfg.linear_key_head_dim,
                         value_dim=cfg.linear_value_head_dim)
        hv, self._eps = cfg.linear_num_value_heads, cfg.rms_norm_eps
        keys = cfg.linear_num_key_heads * cfg.linear_key_head_dim
        values = hv * cfg.linear_value_head_dim
        # the published fused in_proj_qkvz, up to a permutation of its
        # columns: [q; k; v] (what the convolution takes) apart from z
        self.in_proj_qkv = _linear(cfg, cfg.hidden_size, 2 * keys + values)
        self.in_proj_z = _linear(cfg, cfg.hidden_size, values)
        self.in_proj_ba = _linear(cfg, cfg.hidden_size, 2 * hv)
        self.conv_weight = self.create_parameter(
            shape=[cfg.linear_conv_kernel_dim, 2 * keys + values],
            default_initializer=_normal(cfg))
        self.A_log = self.create_parameter(
            shape=[hv], default_initializer=_LogUniform(*cfg.a_range,
                                                        jnp.log))
        self.dt_bias = self.create_parameter(
            shape=[hv], default_initializer=_LogUniform(
                *cfg.dt_range, _softplus_inverse))
        self.norm_weight = self.create_parameter(
            shape=[cfg.linear_value_head_dim],
            default_initializer=I.Normal(1.0, cfg.norm_weight_std))
        self.out_proj = _linear(cfg, values, cfg.hidden_size)

    def forward(self, u, positions, cache=None):
        b, s, _ = u.shape
        x = self.in_proj_qkv(u)
        g, beta = dispatch("gated_delta_decay", _decay_and_beta_impl,
                           (self.in_proj_ba(u), self.A_log, self.dt_bias),
                           {}, differentiable=False)
        if cache is not None:
            o = cache.delta_update(x, g, beta, self.conv_weight,
                                   **self.dims)
        else:
            o = dispatch("gated_delta_dense", _delta_dense_impl,
                         (x, g, beta, self.conv_weight), self.dims,
                         differentiable=False)
        z = paddle.reshape(self.in_proj_z(u), o.shape)
        o = dispatch("gated_rms_norm", _gated_norm_impl,
                     (o, z, self.norm_weight), dict(eps=float(self._eps)),
                     differentiable=False)
        return self.out_proj(paddle.reshape(o, [b, s, -1]))


# ---------------------------------------------------------------------
# the expert layer
# ---------------------------------------------------------------------
def _routed_impl(x, router, gate_up, down, carried, *, top_k, norm_topk,
                 held, use_pallas):
    """Router over all the experts, plan of the held ones, grouped
    kernel, combine, on flat tokens ``x`` [T, D]
    (``distributed/auto_parallel/moe_dispatch.py``); the router in
    float32.  Returns ``(y, plan counters)``."""
    from ..distributed.auto_parallel import moe_dispatch as md
    logits = jnp.dot(x.astype(jnp.float32), router.astype(jnp.float32),
                     precision="highest")
    idx, weight = md.softmax_topk_router(logits, top_k, norm_topk)
    return md.gated_experts(x, idx, weight, gate_up, down, carried,
                            use_pallas=use_pallas, held=held)


class Qwen3NextExperts(nn.Layer):
    """The held experts' two stacks, as the grouped kernel reads them."""

    def __init__(self, cfg: Qwen3NextConfig):
        super().__init__()
        lo, hi = cfg.held_experts
        D, W = cfg.hidden_size, cfg.moe_intermediate_size
        # in the served type from the start: no float32 copy of a stack
        self.gate_up = self.create_parameter(
            shape=[hi - lo, D, 2 * W], dtype=cfg.dtype,
            default_initializer=_normal(cfg))
        self.down = self.create_parameter(
            shape=[hi - lo, W, D], dtype=cfg.dtype,
            default_initializer=_normal(cfg))


class Qwen3NextMoE(nn.Layer):
    def __init__(self, cfg: Qwen3NextConfig):
        super().__init__()
        self.top_k, self.norm_topk = (cfg.num_experts_per_tok,
                                      cfg.norm_topk_prob)
        self.held = cfg.held_experts
        # N(0, 1/hidden): unit-variance logits under a unit-RMS input
        self.router = nn.Linear(
            cfg.hidden_size, cfg.num_experts, bias_attr=False,
            weight_attr=_normal(cfg, cfg.hidden_size ** -0.5))
        self.experts = Qwen3NextExperts(cfg)
        self.shared_expert = AfmoeMLP(
            cfg, cfg.shared_expert_intermediate_size)       # a SwiGLU
        self.shared_expert_gate = _linear(cfg, cfg.hidden_size, 1)

    def forward(self, x, cache=None):
        from ..ops.pallas_gate import pallas_enabled
        shape = list(x.shape)
        flat = paddle.reshape(x, [-1, shape[-1]])
        carried = paddle.ones([flat.shape[0]], dtype="bool") \
            if cache is None else cache.carried_rows()
        routed, counters = dispatch(
            "qwen3_next_routed_experts", _routed_impl,
            (flat, self.router.weight, self.experts.gate_up,
             self.experts.down, carried),
            dict(top_k=self.top_k, norm_topk=bool(self.norm_topk),
                 held=self.held,
                 use_pallas=pallas_enabled("grouped_matmul")),
            differentiable=False)
        if cache is not None:
            cache.report("moe", counters)
        with block("ffn"):
            shared = F.sigmoid(self.shared_expert_gate(x)) \
                * self.shared_expert(x)
        return shared + paddle.reshape(routed, shape)


class Qwen3NextLayer(nn.Layer):
    def __init__(self, cfg: Qwen3NextConfig, index):
        super().__init__()
        self.input_layernorm = Qwen3NextRMSNorm(cfg, cfg.hidden_size)
        self.attention = cfg.is_attention(index)
        if self.attention:
            self.self_attn = Qwen3NextAttention(cfg)
        else:
            self.linear_attn = GatedDeltaNet(cfg)
        self.post_attention_layernorm = Qwen3NextRMSNorm(cfg,
                                                         cfg.hidden_size)
        self.mlp = Qwen3NextMoE(cfg)

    def forward(self, x, positions, cache=None):
        mixer = self.self_attn if self.attention else self.linear_attn
        with block("attention" if self.attention else "recurrent"):
            h = x + mixer(self.input_layernorm(x), positions, cache)
        # the shared expert is an "ffn" inside
        with block("experts"):
            return h + self.mlp(self.post_attention_layernorm(h), cache)


class Qwen3NextModel(nn.Layer):
    def __init__(self, cfg: Qwen3NextConfig):
        super().__init__()
        self.config = cfg
        self.embed_tokens = nn.Embedding(
            cfg.vocab_size, cfg.hidden_size,
            weight_attr=_normal(cfg)).astype(cfg.dtype)
        self.layers = nn.LayerList([
            Qwen3NextLayer(cfg, i).astype(cfg.dtype)
            for i in range(cfg.num_hidden_layers)])
        self.norm = Qwen3NextRMSNorm(cfg, cfg.hidden_size).astype(cfg.dtype)

    def forward(self, input_ids, cache=None):
        b, s = input_ids.shape
        positions = cache.position_ids if cache is not None \
            else paddle.arange(0, s, dtype="int64")
        with block("embed"):
            x = self.embed_tokens(input_ids)
        for i, layer in enumerate(self.layers):
            x = layer(x, positions, None if cache is None else cache[i])
        with block("head"):
            return self.norm(x)


class Qwen3NextForCausalLM(nn.Layer, GenerationMixin):
    def __init__(self, cfg: Qwen3NextConfig):
        super().__init__()
        self.config = cfg
        self.model = Qwen3NextModel(cfg)
        self.lm_head = _linear(cfg, cfg.hidden_size,
                               cfg.vocab_size).astype(cfg.dtype)

    def cache_spec(self):
        """What each layer keeps between steps, for the serving
        engine: paged K/V with grouped heads for an attention layer;
        for a delta layer the float32 state a value head and the
        convolution's last inputs, in the model's type."""
        cfg = self.config
        channels = 2 * cfg.linear_num_key_heads * cfg.linear_key_head_dim \
            + cfg.linear_num_value_heads * cfg.linear_value_head_dim
        spec = []
        for i in range(cfg.num_hidden_layers):
            if cfg.is_attention(i):
                spec.append({"kind": "paged_kv",
                             "num_kv_heads": cfg.num_key_value_heads,
                             "query_heads": cfg.num_attention_heads,
                             "head_dim": cfg.head_dim,
                             "block_size": cfg.kv_block_size})
            else:
                spec.append({"kind": "recurrent", "states": {
                    "delta": {"shape": (cfg.linear_num_value_heads,
                                        cfg.linear_key_head_dim,
                                        cfg.linear_value_head_dim),
                              "dtype": "float32"},
                    "conv": {"shape": (cfg.linear_conv_kernel_dim - 1,
                                       channels),
                             "dtype": cfg.dtype}}})
        return spec

    def forward(self, input_ids, cache=None, use_cache=False):
        if use_cache:
            raise NotImplementedError(
                "Qwen3-Next decodes through the serving engine's cache "
                "(GenerationEngine), not a concatenated one")
        hidden = self.model(input_ids, cache)
        with block("head"):
            return self.lm_head(hidden)
