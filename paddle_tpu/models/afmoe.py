"""AFMoE: sliding-window and full attention layers mixed, routed experts
beside a shared expert (arcee-ai Trinity, ``model_type: afmoe``).

Built from the published config's own keys:

  * ``layer_types`` — ``sliding_attention`` layers rotate q and k
    (rotate-half RoPE at the token's absolute position) and see the last
    ``sliding_window`` tokens; ``full_attention`` layers see no positions
    and the whole context.  Both: grouped KV heads, RMSNorm over the head
    dimension on q and k, a sigmoid output gate;
  * ``num_dense_layers`` leading layers keep a dense SwiGLU; every other
    layer routes each token to ``num_experts_per_tok`` of ``num_experts``
    SwiGLU experts through a sigmoid router whose ``expert_bias`` moves
    the choice and not the weight, and adds ``num_shared_experts`` shared
    expert;
  * four RMSNorms a block (sandwich): ``h = x + post_attn(attn(in(x)))``,
    ``x' = h + post_mlp(ffn(pre_mlp(h)))``; the embedding times
    ``sqrt(hidden_size)`` (``mup_enabled``); an untied head.

The routed experts' weights are stored as the grouped kernel reads them
(``ops/pallas_grouped.py``): one ``[E, hidden, 2 * width]`` stack with
each expert's gate columns beside its up columns and one ``[E, width,
hidden]`` down stack, so a step concatenates, pads and transposes
nothing.

With no cache the model is the dense forward (a whole prompt at once).
With the engine's cache view each attention asks its layer cache
(``cache.attend``); ``cache_spec()`` gives the sliding layers a
``window``, so the cache manager keeps them in a group with block tables
of its own.
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
from .generation import GenerationMixin
from .llama import apply_rotary_pos_emb
from .minicpm_sala import rope_tables

SLIDING, FULL = "sliding_attention", "full_attention"


@dataclass
class AfmoeConfig:
    vocab_size: int = 200192
    hidden_size: int = 2048
    intermediate_size: int = 6144        # the dense layers' FFN
    moe_intermediate_size: int = 1024    # a routed or shared expert's
    num_hidden_layers: int = 32
    num_dense_layers: int = 2
    layer_types: tuple = ()
    num_attention_heads: int = 32
    num_key_value_heads: int = 4
    head_dim: int = 128
    sliding_window: int = 2048
    num_experts: int = 128
    num_experts_per_tok: int = 8
    num_shared_experts: int = 1
    route_norm: bool = True
    route_scale: float = 2.826
    score_func: str = "sigmoid"
    mup_enabled: bool = True
    max_position_embeddings: int = 131072
    rms_norm_eps: float = 1e-5
    rope_theta: float = 10000.0
    #: std of every projection's and table's normal draw
    initializer_range: float = 0.02
    #: std of the expert bias's draw (a trained buffer; 0 in a fresh one)
    expert_bias_std: float = 0.0
    #: tokens a KV block holds in the serving cache
    kv_block_size: int = 64
    #: the type the parameters are held in; each layer is cast as it is
    #: built (``minicpm_sala.py``)
    dtype: str = "float32"

    def __post_init__(self):
        self.layer_types = tuple(self.layer_types) or \
            (FULL,) * self.num_hidden_layers
        if len(self.layer_types) != self.num_hidden_layers:
            raise ValueError(f"{len(self.layer_types)} layer_types for "
                             f"{self.num_hidden_layers} layers")
        unknown = set(self.layer_types) - {SLIDING, FULL}
        if unknown:
            raise ValueError(f"unknown layer types {sorted(unknown)}")
        if self.score_func != "sigmoid":
            raise ValueError("the router scores with a sigmoid; got "
                             f"score_func {self.score_func!r}")
        if self.num_shared_experts != 1:
            raise ValueError("one shared expert a layer; got "
                             f"{self.num_shared_experts}")


class _ChunkedNormal(I.Initializer):
    """``N(0, std)`` drawn a slice of the leading axis at a time and
    written in the parameter's own type: drawn whole in float32, a
    128 x 2048 x 2048 expert stack and the generator's temporaries would
    stand three times over beside the weights (6 GB of a 16 GB chip)."""

    def __init__(self, std):
        self.std = std

    def generate(self, shape, dtype):
        from ..framework.random import default_generator
        groups = math.gcd(shape[0], 128)
        part = (shape[0] // groups,) + tuple(shape[1:])
        keys = jax.random.split(default_generator().next_key(), groups)
        draw = jax.jit(lambda keys: jax.lax.map(
            lambda k: (self.std * jax.random.normal(k, part, jnp.float32))
            .astype(dtype), keys))
        return draw(keys).reshape(shape)


def _normal(cfg, std=None):
    return _ChunkedNormal(cfg.initializer_range if std is None else std)


def _linear(cfg, fan_in, fan_out):
    return nn.Linear(fan_in, fan_out, weight_attr=_normal(cfg),
                     bias_attr=False)


def _dense_attention_impl(q, k, v, *, window):
    """No cache: ``q`` [b, s, H, D] over ``k``/``v`` [b, s, Hkv, D],
    causal, the last ``window`` tokens when given; float32 softmax."""
    b, s, H, D = q.shape
    kv_heads = k.shape[2]
    qg = q.reshape(b, s, kv_heads, H // kv_heads, D)
    scores = jnp.einsum("bqngd,bknd->bngqk", qg, k,
                        preferred_element_type=jnp.float32) / math.sqrt(D)
    t = jnp.arange(s)
    seen = t[None, :] <= t[:, None]
    if window is not None:
        seen &= t[None, :] > t[:, None] - window
    probs = jax.nn.softmax(jnp.where(seen, scores, -1e30), axis=-1)
    out = jnp.einsum("bngqk,bknd->bqngd", probs.astype(q.dtype), v,
                     preferred_element_type=jnp.float32)
    return out.reshape(b, s, H, D).astype(q.dtype)


class AfmoeAttention(nn.Layer):
    def __init__(self, cfg: AfmoeConfig, kind):
        super().__init__()
        self.num_heads, self.head_dim = cfg.num_attention_heads, cfg.head_dim
        self.num_kv_heads = cfg.num_key_value_heads
        self.window = cfg.sliding_window if kind == SLIDING else None
        self.theta = cfg.rope_theta
        inner = self.num_heads * self.head_dim
        kv = self.num_kv_heads * self.head_dim
        self.q_proj = _linear(cfg, cfg.hidden_size, inner)
        self.k_proj = _linear(cfg, cfg.hidden_size, kv)
        self.v_proj = _linear(cfg, cfg.hidden_size, kv)
        self.g_proj = _linear(cfg, cfg.hidden_size, inner)
        self.o_proj = _linear(cfg, inner, cfg.hidden_size)
        self.q_norm = nn.RMSNorm(self.head_dim, epsilon=cfg.rms_norm_eps)
        self.k_norm = nn.RMSNorm(self.head_dim, epsilon=cfg.rms_norm_eps)

    def forward(self, u, positions, cache=None):
        b, s, _ = u.shape
        q = self.q_norm(paddle.reshape(
            self.q_proj(u), [b, s, self.num_heads, self.head_dim]))
        k = self.k_norm(paddle.reshape(
            self.k_proj(u), [b, s, self.num_kv_heads, self.head_dim]))
        v = paddle.reshape(self.v_proj(u),
                           [b, s, self.num_kv_heads, self.head_dim])
        if self.window is not None:      # positions: sliding layers only
            cos, sin = rope_tables(positions, self.head_dim, self.theta)
            qr, kr = apply_rotary_pos_emb(q, k, cos, sin)
            q, k = qr.astype(u.dtype), kr.astype(u.dtype)
        if cache is not None:
            o = cache.attend(q, k, v)
        else:
            o = dispatch("afmoe_attention_dense", _dense_attention_impl,
                         (q, k, v), dict(window=self.window),
                         differentiable=False)
        o = paddle.reshape(o, [b, s, -1])
        return self.o_proj(o * F.sigmoid(self.g_proj(u)))


class AfmoeMLP(nn.Layer):
    """SwiGLU: the dense layers' FFN and the shared expert."""

    def __init__(self, cfg: AfmoeConfig, width):
        super().__init__()
        self.gate_proj = _linear(cfg, cfg.hidden_size, width)
        self.up_proj = _linear(cfg, cfg.hidden_size, width)
        self.down_proj = _linear(cfg, width, cfg.hidden_size)

    def forward(self, x):
        with block("ffn"):
            return self.down_proj(
                F.silu(self.gate_proj(x)) * self.up_proj(x))


def _routed_impl(x, router, bias, gate_up, down, carried, *, top_k,
                 route_scale, route_norm, use_pallas):
    """Router, plan, grouped kernel, combine on flat tokens ``x`` [T, D]
    (``distributed/auto_parallel/moe_dispatch.py``); the router in
    float32.  Returns ``(y, plan counters)``."""
    from ..distributed.auto_parallel import moe_dispatch as md
    logits = jnp.dot(x.astype(jnp.float32), router.astype(jnp.float32),
                     precision="highest")
    idx, weight = md.sigmoid_topk_router(logits, bias, top_k, route_scale,
                                         route_norm)
    return md.gated_experts(x, idx, weight, gate_up, down, carried,
                            use_pallas=use_pallas,
                            held=(0, gate_up.shape[0]))


class AfmoeExperts(nn.Layer):
    """The routed experts' two stacks, as the grouped kernel reads them."""

    def __init__(self, cfg: AfmoeConfig):
        super().__init__()
        E, D, W = (cfg.num_experts, cfg.hidden_size,
                   cfg.moe_intermediate_size)
        # in the served type from the start: no float32 copy of a stack
        self.gate_up = self.create_parameter(
            shape=[E, D, 2 * W], dtype=cfg.dtype,
            default_initializer=_normal(cfg))
        self.down = self.create_parameter(
            shape=[E, W, D], dtype=cfg.dtype,
            default_initializer=_normal(cfg))


class AfmoeMoE(nn.Layer):
    def __init__(self, cfg: AfmoeConfig):
        super().__init__()
        self.top_k = cfg.num_experts_per_tok
        self.route_scale, self.route_norm = cfg.route_scale, cfg.route_norm
        # N(0, 1/hidden): unit-variance logits under a unit-RMS input
        self.router = nn.Linear(
            cfg.hidden_size, cfg.num_experts, bias_attr=False,
            weight_attr=_normal(cfg, cfg.hidden_size ** -0.5))
        self.expert_bias = self.create_parameter(
            shape=[cfg.num_experts], is_bias=True,
            default_initializer=_normal(cfg, cfg.expert_bias_std))
        self.expert_bias.stop_gradient = True    # a buffer: choice only
        self.experts = AfmoeExperts(cfg)
        self.shared = AfmoeMLP(cfg, cfg.moe_intermediate_size)

    def forward(self, x, cache=None):
        from ..ops.pallas_gate import pallas_enabled
        shape = list(x.shape)
        flat = paddle.reshape(x, [-1, shape[-1]])
        carried = paddle.ones([flat.shape[0]], dtype="bool") \
            if cache is None else cache.carried_rows()
        routed, counters = dispatch(
            "afmoe_routed_experts", _routed_impl,
            (flat, self.router.weight, self.expert_bias,
             self.experts.gate_up, self.experts.down, carried),
            dict(top_k=self.top_k, route_scale=float(self.route_scale),
                 route_norm=bool(self.route_norm),
                 use_pallas=pallas_enabled("grouped_matmul")),
            differentiable=False)
        if cache is not None:
            cache.report("moe", counters)
        return self.shared(x) + paddle.reshape(routed, shape)


class AfmoeLayer(nn.Layer):
    def __init__(self, cfg: AfmoeConfig, index):
        super().__init__()
        eps = cfg.rms_norm_eps
        self.input_layernorm = nn.RMSNorm(cfg.hidden_size, epsilon=eps)
        self.self_attn = AfmoeAttention(cfg, cfg.layer_types[index])
        self.post_attention_layernorm = nn.RMSNorm(cfg.hidden_size,
                                                   epsilon=eps)
        self.pre_mlp_layernorm = nn.RMSNorm(cfg.hidden_size, epsilon=eps)
        self.routed = index >= cfg.num_dense_layers
        self.mlp = AfmoeMoE(cfg) if self.routed \
            else AfmoeMLP(cfg, cfg.intermediate_size)
        self.post_mlp_layernorm = nn.RMSNorm(cfg.hidden_size, epsilon=eps)

    def forward(self, x, positions, cache=None):
        with block("attention"):
            h = x + self.post_attention_layernorm(
                self.self_attn(self.input_layernorm(x), positions, cache))
        # the routed layer's shared expert is an "ffn" inside
        with block("experts" if self.routed else "ffn"):
            u = self.pre_mlp_layernorm(h)
            m = self.mlp(u, cache) if self.routed else self.mlp(u)
            return h + self.post_mlp_layernorm(m)


class AfmoeModel(nn.Layer):
    def __init__(self, cfg: AfmoeConfig):
        super().__init__()
        self.config = cfg
        self.embed_tokens = nn.Embedding(
            cfg.vocab_size, cfg.hidden_size,
            weight_attr=_normal(cfg)).astype(cfg.dtype)
        self.layers = nn.LayerList([
            AfmoeLayer(cfg, i).astype(cfg.dtype)
            for i in range(cfg.num_hidden_layers)])
        self.norm = nn.RMSNorm(
            cfg.hidden_size, epsilon=cfg.rms_norm_eps).astype(cfg.dtype)
        self._embed_scale = math.sqrt(cfg.hidden_size) \
            if cfg.mup_enabled else 1.0

    def forward(self, input_ids, cache=None):
        b, s = input_ids.shape
        positions = cache.position_ids if cache is not None \
            else paddle.arange(0, s, dtype="int64")
        with block("embed"):
            x = self.embed_tokens(input_ids) * self._embed_scale
        for i, layer in enumerate(self.layers):
            x = layer(x, positions, None if cache is None else cache[i])
        with block("head"):
            return self.norm(x)


class AfmoeForCausalLM(nn.Layer, GenerationMixin):
    def __init__(self, cfg: AfmoeConfig):
        super().__init__()
        self.config = cfg
        self.model = AfmoeModel(cfg)
        self.lm_head = _linear(cfg, cfg.hidden_size,
                               cfg.vocab_size).astype(cfg.dtype)

    def cache_spec(self):
        """Every layer pages K/V with grouped heads; a sliding layer's
        ``window`` puts it in a group whose blocks go back to the pool
        as a row's position passes them."""
        cfg = self.config
        spec = []
        for kind in cfg.layer_types:
            layer = {"kind": "paged_kv",
                     "num_kv_heads": cfg.num_key_value_heads,
                     "query_heads": cfg.num_attention_heads,
                     "head_dim": cfg.head_dim,
                     "block_size": cfg.kv_block_size}
            if kind == SLIDING:
                layer["window"] = cfg.sliding_window
            spec.append(layer)
        return spec

    def forward(self, input_ids, cache=None, use_cache=False):
        if use_cache:
            raise NotImplementedError(
                "AFMoE decodes through the serving engine's cache "
                "(GenerationEngine), not a concatenated one")
        hidden = self.model(input_ids, cache)
        with block("head"):
            return self.lm_head(hidden)
