"""LongCat-Flash: latent attention (MLA) in shortcut-connected MoE double
layers, routed experts beside zero-compute ones (``LongCat-Flash-Omni``'s
language model).

Built from the published config's own keys:

  * a **double layer** is two MLA sublayers and two dense SwiGLU MLPs
    with one expert layer beside them, fed by the first MLP's input and
    added at the end (the *shortcut*)::

        h1 = x  + MLA_0(N_in0(x))
        u  = N_post0(h1);  m = MoE(u)
        h2 = h1 + MLP_0(u)
        h3 = h2 + MLA_1(N_in1(h2))
        x' = h3 + MLP_1(N_post1(h3)) + m

  * **MLA** (``attention_method: MLA``): ``c_q = N_q(u W_DQ) sqrt(hidden
    / q_lora_rank)``, ``[q_nope | q_rope] = c_q W_UQ`` a head;
    ``[a | k_rope] = u W_DKV``, ``c = N_kv(a) sqrt(hidden /
    kv_lora_rank)``; RoPE on the rope lanes of the query and on the one
    ``k_rope`` all heads share; a head's key is ``[c W_UK_h | k_rope]``,
    its value ``c W_UV_h``; scale ``(nope + rope)^-1/2``; ``W_O`` over
    the heads' values;
  * the **expert layer**: a float32 softmax over ``n_routed_experts +
    zero_expert_num`` outputs, the ``moe_topk`` largest of the
    probabilities plus a choice-only bias, each chosen weight its
    probability times ``routed_scaling_factor``; a routed expert is a
    SwiGLU of width ``expert_ffn_hidden_size``, a zero expert the
    identity;
  * RMSNorms with a plain weight; an untied head.

The model is served in the **absorbed** form: ``W_UK`` folds into each
head's query (``q_nope W_UK_h^T``, ``kv_lora_rank`` lanes, beside the
rotary query), scores are dots with a token's latent row ``[c |
k_rope]``, and the heads' weighted sums of ``c`` go through ``W_UV``.
The serving engine keeps that one row a token for all heads
(``cache_spec()``: the ``latent`` kind) and reads it through
``ops/pallas_latent.py``; with no cache the forward attends over the
rows of the whole prompt at once in the same form.

``expert_shard = (chips, index)`` is this chip's share of a deployment
in which ``chips`` chips share each layer (``qwen3_next.py``): it holds
experts ``[index E / chips, (index + 1) E / chips)``, routes over all
of them and the zero experts, and computes the part its experts give
beside the zero experts' whole term.
"""
from __future__ import annotations

import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from .. import nn
from ..core.dispatch import dispatch
from ..nn import initializer as I
from ..observability import block
from .afmoe import AfmoeMLP, _linear, _normal
from .generation import GenerationMixin


@dataclass
class LongcatFlashConfig:
    vocab_size: int = 131072
    hidden_size: int = 6144
    ffn_hidden_size: int = 12288
    expert_ffn_hidden_size: int = 2048
    #: double layers
    num_layers: int = 28
    num_attention_heads: int = 64
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    mla_scale_q_lora: bool = True
    mla_scale_kv_lora: bool = True
    #: routed experts the router chooses among (the published count)
    n_routed_experts: int = 512
    zero_expert_num: int = 256
    moe_topk: int = 12
    routed_scaling_factor: float = 6.0
    #: ``(chips, index)``: the chips that share a layer and which of
    #: them this is; it holds ``n_routed_experts / chips`` experts a layer
    expert_shard: tuple = (1, 0)
    max_position_embeddings: int = 131072
    rms_norm_eps: float = 1e-5
    rope_theta: float = 10000000.0
    #: std of every projection's and table's normal draw
    initializer_range: float = 0.02
    #: std of the norm weights' draw about one (ones in a fresh model)
    norm_weight_std: float = 0.0
    #: std of the expert bias's draw (zeros in a fresh model)
    expert_bias_std: float = 0.0
    #: tokens a block of the latent pool holds
    kv_block_size: int = 64
    #: the type the parameters are held in; each layer is cast as it is
    #: built (``minicpm_sala.py``)
    dtype: str = "float32"

    def __post_init__(self):
        chips, index = self.expert_shard = tuple(self.expert_shard)
        if self.n_routed_experts % chips or not 0 <= index < chips:
            raise ValueError(f"{self.n_routed_experts} experts over {chips} "
                             f"chips, of which this is number {index}")

    @property
    def held_experts(self):
        """``(lo, hi)``: the experts of each layer that live here."""
        chips, index = self.expert_shard
        share = self.n_routed_experts // chips
        return index * share, (index + 1) * share

    @property
    def latent_width(self):
        """Lanes of a token's latent row: ``c`` then ``k_rope``."""
        return self.kv_lora_rank + self.qk_rope_head_dim


# ---------------------------------------------------------------------
# norms and positions
# ---------------------------------------------------------------------
def _rms_norm_impl(x, w, *, eps, scale):
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt((xf * xf).mean(-1, keepdims=True) + eps)
    return (y * w.astype(jnp.float32) * scale).astype(x.dtype)


class LongcatFlashRMSNorm(nn.Layer):
    """``x rsqrt(mean(x^2) + eps) w`` in float32, times a fixed
    ``scale`` (MLA's ``mla_scale_*``)."""

    def __init__(self, cfg: LongcatFlashConfig, size, scale=1.0):
        super().__init__()
        self._eps, self._scale = cfg.rms_norm_eps, float(scale)
        self.weight = self.create_parameter(
            shape=[size], default_initializer=I.Normal(
                1.0, cfg.norm_weight_std))

    def forward(self, x):
        return dispatch("longcat_rms_norm", _rms_norm_impl,
                        (x, self.weight),
                        dict(eps=float(self._eps), scale=self._scale),
                        differentiable=False)


def _rope_impl(q, k, positions, *, theta):
    """Interleaved RoPE as DeepSeek-style MLA code applies it: the lanes
    of ``q`` [b, s, H, d] and ``k`` [b, s, d] are taken as pairs ``(2i,
    2i + 1)``, laid out evens then odds and turned by rotate-half at
    ``theta^(-2i/d)``: the pairs turned in place, then one permutation
    of the lanes, the same for queries and keys."""
    d = q.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = positions.reshape(-1).astype(jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)            # [s, d / 2]

    def turn(x, cos, sin):
        xf = x.astype(jnp.float32)
        a, b = xf[..., 0::2], xf[..., 1::2]
        return jnp.concatenate([a * cos - b * sin, b * cos + a * sin],
                               -1).astype(x.dtype)

    return (turn(q, cos[None, :, None], sin[None, :, None]),
            turn(k, cos[None], sin[None]))


# ---------------------------------------------------------------------
# latent attention
# ---------------------------------------------------------------------
def _absorb_impl(q, w_ukv, *, heads, nope, value):
    """``q`` [b, s, H, nope + rope] -> [b, s, H, kv_lora_rank + rope]:
    each head's ``q_nope W_UK_h^T`` beside its rotary lanes."""
    w = w_ukv.reshape(w_ukv.shape[0], heads, nope + value)[..., :nope]
    qa = jnp.einsum("bshn,chn->bshc", q[..., :nope], w,
                    preferred_element_type=jnp.float32)
    return jnp.concatenate([qa.astype(q.dtype), q[..., nope:]], -1)


def _values_impl(o, w_ukv, *, heads, nope, value):
    """``o`` [b, s, H, kv_lora_rank] -> [b, s, H * value]: each head's
    weighted sum of ``c`` through its ``W_UV``."""
    w = w_ukv.reshape(w_ukv.shape[0], heads, nope + value)[..., nope:]
    y = jnp.einsum("bshc,chv->bshv", o, w,
                   preferred_element_type=jnp.float32)
    b, s = o.shape[:2]
    return y.reshape(b, s, heads * value).astype(o.dtype)


def _latent_dense_impl(q, rows, *, value_lanes, scale):
    """No cache: ``q`` [b, s, H, width] over the prompt's own rows [b,
    s, width], causal; float32 softmax; values the rows' first
    ``value_lanes`` lanes."""
    s = q.shape[1]
    scores = jnp.einsum("bqhc,bkc->bhqk", q, rows,
                        preferred_element_type=jnp.float32) * scale
    t = jnp.arange(s)
    probs = jax.nn.softmax(
        jnp.where(t[None, :] <= t[:, None], scores, -1e30), axis=-1)
    out = jnp.einsum("bhqk,bkc->bqhc", probs.astype(q.dtype),
                     rows[..., :value_lanes],
                     preferred_element_type=jnp.float32)
    return out.astype(q.dtype)


class LongcatFlashMLA(nn.Layer):
    def __init__(self, cfg: LongcatFlashConfig):
        super().__init__()
        H, D = cfg.num_attention_heads, cfg.hidden_size
        self.heads, self.theta = H, cfg.rope_theta
        self.nope, self.rope = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
        self.value, self.rank = cfg.v_head_dim, cfg.kv_lora_rank
        self.scale = (self.nope + self.rope) ** -0.5
        self.q_a_proj = _linear(cfg, D, cfg.q_lora_rank)
        self.q_a_layernorm = LongcatFlashRMSNorm(
            cfg, cfg.q_lora_rank, math.sqrt(D / cfg.q_lora_rank)
            if cfg.mla_scale_q_lora else 1.0)
        self.q_b_proj = _linear(cfg, cfg.q_lora_rank,
                                H * (self.nope + self.rope))
        self.kv_a_proj_with_mqa = _linear(cfg, D, self.rank + self.rope)
        self.kv_a_layernorm = LongcatFlashRMSNorm(
            cfg, self.rank, math.sqrt(D / self.rank)
            if cfg.mla_scale_kv_lora else 1.0)
        # a head's W_UK (nope lanes) then its W_UV (value lanes)
        self.kv_b_proj = _linear(cfg, self.rank,
                                 H * (self.nope + self.value))
        self.o_proj = _linear(cfg, H * self.value, D)

    def forward(self, u, positions, cache=None):
        b, s, _ = u.shape
        dims = dict(heads=self.heads, nope=self.nope, value=self.value)
        q = paddle.reshape(self.q_b_proj(self.q_a_layernorm(
            self.q_a_proj(u))), [b, s, self.heads, self.nope + self.rope])
        a, k_rope = paddle.split(self.kv_a_proj_with_mqa(u),
                                 [self.rank, self.rope], axis=-1)
        q_nope, q_rope = paddle.split(q, [self.nope, self.rope], axis=-1)
        q_rope, k_rope = dispatch("mla_rope", _rope_impl,
                                  (q_rope, k_rope, positions),
                                  dict(theta=float(self.theta)),
                                  differentiable=False)
        q = dispatch("mla_absorb", _absorb_impl,
                     (paddle.concat([q_nope, q_rope], -1),
                      self.kv_b_proj.weight), dims, differentiable=False)
        rows = paddle.concat([self.kv_a_layernorm(a), k_rope], -1)
        if cache is not None:
            o = cache.attend(q, rows, self.scale)
        else:
            o = dispatch("mla_dense", _latent_dense_impl, (q, rows),
                         dict(value_lanes=self.rank, scale=self.scale),
                         differentiable=False)
        return self.o_proj(dispatch("mla_values", _values_impl,
                                    (o, self.kv_b_proj.weight), dims,
                                    differentiable=False))


# ---------------------------------------------------------------------
# the expert layer
# ---------------------------------------------------------------------
def _routed_impl(x, router, bias, gate_up, down, carried, *, top_k, scale,
                 held, identity, use_pallas):
    """Router over the routed and the zero experts, plan of the held
    ones, grouped kernel, combine with the zero experts' term, on flat
    tokens ``x`` [T, D] (``distributed/auto_parallel/moe_dispatch.py``);
    the router in float32.  Returns ``(y, plan counters)``."""
    from ..distributed.auto_parallel import moe_dispatch as md
    logits = jnp.dot(x.astype(jnp.float32), router.astype(jnp.float32),
                     precision="highest")
    idx, weight = md.softmax_bias_topk_router(logits, bias, top_k, scale)
    return md.gated_experts(x, idx, weight, gate_up, down, carried,
                            use_pallas=use_pallas, held=held,
                            identity=identity)


class LongcatFlashExperts(nn.Layer):
    """The held experts' two stacks, as the grouped kernel reads them."""

    def __init__(self, cfg: LongcatFlashConfig):
        super().__init__()
        lo, hi = cfg.held_experts
        D, W = cfg.hidden_size, cfg.expert_ffn_hidden_size
        # in the served type from the start: no float32 copy of a stack
        self.gate_up = self.create_parameter(
            shape=[hi - lo, D, 2 * W], dtype=cfg.dtype,
            default_initializer=_normal(cfg))
        self.down = self.create_parameter(
            shape=[hi - lo, W, D], dtype=cfg.dtype,
            default_initializer=_normal(cfg))


class LongcatFlashMoE(nn.Layer):
    def __init__(self, cfg: LongcatFlashConfig):
        super().__init__()
        self.top_k, self.scale = cfg.moe_topk, cfg.routed_scaling_factor
        self.held, self.identity = cfg.held_experts, cfg.n_routed_experts
        outputs = cfg.n_routed_experts + cfg.zero_expert_num
        # N(0, 1/hidden): unit-variance logits under a unit-RMS input
        self.router = nn.Linear(
            cfg.hidden_size, outputs, bias_attr=False,
            weight_attr=_normal(cfg, cfg.hidden_size ** -0.5))
        self.expert_bias = self.create_parameter(
            shape=[outputs], is_bias=True,
            default_initializer=_normal(cfg, cfg.expert_bias_std))
        self.experts = LongcatFlashExperts(cfg)

    def forward(self, x, cache=None):
        from ..ops.pallas_gate import pallas_enabled
        shape = list(x.shape)
        flat = paddle.reshape(x, [-1, shape[-1]])
        carried = paddle.ones([flat.shape[0]], dtype="bool") \
            if cache is None else cache.carried_rows()
        routed, counters = dispatch(
            "longcat_routed_experts", _routed_impl,
            (flat, self.router.weight, self.expert_bias,
             self.experts.gate_up, self.experts.down, carried),
            dict(top_k=self.top_k, scale=float(self.scale), held=self.held,
                 identity=self.identity,
                 use_pallas=pallas_enabled("grouped_matmul")),
            differentiable=False)
        if cache is not None:
            cache.report("moe", counters)
        return paddle.reshape(routed, shape)


class LongcatFlashDecoderLayer(nn.Layer):
    """One double layer: two MLA sublayers, two dense MLPs and the
    shortcut expert layer (module docstring)."""

    def __init__(self, cfg: LongcatFlashConfig):
        super().__init__()
        D = cfg.hidden_size
        self.input_layernorm = nn.LayerList(
            [LongcatFlashRMSNorm(cfg, D) for _ in range(2)])
        self.self_attn = nn.LayerList(
            [LongcatFlashMLA(cfg) for _ in range(2)])
        self.post_attention_layernorm = nn.LayerList(
            [LongcatFlashRMSNorm(cfg, D) for _ in range(2)])
        self.mlps = nn.LayerList(
            [AfmoeMLP(cfg, cfg.ffn_hidden_size) for _ in range(2)])
        self.mlp = LongcatFlashMoE(cfg)

    def forward(self, x, positions, caches=(None, None)):
        with block("attention"):
            h = x + self.self_attn[0](self.input_layernorm[0](x),
                                      positions, caches[0])
        with block("ffn"):
            u = self.post_attention_layernorm[0](h)
        with block("experts"):
            shortcut = self.mlp(u, caches[0])
        h = h + self.mlps[0](u)                # an "ffn" inside
        with block("attention"):
            h = h + self.self_attn[1](self.input_layernorm[1](h),
                                      positions, caches[1])
        with block("ffn"):
            u = self.post_attention_layernorm[1](h)
        return h + self.mlps[1](u) + shortcut


class LongcatFlashModel(nn.Layer):
    def __init__(self, cfg: LongcatFlashConfig):
        super().__init__()
        self.config = cfg
        self.embed_tokens = nn.Embedding(
            cfg.vocab_size, cfg.hidden_size,
            weight_attr=_normal(cfg)).astype(cfg.dtype)
        self.layers = nn.LayerList([
            LongcatFlashDecoderLayer(cfg).astype(cfg.dtype)
            for _ in range(cfg.num_layers)])
        self.norm = LongcatFlashRMSNorm(cfg,
                                        cfg.hidden_size).astype(cfg.dtype)

    def forward(self, input_ids, cache=None):
        b, s = input_ids.shape
        positions = cache.position_ids if cache is not None \
            else paddle.arange(0, s, dtype="int64")
        with block("embed"):
            x = self.embed_tokens(input_ids)
        for i, layer in enumerate(self.layers):
            caches = (None, None) if cache is None \
                else (cache[2 * i], cache[2 * i + 1])
            x = layer(x, positions, caches)
        with block("head"):
            return self.norm(x)


class LongcatFlashForCausalLM(nn.Layer, GenerationMixin):
    def __init__(self, cfg: LongcatFlashConfig):
        super().__init__()
        self.config = cfg
        self.model = LongcatFlashModel(cfg)
        self.lm_head = _linear(cfg, cfg.hidden_size,
                               cfg.vocab_size).astype(cfg.dtype)

    def cache_spec(self):
        """What each MLA sublayer keeps between steps, for the serving
        engine: one latent row ``[c | k_rope]`` a token for all its
        heads, whose first ``kv_lora_rank`` lanes are the values of the
        absorbed form; two sublayers a double layer, in order."""
        cfg = self.config
        return [{"kind": "latent", "width": cfg.latent_width,
                 "value_lanes": cfg.kv_lora_rank,
                 "query_heads": cfg.num_attention_heads,
                 "block_size": cfg.kv_block_size}
                for _ in range(2 * cfg.num_layers)]

    def forward(self, input_ids, cache=None, use_cache=False):
        if use_cache:
            raise NotImplementedError(
                "LongCat-Flash decodes through the serving engine's cache "
                "(GenerationEngine), not a concatenated one")
        hidden = self.model(input_ids, cache)
        with block("head"):
            return self.lm_head(hidden)
