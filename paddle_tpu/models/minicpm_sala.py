"""MiniCPM-SALA: block-sparse attention and lightning linear attention
layers side by side (openbmb/MiniCPM-SALA, ``model_type:
minicpm_sala``).

``mixer_types`` decides each layer's mixer:

  * ``minicpm4`` — InfLLM-V2 block-sparse attention (MiniCPM4 report,
    arXiv:2506.07900): grouped KV heads, q/k RMSNorm, no positions, a
    selector over mean-pooled keys (ops/pallas_sparse.py), a sigmoid
    output gate;
  * ``lightning-attn`` — Lightning Attention (arXiv:2401.04658): a
    decayed ``d x d`` recurrent state a head, q/k RMSNorm, rotary
    positions on q and k, RMSNorm over the concatenated heads, a
    sigmoid output gate.

muP scales: the embedding times ``scale_emb``, every residual branch
times ``scale_depth / sqrt(published_layers)``, the logits over
``hidden_size / dim_model_base``; the head is untied.

With no cache the model is the dense forward (a whole prompt at once).
With the engine's cache view each mixer asks its layer cache for what
it needs: a sparse layer ``cache.attend(q, k, v)``, a lightning layer
``cache.update(q, k, v)``.  ``cache_spec()`` tells the engine which
kind of state each layer keeps.
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
from ..observability import block
from ..ops import pallas_lightning as pll
from ..ops import pallas_sparse as pls
from .generation import GenerationMixin
from .llama import LlamaMLP, apply_rotary_pos_emb

LIGHTNING, SPARSE = "lightning-attn", "minicpm4"


@dataclass
class MiniCPMSALAConfig:
    vocab_size: int = 73448
    hidden_size: int = 4096
    intermediate_size: int = 16384
    num_hidden_layers: int = 32
    mixer_types: tuple = ()
    num_attention_heads: int = 32
    num_key_value_heads: int = 2
    head_dim: int = 128
    lightning_nh: int = 32
    lightning_nkv: int = 32
    lightning_head_dim: int = 128
    max_position_embeddings: int = 524288
    rms_norm_eps: float = 1e-6
    rope_theta: float = 10000.0
    scale_emb: float = 12.0
    scale_depth: float = 1.4
    published_layers: int = 0        # 0 -> num_hidden_layers
    dim_model_base: int = 256
    sparse_kernel_size: int = 32
    sparse_kernel_stride: int = 16
    sparse_block_size: int = 64
    sparse_window_size: int = 2048
    sparse_init_blocks: int = 1
    sparse_dense_len: int = 8192
    sparse_topk: int = 64
    #: the type the parameters are held in.  Each layer is cast as it
    #: is built: 3.9 B parameters in float32 first would be a whole
    #: 16 GB chip
    dtype: str = "float32"

    def __post_init__(self):
        self.mixer_types = tuple(self.mixer_types) or \
            (LIGHTNING,) * self.num_hidden_layers
        if len(self.mixer_types) != self.num_hidden_layers:
            raise ValueError(
                f"{len(self.mixer_types)} mixer_types for "
                f"{self.num_hidden_layers} layers")
        unknown = set(self.mixer_types) - {LIGHTNING, SPARSE}
        if unknown:
            raise ValueError(f"unknown mixer types {sorted(unknown)}")
        if self.lightning_nkv != self.lightning_nh:
            raise ValueError("lightning layers keep one state a head: "
                             "lightning_nkv must equal lightning_nh")
        if not self.published_layers:
            self.published_layers = self.num_hidden_layers

    @property
    def residual_scale(self):
        return self.scale_depth / math.sqrt(self.published_layers)

    @property
    def sparse_sizes(self):
        return pls.SparseSizes(
            self.sparse_kernel_size, self.sparse_kernel_stride,
            self.sparse_block_size, self.sparse_window_size,
            self.sparse_init_blocks, self.sparse_dense_len,
            self.sparse_topk)


def _rope_tables_impl(positions, *, head_dim, theta):
    inv = 1.0 / theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                          / head_dim)
    ang = positions.reshape(-1).astype(jnp.float32)[:, None] * inv[None, :]
    ang = jnp.concatenate([ang, ang], axis=-1)
    return jnp.cos(ang), jnp.sin(ang)


def rope_tables(positions, head_dim, theta):
    """``(cos, sin)`` [s, head_dim] float32 at absolute ``positions``,
    computed in the step (a table over 524,288 positions would be half
    a gigabyte)."""
    return dispatch("rope_tables", _rope_tables_impl, (positions,),
                    dict(head_dim=int(head_dim), theta=float(theta)),
                    differentiable=False)


class LightningMixer(nn.Layer):
    def __init__(self, cfg: MiniCPMSALAConfig):
        super().__init__()
        self.num_heads, self.head_dim = cfg.lightning_nh, cfg.lightning_head_dim
        inner = self.num_heads * self.head_dim
        self.theta = cfg.rope_theta
        self.slopes = tuple(float(s)
                            for s in pll.decay_slopes(self.num_heads))
        self.q_proj = nn.Linear(cfg.hidden_size, inner, bias_attr=False)
        self.k_proj = nn.Linear(cfg.hidden_size, inner, bias_attr=False)
        self.v_proj = nn.Linear(cfg.hidden_size, inner, bias_attr=False)
        self.g_proj = nn.Linear(cfg.hidden_size, inner, bias_attr=False)
        self.o_proj = nn.Linear(inner, cfg.hidden_size, bias_attr=False)
        self.q_norm = nn.RMSNorm(self.head_dim, epsilon=cfg.rms_norm_eps)
        self.k_norm = nn.RMSNorm(self.head_dim, epsilon=cfg.rms_norm_eps)
        self.out_norm = nn.RMSNorm(inner, epsilon=cfg.rms_norm_eps)

    def forward(self, u, positions, cache=None):
        b, s, _ = u.shape
        shape = [b, s, self.num_heads, self.head_dim]
        q = self.q_norm(paddle.reshape(self.q_proj(u), shape))
        k = self.k_norm(paddle.reshape(self.k_proj(u), shape))
        v = paddle.reshape(self.v_proj(u), shape)
        cos, sin = rope_tables(positions, self.head_dim, self.theta)
        qr, kr = apply_rotary_pos_emb(q, k, cos, sin)
        q, k = qr.astype(u.dtype), kr.astype(u.dtype)
        if cache is not None:
            o = cache.update(q, k, v, self.slopes)
        else:
            o = dispatch("lightning_dense", pll.lightning_dense, (q, k, v),
                         dict(slopes=self.slopes), differentiable=False)
        o = self.out_norm(paddle.reshape(o, [b, s, -1]))
        return self.o_proj(o * F.sigmoid(self.g_proj(u)))


def _sparse_dense_impl(q, k, v, *, sizes):
    """No cache: every sequence of ``q`` [b, s, H, D], ``k``/``v``
    [b, s, Hkv, D] selects and attends over its own keys."""
    b, s, H, D = q.shape
    kv_heads = k.shape[2]
    pad = -s % sizes.block
    t = jnp.arange(s, dtype=jnp.int32)

    def one(q1, k1, v1):
        ck = pls.compress_dense(k1, sizes)
        kp = jnp.pad(k1, ((0, pad), (0, 0), (0, 0)))
        vp = jnp.pad(v1, ((0, pad), (0, 0), (0, 0)))
        o = pls.sparse_block_attention(
            q1.reshape(s, kv_heads, H // kv_heads, D), t, kp, vp, ck, sizes)
        return o.reshape(s, H, D)

    return jax.vmap(one)(q, k, v)


class SparseMixer(nn.Layer):
    def __init__(self, cfg: MiniCPMSALAConfig):
        super().__init__()
        self.num_heads, self.head_dim = cfg.num_attention_heads, cfg.head_dim
        self.num_kv_heads = cfg.num_key_value_heads
        self.sizes = cfg.sparse_sizes
        inner = self.num_heads * self.head_dim
        kv = self.num_kv_heads * self.head_dim
        self.q_proj = nn.Linear(cfg.hidden_size, inner, bias_attr=False)
        self.k_proj = nn.Linear(cfg.hidden_size, kv, bias_attr=False)
        self.v_proj = nn.Linear(cfg.hidden_size, kv, bias_attr=False)
        self.g_proj = nn.Linear(cfg.hidden_size, inner, bias_attr=False)
        self.o_proj = nn.Linear(inner, cfg.hidden_size, bias_attr=False)
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
        if cache is not None:
            o = cache.attend(q, k, v, self.sizes)
        else:
            o = dispatch("sparse_attention_dense", _sparse_dense_impl,
                         (q, k, v), dict(sizes=self.sizes),
                         differentiable=False)
        o = paddle.reshape(o, [b, s, -1])
        return self.o_proj(o * F.sigmoid(self.g_proj(u)))


class MiniCPMSALALayer(nn.Layer):
    def __init__(self, cfg: MiniCPMSALAConfig, kind):
        super().__init__()
        self.kind, self.scale = kind, cfg.residual_scale
        self.input_layernorm = nn.RMSNorm(cfg.hidden_size,
                                          epsilon=cfg.rms_norm_eps)
        self.mixer = (LightningMixer if kind == LIGHTNING
                      else SparseMixer)(cfg)
        self.post_attention_layernorm = nn.RMSNorm(
            cfg.hidden_size, epsilon=cfg.rms_norm_eps)
        self.mlp = LlamaMLP(cfg)

    def forward(self, x, positions, cache=None):
        with block("recurrent" if self.kind == LIGHTNING else "attention"):
            x = x + self.mixer(self.input_layernorm(x), positions,
                               cache) * self.scale
        with block("ffn"):
            return x + self.mlp(
                self.post_attention_layernorm(x)) * self.scale


class MiniCPMSALAModel(nn.Layer):
    def __init__(self, cfg: MiniCPMSALAConfig):
        super().__init__()
        self.config = cfg
        self.embed_tokens = nn.Embedding(
            cfg.vocab_size, cfg.hidden_size).astype(cfg.dtype)
        self.layers = nn.LayerList([
            MiniCPMSALALayer(cfg, kind).astype(cfg.dtype)
            for kind in cfg.mixer_types])
        self.norm = nn.RMSNorm(
            cfg.hidden_size, epsilon=cfg.rms_norm_eps).astype(cfg.dtype)

    def forward(self, input_ids, cache=None):
        b, s = input_ids.shape
        if cache is not None:
            # the serving cache: rows sit at different absolute
            # positions, so the engine supplies them per step
            positions = cache.position_ids
        else:
            positions = paddle.arange(0, s, dtype="int64")
        with block("embed"):
            x = self.embed_tokens(input_ids) * self.config.scale_emb
        for i, layer in enumerate(self.layers):
            x = layer(x, positions, None if cache is None else cache[i])
        with block("head"):
            return self.norm(x)


class MiniCPMSALAForCausalLM(nn.Layer, GenerationMixin):
    def __init__(self, cfg: MiniCPMSALAConfig):
        super().__init__()
        self.config = cfg
        self.model = MiniCPMSALAModel(cfg)
        self.lm_head = nn.Linear(cfg.hidden_size, cfg.vocab_size,
                                 bias_attr=False).astype(cfg.dtype)
        self._logit_scale = cfg.dim_model_base / cfg.hidden_size

    def cache_spec(self):
        """What each layer keeps between steps, for the serving
        engine: paged K/V (with the selector's compressed keys) for a
        sparse layer, one float32 state a head for a lightning layer."""
        cfg = self.config
        sizes = cfg.sparse_sizes
        spec = []
        for kind in cfg.mixer_types:
            if kind == SPARSE:
                spec.append({"kind": "paged_kv",
                             "num_kv_heads": cfg.num_key_value_heads,
                             "head_dim": cfg.head_dim,
                             "block_size": sizes.block,
                             "sparse_sizes": sizes})
            else:
                spec.append({"kind": "recurrent", "states": {"state": {
                    "shape": (cfg.lightning_nh, cfg.lightning_head_dim,
                              cfg.lightning_head_dim),
                    "dtype": "float32"}}})
        return spec

    def forward(self, input_ids, cache=None, use_cache=False):
        if use_cache:
            raise NotImplementedError(
                "MiniCPM-SALA decodes through the serving engine's "
                "cache (GenerationEngine), not a concatenated one")
        hidden = self.model(input_ids, cache)
        with block("head"):
            return self.lm_head(hidden) * self._logit_scale
