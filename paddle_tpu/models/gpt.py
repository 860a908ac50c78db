"""GPT decoder-only LM (BASELINE.md config #4: GPT-3 1.3B class).

Reference parity: `paddlenlp/transformers/gpt/modeling.py` [UNVERIFIED —
empty reference mount].  TPU-native notes: attention routes through
F.scaled_dot_product_attention → the Pallas flash kernel on TPU; the LM
loss uses the fused softmax-xent path via F.cross_entropy; recompute
(jax.checkpoint) can wrap each block via `recompute=True`.
"""
from __future__ import annotations

from dataclasses import dataclass

import paddle_tpu as paddle
from .. import nn
from ..nn import functional as F
from ..observability import block
from .generation import GenerationMixin


@dataclass
class GPTConfig:
    vocab_size: int = 50304
    hidden_size: int = 768
    num_hidden_layers: int = 12
    num_attention_heads: int = 12
    intermediate_size: int = 0      # 0 → 4*hidden
    max_position_embeddings: int = 1024
    hidden_dropout_prob: float = 0.0
    use_flash_attention: bool = True
    use_recompute: bool = False
    tie_word_embeddings: bool = True
    # lax.scan over stacked block weights (nn/layer/scanned.py):
    # compile time O(1) in depth; only the no-cache training path
    use_scan_layers: bool = False

    def __post_init__(self):
        if not self.intermediate_size:
            self.intermediate_size = 4 * self.hidden_size


class GPTAttention(nn.Layer):
    def __init__(self, cfg: GPTConfig):
        super().__init__()
        self.num_heads = cfg.num_attention_heads
        self.head_dim = cfg.hidden_size // cfg.num_attention_heads
        self.use_flash = cfg.use_flash_attention
        self.qkv_proj = nn.Linear(cfg.hidden_size, 3 * cfg.hidden_size)
        self.out_proj = nn.Linear(cfg.hidden_size, cfg.hidden_size)

    def forward(self, x, cache=None, use_cache=False):
        b, s, h = x.shape
        qkv = self.qkv_proj(x)
        # multi-LoRA serving (inference/serving/lora): per-q-block
        # adapter deltas ride the segmented SGMV epilogue after each
        # projection; rows without an adapter hit the zero segment
        lora = getattr(cache, "lora", None) if cache is not None else None
        if lora is not None and lora.active(self.qkv_proj):
            qkv = lora.apply(qkv, x, self.qkv_proj)
        qkv = paddle.reshape(qkv, [b, s, 3, self.num_heads, self.head_dim])
        q, k, v = paddle.unbind(qkv, axis=2)     # each [b, s, nh, hd]
        if cache is not None and hasattr(cache, "attend"):
            # paged serving cache (inference/serving): the layer view
            # scatters K/V into the block pool and attends through the
            # block tables; dense semantics below stay untouched
            attn = cache.attend(q, k, v, use_flash=self.use_flash)
            attn = paddle.reshape(attn, [b, s, h])
            out = self.out_proj(attn)
            if lora is not None and lora.active(self.out_proj):
                out = lora.apply(out, attn, self.out_proj)
            if use_cache:
                return out, cache
            return out
        if cache is not None:
            # decode: extend K/V with the cached prefix; the SDPA causal
            # mask is bottom-right aligned, so new rows see everything
            k = paddle.concat([cache[0], k], axis=1)
            v = paddle.concat([cache[1], v], axis=1)
        from ..nn.functional.flash_attention import sdp_kernel
        # enable_flash=True is exactly the automatic-selection default
        with sdp_kernel(enable_flash=self.use_flash):
            out = F.scaled_dot_product_attention(q, k, v, is_causal=True)
        out = paddle.reshape(out, [b, s, h])
        out = self.out_proj(out)
        if use_cache:
            return out, (k, v)
        return out


class GPTMLP(nn.Layer):
    def __init__(self, cfg: GPTConfig):
        super().__init__()
        self.fc1 = nn.Linear(cfg.hidden_size, cfg.intermediate_size)
        self.fc2 = nn.Linear(cfg.intermediate_size, cfg.hidden_size)

    def forward(self, x, lora=None):
        # fc1's bias+gelu fold into the matmul epilogue on TPU
        w_q = getattr(self.fc1, "weight_q", None)
        if w_q is not None:
            h = F.linear_act_int8(x, w_q, self.fc1.weight_scale,
                                  self.fc1.bias, act="gelu_tanh")
        elif lora is not None and lora.active(self.fc1):
            # the activation defers past the LoRA delta — the SGMV
            # epilogue computes act(z + delta) in one fused pass
            z = F.linear(x, self.fc1.weight, self.fc1.bias)
            h = lora.apply(z, x, self.fc1, act="gelu_tanh")
        else:
            h = F.linear_act(x, self.fc1.weight, self.fc1.bias,
                             act="gelu_tanh")
        y = self.fc2(h)
        if lora is not None and lora.active(self.fc2):
            y = lora.apply(y, h, self.fc2)
        return y


class GPTBlock(nn.Layer):
    def __init__(self, cfg: GPTConfig):
        super().__init__()
        self.ln_1 = nn.LayerNorm(cfg.hidden_size)
        self.attn = GPTAttention(cfg)
        self.ln_2 = nn.LayerNorm(cfg.hidden_size)
        self.mlp = GPTMLP(cfg)
        # GPT-2 style residual dropout (config default 0.0 — a no-op
        # unless the user opts in; scan_layers requires it stay 0)
        self.dropout = nn.Dropout(cfg.hidden_dropout_prob)

    def forward(self, x, cache=None, use_cache=False):
        lora = getattr(cache, "lora", None) if cache is not None else None
        new_cache = None
        with block("attention"):
            if use_cache:
                a, new_cache = self.attn(self.ln_1(x), cache, True)
            else:
                a = self.attn(self.ln_1(x), cache)
            x = x + self.dropout(a)
        with block("ffn"):
            x = x + self.dropout(self.mlp(self.ln_2(x), lora=lora))
        return (x, new_cache) if use_cache else x


class GPTModel(nn.Layer):
    def __init__(self, cfg: GPTConfig):
        super().__init__()
        self.config = cfg
        self.wte = nn.Embedding(cfg.vocab_size, cfg.hidden_size)
        self.wpe = nn.Embedding(cfg.max_position_embeddings,
                                cfg.hidden_size)
        self.h = nn.LayerList([GPTBlock(cfg)
                               for _ in range(cfg.num_hidden_layers)])
        self.ln_f = nn.LayerNorm(cfg.hidden_size)
        self._recompute = cfg.use_recompute

    def forward(self, input_ids, cache=None, use_cache=False):
        b, s = input_ids.shape
        if cache is not None and getattr(cache, "position_ids", None) \
                is not None:
            # paged serving cache: rows sit at different absolute
            # positions, so the engine supplies them per step
            pos = cache.position_ids
        else:
            past = 0 if cache is None else cache[0][0].shape[1]
            pos = paddle.arange(past, past + s, dtype="int64")
        with block("embed"):
            x = self.wte(input_ids) + self.wpe(pos)
        drop_active = (self.training
                       and self.config.hidden_dropout_prob > 0)
        # the memory guard's ladder can flip recompute on globally
        # without touching the model config
        from ..memory.guard import remat_enabled
        use_remat = self._recompute or remat_enabled()
        if (self.config.use_scan_layers and cache is None
                and not use_cache and not drop_active):
            from ..nn.layer import scanned
            x = scanned.scan_layer_stack(self.h, x,
                                         remat=use_remat)
            with block("head"):
                return self.ln_f(x)
        if (self.config.use_scan_layers and drop_active
                and not getattr(self, "_scan_fallback_warned", False)):
            self._scan_fallback_warned = True
            import logging
            logging.getLogger("paddle_tpu.models").warning(
                "use_scan_layers requires dropout == 0 (per-layer rng "
                "is not threaded through the scanned stack); falling "
                "back to the unrolled layer loop")
        new_caches = []
        for i, blk in enumerate(self.h):
            layer_cache = None if cache is None else cache[i]
            if use_cache:
                x, c = blk(x, layer_cache, True)
                new_caches.append(c)
            elif use_remat and layer_cache is None:
                from ..distributed.fleet.recompute import recompute
                x = recompute(blk, x)
            else:
                # a supplied cache participates even when the caller
                # doesn't want an updated one back
                x = blk(x, layer_cache)
        with block("head"):
            x = self.ln_f(x)
        if use_cache:
            return x, new_caches
        return x


def paged_kv_spec(cfg):
    """What each layer of a GPT keeps between steps, for the serving
    engine (inference/serving/engine.py reads its geometry from the
    model's ``cache_spec()``): paged K/V with as many KV heads as query
    heads."""
    return [{"kind": "paged_kv",
             "num_kv_heads": cfg.num_attention_heads,
             "head_dim": cfg.hidden_size // cfg.num_attention_heads}
            for _ in range(cfg.num_hidden_layers)]


class GPTForCausalLM(nn.Layer, GenerationMixin):
    def __init__(self, cfg: GPTConfig):
        super().__init__()
        self.gpt = GPTModel(cfg)
        if cfg.tie_word_embeddings:
            self.lm_head = None
        else:
            self.lm_head = nn.Linear(cfg.hidden_size, cfg.vocab_size,
                                     bias_attr=False)

    def cache_spec(self):
        return paged_kv_spec(self.gpt.config)

    def forward(self, input_ids, cache=None, use_cache=False):
        if use_cache:
            hidden, new_cache = self.gpt(input_ids, cache, True)
        else:
            hidden = self.gpt(input_ids, cache)
            new_cache = None
        with block("head"):
            if self.lm_head is not None:
                logits = self.lm_head(hidden)
            else:
                logits = paddle.matmul(hidden, self.gpt.wte.weight,
                                       transpose_y=True)
        if use_cache:
            return logits, new_cache
        return logits


class GPTPretrainingCriterion(nn.Layer):
    """Shifted next-token LM loss (ignore_index=-100 for padding)."""

    def forward(self, logits, labels):
        b, s, v = logits.shape
        with block("head"):
            logits = paddle.reshape(logits[:, :-1, :], [-1, v])
            labels = paddle.reshape(labels[:, 1:], [-1])
            return F.cross_entropy(logits, labels, reduction="mean")
