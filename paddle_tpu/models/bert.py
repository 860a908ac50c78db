"""BERT encoder + MLM head (BASELINE.md config #3: BERT-base MLM).

Reference parity: `paddlenlp/transformers/bert/modeling.py` [UNVERIFIED —
empty reference mount].
"""
from __future__ import annotations

from dataclasses import dataclass

import paddle_tpu as paddle
from .. import nn
from ..nn import functional as F
from ..nn import initializer as I
from ..observability import block


@dataclass
class BertConfig:
    vocab_size: int = 30522
    hidden_size: int = 768
    num_hidden_layers: int = 12
    num_attention_heads: int = 12
    intermediate_size: int = 3072
    max_position_embeddings: int = 512
    type_vocab_size: int = 2
    layer_norm_eps: float = 1e-12
    # published init (bert-base-uncased config.json): every weight
    # matrix ~ N(0, 0.02).  With the tied output head, the framework's
    # N(0, 1) embedding default put the loss at init far above
    # ln(vocab).
    initializer_range: float = 0.02
    # Paddle-parity defaults (paddlenlp BertConfig): dropout on the
    # embeddings, each sublayer output, and the attention probs.  The
    # static Executor threads the generator state per step, so dropout
    # works in static programs and the fused run_steps loop.
    hidden_dropout_prob: float = 0.1
    attention_probs_dropout_prob: float = 0.1
    # lax.scan over stacked layer weights: compile time O(1) in depth
    # (nn/layer/scanned.py); numerics identical to the unrolled loop.
    # Requires dropout == 0 (per-layer rng inside the scanned stack is
    # not threaded) — BertModel falls back to the unrolled loop loudly.
    use_scan_layers: bool = False


def _weight_attr(cfg):
    return paddle.ParamAttr(
        initializer=I.Normal(0.0, cfg.initializer_range))


class BertEmbeddings(nn.Layer):
    def __init__(self, cfg: BertConfig):
        super().__init__()
        self.word_embeddings = nn.Embedding(
            cfg.vocab_size, cfg.hidden_size, weight_attr=_weight_attr(cfg))
        self.position_embeddings = nn.Embedding(
            cfg.max_position_embeddings, cfg.hidden_size,
            weight_attr=_weight_attr(cfg))
        self.token_type_embeddings = nn.Embedding(
            cfg.type_vocab_size, cfg.hidden_size,
            weight_attr=_weight_attr(cfg))
        self.layer_norm = nn.LayerNorm(cfg.hidden_size,
                                       epsilon=cfg.layer_norm_eps)
        self.dropout = nn.Dropout(cfg.hidden_dropout_prob)

    def forward(self, input_ids, token_type_ids=None):
        b, s = input_ids.shape
        with block("embed"):
            pos = paddle.arange(s, dtype="int64")
            x = self.word_embeddings(input_ids) \
                + self.position_embeddings(pos)
            if token_type_ids is not None:
                x = x + self.token_type_embeddings(token_type_ids)
            return self.dropout(self.layer_norm(x))


class BertSelfAttention(nn.Layer):
    def __init__(self, cfg: BertConfig):
        super().__init__()
        self.num_heads = cfg.num_attention_heads
        self.head_dim = cfg.hidden_size // cfg.num_attention_heads
        self.attn_drop_p = cfg.attention_probs_dropout_prob
        self.qkv = nn.Linear(cfg.hidden_size, 3 * cfg.hidden_size,
                             weight_attr=_weight_attr(cfg))
        self.out = nn.Linear(cfg.hidden_size, cfg.hidden_size,
                             weight_attr=_weight_attr(cfg))

    def forward(self, x, attn_mask=None):
        b, s, h = x.shape
        qkv = paddle.reshape(self.qkv(x),
                             [b, s, 3, self.num_heads, self.head_dim])
        q, k, v = paddle.unbind(qkv, axis=2)
        out = F.scaled_dot_product_attention(
            q, k, v, attn_mask=attn_mask, dropout_p=self.attn_drop_p,
            training=self.training)
        return self.out(paddle.reshape(out, [b, s, h]))


class BertLayer(nn.Layer):
    def __init__(self, cfg: BertConfig):
        super().__init__()
        self.attention = BertSelfAttention(cfg)
        self.ln1 = nn.LayerNorm(cfg.hidden_size, epsilon=cfg.layer_norm_eps)
        self.fc1 = nn.Linear(cfg.hidden_size, cfg.intermediate_size,
                             weight_attr=_weight_attr(cfg))
        self.fc2 = nn.Linear(cfg.intermediate_size, cfg.hidden_size,
                             weight_attr=_weight_attr(cfg))
        self.ln2 = nn.LayerNorm(cfg.hidden_size, epsilon=cfg.layer_norm_eps)
        self.hidden_drop_p = cfg.hidden_dropout_prob

    def forward(self, x, attn_mask=None):
        # post-norm: hidden dropout and the residual adds fuse into the
        # LN kernel; fc1's bias+gelu fold into the matmul epilogue (both
        # TPU-gated)
        p = self.hidden_drop_p
        with block("attention"):
            x = self.ln1.forward_fused(self.attention(x, attn_mask), x, p)
        with block("ffn"):
            h = F.linear_act(x, self.fc1.weight, self.fc1.bias,
                             act="gelu_tanh")
            x = self.ln2.forward_fused(self.fc2(h), x, p)
        return x


class BertModel(nn.Layer):
    def __init__(self, cfg: BertConfig):
        super().__init__()
        self.config = cfg
        self.embeddings = BertEmbeddings(cfg)
        self.encoder = nn.LayerList([BertLayer(cfg)
                                     for _ in range(cfg.num_hidden_layers)])

    def forward(self, input_ids, token_type_ids=None, attn_mask=None):
        x = self.embeddings(input_ids, token_type_ids)
        cfg = self.config
        drop_active = self.training and (
            cfg.hidden_dropout_prob > 0
            or cfg.attention_probs_dropout_prob > 0)
        if cfg.use_scan_layers and attn_mask is None:
            if drop_active:
                if not getattr(self, "_scan_fallback_warned", False):
                    self._scan_fallback_warned = True
                    import logging
                    logging.getLogger("paddle_tpu.models").warning(
                        "use_scan_layers requires dropout == 0 "
                        "(per-layer rng is not threaded through the "
                        "scanned stack); falling back to the unrolled "
                        "layer loop")
            else:
                from ..nn.layer import scanned
                return scanned.scan_layer_stack(self.encoder, x)
        for layer in self.encoder:
            x = layer(x, attn_mask)
        return x


class TiedMLMHead(nn.Layer):
    """transform → gelu → LN → logits tied to the word embedding; the
    shared masked-LM head for BERT-family encoders (ERNIE reuses it)."""

    def __init__(self, cfg: BertConfig):
        super().__init__()
        self.transform = nn.Linear(cfg.hidden_size, cfg.hidden_size,
                                   weight_attr=_weight_attr(cfg))
        self.ln = nn.LayerNorm(cfg.hidden_size,
                               epsilon=cfg.layer_norm_eps)

    def forward(self, hidden, word_embedding_weight, labels=None):
        with block("head"):
            hidden = self.ln(F.linear_act(
                hidden, self.transform.weight, self.transform.bias,
                act="gelu_tanh"))
            logits = paddle.matmul(hidden, word_embedding_weight,
                                   transpose_y=True)
            if labels is None:
                return logits
            v = logits.shape[-1]
            loss = F.cross_entropy(paddle.reshape(logits, [-1, v]),
                                   paddle.reshape(labels, [-1]),
                                   ignore_index=-100, reduction="mean")
            return loss, logits


class BertForMaskedLM(nn.Layer):
    def __init__(self, cfg: BertConfig):
        super().__init__()
        self.bert = BertModel(cfg)
        self.cls = TiedMLMHead(cfg)

    def forward(self, input_ids, token_type_ids=None, labels=None):
        hidden = self.bert(input_ids, token_type_ids)
        return self.cls(hidden,
                        self.bert.embeddings.word_embeddings.weight,
                        labels)
