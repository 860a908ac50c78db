"""The ``bert`` family: builds the program's ``BertForMaskedLM`` from a
config file's keys, holds the plain reference forward and the FLOPs a
training token needs."""
import jax
import jax.numpy as jnp

from benchmarks.families import _plain

FEEDS = ("ids", "labels")


def build(cfg):
    from paddle_tpu.models import BertConfig, BertForMaskedLM
    if cfg["hidden_act"] != "gelu_tanh":
        raise ValueError("the program's BERT computes gelu_tanh only")
    return BertForMaskedLM(BertConfig(**{k: cfg[k] for k in (
        "vocab_size", "hidden_size", "num_hidden_layers",
        "num_attention_heads", "intermediate_size",
        "max_position_embeddings", "type_vocab_size", "layer_norm_eps",
        "initializer_range", "hidden_dropout_prob",
        "attention_probs_dropout_prob")}))


def loss(model, feeds):
    return model(feeds["ids"], labels=feeds["labels"])[0]


def logits(model, ids):
    return model(ids)


def reference_logits(params, cfg, ids):
    """MLM logits ``[b, s, vocab]`` in float32 at the highest matmul
    precision, from the published equations (post-norm encoder, tied
    head) with the departures the config file lists under ``changed``."""
    eps, heads = cfg["layer_norm_eps"], cfg["num_attention_heads"]
    emb = "bert.embeddings."
    layers, params = _plain.split_layers(params, "bert.encoder",
                                         cfg["num_hidden_layers"])

    def layer(x, w):
        w = _plain.f32(w)
        a = _plain.attention(x @ w["attention.qkv.weight"]
                             + w["attention.qkv.bias"], heads, False)
        a = a @ w["attention.out.weight"] + w["attention.out.bias"]
        x = _plain.layer_norm(a + x, w["ln1.weight"], w["ln1.bias"], eps)
        f = _plain.gelu_tanh(x @ w["fc1.weight"] + w["fc1.bias"])
        f = f @ w["fc2.weight"] + w["fc2.bias"]
        return _plain.layer_norm(f + x, w["ln2.weight"], w["ln2.bias"],
                                 eps), None

    with jax.default_matmul_precision("highest"):
        wte = params[emb + "word_embeddings.weight"]
        x = wte[ids] + params[emb + "position_embeddings.weight"][
            :ids.shape[1]]
        x = _plain.layer_norm(x, params[emb + "layer_norm.weight"],
                              params[emb + "layer_norm.bias"], eps)
        x, _ = jax.lax.scan(layer, x, layers)
        t = _plain.gelu_tanh(x @ params["cls.transform.weight"]
                             + params["cls.transform.bias"])
        t = _plain.layer_norm(t, params["cls.ln.weight"],
                              params["cls.ln.bias"], eps)
        return t @ wte.T


def train_flops_per_token(cfg, seq):
    """Forward and backward: 6 per matmul weight (the tied head's
    ``vocab x hidden`` counts once, as the one matmul it is; the
    embedding lookups are not matmuls) plus attention's two
    ``seq x seq`` products, ``12 * layers * seq * hidden`` (bench.py's
    arithmetic).  Recomputation would not count; none is on."""
    h, i = cfg["hidden_size"], cfg["intermediate_size"]
    layers = cfg["num_hidden_layers"]
    weights = layers * (4 * h * h + 2 * h * i) + h * h \
        + cfg["vocab_size"] * h
    return 6 * weights + 12 * layers * seq * h
