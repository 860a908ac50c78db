"""The ``gpt2`` family: builds the program's ``GPTForCausalLM`` from a
GPT-2 config file's keys, cast to the served dtype, and holds the plain
reference forward."""
import jax
import jax.numpy as jnp

from benchmarks.families import _plain


def build(cfg):
    from paddle_tpu.models import GPTConfig, GPTForCausalLM
    if cfg["activation_function"] != "gelu_new" \
            or cfg["layer_norm_epsilon"] != 1e-5:
        raise ValueError("the program's GPT computes gelu_new (tanh) "
                         "and LayerNorm eps 1e-5 only")
    model = GPTForCausalLM(GPTConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["n_embd"],
        num_hidden_layers=cfg["n_layer"],
        num_attention_heads=cfg["n_head"],
        max_position_embeddings=cfg["n_positions"],
        tie_word_embeddings=cfg["tie_word_embeddings"]))
    # The program's GPT draws its embeddings from N(0, 1).  Under the
    # tied head the input token's own embedding then outweighs every
    # other logit by tens of standard deviations, greedy decoding
    # repeats its last token, and a logit-margin check sees nothing.
    # The published initializer_range keeps the logits of one scale.
    for table in (model.gpt.wte, model.gpt.wpe):
        table.weight.set_value(table.weight * cfg["initializer_range"])
    if cfg["dtype"] == "bfloat16":
        model = model.bfloat16()
    model.eval()
    return model


def reference_logits(params, cfg, ids):
    """Next-token logits ``[b, s, vocab]`` in float32 at the highest
    matmul precision: pre-norm blocks, causal attention, ``gelu_new``,
    final norm, head tied to the token embedding (Radford et al. 2019)."""
    eps, heads = cfg["layer_norm_epsilon"], cfg["n_head"]
    layers, params = _plain.split_layers(params, "gpt.h", cfg["n_layer"])

    def block(x, w):
        w = _plain.f32(w)
        a = _plain.layer_norm(x, w["ln_1.weight"], w["ln_1.bias"], eps)
        a = _plain.attention(a @ w["attn.qkv_proj.weight"]
                             + w["attn.qkv_proj.bias"], heads, True)
        x = x + a @ w["attn.out_proj.weight"] + w["attn.out_proj.bias"]
        m = _plain.layer_norm(x, w["ln_2.weight"], w["ln_2.bias"], eps)
        m = _plain.gelu_tanh(m @ w["mlp.fc1.weight"] + w["mlp.fc1.bias"])
        return x + m @ w["mlp.fc2.weight"] + w["mlp.fc2.bias"], None

    with jax.default_matmul_precision("highest"):
        wte = params["gpt.wte.weight"]
        x = wte[ids] + params["gpt.wpe.weight"][:ids.shape[1]]
        x, _ = jax.lax.scan(block, x, layers)
        x = _plain.layer_norm(x, params["gpt.ln_f.weight"],
                              params["gpt.ln_f.bias"], eps)
        return x @ wte.T
