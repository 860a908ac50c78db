"""Plain float32 ``jax.numpy`` pieces the reference forwards share.  No
kernel, no cache, no program code: only what the published equations
say."""
import jax
import jax.numpy as jnp


def layer_norm(x, weight, bias, eps):
    mean = x.mean(-1, keepdims=True)
    var = ((x - mean) ** 2).mean(-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * weight + bias


def gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        0.7978845608028654 * (x + 0.044715 * x ** 3)))


def attention(qkv, heads, causal):
    """``qkv`` is ``[b, s, 3*h]`` laid out ``(3, heads, head_dim)``, as
    both model files reshape it."""
    b, s, h3 = qkv.shape
    q, k, v = jnp.moveaxis(qkv.reshape(b, s, 3, heads, h3 // 3 // heads),
                           2, 0)                      # [b, s, nh, hd]
    scores = jnp.einsum("bqnd,bknd->bnqk", q, k) / jnp.sqrt(
        jnp.float32(q.shape[-1]))
    if causal:
        scores = jnp.where(jnp.tril(jnp.ones((s, s), bool)), scores,
                           -jnp.inf)
    out = jnp.einsum("bnqk,bknd->bqnd", jax.nn.softmax(scores, -1), v)
    return out.reshape(b, s, h3 // 3)


def split_layers(params, prefix, n_layers):
    """``({suffix: [n_layers, ...]}, rest)``: the parameters named
    ``<prefix>.<i>.<suffix>`` stacked in the type they are held in, so
    that the layers run under one ``lax.scan`` (one layer is compiled,
    not ``n_layers``), and the others widened to float32."""
    suffixes = [k[len(prefix) + 3:] for k in params
                if k.startswith(prefix + ".0.")]
    layers = {s: jnp.stack([params[f"{prefix}.{i}.{s}"]
                            for i in range(n_layers)]) for s in suffixes}
    return layers, f32({k: v for k, v in params.items()
                        if not k.startswith(prefix + ".")})


def f32(tree):
    """Widen to float32 where it is used (inside the scanned layer, so
    that only one layer of a bf16 model is held in float32 at a time)."""
    return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), tree)


def arrays(model):
    """The program's parameter arrays, by name, in the type they are
    held in."""
    out = {}
    for name, p in model.named_parameters():
        v = p.value()
        out[name] = v.force() if hasattr(v, "force") else v  # lazy tier
    return out
