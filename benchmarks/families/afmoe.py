"""The ``afmoe`` family: builds the program's ``AfmoeForCausalLM`` from a
Trinity config file's keys and holds the plain reference forward,
written from the equations of ISSUE 32 (PERF.md section 4 repeats them):
sliding-window and full attention layers with grouped KV heads, q/k
RMSNorm and a sigmoid output gate; a sigmoid router with a
selection-only bias over routed SwiGLU experts beside a shared one;
four RMSNorms a block; the embedding times ``sqrt(hidden)``; an untied
head.

The reference is float32 ``jax.numpy`` at the highest matmul precision:
no kernel, no cache, no dispatch plan.  Attention runs one block of
queries at a time against every key, and an expert layer one block of
experts at a time over every token, each token's chosen experts picked
out by a mask, so that 14 K tokens fit beside the weights and no whole
expert stack ever stands in float32.  It takes the program's arrays by
name.

Also here, for the per-layer metrics: the operations and bytes of the
grouped expert kernel (``moe_*``) and of the attention kernel
(``attention_*``), from shapes and the program's counters.
"""
import functools
import math

import jax
import jax.numpy as jnp

from benchmarks.families import _plain
from benchmarks.families.minicpm_sala import (_CONFIGS, _freeze, rms_norm,
                                              rope)

SLIDING = "sliding_attention"
QUERY_BLOCK = 128          # queries an attention layer takes at a time
EXPERT_BLOCK = 8           # experts an expert layer widens at a time
MLP_ROWS = 2048            # rows a feed-forward takes at a time


def build(cfg):
    from paddle_tpu.models.afmoe import AfmoeConfig, AfmoeForCausalLM
    model = AfmoeForCausalLM(AfmoeConfig(
        dtype=cfg["dtype"],       # each layer cast as it is built
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        intermediate_size=cfg["intermediate_size"],
        moe_intermediate_size=cfg["moe_intermediate_size"],
        num_hidden_layers=cfg["num_hidden_layers"],
        num_dense_layers=cfg["num_dense_layers"],
        layer_types=tuple(cfg["layer_types"]),
        num_attention_heads=cfg["num_attention_heads"],
        num_key_value_heads=cfg["num_key_value_heads"],
        head_dim=cfg["head_dim"], sliding_window=cfg["sliding_window"],
        num_experts=cfg["num_experts"],
        num_experts_per_tok=cfg["num_experts_per_tok"],
        num_shared_experts=cfg["num_shared_experts"],
        route_norm=cfg["route_norm"], route_scale=cfg["route_scale"],
        score_func=cfg["score_func"], mup_enabled=cfg["mup_enabled"],
        max_position_embeddings=cfg["max_position_embeddings"],
        rms_norm_eps=cfg["rms_norm_eps"], rope_theta=cfg["rope_theta"],
        initializer_range=cfg["initializer_range"],
        expert_bias_std=cfg["expert_bias_std"],
        kv_block_size=cfg["kv_block_size"]))
    model.eval()
    return model


# ---------------------------------------------------------------------
# the plain reference
# ---------------------------------------------------------------------
def held_in(a, dtype, scale=None):
    """``a`` as a tensor held in ``dtype`` under a per-tensor ``scale``
    (as float8 is used; taken from ``a`` when not given) and widened
    again; ``None`` leaves it.  The reference at a lower precision is
    the check's control: it has to come out as not correct."""
    a = a.astype(jnp.float32)
    if dtype is None:
        return a
    if scale is None:
        scale = tensor_scale(a, dtype)
    return (a / scale).astype(dtype).astype(jnp.float32) * scale


def tensor_scale(a, dtype):
    return None if dtype is None else \
        jnp.abs(a).max().astype(jnp.float32) / float(jnp.finfo(dtype).max)


def attention(u, w, cfg, window):
    """``u`` [s, hidden] -> [s, hidden]: token ``t`` attends ``s`` with
    ``t - window < s <= t`` (``window`` None: ``s <= t``)."""
    s, eps = u.shape[0], cfg["rms_norm_eps"]
    nh, nkv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    g = nh // nkv
    q = rms_norm((u @ w["self_attn.q_proj.weight"]).reshape(s, nh, d),
                 w["self_attn.q_norm.weight"], eps)
    k = rms_norm((u @ w["self_attn.k_proj.weight"]).reshape(s, nkv, d),
                 w["self_attn.k_norm.weight"], eps)
    v = (u @ w["self_attn.v_proj.weight"]).reshape(s, nkv, d)
    if window is not None:               # positions: sliding layers only
        pos = jnp.arange(s)
        q, k = rope(q, pos, cfg["rope_theta"]), rope(k, pos,
                                                     cfg["rope_theta"])
    qb = min(QUERY_BLOCK, s)
    pad = -s % qb
    q_pad = jnp.pad(q.reshape(s, nkv, g, d),
                    ((0, pad), (0, 0), (0, 0), (0, 0)))
    t_pad = jnp.arange(s + pad)
    cols = jnp.arange(s)

    def one_block(args):
        q_b, t_b = args                                  # [qb, nkv, g, d]
        seen = cols[None, :] <= t_b[:, None]
        if window is not None:
            seen &= cols[None, :] > t_b[:, None] - window
        a = jnp.einsum("qngd,snd->nqgs", q_b, k) / math.sqrt(d)
        a = jax.nn.softmax(jnp.where(seen[None, :, None, :], a, -jnp.inf),
                           -1)
        return jnp.einsum("nqgs,snd->qngd", a, v)

    o = jax.lax.map(one_block, (q_pad.reshape(-1, qb, nkv, g, d),
                                t_pad.reshape(-1, qb)))
    o = o.reshape(-1, nh * d)[:s]
    o = o * jax.nn.sigmoid(u @ w["self_attn.g_proj.weight"])
    return o @ w["self_attn.o_proj.weight"]


def swiglu(rows, gate, up, down):
    return (jax.nn.silu(rows @ gate) * (rows @ up)) @ down


def route(u, w, cfg):
    """``[s, E]`` float32: each token's weight on each expert, zero
    outside its chosen set.  The set is the ``k`` largest of ``sigmoid +
    bias`` (ties to the lower index); the weight is the sigmoid alone,
    over the chosen sum, times ``route_scale``."""
    scores = jax.nn.sigmoid(u @ w["mlp.router.weight"])
    choice = scores + w["mlp.expert_bias"]
    # rank by falling choice, the lower index first among equals
    order = jnp.argsort(-choice, axis=-1, stable=True)
    rank = jnp.argsort(order, axis=-1, stable=True)
    kept = jnp.where(rank < cfg["num_experts_per_tok"], scores, 0.0)
    if cfg["route_norm"]:
        kept = kept / (kept.sum(-1, keepdims=True) + 1e-20)
    return cfg["route_scale"] * kept


def expert_ffn(u, w, stacks, cfg, low):
    """``Shared(u) + sum_e weight[t, e] * Expert_e(u)``: every expert
    over every token, ``EXPERT_BLOCK`` experts widened at a time, the
    unchosen ones weighted zero.  ``stacks`` are the two expert stacks in
    the type they are held in, with their per-tensor scales."""
    (gate_up, gu_scale), (down, dn_scale) = stacks
    E, _, two_w = gate_up.shape
    width = two_w // 2
    weight = route(u, w, cfg)                            # [s, E]
    eb = math.gcd(E, EXPERT_BLOCK)
    blocks = (gate_up.reshape(E // eb, eb, *gate_up.shape[1:]),
              down.reshape(E // eb, eb, *down.shape[1:]),
              weight.T.reshape(E // eb, eb, -1))

    def one_block(total, args):
        gu, dn, wt = args
        gu = low(gu, scale=gu_scale)
        dn = low(dn, scale=dn_scale)
        h = jnp.einsum("sd,edf->esf", u, gu)
        y = jnp.einsum("esw,ewd->esd",
                       jax.nn.silu(h[..., :width]) * h[..., width:], dn)
        return total + (wt[:, :, None] * y).sum(0), None

    routed, _ = jax.lax.scan(one_block, jnp.zeros_like(u), blocks)
    shared = swiglu(u, w["mlp.shared.gate_proj.weight"],
                    w["mlp.shared.up_proj.weight"],
                    w["mlp.shared.down_proj.weight"])
    return shared + routed


@functools.partial(jax.jit, static_argnames=(
    "window", "routed", "cfg_key", "compute_dtype"))
def _layer(x, w, stacks, window, routed, cfg_key, compute_dtype=None):
    """One block.  With ``compute_dtype`` the weights, what enters the
    projections and what each half of the block hands on are held in
    that type; the sums stay float32."""
    cfg = _CONFIGS[cfg_key]
    low = functools.partial(held_in, dtype=compute_dtype)
    w = jax.tree_util.tree_map(low, w)
    eps = cfg["rms_norm_eps"]
    with jax.default_matmul_precision("highest"):
        u = low(rms_norm(x, w["input_layernorm.weight"], eps))
        h = x + low(rms_norm(attention(u, w, cfg, window),
                             w["post_attention_layernorm.weight"], eps))
        m = low(rms_norm(h, w["pre_mlp_layernorm.weight"], eps))

        def ffn(rows):
            if routed:
                return expert_ffn(rows, w, stacks, cfg, low)
            return swiglu(rows, w["mlp.gate_proj.weight"],
                          w["mlp.up_proj.weight"],
                          w["mlp.down_proj.weight"])

        # MLP_ROWS at a time: an expert block's [8, rows, 2 * width]
        # float32 intermediate is 134 MB at 2,048 rows
        blocked = m.shape[0] % MLP_ROWS == 0 and m.shape[0] > MLP_ROWS
        m = jax.lax.map(ffn, m.reshape(-1, MLP_ROWS, m.shape[1])) \
            .reshape(m.shape) if blocked else ffn(m)
        return h + low(rms_norm(m, w["post_mlp_layernorm.weight"], eps))


_STACKS = ("mlp.experts.gate_up", "mlp.experts.down")


def reference_hidden(params, cfg, ids, compute_dtype=None):
    """Final hidden states after the last RMSNorm, ``[s, hidden]``
    float32, for one sequence ``ids`` [s]: one jitted function a layer
    kind, a layer's weights widened to float32 only while it runs (the
    expert stacks a block of experts at a time).  With
    ``compute_dtype`` (see `_layer`) the reference at that precision."""
    key, prefix = _freeze(cfg), "model.layers"
    x = math.sqrt(cfg["hidden_size"]) if cfg["mup_enabled"] else 1.0
    x = x * params["model.embed_tokens.weight"][ids].astype(jnp.float32)
    for i, kind in enumerate(cfg["layer_types"]):
        head = f"{prefix}.{i}."
        w = {k[len(head):]: a for k, a in params.items()
             if k.startswith(head)}
        routed = i >= cfg["num_dense_layers"]
        stacks = None
        if routed:
            stacks = tuple((w[k], tensor_scale(w[k], compute_dtype))
                           for k in _STACKS)
            w = {k: a for k, a in w.items() if k not in _STACKS}
        x = _layer(x, w, stacks,
                   window=cfg["sliding_window"] if kind == SLIDING else None,
                   routed=routed, cfg_key=key, compute_dtype=compute_dtype)
    return rms_norm(x, params["model.norm.weight"].astype(jnp.float32),
                    cfg["rms_norm_eps"])


def reference_head(params, cfg, hidden, compute_dtype=None):
    """Logits of the rows ``hidden`` [n, hidden]: the untied head."""
    with jax.default_matmul_precision("highest"):
        return held_in(hidden, compute_dtype) @ held_in(
            params["lm_head.weight"], compute_dtype)


def reference_logits(params, cfg, ids):
    """Next-token logits ``[b, s, vocab]``, the harness's contract; for
    sizes at which every position's logits fit."""
    return jnp.stack([reference_head(params, cfg,
                                     reference_hidden(params, cfg, row))
                      for row in ids])


# ---------------------------------------------------------------------
# the kernels' work, from shapes and the program's counters
# ---------------------------------------------------------------------
def moe_flops(assignments, experts_touched, cfg):
    """Operations of the routed experts: gate, up and down projection of
    every (token, expert) assignment the steps dispatched
    (``moe_assignments``: carried rows times top-k, summed over the
    expert layers)."""
    return assignments * 3 * 2 * cfg["hidden_size"] \
        * cfg["moe_intermediate_size"]


def moe_bytes(assignments, experts_touched, cfg, itemsize=2):
    """Bytes the grouped kernel must move: the three matrices of every
    expert with at least one row (``moe_experts_touched``, summed over
    layers and steps), each read once, and every assignment's row in and
    out of both calls."""
    d, width = cfg["hidden_size"], cfg["moe_intermediate_size"]
    return (experts_touched * 3 * d * width
            + assignments * 2 * (d + width)) * itemsize


def attention_bytes(window_blocks, full_blocks, cfg, itemsize=2):
    """Bytes of the attention kernel's reads: K and V of every block a
    row (or a chunk's q-block) read, all KV heads (the program's
    counters ``kv_blocks_read_window`` + ``kv_blocks_read_full``, summed
    over rows and layers)."""
    return (window_blocks + full_blocks) * cfg["kv_block_size"] \
        * cfg["num_key_value_heads"] * cfg["head_dim"] * 2 * itemsize


def attention_flops(window_blocks, full_blocks, cfg):
    """``Q K^T`` and ``P V`` of one query token against every block
    read, all query heads: a floor, since a chunk's q-block holds many
    query tokens and is counted as one."""
    return (window_blocks + full_blocks) * cfg["kv_block_size"] \
        * cfg["num_attention_heads"] * cfg["head_dim"] * 4
