"""The ``qwen3_next`` family: builds the program's
``Qwen3NextForCausalLM`` from a Qwen3-Next config file's keys and holds
the plain reference forward, written from the equations of ISSUE 35
(PERF.md section 4 repeats them): Gated DeltaNet layers (a causal
depthwise convolution, then the gated delta rule, arXiv:2412.06464) and
gated softmax attention layers with rotary positions on a quarter of
the head, three to one; a softmax router with renormalised top-k over
routed SwiGLU experts beside a shared expert behind a sigmoid gate;
zero-centred RMSNorms; an untied head.

The reference is float32 ``jax.numpy`` at the highest matmul precision:
no kernel, no cache, no chunked form, no dispatch plan.  A delta layer
is the token-by-token recurrence (``lax.scan``); attention runs one
block of queries at a time against every key; an expert layer widens a
few of the held experts at a time over every token, each token's chosen
experts picked out by a mask.  It is given the same share of the
deployment as the program: the router chooses among all the published
experts, only the terms of the held ones ``[lo, hi)`` are summed, and
the vocabulary is the slice the tables hold.  It takes the program's
arrays by name.

Also here, for the per-layer metrics: the operations and bytes of the
two gated delta rule kernels (``gated_delta_*``), from shapes and the
program's counters; those of the grouped expert kernel (``moe_*``) and
of the attention kernel (``attention_*``) are ``families/afmoe.py``'s.
"""
import functools
import math

import jax
import jax.numpy as jnp

from benchmarks.families.afmoe import (attention_bytes,  # noqa: F401
                                       attention_flops, held_in, moe_bytes,
                                       moe_flops, swiglu, tensor_scale)
from benchmarks.families.minicpm_sala import _CONFIGS, _freeze

QUERY_BLOCK = 128          # queries an attention layer takes at a time
EXPERT_BLOCK = 8           # experts an expert layer widens at a time
MLP_ROWS = 2048            # rows a feed-forward takes at a time
L2_EPS = 1e-6


def held_experts(cfg):
    """``(lo, hi)``: the experts of each layer that this chip holds."""
    shard = cfg["expert_shard"]
    share = cfg["published_num_experts"] // shard["chips"]
    return shard["index"] * share, (shard["index"] + 1) * share


def is_attention(cfg, index):
    return (index + 1) % cfg["full_attention_interval"] == 0


def build(cfg):
    from paddle_tpu.models.qwen3_next import (Qwen3NextConfig,
                                              Qwen3NextForCausalLM)
    shard = cfg["expert_shard"]
    lo, hi = held_experts(cfg)
    if hi - lo != cfg["num_experts"]:
        raise ValueError(
            f"num_experts {cfg['num_experts']} is not one chip's share of "
            f"{cfg['published_num_experts']} over {shard['chips']}")
    model = Qwen3NextForCausalLM(Qwen3NextConfig(
        dtype=cfg["dtype"],       # each layer cast as it is built
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        num_hidden_layers=cfg["num_hidden_layers"],
        full_attention_interval=cfg["full_attention_interval"],
        num_attention_heads=cfg["num_attention_heads"],
        num_key_value_heads=cfg["num_key_value_heads"],
        head_dim=cfg["head_dim"],
        partial_rotary_factor=cfg["partial_rotary_factor"],
        rope_theta=cfg["rope_theta"],
        linear_conv_kernel_dim=cfg["linear_conv_kernel_dim"],
        linear_key_head_dim=cfg["linear_key_head_dim"],
        linear_num_key_heads=cfg["linear_num_key_heads"],
        linear_num_value_heads=cfg["linear_num_value_heads"],
        linear_value_head_dim=cfg["linear_value_head_dim"],
        moe_intermediate_size=cfg["moe_intermediate_size"],
        shared_expert_intermediate_size=cfg[
            "shared_expert_intermediate_size"],
        num_experts=cfg["published_num_experts"],
        num_experts_per_tok=cfg["num_experts_per_tok"],
        norm_topk_prob=cfg["norm_topk_prob"],
        expert_shard=(shard["chips"], shard["index"]),
        max_position_embeddings=cfg["max_position_embeddings"],
        rms_norm_eps=cfg["rms_norm_eps"],
        initializer_range=cfg["initializer_range"],
        norm_weight_std=cfg["norm_weight_std"],
        a_range=tuple(cfg["a_range"]), dt_range=tuple(cfg["dt_range"]),
        kv_block_size=cfg["kv_block_size"]))
    model.eval()
    return model


# ---------------------------------------------------------------------
# the plain reference
# ---------------------------------------------------------------------
def norm(x, weight, eps):
    """``x rsqrt(mean(x^2) + eps) (1 + w)``."""
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) \
        * (1.0 + weight)


def partial_rope(x, positions, theta, rotary_dim):
    """Rotate-half RoPE on lanes ``[0, rotary_dim)`` of ``x`` [s, heads,
    d] at absolute ``positions`` [s]; the other lanes pass."""
    inv = 1.0 / theta ** (jnp.arange(0, rotary_dim, 2, dtype=jnp.float32)
                          / rotary_dim)
    ang = positions.astype(jnp.float32)[:, None] * inv[None, :]
    ang = jnp.concatenate([ang, ang], -1)[:, None, :]
    turned, rest = x[..., :rotary_dim], x[..., rotary_dim:]
    half = rotary_dim // 2
    rot = jnp.concatenate([-turned[..., half:], turned[..., :half]], -1)
    return jnp.concatenate(
        [turned * jnp.cos(ang) + rot * jnp.sin(ang), rest], -1)


def gated_attention(u, w, cfg):
    """``u`` [s, hidden] -> [s, hidden]: causal softmax attention, each
    KV head serving its group of query heads, under the output gate that
    ``q_proj`` carries."""
    s, eps = u.shape[0], cfg["rms_norm_eps"]
    nh, nkv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    g = nh // nkv
    qg = (u @ w["self_attn.q_proj.weight"]).reshape(s, nh, 2 * d)
    q, gate = qg[..., :d], qg[..., d:].reshape(s, nh * d)
    q = norm(q, w["self_attn.q_norm.weight"], eps)
    k = norm((u @ w["self_attn.k_proj.weight"]).reshape(s, nkv, d),
             w["self_attn.k_norm.weight"], eps)
    v = (u @ w["self_attn.v_proj.weight"]).reshape(s, nkv, d)
    pos = jnp.arange(s)
    rotary = int(d * cfg["partial_rotary_factor"])
    q = partial_rope(q, pos, cfg["rope_theta"], rotary)
    k = partial_rope(k, pos, cfg["rope_theta"], rotary)
    qb = min(QUERY_BLOCK, s)
    pad = -s % qb
    q_pad = jnp.pad(q.reshape(s, nkv, g, d),
                    ((0, pad), (0, 0), (0, 0), (0, 0)))
    t_pad = jnp.arange(s + pad)
    cols = jnp.arange(s)

    def one_block(args):
        q_b, t_b = args                                  # [qb, nkv, g, d]
        seen = cols[None, :] <= t_b[:, None]
        a = jnp.einsum("qngd,snd->nqgs", q_b, k) / math.sqrt(d)
        a = jax.nn.softmax(jnp.where(seen[None, :, None, :], a, -jnp.inf),
                           -1)
        return jnp.einsum("nqgs,snd->qngd", a, v)

    o = jax.lax.map(one_block, (q_pad.reshape(-1, qb, nkv, g, d),
                                t_pad.reshape(-1, qb)))
    o = o.reshape(-1, nh * d)[:s]
    return (o * jax.nn.sigmoid(gate)) @ w["self_attn.o_proj.weight"]


def l2norm(x):
    return x * jax.lax.rsqrt((x * x).sum(-1, keepdims=True) + L2_EPS)


def gated_delta_net(u, w, cfg):
    """``u`` [s, hidden] -> [s, hidden]: the convolution, then the
    recurrence token by token."""
    s, eps = u.shape[0], cfg["rms_norm_eps"]
    hk, hv = cfg["linear_num_key_heads"], cfg["linear_num_value_heads"]
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    width = cfg["linear_conv_kernel_dim"]
    x = u @ w["linear_attn.in_proj_qkv.weight"]          # [s, channels]
    padded = jnp.pad(x, ((width - 1, 0), (0, 0)))
    taps = w["linear_attn.conv_weight"]                  # [width, ch]
    x = jax.nn.silu(sum(taps[i] * padded[i:i + s] for i in range(width)))
    nq = hk * dk
    q = l2norm(x[:, :nq].reshape(s, hk, dk)) / math.sqrt(dk)
    k = l2norm(x[:, nq:2 * nq].reshape(s, hk, dk))
    v = x[:, 2 * nq:].reshape(s, hv, dv)
    q, k = jnp.repeat(q, hv // hk, 1), jnp.repeat(k, hv // hk, 1)
    ba = u @ w["linear_attn.in_proj_ba.weight"]
    beta = jax.nn.sigmoid(ba[:, :hv])
    g = -jnp.exp(w["linear_attn.A_log"]) * jax.nn.softplus(
        ba[:, hv:] + w["linear_attn.dt_bias"])

    def step(state, xs):
        q_t, k_t, v_t, g_t, b_t = xs
        state = jnp.exp(g_t)[:, None, None] * state
        delta = b_t[:, None] * (v_t - jnp.einsum("hde,hd->he", state, k_t))
        state = state + k_t[:, :, None] * delta[:, None, :]
        o_t = jnp.einsum("hde,hd->he", state, q_t)
        return state, o_t

    _, o = jax.lax.scan(step, jnp.zeros((hv, dk, dv), jnp.float32),
                        (q, k, v, g, beta))
    z = (u @ w["linear_attn.in_proj_z.weight"]).reshape(s, hv, dv)
    o = o * jax.lax.rsqrt((o * o).mean(-1, keepdims=True) + eps) \
        * w["linear_attn.norm_weight"] * jax.nn.silu(z)
    return o.reshape(s, hv * dv) @ w["linear_attn.out_proj.weight"]


def route(u, w, cfg):
    """``[s, E]`` float32 over all the published experts: each token's
    weight on each, zero outside its chosen set.  The set is the ``k``
    most probable (ties to the lower index); the weight is the
    probability over the chosen probabilities' sum."""
    p = jax.nn.softmax(u @ w["mlp.router.weight"], -1)
    # rank by falling probability, the lower index first among equals
    order = jnp.argsort(-p, axis=-1, stable=True)
    rank = jnp.argsort(order, axis=-1, stable=True)
    kept = jnp.where(rank < cfg["num_experts_per_tok"], p, 0.0)
    if cfg["norm_topk_prob"]:
        kept = kept / kept.sum(-1, keepdims=True)
    return kept


def expert_ffn(u, w, stacks, cfg, low, held=None):
    """``sigmoid(u w_s) Shared(u) + sum over the held e of weight[t, e]
    Expert_e(u)``: every held expert over every token, ``EXPERT_BLOCK``
    of them widened at a time, the unchosen ones weighted zero.
    ``stacks`` are the two stacks of the held experts in the type they
    are held in, with their per-tensor scales."""
    (gate_up, gu_scale), (down, dn_scale) = stacks
    lo, hi = held or held_experts(cfg)
    E, _, two_w = gate_up.shape
    width = two_w // 2
    weight = route(u, w, cfg)[:, lo:hi]                  # [s, held]
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
    shared = swiglu(u, w["mlp.shared_expert.gate_proj.weight"],
                    w["mlp.shared_expert.up_proj.weight"],
                    w["mlp.shared_expert.down_proj.weight"])
    return jax.nn.sigmoid(u @ w["mlp.shared_expert_gate.weight"]) * shared \
        + routed


@functools.partial(jax.jit, static_argnames=(
    "attention", "cfg_key", "compute_dtype"))
def _layer(x, w, stacks, attention, cfg_key, compute_dtype=None):
    """One block.  With ``compute_dtype`` the weights, what enters the
    projections and what each half of the block hands on are held in
    that type; the sums stay float32."""
    cfg = _CONFIGS[cfg_key]
    low = functools.partial(held_in, dtype=compute_dtype)
    w = jax.tree_util.tree_map(low, w)
    eps = cfg["rms_norm_eps"]
    with jax.default_matmul_precision("highest"):
        u = low(norm(x, w["input_layernorm.weight"], eps))
        mixed = gated_attention(u, w, cfg) if attention \
            else gated_delta_net(u, w, cfg)
        h = x + low(mixed)
        m = low(norm(h, w["post_attention_layernorm.weight"], eps))
        ffn = lambda rows: expert_ffn(rows, w, stacks, cfg,  # noqa: E731
                                      low)
        # MLP_ROWS at a time: an expert block's [8, rows, 2 * width]
        # float32 intermediate is 67 MB at 2,048 rows
        blocked = m.shape[0] % MLP_ROWS == 0 and m.shape[0] > MLP_ROWS
        m = jax.lax.map(ffn, m.reshape(-1, MLP_ROWS, m.shape[1])) \
            .reshape(m.shape) if blocked else ffn(m)
        return h + low(m)


_STACKS = ("mlp.experts.gate_up", "mlp.experts.down")


def reference_hidden(params, cfg, ids, compute_dtype=None):
    """Final hidden states after the last norm, ``[s, hidden]`` float32,
    for one sequence ``ids`` [s]: one jitted function a layer kind, a
    layer's weights widened to float32 only while it runs (the expert
    stacks a block of experts at a time).  With ``compute_dtype`` (see
    `_layer`) the reference at that precision."""
    key, prefix = _freeze(cfg), "model.layers"
    x = params["model.embed_tokens.weight"][ids].astype(jnp.float32)
    for i in range(cfg["num_hidden_layers"]):
        head = f"{prefix}.{i}."
        w = {k[len(head):]: a for k, a in params.items()
             if k.startswith(head)}
        stacks = tuple((w[k], tensor_scale(w[k], compute_dtype))
                       for k in _STACKS)
        w = {k: a for k, a in w.items() if k not in _STACKS}
        x = _layer(x, w, stacks, attention=is_attention(cfg, i),
                   cfg_key=key, compute_dtype=compute_dtype)
    return norm(x, params["model.norm.weight"].astype(jnp.float32),
                cfg["rms_norm_eps"])


def reference_head(params, cfg, hidden, compute_dtype=None):
    """Logits of the rows ``hidden`` [n, hidden]: the untied head over
    the vocabulary rows held here."""
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
# ``moe_flops / moe_bytes`` and ``attention_flops / attention_bytes`` are
# ``families/afmoe.py``'s, by import: the same kernels do the same work
# a unit of the same counters.  Here ``moe_assignments`` and
# ``moe_experts_touched`` count the held experts alone, and no layer has
# a window, so ``kv_blocks_read_window`` is 0.


def _delta_layers(cfg):
    return sum(not is_attention(cfg, i)
               for i in range(cfg["num_hidden_layers"]))


def gated_delta_bytes(rows, chunk_tokens, chunks, cfg, itemsize=2):
    """HBM bytes the delta layers' two kernels must move for ``rows``
    decode rows and ``chunk_tokens`` prompt tokens in ``chunks`` chunks
    (each counted once, as the engine's steps carried them): a float32
    state a value head read and written once a row and once a chunk; q
    and k (key heads), v in and o out (value heads) once a token, in the
    model's type; g and beta in float32."""
    hk, hv = cfg["linear_num_key_heads"], cfg["linear_num_value_heads"]
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    state = 2 * hv * dk * dv * 4 * (rows + chunks)
    token = (2 * hk * dk + 2 * hv * dv) * itemsize + 2 * hv * 4
    return _delta_layers(cfg) * (state + token * (rows + chunk_tokens))


def gated_delta_flops(rows, chunk_tokens, chunks, cfg, block=64):
    """Operations, a value head: a decode row is the decay, the
    prediction ``S^T k``, the rank-one write and the read ``S^T q`` (``6
    d_k d_v + d_k d_v``); a chunk in sub-chunks of ``block`` rows is ``K
    K^T`` and ``Q K^T`` (``4 block^2 d_k``), ``K S``, ``Q S`` and ``K^T
    U`` (``6 block d_k d_v``), the solve as a product with the inverse
    and ``(Q K^T) U`` (``4 block^2 d_v``).  The inverse's own products
    are the algorithm's choice and are not counted."""
    hv = cfg["linear_num_value_heads"]
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    blocks = -(-chunk_tokens // block)
    return _delta_layers(cfg) * hv * (
        rows * 7 * dk * dv
        + blocks * (4 * block * block * dk + 6 * block * dk * dv
                    + 4 * block * block * dv))
