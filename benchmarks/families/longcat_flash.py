"""The ``longcat_flash`` family: builds the program's
``LongcatFlashForCausalLM`` from a LongCat-Flash config file's keys and
holds the plain reference forward, written from the equations of ISSUE
40 (PERF.md section 4 and the config file's ``assumed`` repeat them):
double layers of two latent-attention (MLA) sublayers and two dense
SwiGLU MLPs with a shortcut expert layer beside them; a softmax router
over the routed and the zero-compute experts with a choice-only bias and
a fixed scale; RMSNorms with a plain weight; an untied head.

The reference is float32 ``jax.numpy`` at the highest matmul precision:
no kernel, no cache, no absorbed form, no dispatch plan.  MLA is
computed as written: each head's keys ``[c W_UK_h | RoPE(k_rope)]`` and
values ``c W_UV_h`` up-projected from the latent, RoPE turning each
lane pair in place, one block of queries at a time against every key.
An expert layer widens a few of the held experts at a time over every
token, each token's chosen experts picked out by a mask, and adds the
zero experts' weighted input.  It is given the same share of the
deployment as the program: the router chooses among all the published
experts and the zero experts, only the terms of the held experts ``[lo,
hi)`` are summed, and the vocabulary is the slice the tables hold.  It
takes the program's arrays by name.

Also here, for the per-layer metrics: the operations and bytes of the
grouped expert kernel (``moe_*``) and of the latent attention kernel
(``latent_attention_*``), from shapes and the program's counters.
"""
import functools
import math

import jax
import jax.numpy as jnp

# the parent of this change has no such module: a cell of this family
# fails there at once, before any line of the contract is printed
from paddle_tpu.models.longcat_flash import (LongcatFlashConfig,
                                             LongcatFlashForCausalLM)

from benchmarks.families.afmoe import held_in, swiglu, tensor_scale
from benchmarks.families.minicpm_sala import _CONFIGS, _freeze, rms_norm

QUERY_BLOCK = 128          # queries an MLA sublayer takes at a time
EXPERT_BLOCK = 8           # experts an expert layer widens at a time
MLP_ROWS = 2048            # rows a feed-forward takes at a time


def held_experts(cfg):
    """``(lo, hi)``: the experts of each layer that this chip holds."""
    shard = cfg["expert_shard"]
    share = cfg["published_n_routed_experts"] // shard["chips"]
    return shard["index"] * share, (shard["index"] + 1) * share


def build(cfg):
    shard = cfg["expert_shard"]
    lo, hi = held_experts(cfg)
    if hi - lo != cfg["n_routed_experts"]:
        raise ValueError(
            f"n_routed_experts {cfg['n_routed_experts']} is not one chip's "
            f"share of {cfg['published_n_routed_experts']} over "
            f"{shard['chips']}")
    model = LongcatFlashForCausalLM(LongcatFlashConfig(
        dtype=cfg["dtype"],       # each layer cast as it is built
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        ffn_hidden_size=cfg["ffn_hidden_size"],
        expert_ffn_hidden_size=cfg["expert_ffn_hidden_size"],
        num_layers=cfg["num_layers"],
        num_attention_heads=cfg["num_attention_heads"],
        q_lora_rank=cfg["q_lora_rank"], kv_lora_rank=cfg["kv_lora_rank"],
        qk_nope_head_dim=cfg["qk_nope_head_dim"],
        qk_rope_head_dim=cfg["qk_rope_head_dim"],
        v_head_dim=cfg["v_head_dim"],
        mla_scale_q_lora=cfg["mla_scale_q_lora"],
        mla_scale_kv_lora=cfg["mla_scale_kv_lora"],
        n_routed_experts=cfg["published_n_routed_experts"],
        zero_expert_num=cfg["zero_expert_num"], moe_topk=cfg["moe_topk"],
        routed_scaling_factor=cfg["routed_scaling_factor"],
        expert_shard=(shard["chips"], shard["index"]),
        max_position_embeddings=cfg["max_position_embeddings"],
        rms_norm_eps=cfg["rms_norm_eps"], rope_theta=cfg["rope_theta"],
        initializer_range=cfg["initializer_range"],
        norm_weight_std=cfg["norm_weight_std"],
        expert_bias_std=cfg["expert_bias_std"],
        kv_block_size=cfg["kv_block_size"]))
    model.eval()
    return model


# ---------------------------------------------------------------------
# the plain reference
# ---------------------------------------------------------------------
def rope_pairs(x, positions, theta):
    """RoPE on ``x`` [s, ..., d]: lanes ``(2i, 2i + 1)`` turned as one
    complex number by ``positions * theta^(-2i/d)``, in place."""
    d = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = positions.astype(jnp.float32)[:, None] * inv[None, :]
    ang = ang.reshape(ang.shape[:1] + (1,) * (x.ndim - 2) + ang.shape[1:])
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * jnp.cos(ang) - b * jnp.sin(ang),
                      a * jnp.sin(ang) + b * jnp.cos(ang)],
                     -1).reshape(x.shape)


def mla(u, w, cfg):
    """``u`` [s, hidden] -> [s, hidden]: latent attention as written,
    every head's keys and values up-projected from the latent."""
    s, D = u.shape
    H, eps = cfg["num_attention_heads"], cfg["rms_norm_eps"]
    nope, rope, vd = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                      cfg["v_head_dim"])
    rank, q_rank = cfg["kv_lora_rank"], cfg["q_lora_rank"]
    c_q = rms_norm(u @ w["q_a_proj.weight"], w["q_a_layernorm.weight"], eps)
    if cfg["mla_scale_q_lora"]:
        c_q = c_q * math.sqrt(D / q_rank)
    q = (c_q @ w["q_b_proj.weight"]).reshape(s, H, nope + rope)
    a_kr = u @ w["kv_a_proj_with_mqa.weight"]
    c = rms_norm(a_kr[:, :rank], w["kv_a_layernorm.weight"], eps)
    if cfg["mla_scale_kv_lora"]:
        c = c * math.sqrt(D / rank)
    pos = jnp.arange(s)
    q = jnp.concatenate([q[..., :nope],
                         rope_pairs(q[..., nope:], pos, cfg["rope_theta"])],
                        -1)
    k_rope = rope_pairs(a_kr[:, rank:], pos, cfg["rope_theta"])
    kv = (c @ w["kv_b_proj.weight"]).reshape(s, H, nope + vd)
    k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(
        k_rope[:, None, :], (s, H, rope))], -1)
    v = kv[..., nope:]
    qb = min(QUERY_BLOCK, s)
    pad = -s % qb
    q_pad = jnp.pad(q, ((0, pad), (0, 0), (0, 0)))
    t_pad = jnp.arange(s + pad)
    cols = jnp.arange(s)

    def one_block(args):
        q_b, t_b = args                                  # [qb, H, 192]
        a = jnp.einsum("qhd,shd->hqs", q_b, k) / math.sqrt(nope + rope)
        seen = cols[None, :] <= t_b[:, None]
        a = jax.nn.softmax(jnp.where(seen[None], a, -jnp.inf), -1)
        return jnp.einsum("hqs,shv->qhv", a, v)

    o = jax.lax.map(one_block, (q_pad.reshape(-1, qb, H, nope + rope),
                                t_pad.reshape(-1, qb)))
    return o.reshape(-1, H * vd)[:s] @ w["o_proj.weight"]


def route(u, w, cfg):
    """``[s, routed + zero]`` float32: each token's weight on each
    expert, zero outside its chosen set.  The set is the ``moe_topk``
    largest of the probabilities plus the bias (ties to the lower
    index); the weight is the probability alone times
    ``routed_scaling_factor``, with no renormalisation."""
    p = jax.nn.softmax(u @ w["mlp.router.weight"], -1)
    choice = p + w["mlp.expert_bias"]
    # rank by falling choice, the lower index first among equals
    order = jnp.argsort(-choice, axis=-1, stable=True)
    rank = jnp.argsort(order, axis=-1, stable=True)
    return cfg["routed_scaling_factor"] * jnp.where(
        rank < cfg["moe_topk"], p, 0.0)


def expert_layer(u, w, stacks, cfg, low, held=None):
    """``sum over the held e of weight[t, e] Expert_e(u) + sum over the
    zero experts z of weight[t, z] u``: every held expert over every
    token, ``EXPERT_BLOCK`` of them widened at a time, the unchosen ones
    weighted zero.  ``stacks`` are the two stacks of the held experts in
    the type they are held in, with their per-tensor scales."""
    (gate_up, gu_scale), (down, dn_scale) = stacks
    lo, hi = held or held_experts(cfg)
    E, _, two_w = gate_up.shape
    width = two_w // 2
    weight = route(u, w, cfg)
    routed_w = weight[:, lo:hi]                          # [s, held]
    zero_w = weight[:, cfg["published_n_routed_experts"]:].sum(-1)
    eb = math.gcd(E, EXPERT_BLOCK)
    blocks = (gate_up.reshape(E // eb, eb, *gate_up.shape[1:]),
              down.reshape(E // eb, eb, *down.shape[1:]),
              routed_w.T.reshape(E // eb, eb, -1))

    def one_block(total, args):
        gu, dn, wt = args
        gu = low(gu, scale=gu_scale)
        dn = low(dn, scale=dn_scale)
        h = jnp.einsum("sd,edf->esf", u, gu)
        y = jnp.einsum("esw,ewd->esd",
                       jax.nn.silu(h[..., :width]) * h[..., width:], dn)
        return total + (wt[:, :, None] * y).sum(0), None

    routed, _ = jax.lax.scan(one_block, jnp.zeros_like(u), blocks)
    return routed + zero_w[:, None] * u


def _rows(fn, m):
    """``fn`` over ``m`` [s, hidden], ``MLP_ROWS`` rows at a time where
    ``s`` is a whole number of them: an expert block's [8, rows, 4096]
    float32 intermediate is 268 MB at 2,048 rows."""
    if m.shape[0] % MLP_ROWS == 0 and m.shape[0] > MLP_ROWS:
        return jax.lax.map(fn, m.reshape(-1, MLP_ROWS, m.shape[1])) \
            .reshape(m.shape)
    return fn(m)


def _low(compute_dtype):
    return functools.partial(held_in, dtype=compute_dtype)


_STATIC = ("cfg_key", "compute_dtype")


@functools.partial(jax.jit, static_argnames=_STATIC)
def _attention_half(x, w, cfg_key, compute_dtype=None):
    """``x + MLA(N_in(x))``; ``w`` the sublayer's norm (``norm``) and
    MLA weights.  With ``compute_dtype`` the weights, what enters the
    projections and what the sublayer hands on are held in that type;
    the sums stay float32.  One sublayer's weights are widened at a
    time: a whole double layer's in float32 are 2.6 GB."""
    cfg, low = _CONFIGS[cfg_key], _low(compute_dtype)
    w = jax.tree_util.tree_map(low, w)
    with jax.default_matmul_precision("highest"):
        u = low(rms_norm(x, w.pop("norm"), cfg["rms_norm_eps"]))
        return x + low(mla(u, w, cfg))


@functools.partial(jax.jit, static_argnames=_STATIC)
def _mlp_half(h, w, cfg_key, compute_dtype=None):
    """``(h + MLP(N_post(h)), N_post(h))``: what the expert layer of the
    first half takes is the MLP's input."""
    cfg, low = _CONFIGS[cfg_key], _low(compute_dtype)
    w = jax.tree_util.tree_map(low, w)
    with jax.default_matmul_precision("highest"):
        u = low(rms_norm(h, w["norm"], cfg["rms_norm_eps"]))
        return h + low(_rows(lambda rows: swiglu(
            rows, w["gate_proj.weight"], w["up_proj.weight"],
            w["down_proj.weight"]), u)), u


@functools.partial(jax.jit, static_argnames=_STATIC)
def _expert_half(u, w, stacks, cfg_key, compute_dtype=None):
    """The expert layer over ``u``, the shortcut's term."""
    cfg, low = _CONFIGS[cfg_key], _low(compute_dtype)
    w = jax.tree_util.tree_map(low, w)
    with jax.default_matmul_precision("highest"):
        return low(_rows(lambda rows: expert_layer(rows, w, stacks, cfg,
                                                   low), u))


_STACKS = ("mlp.experts.gate_up", "mlp.experts.down")


def reference_hidden(params, cfg, ids, compute_dtype=None):
    """Final hidden states after the last norm, ``[s, hidden]`` float32,
    for one sequence ``ids`` [s]: a double layer as its four halves
    (module docstring), one jitted function each, a half's weights
    widened to float32 only while it runs (the expert stacks a block of
    experts at a time).  With ``compute_dtype`` (see `_attention_half`)
    the reference at that precision."""
    key, prefix = _freeze(cfg), "model.layers"
    kw = dict(cfg_key=key, compute_dtype=compute_dtype)
    x = params["model.embed_tokens.weight"][ids].astype(jnp.float32)
    for i in range(cfg["num_layers"]):
        head = f"{prefix}.{i}."
        w = {k[len(head):]: a for k, a in params.items()
             if k.startswith(head)}

        def part(name, norm):
            got = {k[len(name):]: a for k, a in w.items()
                   if k.startswith(name)}
            return {**got, "norm": w[norm]}
        stacks = tuple((w[k], tensor_scale(w[k], compute_dtype))
                       for k in _STACKS)
        h = _attention_half(x, part("self_attn.0.",
                                    "input_layernorm.0.weight"), **kw)
        h, u = _mlp_half(h, part("mlps.0.",
                                 "post_attention_layernorm.0.weight"), **kw)
        # the shortcut: the expert layer sees the first MLP's input, and
        # its output joins at the end
        shortcut = _expert_half(u, {k: w[k] for k in (
            "mlp.router.weight", "mlp.expert_bias")}, stacks, **kw)
        h = _attention_half(h, part("self_attn.1.",
                                    "input_layernorm.1.weight"), **kw)
        h, _ = _mlp_half(h, part("mlps.1.",
                                 "post_attention_layernorm.1.weight"), **kw)
        x = h + shortcut
    return rms_norm(x, params["model.norm.weight"].astype(jnp.float32),
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
def moe_flops(assignments, experts_touched, cfg):
    """Operations of the routed experts: gate, up and down projection of
    every (token, held expert) assignment the steps dispatched
    (``moe_assignments``, summed over the expert layers); the zero
    experts' assignments are no kernel's work."""
    return assignments * 3 * 2 * cfg["hidden_size"] \
        * cfg["expert_ffn_hidden_size"]


def moe_bytes(assignments, experts_touched, cfg, itemsize=2):
    """Bytes the grouped kernel must move: the three matrices of every
    expert with at least one row (``moe_experts_touched``, summed over
    layers and steps), each read once, and every assignment's row in and
    out of both calls."""
    d, width = cfg["hidden_size"], cfg["expert_ffn_hidden_size"]
    return (experts_touched * 3 * d * width
            + assignments * 2 * (d + width)) * itemsize


def _latent_width(cfg):
    return cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]


def latent_attention_flops(blocks, pairs, rows, tokens, cfg):
    """Operations of the absorbed form the latent kernel computes: for
    every (query head row, visible key) pair (``latent_pairs``, summed
    over the latent layers), the score over the whole latent row and the
    value sum over its ``kv_lora_rank`` lanes.  A floor: the kernel also
    computes on the masked keys of the blocks it fetches."""
    return pairs * 2 * (_latent_width(cfg) + cfg["kv_lora_rank"])


def latent_attention_bytes(blocks, pairs, rows, tokens, cfg, itemsize=2):
    """Bytes the latent kernel must move: every latent block its walks
    fetch (``latent_blocks_read``: a row of ``kv_lora_rank +
    qk_rope_head_dim`` lanes a token), and each head's absorbed query in
    and value sum out for every decode row and prompt token the steps
    carried, in every latent layer (two a double layer)."""
    width = _latent_width(cfg)
    heads_rows = 2 * cfg["num_layers"] * cfg["num_attention_heads"] \
        * (rows + tokens)
    return (blocks * cfg["kv_block_size"] * width
            + heads_rows * (width + cfg["kv_lora_rank"])) * itemsize
