"""The ``minicpm_sala`` family: builds the program's
``MiniCPMSALAForCausalLM`` from a MiniCPM-SALA config file's keys and
holds the plain reference forward, written from the equations of
ISSUE 28 (PERF.md section 4 repeats them): InfLLM-V2 block-sparse
attention (MiniCPM4, arXiv:2506.07900) and Lightning Attention
(arXiv:2401.04658) side by side, muP scales, an untied head.

The reference is float32 ``jax.numpy`` at the highest matmul precision:
no kernel, no cache, no chunked form.  A lightning layer is the
token-by-token recurrence; a sparse layer scores, selects and attends
one block of queries at a time so that 18 K tokens fit beside the
weights.  It takes the program's arrays by name.

Also here, for the per-layer metrics: the operations and bytes of the
two new kernels (``lightning_*``, ``sparse_*``), from shapes and the
program's counters.
"""
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.families import _plain

LIGHTNING, SPARSE = "lightning-attn", "minicpm4"
QUERY_BLOCK = 256          # queries a sparse layer attends at a time
MLP_ROWS = 2048            # rows the feed-forward takes at a time


def sparse_sizes(cfg):
    """The selector's six sizes (``assumed.sparse_config``)."""
    s = cfg["sparse_config"]
    return (s["kernel_size"], s["kernel_stride"], s["block_size"],
            s["window_size"], s["init_blocks"], s["dense_len"], s["topk"])


def build(cfg):
    from paddle_tpu.models.minicpm_sala import (MiniCPMSALAConfig,
                                                MiniCPMSALAForCausalLM)
    sp = cfg["sparse_config"]
    model = MiniCPMSALAForCausalLM(MiniCPMSALAConfig(
        dtype=cfg["dtype"],       # each layer cast as it is built
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        intermediate_size=cfg["intermediate_size"],
        num_hidden_layers=cfg["num_hidden_layers"],
        mixer_types=tuple(cfg["mixer_types"]),
        num_attention_heads=cfg["num_attention_heads"],
        num_key_value_heads=cfg["num_key_value_heads"],
        head_dim=cfg["head_dim"], lightning_nh=cfg["lightning_nh"],
        lightning_nkv=cfg["lightning_nkv"],
        lightning_head_dim=cfg["lightning_head_dim"],
        max_position_embeddings=cfg["max_position_embeddings"],
        rms_norm_eps=cfg["rms_norm_eps"], rope_theta=cfg["rope_theta"],
        scale_emb=cfg["scale_emb"], scale_depth=cfg["scale_depth"],
        published_layers=cfg["published_layers"],
        dim_model_base=cfg["dim_model_base"],
        sparse_kernel_size=sp["kernel_size"],
        sparse_kernel_stride=sp["kernel_stride"],
        sparse_block_size=sp["block_size"],
        sparse_window_size=sp["window_size"],
        sparse_init_blocks=sp["init_blocks"],
        sparse_dense_len=sp["dense_len"], sparse_topk=sp["topk"]))
    # The program draws nn.Embedding from N(0, 1).  Times scale_emb the
    # residual stream would have an RMS of 12 and the blocks' unit-size
    # contributions (times scale_depth / sqrt(L) = 0.25) would vanish
    # beside it: the logits would be a function of the last token alone
    # and the check would see neither mixer.  1 / scale_emb makes the
    # embedded stream unit-RMS, as a trained muP model's is.
    table = model.model.embed_tokens
    table.weight.set_value(table.weight / cfg["scale_emb"])
    model.eval()
    return model


# ---------------------------------------------------------------------
# the plain reference
# ---------------------------------------------------------------------
def rms_norm(x, weight, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * weight


def rope(x, positions, theta):
    """Rotary embedding over the whole head dimension (rotate-half
    layout), ``x`` [s, heads, d] at absolute ``positions`` [s]."""
    d = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = positions.astype(jnp.float32)[:, None] * inv[None, :]
    ang = jnp.concatenate([ang, ang], -1)[:, None, :]          # [s, 1, d]
    rot = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], -1)
    return x * jnp.cos(ang) + rot * jnp.sin(ang)


def decay_slopes(heads):
    """``s_h = 2 ** (-8 (h + 1) / heads)`` (``assumed.lightning_decay``)."""
    return 2.0 ** (-8.0 * (np.arange(heads) + 1) / heads)


def lightning_mixer(u, w, cfg):
    """``u`` [s, hidden] -> [s, hidden]: the recurrence, token by token."""
    s, eps = u.shape[0], cfg["rms_norm_eps"]
    nh, d = cfg["lightning_nh"], cfg["lightning_head_dim"]
    q = rms_norm((u @ w["mixer.q_proj.weight"]).reshape(s, nh, d),
                 w["mixer.q_norm.weight"], eps)
    k = rms_norm((u @ w["mixer.k_proj.weight"]).reshape(s, nh, d),
                 w["mixer.k_norm.weight"], eps)
    v = (u @ w["mixer.v_proj.weight"]).reshape(s, nh, d)
    pos = jnp.arange(s)
    q, k = rope(q, pos, cfg["rope_theta"]), rope(k, pos, cfg["rope_theta"])
    lam = jnp.exp(-jnp.asarray(decay_slopes(nh), jnp.float32))

    def step(state, qkv):
        q_t, k_t, v_t = qkv                                  # [nh, d]
        state = lam[:, None, None] * state \
            + k_t[:, :, None] * v_t[:, None, :]
        o_t = jnp.einsum("hd,hde->he", q_t / math.sqrt(d), state)
        return state, o_t

    _, o = jax.lax.scan(step, jnp.zeros((nh, d, d), jnp.float32),
                        (q, k, v))
    o = rms_norm(o.reshape(s, nh * d), w["mixer.out_norm.weight"], eps)
    o = o * jax.nn.sigmoid(u @ w["mixer.g_proj.weight"])
    return o @ w["mixer.o_proj.weight"]


def block_overlaps(n_blocks, n_keys, kernel, stride, block):
    """``[n_blocks, m]`` indices of the compressed keys whose tokens
    overlap each block (``-1`` pads): ``j = 4b - 1 ... 4b + 3`` at the
    published sizes."""
    rows = [[j for j in range(n_keys) if stride * j < (b + 1) * block
             and stride * j + kernel > b * block] for b in range(n_blocks)]
    m = max(1, max(len(r) for r in rows))
    return np.asarray([r + [-1] * (m - len(r)) for r in rows], np.int32)


def select_blocks(scores_j, t, n_blocks, sizes):
    """Which blocks each token reads.  ``scores_j`` [..., n_keys]: the
    group's summed softmax over the visible compressed keys of token
    ``t`` [...] (broadcastable).  Returns bool [..., n_blocks]."""
    kernel, stride, block, window, init, dense_len, topk = sizes
    n_keys = scores_j.shape[-1]
    over = jnp.asarray(block_overlaps(n_blocks, n_keys, kernel, stride,
                                      block))
    visible = stride * jnp.arange(n_keys) + kernel - 1 <= t[..., None]
    masked = jnp.where(visible, scores_j, -jnp.inf)
    per_block = jnp.where(over >= 0, masked[..., jnp.maximum(over, 0)],
                          -jnp.inf).max(-1)                  # [..., n_blocks]
    b = jnp.arange(n_blocks)
    b_t = (t // block)[..., None]
    w_lo = (jnp.maximum(t - window + 1, 0) // block)[..., None]
    candidate = (b >= init) & (b < w_lo)
    ranked = jnp.where(candidate, per_block, -jnp.inf)
    # rank by falling score, the lower index first among equals
    order = jnp.argsort(-ranked, axis=-1, stable=True)
    rank = jnp.argsort(order, axis=-1, stable=True)
    chosen = candidate & (rank < topk)
    sparse = (b < init) | ((b >= w_lo) & (b <= b_t)) | chosen
    dense = (t + 1 <= dense_len)[..., None]
    return jnp.where(dense, b <= b_t, sparse & (b <= b_t))


def sparse_mixer(u, w, cfg, score_dtype=None):
    """``u`` [s, hidden] -> ``([s, hidden], flips)``.  With
    ``score_dtype`` the selection's scores are taken from q and pooled
    keys rounded to that type (what a program that scores in bfloat16
    sees), the attention follows that selection, and ``flips`` counts
    the (token, KV head, block) choices that differ from the float32
    selection."""
    s, eps = u.shape[0], cfg["rms_norm_eps"]
    nh, nkv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    sizes = sparse_sizes(cfg)
    kernel, stride, block = sizes[:3]
    g = nh // nkv
    q = rms_norm((u @ w["mixer.q_proj.weight"]).reshape(s, nh, d),
                 w["mixer.q_norm.weight"], eps).reshape(s, nkv, g, d)
    k = rms_norm((u @ w["mixer.k_proj.weight"]).reshape(s, nkv, d),
                 w["mixer.k_norm.weight"], eps)
    v = (u @ w["mixer.v_proj.weight"]).reshape(s, nkv, d)
    n_blocks, n_keys = -(-s // block), max(1, (s - kernel) // stride + 1)
    # compressed keys: the mean of `kernel` keys every `stride` tokens
    idx = stride * np.arange(n_keys)[:, None] + np.arange(kernel)[None, :]
    ck = k[np.minimum(idx, s - 1)].mean(1)                   # [n_keys, nkv, d]
    qb = min(QUERY_BLOCK, s)
    pad = -s % qb
    q_pad = jnp.pad(q, ((0, pad), (0, 0), (0, 0), (0, 0)))
    t_pad = jnp.arange(s + pad)

    def one_block(args):
        q_b, t_b = args                                      # [qb, nkv, g, d]
        vis = (stride * jnp.arange(n_keys) + kernel - 1
               <= t_b[None, :, None, None])

        def selection(q_s, ck_s):
            sc = jnp.einsum("qngd,jnd->nqgj", q_s, ck_s) / math.sqrt(d)
            p = jax.nn.softmax(jnp.where(vis, sc, -jnp.inf), -1)
            p = jnp.where(vis, p, 0.0).sum(2)                # [nkv, qb, j]
            return select_blocks(p, t_b[None, :], n_blocks, sizes)

        sel, flips = selection(q_b, ck), 0
        if score_dtype is not None:
            rounded = selection(
                q_b.astype(score_dtype).astype(jnp.float32),
                ck.astype(score_dtype).astype(jnp.float32))
            flips = ((rounded != sel) & (t_b < s)[None, :, None]).sum()
            sel = rounded
        cols = jnp.arange(n_blocks * block)
        mask = jnp.repeat(sel, block, -1)[..., :s] \
            & (cols[:s] <= t_b[None, :, None])               # [nkv, qb, s]
        a = jnp.einsum("qngd,snd->nqgs", q_b, k) / math.sqrt(d)
        a = jax.nn.softmax(jnp.where(mask[:, :, None, :], a, -jnp.inf), -1)
        return jnp.einsum("nqgs,snd->qngd", a, v), flips

    o, flips = jax.lax.map(one_block, (q_pad.reshape(-1, qb, nkv, g, d),
                                       t_pad.reshape(-1, qb)))
    o = o.reshape(-1, nh * d)[:s]
    o = o * jax.nn.sigmoid(u @ w["mixer.g_proj.weight"])
    return o @ w["mixer.o_proj.weight"], jnp.sum(flips)


def held_in(a, dtype):
    """``a`` as a tensor held in ``dtype`` under a per-tensor scale (as
    float8 is used) and widened again; ``None`` leaves it.  The
    reference at a lower precision is the check's control: it has to
    come out as not correct."""
    if dtype is None:
        return a
    scale = jnp.abs(a).max() / float(jnp.finfo(dtype).max)
    return (a / scale).astype(dtype).astype(jnp.float32) * scale


@functools.partial(jax.jit, static_argnames=(
    "kind", "cfg_key", "score_dtype", "compute_dtype"))
def _layer(x, w, kind, cfg_key, score_dtype=None, compute_dtype=None):
    """One block.  With ``compute_dtype`` the weights, what enters the
    projections and what each half of the block hands on are held in
    that type; the sums stay float32."""
    cfg = _CONFIGS[cfg_key]
    low = functools.partial(held_in, dtype=compute_dtype)
    w = jax.tree_util.tree_map(low, _plain.f32(w))
    c = cfg["scale_depth"] / math.sqrt(cfg["published_layers"])
    eps = cfg["rms_norm_eps"]
    with jax.default_matmul_precision("highest"):
        u = low(rms_norm(x, w["input_layernorm.weight"], eps))
        if kind == LIGHTNING:
            mixed, flips = lightning_mixer(u, w, cfg), 0
        else:
            mixed, flips = sparse_mixer(u, w, cfg, score_dtype)
        h = x + c * low(mixed)
        m = low(rms_norm(h, w["post_attention_layernorm.weight"], eps))

        def mlp(rows):
            return (jax.nn.silu(rows @ w["mlp.gate_proj.weight"])
                    * (rows @ w["mlp.up_proj.weight"])) \
                @ w["mlp.down_proj.weight"]

        # MLP_ROWS at a time: 18 K rows of the 16,384-wide intermediate
        # in float32 would be 1.2 GB, three times over
        blocked = m.shape[0] % MLP_ROWS == 0 and m.shape[0] > MLP_ROWS
        m = jax.lax.map(mlp, m.reshape(-1, MLP_ROWS, m.shape[1])) \
            .reshape(m.shape) if blocked else mlp(m)
        return h + c * low(m), flips


_CONFIGS = {}


def _freeze(cfg):
    """A hashable handle on a config dict for the jitted layer."""
    key = repr(sorted((k, repr(v)) for k, v in cfg.items()))
    _CONFIGS[key] = cfg
    return key


def reference_hidden(params, cfg, ids, score_dtype=None,
                     compute_dtype=None):
    """Final hidden states after the last RMSNorm, ``[s, hidden]``
    float32, for one sequence ``ids`` [s]: one jitted function a layer
    kind, a layer's weights widened to float32 only while it runs.
    With ``score_dtype`` (see `sparse_mixer`) it returns ``(hidden,
    flips)``; with ``compute_dtype`` (see `_layer`) the reference at
    that precision."""
    key, prefix = _freeze(cfg), "model.layers"
    x = cfg["scale_emb"] * params["model.embed_tokens.weight"][ids] \
        .astype(jnp.float32)
    flips = 0
    for i, kind in enumerate(cfg["mixer_types"]):
        head = f"{prefix}.{i}."
        w = {k[len(head):]: a for k, a in params.items()
             if k.startswith(head)}
        x, n = _layer(x, w, kind=kind, cfg_key=key, score_dtype=score_dtype,
                      compute_dtype=compute_dtype)
        flips = flips + n
    x = rms_norm(x, params["model.norm.weight"].astype(jnp.float32),
                 cfg["rms_norm_eps"])
    return x if score_dtype is None else (x, flips)


def reference_head(params, cfg, hidden, compute_dtype=None):
    """Logits of the rows ``hidden`` [n, hidden]: the untied head over
    ``hidden_size / dim_model_base``."""
    with jax.default_matmul_precision("highest"):
        return (held_in(hidden, compute_dtype) @ held_in(
            params["lm_head.weight"].astype(jnp.float32), compute_dtype)) \
            / (cfg["hidden_size"] / cfg["dim_model_base"])


def reference_logits(params, cfg, ids):
    """Next-token logits ``[b, s, vocab]``, the harness's contract; for
    sizes at which every position's logits fit."""
    return jnp.stack([reference_head(params, cfg,
                                     reference_hidden(params, cfg, row))
                      for row in ids])


# ---------------------------------------------------------------------
# the new kernels' work, from shapes and the program's counters
# ---------------------------------------------------------------------
def lightning_bytes(rows, chunk_tokens, chunks, cfg, itemsize=2):
    """HBM bytes the lightning layers' kernels must move for ``rows``
    decode rows and ``chunk_tokens`` prompt tokens in ``chunks`` chunks
    (each counted once, as the engine's steps carried them): a float32
    state read and written per row and per chunk, and q, k, v in and
    the output out per token."""
    nh, d = cfg["lightning_nh"], cfg["lightning_head_dim"]
    state = 2 * nh * d * d * 4 * (rows + chunks)
    qkvo = 4 * nh * d * itemsize * (rows + chunk_tokens)
    return _count(cfg, LIGHTNING) * (state + qkvo)


def lightning_flops(rows, chunk_tokens, chunks, cfg, block=128):
    """Operations: a decode row is the decayed rank-one update and the
    read (``4 d^2`` a head); a chunk in blocks of ``block`` rows is
    ``Q K^T`` and ``(.) V`` (``4 block^2 d``) and ``Q S`` and ``K^T V``
    (``4 block d^2``) a block and head."""
    nh, d = cfg["lightning_nh"], cfg["lightning_head_dim"]
    blocks = -(-chunk_tokens // block)
    return _count(cfg, LIGHTNING) * nh * (
        rows * 4 * d * d
        + blocks * (4 * block * block * d + 4 * block * d * d))


def sparse_bytes(selected_blocks, visible_blocks, cfg, itemsize=2):
    """Bytes of the sparse layers' decode rows, from the program's
    counters (``sparse.blocks_selected`` / ``sparse.blocks_visible``:
    summed over rows, sparse layers and KV heads): K and V of every
    selected block, and the pooled keys of every visible block that the
    row scores."""
    d, sp = cfg["head_dim"], cfg["sparse_config"]
    keys = sp["block_size"] // sp["kernel_stride"]
    return (selected_blocks * 2 * sp["block_size"]
            + visible_blocks * keys) * d * itemsize


def sparse_flops(selected_blocks, visible_blocks, cfg):
    """``Q K^T`` and ``P V`` over the selected blocks and the scores
    over the visible pooled keys, for the group's query heads."""
    d, sp = cfg["head_dim"], cfg["sparse_config"]
    g = cfg["num_attention_heads"] // cfg["num_key_value_heads"]
    keys = sp["block_size"] // sp["kernel_stride"]
    return g * d * (selected_blocks * 4 * sp["block_size"]
                    + visible_blocks * 2 * keys)


def _count(cfg, kind):
    return sum(m == kind for m in cfg["mixer_types"])
