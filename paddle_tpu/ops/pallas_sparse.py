"""Block-sparse attention's selector (InfLLM-V2, MiniCPM4 report,
arXiv:2506.07900): compressed keys, block scores, the selected set.

A sparse layer keeps, beside its paged K/V, one mean-pooled key per
``stride`` tokens and KV head (``k_bar_j = mean(k[stride * j : stride
* j + kernel])``), visible to token ``t`` once its last token is
(``stride * j + kernel - 1 <= t``).  Token ``t`` of KV group ``g``
scores the visible compressed keys with every query head of the group
(``softmax_j(q_h . k_bar_j / sqrt d)``, summed over the group's heads);
a block's score is the largest over the compressed keys whose tokens
overlap it.  The token reads block 0 (``init`` blocks), every block
that holds one of its last ``window`` tokens, and the ``topk`` best of
the blocks between; a context of at most ``dense_len`` tokens reads
everything.

Here: the scoring as a Pallas kernel (`sparse_select_scores`, one
program a decode row over its slot's slab of compressed keys) with its
composite; the selection and the tables that hand a decode row's
selected blocks to ``ragged_paged_attention`` (`selected_tables`); and
the masked composite that a prefill chunk's tokens, each with its own
selection, run (`sparse_block_attention`).
"""
from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .pallas_tiles import _NEG_INF, _interpret, _kernel_span, _x32

__all__ = ["SparseSizes", "sparse_select_scores", "select_scores_ref",
           "select_blocks", "selected_tables", "selected_count",
           "sparse_block_attention", "compress_keys", "compress_dense",
           "block_overlaps"]

_NT = (((1,), (1,)), ((), ()))


class SparseSizes(NamedTuple):
    """The selector's sizes (hashable: a static argument)."""
    kernel: int = 32
    stride: int = 16
    block: int = 64
    window: int = 2048
    init: int = 1
    dense_len: int = 8192
    topk: int = 64

    def num_keys(self, max_len):
        """Compressed keys a context of ``max_len`` tokens can have."""
        return max(1, -(-int(max_len) // self.stride))

    def table_width(self, max_len):
        """The most blocks one token reads."""
        sparse = (self.init + self.topk
                  + (self.window + self.block - 2) // self.block + 1)
        dense = -(-min(self.dense_len, int(max_len)) // self.block)
        return min(max(sparse, dense), -(-int(max_len) // self.block))


def selected_count(context, sizes):
    """Blocks the token at the end of a ``context``-token sequence
    reads and blocks it can see: ``(selected, visible)``.  The count
    does not depend on the scores (host arithmetic, for the counters)."""
    t = int(context) - 1
    visible = t // sizes.block + 1
    if context <= sizes.dense_len:
        return visible, visible
    w_lo = max(t - sizes.window + 1, 0) // sizes.block
    fixed = min(sizes.init, w_lo) + (visible - w_lo)
    return fixed + min(sizes.topk, max(0, w_lo - sizes.init)), visible


def block_overlaps(n_blocks, n_keys, sizes):
    """``[n_blocks, m]`` indices of the compressed keys whose tokens
    overlap each block, ``-1`` padded (``4b - 1 ... 4b + 3`` at the
    published sizes)."""
    lo = [max(0, -(-(b * sizes.block - sizes.kernel + 1) // sizes.stride))
          for b in range(n_blocks)]
    hi = [min(n_keys - 1, ((b + 1) * sizes.block - 1) // sizes.stride)
          for b in range(n_blocks)]
    m = max(1, max(h - l + 1 for l, h in zip(lo, hi)))
    rows = [list(range(l, h + 1)) for l, h in zip(lo, hi)]
    return np.asarray([r + [-1] * (m - len(r)) for r in rows], np.int32)


# ---------------------------------------------------------------------
# compressed keys
# ---------------------------------------------------------------------
def compress_keys(k_pool, ck_pool, tables, seq, j, slot, sizes):
    """Write the compressed keys ``j`` [N] of sequences ``seq`` [N]
    (rows of ``tables`` [S + 1, W]: the last row is the null sequence)
    into slots ``slot`` [N] of ``ck_pool`` [slots, Hkv, J, D]: the mean,
    in float32, of ``kernel`` keys gathered from ``k_pool`` [blocks,
    block, Hkv * D] through the block table.  Entries with ``slot`` 0
    land in the pad slot."""
    pos = sizes.stride * j[:, None] + jnp.arange(sizes.kernel)[None, :]
    blk = jnp.take_along_axis(tables[seq], pos // sizes.block, axis=1)
    keys = k_pool[blk, pos % sizes.block]             # [N, kernel, Hkv * D]
    mean = keys.astype(jnp.float32).mean(1).astype(ck_pool.dtype)
    return ck_pool.at[slot, :, j, :].set(
        mean.reshape(mean.shape[0], ck_pool.shape[1], ck_pool.shape[3]))


def compress_dense(k, sizes):
    """No cache: ``k`` [S, Hkv, D] -> [Hkv, J, D], keys whose window
    runs past the end left at zero (never visible)."""
    S = k.shape[0]
    n_keys = sizes.num_keys(S)
    pos = sizes.stride * np.arange(n_keys)[:, None] \
        + np.arange(sizes.kernel)[None, :]
    whole = jnp.asarray(pos[:, -1] < S)
    keys = k[np.minimum(pos, S - 1)].astype(jnp.float32).mean(1)
    keys = jnp.where(whole[:, None, None], keys, 0.0).astype(k.dtype)
    return jnp.swapaxes(keys, 0, 1)


# ---------------------------------------------------------------------
# scoring
# ---------------------------------------------------------------------
def select_scores_ref(q, ck, t, sizes):
    """``q`` [N, Hkv, G, D]; ``ck`` [N, Hkv, J, D] (each row's slab) or
    [Hkv, J, D] (one sequence's, for all rows); ``t`` [N] the rows'
    positions (negative: an idle row).  Returns the group's summed
    softmax over the visible compressed keys, [N, Hkv, J] float32, zero
    where nothing is visible."""
    d = q.shape[-1]
    s = jnp.einsum("nhgd,nhjd->nhgj" if ck.ndim == 4 else "nhgd,hjd->nhgj",
                   q, ck, preferred_element_type=jnp.float32) / math.sqrt(d)
    jj = jnp.arange(ck.shape[-2], dtype=jnp.int32)
    vis = (sizes.stride * jj + sizes.kernel - 1)[None, :] \
        <= t.astype(jnp.int32)[:, None]                     # [N, J]
    vis = vis[:, None, None, :]
    s = jnp.where(vis, s, _NEG_INF)
    p = jnp.where(vis, jnp.exp(s - s.max(-1, keepdims=True)), 0.0)
    l = p.sum(-1, keepdims=True)
    return (p / jnp.where(l == 0.0, 1.0, l)).sum(2)


def _score_kernel(slot_ref, t_ref, q_ref, ck_ref, o_ref, *, kv_heads,
                  group, scale, kernel, stride):
    t = t_ref[pl.program_id(0)]
    for g in range(kv_heads):
        q = q_ref[0, g * group:(g + 1) * group, :].astype(jnp.float32)
        ck = ck_ref[0, g].astype(jnp.float32)                # (J, D)
        s = jax.lax.dot_general(q, ck, _NT,
                                preferred_element_type=jnp.float32) * scale
        jj = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        vis = stride * jj + (kernel - 1) <= t
        s = jnp.where(vis, s, _NEG_INF)
        p = jnp.where(vis, jnp.exp(s - jnp.max(s, axis=-1, keepdims=True)),
                      0.0)
        l = jnp.sum(p, axis=-1, keepdims=True)
        p = p / jnp.where(l == 0.0, 1.0, l)
        o_ref[0, g:g + 1, :] = jnp.sum(p, axis=0, keepdims=True)


@functools.partial(jax.jit, static_argnames=("sizes",))
@_x32
def _score_call(q, ck_pool, slots, t, *, sizes):
    S, H, D = q.shape
    _, kv_heads, J, _ = ck_pool.shape
    with _kernel_span("sparse_select", "fwd") as name:
        return pl.pallas_call(
            functools.partial(_score_kernel, kv_heads=kv_heads,
                              group=H // kv_heads,
                              scale=1.0 / math.sqrt(D),
                              kernel=sizes.kernel, stride=sizes.stride),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=2,
                grid=(S,),
                in_specs=[pl.BlockSpec((1, H, D),
                                       lambda r, sl, t: (r, 0, 0)),
                          pl.BlockSpec((1, kv_heads, J, D),
                                       lambda r, sl, t: (sl[r], 0, 0, 0))],
                out_specs=pl.BlockSpec((1, kv_heads, J),
                                       lambda r, sl, t: (r, 0, 0))),
            out_shape=jax.ShapeDtypeStruct((S, kv_heads, J), jnp.float32),
            interpret=_interpret(),
            name=name,
        )(slots.astype(jnp.int32), t.astype(jnp.int32), q, ck_pool)


def sparse_select_scores(q, ck_pool, slots, t, sizes, use_pallas=False):
    """Scores of ``S`` decode rows: ``q`` [S, H, D] (heads grouped by
    KV head), row ``r`` against slot ``slots[r]`` of ``ck_pool``
    [slots, Hkv, J, D] at position ``t[r]``.  [S, Hkv, J] float32."""
    if use_pallas:
        return _score_call(q, ck_pool, slots, t, sizes=sizes)
    S, H, D = q.shape
    kv_heads = ck_pool.shape[1]
    return select_scores_ref(q.reshape(S, kv_heads, H // kv_heads, D),
                             ck_pool[slots], t, sizes)


# ---------------------------------------------------------------------
# selection
# ---------------------------------------------------------------------
def select_blocks(scores, t, n_blocks, sizes):
    """``scores`` [..., J] (summed softmax), ``t`` [...] (broadcast
    against the leading dims): bool [..., n_blocks], the blocks token
    ``t`` reads.  The ``topk`` are taken by falling score, the lower
    index first among equals (``lax.top_k``'s order)."""
    n_keys = scores.shape[-1]
    over = jnp.asarray(block_overlaps(n_blocks, n_keys, sizes))
    t = t.astype(jnp.int32)[..., None]
    jj = jnp.arange(n_keys, dtype=jnp.int32)
    vis = sizes.stride * jj + sizes.kernel - 1 <= t
    masked = jnp.where(vis, scores, -jnp.inf)
    per_block = jnp.where(over >= 0, masked[..., jnp.maximum(over, 0)],
                          -jnp.inf).max(-1)
    b = jnp.arange(n_blocks, dtype=jnp.int32)
    b_t = t // sizes.block
    w_lo = jnp.maximum(t - sizes.window + 1, 0) // sizes.block
    candidate = (b >= sizes.init) & (b < w_lo)
    k = min(sizes.topk, n_blocks)
    ranked = jnp.where(candidate, per_block, -jnp.inf)
    _, idx = jax.lax.top_k(ranked, k)
    chosen = jnp.put_along_axis(jnp.zeros(ranked.shape, bool), idx,
                                True, axis=-1, inplace=False) & candidate
    sparse = (b < sizes.init) | (b >= w_lo) | chosen
    dense = t + 1 <= sizes.dense_len
    return jnp.where(dense, True, sparse) & (b <= b_t)


def selected_tables(scores, t, tables, sizes, width):
    """A decode row's selected blocks as a block table of its own.
    ``scores`` [S, Hkv, J]; ``t`` [S]; ``tables`` [S, W] physical
    blocks.  Returns ``(sel_tables [S, Hkv, width], sel_ctx [S, Hkv])``:
    the selected blocks in rising order (the rest pad block 0) and the
    row's position in that compacted context plus one, so that
    ``ragged_paged_attention`` masks as it does for a whole table: the
    last selected block is always the one that holds ``t``."""
    W = tables.shape[1]
    sel = select_blocks(scores, t[:, None], W, sizes)        # [S, Hkv, W]
    n_sel = sel.sum(-1).astype(jnp.int32)
    order = jnp.argsort(~sel, axis=-1, stable=True)[..., :width]
    phys = jnp.take_along_axis(
        jnp.broadcast_to(tables[:, None, :], sel.shape), order, axis=-1)
    live = jnp.arange(width, dtype=jnp.int32) < n_sel[..., None]
    ctx = (n_sel - 1) * sizes.block + (t % sizes.block)[:, None] + 1
    return (jnp.where(live, phys, 0).astype(jnp.int32),
            jnp.where(t[:, None] >= 0, ctx, 0).astype(jnp.int32))


# ---------------------------------------------------------------------
# the masked composite (a chunk's tokens, and the no-cache forward)
# ---------------------------------------------------------------------
def sparse_block_attention(q, t, k, v, ck, sizes, q_tile=128):
    """``q`` [C, Hkv, G, D] at positions ``t`` [C] (negative: padding)
    over one sequence's keys ``k``, ``v`` [L, Hkv, D] (``L`` a multiple
    of the block; what lies past a token's position is masked) and its
    compressed keys ``ck`` [Hkv, J, D].  Every token selects for itself;
    the queries run ``q_tile`` at a time.  Returns [C, Hkv, G, D]."""
    C, kv_heads, G, D = q.shape
    L = k.shape[0]
    n_blocks = L // sizes.block
    scale = 1.0 / math.sqrt(D)
    q_tile = min(q_tile, C)
    pad = -C % q_tile
    if pad:
        q = jnp.pad(q, ((0, pad), (0, 0), (0, 0), (0, 0)))
        t = jnp.pad(t, (0, pad), constant_values=-1)
    cols = jnp.arange(L, dtype=jnp.int32)

    def tile(args):
        q_b, t_b = args
        scores = select_scores_ref(q_b, ck, t_b, sizes)      # [n, Hkv, J]
        sel = select_blocks(scores, t_b[:, None], n_blocks, sizes)
        mask = jnp.repeat(sel, sizes.block, axis=-1) \
            & (cols[None, None, :] <= t_b[:, None, None])    # [n, Hkv, L]
        s = jnp.einsum("nhgd,lhd->nhgl", q_b, k,
                       preferred_element_type=jnp.float32) * scale
        s = jnp.where(mask[:, :, None, :], s, _NEG_INF)
        p = jax.nn.softmax(s, axis=-1)
        p = jnp.where(mask[:, :, None, :], p, 0.0).astype(v.dtype)
        return jnp.einsum("nhgl,lhd->nhgd", p, v,
                          preferred_element_type=jnp.float32
                          ).astype(q.dtype)

    out = jax.lax.map(tile, (q.reshape(-1, q_tile, kv_heads, G, D),
                             t.astype(jnp.int32).reshape(-1, q_tile)))
    return out.reshape(-1, kv_heads, G, D)[:C]
