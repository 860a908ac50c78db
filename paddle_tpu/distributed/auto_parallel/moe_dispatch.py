"""Dropless MoE token routing + ring all-to-all expert dispatch.

The capacity formulation in ``incubate/.../moe_layer.py`` drops every
token past slot ``C`` of its expert (``keep = loc < C``).  This module
is the dropless alternative the grouped-expert Pallas kernel
(`ops.pallas_grouped`) is built for: every (token, expert) assignment
gets a real row in a block-aligned grouped buffer, experts own whole
``block_rows``-row runs described by `pallas_tiles.group_segments`, and
nothing is ever dropped — load imbalance costs padding, not quality.

Routing is three pure pieces (all jnp-traceable, fully deterministic —
the stable argsort gives tokens of one expert their arrival order):

  * `dropless_plan`   — top-k assignments -> (row of each assignment,
    block_group descriptor for the kernel, per-expert counts);
  * `dropless_dispatch` — scatter tokens into the grouped buffer;
  * `dropless_combine`  — gather expert outputs back and weighted-sum
    the k choices per token.

`sigmoid_topk_router` is the sigmoid router with a selection-only bias,
`softmax_topk_router` the softmax router with renormalised top-k, and
`gated_experts` strings plan, scatter, the gated grouped kernel, the
down projection and the combine together for a SwiGLU expert layer
(`models/afmoe.py`, `models/qwen3_next.py`).  A layer that holds only
the experts ``held = (lo, hi)`` of those the router chooses among plans
its own: every other assignment goes where the rows that carry nothing
go, and the result is the part that its experts give.

Expert parallelism crosses the ``ep`` mesh axis with all-to-all.
`ring_all_to_all_local` decomposes that collective into per-peer
``ppermute`` hops — the PR 11 ring-overlap discipline
(`overlap.all_gather_matmul_local`): in overlapped mode every hop is
independent of the expert matmul that follows, so XLA schedules the
transfer under the MXU; the sequential fallback is one
``jax.lax.all_to_all`` and both paths are bit-exact (pure data
movement, no arithmetic).  Mode selection reuses
``overlap.select_mode`` so ``PADDLE_TPU_OVERLAP`` and the cached probe
govern MoE dispatch exactly like the TP matmul ring.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ...ops.pallas_tiles import group_segments, num_group_blocks

__all__ = [
    "dropless_combine", "dropless_dispatch", "dropless_plan",
    "expert_imbalance", "gated_experts", "plan_counters",
    "ring_all_to_all_local", "sigmoid_topk_router", "softmax_topk_router",
]


# ---------------------------------------------------------------------------
# Dropless routing (single-device / inside one shard)
# ---------------------------------------------------------------------------

def sigmoid_topk_router(logits, bias, top_k, route_scale=1.0,
                        route_norm=True):
    """A sigmoid router with a selection-only bias.  ``logits`` [N, E]
    float32; ``bias`` [E] or None.  Scores are ``sigmoid(logits)``; the
    chosen set is the ``top_k`` largest of ``score + bias`` (ties to the
    lower index: `jax.lax.top_k` is stable), and a chosen expert's
    weight is its own score, bias left out, over the chosen scores' sum
    (``route_norm``) times ``route_scale``.  Returns ``(topk_idx,
    topk_weight)`` [N, top_k], int32 and float32."""
    scores = jax.nn.sigmoid(logits.astype(jnp.float32))
    choice = scores if bias is None else scores + bias.astype(jnp.float32)
    _, idx = jax.lax.top_k(choice, top_k)
    w = jnp.take_along_axis(scores, idx, axis=-1)
    if route_norm:
        w = w / (w.sum(-1, keepdims=True) + 1e-20)
    return idx.astype(jnp.int32), w * route_scale


def softmax_topk_router(logits, top_k, norm_topk=True):
    """A softmax router.  ``logits`` [N, E] float32; the chosen set is
    the ``top_k`` largest of ``softmax(logits)`` (ties to the lower
    index), a chosen expert's weight its probability, over the chosen
    probabilities' sum with ``norm_topk``.  Returns ``(topk_idx,
    topk_weight)`` [N, top_k], int32 and float32."""
    w, idx = jax.lax.top_k(jax.nn.softmax(logits.astype(jnp.float32), -1),
                           top_k)
    if norm_topk:
        w = w / w.sum(-1, keepdims=True)
    return idx.astype(jnp.int32), w


def dropless_plan(topk_idx, num_experts, block_rows, num_blocks=None,
                  carried=None, held=None):
    """Plan the grouped layout for top-k assignments — droplessly.

    ``topk_idx``: [N, k] int expert choices.  With ``held = (lo, hi)``
    the choices are among more experts than this plan lays out: the
    ``num_experts = hi - lo`` experts ``lo <= e < hi`` are planned as
    groups ``e - lo`` and an assignment to any other expert is
    dispatched nowhere, as a token's that carries nothing.
    ``num_blocks`` must be
    the static `pallas_tiles.num_group_blocks(N * k, num_experts,
    block_rows)` (computed here when N is concrete).  ``carried`` [N]
    bool marks the tokens that carry something (the serving engine's
    flat buffer is mostly padding): the others are dispatched to no
    expert, so a decode-only step plans its few rows' choices and the
    rest of the buffer is null blocks.

    Returns ``(rows, block_group, counts)``:
      * ``rows``: [N * k] int32 — the grouped-buffer row of flat
        assignment ``n * k + j`` (rows are unique: scatter is exact);
        an assignment of a token that carries nothing gets the row
        past the buffer's end (dropped on scatter, zero on gather);
      * ``block_group``: [num_blocks] int32 kernel descriptor
        (``num_experts`` = null block);
      * ``counts``: [num_experts] int32 tokens per expert (the
        imbalance/diagnostic gauge).

    Deterministic: the argsort is stable, so within one expert tokens
    keep their (token-major, then choice-major) arrival order.
    """
    N, k = topk_idx.shape
    T = N * k
    e_flat = topk_idx.reshape(-1).astype(jnp.int32)
    if held is not None:
        lo, hi = held
        e_flat = jnp.where((e_flat >= lo) & (e_flat < hi), e_flat - lo,
                           num_experts)
    if carried is not None:
        e_flat = jnp.where(jnp.repeat(carried, k), e_flat, num_experts)
    # group `num_experts` collects what goes nowhere
    counts = jnp.zeros((num_experts + 1,), jnp.int32).at[e_flat].add(1)
    if num_blocks is None:
        num_blocks = num_group_blocks(T, num_experts, block_rows)
    gid, offsets = group_segments(counts[:num_experts], block_rows,
                                  num_blocks)
    order = jnp.argsort(e_flat, stable=True)
    e_sorted = e_flat[order]
    csum = jnp.cumsum(counts) - counts                  # exclusive
    rank = jnp.arange(T, dtype=jnp.int32) - csum[e_sorted]
    placed = offsets[jnp.minimum(e_sorted, num_experts - 1)] + rank
    rows = jnp.zeros((T,), jnp.int32).at[order].set(
        jnp.where(e_sorted < num_experts, placed, num_blocks * block_rows))
    return rows, gid, counts[:num_experts]


def dropless_dispatch(x, rows, top_k, padded_rows):
    """Scatter [N, D] tokens into the [padded_rows, D] grouped buffer:
    assignment ``n * k + j`` lands whole at ``rows[n * k + j]``;
    padding rows stay zero (the grouped kernel's contract), and a row
    past the buffer's end (a token that carries nothing) is dropped."""
    N, D = x.shape
    xr = jnp.repeat(x, top_k, axis=0)                   # [N*k, D]
    return jnp.zeros((padded_rows, D), x.dtype).at[rows].set(
        xr, mode="drop")


def dropless_combine(y_rows, rows, topk_val):
    """Gather expert outputs back and weighted-sum the k choices:
    ``y[n] = sum_j topk_val[n, j] * y_rows[rows[n*k+j]]`` (f32
    accumulation, cast back to the buffer dtype); a row past the
    buffer's end reads as zero."""
    N, k = topk_val.shape
    g = y_rows.at[rows].get(mode="fill", fill_value=0) \
        .reshape(N, k, y_rows.shape[-1])
    return jnp.einsum(
        "nk,nkd->nd", topk_val.astype(jnp.float32),
        g.astype(jnp.float32)).astype(y_rows.dtype)


def plan_counters(counts, block_rows, routed):
    """What a plan dispatched, as int32 ``[assignments, experts
    touched, rows of the grouped buffer the kernel runs over (padding
    included), the fullest expert's rows, assignments routed]``: the
    first four of the experts planned here, the last (``routed``) over
    all the experts the router chose among."""
    blocks = (counts + block_rows - 1) // block_rows
    return jnp.stack([counts.sum(), (counts > 0).sum(),
                      blocks.sum() * block_rows,
                      counts.max(), routed]).astype(jnp.int32)


def gated_experts(x, topk_idx, topk_weight, w_gate_up, w_down,
                  carried=None, act="silu", use_pallas=False, held=None):
    """The routed half of a gated expert layer on flat tokens: plan,
    scatter, ``act(x @ w_gate[e]) * (x @ w_up[e])`` then ``@ w_down[e]``
    through the grouped kernel (or its composite), weighted sum.
    ``w_gate_up`` [E, D, 2 I] and ``w_down`` [E, I, D] are read where
    they lie.  ``held = (lo, hi)``: the stacks are experts ``lo ... hi -
    1`` of those ``topk_idx`` names (``E = hi - lo``), and ``y`` is the
    part of the layer's result that they give; without it the stacks
    are all the experts.  Returns ``(y [N, D], plan_counters)``."""
    from ...ops import pallas_grouped as pg
    N, k = topk_idx.shape
    E = w_gate_up.shape[0]
    if held is not None and held[1] - held[0] != E:
        raise ValueError(f"experts {held[0]} to {held[1]} held, stacks "
                         f"of {E}")
    bm, nb, rows_total = pg.grouped_layout(N * k, E, x.dtype)
    use_pallas = use_pallas and bool(pg.gated_block_n(
        bm, x.shape[1], w_gate_up.shape[2] // 2, x.dtype))
    rows, gid, counts = dropless_plan(topk_idx, E, bm, nb, carried, held)
    routed = k * (N if carried is None else carried.sum())
    xd = dropless_dispatch(x, rows, k, rows_total)
    gated, down = (pg.grouped_gated_act, pg.grouped_linear_act) \
        if use_pallas else (pg.grouped_gated_act_ref,
                            pg.grouped_linear_act_ref)
    h = gated(xd, w_gate_up, block_group=gid, act=act)
    y_rows = down(h, w_down, block_group=gid)
    return (dropless_combine(y_rows, rows, topk_weight),
            plan_counters(counts, bm, routed))


def expert_imbalance(counts):
    """Load-imbalance gauge: ``max(counts) / mean(counts)`` (1.0 =
    perfectly balanced; the TPU508 threshold)."""
    c = jnp.asarray(counts, jnp.float32)
    return jnp.max(c) / jnp.maximum(jnp.mean(c), 1.0)


# ---------------------------------------------------------------------------
# Ring all-to-all (call inside shard_map)
# ---------------------------------------------------------------------------

def ring_all_to_all_local(x, *, axis, axis_size, mode="overlap"):
    """Per-shard tiled all-to-all on dim 0 through per-peer ``ppermute``
    hops (device ``i``'s chunk ``j`` lands at position ``i`` on device
    ``j`` — ``jax.lax.all_to_all(split=0, concat=0, tiled=True)``
    semantics, bit-exact: pure data movement).

    Overlapped mode issues one ``ppermute`` per peer offset; each hop
    is independent of the caller's subsequent compute on
    already-resident chunks, so XLA runs the transfers under the expert
    matmuls (the `overlap.all_gather_matmul_local` discipline).
    Sequential mode is the single fused collective.
    """
    P = int(axis_size)
    if P <= 1:
        return x
    if mode == "sequential":
        return jax.lax.all_to_all(x, axis, split_axis=0, concat_axis=0,
                                  tiled=True)
    C = x.shape[0] // P
    me = jax.lax.axis_index(axis)
    zero = jnp.zeros((), me.dtype)

    def chunk(i):
        idx = (i % P) * C
        return jax.lax.dynamic_slice(
            x, (idx,) + (zero,) * (x.ndim - 1), (C,) + x.shape[1:])

    out = jnp.zeros_like(x)
    # own chunk stays resident — no hop
    out = jax.lax.dynamic_update_slice(
        out, chunk(me), (me * C,) + (zero,) * (x.ndim - 1))
    for r in range(1, P):
        # peer-offset r: i sends its chunk (i+r) to device (i+r), where
        # it lands at source position (d-r); every hop is independent
        perm = [(i, (i + r) % P) for i in range(P)]
        recv = jax.lax.ppermute(chunk(me + r), axis, perm)
        out = jax.lax.dynamic_update_slice(
            out, recv, (((me - r) % P) * C,) + (zero,) * (x.ndim - 1))
    return out
