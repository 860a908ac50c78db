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

`measured_ep_dispatch` drives the ring from the host (the
``measured_sharded_matmul`` pattern), emitting ``cat="collective"``
spans carrying ``axis="ep"`` whose lifetime brackets the in-flight hop
while the resident chunk's expert compute dispatches inside the window
— that is what ``observability.phase_breakdown()`` turns into
``overlap_ratio_ep``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ... import observability as obs
from ...ops.pallas_tiles import group_segments, num_group_blocks

__all__ = [
    "dropless_combine", "dropless_dispatch", "dropless_plan",
    "expert_imbalance", "measured_ep_dispatch", "ring_all_to_all_local",
]


# ---------------------------------------------------------------------------
# Dropless routing (single-device / inside one shard)
# ---------------------------------------------------------------------------

def dropless_plan(topk_idx, num_experts, block_rows, num_blocks=None):
    """Plan the grouped layout for top-k assignments — droplessly.

    ``topk_idx``: [N, k] int expert choices.  ``num_blocks`` must be
    the static `pallas_tiles.num_group_blocks(N * k, num_experts,
    block_rows)` (computed here when N is concrete).

    Returns ``(rows, block_group, counts)``:
      * ``rows``: [N * k] int32 — the grouped-buffer row of flat
        assignment ``n * k + j`` (rows are unique: scatter is exact);
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
    counts = jnp.zeros((num_experts,), jnp.int32).at[e_flat].add(1)
    if num_blocks is None:
        num_blocks = num_group_blocks(T, num_experts, block_rows)
    gid, offsets = group_segments(counts, block_rows, num_blocks)
    order = jnp.argsort(e_flat, stable=True)
    e_sorted = e_flat[order]
    csum = jnp.cumsum(counts) - counts                  # exclusive
    rank = jnp.arange(T, dtype=jnp.int32) - csum[e_sorted]
    rows = jnp.zeros((T,), jnp.int32).at[order].set(
        offsets[e_sorted] + rank)
    return rows, gid, counts


def dropless_dispatch(x, rows, top_k, padded_rows):
    """Scatter [N, D] tokens into the [padded_rows, D] grouped buffer:
    assignment ``n * k + j`` lands whole at ``rows[n * k + j]``;
    padding rows stay zero (the grouped kernel's contract)."""
    N, D = x.shape
    xr = jnp.repeat(x, top_k, axis=0)                   # [N*k, D]
    return jnp.zeros((padded_rows, D), x.dtype).at[rows].set(xr)


def dropless_combine(y_rows, rows, topk_val):
    """Gather expert outputs back and weighted-sum the k choices:
    ``y[n] = sum_j topk_val[n, j] * y_rows[rows[n*k+j]]`` (f32
    accumulation, cast back to the buffer dtype)."""
    N, k = topk_val.shape
    g = y_rows[rows].reshape(N, k, y_rows.shape[-1])
    return jnp.einsum(
        "nk,nkd->nd", topk_val.astype(jnp.float32),
        g.astype(jnp.float32)).astype(y_rows.dtype)


def expert_imbalance(counts):
    """Load-imbalance gauge: ``max(counts) / mean(counts)`` (1.0 =
    perfectly balanced; the bench gauge and the TPU508 threshold)."""
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


# ---------------------------------------------------------------------------
# Measured host-driven ring (timeline evidence for overlap_ratio_ep)
# ---------------------------------------------------------------------------

#: (plan token, axis, shape, dtype) -> compiled one-hop rotation
_rot_cache: dict = {}


def _rot_fn(plan, axis, x):
    from jax.sharding import PartitionSpec as P
    key = (plan.cache_token(), axis, x.shape, str(x.dtype))
    fn = _rot_cache.get(key)
    if fn is not None:
        return fn
    size = plan.axis_size(axis)
    perm = [(i, (i + 1) % size) for i in range(size)]
    spec = P(*((axis,) + (None,) * (x.ndim - 1)))
    rot = jax.shard_map(lambda v: jax.lax.ppermute(v, axis, perm),
                        mesh=plan.mesh, in_specs=spec, out_specs=spec,
                        check_vma=False)
    fn = jax.jit(rot).lower(x).compile()
    _rot_cache[key] = fn
    return fn


def measured_ep_dispatch(xd, expert_fn, *, plan, axis="ep", mode=None):
    """Drive the expert-dispatch ring step-wise from the host so the
    timeline records *real* ``axis="ep"`` collective spans.

    ``xd``: the global grouped token buffer, dim 0 sharded over
    ``axis`` (each of the P ring positions holds one chunk);
    ``expert_fn(xd)`` is the expert compute over the whole buffer.  Each of the P-1 ring
    hops is a compiled one-hop ``ppermute`` over the plan's mesh
    running inside a ``cat="collective"`` span carrying the ``ep`` axis
    attr; overlapped mode dispatches the resident chunks' expert
    compute while the hop is in flight — that nesting is what
    ``phase_breakdown()`` turns into ``overlap_ratio_ep``.  Sequential
    mode blocks on each hop first, so its ratio is ~0.  Step 0's
    compute over the un-rotated buffer is the real result (later
    steps' compute on rotated copies models the pipelined chunk
    arrival, exactly like ``measured_sharded_matmul``'s replicated
    partials).
    """
    from . import overlap as _overlap
    if plan is None or plan.is_virtual or plan.axis_size(axis) <= 1:
        raise ValueError("measured_ep_dispatch needs a real plan with "
                         f"axis {axis!r} > 1")
    if mode is None:
        mode = _overlap.select_mode(plan, axis)
    P = int(plan.axis_size(axis))
    xd = jnp.asarray(xd)
    rot = _rot_fn(plan, axis, xd)
    nb = int(xd.size) * xd.dtype.itemsize // P
    out = None
    cur = xd
    for r in range(P):
        if mode == "overlap" and r < P - 1:
            with obs.span("collective:moe.all_to_all", cat="collective",
                          axis=axis, bytes=nb, mode=mode, peers=P):
                nxt = rot(cur)
                with obs.span("dispatch:moe.expert_chunk",
                              cat="dispatch", axis=axis, mode=mode):
                    y = expert_fn(cur)
                    jax.block_until_ready(y)
                jax.block_until_ready(nxt)
        else:
            nxt = None
            if r < P - 1:
                with obs.span("collective:moe.all_to_all",
                              cat="collective", axis=axis, bytes=nb,
                              mode=mode, peers=P):
                    nxt = rot(cur)
                    jax.block_until_ready(nxt)
            with obs.span("dispatch:moe.expert_chunk", cat="dispatch",
                          axis=axis, mode=mode):
                y = expert_fn(cur)
                jax.block_until_ready(y)
        if r == 0:
            out = y
        if nxt is not None:
            cur = nxt
    return out
