"""Grouped-expert matmul Pallas kernel: the MoE dropless-dispatch GEMM.

One kernel computes ``act(x @ w[e] + b[e])`` for every expert ``e`` in a
single pass over a flat, block-aligned token buffer

    x: [R, K]      R = num_blocks * block_rows

where each expert owns a run of whole ``block_rows``-row blocks (the
dropless router pads every expert's token count up to a block multiple,
exactly like the serving engine's ragged q-blocks).  One scalar array
describes the grouped layout:

    block_group[i]   which expert owns block ``i``
                     (``num_experts`` = null block: all rows padding)

built by `pallas_tiles.group_segments` from the per-expert token
counts.  The scalar-prefetched descriptor drives the weight/bias
BlockSpec index maps — the same machinery `pallas_ragged.py` uses to
route q-blocks through per-sequence block tables — while the matmul
itself is matmul-epilogue's full-K f32 accumulator
(`pallas_tiles.matmul_accum_blocks`): resident (block_rows, K) token
rows, N split under the VMEM weight-block budget.

The backward runs three pieces: ``dz = g * act'(z)`` elementwise in
XLA (exact, saved pre-activation), ``dx`` through this same kernel
with the transposed expert weights, and ``dw`` through a dedicated
grouped-accumulation kernel whose output block index map follows
``block_group`` — consecutive same-expert programs accumulate into one
revisited (1, bk, bn) block, the sequential-grid pattern of the LN
dgamma reduction.  ``db`` is a segment-sum in XLA.

`grouped_linear_act_ref` is the bit-exact XLA composite (same
per-block full-K f32 dots, same epilogue order) callers fall back to
when the gate disables the kernel.  Gated through ``pallas_gate``
("grouped_matmul" probe); `grouped_matmul_block_plan` exports the
exact specs for `analysis.tiling.audit_grouped_matmul` / tpu_lint.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .pallas_fused import ACTIVATIONS, _act_f32, _act_grad_f32
from .pallas_kernels import _mxu_dot
from .pallas_tiles import (_demote_f64, _interpret, _kernel_span,
                           _min_rows, _pad_dim, _round_up, _x32,
                           group_segments, matmul_accum_blocks,
                           num_group_blocks)

_NN = (((1,), (0,)), ((), ()))       # (m, k) . (k, n)

__all__ = [
    "grouped_block_rows",
    "grouped_gated_act",
    "grouped_gated_act_ref",
    "grouped_layout",
    "grouped_linear_act",
    "grouped_linear_act_ref",
    "grouped_matmul_block_plan",
    "lora_epilogue_block_plan",
    "lora_rank_pad",
    "lora_segment_epilogue",
    "lora_segment_epilogue_ref",
]


def grouped_block_rows(tokens, num_experts, dtype) -> int:
    """Rows per grouped block: adapts to the expected per-expert load
    (small decode batches must not pay a 128-row pad per expert) while
    staying a legal Mosaic sublane multiple, capped at one MXU height."""
    per = -(-max(int(tokens), 1) // max(int(num_experts), 1))
    return min(128, _round_up(per, _min_rows(jnp.dtype(dtype))))


def grouped_layout(tokens, num_experts, dtype):
    """(block_rows, num_blocks, rows): the static padded grouped layout
    for ``tokens`` dispatched rows across ``num_experts`` experts.  The
    router and the kernel must agree on this — routing scatters into
    ``rows`` flat rows, the kernel walks ``num_blocks`` blocks."""
    bm = grouped_block_rows(tokens, num_experts, dtype)
    nb = num_group_blocks(int(tokens), int(num_experts), bm)
    return bm, nb, nb * bm


def _null_block(gid_ref, num_experts):
    """Whether this program's row block belongs to no expert."""
    return gid_ref[pl.program_id(0)] >= num_experts


def _expert_block(gid, i, j, num_experts, offset=0):
    """A weight index map's (expert, 0, column block) for row block
    ``i``: a null block (``gid == num_experts``) names the last
    expert's first column block whatever ``j`` is, so that a run of
    null blocks fetches one tile and the stack needs no zero expert
    appended."""
    live = gid[i] < num_experts
    return (jnp.minimum(gid[i], num_experts - 1), 0,
            jnp.where(live, j, 0) + offset)


def _gmm_fwd_kernel(gid_ref, x_ref, w_ref, *refs, act, num_experts,
                    has_bias, save_z):
    """One (block, n-block) program: full-K dot against the owning
    expert's weight slice into a float32 accumulator (gid routes the
    index map).  A null block does no dot: its rows are ``act(0)``."""
    b_ref = refs[0] if has_bias else None
    o_ref = refs[1 if has_bias else 0]
    z_ref = refs[-1] if save_z else None
    null = _null_block(gid_ref, num_experts)

    def emit(z):
        if z_ref is not None:
            z_ref[:] = z.astype(z_ref.dtype)
        o_ref[:] = _act_f32(z, act).astype(o_ref.dtype)

    @pl.when(jnp.logical_not(null))
    def _live():
        z = _mxu_dot(x_ref[:], w_ref[0], _NN)              # (bm, bn)
        if b_ref is not None:
            z = z + b_ref[0].astype(jnp.float32)
        emit(z)

    @pl.when(null)
    def _null():
        emit(jnp.zeros(o_ref.shape, jnp.float32))


@_x32
def _gmm_call(xp, wp, bp, gid, act, bm, bn, direction, save_z=True):
    """Dispatch the grouped matmul pallas_call.  xp: [R, K] grouped
    rows; wp: [E, K, n_pad]; bp: [E, 1, n_pad] or None; gid: [R // bm]
    int32 block descriptors (``E``: a null block).  Returns ``(out, z)``
    (``z`` None unless ``save_z``)."""
    R, K = xp.shape
    E, _, n_pad = wp.shape
    nb = R // bm
    by_expert = lambda i, j, gid: _expert_block(gid, i, j, E)  # noqa: E731
    in_specs = [pl.BlockSpec((bm, K), lambda i, j, gid: (i, 0)),
                pl.BlockSpec((1, K, bn), by_expert)]
    operands = [xp, wp]
    if bp is not None:
        in_specs.append(pl.BlockSpec((1, 1, bn), by_expert))
        operands.append(bp)
    out_spec = pl.BlockSpec((bm, bn), lambda i, j, gid: (i, j))
    out_shape = jax.ShapeDtypeStruct((R, n_pad), xp.dtype)
    with _kernel_span("grouped_matmul", direction) as kernel_name:
        outs = pl.pallas_call(
            functools.partial(_gmm_fwd_kernel, act=act, num_experts=E,
                              has_bias=bp is not None, save_z=save_z),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1,
                grid=(nb, n_pad // bn),
                in_specs=in_specs,
                out_specs=[out_spec] * (2 if save_z else 1),
            ),
            out_shape=[out_shape] * (2 if save_z else 1),
            interpret=_interpret(),
            name=kernel_name,
        )(gid, *operands)
    return outs[0], (outs[1] if save_z else None)


def _gmm_gated_kernel(gid_ref, x_ref, wg_ref, wu_ref, o_ref, *, act,
                      num_experts):
    """One (block, n-block) program of the gated form: the expert's gate
    and up column blocks against the same resident rows, then
    ``act(gate) * up``; operands in their own type into float32
    accumulators.  No pre-activation is written."""
    null = _null_block(gid_ref, num_experts)

    @pl.when(jnp.logical_not(null))
    def _live():
        x = x_ref[:]
        gate = _mxu_dot(x, wg_ref[0], _NN)
        up = _mxu_dot(x, wu_ref[0], _NN)
        o_ref[:] = (_act_f32(gate, act) * up).astype(o_ref.dtype)

    @pl.when(null)
    def _null():
        o_ref[:] = jnp.zeros(o_ref.shape, o_ref.dtype)


@_x32
def _gmm_gated_call(xp, w, gid, act, bm, bn, direction="fwd"):
    """xp: [R, K]; w: [E, K, 2 N], an expert's gate columns then its up
    columns, N a multiple of ``bn``; gid: [R // bm].  Returns [R, N].
    The stack is passed twice, read through two index maps: a program
    takes column block ``j`` of the gate half and of the up half."""
    R, K = xp.shape
    E, _, n2 = w.shape
    n = n2 // 2
    nb, up0 = R // bm, n // bn

    def w_spec(offset):
        return pl.BlockSpec(
            (1, K, bn),
            lambda i, j, gid: _expert_block(gid, i, j, E, offset))

    with _kernel_span("grouped_matmul", direction) as kernel_name:
        return pl.pallas_call(
            functools.partial(_gmm_gated_kernel, act=act, num_experts=E),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1,
                grid=(nb, n // bn),
                in_specs=[pl.BlockSpec((bm, K), lambda i, j, gid: (i, 0)),
                          w_spec(0), w_spec(up0)],
                out_specs=pl.BlockSpec((bm, bn), lambda i, j, gid: (i, j)),
            ),
            out_shape=jax.ShapeDtypeStruct((R, n), xp.dtype),
            interpret=_interpret(),
            name=kernel_name,
        )(gid, xp, w, w)


def _gmm_dw_kernel(gid_ref, x_ref, dz_ref, dw_ref):
    """dw[e] += x_blk^T @ dz_blk: the block dim is innermost, so for a
    fixed (k-block, n-block) the programs of one expert are consecutive
    and the revisited (1, bk, bn) output block accumulates sequentially
    (LN-dgamma pattern); a new expert's first visit re-initialises."""
    m = pl.program_id(2)
    e = gid_ref[m]
    prev = gid_ref[jnp.maximum(m - 1, 0)]

    @pl.when(jnp.logical_or(m == 0, e != prev))
    def _init():
        dw_ref[...] = jnp.zeros_like(dw_ref)

    dw_ref[...] += jax.lax.dot_general(
        x_ref[:].astype(jnp.float32), dz_ref[:].astype(jnp.float32),
        (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)[None]       # (1, bk, bn)


def _gmm_dw_blocks(k, n, dtype):
    """(bk, bn, k_pad, n_pad) for the dw accumulation: both weight dims
    are output dims here, split on the same VMEM-budgeted lane grid."""
    bk = min(_round_up(max(k, 1), 128), 512)
    _, bn, _, n_pad = matmul_accum_blocks(8, k, n, dtype)
    return bk, bn, _round_up(k, bk), n_pad


@_x32
def _gmm_dw_call(xp, dzp, gid, num_experts, bm, bk, bn):
    R, k_pad = xp.shape
    n_pad = dzp.shape[1]
    nb = R // bm
    with _kernel_span("grouped_matmul", "bwd_dw") as kernel_name:
        dw = pl.pallas_call(
            _gmm_dw_kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1,
                grid=(k_pad // bk, n_pad // bn, nb),
                in_specs=[
                    pl.BlockSpec((bm, bk),
                                 lambda kb, nb_, m, gid: (m, kb)),
                    pl.BlockSpec((bm, bn),
                                 lambda kb, nb_, m, gid: (m, nb_)),
                ],
                out_specs=pl.BlockSpec(
                    (1, bk, bn),
                    lambda kb, nb_, m, gid: (gid[m], kb, nb_)),
            ),
            out_shape=jax.ShapeDtypeStruct(
                (num_experts + 1, k_pad, n_pad), jnp.float32),
            interpret=_interpret(),
            name=kernel_name,
        )(gid, xp, dzp)
    return dw


def _padded(w, b, n_pad):
    """Pad N to whole column blocks (no copy where it already is):
    wp [E, K, n_pad], bp [E, 1, n_pad] or None."""
    return (_pad_dim(w, 2, n_pad),
            None if b is None else _pad_dim(b, 1, n_pad)[:, None, :])


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _grouped_2d(x, w, b, gid, act):
    # no gradient asked: the pre-activation is not written
    return _grouped_2d_run(x, w, b, gid, act, save_z=False)[0]


def _grouped_2d_run(x, w, b, gid, act, save_z):
    R, K = x.shape
    E, _, N = w.shape
    bm = R // gid.shape[0]
    _, bn, _, n_pad = matmul_accum_blocks(bm, K, N, x.dtype)
    wp, bp = _padded(w, b, n_pad)
    out, z = _gmm_call(x, wp, bp, gid, act, bm, bn, "fwd", save_z)
    return out[:, :N], z


def _grouped_2d_fwd(x, w, b, gid, act):
    out, z = _grouped_2d_run(x, w, b, gid, act, save_z=True)
    return out, (x, w, b, gid, z[:, :w.shape[2]])


def _grouped_2d_bwd(act, res, g):
    x, w, b, gid, z = res
    R, K = x.shape
    E, _, N = w.shape
    bm = R // gid.shape[0]
    # epilogue backward: elementwise in XLA on the saved pre-activation
    dz32 = g.astype(jnp.float32) * _act_grad_f32(z.astype(jnp.float32),
                                                 act)
    dz = dz32.astype(x.dtype)
    # dx rides the SAME grouped kernel with transposed expert weights
    # (contraction over N, output K); bias zeros, identity epilogue
    wt = jnp.swapaxes(w, 1, 2)                          # [E, N, K]
    _, bn2, _, k_pad = matmul_accum_blocks(bm, N, K, x.dtype)
    dx_pad, _ = _gmm_call(dz, _pad_dim(wt, 2, k_pad), None, gid, "none",
                          bm, bn2, "bwd_dx", save_z=False)
    dx = dx_pad[:, :K].astype(x.dtype)
    # dw through the grouped-accumulation kernel
    bk, bn, k_pad2, n_pad = _gmm_dw_blocks(K, N, x.dtype)
    dw_full = _gmm_dw_call(_pad_dim(x, 1, k_pad2), _pad_dim(dz, 1, n_pad),
                           gid, E, bm, bk, bn)
    # experts that own zero blocks were never visited: their output
    # blocks are uninitialised — mask them to exact zeros
    blocks_per = jax.ops.segment_sum(
        jnp.ones_like(gid), gid, num_segments=E + 1)[:E]
    dw = jnp.where((blocks_per > 0)[:, None, None],
                   dw_full[:E, :K, :N], 0.0).astype(w.dtype)
    # db: per-expert row segment-sum (padding rows carry zero cotangent)
    row_gid = jnp.repeat(gid, bm)
    db = None if b is None else jax.ops.segment_sum(
        dz32, row_gid, num_segments=E + 1)[:E].astype(b.dtype)
    return dx, dw, db, np.zeros(gid.shape, dtype=jax.dtypes.float0)


_grouped_2d.defvjp(_grouped_2d_fwd, _grouped_2d_bwd)


def _check_layout(x, w, b, block_group):
    E, K, N = w.shape
    R = x.shape[0]
    nb = block_group.shape[0]
    if x.shape[1] != K:
        raise ValueError(f"x K={x.shape[1]} vs w K={K}")
    if R % nb:
        raise ValueError(
            f"{R} grouped rows not divisible by {nb} block descriptors")
    bm = R // nb
    if bm % _min_rows(x.dtype):
        raise ValueError(
            f"block_rows {bm} is not a {jnp.dtype(x.dtype).name} "
            f"sublane multiple ({_min_rows(x.dtype)})")
    if b is not None and tuple(b.shape) != (E, N):
        raise ValueError(f"b shape {b.shape} != ({E}, {N})")


def grouped_linear_act(x, w, b=None, *, block_group, act="none"):
    """``act(x @ w[e] + b[e])`` over block-aligned grouped rows; the
    Pallas path (interpret mode off-TPU); differentiable in x, w, b.

    x: [R, K] rows in grouped layout (R = num_blocks * block_rows,
    padding rows zero); w: [E, K, N] stacked expert weights, read where
    they lie (no expert is appended and, with N in whole column blocks,
    nothing is padded); b: [E, N] or None; block_group: [num_blocks]
    int32 from `pallas_tiles.group_segments` (``E`` marks a null block,
    whose rows come out as ``act(0)``).  Padding-row outputs are
    garbage-free but meaningless — callers gather only the dispatched
    rows back out.  Without a gradient the pre-activation is not
    written.
    """
    if act not in ACTIVATIONS:
        raise ValueError(f"act must be one of {ACTIVATIONS}, got {act!r}")
    x, w, b = _demote_f64(x, w, b)
    _check_layout(x, w, b, block_group)
    return _grouped_2d(x, w, None if b is None else b.astype(x.dtype),
                       block_group.astype(jnp.int32), act)


def _blocks_by_expert(x, w, block_group):
    """The composites' view: rows as [nb, bm, K] float32, each block's
    expert weights gathered (a null block takes the last expert's and
    is masked by the caller), and which blocks are live."""
    E = w.shape[0]
    gid = block_group.astype(jnp.int32)
    nb = gid.shape[0]
    xb = x.reshape(nb, x.shape[0] // nb, x.shape[1]).astype(jnp.float32)
    return xb, jnp.minimum(gid, E - 1), (gid < E)[:, None, None]


_BLOCK_DOT = (((2,), (1,)), ((0,), (0,)))     # [nb, bm, K] . [nb, K, N]


def grouped_linear_act_ref(x, w, b=None, *, block_group, act="none"):
    """XLA composite of `grouped_linear_act`: the same per-block
    full-K f32 dots (batched over blocks) and the same epilogue order —
    the dispatch fallback when the gate is off, and the parity
    reference for the kernel tests.  Numerically equivalent to the
    kernel within dot reduction order (the blocks batch into one 3D
    dot here): a few f32 ULP, never a tolerance-visible gap."""
    if act not in ACTIVATIONS:
        raise ValueError(f"act must be one of {ACTIVATIONS}, got {act!r}")
    x, w, b = _demote_f64(x, w, b)
    _check_layout(x, w, b, block_group)
    xb, eid, live = _blocks_by_expert(x, w, block_group)
    z = jax.lax.dot_general(xb, w[eid].astype(jnp.float32), _BLOCK_DOT,
                            preferred_element_type=jnp.float32)
    if b is not None:
        z = z + b.astype(x.dtype)[eid][:, None, :].astype(jnp.float32)
    z = jnp.where(live, z, 0.0)
    return _act_f32(z, act).reshape(x.shape[0], -1).astype(x.dtype)


def _check_gated(x, w, block_group, act):
    if act not in ACTIVATIONS:
        raise ValueError(f"act must be one of {ACTIVATIONS}, got {act!r}")
    if w.shape[2] % 2:
        raise ValueError(f"a gated stack holds gate and up columns side "
                         f"by side; got {w.shape[2]} columns")
    _check_layout(x, w, None, block_group)


def gated_block_n(block_rows, k, n, dtype) -> int:
    """Column block of the gated form: two weight tiles a program, so
    the budget is `matmul_accum_blocks`'s at twice the depth; a divisor
    of ``n`` or 0 (no such block: the composite runs)."""
    _, bn, _, _ = matmul_accum_blocks(block_rows, 2 * k, n, dtype)
    while bn >= 128 and n % bn:
        bn //= 2
    return bn if bn >= 128 else 0


def grouped_gated_act(x, w, *, block_group, act="silu"):
    """``act(x @ w_gate[e]) * (x @ w_up[e])`` over block-aligned grouped
    rows, one Mosaic program a (row block, column block): the SwiGLU
    half of an expert.  x: [R, K]; w: [E, K, 2 N], each expert's gate
    columns then its up columns, stored so once (N in whole 128-column
    blocks); block_group as for `grouped_linear_act`.  Returns [R, N];
    a null block's rows are zero.  Forward only: no pre-activation is
    written and no gradient is defined."""
    x, w = _demote_f64(x, w)
    _check_gated(x, w, block_group, act)
    bm = x.shape[0] // block_group.shape[0]
    bn = gated_block_n(bm, x.shape[1], w.shape[2] // 2, x.dtype)
    if not bn:
        raise ValueError(f"{w.shape[2] // 2} gated columns do not split "
                         "into 128-column blocks")
    return _gmm_gated_call(x, w, block_group.astype(jnp.int32), act, bm, bn)


def grouped_gated_act_ref(x, w, *, block_group, act="silu"):
    """XLA composite of `grouped_gated_act` (the CPU's path and a
    declined gate's): float32 dots batched over blocks."""
    x, w = _demote_f64(x, w)
    _check_gated(x, w, block_group, act)
    n = w.shape[2] // 2
    xb, eid, live = _blocks_by_expert(x, w, block_group)
    both = jax.lax.dot_general(xb, w[eid].astype(jnp.float32), _BLOCK_DOT,
                               preferred_element_type=jnp.float32)
    out = _act_f32(both[..., :n], act) * both[..., n:]
    return jnp.where(live, out, 0.0).reshape(x.shape[0], n).astype(x.dtype)


# =====================================================================
# Segmented LoRA SGMV epilogue: act(z + (x @ A[a]) @ B[a])
# =====================================================================
#
# The multi-LoRA serving epilogue (inference/serving/lora.py): after the
# base matmul produced the pre-activation ``z = x @ W + b``, each
# block-aligned row block adds its OWN adapter's low-rank update before
# the activation fires.  The per-block ``block_adapter`` descriptor is
# the same scalar-prefetched routing machinery as ``block_group`` above
# — in the engine it is literally the ragged step's per-q-block array,
# so one compiled program serves a batch where every row may carry a
# different adapter.  Null rows (``block_adapter == L``) ride an
# appended zero adapter: their output is ``act(z + 0.0)``, bitwise the
# plain fused epilogue.  The ``alpha / r`` scale is folded into the
# packed B stack at load time (lora.py), so merge/unmerge and this
# kernel share one scaled-B representation.
#
# Backward (custom_vjp, so per-tenant fine-tuning trains THROUGH the
# serving kernel): ``ds = g * act'(s)`` elementwise in XLA on the saved
# pre-activation sum; ``dz = ds`` (the base path's cotangent);
# ``dx = (ds @ B[a]^T) @ A[a]^T`` rides `_gmm_call` twice with the
# transposed stacks; ``dA = x^T @ (ds @ B[a]^T)`` and
# ``dB = (x @ A[a])^T @ ds`` ride the `_gmm_dw_call` grouped
# accumulator.  Adapters owning zero blocks are masked to exact zeros,
# the same uninitialised-block discipline as the grouped dw.


def lora_rank_pad(rank, dtype) -> int:
    """Packed adapter rank: ``rank`` rounded up to the dtype's minimum
    sublane count, so the B-stack's (r, bn) blocks tile legally and the
    A-stack's trailing dim lands lane-aligned after Mosaic's internal
    padding.  The store packs every adapter at this width (zero-filled
    tail rank columns contribute exact zeros to the update)."""
    return _round_up(max(int(rank), 1), _min_rows(jnp.dtype(dtype)))


def _lora_fwd_kernel(aid_ref, z_ref, x_ref, a_ref, b_ref, o_ref, s_ref,
                     *, act):
    """One (block, n-block) program: both low-rank dots in f32 against
    the owning adapter's slices (aid routes the index maps; the body
    never branches — null blocks hit the appended zero adapter)."""
    t = jax.lax.dot_general(
        x_ref[:].astype(jnp.float32), a_ref[0].astype(jnp.float32),
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)             # (bm, r)
    d = jax.lax.dot_general(
        t, b_ref[0].astype(jnp.float32),
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)             # (bm, bn)
    s = z_ref[:].astype(jnp.float32) + d
    s_ref[:] = s.astype(s_ref.dtype)
    o_ref[:] = _act_f32(s, act).astype(o_ref.dtype)


@_x32
def _lora_call(zp, xp, ap, bp, aid, act, bm, bn, direction):
    """Dispatch the SGMV epilogue pallas_call.  zp: [R, n_pad] base
    pre-activation; xp: [R, K] block-aligned rows; ap: [L+1, K, r]
    (zero null adapter appended); bp: [L+1, r, n_pad]; aid: [R // bm]
    int32 block descriptors."""
    R, K = xp.shape
    n_pad = bp.shape[2]
    r = ap.shape[2]
    nb = R // bm
    with _kernel_span("lora_sgmv", direction) as kernel_name:
        out, s = pl.pallas_call(
            functools.partial(_lora_fwd_kernel, act=act),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1,
                grid=(nb, n_pad // bn),
                in_specs=[
                    pl.BlockSpec((bm, bn), lambda i, j, aid: (i, j)),
                    pl.BlockSpec((bm, K), lambda i, j, aid: (i, 0)),
                    pl.BlockSpec((1, K, r),
                                 lambda i, j, aid: (aid[i], 0, 0)),
                    pl.BlockSpec((1, r, bn),
                                 lambda i, j, aid: (aid[i], 0, j)),
                ],
                out_specs=[
                    pl.BlockSpec((bm, bn), lambda i, j, aid: (i, j)),
                    pl.BlockSpec((bm, bn), lambda i, j, aid: (i, j)),
                ],
            ),
            out_shape=[
                jax.ShapeDtypeStruct((R, n_pad), xp.dtype),
                jax.ShapeDtypeStruct((R, n_pad), xp.dtype),
            ],
            interpret=_interpret(),
            name=kernel_name,
        )(aid, zp, xp, ap, bp)
    return out, s


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _lora_2d(z, x, a, b, aid, act):
    return _lora_2d_fwd(z, x, a, b, aid, act)[0]


def _lora_2d_fwd(z, x, a, b, aid, act):
    R, K = x.shape
    L, _, r = a.shape
    N = b.shape[2]
    bm = R // aid.shape[0]
    _, bn, _, n_pad = matmul_accum_blocks(bm, K, N, x.dtype)
    ap = jnp.concatenate([a, jnp.zeros((1, K, r), a.dtype)], axis=0)
    bp = _pad_dim(jnp.concatenate(
        [b, jnp.zeros((1, r, N), b.dtype)], axis=0), 2, n_pad)
    zp = _pad_dim(z, 1, n_pad)
    out, s = _lora_call(zp, x, ap, bp, aid, act, bm, bn, "fwd")
    return out[:, :N], (z, x, a, b, aid, s[:, :N])


def _lora_2d_bwd(act, res, g):
    z, x, a, b, aid, s = res
    R, K = x.shape
    L, _, r = a.shape
    N = b.shape[2]
    bm = R // aid.shape[0]
    # epilogue backward: elementwise in XLA on the saved pre-activation
    ds32 = g.astype(jnp.float32) * _act_grad_f32(s.astype(jnp.float32),
                                                 act)
    dz = ds32.astype(z.dtype)         # the base path's cotangent
    ds = ds32.astype(x.dtype)
    # u = ds @ B[a]^T through the grouped kernel (contraction over N)
    bt = jnp.swapaxes(b, 1, 2)                          # [L, N, r]
    _, bn_u, _, r_pad = matmul_accum_blocks(bm, N, r, x.dtype)
    u_pad, _ = _gmm_call(ds, _pad_dim(bt, 2, r_pad), None, aid, "none", bm,
                         bn_u, "bwd_dx", save_z=False)
    u = u_pad[:, :r].astype(x.dtype)
    # dx = u @ A[a]^T
    at = jnp.swapaxes(a, 1, 2)                          # [L, r, K]
    _, bn_x, _, k_pad = matmul_accum_blocks(bm, r, K, x.dtype)
    dx_pad, _ = _gmm_call(u, _pad_dim(at, 2, k_pad), None, aid, "none", bm,
                          bn_x, "bwd_dx", save_z=False)
    dx = dx_pad[:, :K].astype(x.dtype)
    # t = x @ A[a] recomputed (cheaper than a third fwd output)
    _, bn_t, _, r_pad2 = matmul_accum_blocks(bm, K, r, x.dtype)
    t_pad, _ = _gmm_call(x, _pad_dim(a, 2, r_pad2), None, aid, "none", bm,
                         bn_t, "fwd", save_z=False)
    t = t_pad[:, :r].astype(x.dtype)
    # dA[l] = x^T @ u and dB[l] = t^T @ ds through the grouped dw
    # accumulator.  The accumulator's revisited-block init trick needs
    # each adapter's blocks CONSECUTIVE — the MoE router guarantees
    # that, but serving q-blocks arrive in request order — so the
    # blocks are stable-sorted by adapter id first (a pure function of
    # the descriptor: the permutation replays bit-identically).
    # Adapters owning zero blocks were never visited — mask their
    # uninitialised output blocks to exact zeros.
    nbk = aid.shape[0]
    order = jnp.argsort(aid, stable=True)
    sgid = aid[order]

    def _by_adapter(v):
        return v.reshape(nbk, bm, v.shape[1])[order].reshape(v.shape)

    bk_a, bn_a, k_pad2, ra_pad = _gmm_dw_blocks(K, r, x.dtype)
    da_full = _gmm_dw_call(_by_adapter(_pad_dim(x, 1, k_pad2)),
                           _by_adapter(_pad_dim(u, 1, ra_pad)),
                           sgid, L, bm, bk_a, bn_a)
    bk_b, bn_b, rb_pad, nb_pad = _gmm_dw_blocks(r, N, x.dtype)
    db_full = _gmm_dw_call(_by_adapter(_pad_dim(t, 1, rb_pad)),
                           _by_adapter(_pad_dim(ds, 1, nb_pad)),
                           sgid, L, bm, bk_b, bn_b)
    blocks_per = jax.ops.segment_sum(
        jnp.ones_like(aid), aid, num_segments=L + 1)[:L]
    live = (blocks_per > 0)[:, None, None]
    da = jnp.where(live, da_full[:L, :K, :r], 0.0).astype(a.dtype)
    db = jnp.where(live, db_full[:L, :r, :N], 0.0).astype(b.dtype)
    return dz, dx, da, db, np.zeros(aid.shape, dtype=jax.dtypes.float0)


_lora_2d.defvjp(_lora_2d_fwd, _lora_2d_bwd)


def _check_lora_layout(z, x, a, b, block_adapter):
    L, K, r = a.shape
    R = x.shape[0]
    nb = block_adapter.shape[0]
    if x.shape[1] != K:
        raise ValueError(f"x K={x.shape[1]} vs a_stack K={K}")
    if tuple(b.shape[:2]) != (L, r):
        raise ValueError(
            f"b_stack leading dims {tuple(b.shape[:2])} != ({L}, {r})")
    if tuple(z.shape) != (R, b.shape[2]):
        raise ValueError(
            f"z shape {tuple(z.shape)} != ({R}, {b.shape[2]})")
    if R % nb:
        raise ValueError(
            f"{R} rows not divisible by {nb} block descriptors")
    bm = R // nb
    if bm % _min_rows(x.dtype):
        raise ValueError(
            f"block_rows {bm} is not a {jnp.dtype(x.dtype).name} "
            f"sublane multiple ({_min_rows(x.dtype)})")


def lora_segment_epilogue(z, x, a_stack, b_stack, *, block_adapter,
                          act="none"):
    """``act(z + (x @ A[a]) @ B[a])`` over block-aligned rows; the
    Pallas path (interpret mode off-TPU); differentiable in z, x and
    both adapter stacks.

    z: [R, N] base pre-activation (``x @ W + b``); x: [R, K] rows in
    q-block/grouped layout; a_stack: [L, K, r] packed adapter A
    weights; b_stack: [L, r, N] packed B weights WITH the ``alpha/r``
    scale folded in; block_adapter: [R // block_rows] int32 per-block
    adapter ids (``L`` marks a null block — zero update, so those rows
    emit ``act(z)`` bitwise).
    """
    if act not in ACTIVATIONS:
        raise ValueError(f"act must be one of {ACTIVATIONS}, got {act!r}")
    z, x, a_stack, b_stack = _demote_f64(z, x, a_stack, b_stack)
    _check_lora_layout(z, x, a_stack, b_stack, block_adapter)
    return _lora_2d(z, x, a_stack, b_stack,
                    block_adapter.astype(jnp.int32), act)


def lora_segment_epilogue_ref(z, x, a_stack, b_stack, *, block_adapter,
                              act="none"):
    """XLA composite of `lora_segment_epilogue`: the same per-block
    full-K f32 dots (batched over blocks) in the same order — low-rank
    contraction, expansion, add, activation — so it is the dispatch
    fallback when the gate is off and the parity reference for the
    kernel tests.  Numerically equivalent to the kernel within dot
    reduction order."""
    if act not in ACTIVATIONS:
        raise ValueError(f"act must be one of {ACTIVATIONS}, got {act!r}")
    z, x, a_stack, b_stack = _demote_f64(z, x, a_stack, b_stack)
    _check_lora_layout(z, x, a_stack, b_stack, block_adapter)
    L, K, r = a_stack.shape
    N = b_stack.shape[2]
    aid = block_adapter.astype(jnp.int32)
    nb = aid.shape[0]
    bm = x.shape[0] // nb
    ap = jnp.concatenate(
        [a_stack, jnp.zeros((1, K, r), a_stack.dtype)], axis=0)
    bp = jnp.concatenate(
        [b_stack, jnp.zeros((1, r, N), b_stack.dtype)], axis=0)
    xb = x.reshape(nb, bm, K).astype(jnp.float32)
    ag = ap[aid].astype(jnp.float32)                    # [nb, K, r]
    t = jax.lax.dot_general(
        xb, ag, (((2,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32)             # [nb, bm, r]
    bg = bp[aid].astype(jnp.float32)                    # [nb, r, N]
    d = jax.lax.dot_general(
        t, bg, (((2,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32)
    s = z.reshape(nb, bm, N).astype(jnp.float32) + d
    return _act_f32(s, act).reshape(nb * bm, N).astype(x.dtype)


def lora_epilogue_block_plan(tokens, k, n, rank, num_adapters,
                             dtype=jnp.float32, direction="fwd",
                             block_rows=None):
    """The exact block plan the SGMV epilogue uses for ``tokens`` rows.
    Same contract as `grouped_matmul_block_plan`; the scalar-prefetched
    ``block_adapter`` descriptor is untiled and omitted.

    ``block_rows`` pins the serving engine's ragged q-block height;
    default is the grouped fine-tuning layout.  ``direction`` selects
    ``"fwd"`` (`_lora_call`; also the shape of the two dx passes with
    dims permuted) or ``"bwd_dw"`` (the dA grouped accumulation).
    """
    dtype = jnp.dtype(dtype)
    f32 = jnp.dtype(jnp.float32)
    if block_rows:
        bm = int(block_rows)
        nb = -(-int(tokens) // bm)
    else:
        bm, nb, _ = grouped_layout(tokens, num_adapters, dtype)
    rows = nb * bm
    r = lora_rank_pad(rank, dtype)
    L = num_adapters
    base = {"direction": direction, "block_rows": bm, "num_blocks": nb,
            "rank": r, "scratch": ()}
    if direction == "fwd":
        _, bn, _, n_pad = matmul_accum_blocks(bm, k, n, dtype)
        base["grid"] = (nb, n_pad // bn)
        base["block_n"] = bn
        base["operands"] = [
            ("z", (bm, bn), (rows, n_pad), dtype),
            ("x", (bm, k), (rows, k), dtype),
            ("a", (1, k, r), (L + 1, k, r), dtype),
            ("b", (1, r, bn), (L + 1, r, n_pad), dtype),
            ("out", (bm, bn), (rows, n_pad), dtype),
            ("s", (bm, bn), (rows, n_pad), dtype),
        ]
    elif direction == "bwd_dw":
        bk, bn, k_pad, r_pad = _gmm_dw_blocks(k, r, dtype)
        base["grid"] = (k_pad // bk, r_pad // bn, nb)
        base["block_k"] = bk
        base["block_n"] = bn
        base["operands"] = [
            ("x", (bm, bk), (rows, k_pad), dtype),
            ("u", (bm, bn), (rows, r_pad), dtype),
            ("da", (1, bk, bn), (L + 1, k_pad, r_pad), f32),
        ]
    else:
        raise ValueError(
            f"direction must be fwd|bwd_dw, got {direction!r}")
    return base


def grouped_matmul_block_plan(tokens, k, n, num_experts,
                              dtype=jnp.float32, direction="fwd"):
    """The exact block plan the grouped matmul uses for ``tokens``
    dispatched rows.  Same contract as `flash_block_plan`; the scalar-
    prefetched ``block_group`` descriptor is untiled and omitted, like
    `ragged_block_plan`'s tables.

    ``direction`` selects ``"fwd"`` (`_gmm_call`, also the shape of the
    dx pass with k/n swapped) or ``"bwd_dw"`` (`_gmm_dw_call`).
    """
    dtype = jnp.dtype(dtype)
    f32 = jnp.dtype(jnp.float32)
    bm, nb, rows = grouped_layout(tokens, num_experts, dtype)
    E = num_experts
    base = {"direction": direction, "block_rows": bm, "num_blocks": nb,
            "scratch": ()}
    if direction == "fwd":
        _, bn, _, n_pad = matmul_accum_blocks(bm, k, n, dtype)
        base["grid"] = (nb, n_pad // bn)
        base["block_n"] = bn
        base["operands"] = [
            ("x", (bm, k), (rows, k), dtype),
            ("w", (1, k, bn), (E, k, n_pad), dtype),
            ("b", (1, 1, bn), (E, 1, n_pad), dtype),
            ("out", (bm, bn), (rows, n_pad), dtype),
            ("z", (bm, bn), (rows, n_pad), dtype),
        ]
    elif direction == "bwd_dw":
        bk, bn, k_pad, n_pad = _gmm_dw_blocks(k, n, dtype)
        base["grid"] = (k_pad // bk, n_pad // bn, nb)
        base["block_k"] = bk
        base["block_n"] = bn
        base["operands"] = [
            ("x", (bm, bk), (rows, k_pad), dtype),
            ("dz", (bm, bn), (rows, n_pad), dtype),
            ("dw", (1, bk, bn), (E + 1, k_pad, n_pad), f32),
        ]
    else:
        raise ValueError(
            f"direction must be fwd|bwd_dw, got {direction!r}")
    return base
