"""Pallas TPU kernels for the hot op set.

Reference parity: the reference implements these as hand-written CUDA in
`paddle/phi/kernels/gpu/` — `flash_attn_kernel.cu` (wrapping
third_party/flashattn), `layer_norm_kernel.cu`, `rms_norm_kernel.cu`,
`c_softmax_with_cross_entropy_op.cu` [UNVERIFIED — empty reference mount;
upstream-layout paths per SURVEY.md §2.1].

TPU-native design: each kernel is a `pl.pallas_call` tiled for the MXU/VPU
(blocks of 128 lanes, f32 accumulation in VMEM) wrapped in
`jax.custom_vjp` so both the eager tape (jax.vjp in core/dispatch.py) and
`to_static` (jax.jit) differentiate through the hand-written backward.

On non-TPU backends (tests run on XLA-CPU) the same kernels execute in
Pallas interpret mode, so numerics are validated everywhere the suite
runs; on TPU they compile via Mosaic.

Mosaic block-mapping rules honoured here (the round-2 kernels violated
them and failed to compile on hardware): the last two dims of every
BlockSpec must each be divisible by (8, 128) or equal to the overall
array dim.  Consequently:
  * every array crossing the pallas_call boundary is rank >= 2;
  * per-row statistics (lse, mean, rstd, loss, delta, incoming
    cotangents, integer labels) travel as f32/int32 arrays with a
    trailing `_STAT_LANES == 8` lane dim — written as lane-broadcasts,
    read back via `[:, :1]` (8 == the array dim satisfies the lane
    rule; only 8x memory on arrays that are tiny to begin with);
  * in-kernel reductions keep dims (`keepdims=True`) so all VPU values
    stay rank-2;
  * dgamma/dbeta are reduced with the sequential-grid accumulation
    pattern: one (8, N) output block revisited by every program,
    zero-initialised under `pl.when(program_id == 0)`.

Layout conventions:
  * attention layout inside the kernels is [batch*heads, seq, head_dim]
    (callers convert from Paddle's [B, S, H, D]);
  * sequence dims are padded to a multiple of the block size here, with
    padding masked inside the kernels (cols → -inf, padded lse → +inf);
  * all softmax/variance math runs in float32 regardless of input dtype.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

# pltpu is importable on CPU builds of jax as well; the VMEM scratch
# accumulators in the xent kernels require it even in interpret mode
from jax.experimental.pallas import tpu as pltpu

__all__ = [
    "flash_attention",
    "flash_block_plan",
    "fused_layer_norm",
    "fused_rms_norm",
    "fused_softmax_cross_entropy",
    "paged_attention",
    "paged_block_plan",
]

# Shared tile primitives (see ops/pallas_tiles.py): tracing policy,
# dtype-aware block picking, stat-lane layout, padding.  These names are
# re-exported here so downstream `from .pallas_kernels import _x32, ...`
# keeps binding the SAME objects — the refactor's bit-identity contract.
from .pallas_tiles import (_NEG_INF, _STAT_LANES, _demote_f64,
                           _interpret, _kernel_span, _lanes,
                           _ln_block_rows, _min_rows, _pad_dim,
                           _round_up, _sane_block, _x32, _xent_blocks,
                           softmax_scratch, stat_scratch)


# =====================================================================
# Flash attention
# =====================================================================

def _attn_fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *,
                     scale, causal, block_k, sk_real, offset):
    """One (batch*head, q-block) program: online-softmax over K blocks."""
    q = q_ref[0].astype(jnp.float32)                     # (block_q, D)
    block_q, _ = q.shape
    sk_pad = k_ref.shape[1]
    q_start = pl.program_id(1) * block_q

    num_k_blocks = sk_pad // block_k
    if causal:
        # highest kv index any row in this q block may attend to
        hi = q_start + block_q + offset
        num_k_blocks = jnp.minimum(
            num_k_blocks, (jnp.maximum(hi, 0) + block_k - 1) // block_k)

    def body(i, carry):
        m_prev, l_prev, acc = carry                       # (bq,1)x2,(bq,D)
        k_blk = k_ref[0, pl.ds(i * block_k, block_k), :].astype(jnp.float32)
        v_blk = v_ref[0, pl.ds(i * block_k, block_k), :].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # (bq, bk)
        col = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1) + i * block_k
        mask = col < sk_real                              # K padding
        if causal:
            row = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) + q_start
            mask = jnp.logical_and(mask, col <= row + offset)
        s = jnp.where(mask, s, _NEG_INF)
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        # explicit zero on masked cols: for a fully-masked row s == m_new
        # == _NEG_INF and exp(s - m_new) would be 1, not 0
        p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
        acc = acc * alpha + jax.lax.dot_general(
            p, v_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return m_new, l_new, acc

    m0 = jnp.full((block_q, 1), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((block_q, 1), jnp.float32)
    acc0 = jnp.zeros((block_q, q.shape[-1]), jnp.float32)
    m, l, acc = jax.lax.fori_loop(0, num_k_blocks, body, (m0, l0, acc0))
    l_safe = jnp.where(l == 0.0, 1.0, l)
    o_ref[0] = (acc / l_safe).astype(o_ref.dtype)
    lse = jnp.where(l == 0.0, _NEG_INF, m + jnp.log(l_safe))  # (bq, 1)
    lse_ref[0] = jnp.broadcast_to(lse, (block_q, _STAT_LANES))


def _attn_bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                        dq_ref, *, scale, causal, block_k, sk_real, offset):
    q = q_ref[0].astype(jnp.float32)
    do = do_ref[0].astype(jnp.float32)
    lse = lse_ref[0][:, :1]                               # (bq, 1)
    delta = delta_ref[0][:, :1]
    block_q = q.shape[0]
    sk_pad = k_ref.shape[1]
    q_start = pl.program_id(1) * block_q

    num_k_blocks = sk_pad // block_k
    if causal:
        hi = q_start + block_q + offset
        num_k_blocks = jnp.minimum(
            num_k_blocks, (jnp.maximum(hi, 0) + block_k - 1) // block_k)

    def body(i, dq):
        k_blk = k_ref[0, pl.ds(i * block_k, block_k), :].astype(jnp.float32)
        v_blk = v_ref[0, pl.ds(i * block_k, block_k), :].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        col = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1) + i * block_k
        mask = col < sk_real
        if causal:
            row = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) + q_start
            mask = jnp.logical_and(mask, col <= row + offset)
        s = jnp.where(mask, s, _NEG_INF)
        p = jnp.exp(s - lse)                              # (bq, bk)
        dp = jax.lax.dot_general(
            do, v_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * scale
        return dq + jax.lax.dot_general(
            ds, k_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    dq0 = jnp.zeros_like(q)
    dq = jax.lax.fori_loop(0, num_k_blocks, body, dq0)
    dq_ref[0] = dq.astype(dq_ref.dtype)


def _attn_bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                         dk_ref, dv_ref, *, scale, causal, block_q,
                         sq_real, offset):
    k = k_ref[0].astype(jnp.float32)                     # (block_k, D)
    v = v_ref[0].astype(jnp.float32)
    block_k = k.shape[0]
    sq_pad = q_ref.shape[1]
    k_start = pl.program_id(1) * block_k

    lo = 0
    num_q_blocks = sq_pad // block_q
    if causal:
        # first q row that can see this k block: row >= k_start - offset
        lo = jnp.maximum(k_start - offset, 0) // block_q

    def body(i, carry):
        dk, dv = carry
        q_blk = q_ref[0, pl.ds(i * block_q, block_q), :].astype(jnp.float32)
        do_blk = do_ref[0, pl.ds(i * block_q, block_q), :].astype(
            jnp.float32)
        lse_blk = lse_ref[0, pl.ds(i * block_q, block_q), :][:, :1]
        delta_blk = delta_ref[0, pl.ds(i * block_q, block_q), :][:, :1]
        s = jax.lax.dot_general(
            q_blk, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # (bq, bk)
        row = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) + i * block_q
        mask = row < sq_real
        if causal:
            col = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1) + k_start
            mask = jnp.logical_and(mask, col <= row + offset)
        s = jnp.where(mask, s, _NEG_INF)
        p = jnp.exp(s - lse_blk)
        dv = dv + jax.lax.dot_general(
            p, do_blk, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(
            do_blk, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - delta_blk) * scale
        dk = dk + jax.lax.dot_general(
            ds, q_blk, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return dk, dv

    dk0 = jnp.zeros_like(k)
    dv0 = jnp.zeros_like(v)
    dk, dv = jax.lax.fori_loop(lo, num_q_blocks, body, (dk0, dv0))
    dk_ref[0] = dk.astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)


@_x32
def _flash_fwd(q, k, v, scale, causal, sq_real, sk_real, block_q, block_k):
    bh, sq_pad, d = q.shape
    sk_pad = k.shape[1]
    offset = sk_real - sq_real  # causal alignment for cross-length attn
    grid = (bh, sq_pad // block_q)
    with _kernel_span("flash_attention", "fwd") as kernel_name:
        out, lse = pl.pallas_call(
        functools.partial(_attn_fwd_kernel, scale=scale, causal=causal,
                          block_k=block_k, sk_real=sk_real, offset=offset),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, sk_pad, d), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, sk_pad, d), lambda b, i: (b, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, block_q, _STAT_LANES), lambda b, i: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, sq_pad, d), q.dtype),
            jax.ShapeDtypeStruct((bh, sq_pad, _STAT_LANES), jnp.float32),
        ],
        interpret=_interpret(),
        name=kernel_name,
    )(q, k, v)
    return out, lse


@_x32
def _flash_bwd(q, k, v, do, out, lse, scale, causal, sq_real, sk_real,
               block_q, block_k):
    """lse arrives in the (BH, Sq_pad, _STAT_LANES) stat-lane layout."""
    bh, sq_pad, d = q.shape
    sk_pad = k.shape[1]
    offset = sk_real - sq_real
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1, keepdims=True)              # (BH, Sq_pad, 1)
    delta = jnp.broadcast_to(delta, (bh, sq_pad, _STAT_LANES))
    # p = exp(s - lse) must be 0 wherever a row has no visible keys:
    # padded q rows AND real rows the causal mask empties (Sq > Sk case,
    # forward stored lse = _NEG_INF there).  Force lse huge so exp → 0.
    row = jnp.arange(sq_pad)[None, :, None]
    empty = jnp.logical_or(row >= sq_real, lse <= _NEG_INF / 2)
    lse_safe = jnp.where(empty, jnp.float32(1e30), lse)
    with _kernel_span("flash_attention", "bwd_dq") as kernel_name:
        dq = pl.pallas_call(
        functools.partial(_attn_bwd_dq_kernel, scale=scale, causal=causal,
                          block_k=block_k, sk_real=sk_real, offset=offset),
        grid=(bh, sq_pad // block_q),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, sk_pad, d), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, sk_pad, d), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, block_q, d), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, block_q, _STAT_LANES), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, block_q, _STAT_LANES), lambda b, i: (b, i, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda b, i: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, sq_pad, d), q.dtype),
        interpret=_interpret(),
        name=kernel_name,
    )(q, k, v, do, lse_safe, delta)
    with _kernel_span("flash_attention", "bwd_dkv") as kernel_name:
        dk, dv = pl.pallas_call(
        functools.partial(_attn_bwd_dkv_kernel, scale=scale, causal=causal,
                          block_q=block_q, sq_real=sq_real, offset=offset),
        grid=(bh, sk_pad // block_k),
        in_specs=[
            pl.BlockSpec((1, sq_pad, d), lambda b, j: (b, 0, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, j: (b, j, 0)),
            pl.BlockSpec((1, sq_pad, d), lambda b, j: (b, 0, 0)),
            pl.BlockSpec((1, sq_pad, _STAT_LANES), lambda b, j: (b, 0, 0)),
            pl.BlockSpec((1, sq_pad, _STAT_LANES), lambda b, j: (b, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_k, d), lambda b, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, j: (b, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, sk_pad, d), k.dtype),
            jax.ShapeDtypeStruct((bh, sk_pad, d), v.dtype),
        ],
        interpret=_interpret(),
        name=kernel_name,
    )(q, k, v, do, lse_safe, delta)
    return dq, dk, dv


def set_flash_block_sizes(block_q=None, block_k=None):
    """Process-wide override for the sweep harness."""
    global _block_override
    _block_override = (block_q, block_k)


_block_override = (None, None)


def _pick_block(seq: int, which: int = 0, dtype=jnp.float32) -> int:
    """Q/K block rows for `seq`: legal by construction for `dtype`
    (sublane multiple of _min_rows), covering `seq` after _round_up
    padding.  The sweep harness's override is clamped to legality
    rather than trusted — an illegal sweep value degrades to the
    default instead of crashing Mosaic."""
    mr = _min_rows(dtype)
    ov = _sane_block(_block_override[which], seq, mr)
    if ov:
        return ov
    return 128 if seq >= 128 else _round_up(max(seq, mr), mr)


def flash_block_plan(batch, seq_q, seq_k, heads, head_dim,
                     dtype=jnp.float32, direction="fwd"):
    """The exact block plan the flash kernels use for these shapes.

    ``direction`` selects the pallas_call being described: ``"fwd"``
    (`_flash_fwd`), ``"bwd_dq"`` (the dq pass of `_flash_bwd`) or
    ``"bwd_dkv"`` (its dk/dv pass).  Returns grid, chosen block sizes,
    and per-operand (name, block_shape, padded_array_shape, dtype)
    tuples in pallas_call order — the input
    `analysis.tiling.check_pallas_call` validates statically (and the
    gate uses to diagnose probe failures).  Keep in lockstep with the
    kernel builders' specs.
    """
    dtype = jnp.dtype(dtype)
    block_q = _pick_block(seq_q, 0, dtype)
    block_k = _pick_block(seq_k, 1, dtype)
    bh = batch * heads
    sq_pad = _round_up(seq_q, block_q)
    sk_pad = _round_up(seq_k, block_k)
    d = head_dim
    f32 = jnp.dtype(jnp.float32)
    base = {
        "direction": direction,
        "block_q": block_q,
        "block_k": block_k,
        "scratch": (),
    }
    q_blk = ("q", (1, block_q, d), (bh, sq_pad, d), dtype)
    q_full = ("q", (1, sq_pad, d), (bh, sq_pad, d), dtype)
    k_blk = ("k", (1, block_k, d), (bh, sk_pad, d), dtype)
    k_full = ("k", (1, sk_pad, d), (bh, sk_pad, d), dtype)
    v_blk = ("v", (1, block_k, d), (bh, sk_pad, d), dtype)
    v_full = ("v", (1, sk_pad, d), (bh, sk_pad, d), dtype)
    stat_blk = lambda name: (  # noqa: E731 - local table helper
        name, (1, block_q, _STAT_LANES), (bh, sq_pad, _STAT_LANES), f32)
    stat_full = lambda name: (  # noqa: E731
        name, (1, sq_pad, _STAT_LANES), (bh, sq_pad, _STAT_LANES), f32)
    if direction == "fwd":
        base["grid"] = (bh, sq_pad // block_q)
        base["operands"] = [
            q_blk, k_full, v_full,
            ("out", (1, block_q, d), (bh, sq_pad, d), dtype),
            stat_blk("lse"),
        ]
    elif direction == "bwd_dq":
        base["grid"] = (bh, sq_pad // block_q)
        base["operands"] = [
            q_blk, k_full, v_full,
            ("do", (1, block_q, d), (bh, sq_pad, d), dtype),
            stat_blk("lse"), stat_blk("delta"),
            ("dq", (1, block_q, d), (bh, sq_pad, d), dtype),
        ]
    elif direction == "bwd_dkv":
        base["grid"] = (bh, sk_pad // block_k)
        base["operands"] = [
            q_full, k_blk, v_blk,
            ("do", (1, sq_pad, d), (bh, sq_pad, d), dtype),
            stat_full("lse"), stat_full("delta"),
            ("dk", (1, block_k, d), (bh, sk_pad, d), dtype),
            ("dv", (1, block_k, d), (bh, sk_pad, d), dtype),
        ]
    else:
        raise ValueError(
            f"direction must be fwd|bwd_dq|bwd_dkv, got {direction!r}")
    return base


def paged_block_plan(num_heads, head_dim, block_size, num_blocks=64,
                     batch=1, table_width=8, dtype=jnp.float32):
    """The paged decode-attention block plan (see `paged_attention`)."""
    dtype = jnp.dtype(dtype)
    f32 = jnp.dtype(jnp.float32)
    D = head_dim
    pool = (num_blocks, num_heads, block_size, D)
    return {
        "grid": (batch, num_heads, table_width),
        "operands": [
            ("q", (1, 1, 1, D), (batch, num_heads, 1, D), dtype),
            ("k_pool", (1, 1, block_size, D), pool, dtype),
            ("v_pool", (1, 1, block_size, D), pool, dtype),
            ("out", (1, 1, 1, D), (batch, num_heads, 1, D), dtype),
        ],
        "scratch": (
            ((_STAT_LANES, D), f32),
            ((_STAT_LANES, _STAT_LANES), f32),
            ((_STAT_LANES, _STAT_LANES), f32),
        ),
    }


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _flash_attention_bhsd(q, k, v, scale, causal):
    out, _ = _flash_attention_bhsd_fwd(q, k, v, scale, causal)
    return out


def _flash_attention_bhsd_fwd(q, k, v, scale, causal):
    bh, sq, d = q.shape
    sk = k.shape[1]
    block_q = _pick_block(sq, 0, q.dtype)
    block_k = _pick_block(sk, 1, q.dtype)
    qp = _pad_dim(q, 1, _round_up(sq, block_q))
    kp = _pad_dim(k, 1, _round_up(sk, block_k))
    vp = _pad_dim(v, 1, _round_up(sk, block_k))
    out, lse = _flash_fwd(qp, kp, vp, scale, causal, sq, sk,
                          block_q, block_k)
    return out[:, :sq], (q, k, v, out, lse)


def _flash_attention_bhsd_bwd(scale, causal, res, g):
    q, k, v, out_pad, lse = res
    bh, sq, d = q.shape
    sk = k.shape[1]
    block_q = _pick_block(sq, 0, q.dtype)
    block_k = _pick_block(sk, 1, q.dtype)
    qp = _pad_dim(q, 1, _round_up(sq, block_q))
    kp = _pad_dim(k, 1, _round_up(sk, block_k))
    vp = _pad_dim(v, 1, _round_up(sk, block_k))
    gp = _pad_dim(g, 1, _round_up(sq, block_q))
    dq, dk, dv = _flash_bwd(qp, kp, vp, gp, out_pad, lse, scale, causal,
                            sq, sk, block_q, block_k)
    return dq[:, :sq], dk[:, :sk], dv[:, :sk]


_flash_attention_bhsd.defvjp(_flash_attention_bhsd_fwd,
                             _flash_attention_bhsd_bwd)


def flash_attention(q, k, v, *, causal=False, scale=None):
    """Flash attention over Paddle layout [B, S, H, D]; differentiable.

    Online-softmax tiled for the MXU with a hand-written flash backward
    (the reference's flash_attn_kernel.cu + flash_attn_grad role).
    Supports head_dim not a multiple of 128 (Mosaic pads lanes), uneven
    sequence lengths (padded + masked here), causal cross-attention
    (Sk != Sq aligned bottom-right, matching flash-attn semantics).
    """
    if scale is None:
        scale = 1.0 / (q.shape[-1] ** 0.5)
    q, k, v = _demote_f64(q, k, v)
    b, sq, h, d = q.shape
    sk = k.shape[1]
    qt = jnp.swapaxes(q, 1, 2).reshape(b * h, sq, d)
    kt = jnp.swapaxes(k, 1, 2).reshape(b * h, sk, d)
    vt = jnp.swapaxes(v, 1, 2).reshape(b * h, sk, d)
    out = _flash_attention_bhsd(qt, kt, vt, float(scale), bool(causal))
    return jnp.swapaxes(out.reshape(b, h, sq, d), 1, 2)


# =====================================================================
# Fused layer norm / rms norm
# =====================================================================

def _ln_fwd_kernel(x_ref, g_ref, b_ref, o_ref, mu_ref, rstd_ref, *, eps):
    x = x_ref[:].astype(jnp.float32)                    # (block_rows, N)
    br = x.shape[0]
    mu = jnp.mean(x, axis=-1, keepdims=True)
    xc = x - mu
    var = jnp.mean(xc * xc, axis=-1, keepdims=True)
    rstd = jax.lax.rsqrt(var + eps)
    xhat = xc * rstd
    o_ref[:] = (xhat * g_ref[:].astype(jnp.float32)
                + b_ref[:].astype(jnp.float32)).astype(o_ref.dtype)
    mu_ref[:] = jnp.broadcast_to(mu, (br, _STAT_LANES))
    rstd_ref[:] = jnp.broadcast_to(rstd, (br, _STAT_LANES))


def _ln_bwd_kernel(x_ref, g_ref, mu_ref, rstd_ref, do_ref,
                   dx_ref, dg_ref, db_ref):
    x = x_ref[:].astype(jnp.float32)
    do = do_ref[:].astype(jnp.float32)
    gamma = g_ref[:].astype(jnp.float32)                # (1, N)
    mu = mu_ref[:][:, :1]
    rstd = rstd_ref[:][:, :1]
    xhat = (x - mu) * rstd

    # dgamma/dbeta: sequential-grid accumulation into one revisited block
    @pl.when(pl.program_id(0) == 0)
    def _init():
        dg_ref[:] = jnp.zeros_like(dg_ref)
        db_ref[:] = jnp.zeros_like(db_ref)

    dg = jnp.sum(do * xhat, axis=0, keepdims=True)      # (1, N)
    db = jnp.sum(do, axis=0, keepdims=True)
    dg_ref[:] = dg_ref[:] + jnp.broadcast_to(dg, dg_ref.shape)
    db_ref[:] = db_ref[:] + jnp.broadcast_to(db, db_ref.shape)

    dxhat = do * gamma
    m1 = jnp.mean(dxhat, axis=-1, keepdims=True)
    m2 = jnp.mean(dxhat * xhat, axis=-1, keepdims=True)
    dx = (dxhat - m1 - xhat * m2) * rstd
    dx_ref[:] = dx.astype(dx_ref.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _fused_layer_norm_2d(x, gamma, beta, eps):
    return _fused_layer_norm_2d_fwd(x, gamma, beta, eps)[0]


@_x32
def _fused_layer_norm_2d_fwd(x, gamma, beta, eps):
    rows, n = x.shape
    br = _ln_block_rows(rows, n)
    rows_pad = _round_up(rows, br)
    xp = _pad_dim(x, 0, rows_pad)
    with _kernel_span("layer_norm", "fwd") as kernel_name:
        out, mu, rstd = pl.pallas_call(
        functools.partial(_ln_fwd_kernel, eps=eps),
        grid=(rows_pad // br,),
        in_specs=[
            pl.BlockSpec((br, n), lambda i: (i, 0)),
            pl.BlockSpec((1, n), lambda i: (0, 0)),
            pl.BlockSpec((1, n), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((br, n), lambda i: (i, 0)),
            pl.BlockSpec((br, _STAT_LANES), lambda i: (i, 0)),
            pl.BlockSpec((br, _STAT_LANES), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((rows_pad, n), x.dtype),
            jax.ShapeDtypeStruct((rows_pad, _STAT_LANES), jnp.float32),
            jax.ShapeDtypeStruct((rows_pad, _STAT_LANES), jnp.float32),
        ],
        interpret=_interpret(),
        name=kernel_name,
    )(xp, gamma.reshape(1, n), beta.reshape(1, n))
    return out[:rows], (x, gamma, mu, rstd)


@_x32
def _fused_layer_norm_2d_bwd(eps, res, do):
    x, gamma, mu, rstd = res
    rows, n = x.shape
    br = _ln_block_rows(rows, n)
    rows_pad = _round_up(rows, br)
    nb = rows_pad // br
    xp = _pad_dim(x, 0, rows_pad)
    dop = _pad_dim(do, 0, rows_pad)
    with _kernel_span("layer_norm", "bwd") as kernel_name:
        dx, dg_acc, db_acc = pl.pallas_call(
        _ln_bwd_kernel,
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((br, n), lambda i: (i, 0)),
            pl.BlockSpec((1, n), lambda i: (0, 0)),
            pl.BlockSpec((br, _STAT_LANES), lambda i: (i, 0)),
            pl.BlockSpec((br, _STAT_LANES), lambda i: (i, 0)),
            pl.BlockSpec((br, n), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((br, n), lambda i: (i, 0)),
            pl.BlockSpec((8, n), lambda i: (0, 0)),
            pl.BlockSpec((8, n), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((rows_pad, n), x.dtype),
            jax.ShapeDtypeStruct((8, n), jnp.float32),
            jax.ShapeDtypeStruct((8, n), jnp.float32),
        ],
        interpret=_interpret(),
        name=kernel_name,
    )(xp, gamma.reshape(1, n), mu, rstd, dop)
    dgamma = dg_acc[0].astype(gamma.dtype)
    dbeta = db_acc[0].astype(gamma.dtype)
    return dx[:rows], dgamma, dbeta


_fused_layer_norm_2d.defvjp(_fused_layer_norm_2d_fwd,
                            _fused_layer_norm_2d_bwd)


def fused_layer_norm(x, gamma, beta, eps=1e-5):
    """LayerNorm over the last dim, any leading shape; differentiable."""
    x, gamma, beta = _demote_f64(x, gamma, beta)
    shape = x.shape
    n = shape[-1]
    out = _fused_layer_norm_2d(x.reshape(-1, n), gamma, beta, float(eps))
    return out.reshape(shape)


def _rms_fwd_kernel(x_ref, g_ref, o_ref, rstd_ref, *, eps):
    x = x_ref[:].astype(jnp.float32)
    br = x.shape[0]
    ms = jnp.mean(x * x, axis=-1, keepdims=True)
    rstd = jax.lax.rsqrt(ms + eps)
    o_ref[:] = (x * rstd * g_ref[:].astype(jnp.float32)).astype(
        o_ref.dtype)
    rstd_ref[:] = jnp.broadcast_to(rstd, (br, _STAT_LANES))


def _rms_bwd_kernel(x_ref, g_ref, rstd_ref, do_ref, dx_ref, dg_ref):
    x = x_ref[:].astype(jnp.float32)
    do = do_ref[:].astype(jnp.float32)
    gamma = g_ref[:].astype(jnp.float32)                # (1, N)
    rstd = rstd_ref[:][:, :1]
    xhat = x * rstd

    @pl.when(pl.program_id(0) == 0)
    def _init():
        dg_ref[:] = jnp.zeros_like(dg_ref)

    dg = jnp.sum(do * xhat, axis=0, keepdims=True)
    dg_ref[:] = dg_ref[:] + jnp.broadcast_to(dg, dg_ref.shape)

    dxhat = do * gamma
    m = jnp.mean(dxhat * xhat, axis=-1, keepdims=True)
    dx = (dxhat - xhat * m) * rstd
    dx_ref[:] = dx.astype(dx_ref.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _fused_rms_norm_2d(x, gamma, eps):
    return _fused_rms_norm_2d_fwd(x, gamma, eps)[0]


@_x32
def _fused_rms_norm_2d_fwd(x, gamma, eps):
    rows, n = x.shape
    br = _ln_block_rows(rows, n)
    rows_pad = _round_up(rows, br)
    xp = _pad_dim(x, 0, rows_pad)
    with _kernel_span("rms_norm", "fwd") as kernel_name:
        out, rstd = pl.pallas_call(
        functools.partial(_rms_fwd_kernel, eps=eps),
        grid=(rows_pad // br,),
        in_specs=[
            pl.BlockSpec((br, n), lambda i: (i, 0)),
            pl.BlockSpec((1, n), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((br, n), lambda i: (i, 0)),
            pl.BlockSpec((br, _STAT_LANES), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((rows_pad, n), x.dtype),
            jax.ShapeDtypeStruct((rows_pad, _STAT_LANES), jnp.float32),
        ],
        interpret=_interpret(),
        name=kernel_name,
    )(xp, gamma.reshape(1, n))
    return out[:rows], (x, gamma, rstd)


@_x32
def _fused_rms_norm_2d_bwd(eps, res, do):
    x, gamma, rstd = res
    rows, n = x.shape
    br = _ln_block_rows(rows, n)
    rows_pad = _round_up(rows, br)
    nb = rows_pad // br
    xp = _pad_dim(x, 0, rows_pad)
    dop = _pad_dim(do, 0, rows_pad)
    with _kernel_span("rms_norm", "bwd") as kernel_name:
        dx, dg_acc = pl.pallas_call(
        _rms_bwd_kernel,
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((br, n), lambda i: (i, 0)),
            pl.BlockSpec((1, n), lambda i: (0, 0)),
            pl.BlockSpec((br, _STAT_LANES), lambda i: (i, 0)),
            pl.BlockSpec((br, n), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((br, n), lambda i: (i, 0)),
            pl.BlockSpec((8, n), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((rows_pad, n), x.dtype),
            jax.ShapeDtypeStruct((8, n), jnp.float32),
        ],
        interpret=_interpret(),
        name=kernel_name,
    )(xp, gamma.reshape(1, n), rstd, dop)
    dgamma = dg_acc[0].astype(gamma.dtype)
    return dx[:rows], dgamma


_fused_rms_norm_2d.defvjp(_fused_rms_norm_2d_fwd, _fused_rms_norm_2d_bwd)


def fused_rms_norm(x, gamma, eps=1e-6):
    """RMSNorm over the last dim, any leading shape; differentiable."""
    x, gamma = _demote_f64(x, gamma)
    shape = x.shape
    n = shape[-1]
    out = _fused_rms_norm_2d(x.reshape(-1, n), gamma, float(eps))
    return out.reshape(shape)


# =====================================================================
# Fused softmax cross-entropy (from logits + integer labels)
# =====================================================================

def _xent_fwd_kernel(x_ref, lbl_ref, loss_ref, lse_ref,
                     m_acc, l_acc, pick_acc, *, block_v):
    """Online logsumexp over vocab blocks.

    Grid is (row_blocks, vocab_blocks) with the vocab dim minor, so for a
    fixed row block the vocab programs run sequentially and the VMEM
    scratch accumulators (running max / sum-exp / picked logit) persist
    across them.  VMEM use is O(block_rows * block_v) regardless of the
    full vocab size — round 2's full-row (br, V) blocks OOMed scoped VMEM
    at V=30k in the backward (BENCH_r02/r03 crash).
    """
    j = pl.program_id(1)
    x = x_ref[:].astype(jnp.float32)                   # (block_rows, bv)
    br = x.shape[0]
    lbl = lbl_ref[:][:, :1]                            # (block_rows, 1)

    @pl.when(j == 0)
    def _():
        m_acc[:] = jnp.full((br, _STAT_LANES), _NEG_INF, jnp.float32)
        l_acc[:] = jnp.zeros((br, _STAT_LANES), jnp.float32)
        pick_acc[:] = jnp.zeros((br, _STAT_LANES), jnp.float32)

    m_prev = m_acc[:][:, :1]
    m_blk = jnp.max(x, axis=-1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_blk)
    l_new = (l_acc[:][:, :1] * jnp.exp(m_prev - m_new)
             + jnp.sum(jnp.exp(x - m_new), axis=-1, keepdims=True))
    col = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1) + j * block_v
    picked = jnp.sum(jnp.where(col == lbl, x, 0.0), axis=-1, keepdims=True)
    m_acc[:] = jnp.broadcast_to(m_new, (br, _STAT_LANES))
    l_acc[:] = jnp.broadcast_to(l_new, (br, _STAT_LANES))
    pick_acc[:] += jnp.broadcast_to(picked, (br, _STAT_LANES))

    @pl.when(j == pl.num_programs(1) - 1)
    def _():
        lse = m_acc[:][:, :1] + jnp.log(l_acc[:][:, :1])
        # ignore_index rows (lbl < 0) produce 0 loss
        valid = lbl >= 0
        loss = jnp.where(valid, lse - pick_acc[:][:, :1], 0.0)
        loss_ref[:] = jnp.broadcast_to(loss, (br, _STAT_LANES))
        lse_ref[:] = jnp.broadcast_to(lse, (br, _STAT_LANES))


def _xent_bwd_kernel(x_ref, lbl_ref, lse_ref, g_ref, dx_ref, *, block_v):
    x = x_ref[:].astype(jnp.float32)                   # (block_rows, bv)
    lbl = lbl_ref[:][:, :1]
    lse = lse_ref[:][:, :1]
    g = g_ref[:][:, :1]
    p = jnp.exp(x - lse)
    col = (jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
           + pl.program_id(1) * block_v)
    valid = (lbl >= 0).astype(jnp.float32)
    dx = jnp.where(col == lbl, p - 1.0, p) * (g * valid)
    dx_ref[:] = dx.astype(dx_ref.dtype)


@jax.custom_vjp
def _fused_xent_2d(logits, labels):
    return _fused_xent_2d_fwd(logits, labels)[0]


@_x32
def _fused_xent_2d_fwd(logits, labels):
    rows, v = logits.shape
    br, bv, rows_pad, v_pad = _xent_blocks(rows, v)
    # pad vocab with -inf so padded columns vanish from the logsumexp
    xp = _pad_dim(_pad_dim(logits, 0, rows_pad), 1, v_pad,
                  value=_NEG_INF)
    lp = _lanes(_pad_dim(labels.astype(jnp.int32), 0, rows_pad, value=-1))
    with _kernel_span("softmax_cross_entropy", "fwd") as kernel_name:
        loss, lse = pl.pallas_call(
        functools.partial(_xent_fwd_kernel, block_v=bv),
        grid=(rows_pad // br, v_pad // bv),
        in_specs=[
            pl.BlockSpec((br, bv), lambda i, j: (i, j)),
            pl.BlockSpec((br, _STAT_LANES), lambda i, j: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((br, _STAT_LANES), lambda i, j: (i, 0)),
            pl.BlockSpec((br, _STAT_LANES), lambda i, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((rows_pad, _STAT_LANES), jnp.float32),
            jax.ShapeDtypeStruct((rows_pad, _STAT_LANES), jnp.float32),
        ],
        scratch_shapes=stat_scratch(br, 3),
        interpret=_interpret(),
        name=kernel_name,
    )(xp, lp)
    return loss[:rows, 0], (logits, labels, lse[:rows])


@_x32
def _fused_xent_2d_bwd(res, g):
    logits, labels, lse = res
    rows, v = logits.shape
    br, bv, rows_pad, v_pad = _xent_blocks(rows, v)
    xp = _pad_dim(_pad_dim(logits, 0, rows_pad), 1, v_pad,
                  value=_NEG_INF)
    lp = _lanes(_pad_dim(labels.astype(jnp.int32), 0, rows_pad, value=-1))
    lsep = _pad_dim(lse, 0, rows_pad)
    gp = _lanes(_pad_dim(g.astype(jnp.float32), 0, rows_pad))
    with _kernel_span("softmax_cross_entropy", "bwd") as kernel_name:
        dx = pl.pallas_call(
        functools.partial(_xent_bwd_kernel, block_v=bv),
        grid=(rows_pad // br, v_pad // bv),
        in_specs=[
            pl.BlockSpec((br, bv), lambda i, j: (i, j)),
            pl.BlockSpec((br, _STAT_LANES), lambda i, j: (i, 0)),
            pl.BlockSpec((br, _STAT_LANES), lambda i, j: (i, 0)),
            pl.BlockSpec((br, _STAT_LANES), lambda i, j: (i, 0)),
        ],
        out_specs=pl.BlockSpec((br, bv), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((rows_pad, v_pad), logits.dtype),
        interpret=_interpret(),
        name=kernel_name,
    )(xp, lp, lsep, gp)
    return dx[:rows, :v], None


_fused_xent_2d.defvjp(_fused_xent_2d_fwd, _fused_xent_2d_bwd)


def fused_softmax_cross_entropy(logits, labels):
    """Per-example softmax cross-entropy from integer labels.

    logits: [..., V]; labels: [...] int. Labels < 0 are ignored (loss 0,
    zero gradient), matching softmax_with_cross_entropy ignore_index
    handling after relabeling.
    """
    logits, = _demote_f64(logits)
    shape = logits.shape
    v = shape[-1]
    loss = _fused_xent_2d(logits.reshape(-1, v), labels.reshape(-1))
    return loss.reshape(shape[:-1])


# =====================================================================
# Paged decode attention (serving)
# =====================================================================

def _paged_attn_kernel(bt_ref, cl_ref, q_ref, k_ref, v_ref, o_ref,
                       acc_ref, m_ref, l_ref, *, block_size, scale,
                       w_last):
    """One (batch, head, table-slot) program over a paged KV pool.

    Scalar-prefetched block tables drive the K/V BlockSpec index maps,
    so each program streams exactly the block its sequence owns at slot
    ``w`` — the online-softmax state (acc/m/l) lives in VMEM scratch
    and survives the sequential innermost grid dim.  The single query
    row is broadcast to 8 sublanes to satisfy Mosaic's (8, 128) tiling;
    row 0 is written out at the last slot.
    """
    b = pl.program_id(0)
    w = pl.program_id(2)
    ctx = cl_ref[b]

    @pl.when(w == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    @pl.when(w * block_size < ctx)
    def _block():
        d = q_ref.shape[-1]
        q = jnp.broadcast_to(q_ref[0, 0].astype(jnp.float32),
                             (_STAT_LANES, d))          # (8, D)
        k = k_ref[0, 0].astype(jnp.float32)             # (bs, D)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # (8, bs)
        col = (jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
               + w * block_size)
        mask = col < ctx
        s = jnp.where(mask, s, _NEG_INF)
        m_prev = m_ref[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        # explicit zero on masked cols (exp(_NEG_INF - m) is 1 when a
        # block were fully masked; the pl.when guard makes that
        # unreachable but keep the invariant local)
        p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = _lanes(alpha * l_ref[:, :1]
                            + jnp.sum(p, axis=-1, keepdims=True))
        v = v_ref[0, 0].astype(jnp.float32)             # (bs, D)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = _lanes(m_new)

    @pl.when(w == w_last)
    def _emit():
        l = l_ref[:, :1]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        out = acc_ref[...] / l_safe
        # ctx==0 pad row -> zeros.  Broadcast the f32 stat and compare
        # at full shape, never broadcast the (rows, 1) predicate: the
        # Mosaic lowering of a bool broadcast_in_dim expands i1 through
        # an integer select/compare whose width follows the x64 mode AT
        # LOWERING TIME (outside the _x32 scope), and the layout pass
        # aborts on i64 ("bitwidth_ <= 32").
        out = jnp.where(jnp.broadcast_to(l, out.shape) > 0.0, out, 0.0)
        o_ref[...] = out[:1][None, None].astype(o_ref.dtype)


@_x32
def paged_attention(q, k_pool, v_pool, block_tables, context_lens,
                    scale=None):
    """Decode attention through per-sequence block tables.

    q: [B, 1, H, D]; k_pool/v_pool: [num_blocks, H, block_size, D];
    block_tables: [B, W] int32 pool block ids (pad entries -> block 0);
    context_lens: [B] int32 visible tokens per sequence (0 -> zero
    output, matching the XLA fallback's any_visible semantics).
    Returns [B, 1, H, D].
    """
    q, k_pool, v_pool = _demote_f64(q, k_pool, v_pool)
    B, s, H, D = q.shape
    if s != 1:
        raise ValueError(f"paged_attention decodes 1 token, got s={s}")
    num_blocks, _, block_size, _ = k_pool.shape
    W = block_tables.shape[1]
    if scale is None:
        scale = 1.0 / (D ** 0.5)

    qt = jnp.swapaxes(q, 1, 2)                          # [B, H, 1, D]
    bt = block_tables.astype(jnp.int32)
    cl = context_lens.astype(jnp.int32)

    with _kernel_span("paged_attention", "fwd") as kernel_name:
        out = pl.pallas_call(
        functools.partial(_paged_attn_kernel, block_size=block_size,
                          scale=float(scale), w_last=W - 1),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, H, W),
            in_specs=[
                pl.BlockSpec((1, 1, 1, D),
                             lambda b, h, w, bt, cl: (b, h, 0, 0)),
                pl.BlockSpec((1, 1, block_size, D),
                             lambda b, h, w, bt, cl: (bt[b, w], h, 0, 0)),
                pl.BlockSpec((1, 1, block_size, D),
                             lambda b, h, w, bt, cl: (bt[b, w], h, 0, 0)),
            ],
            out_specs=pl.BlockSpec((1, 1, 1, D),
                                   lambda b, h, w, bt, cl: (b, h, 0, 0)),
            scratch_shapes=softmax_scratch(_STAT_LANES, D),
        ),
        out_shape=jax.ShapeDtypeStruct((B, H, 1, D), q.dtype),
        interpret=_interpret(),
        name=kernel_name,
    )(bt, cl, qt, k_pool, v_pool)
    return jnp.swapaxes(out, 1, 2)                      # [B, 1, H, D]
