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
    "flash_dropout_keep",
    "fused_layer_norm",
    "fused_rms_norm",
    "fused_softmax_cross_entropy",
]

# Shared tile primitives (see ops/pallas_tiles.py): tracing policy,
# dtype-aware block picking, stat-lane layout, padding.  These names are
# re-exported here so downstream `from .pallas_kernels import _x32, ...`
# keeps binding the SAME objects — the refactor's bit-identity contract.
from .pallas_tiles import (_NEG_INF, _STAT_LANES, _demote_f64,
                           _interpret, _kernel_span, _lanes,
                           _ln_block_rows, _min_rows, _pad_dim,
                           _round_up, _sane_block, _x32, _xent_blocks,
                           stat_scratch)


# =====================================================================
# Flash attention
# =====================================================================

def _keep_threshold(dropout_p: float) -> int:
    """A 32-bit word below this keeps its element: floor((1-p) * 2**32),
    so the keep probability is 1-p to 2**-32."""
    return int((1.0 - dropout_p) * 2 ** 32)


def _mix32(x):
    """One round of an integer finalizer (xor-shift, odd multiply) on
    uint32 words: every input bit reaches every output bit."""
    x = x ^ (x >> 16)
    x = x * jnp.uint32(0x7FEB352D)
    x = x ^ (x >> 15)
    x = x * jnp.uint32(0x846CA68B)
    return x ^ (x >> 16)


def _hash_bits(seed, tile, shape):
    """Counter-based stand-in for the core's generator: two finalizer
    rounds over the element index, keyed by (seed, tile)."""
    def u32(x):
        return jnp.asarray(x, jnp.int32).astype(jnp.uint32)

    key = _mix32(_mix32(u32(seed)) + u32(tile))
    row = jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    col = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    return _mix32(_mix32(u32(row * shape[1] + col) ^ key) + key)


def _tile_bits(seed, tile, shape):
    """The `shape` uint32 words of dropout tile number `tile` under
    `seed`: the one bit source of the forward kernel, both backward
    kernels and `flash_dropout_keep`.  On the chip the core's generator,
    reseeded per tile (this Mosaic takes two seed words); it has no CPU
    form (jax 0.9 lowers `prng_seed` for no other platform), so
    interpret mode hashes instead."""
    if _interpret():
        return _hash_bits(seed, tile, shape)
    pltpu.prng_seed(seed, tile)
    return pltpu.bitcast(pltpu.prng_random_bits(shape), jnp.uint32)


def _tile_keep(seed_ref, bh, qi, ki, grid, shape, dropout_p):
    """Keep mask of the tile at (batch*head `bh`, q-block `qi`, k-block
    `ki`) of a `grid` = (q-blocks, k-blocks) attention matrix."""
    tile = (bh * grid[0] + qi) * grid[1] + ki
    return _tile_bits(seed_ref[0], tile, shape) < jnp.uint32(
        _keep_threshold(dropout_p))


# dot_general dimension numbers of a @ b.T, a @ b and a.T @ b
_NT = (((1,), (1,)), ((), ()))
_NN = (((1,), (0,)), ((), ()))
_TN = (((0,), (0,)), ((), ()))


def _mxu_dot(a, b, dims):
    """`a` . `b` contracted over `dims`, operands in the dtype they come
    in, accumulated in float32.  The framework's process-wide "highest"
    matmul precision is meant for float32 operands; on bfloat16 ones
    Mosaic refuses it ("Bad lhs type"), and their products are exact in
    float32 anyway, so they ask for the default."""
    precision = (None if a.dtype == jnp.float32
                 else jax.lax.Precision.DEFAULT)
    return jax.lax.dot_general(a, b, dims, precision=precision,
                               preferred_element_type=jnp.float32)


def _attn_fwd_kernel(*refs, scale, causal, block_k, sk_real, offset,
                     dropout_p):
    """One (batch*head, q-block) program: online-softmax over K blocks.

    With `dropout_p` > 0 the first ref is the scalar-prefetched seed;
    `l` and `lse` come from the undropped probabilities, the
    accumulator from the kept ones, scaled by 1/(1-p) once at the end."""
    seed_ref, refs = (refs[0], refs[1:]) if dropout_p else (None, refs)
    q_ref, k_ref, v_ref, o_ref, lse_ref = refs
    q = q_ref[0]                                          # (block_q, D)
    block_q, _ = q.shape
    sk_pad = k_ref.shape[1]
    bh, q_blk = pl.program_id(0), pl.program_id(1)  # not inside the loop
    tiles = (pl.num_programs(1), sk_pad // block_k)
    q_start = q_blk * block_q

    num_k_blocks = sk_pad // block_k
    if causal:
        # highest kv index any row in this q block may attend to
        hi = q_start + block_q + offset
        num_k_blocks = jnp.minimum(
            num_k_blocks, (jnp.maximum(hi, 0) + block_k - 1) // block_k)

    def body(i, carry):
        m_prev, l_prev, acc = carry                       # (bq,1)x2,(bq,D)
        k_blk = k_ref[0, pl.ds(i * block_k, block_k), :]
        v_blk = v_ref[0, pl.ds(i * block_k, block_k), :]
        s = _mxu_dot(q, k_blk, _NT) * scale               # (bq, bk)
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
        if dropout_p:
            keep = _tile_keep(seed_ref, bh, q_blk, i, tiles, p.shape,
                              dropout_p)
            p = jnp.where(keep, p, 0.0)
        acc = acc * alpha + _mxu_dot(p.astype(v_blk.dtype), v_blk, _NN)
        return m_new, l_new, acc

    m0 = jnp.full((block_q, 1), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((block_q, 1), jnp.float32)
    acc0 = jnp.zeros((block_q, q.shape[-1]), jnp.float32)
    m, l, acc = jax.lax.fori_loop(0, num_k_blocks, body, (m0, l0, acc0))
    l_safe = jnp.where(l == 0.0, 1.0, l)
    denom = l_safe * (1.0 - dropout_p) if dropout_p else l_safe
    o_ref[0] = (acc / denom).astype(o_ref.dtype)
    lse = jnp.where(l == 0.0, _NEG_INF, m + jnp.log(l_safe))  # (bq, 1)
    lse_ref[0] = jnp.broadcast_to(lse, (block_q, _STAT_LANES))


def _attn_bwd_dq_kernel(*refs, scale, causal, block_k, sk_real, offset,
                        dropout_p):
    """dq of one (batch*head, q-block).  Dropout (FlashAttention-2): the
    tile's mask is drawn again and applied to dP = dO V^T; its 1/(1-p)
    is folded into `scale` and `delta` (dS = P (keep dP/(1-p) - delta))."""
    seed_ref, refs = (refs[0], refs[1:]) if dropout_p else (None, refs)
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref = refs
    q = q_ref[0]
    do = do_ref[0]
    lse = lse_ref[0][:, :1]                               # (bq, 1)
    delta = delta_ref[0][:, :1]
    if dropout_p:
        delta = delta * (1.0 - dropout_p)
    scale_ds = scale / (1.0 - dropout_p)
    block_q = q.shape[0]
    sk_pad = k_ref.shape[1]
    bh, q_blk = pl.program_id(0), pl.program_id(1)  # not inside the loop
    tiles = (pl.num_programs(1), sk_pad // block_k)
    q_start = q_blk * block_q

    num_k_blocks = sk_pad // block_k
    if causal:
        hi = q_start + block_q + offset
        num_k_blocks = jnp.minimum(
            num_k_blocks, (jnp.maximum(hi, 0) + block_k - 1) // block_k)

    def body(i, dq):
        k_blk = k_ref[0, pl.ds(i * block_k, block_k), :]
        v_blk = v_ref[0, pl.ds(i * block_k, block_k), :]
        s = _mxu_dot(q, k_blk, _NT) * scale
        col = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1) + i * block_k
        mask = col < sk_real
        if causal:
            row = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) + q_start
            mask = jnp.logical_and(mask, col <= row + offset)
        s = jnp.where(mask, s, _NEG_INF)
        p = jnp.exp(s - lse)                              # (bq, bk)
        dp = _mxu_dot(do, v_blk, _NT)
        if dropout_p:
            keep = _tile_keep(seed_ref, bh, q_blk, i, tiles, p.shape,
                              dropout_p)
            dp = jnp.where(keep, dp, 0.0)
        ds = p * (dp - delta) * scale_ds
        return dq + _mxu_dot(ds.astype(k_blk.dtype), k_blk, _NN)

    dq0 = jnp.zeros(q.shape, jnp.float32)
    dq = jax.lax.fori_loop(0, num_k_blocks, body, dq0)
    dq_ref[0] = dq.astype(dq_ref.dtype)


def _attn_bwd_dkv_kernel(*refs, scale, causal, block_q, sq_real, offset,
                         dropout_p):
    """dk, dv of one (batch*head, k-block); dropout as in the dq kernel,
    and dV = (P keep / (1-p))^T dO with its 1/(1-p) applied at the end."""
    seed_ref, refs = (refs[0], refs[1:]) if dropout_p else (None, refs)
    (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
     dk_ref, dv_ref) = refs
    k = k_ref[0]                                          # (block_k, D)
    v = v_ref[0]
    scale_ds = scale / (1.0 - dropout_p)
    block_k = k.shape[0]
    sq_pad = q_ref.shape[1]
    bh, k_blk = pl.program_id(0), pl.program_id(1)  # not inside the loop
    tiles = (sq_pad // block_q, pl.num_programs(1))
    k_start = k_blk * block_k

    lo = 0
    num_q_blocks = sq_pad // block_q
    if causal:
        # first q row that can see this k block: row >= k_start - offset
        lo = jnp.maximum(k_start - offset, 0) // block_q

    def body(i, carry):
        dk, dv = carry
        q_blk = q_ref[0, pl.ds(i * block_q, block_q), :]
        do_blk = do_ref[0, pl.ds(i * block_q, block_q), :]
        lse_blk = lse_ref[0, pl.ds(i * block_q, block_q), :][:, :1]
        delta_blk = delta_ref[0, pl.ds(i * block_q, block_q), :][:, :1]
        s = _mxu_dot(q_blk, k, _NT) * scale               # (bq, bk)
        row = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) + i * block_q
        mask = row < sq_real
        if causal:
            col = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1) + k_start
            mask = jnp.logical_and(mask, col <= row + offset)
        s = jnp.where(mask, s, _NEG_INF)
        p = jnp.exp(s - lse_blk)
        dp = _mxu_dot(do_blk, v, _NT)
        p_kept = p
        if dropout_p:
            keep = _tile_keep(seed_ref, bh, i, k_blk, tiles, p.shape,
                              dropout_p)
            p_kept = jnp.where(keep, p, 0.0)
            dp = jnp.where(keep, dp, 0.0)
            delta_blk = delta_blk * (1.0 - dropout_p)
        dv = dv + _mxu_dot(p_kept.astype(do_blk.dtype), do_blk, _TN)
        ds = p * (dp - delta_blk) * scale_ds
        dk = dk + _mxu_dot(ds.astype(q_blk.dtype), q_blk, _TN)
        return dk, dv

    dk0 = jnp.zeros(k.shape, jnp.float32)
    dv0 = jnp.zeros(v.shape, jnp.float32)
    dk, dv = jax.lax.fori_loop(lo, num_q_blocks, body, (dk0, dv0))
    if dropout_p:
        dv = dv / (1.0 - dropout_p)
    dk_ref[0] = dk.astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)


def _seeded_call(kernel, grid, in_specs, out_specs, out_shape, name, seed,
                *operands):
    """The pallas_call of one kernel on `operands`; with a `seed` (the
    kernel draws a dropout mask) it rides in front of them as scalar
    prefetch, and every index map takes it as a trailing argument."""
    if seed is None:
        return pl.pallas_call(kernel, grid=grid, in_specs=in_specs,
                              out_specs=out_specs, out_shape=out_shape,
                              interpret=_interpret(), name=name)(*operands)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=grid, in_specs=in_specs,
            out_specs=out_specs),
        out_shape=out_shape, interpret=_interpret(), name=name)(
            seed, *operands)


def _blk(shape):
    """Block (1, rows, d) that walks rows with the second grid index."""
    return pl.BlockSpec(shape, lambda b, i, *_: (b, i, 0))


def _whole(shape):
    """Block (1, rows, d) holding every row of one batch*head."""
    return pl.BlockSpec(shape, lambda b, i, *_: (b, 0, 0))


# The two builders below are jitted on their static arguments: a model
# calls them once a layer with the same shapes, and a jitted callee is
# traced once and lowered once (one Mosaic kernel, N calls to it) where
# a plain one is traced and lowered N times.  Twelve layers cost a
# second of every process's start that way, more in the lazy tier,
# which traces a step several times (PERF.md section 6, PR 27).
_FLASH_STATIC = ("scale", "causal", "sq_real", "sk_real", "block_q",
                 "block_k", "dropout_p")


@functools.partial(jax.jit, static_argnames=_FLASH_STATIC)
@_x32
def _flash_fwd(q, k, v, scale, causal, sq_real, sk_real, block_q, block_k,
               dropout_p=0.0, seed=None):
    bh, sq_pad, d = q.shape
    sk_pad = k.shape[1]
    offset = sk_real - sq_real  # causal alignment for cross-length attn
    with _kernel_span("flash_attention", "fwd") as kernel_name:
        out, lse = _seeded_call(
            functools.partial(_attn_fwd_kernel, scale=scale, causal=causal,
                              block_k=block_k, sk_real=sk_real,
                              offset=offset, dropout_p=dropout_p),
            (bh, sq_pad // block_q),
            [_blk((1, block_q, d)), _whole((1, sk_pad, d)),
             _whole((1, sk_pad, d))],
            [_blk((1, block_q, d)), _blk((1, block_q, _STAT_LANES))],
            [jax.ShapeDtypeStruct((bh, sq_pad, d), q.dtype),
             jax.ShapeDtypeStruct((bh, sq_pad, _STAT_LANES), jnp.float32)],
            kernel_name, seed, q, k, v)
    return out, lse


@functools.partial(jax.jit, static_argnames=_FLASH_STATIC)
@_x32
def _flash_bwd(q, k, v, do, out, lse, scale, causal, sq_real, sk_real,
               block_q, block_k, dropout_p=0.0, seed=None):
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
        dq = _seeded_call(
            functools.partial(_attn_bwd_dq_kernel, scale=scale,
                              causal=causal, block_k=block_k,
                              sk_real=sk_real, offset=offset,
                              dropout_p=dropout_p),
            (bh, sq_pad // block_q),
            [_blk((1, block_q, d)), _whole((1, sk_pad, d)),
             _whole((1, sk_pad, d)), _blk((1, block_q, d)),
             _blk((1, block_q, _STAT_LANES)),
             _blk((1, block_q, _STAT_LANES))],
            _blk((1, block_q, d)),
            jax.ShapeDtypeStruct((bh, sq_pad, d), q.dtype),
            kernel_name, seed, q, k, v, do, lse_safe, delta)
    with _kernel_span("flash_attention", "bwd_dkv") as kernel_name:
        dk, dv = _seeded_call(
            functools.partial(_attn_bwd_dkv_kernel, scale=scale,
                              causal=causal, block_q=block_q,
                              sq_real=sq_real, offset=offset,
                              dropout_p=dropout_p),
            (bh, sk_pad // block_k),
            [_whole((1, sq_pad, d)), _blk((1, block_k, d)),
             _blk((1, block_k, d)), _whole((1, sq_pad, d)),
             _whole((1, sq_pad, _STAT_LANES)),
             _whole((1, sq_pad, _STAT_LANES))],
            [_blk((1, block_k, d)), _blk((1, block_k, d))],
            [jax.ShapeDtypeStruct((bh, sk_pad, d), k.dtype),
             jax.ShapeDtypeStruct((bh, sk_pad, d), v.dtype)],
            kernel_name, seed, q, k, v, do, lse_safe, delta)
    return dq, dk, dv


def set_flash_block_sizes(block_q=None, block_k=None):
    """Process-wide override for the sweep harness."""
    global _block_override
    _block_override = (block_q, block_k)


_block_override = (None, None)


# Largest Q/K block rows by (head_dim, dtype), from the sweep on a TPU
# v5e (scripts/flash_block_sweep.py, PERF.md section 6, PR 27): forward
# plus backward at 192 x 512 x 64 took 4.35 ms with 128 x 128 blocks,
# 2.20 with 256 x 256, 1.51 with 512 x 512 (dropout 0.1; the same
# order without), and 128 x 1024 x 64 causal 6.51 / 3.49 / 2.73 (1024 x
# 1024: 3.02).  What was not swept keeps 128.
_FLASH_BLOCK_CAP = {(64, jnp.dtype(jnp.bfloat16)): 512}


def _pick_block(seq: int, which: int = 0, dtype=jnp.float32,
                head_dim: int = 0) -> int:
    """Q/K block rows for `seq`: legal by construction for `dtype`
    (sublane multiple of _min_rows), covering `seq` after _round_up
    padding.  From 128 rows on, the largest multiple of 128 under the
    (head_dim, dtype) cap that pads `seq` no further than 128-row blocks
    would.  The sweep harness's override is clamped to legality rather
    than trusted — an illegal sweep value degrades to the default
    instead of crashing Mosaic."""
    mr = _min_rows(dtype)
    ov = _sane_block(_block_override[which], seq, mr)
    if ov:
        return ov
    if seq < 128:
        return _round_up(max(seq, mr), mr)
    n = _round_up(seq, 128) // 128
    cap = _FLASH_BLOCK_CAP.get((head_dim, jnp.dtype(dtype)), 128) // 128
    return 128 * max(m for m in range(1, cap + 1) if n % m == 0)


def _flash_blocks(sq, sk, d, dtype):
    return _pick_block(sq, 0, dtype, d), _pick_block(sk, 1, dtype, d)


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
    block_q, block_k = _flash_blocks(seq_q, seq_k, head_dim, dtype)
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


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _flash_attention_bhsd(q, k, v, seed, scale, causal, dropout_p=0.0):
    """`seed`: int32[1] when `dropout_p` > 0, else None."""
    out, _ = _flash_attention_bhsd_fwd(q, k, v, seed, scale, causal,
                                       dropout_p)
    return out


def _flash_attention_bhsd_fwd(q, k, v, seed, scale, causal, dropout_p):
    bh, sq, d = q.shape
    sk = k.shape[1]
    block_q, block_k = _flash_blocks(sq, sk, d, q.dtype)
    qp = _pad_dim(q, 1, _round_up(sq, block_q))
    kp = _pad_dim(k, 1, _round_up(sk, block_k))
    vp = _pad_dim(v, 1, _round_up(sk, block_k))
    out, lse = _flash_fwd(qp, kp, vp, scale, causal, sq, sk,
                          block_q, block_k, dropout_p, seed)
    return out[:, :sq], (q, k, v, seed, out, lse)


def _flash_attention_bhsd_bwd(scale, causal, dropout_p, res, g):
    q, k, v, seed, out_pad, lse = res
    bh, sq, d = q.shape
    sk = k.shape[1]
    block_q, block_k = _flash_blocks(sq, sk, d, q.dtype)
    qp = _pad_dim(q, 1, _round_up(sq, block_q))
    kp = _pad_dim(k, 1, _round_up(sk, block_k))
    vp = _pad_dim(v, 1, _round_up(sk, block_k))
    gp = _pad_dim(g, 1, _round_up(sq, block_q))
    dq, dk, dv = _flash_bwd(qp, kp, vp, gp, out_pad, lse, scale, causal,
                            sq, sk, block_q, block_k, dropout_p, seed)
    return dq[:, :sq], dk[:, :sk], dv[:, :sk], None


_flash_attention_bhsd.defvjp(_flash_attention_bhsd_fwd,
                             _flash_attention_bhsd_bwd)


def _dropout_seed(dropout_p, seed):
    """(static rate, int32[1] seed or None) of one call to a kernel
    that draws its dropout mask."""
    dropout_p = float(dropout_p)
    if not 0.0 <= dropout_p < 1.0:
        raise ValueError(f"dropout_p must be in [0, 1), got {dropout_p}")
    if dropout_p == 0.0:
        return 0.0, None
    if seed is None:
        raise ValueError("dropout_p > 0 needs a seed")
    return dropout_p, jnp.asarray(seed).astype(jnp.int32).reshape((1,))


def flash_attention(q, k, v, *, causal=False, scale=None, dropout_p=0.0,
                    seed=None):
    """Flash attention over Paddle layout [B, S, H, D]; differentiable.

    Online-softmax tiled for the MXU with a hand-written flash backward
    (the reference's flash_attn_kernel.cu + flash_attn_grad role).
    Supports head_dim not a multiple of 128 (Mosaic pads lanes), uneven
    sequence lengths (padded + masked here), causal cross-attention
    (Sk != Sq aligned bottom-right, matching flash-attn semantics).

    `dropout_p` (static) > 0 drops attention probabilities inside the
    kernels: every element of every (batch, head) draws its own 32-bit
    word from a stream fixed by the integer `seed` and its tile, is kept
    with probability 1 - p and scaled by 1 / (1 - p); the backward
    kernels draw the same words again, so no mask reaches HBM.
    `flash_dropout_keep` writes that mask out.  At 0 the kernels hold no
    generator code.
    """
    if scale is None:
        scale = 1.0 / (q.shape[-1] ** 0.5)
    dropout_p, seed = _dropout_seed(dropout_p, seed)
    q, k, v = _demote_f64(q, k, v)
    b, sq, h, d = q.shape
    sk = k.shape[1]
    qt = jnp.swapaxes(q, 1, 2).reshape(b * h, sq, d)
    kt = jnp.swapaxes(k, 1, 2).reshape(b * h, sk, d)
    vt = jnp.swapaxes(v, 1, 2).reshape(b * h, sk, d)
    out = _flash_attention_bhsd(qt, kt, vt, seed, float(scale),
                                bool(causal), dropout_p)
    return jnp.swapaxes(out.reshape(b, h, sq, d), 1, 2)


def _keep_kernel(seed_ref, keep_ref, *, dropout_p):
    keep = _tile_keep(seed_ref, pl.program_id(0), pl.program_id(1),
                      pl.program_id(2),
                      (pl.num_programs(1), pl.num_programs(2)),
                      keep_ref.shape[1:], dropout_p)
    keep_ref[0] = keep.astype(keep_ref.dtype)


@_x32
def flash_dropout_keep(seed, batch, seq_q, seq_k, heads, head_dim, *,
                       dropout_p, dtype=jnp.float32):
    """The keep mask `flash_attention(..., dropout_p, seed)` applies to
    [batch, seq, heads, head_dim] inputs of `dtype`, as bool
    [batch, heads, seq_q, seq_k]: the same tile stream, written out.
    For checking the kernels against a composite under an explicit
    mask; the training path never builds it."""
    dropout_p, seed = _dropout_seed(dropout_p, seed)
    block_q, block_k = _flash_blocks(seq_q, seq_k, head_dim, dtype)
    bh = batch * heads
    sq_pad, sk_pad = _round_up(seq_q, block_q), _round_up(seq_k, block_k)
    with _kernel_span("flash_attention", "keep") as kernel_name:
        keep = pl.pallas_call(
            functools.partial(_keep_kernel, dropout_p=dropout_p),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1,
                grid=(bh, sq_pad // block_q, sk_pad // block_k),
                in_specs=[],
                out_specs=pl.BlockSpec((1, block_q, block_k),
                                       lambda b, i, j, *_: (b, i, j))),
            out_shape=jax.ShapeDtypeStruct((bh, sq_pad, sk_pad),
                                           jnp.int32),
            interpret=_interpret(), name=kernel_name)(seed)
    return keep[:, :seq_q, :seq_k].reshape(
        batch, heads, seq_q, seq_k).astype(bool)


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


def _ln_bwd_tile(x_ref, g_ref, mu_ref, rstd_ref, do_ref, dg_ref, db_ref):
    """One row block of the layer-norm backward: accumulates dgamma and
    dbeta into their revisited blocks and returns the block's dx in
    float32."""
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
    return (dxhat - m1 - xhat * m2) * rstd


def _ln_bwd_kernel(x_ref, g_ref, mu_ref, rstd_ref, do_ref,
                   dx_ref, dg_ref, db_ref):
    dx_ref[:] = _ln_bwd_tile(x_ref, g_ref, mu_ref, rstd_ref, do_ref,
                             dg_ref, db_ref).astype(dx_ref.dtype)


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

def _xent_tile(x_ref, *, block_v, v):
    """One (block_rows, block_v) tile of the logits in float32 beside its
    column numbers.  The operand is read at its own width, so the last
    vocabulary block may reach past column ``v``: what lies there is
    unspecified (it may be NaN, so a select and not a multiply) and is
    set to -inf, which drops it from the logsumexp and zeroes its
    gradient.  Where ``block_v`` divides ``v`` no mask is traced."""
    x = x_ref[:].astype(jnp.float32)
    col = (jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
           + pl.program_id(1) * block_v)
    if v % block_v:
        x = jnp.where(col < v, x, _NEG_INF)
    return x, col


def _xent_fwd_kernel(x_ref, lbl_ref, loss_ref, lse_ref,
                     m_acc, l_acc, pick_acc, *, block_v, v):
    """Online logsumexp over vocab blocks.

    Grid is (row_blocks, vocab_blocks) with the vocab dim minor, so for a
    fixed row block the vocab programs run sequentially and the VMEM
    scratch accumulators (running max / sum-exp / picked logit) persist
    across them.  VMEM use is O(block_rows * block_v) regardless of the
    full vocab size — round 2's full-row (br, V) blocks OOMed scoped VMEM
    at V=30k in the backward.
    """
    j = pl.program_id(1)
    x, col = _xent_tile(x_ref, block_v=block_v, v=v)   # (block_rows, bv)
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


def _xent_bwd_kernel(x_ref, lbl_ref, lse_ref, g_ref, dx_ref, *, block_v, v):
    x, col = _xent_tile(x_ref, block_v=block_v, v=v)   # (block_rows, bv)
    lbl = lbl_ref[:][:, :1]
    lse = lse_ref[:][:, :1]
    g = g_ref[:][:, :1]
    p = jnp.exp(x - lse)
    valid = (lbl >= 0).astype(jnp.float32)
    dx = jnp.where(col == lbl, p - 1.0, p) * (g * valid)
    dx_ref[:] = dx.astype(dx_ref.dtype)


@jax.custom_vjp
def _fused_xent_2d(logits, labels):
    return _fused_xent_2d_fwd(logits, labels)[0]


# Only the [rows, _STAT_LANES] label / lse / cotangent strips below are
# padded to whole row blocks.  The logits matrix and its gradient keep
# their own shape: what a partial block reads past their edge is masked
# (columns, `_xent_tile`) or never written back (rows, which do not
# depend on each other).

@_x32
def _fused_xent_2d_fwd(logits, labels):
    rows, v = logits.shape
    br, bv = _xent_blocks(rows, v)
    rows_pad = _round_up(rows, br)
    lp = _lanes(_pad_dim(labels.astype(jnp.int32), 0, rows_pad, value=-1))
    with _kernel_span("softmax_cross_entropy", "fwd") as kernel_name:
        loss, lse = pl.pallas_call(
        functools.partial(_xent_fwd_kernel, block_v=bv, v=v),
        grid=(rows_pad // br, pl.cdiv(v, bv)),
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
    )(logits, lp)
    return loss[:rows, 0], (logits, labels, lse[:rows])


@_x32
def _fused_xent_2d_bwd(res, g):
    logits, labels, lse = res
    rows, v = logits.shape
    br, bv = _xent_blocks(rows, v)
    rows_pad = _round_up(rows, br)
    lp = _lanes(_pad_dim(labels.astype(jnp.int32), 0, rows_pad, value=-1))
    lsep = _pad_dim(lse, 0, rows_pad)
    gp = _lanes(_pad_dim(g.astype(jnp.float32), 0, rows_pad))
    with _kernel_span("softmax_cross_entropy", "bwd") as kernel_name:
        dx = pl.pallas_call(
        functools.partial(_xent_bwd_kernel, block_v=bv, v=v),
        grid=(rows_pad // br, pl.cdiv(v, bv)),
        in_specs=[
            pl.BlockSpec((br, bv), lambda i, j: (i, j)),
            pl.BlockSpec((br, _STAT_LANES), lambda i, j: (i, 0)),
            pl.BlockSpec((br, _STAT_LANES), lambda i, j: (i, 0)),
            pl.BlockSpec((br, _STAT_LANES), lambda i, j: (i, 0)),
        ],
        out_specs=pl.BlockSpec((br, bv), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((rows, v), logits.dtype),
        interpret=_interpret(),
        name=kernel_name,
    )(logits, lp, lsep, gp)
    return dx, None


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
