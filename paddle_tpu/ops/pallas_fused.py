"""Fused training-path Pallas kernels: layernorm+residual-add and
matmul-epilogue (bias + activation folded into the matmul consumer).

Reference parity: the reference ships these as hand-written CUDA
fusions — `fused_layernorm_residual_dropout_bias` and the cuBLASLt
epilogue path behind `fused_gemm_epilogue` [UNVERIFIED — empty
reference mount].

TPU-native design: same Mosaic tiling discipline as
`pallas_kernels.py` (this module reuses its helpers and the layer-norm
backward kernel outright — the LN+residual backward is the LN backward
with the saved sum `s = x + residual` in place of `x`, since
`d(x)/d(residual)` are identical).  Both kernels are `jax.custom_vjp`
so the eager tape and `to_static` differentiate through the
hand-written backward, and both export block plans
(`ln_residual_block_plan` / `matmul_epilogue_block_plan`) that
`analysis.tiling` verifies statically before anything touches the TPU.

Activation math is hand-differentiated in f32 inside the kernels; the
names mirror the XLA fallbacks the nn.functional layer keeps bit-exact:
``gelu`` = erf form (`jax.nn.gelu(approximate=False)`), ``gelu_tanh`` =
tanh form (`approximate=True`), ``silu``, ``relu``, ``none``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from .pallas_kernels import (_dropout_seed, _keep_threshold, _ln_bwd_kernel,
                             _ln_bwd_tile, _seeded_call, _tile_bits)
from .pallas_tiles import (_STAT_LANES, _demote_f64, _interpret,
                           _kernel_span, _ln_block_rows, _pad_dim,
                           _round_up, _x32, matmul_accum_blocks)

__all__ = [
    "ACTIVATIONS",
    "fused_layer_norm_residual",
    "fused_linear_act",
    "fused_linear_act_int8",
    "layer_norm_residual_dropout_keep",
    "ln_residual_block_plan",
    "matmul_epilogue_block_plan",
]

ACTIVATIONS = ("none", "relu", "gelu", "gelu_tanh", "silu")

_SQRT_2 = 2.0 ** 0.5
_INV_SQRT_2PI = 0.3989422804014327     # 1/sqrt(2*pi)
_GELU_C = 0.7978845608028654           # sqrt(2/pi)
_GELU_A = 0.044715


def _erf_f32(x):
    """erf for kernel bodies: Mosaic has no lowering for the erf
    primitive (jax 0.9), so exact gelu could not compile for the chip.
    Abramowitz & Stegun 7.1.26, |error| < 1.5e-7."""
    a = jnp.abs(x)
    t = 1.0 / (1.0 + 0.3275911 * a)
    poly = t * (0.254829592 + t * (-0.284496736 + t * (
        1.421413741 + t * (-1.453152027 + t * 1.061405429))))
    y = 1.0 - poly * jnp.exp(-a * a)
    return jnp.where(x < 0.0, -y, y)


def _act_f32(z, act):
    if act == "none":
        return z
    if act == "relu":
        return jnp.maximum(z, 0.0)
    if act == "gelu":
        return 0.5 * z * (1.0 + _erf_f32(z / _SQRT_2))
    if act == "gelu_tanh":
        t = jnp.tanh(_GELU_C * (z + _GELU_A * z * z * z))
        return 0.5 * z * (1.0 + t)
    if act == "silu":
        return z * jax.nn.sigmoid(z)
    raise ValueError(f"act must be one of {ACTIVATIONS}, got {act!r}")


def _act_grad_f32(z, act):
    if act == "none":
        return jnp.ones_like(z)
    if act == "relu":
        return (z > 0.0).astype(z.dtype)
    if act == "gelu":
        # d/dz [z*Phi(z)] = Phi(z) + z*phi(z)
        phi = _INV_SQRT_2PI * jnp.exp(-0.5 * z * z)
        return 0.5 * (1.0 + _erf_f32(z / _SQRT_2)) + z * phi
    if act == "gelu_tanh":
        u = _GELU_C * (z + _GELU_A * z * z * z)
        t = jnp.tanh(u)
        du = _GELU_C * (1.0 + 3.0 * _GELU_A * z * z)
        return 0.5 * (1.0 + t) + 0.5 * z * (1.0 - t * t) * du
    if act == "silu":
        s = jax.nn.sigmoid(z)
        return s * (1.0 + z * (1.0 - s))
    raise ValueError(f"act must be one of {ACTIVATIONS}, got {act!r}")


# =====================================================================
# Fused layernorm + residual add
# =====================================================================

# Most bytes in one streamed block of the form that draws a dropout
# mask, from the sweep on a TPU v5e at (8192, 768)
# (scripts/ln_residual_block_sweep.py, PERF.md section 6, PR 29):
# forward plus backward took 0.173 / 0.133 / 0.120 / 0.112 ms with 64 /
# 128 / 256 / 512 rows in bfloat16 and 0.286 / 0.264 / 0.261 ms with 64
# / 128 / 256 in float32, where 512 rows no longer fit VMEM: 768 KiB in
# both.  The plain form's 256 rows were within 1.2 % of its best there.
_LN_RES_DROPOUT_BLOCK_BYTES = 768 << 10


def _ln_res_block_rows(rows, n, dropout=False, dtype=jnp.float32):
    """Rows in one block of the LN+residual kernels at (rows, n).  The
    forward and the backward of a call both take it from here, so the
    dropout form numbers its mask tiles the same way in both."""
    if dropout:
        fit = _LN_RES_DROPOUT_BLOCK_BYTES // (n * jnp.dtype(dtype).itemsize)
        cap = max(16, min(512, fit // 16 * 16))
    else:
        # the forward streams 4 (br, N) blocks (x, r, out, s) where plain
        # LN streams 2; halve the row budget so the double-buffered VMEM
        # estimate stays well under the 16MB ceiling at BERT-base widths
        cap = 256
    return min(_ln_block_rows(rows, n), cap)


def _block_keep(seed_ref, shape, dropout_p):
    """Keep mask of this program's row block: tile `program_id(0)` of
    the stream under the call's seed, in the forward, the backward and
    `layer_norm_residual_dropout_keep` alike."""
    return _tile_bits(seed_ref[0], pl.program_id(0), shape) < jnp.uint32(
        _keep_threshold(dropout_p))


def _ln_res_fwd_kernel(*refs, eps, dropout_p=0.0):
    """refs: [seed, if dropout_p], x, r, gamma, beta, out, s, mu, rstd."""
    if dropout_p:
        seed_ref, *refs = refs
    x_ref, r_ref, g_ref, b_ref, o_ref, s_ref, mu_ref, rstd_ref = refs
    x = x_ref[:].astype(jnp.float32)
    if dropout_p:
        x = jnp.where(_block_keep(seed_ref, x.shape, dropout_p),
                      x * (1.0 / (1.0 - dropout_p)), 0.0)
    # add and statistics both run in f32; the saved sum is stored in
    # the input dtype (the residual stream's own precision)
    s = x + r_ref[:].astype(jnp.float32)                # (block_rows, N)
    br = s.shape[0]
    mu = jnp.mean(s, axis=-1, keepdims=True)
    sc = s - mu
    var = jnp.mean(sc * sc, axis=-1, keepdims=True)
    rstd = jax.lax.rsqrt(var + eps)
    shat = sc * rstd
    o_ref[:] = (shat * g_ref[:].astype(jnp.float32)
                + b_ref[:].astype(jnp.float32)).astype(o_ref.dtype)
    s_ref[:] = s.astype(s_ref.dtype)
    mu_ref[:] = jnp.broadcast_to(mu, (br, _STAT_LANES))
    rstd_ref[:] = jnp.broadcast_to(rstd, (br, _STAT_LANES))


def _ln_res_drop_bwd_kernel(seed_ref, s_ref, g_ref, mu_ref, rstd_ref,
                            do_ref, dx_ref, dr_ref, dg_ref, db_ref, *,
                            dropout_p):
    """The LN backward on the saved sum is d(residual); d(x) is that
    under the block's mask, drawn again from the forward's tile."""
    ds = _ln_bwd_tile(s_ref, g_ref, mu_ref, rstd_ref, do_ref, dg_ref,
                      db_ref)
    dr_ref[:] = ds.astype(dr_ref.dtype)
    dx_ref[:] = jnp.where(_block_keep(seed_ref, ds.shape, dropout_p),
                          ds * (1.0 / (1.0 - dropout_p)),
                          0.0).astype(dx_ref.dtype)


def _rows(shape):
    """Block that walks the rows with the grid index."""
    return pl.BlockSpec(shape, lambda i, *_: (i, 0))


def _fixed(shape):
    """Block every program sees whole: gamma, beta, their gradients."""
    return pl.BlockSpec(shape, lambda i, *_: (0, 0))


@_x32
def _ln_res_fwd_call(x, r, gamma, beta, seed, eps, dropout_p):
    """(out, s, mu, rstd) of the forward kernel; mu and rstd keep their
    padded rows for the backward."""
    rows, n = x.shape
    br = _ln_res_block_rows(rows, n, dropout_p > 0.0, x.dtype)
    rows_pad = _round_up(rows, br)
    row_out = jax.ShapeDtypeStruct((rows_pad, n), x.dtype)
    stat_out = jax.ShapeDtypeStruct((rows_pad, _STAT_LANES), jnp.float32)
    with _kernel_span("layer_norm_residual", "fwd") as kernel_name:
        out, s, mu, rstd = _seeded_call(
            functools.partial(_ln_res_fwd_kernel, eps=eps,
                              dropout_p=dropout_p),
            (rows_pad // br,),
            [_rows((br, n)), _rows((br, n)), _fixed((1, n)),
             _fixed((1, n))],
            [_rows((br, n)), _rows((br, n)), _rows((br, _STAT_LANES)),
             _rows((br, _STAT_LANES))],
            [row_out, row_out, stat_out, stat_out], kernel_name, seed,
            _pad_dim(x, 0, rows_pad), _pad_dim(r, 0, rows_pad),
            gamma.reshape(1, n), beta.reshape(1, n))
    return out[:rows], s[:rows], mu, rstd


@_x32
def _ln_res_bwd_call(s, gamma, mu, rstd, do, seed, dropout_p):
    """(d_x, d_residual, dgamma, dbeta); without dropout the first two
    are one array, the plain LN backward on the saved sum."""
    rows, n = s.shape
    br = _ln_res_block_rows(rows, n, dropout_p > 0.0, s.dtype)
    rows_pad = _round_up(rows, br)
    if dropout_p:
        kernel, row_outs = functools.partial(
            _ln_res_drop_bwd_kernel, dropout_p=dropout_p), 2
    else:
        kernel, row_outs = _ln_bwd_kernel, 1
    sp = _pad_dim(s, 0, rows_pad)
    dop = _pad_dim(do, 0, rows_pad)
    row_out = jax.ShapeDtypeStruct((rows_pad, n), s.dtype)
    acc_out = jax.ShapeDtypeStruct((8, n), jnp.float32)
    with _kernel_span("layer_norm_residual", "bwd") as kernel_name:
        *d_rows, dg_acc, db_acc = _seeded_call(
            kernel, (rows_pad // br,),
            [_rows((br, n)), _fixed((1, n)), _rows((br, _STAT_LANES)),
             _rows((br, _STAT_LANES)), _rows((br, n))],
            [_rows((br, n))] * row_outs + [_fixed((8, n))] * 2,
            [row_out] * row_outs + [acc_out] * 2, kernel_name, seed,
            sp, gamma.reshape(1, n), mu, rstd, dop)
    dgamma = dg_acc[0].astype(gamma.dtype)
    dbeta = db_acc[0].astype(gamma.dtype)
    d_rows = [d[:rows] for d in d_rows]
    return d_rows[0], d_rows[-1], dgamma, dbeta


# A model calls the dropout form once a sublayer with the same shapes:
# through jitted builders it is traced and lowered once (one Mosaic
# kernel, N calls to it), as the flash kernels are (PERF.md section 6,
# PR 27).  The plain form stays inline: its lowered program is the one
# every eval() path and the serving cells had.
_ln_res_fwd_call_jit = jax.jit(_ln_res_fwd_call,
                               static_argnames=("eps", "dropout_p"))
_ln_res_bwd_call_jit = jax.jit(_ln_res_bwd_call,
                               static_argnames=("dropout_p",))


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _fused_ln_residual_2d(x, r, gamma, beta, seed, eps, dropout_p=0.0):
    """`seed`: int32[1] when `dropout_p` > 0, else None."""
    return _fused_ln_residual_2d_fwd(x, r, gamma, beta, seed, eps,
                                     dropout_p)[0]


def _fused_ln_residual_2d_fwd(x, r, gamma, beta, seed, eps, dropout_p):
    call = _ln_res_fwd_call_jit if dropout_p else _ln_res_fwd_call
    out, s, mu, rstd = call(x, r, gamma, beta, seed, eps=eps,
                            dropout_p=dropout_p)
    return out, (s, gamma, mu, rstd, seed)


def _fused_ln_residual_2d_bwd(eps, dropout_p, res, do):
    s, gamma, mu, rstd, seed = res
    call = _ln_res_bwd_call_jit if dropout_p else _ln_res_bwd_call
    return call(s, gamma, mu, rstd, do, seed, dropout_p=dropout_p) + (None,)


_fused_ln_residual_2d.defvjp(_fused_ln_residual_2d_fwd,
                             _fused_ln_residual_2d_bwd)


def fused_layer_norm_residual(x, residual, gamma, beta, eps=1e-5, *,
                              dropout_p=0.0, seed=None):
    """LayerNorm(dropout(x) + residual) over the last dim, fused;
    differentiable.

    The residual add, mean/variance, normalize and affine all run in a
    single VMEM pass (one read of x/residual instead of the unfused
    add-then-norm's two), and the backward reuses the plain LN backward
    on the saved sum.

    `dropout_p` (static) > 0 drops elements of `x` inside the kernels:
    every element draws its own 32-bit word from a stream fixed by the
    integer `seed` and its row block, is kept with probability 1 - p
    and scaled by 1 / (1 - p); the backward kernel draws the same words
    again, so no mask reaches HBM.  `layer_norm_residual_dropout_keep`
    writes that mask out.  At 0 the kernels hold no generator code.
    """
    dropout_p, seed = _dropout_seed(dropout_p, seed)
    x, residual, gamma, beta = _demote_f64(x, residual, gamma, beta)
    shape = x.shape
    n = shape[-1]
    out = _fused_ln_residual_2d(x.reshape(-1, n), residual.reshape(-1, n),
                                gamma, beta, seed, float(eps), dropout_p)
    return out.reshape(shape)


def _ln_res_keep_kernel(seed_ref, keep_ref, *, dropout_p):
    keep_ref[:] = _block_keep(seed_ref, keep_ref.shape,
                              dropout_p).astype(keep_ref.dtype)


@_x32
def layer_norm_residual_dropout_keep(seed, rows, n, dropout_p,
                                     dtype=jnp.float32):
    """The keep mask `fused_layer_norm_residual(..., dropout_p, seed)`
    applies to `rows` x `n` inputs of `dtype`, as bool [rows, n]: the
    same tile stream, written out.  For checking the kernels against a
    composite under an explicit mask; the training path never builds
    it."""
    dropout_p, seed = _dropout_seed(dropout_p, seed)
    br = _ln_res_block_rows(rows, n, True, dtype)
    rows_pad = _round_up(rows, br)
    with _kernel_span("layer_norm_residual", "keep") as kernel_name:
        keep = _seeded_call(
            functools.partial(_ln_res_keep_kernel, dropout_p=dropout_p),
            (rows_pad // br,), [], _rows((br, n)),
            jax.ShapeDtypeStruct((rows_pad, n), jnp.int32), kernel_name,
            seed)
    return keep[:rows].astype(bool)


def ln_residual_block_plan(rows, hidden, dtype=jnp.float32,
                           direction="fwd", dropout=False):
    """The exact block plan the LN+residual kernels use for (rows, N).

    Same contract as `flash_block_plan`: per-operand (name, block_shape,
    padded_array_shape, dtype) in pallas_call order, statically
    checkable by `analysis.tiling.check_pallas_call`.  Keep in lockstep
    with `_ln_res_fwd_call` / `_ln_res_bwd_call`.  ``dropout`` gives the
    form that draws its mask: its own row cap, the seed as a scalar-
    prefetch operand (SMEM, untiled, listed under ``scalar_prefetch``)
    and d(x) beside d(residual) in the backward.
    """
    dtype = jnp.dtype(dtype)
    f32 = jnp.dtype(jnp.float32)
    n = hidden
    br = _ln_res_block_rows(rows, n, dropout, dtype)
    rows_pad = _round_up(rows, br)
    row_blk = lambda name, dt: (  # noqa: E731 - local table helper
        name, (br, n), (rows_pad, n), dt)
    stat = lambda name: (  # noqa: E731
        name, (br, _STAT_LANES), (rows_pad, _STAT_LANES), f32)
    if direction == "fwd":
        operands = [
            row_blk("x", dtype), row_blk("residual", dtype),
            ("gamma", (1, n), (1, n), dtype),
            ("beta", (1, n), (1, n), dtype),
            row_blk("out", dtype), row_blk("s", dtype),
            stat("mu"), stat("rstd"),
        ]
    elif direction == "bwd":
        operands = [
            row_blk("s", dtype),
            ("gamma", (1, n), (1, n), dtype),
            stat("mu"), stat("rstd"),
            row_blk("do", dtype), row_blk("dx", dtype),
            *([row_blk("d_residual", dtype)] if dropout else []),
            ("dgamma", (8, n), (8, n), f32),
            ("dbeta", (8, n), (8, n), f32),
        ]
    else:
        raise ValueError(f"direction must be fwd|bwd, got {direction!r}")
    return {
        "direction": direction,
        "grid": (rows_pad // br,),
        "block_rows": br,
        "scalar_prefetch": ((("seed", (1,), jnp.dtype(jnp.int32)),)
                            if dropout else ()),
        "operands": operands,
        "scratch": (),
    }


# =====================================================================
# Matmul-epilogue fusion: act(x @ w + b)
# =====================================================================

def _me_fwd_kernel(x_ref, w_ref, b_ref, o_ref, z_ref, *, act):
    # f32 operands: Mosaic's tpu.matmul rejects bf16 inputs here (same
    # convention as the flash kernels); accumulation + epilogue in f32
    z = jax.lax.dot_general(
        x_ref[:].astype(jnp.float32), w_ref[:].astype(jnp.float32),
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)             # (bm, bn)
    z = z + b_ref[:].astype(jnp.float32)
    z_ref[:] = z.astype(z_ref.dtype)
    o_ref[:] = _act_f32(z, act).astype(o_ref.dtype)


def _me_bwd_kernel(z_ref, g_ref, dz_ref, db_ref, *, act):
    z = z_ref[:].astype(jnp.float32)
    g = g_ref[:].astype(jnp.float32)
    dz = g * _act_grad_f32(z, act)
    dz_ref[:] = dz.astype(dz_ref.dtype)

    # dbias: sequential-grid accumulation — the grid is (n_blocks,
    # m_blocks) with m minor, so every revisit of this db block is
    # consecutive
    @pl.when(pl.program_id(1) == 0)
    def _init():
        db_ref[:] = jnp.zeros_like(db_ref)

    db = jnp.sum(dz, axis=0, keepdims=True)             # (1, bn)
    db_ref[:] = db_ref[:] + jnp.broadcast_to(db, db_ref.shape)


def _me_blocks(m, k, n, dtype):
    """(bm, bn, m_pad, n_pad): the shared k-blocked f32 accumulator
    plan (`pallas_tiles.matmul_accum_blocks`) at this dtype."""
    return matmul_accum_blocks(m, k, n, dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _matmul_epilogue_2d(x, w, b, act):
    return _matmul_epilogue_2d_fwd(x, w, b, act)[0]


@_x32
def _matmul_epilogue_2d_fwd(x, w, b, act):
    m, k = x.shape
    n = w.shape[1]
    bm, bn, m_pad, n_pad = _me_blocks(m, k, n, x.dtype)
    xp = _pad_dim(x, 0, m_pad)
    wp = _pad_dim(w, 1, n_pad)
    bp = _pad_dim(b.reshape(1, n), 1, n_pad)
    with _kernel_span("matmul_epilogue", "fwd") as kernel_name:
        out, z = pl.pallas_call(
            functools.partial(_me_fwd_kernel, act=act),
            grid=(m_pad // bm, n_pad // bn),
            in_specs=[
                pl.BlockSpec((bm, k), lambda i, j: (i, 0)),
                pl.BlockSpec((k, bn), lambda i, j: (0, j)),
                pl.BlockSpec((1, bn), lambda i, j: (0, j)),
            ],
            out_specs=[
                pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
                pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((m_pad, n_pad), x.dtype),
                jax.ShapeDtypeStruct((m_pad, n_pad), x.dtype),
            ],
            interpret=_interpret(),
            name=kernel_name,
        )(xp, wp, bp)
    return out[:m, :n], (x, w, b, z[:m, :n])


@_x32
def _matmul_epilogue_2d_bwd(act, res, g):
    x, w, b, z = res
    m, k = x.shape
    n = w.shape[1]
    bm, bn, m_pad, n_pad = _me_blocks(m, k, n, x.dtype)
    zp = _pad_dim(_pad_dim(z, 0, m_pad), 1, n_pad)
    gp = _pad_dim(_pad_dim(g, 0, m_pad), 1, n_pad)
    with _kernel_span("matmul_epilogue", "bwd") as kernel_name:
        dz_pad, db_acc = pl.pallas_call(
            functools.partial(_me_bwd_kernel, act=act),
            grid=(n_pad // bn, m_pad // bm),
            in_specs=[
                pl.BlockSpec((bm, bn), lambda j, i: (i, j)),
                pl.BlockSpec((bm, bn), lambda j, i: (i, j)),
            ],
            out_specs=[
                pl.BlockSpec((bm, bn), lambda j, i: (i, j)),
                pl.BlockSpec((8, bn), lambda j, i: (0, j)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((m_pad, n_pad), x.dtype),
                jax.ShapeDtypeStruct((8, n_pad), jnp.float32),
            ],
            interpret=_interpret(),
            name=kernel_name,
        )(zp, gp)
    dz = dz_pad[:m, :n]
    # dx / dw are plain matmuls XLA already schedules optimally — the
    # fusion win is the epilogue, so hand these back to XLA
    dx = jax.lax.dot_general(
        dz, w, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32).astype(x.dtype)
    dw = jax.lax.dot_general(
        x, dz, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32).astype(w.dtype)
    db = db_acc[0, :n].astype(b.dtype)
    return dx, dw, db


_matmul_epilogue_2d.defvjp(_matmul_epilogue_2d_fwd,
                           _matmul_epilogue_2d_bwd)


def fused_linear_act(x, w, b, act="none"):
    """act(x @ w + b) with bias + activation fused into the matmul
    consumer; differentiable.  x: [..., K]; w: [K, N]; b: [N]."""
    if act not in ACTIVATIONS:
        raise ValueError(f"act must be one of {ACTIVATIONS}, got {act!r}")
    x, w, b = _demote_f64(x, w, b)
    shape = x.shape
    k = shape[-1]
    n = w.shape[-1]
    out = _matmul_epilogue_2d(x.reshape(-1, k), w, b.reshape(n), act)
    return out.reshape(shape[:-1] + (n,))


# =====================================================================
# Int8-weight matmul epilogue: act((x @ w_int8) * scale + b)
# =====================================================================
#
# The weight lives in HBM as int8 with one f32 scale per OUTPUT channel.
# Per-output-channel dequant commutes with the contraction —
# x @ (w_q * diag(s)) == (x @ w_q) * s — so the kernel keeps the int8
# tiles all the way into VMEM (half the weight bandwidth of bf16, a
# quarter of f32) and applies the scale once on the f32 accumulator:
# one multiply per OUTPUT element instead of one per weight element.
# The XLA fallback in nn.functional must use the same post-dot op order
# to stay bit-exact with the interpret-mode kernel.


def _me_int8_fwd_kernel(x_ref, w_ref, s_ref, b_ref, o_ref, z_ref, *, act):
    # tpu.matmul wants f32 operands (same convention as _me_fwd_kernel);
    # the int8 -> f32 widening happens on the VMEM-resident tile, AFTER
    # the (k, bn) block travelled HBM->VMEM at 1 byte/element
    z = jax.lax.dot_general(
        x_ref[:].astype(jnp.float32), w_ref[:].astype(jnp.float32),
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)             # (bm, bn)
    z = z * s_ref[:] + b_ref[:].astype(jnp.float32)     # dequant epilogue
    z_ref[:] = z.astype(z_ref.dtype)
    o_ref[:] = _act_f32(z, act).astype(o_ref.dtype)


def _me_int8_blocks(m, k, n, x_dtype):
    """(bm, bn, m_pad, n_pad) for the int8-weight variant: the VMEM
    ceiling is driven by the double-buffered (K, bn) weight block at
    1 byte/element, so bn can run wider than the float kernel's; bm
    still follows the ACTIVATION dtype (x is not int8)."""
    return matmul_accum_blocks(m, k, n, x_dtype, weight_itemsize=1)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _matmul_epilogue_int8_2d(x, w_q, scale, b, act):
    return _matmul_epilogue_int8_2d_fwd(x, w_q, scale, b, act)[0]


@_x32
def _matmul_epilogue_int8_2d_fwd(x, w_q, scale, b, act):
    m, k = x.shape
    n = w_q.shape[1]
    bm, bn, m_pad, n_pad = _me_int8_blocks(m, k, n, x.dtype)
    xp = _pad_dim(x, 0, m_pad)
    wp = _pad_dim(w_q, 1, n_pad)
    # padded channels get scale 1.0 so the bwd dscale division below
    # never sees a synthetic zero (their columns are sliced off anyway)
    sp = _pad_dim(scale.reshape(1, n).astype(jnp.float32), 1, n_pad, 1.0)
    bp = _pad_dim(b.reshape(1, n), 1, n_pad)
    with _kernel_span("matmul_epilogue_int8", "fwd") as kernel_name:
        out, z = pl.pallas_call(
            functools.partial(_me_int8_fwd_kernel, act=act),
            grid=(m_pad // bm, n_pad // bn),
            in_specs=[
                pl.BlockSpec((bm, k), lambda i, j: (i, 0)),
                pl.BlockSpec((k, bn), lambda i, j: (0, j)),
                pl.BlockSpec((1, bn), lambda i, j: (0, j)),
                pl.BlockSpec((1, bn), lambda i, j: (0, j)),
            ],
            out_specs=[
                pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
                pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((m_pad, n_pad), x.dtype),
                jax.ShapeDtypeStruct((m_pad, n_pad), x.dtype),
            ],
            interpret=_interpret(),
            name=kernel_name,
        )(xp, wp, sp, bp)
    return out[:m, :n], (x, w_q, scale, b, z[:m, :n])


@_x32
def _matmul_epilogue_int8_2d_bwd(act, res, g):
    x, w_q, scale, b, z = res
    m, k = x.shape
    n = w_q.shape[1]
    bm, bn, m_pad, n_pad = _me_int8_blocks(m, k, n, x.dtype)
    zp = _pad_dim(_pad_dim(z, 0, m_pad), 1, n_pad)
    gp = _pad_dim(_pad_dim(g, 0, m_pad), 1, n_pad)
    # dz/db epilogue backward is dtype-agnostic over z/g — reuse the
    # float kernel at the int8 plan's block sizes
    with _kernel_span("matmul_epilogue_int8", "bwd") as kernel_name:
        dz_pad, db_acc = pl.pallas_call(
            functools.partial(_me_bwd_kernel, act=act),
            grid=(n_pad // bn, m_pad // bm),
            in_specs=[
                pl.BlockSpec((bm, bn), lambda j, i: (i, j)),
                pl.BlockSpec((bm, bn), lambda j, i: (i, j)),
            ],
            out_specs=[
                pl.BlockSpec((bm, bn), lambda j, i: (i, j)),
                pl.BlockSpec((8, bn), lambda j, i: (0, j)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((m_pad, n_pad), x.dtype),
                jax.ShapeDtypeStruct((8, n_pad), jnp.float32),
            ],
            interpret=_interpret(),
            name=kernel_name,
        )(zp, gp)
    dz = dz_pad[:m, :n]
    s32 = scale.reshape(n).astype(jnp.float32)
    # the weight is dequantized ONCE for dx; the quantized tensor
    # itself is integer (no cotangent), but the per-channel scale is a
    # live float leaf — its grad falls out of the saved pre-activation:
    # z = (x @ w_q) * s + b  =>  dz/ds_j = (z_j - b_j) / s_j
    w_deq = w_q.astype(jnp.float32) * s32[None, :]
    dx = jax.lax.dot_general(
        dz, w_deq, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32).astype(x.dtype)
    dz32 = dz.astype(jnp.float32)
    acc = (z.astype(jnp.float32) - b.reshape(1, n).astype(jnp.float32))
    dscale = jnp.sum(dz32 * acc, axis=0) / s32
    db = db_acc[0, :n].astype(b.dtype)
    dw_q = np.zeros(w_q.shape, dtype=jax.dtypes.float0)
    return dx, dw_q, dscale.astype(scale.dtype), db


_matmul_epilogue_int8_2d.defvjp(_matmul_epilogue_int8_2d_fwd,
                                _matmul_epilogue_int8_2d_bwd)


def fused_linear_act_int8(x, w_q, scale, b, act="none"):
    """act((x @ w_int8) * scale + b) with the per-output-channel dequant
    fused into the matmul accumulator; differentiable in x, scale, b.

    x: [..., K] float; w_q: [K, N] int8; scale: [N] f32 per-channel
    dequant scales; b: [N].  The int8 weight is a frozen constant
    (integer primal, float0 cotangent).
    """
    if act not in ACTIVATIONS:
        raise ValueError(f"act must be one of {ACTIVATIONS}, got {act!r}")
    if jnp.dtype(w_q.dtype) != jnp.dtype(jnp.int8):
        raise ValueError(f"w_q must be int8, got {w_q.dtype}")
    x, b = _demote_f64(x, b)
    scale = jnp.asarray(scale, jnp.float32)
    shape = x.shape
    k = shape[-1]
    n = w_q.shape[-1]
    out = _matmul_epilogue_int8_2d(x.reshape(-1, k), w_q,
                                   scale.reshape(n), b.reshape(n), act)
    return out.reshape(shape[:-1] + (n,))


def matmul_epilogue_block_plan(m, k, n, dtype=jnp.float32,
                               direction="fwd", weight_dtype=None):
    """The exact block plan `_matmul_epilogue_2d_{fwd,bwd}` uses for
    an (m, k) @ (k, n) problem.  Same contract as `flash_block_plan`.

    ``weight_dtype=int8`` exports the `_matmul_epilogue_int8_2d` plan
    instead: int8 (k, bn) weight blocks + an f32 (1, bn) per-channel
    scale operand; the activation/output dtype stays ``dtype``.
    """
    dtype = jnp.dtype(dtype)
    f32 = jnp.dtype(jnp.float32)
    wdt = jnp.dtype(weight_dtype) if weight_dtype is not None else dtype
    int8_w = wdt == jnp.dtype(jnp.int8)
    if int8_w:
        bm, bn, m_pad, n_pad = _me_int8_blocks(m, k, n, dtype)
    else:
        bm, bn, m_pad, n_pad = _me_blocks(m, k, n, dtype)
    out_blk = lambda name: (  # noqa: E731 - local table helper
        name, (bm, bn), (m_pad, n_pad), dtype)
    if direction == "fwd":
        grid = (m_pad // bm, n_pad // bn)
        operands = [
            ("x", (bm, k), (m_pad, k), dtype),
            ("w", (k, bn), (k, n_pad), wdt),
        ]
        if int8_w:
            operands.append(("scale", (1, bn), (1, n_pad), f32))
        operands += [
            ("b", (1, bn), (1, n_pad), dtype),
            out_blk("out"), out_blk("z"),
        ]
    elif direction == "bwd":
        grid = (n_pad // bn, m_pad // bm)
        operands = [
            out_blk("z"), out_blk("g"), out_blk("dz"),
            ("db", (8, bn), (8, n_pad), f32),
        ]
    else:
        raise ValueError(f"direction must be fwd|bwd, got {direction!r}")
    return {
        "direction": direction,
        "grid": grid,
        "block_m": bm,
        "block_n": bn,
        "weight_dtype": str(wdt),
        "operands": operands,
        "scratch": (),
    }
