"""Lightning (linear) attention with a decayed recurrent state.

Per head ``h`` with decay ``lam = exp(-s_h)`` (Lightning Attention,
arXiv:2401.04658):

    S_t = lam * S_{t-1} + k_t^T v_t          S in R^{d x d}, float32
    o_t = (q_t / sqrt(d)) S_t

Two forms of the same recurrence, each as a Pallas kernel and as the
``jax.numpy`` composite of the same semantics (the CPU's path and the
declined gate's):

  * **chunk** (`lightning_attention_fwd`): ``C`` consecutive tokens of
    one sequence after state ``S_prev``, in blocks of ``block`` rows.
    Within a block ``O = [(Q K^T / sqrt d) * D] V + Lam (Q / sqrt d)
    S_prev`` with ``D_ij = lam^(i-j)`` for ``i >= j`` and ``Lam_i =
    lam^(i+1)``; ``S_new = lam^n S_prev + sum_j lam^(n-1-j) k_j^T v_j``
    over the block's ``n`` valid rows, so a chunk whose tail is padding
    leaves the state of its last valid token.  The state is read from
    and written to its slot of the pool in place; ``first`` starts from
    zero instead (a request's first chunk);
  * **step** (`lightning_attention_step`): one token each for ``S``
    decode rows, every row against its own slot.

Slot 0 of a pool is the pad slot: rows that carry nothing this step
point at it, as padded tokens point at block 0 of the KV pool.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .pallas_tiles import _interpret, _kernel_span, _x32

__all__ = ["decay_slopes", "chunk_block", "lightning_attention_fwd",
           "lightning_attention_step", "lightning_chunk_ref",
           "lightning_step_ref", "lightning_dense"]

_NT = (((1,), (1,)), ((), ()))      # a @ b.T
_NN = (((1,), (0,)), ((), ()))      # a @ b
_TN = (((0,), (0,)), ((), ()))      # a.T @ b
_STEP_HEADS = 4                      # heads a step-kernel program holds
_SUBLANES = 8


def decay_slopes(num_heads):
    """``s_h = 2 ** (-8 (h + 1) / H)``, the same in every layer."""
    return 2.0 ** (-8.0 * (np.arange(num_heads) + 1) / num_heads)


def chunk_block(chunk_rows):
    """Rows a chunk-kernel block holds: the largest power of two up to
    128 that divides the chunk's padded length."""
    b = 128
    while b > 1 and chunk_rows % b:
        b //= 2
    return b


# ---------------------------------------------------------------------
# composites
# ---------------------------------------------------------------------
def lightning_chunk_ref(q, k, v, state, slopes, n_valid, block=None):
    """``q, k, v`` [C, H, D]; ``state`` [H, D, D] float32; ``slopes``
    [H]; ``n_valid`` rows are real.  Returns ``(o [C, H, D] in q's
    type, new_state)``."""
    C, H, D = q.shape
    block = block or chunk_block(C)
    nb = C // block
    s = jnp.asarray(slopes, jnp.float32)                         # [H]
    scale = 1.0 / math.sqrt(D)
    i = jnp.arange(block, dtype=jnp.int32)
    diff = (i[:, None] - i[None, :]).astype(jnp.float32)

    def one(st, xs):
        qb, kb, vb, base = xs
        qb = qb.astype(jnp.float32) * scale
        kb, vb = kb.astype(jnp.float32), vb.astype(jnp.float32)
        nv = jnp.clip(n_valid - base, 0, block)
        live = (i[:, None] >= i[None, :]) & (i[None, :] < nv)
        dec = jnp.where(live, jnp.exp(-s[:, None, None] * diff), 0.0)
        a = jnp.einsum("ihd,jhd->hij", qb, kb,
                       preferred_element_type=jnp.float32) * dec
        o = jnp.einsum("hij,jhe->ihe", a, vb,
                       preferred_element_type=jnp.float32)
        lam_i = jnp.exp(-s[None, :] * (i[:, None] + 1.0))       # [block,H]
        o = o + lam_i[:, :, None] * jnp.einsum(
            "ihd,hde->ihe", qb, st, preferred_element_type=jnp.float32)
        w = jnp.where((i < nv)[:, None], jnp.exp(
            -s[None, :] * (nv - 1 - i)[:, None].astype(jnp.float32)), 0.0)
        st = jnp.exp(-s * nv.astype(jnp.float32))[:, None, None] * st \
            + jnp.einsum("jhd,jhe->hde", kb * w[:, :, None], vb,
                         preferred_element_type=jnp.float32)
        return st, o

    shape = (nb, block, H, D)
    st, o = jax.lax.scan(
        one, state.astype(jnp.float32),
        (q.reshape(shape), k.reshape(shape), v.reshape(shape),
         jnp.arange(nb, dtype=jnp.int32) * block))
    return o.reshape(C, H, D).astype(q.dtype), st


def lightning_step_ref(q, k, v, pool, slots, slopes):
    """One token a row: ``q, k, v`` [S, H, D]; ``pool`` [N, H, D, D]
    float32; ``slots`` [S].  Returns ``(o [S, H, D], new_pool)``."""
    D = q.shape[-1]
    lam = jnp.exp(-jnp.asarray(slopes, jnp.float32))[None, :, None, None]
    kf, vf = k.astype(jnp.float32), v.astype(jnp.float32)
    st = lam * pool[slots] + kf[..., :, None] * vf[..., None, :]
    o = jnp.einsum("shd,shde->she",
                   q.astype(jnp.float32) / math.sqrt(D), st,
                   preferred_element_type=jnp.float32)
    return o.astype(q.dtype), pool.at[slots].set(st.astype(pool.dtype))


def lightning_dense(q, k, v, slopes):
    """No cache: whole sequences ``[B, S, H, D]`` from a zero state."""
    B, S, H, D = q.shape
    block = min(128, max(8, 1 << (S - 1).bit_length()))
    pad = -S % block
    if pad:
        q, k, v = (jnp.pad(a, ((0, 0), (0, pad), (0, 0), (0, 0)))
                   for a in (q, k, v))
    zero = jnp.zeros((H, D, D), jnp.float32)
    o = jax.vmap(lambda a, b, c: lightning_chunk_ref(
        a, b, c, zero, slopes, S, block)[0])(q, k, v)
    return o[:, :S]


# ---------------------------------------------------------------------
# kernels
# ---------------------------------------------------------------------
def _chunk_kernel(meta_ref, slope_ref, q_ref, k_ref, v_ref, pool_ref,
                  o_ref, pool_out_ref, st_ref, *, block, scale, c_last):
    c = pl.program_id(1)
    n_valid, first = meta_ref[1], meta_ref[2]
    s = slope_ref[pl.program_id(0)]          # the head's slope, a scalar

    @pl.when(c == 0)
    def _load():
        keep = (first == 0).astype(jnp.float32)
        st_ref[...] = pool_ref[0, 0] * keep

    q = q_ref[...].astype(jnp.float32) * scale               # (block, D)
    k = k_ref[...].astype(jnp.float32)
    v = v_ref[...].astype(jnp.float32)
    nv = jnp.clip(n_valid - c * block, 0, block)
    i = jax.lax.broadcasted_iota(jnp.int32, (block, block), 0)
    j = jax.lax.broadcasted_iota(jnp.int32, (block, block), 1)
    dec = jnp.where((i >= j) & (j < nv),
                    jnp.exp(-s * (i - j).astype(jnp.float32)), 0.0)
    a = jax.lax.dot_general(q, k, _NT,
                            preferred_element_type=jnp.float32) * dec
    o = jax.lax.dot_general(a, v, _NN,
                            preferred_element_type=jnp.float32)
    r = jax.lax.broadcasted_iota(jnp.int32, (block, 1), 0)
    st = st_ref[...]
    o = o + jnp.exp(-s * (r + 1).astype(jnp.float32)) \
        * jax.lax.dot_general(q, st, _NN,
                              preferred_element_type=jnp.float32)
    o_ref[...] = o.astype(o_ref.dtype)
    w = jnp.where(r < nv, jnp.exp(-s * (nv - 1 - r).astype(jnp.float32)),
                  0.0)                                       # (block, 1)
    # lam ** nv as a row: Mosaic broadcasts along one axis at a time
    lam_n = jnp.exp(-s * nv.astype(jnp.float32)
                    * jnp.ones((1, st.shape[1]), jnp.float32))
    st_ref[...] = lam_n * st + jax.lax.dot_general(
        k * w, v, _TN, preferred_element_type=jnp.float32)

    @pl.when(c == c_last)
    def _store():
        pool_out_ref[0, 0] = st_ref[...]


@functools.partial(jax.jit, static_argnames=("slopes", "block"))
@_x32
def _chunk_call(q, k, v, pool, meta, *, slopes, block):
    C, H, D = q.shape
    nb = C // block
    row = pl.BlockSpec((block, D), lambda h, c, m, sl: (c, h))
    state = pl.BlockSpec((1, 1, D, D), lambda h, c, m, sl: (m[0], h, 0, 0))
    with _kernel_span("lightning_attention", "fwd") as name:
        o, pool = pl.pallas_call(
            functools.partial(_chunk_kernel, block=block,
                              scale=1.0 / math.sqrt(D), c_last=nb - 1),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=2,
                grid=(H, nb),
                in_specs=[row, row, row, state],
                out_specs=[row, state],
                scratch_shapes=[pltpu.VMEM((D, D), jnp.float32)]),
            out_shape=[jax.ShapeDtypeStruct((C, H * D), q.dtype),
                       jax.ShapeDtypeStruct(pool.shape, pool.dtype)],
            # operands count the scalar-prefetch ones: pool is the 6th
            input_output_aliases={5: 1},
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary")),
            interpret=_interpret(),
            name=name,
        )(meta, jnp.asarray(slopes, jnp.float32), q.reshape(C, H * D),
          k.reshape(C, H * D), v.reshape(C, H * D), pool)
    return o.reshape(C, H, D), pool


def lightning_attention_fwd(q, k, v, pool, slot, n_valid, first, slopes,
                            block=None):
    """The chunk form against slot ``slot`` of ``pool`` [N, H, D, D],
    in place.  ``q, k, v`` [C, H, D]; ``slot``, ``n_valid``, ``first``
    int32 scalars (traced).  Returns ``(o, new_pool)``.  Built through
    a jitted function of its shapes, so that the layers of a model
    trace and lower it once (PERF.md section 6, PR 27)."""
    block = block or chunk_block(q.shape[0])
    meta = jnp.stack([jnp.asarray(x, jnp.int32).reshape(())
                      for x in (slot, n_valid, first)])
    return _chunk_call(q, k, v, pool, meta,
                       slopes=tuple(float(s) for s in slopes), block=block)


def _step_kernel(slot_ref, lam_ref, q_ref, k_ref, v_ref, pool_ref,
                 o_ref, pool_out_ref, *, heads, scale):
    row0 = jax.lax.broadcasted_iota(jnp.int32, (_SUBLANES, 1), 0) == 0
    for h in range(heads):
        lam = lam_ref[pl.program_id(1) * heads + h]          # a scalar
        k = jnp.where(row0, k_ref[0, h], 0.0)                # (8, D)
        st = lam * pool_ref[0, h] + jax.lax.dot_general(
            k, v_ref[0, h], _TN, preferred_element_type=jnp.float32)
        pool_out_ref[0, h] = st
        o_ref[0, h] = jax.lax.dot_general(
            q_ref[0, h] * scale, st, _NN,
            preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnames=("slopes",))
@_x32
def _step_call(q, k, v, pool, slots, *, slopes):
    S, H, D = q.shape
    hb = _STEP_HEADS if H % _STEP_HEADS == 0 else 1
    # a row's vector as 8 equal sublanes: every operand a whole tile
    wide = lambda a: jnp.broadcast_to(                      # noqa: E731
        a.astype(jnp.float32)[:, :, None, :], (S, H, _SUBLANES, D))
    vec = pl.BlockSpec((1, hb, _SUBLANES, D),
                       lambda r, g, sl, lam: (r, g, 0, 0))
    state = pl.BlockSpec((1, hb, D, D),
                         lambda r, g, sl, lam: (sl[r], g, 0, 0))
    with _kernel_span("lightning_attention_step", "fwd") as name:
        o, pool = pl.pallas_call(
            functools.partial(_step_kernel, heads=hb,
                              scale=1.0 / math.sqrt(D)),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=2,
                grid=(S, H // hb),
                in_specs=[vec, vec, vec, state],
                out_specs=[vec, state]),
            out_shape=[jax.ShapeDtypeStruct((S, H, _SUBLANES, D),
                                            jnp.float32),
                       jax.ShapeDtypeStruct(pool.shape, pool.dtype)],
            input_output_aliases={5: 1},
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary", "arbitrary")),
            interpret=_interpret(),
            name=name,
        )(slots.astype(jnp.int32),
          jnp.exp(-jnp.asarray(slopes, jnp.float32)), wide(q), wide(k),
          wide(v), pool)
    return o[:, :, 0, :].astype(q.dtype), pool


def lightning_attention_step(q, k, v, pool, slots, slopes):
    """The one-step form: ``q, k, v`` [S, H, D], row ``r`` against slot
    ``slots[r]`` of ``pool``, in place.  Returns ``(o, new_pool)``."""
    return _step_call(q, k, v, pool, slots,
                      slopes=tuple(float(s) for s in slopes))
