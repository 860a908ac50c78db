"""Latent attention (MLA) over a paged pool of latent rows, absorbed form.

A latent layer keeps ONE row a token for all its heads (``serving/
kv_cache.py``'s ``latent`` kind): the compressed ``c`` (``kv_lora_rank``
lanes) and the shared rotary key ``kr`` side by side, ``[num_blocks,
block_size, width]``.  In the absorbed form every head's query is a row
of the same width (``q_h W_UK_h^T`` beside the head's rotary query), its
score against a token is the dot with that token's row, and its value is
the first ``value_lanes`` lanes of the same row (``c``; the caller
multiplies the result by ``W_UV``).  So K and V are one array, K is
wider than V, and V is a slice of K: a shape the ragged kernel
(`pallas_ragged`) does not take.

The program is one ``block_q``-row q-block whose rows are *head
groups*: row ``r`` is token ``r % block_tokens`` of the q-block's
segment (a decode row's 64 heads are 64 rows of one token; a chunk's
q-block is all its heads over ``block_tokens`` tokens), at position
``q_starts[i] + r % block_tokens``.  The segment descriptors, block
tables and context lengths are the ragged kernel's, scalar-prefetched;
the walk over the table is `pallas_ragged.walk` (live blocks only,
``kv_step`` a step from `pallas_ragged._kv_step`, whole rows of a block
by the program's own double-buffered copies out of the pool left in
HBM).  Scores and values are bfloat16 MXU products on a bfloat16 pool,
accumulated in float32 (`pallas_kernels._mxu_dot`); the softmax is the
ragged kernel's online one, and a row that sees nothing emits zeros.

The call is named ``latent_attention_fwd`` (`pallas_tiles._kernel_span`)
so that the device trace prices it apart from ``ragged_attention_fwd``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .pallas_kernels import _mxu_dot
from .pallas_ragged import _kv_step, walk
from .pallas_tiles import (_NEG_INF, _demote_f64, _interpret, _kernel_span,
                           _lanes, _x32, softmax_scratch)

__all__ = ["latent_attention"]

_NT = (((1,), (1,)), ((), ()))
_NN = (((1,), (0,)), ((), ()))


def _latent_body(bt_ref, cl_ref, sid_ref, qs_ref, qv_ref, q_ref, pool_hbm,
                 o_ref, acc_ref, m_ref, l_ref, buf, sems, *, block_size,
                 block_q, block_tokens, kv_step, scale, value_lanes):
    """One q-block's program: the walk over its sequence's table, every
    step's rows the keys of all the q-block's rows and their first
    ``value_lanes`` lanes the values."""
    i = pl.program_id(0)
    sid = sid_ref[i]
    ctx = cl_ref[sid]
    qs = qs_ref[i]
    qv = qv_ref[i]
    width = buf.shape[-1]
    keys = kv_step * block_size
    last = qs + (block_tokens - 1)
    hi = jnp.minimum(jax.lax.div(ctx + (block_size - 1), block_size),
                     jax.lax.div(last, block_size) + 1)

    acc_ref[...] = jnp.zeros_like(acc_ref)
    m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)

    def compute(g, b):
        x = buf[b].reshape(keys, width)
        row = jax.lax.broadcasted_iota(jnp.int32, (block_q, keys), 0)
        row = row & (block_tokens - 1)        # the row's token
        col = jax.lax.broadcasted_iota(jnp.int32, (block_q, keys), 1) \
            + g * keys
        mask = (row < qv) & (col <= row + qs) & (col < ctx)
        s = _mxu_dot(q_ref[...].astype(x.dtype), x, _NT) * scale
        s = jnp.where(mask, s, _NEG_INF)
        m_prev = m_ref[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = _lanes(alpha * l_ref[:, :1]
                            + jnp.sum(p, axis=-1, keepdims=True))
        acc_ref[...] = acc_ref[...] * alpha + _mxu_dot(
            p.astype(x.dtype), x[:, :value_lanes], _NN)
        m_ref[...] = _lanes(m_new)

    walk(bt_ref, sid, 0, hi, kv_step, [(pool_hbm, buf, False)], buf, sems,
         0, width, compute)

    l = l_ref[:, :1]
    out = acc_ref[...] / jnp.where(l == 0.0, 1.0, l)
    out = jnp.where(jnp.broadcast_to(l, out.shape) > 0.0, out, 0.0)
    o_ref[...] = out.astype(o_ref.dtype)


@_x32
def latent_attention(q, pool, block_tables, context_lens, seq_ids,
                     q_starts, q_valids, *, block_q, block_tokens,
                     value_lanes, scale):
    """Absorbed latent attention over the paged latent pool.

    ``q`` [T, width]: ``T // block_q`` q-blocks of head-group rows (row
    ``r`` of q-block ``i`` is token ``r % block_tokens`` at position
    ``q_starts[i] + r % block_tokens``); ``pool`` [num_blocks,
    block_size, width]; ``block_tables`` [S, W], ``context_lens`` [S];
    ``seq_ids`` / ``q_starts`` / ``q_valids`` [T // block_q] as in
    `pallas_ragged.ragged_paged_attention` (``seq_ids == S``: a null
    q-block, zeros; ``q_valids`` counts tokens).  Returns [T,
    value_lanes]: each row's softmax-weighted sum of the first
    ``value_lanes`` lanes of the rows it sees."""
    q, pool = _demote_f64(q, pool)
    T, width = q.shape
    num_blocks, block_size, pool_width = pool.shape
    if pool_width != width or T % block_q or block_q % block_tokens \
            or block_tokens & (block_tokens - 1):
        raise ValueError(
            f"q {q.shape} in q-blocks of {block_q} rows of head groups of "
            f"{block_tokens} tokens against a pool {pool.shape}")
    nqb = T // block_q
    S, W = block_tables.shape
    kv_step = _kv_step(block_q, block_size, width,
                       jnp.dtype(pool.dtype).itemsize, W)
    bt = jnp.concatenate([block_tables.astype(jnp.int32),
                          jnp.zeros((1, W), jnp.int32)], axis=0)
    cl = jnp.concatenate([context_lens.astype(jnp.int32),
                          jnp.zeros((1,), jnp.int32)], axis=0)
    prefetched = [bt, cl, seq_ids.astype(jnp.int32),
                  q_starts.astype(jnp.int32), q_valids.astype(jnp.int32)]
    with _kernel_span("latent_attention", "fwd") as kernel_name:
        return pl.pallas_call(
            functools.partial(
                _latent_body, block_size=block_size, block_q=block_q,
                block_tokens=int(block_tokens), kv_step=kv_step,
                scale=float(scale), value_lanes=int(value_lanes)),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=len(prefetched),
                grid=(nqb,),
                in_specs=[pl.BlockSpec((block_q, width),
                                       lambda i, *_: (i, 0)),
                          pl.BlockSpec(memory_space=pl.ANY)],
                out_specs=pl.BlockSpec((block_q, value_lanes),
                                       lambda i, *_: (i, 0)),
                scratch_shapes=softmax_scratch(block_q, value_lanes) + [
                    pltpu.VMEM((2, kv_step, block_size, width), pool.dtype),
                    pltpu.SemaphoreType.DMA((1, 2))],
            ),
            out_shape=jax.ShapeDtypeStruct((T, value_lanes), q.dtype),
            interpret=_interpret(),
            name=kernel_name,
        )(*prefetched, q, pool)
