"""Ragged paged attention: ONE kernel for mixed prefill+decode batches.

The serving engine (inference/serving/engine.py) packs every scheduled
token of a step — one prefill *chunk* plus every decode row — into a
single flat, block-aligned query buffer

    q: [T, H, D]      T = num_q_blocks * block_q

where each sequence owns a run of whole ``block_q``-row q-blocks
(*Ragged Paged Attention*, PAPERS.md / arxiv 2604.15464).  Three
per-q-block scalar arrays describe the ragged layout:

    seq_ids[i]   which sequence q-block ``i`` belongs to
                 (``num_seqs`` = null segment: all rows padding)
    q_starts[i]  absolute KV position of the block's first row,
                 i.e. ``context_len - query_len + i_local * block_q``
    q_valids[i]  valid rows in the block (trailing rows are padding)

K/V live in the PR-5 paged pool ``[num_blocks, H, block_size, D]``;
``block_tables [S, W]`` / ``context_lens [S]`` are scalar-prefetched
(they drive the K/V BlockSpec index maps, so each program streams
exactly the block its sequence owns at table slot ``w``), and the
grid is

    (num_q_blocks, num_heads, W)     w innermost, sequential

so the online-softmax state (acc/m/l) in VMEM scratch survives the
walk over a sequence's KV blocks.  Causal masking happens inside each
ragged segment: row ``r`` of q-block ``i`` sees KV position ``c`` iff

    r < q_valids[i]  and  c <= q_starts[i] + r  and  c < context_len

which makes a decode row (query_len 1, start ``ctx-1``) and a prefill
chunk row fall out of the same predicate.  A fully masked row keeps
``l == 0`` and emits exact zeros — the same any-visible semantics as
the XLA fallback (`serving/attention._ragged_ref`).

Two static options, both ``None`` in the program above (whose lowering
they leave as it was):

    block_tokens  a q-block holds ``block_q / block_tokens`` *head
                  groups* of ``block_tokens`` tokens each: row ``r`` is
                  token ``r % block_tokens``, at position ``q_starts[i]
                  + r % block_tokens``.  The ``H`` of ``q`` and of the
                  pool is then the KV heads, and a q-block's rows are
                  the query heads that share one: a KV block is read
                  once for all of them (a prefill chunk with grouped KV
                  heads; with ``block_tokens = 1``, a decode row's
                  heads).  A KV block wholly after the q-block's last
                  token is skipped.
    window        row at position ``p`` sees ``c`` only if ``c > p -
                  window``; a KV block wholly before the q-block's first
                  token's window is skipped, and the first one read is
                  masked inside.

Gated through ``pallas_gate`` ("ragged_attention" probe);
`ragged_block_plan` exports the exact specs for
`analysis.tiling.audit_ragged_attention` / tpu_lint.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .pallas_tiles import (_NEG_INF, _STAT_LANES, _demote_f64,
                           _interpret, _kernel_span, _lanes, _min_rows,
                           _x32, softmax_scratch)

__all__ = ["ragged_paged_attention", "ragged_block_plan",
           "ragged_q_block", "ragged_segments", "KV_SCALE_LANES"]

#: lane width of the per-slot KV dequant scale tables
#: ``[num_blocks, block_size, KV_SCALE_LANES]`` (f32).  One lane keeps
#: the int8 pool's scale overhead at 4 bytes per slot-layer so the
#: capacity win stays ~2x even at small head_dim; both trailing dims of
#: the (1, block_size, 1) scale block cover the full array, which keeps
#: the spec legal at any lane count.
KV_SCALE_LANES = 1


def ragged_q_block(dtype) -> int:
    """Rows per ragged q-block: the Mosaic minimum sublane count for
    ``dtype`` (8 f32 / 16 bf16), never below the stat-lane width."""
    return max(_STAT_LANES, _min_rows(jnp.dtype(dtype)))


def ragged_segments(query_lens, context_lens, block_q,
                    num_q_blocks=None, num_seqs=None):
    """Host-side ragged layout for a mixed batch (numpy, no tracing).

    Returns ``(seq_ids, q_starts, q_valids, offsets, total_rows)``:
    per-q-block descriptor arrays (padded to ``num_q_blocks`` with the
    ``num_seqs`` null segment when given) plus each sequence's flat row
    offset and the total flat rows used.
    """
    query_lens = [int(x) for x in query_lens]
    context_lens = [int(x) for x in context_lens]
    if num_seqs is None:
        num_seqs = len(query_lens)
    sids, starts, valids, offsets = [], [], [], []
    off = 0
    for s, (ql, cl) in enumerate(zip(query_lens, context_lens)):
        offsets.append(off)
        if ql == 0:
            continue
        if ql > cl:
            raise ValueError(
                f"sequence {s}: query_len {ql} > context_len {cl}")
        base = cl - ql
        nseg = -(-ql // block_q)
        for j in range(nseg):
            sids.append(s)
            starts.append(base + j * block_q)
            valids.append(min(block_q, ql - j * block_q))
        off += nseg * block_q
    if num_q_blocks is not None:
        if len(sids) > num_q_blocks:
            raise ValueError(
                f"{len(sids)} q-blocks exceed budget {num_q_blocks}")
        pad = num_q_blocks - len(sids)
        sids += [num_seqs] * pad
        starts += [0] * pad
        valids += [0] * pad
    return (np.asarray(sids, np.int32), np.asarray(starts, np.int32),
            np.asarray(valids, np.int32),
            np.asarray(offsets, np.int32), off)


def _ragged_attn_body(bt_ref, cl_ref, sid_ref, qs_ref, qv_ref,
                      q_ref, k_ref, v_ref, ks_ref, vs_ref, o_ref,
                      acc_ref, m_ref, l_ref, *, block_size, block_q,
                      scale, w_last, window=None, block_tokens=None):
    """One (q-block, head, table-slot) program over the paged pool.

    Scalar-prefetched ``seq_ids`` route each q-block to its sequence's
    block table; the null segment (``seq_ids == num_seqs``) reads
    ``context_len 0`` from the padded tail of ``cl_ref`` so its guard
    never fires and the emit writes zeros.

    ``ks_ref``/``vs_ref`` are the int8 variant's per-slot dequant scale
    blocks ((1, block_size, KV_SCALE_LANES) f32, walked by the SAME
    block-table index map as k/v) or None on the float path; dequant
    happens on the VMEM-resident tile inside the running-softmax loop —
    the int8 bytes are all that crosses HBM.
    """
    i = pl.program_id(0)
    w = pl.program_id(2)
    sid = sid_ref[i]
    ctx = cl_ref[sid]
    qs = qs_ref[i]
    qv = qv_ref[i]

    @pl.when(w == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    live = w * block_size < ctx
    if block_tokens is not None:
        # nothing after the q-block's last token
        live &= w * block_size <= qs + (block_tokens - 1)
    if window is not None:
        # nothing wholly before the first token's window
        live &= (w + 1) * block_size > qs - (window - 1)

    @pl.when(live)
    def _block():
        q = q_ref[0].astype(jnp.float32)                # (bq, D)
        k = k_ref[0, 0].astype(jnp.float32)             # (bs, D)
        if ks_ref is not None:
            k = k * ks_ref[0, :, :1]                    # per-slot dequant
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # (bq, bs)
        row = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        col = (jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
               + w * block_size)
        if block_tokens is not None:
            # head groups: row r is token r % block_tokens (a power of
            # two) of the block
            row = row & (block_tokens - 1)
        # causal inside the ragged segment: row r sits at absolute
        # position qs + r and padding rows (r >= qv) see nothing
        mask = (row < qv) & (col <= row + qs) & (col < ctx)
        if window is not None:
            mask &= col > row + (qs - window)
        s = jnp.where(mask, s, _NEG_INF)
        m_prev = m_ref[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = _lanes(alpha * l_ref[:, :1]
                            + jnp.sum(p, axis=-1, keepdims=True))
        v = v_ref[0, 0].astype(jnp.float32)             # (bs, D)
        if vs_ref is not None:
            v = v * vs_ref[0, :, :1]                    # per-slot dequant
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = _lanes(m_new)

    @pl.when(w == w_last)
    def _emit():
        l = l_ref[:, :1]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        out = acc_ref[...] / l_safe
        # masked/null rows -> zeros.  Broadcast the f32 stat, never the
        # (bq, 1) predicate: Mosaic lowers a bool broadcast_in_dim
        # through an integer select/compare whose width follows the x64
        # mode at LOWERING time (outside _x32) and aborts on i64
        # ("bitwidth_ <= 32"); compare at full shape instead.
        out = jnp.where(jnp.broadcast_to(l, out.shape) > 0.0, out, 0.0)
        o_ref[...] = out[None].astype(o_ref.dtype)


def _ragged_attn_kernel(bt_ref, cl_ref, sid_ref, qs_ref, qv_ref,
                        q_ref, k_ref, v_ref, o_ref,
                        acc_ref, m_ref, l_ref, **kw):
    _ragged_attn_body(bt_ref, cl_ref, sid_ref, qs_ref, qv_ref,
                      q_ref, k_ref, v_ref, None, None, o_ref,
                      acc_ref, m_ref, l_ref, **kw)


def _ragged_attn_int8_kernel(bt_ref, cl_ref, sid_ref, qs_ref, qv_ref,
                             q_ref, k_ref, v_ref, ks_ref, vs_ref, o_ref,
                             acc_ref, m_ref, l_ref, **kw):
    _ragged_attn_body(bt_ref, cl_ref, sid_ref, qs_ref, qv_ref,
                      q_ref, k_ref, v_ref, ks_ref, vs_ref, o_ref,
                      acc_ref, m_ref, l_ref, **kw)


@_x32
def ragged_paged_attention(q, k_pool, v_pool, block_tables, context_lens,
                           seq_ids, q_starts, q_valids, block_q=None,
                           scale=None, k_scales=None, v_scales=None,
                           window=None, block_tokens=None):
    """Mixed prefill+decode attention over the paged KV pool.

    q: [T, H, D] flat block-aligned ragged queries (T % block_q == 0);
    k_pool/v_pool: [num_blocks, H, block_size, D];
    block_tables: [S, W] int32; context_lens: [S] int32;
    seq_ids/q_starts/q_valids: [T // block_q] int32 (see module doc;
    ``seq_ids == S`` marks a null/pad q-block).  Returns [T, H, D].

    Int8 pools additionally take ``k_scales``/``v_scales``
    ``[num_blocks, block_size, KV_SCALE_LANES]`` f32 per-slot dequant
    tables (kv_cache.py maintains them through every block lifecycle
    edge); the kernel walks them with the block tables and dequantizes
    in VMEM.

    ``window`` and ``block_tokens`` (static; see the module doc) bound
    what a row sees and lay head groups into a q-block; ``None`` for
    both is the program without them.
    """
    q, k_pool, v_pool = _demote_f64(q, k_pool, v_pool)
    int8_kv = jnp.dtype(k_pool.dtype) == jnp.dtype(jnp.int8)
    if int8_kv and (k_scales is None or v_scales is None):
        raise ValueError("int8 KV pools need k_scales/v_scales tables")
    T, H, D = q.shape
    if block_q is None:
        block_q = ragged_q_block(q.dtype)
    block_q = int(block_q)
    if block_tokens is not None and (
            block_tokens & (block_tokens - 1) or block_q % block_tokens):
        raise ValueError(f"block_tokens {block_tokens} is no power of two "
                         f"that divides block_q {block_q}")
    options = {}
    if window is not None:
        options["window"] = int(window)
    if block_tokens is not None:
        options["block_tokens"] = int(block_tokens)
    if T % block_q:
        raise ValueError(f"flat query rows {T} not a multiple of "
                         f"block_q {block_q}")
    nqb = T // block_q
    if seq_ids.shape[0] != nqb:
        raise ValueError(f"{seq_ids.shape[0]} segment descriptors for "
                         f"{nqb} q-blocks")
    num_blocks, _, block_size, _ = k_pool.shape
    S, W = block_tables.shape
    if scale is None:
        scale = 1.0 / (D ** 0.5)

    qt = jnp.swapaxes(q, 0, 1)                          # [H, T, D]
    # null segment: seq_ids == S indexes the appended zero row / zero
    # context so the kernel's guard skips every KV block
    bt = jnp.concatenate(
        [block_tables.astype(jnp.int32),
         jnp.zeros((1, W), jnp.int32)], axis=0)          # [S+1, W]
    cl = jnp.concatenate(
        [context_lens.astype(jnp.int32),
         jnp.zeros((1,), jnp.int32)], axis=0)            # [S+1]
    sid = seq_ids.astype(jnp.int32)
    qs = q_starts.astype(jnp.int32)
    qv = q_valids.astype(jnp.int32)

    q_spec = pl.BlockSpec(
        (1, block_q, D),
        lambda i, h, w, bt, cl, sid, qs, qv: (h, i, 0))
    pool_spec = pl.BlockSpec(
        (1, 1, block_size, D),
        lambda i, h, w, bt, cl, sid, qs, qv: (bt[sid[i], w], h, 0, 0))
    in_specs = [q_spec, pool_spec, pool_spec]
    operands = [qt, k_pool, v_pool]
    kernel = _ragged_attn_kernel
    name = "ragged_attention"
    if int8_kv:
        # the scale blocks ride the same block-table walk as k/v; both
        # trailing dims cover the full scale array so the spec is legal
        scale_spec = pl.BlockSpec(
            (1, block_size, KV_SCALE_LANES),
            lambda i, h, w, bt, cl, sid, qs, qv: (bt[sid[i], w], 0, 0))
        in_specs += [scale_spec, scale_spec]
        operands += [k_scales.astype(jnp.float32),
                     v_scales.astype(jnp.float32)]
        kernel = _ragged_attn_int8_kernel
        name = "ragged_attention_int8"

    with _kernel_span(name, "fwd") as kernel_name:
        out = pl.pallas_call(
            functools.partial(
                kernel, block_size=block_size,
                block_q=block_q, scale=float(scale), w_last=W - 1,
                **options),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=5,
                grid=(nqb, H, W),
                in_specs=in_specs,
                out_specs=pl.BlockSpec(
                    (1, block_q, D),
                    lambda i, h, w, bt, cl, sid, qs, qv: (h, i, 0)),
                scratch_shapes=softmax_scratch(block_q, D),
            ),
            out_shape=jax.ShapeDtypeStruct((H, T, D), q.dtype),
            interpret=_interpret(),
            name=kernel_name,
        )(bt, cl, sid, qs, qv, *operands)
    return jnp.swapaxes(out, 0, 1)                      # [T, H, D]


def ragged_block_plan(num_heads, head_dim, block_size, num_q_blocks=4,
                      block_q=None, num_blocks=64, table_width=8,
                      dtype=jnp.float32, kv_dtype=None):
    """The ragged mixed-batch attention block plan (see
    `ragged_paged_attention`).  Scalar-prefetch operands (block tables,
    context lens, segment descriptors) live whole in SMEM, have no
    BlockSpec to audit, and are omitted.

    ``kv_dtype=int8`` exports the int8-pool variant: int8 k/v blocks
    plus the two (1, block_size, KV_SCALE_LANES) f32 per-slot scale
    operands; q/out stay ``dtype`` (the compute precision).
    """
    dtype = jnp.dtype(dtype)
    f32 = jnp.dtype(jnp.float32)
    kvdt = jnp.dtype(kv_dtype) if kv_dtype is not None else dtype
    if block_q is None:
        block_q = ragged_q_block(dtype)
    D = head_dim
    T = num_q_blocks * block_q
    pool = (num_blocks, num_heads, block_size, D)
    operands = [
        ("q", (1, block_q, D), (num_heads, T, D), dtype),
        ("k_pool", (1, 1, block_size, D), pool, kvdt),
        ("v_pool", (1, 1, block_size, D), pool, kvdt),
    ]
    if kvdt == jnp.dtype(jnp.int8):
        scales = (num_blocks, block_size, KV_SCALE_LANES)
        operands += [
            ("k_scales", (1, block_size, KV_SCALE_LANES), scales, f32),
            ("v_scales", (1, block_size, KV_SCALE_LANES), scales, f32),
        ]
    operands.append(("out", (1, block_q, D), (num_heads, T, D), dtype))
    return {
        "grid": (num_q_blocks, num_heads, table_width),
        "block_q": block_q,
        "kv_dtype": str(kvdt),
        "operands": operands,
        "scratch": (
            ((block_q, D), f32),
            ((block_q, _STAT_LANES), f32),
            ((block_q, _STAT_LANES), f32),
        ),
    }
