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

K/V live in the paged pool ``[num_blocks, block_size, H * D]`` (a
token's heads side by side in one row, a block's tokens in consecutive
rows: the layout the step's scatter writes, `serving/kv_cache.py`), which
stays in HBM as it lies (``memory_space=pl.ANY``); ``block_tables [S,
W]`` / ``context_lens [S]`` and the three descriptors are
scalar-prefetched, and the grid is

    (num_q_blocks, lane windows of a row)

one program a q-block and *lane window*: the ``D`` lanes of one head
where ``D`` is whole 128-lane tiles, else the 128 lanes that ``128 / D``
neighbouring heads share (an HBM window narrower than 128 lanes cannot
be copied), whose heads the one program computes, each on its static
lane slice.  The walk over the sequence's block table runs *inside* the
program (several pages a step, fetched by the kernel's own
double-buffered copies: the form of the Ragged Paged Attention kernel,
PAPERS.md): from the scalars it computes the table slots ``[lo, hi)``
that hold a key one of its rows can see,

    lo = block of its first token's window start (0 with no window)
    hi = min(ceil(context_len / block_size),
             1 + block of the q-block's last token)

and a ``lax.fori_loop`` takes them ``kv_step`` slots a step: the windows
``pool[block_tables[seq, w], :, lane0:lane0 + lanes]`` of a step come by
``pltpu.make_async_copy`` into one half of a VMEM buffer ``[2, kv_step,
block_size, lanes]`` while the other half is computed on.  A slot
outside ``[lo, hi)`` is never read, whatever the table's width: a
program costs what its context costs, and a null segment walks nothing.
The online-softmax state (acc/m/l, VMEM scratch) is carried over the
steps; the score block of a step is ``block_q x (kv_step *
block_size)``, keys in their own order.

``kv_step`` follows from the call's shapes (`_kv_step`): 512 keys a step
for a decode row's 16-row q-block, halved while the score block's float32
temporaries and the buffers pass a fixed VMEM budget (a 1,024-row chunk
q-block takes 256 keys).

Causal masking happens inside each ragged segment: row ``r`` of q-block
``i`` sees KV position ``c`` iff

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
                  heads).  The walk ends at the q-block's last
                  token's block.
    window        row at position ``p`` sees ``c`` only if ``c > p -
                  window``; the walk starts at the block of the
                  q-block's first token's window start, masked inside.

A third input, ``head_ids`` [num_q_blocks], gives each q-block the one
head of the pool it reads (``q`` is then ``[T, 1, D]``): the grouped
decode rows, where a (row, KV head) pair is a sequence with a table of
its own.  A narrow head then picks its slice of the window by its place
in it.

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

__all__ = ["ragged_paged_attention", "ragged_block_plan", "pool_copyable",
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


#: keys one step of the walk brings for the smallest q-block (a decode
#: row's): `_kv_step` turns it into table slots
_STEP_KEYS = 512
#: VMEM that a step's score block (its float32 temporaries) and the K/V
#: buffers beside it may take; a larger q-block gets a narrower step
_STEP_VMEM_BYTES = 6 * 2 ** 20


def _kv_step(block_q, block_size, head_dim, kv_itemsize, table_width):
    """Table slots a step of the walk brings: `_STEP_KEYS` keys, no
    more than the table holds, halved until the ``block_q x keys``
    score block (scores, probabilities and the mask beside them, f32)
    and the buffers (two K and two V buffers in the pool's type, one
    widened copy of each) fit `_STEP_VMEM_BYTES`.  Sixteen decode rows
    take 512 keys a step; a 1,024-row chunk q-block 256."""
    step = max(1, min(_STEP_KEYS // block_size, table_width))

    def vmem(step):
        keys = step * block_size
        return (3 * block_q * keys * 4
                + keys * head_dim * (4 * kv_itemsize + 2 * 4))

    while step > 1 and vmem(step) > _STEP_VMEM_BYTES:
        step //= 2
    return step


#: lanes of a vector register row: the narrowest window of an HBM
#: array that a copy may name
_LANES = 128


def _lane_window(head_dim):
    """Lanes of the window of a pool row that one copy of the walk
    names: the head's own ``head_dim`` where that is whole 128-lane
    tiles, else the 128 lanes that ``128 / head_dim`` neighbouring
    heads share (an HBM window narrower than 128 lanes cannot be
    copied: Mosaic, a slice "must be aligned to tiling (128)")."""
    return head_dim if head_dim % _LANES == 0 else _LANES


def pool_copyable(head_dim, num_kv_heads):
    """Whether the walk's copies lower on the chip for a pool whose rows
    hold ``num_kv_heads`` heads of ``head_dim`` lanes side by side: a
    head is whole 128-lane tiles, or a whole number of heads fills a
    tile and the row is whole tiles."""
    if head_dim % _LANES == 0:
        return True
    return (_LANES % head_dim == 0
            and (num_kv_heads * head_dim) % _LANES == 0)


def _ragged_attn_body(bt_ref, cl_ref, sid_ref, qs_ref, qv_ref, hd_ref,
                      q_ref, k_hbm, v_hbm, ks_hbm, vs_hbm, o_ref,
                      acc_ref, m_ref, l_ref, k_buf, v_buf, ks_buf, vs_buf,
                      sems, *, block_size, block_q, kv_step, scale,
                      window=None, block_tokens=None):
    """One (q-block, lane window) program: the walk over the sequence's
    block table runs inside it.

    Scalar-prefetched ``seq_ids`` route each q-block to its sequence's
    block table; the null segment (``seq_ids == num_seqs``) reads
    ``context_len 0`` from the padded tail of ``cl_ref``, walks nothing
    and emits zeros.

    The pools stay in HBM as they lie, ``[num_blocks, block_size, H *
    D]``.  A step of the walk copies the program's lane window of
    ``kv_step`` consecutive table slots' K and V blocks (``[block_size,
    lanes]`` each) into one half of the double buffers while the other
    half is computed on; only slots in ``[lo, hi)`` (the first block the
    first token's window reaches, the last block that holds a key a row
    can see) are ever copied.  The last step's slots past ``hi`` keep
    what the buffer held, masked like any key past the context; their V
    rows are zeroed first, since ``0 x NaN`` is not 0.

    The program's heads are the ``q_ref.shape[0]`` heads from ``hd_ref[i]``
    (a q-block's own KV head: the grouped decode rows) or from its place
    on the grid's head axis.  Heads narrower than 128 lanes share a
    window: a program of the whole window's heads computes each on its
    static lane slice; a program of one head picks its slice by the
    head's place in the window.

    ``ks_hbm``/``vs_hbm`` are the int8 variant's per-slot dequant scale
    tables (copied by the SAME walk into ``ks_buf``/``vs_buf``) or None
    on the float path; dequant happens on the VMEM-resident tiles
    inside the running-softmax loop — the int8 bytes are all that
    crosses HBM.
    """
    i = pl.program_id(0)
    heads, _, head_dim = q_ref.shape
    lanes = k_buf.shape[-1]
    share = lanes // head_dim             # heads a lane window holds
    head = pl.program_id(1) * heads if hd_ref is None else hd_ref[i]
    lane0 = pl.multiple_of(jax.lax.div(head, share) * lanes, lanes)
    sid = sid_ref[i]
    ctx = cl_ref[sid]
    qs = qs_ref[i]
    qv = qv_ref[i]
    keys = kv_step * block_size
    int8_kv = ks_hbm is not None

    def unpack(buf, scales, b):
        """Buffer half ``b`` as a (keys, lanes) float32 matrix, keys in
        their own order."""
        x = buf[b].astype(jnp.float32).reshape(keys, lanes)
        if scales is not None:            # per-slot dequant
            x = x * scales[b].reshape(keys, _LANES)[:, :1]
        return x

    def head_lanes(x, c):
        """The program's head ``c`` of a window's (keys, lanes)."""
        if share == 1:
            return x
        cut = [x[:, s * head_dim:(s + 1) * head_dim] for s in range(share)]
        if heads == share:                # the window's heads in order
            return cut[c]
        place = jnp.full(cut[0].shape, jax.lax.rem(head, share), jnp.int32)
        out = cut[0]
        for s in range(1, share):
            out = jnp.where(place == s, cut[s], out)
        return out

    # nothing past the context, nothing after the q-block's last token
    last = qs + ((block_tokens or block_q) - 1)
    hi = jnp.minimum(jax.lax.div(ctx + (block_size - 1), block_size),
                     jax.lax.div(last, block_size) + 1)
    lo = 0
    if window is not None:
        # nothing wholly before the first token's window
        lo = jax.lax.div(jnp.maximum(qs - (window - 1), 0), block_size)
    steps = jax.lax.div(jnp.maximum(hi - lo, 0) + (kv_step - 1), kv_step)

    # (table in HBM, its buffer, whether a row holds heads side by side)
    walked = [(k_hbm, k_buf, True), (v_hbm, v_buf, True)]
    if int8_kv:
        walked += [(ks_hbm, ks_buf, False), (vs_hbm, vs_buf, False)]

    def copies(g, j, b):
        """Table slot ``lo + g * kv_step + j`` into row ``j`` of buffer
        half ``b``."""
        blk = bt_ref[sid, lo + g * kv_step + j]
        return [pltpu.make_async_copy(
            src.at[blk, :, pl.ds(lane0, lanes)] if per_head
            else src.at[blk], dst.at[b, j],
            sems.at[n, b]) for n, (src, dst, per_head) in enumerate(walked)]

    def live_slots(g):
        return jnp.minimum(hi - lo - g * kv_step, kv_step)

    def start(g, b):
        def one(j, c):
            for dma in copies(g, j, b):
                dma.start()
            return c
        jax.lax.fori_loop(0, live_slots(g), one, 0)

    def wait(g, b):
        def one(j, c):
            for dma in copies(g, j, b):
                dma.wait()
            return c

        def dead(j, c):
            # an int8 V is finite as it lies; its scale may not be
            zeroed = vs_buf if int8_kv else v_buf
            zeroed[b, j] = jnp.zeros(zeroed.shape[2:], zeroed.dtype)
            return c
        n = live_slots(g)
        jax.lax.fori_loop(0, n, one, 0)
        jax.lax.fori_loop(n, kv_step, dead, 0)

    acc_ref[...] = jnp.zeros_like(acc_ref)
    m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)

    @pl.when(steps > 0)
    def _first():
        start(0, 0)

    def step(g, c):
        b = jax.lax.rem(g, 2)

        @pl.when(g + 1 < steps)
        def _next():
            start(g + 1, 1 - b)

        wait(g, b)
        row = jax.lax.broadcasted_iota(jnp.int32, (block_q, keys), 0)
        col = jax.lax.broadcasted_iota(jnp.int32, (block_q, keys), 1)
        col = col + (lo + g * kv_step) * block_size
        if block_tokens is not None:
            # head groups: row r is token r % block_tokens (a power of
            # two) of the block
            row = row & (block_tokens - 1)
        # causal inside the ragged segment: row r sits at absolute
        # position qs + r and padding rows (r >= qv) see nothing
        mask = (row < qv) & (col <= row + qs) & (col < ctx)
        if window is not None:
            mask &= col > row + (qs - window)
        k_win = unpack(k_buf, ks_buf, b)                # (keys, lanes)
        v_win = unpack(v_buf, vs_buf, b)
        for n in range(heads):
            rows = slice(n * block_q, (n + 1) * block_q)
            q = q_ref[n].astype(jnp.float32)            # (bq, D)
            s = jax.lax.dot_general(
                q, head_lanes(k_win, n), (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale  # (bq, keys)
            s = jnp.where(mask, s, _NEG_INF)
            m_prev = m_ref[rows, :1]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
            alpha = jnp.exp(m_prev - m_new)
            l_ref[rows] = _lanes(alpha * l_ref[rows, :1]
                                 + jnp.sum(p, axis=-1, keepdims=True))
            acc_ref[rows] = acc_ref[rows] * alpha + jax.lax.dot_general(
                p, head_lanes(v_win, n), (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            m_ref[rows] = _lanes(m_new)
        return c

    jax.lax.fori_loop(0, steps, step, 0)

    l = l_ref[:, :1]
    l_safe = jnp.where(l == 0.0, 1.0, l)
    out = acc_ref[...] / l_safe
    # masked/null rows -> zeros.  Broadcast the f32 stat, never the
    # (bq, 1) predicate: Mosaic lowers a bool broadcast_in_dim
    # through an integer select/compare whose width follows the x64
    # mode at LOWERING time (outside _x32) and aborts on i64
    # ("bitwidth_ <= 32"); compare at full shape instead.
    out = jnp.where(jnp.broadcast_to(l, out.shape) > 0.0, out, 0.0)
    o_ref[...] = out.reshape(o_ref.shape).astype(o_ref.dtype)


def _ragged_attn_kernel(*refs, scalars, int8_kv, **kw):
    """The body's references by name: ``scalars`` scalar-prefetched
    arrays (the sixth, a q-block's own head, only for the grouped decode
    rows), then q, the pools, an int8 pool's scale tables, the output,
    the softmax's scratch and the walk's buffers."""
    prefetched = list(refs[:scalars]) + [None] * (6 - scalars)
    rest = list(refs[scalars:])
    q_ref, k_hbm, v_hbm = rest[:3]
    ks_hbm, vs_hbm = rest[3:5] if int8_kv else (None, None)
    rest = rest[5 if int8_kv else 3:]
    o_ref, acc_ref, m_ref, l_ref, k_buf, v_buf = rest[:6]
    ks_buf, vs_buf = rest[6:8] if int8_kv else (None, None)
    _ragged_attn_body(*prefetched, q_ref, k_hbm, v_hbm, ks_hbm, vs_hbm,
                      o_ref, acc_ref, m_ref, l_ref, k_buf, v_buf, ks_buf,
                      vs_buf, rest[-1], **kw)


def _walk_scratch(kv_step, block_size, lanes, kv_dtype, int8_kv):
    """The walk's VMEM: two halves of ``kv_step`` K and V windows (and,
    for an int8 pool, of their scale blocks), as (shape, dtype)."""
    bufs = [((2, kv_step, block_size, lanes), kv_dtype)] * 2
    if int8_kv:
        bufs += [((2, kv_step, block_size, _LANES), jnp.float32)] * 2
    return bufs


@_x32
def ragged_paged_attention(q, k_pool, v_pool, block_tables, context_lens,
                           seq_ids, q_starts, q_valids, block_q=None,
                           scale=None, k_scales=None, v_scales=None,
                           window=None, block_tokens=None, head_ids=None):
    """Mixed prefill+decode attention over the paged KV pool.

    q: [T, H, D] flat block-aligned ragged queries (T % block_q == 0);
    k_pool/v_pool: [num_blocks, block_size, H * D] (a token's heads side
    by side in a row);
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
    both is the program without them.  ``head_ids`` [T // block_q]
    int32 gives each q-block the one head of the pool that it reads
    (``q`` is then [T, 1, D], the pool's heads ``lanes / D``).
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
    num_blocks, block_size, row_lanes = k_pool.shape
    lanes = _lane_window(D)
    if row_lanes % lanes or lanes % D or H != (
            row_lanes // D if head_ids is None else 1):
        raise ValueError(f"q {q.shape} does not match a pool of "
                         f"{row_lanes}-lane rows read in windows of "
                         f"{lanes}")
    S, W = block_tables.shape
    if scale is None:
        scale = 1.0 / (D ** 0.5)
    # a program computes the heads of its lane window, or the one head
    # its q-block names
    heads = lanes // D if head_ids is None else 1
    kv_step = _kv_step(block_q, block_size, lanes,
                       jnp.dtype(k_pool.dtype).itemsize, W)

    qt = jnp.swapaxes(q, 0, 1)                          # [H, T, D]
    # null segment: seq_ids == S indexes the appended zero context, so
    # the walk has no step
    bt = jnp.concatenate(
        [block_tables.astype(jnp.int32),
         jnp.zeros((1, W), jnp.int32)], axis=0)          # [S+1, W]
    cl = jnp.concatenate(
        [context_lens.astype(jnp.int32),
         jnp.zeros((1,), jnp.int32)], axis=0)            # [S+1]
    prefetched = [bt, cl, seq_ids.astype(jnp.int32),
                  q_starts.astype(jnp.int32), q_valids.astype(jnp.int32)]
    if head_ids is not None:
        prefetched.append(head_ids.astype(jnp.int32))

    q_spec = pl.BlockSpec((heads, block_q, D), lambda i, h, *_: (h, i, 0))
    # the pools (and the scale tables) stay where they are: the program
    # copies the windows of the blocks its table names
    hbm_spec = pl.BlockSpec(memory_space=pl.ANY)
    in_specs = [q_spec, hbm_spec, hbm_spec]
    operands = [qt, k_pool, v_pool]
    name = "ragged_attention"
    if int8_kv:
        in_specs += [hbm_spec, hbm_spec]
        # the one lane over a whole row of lanes: the least an HBM
        # window may span, and what it takes in HBM's tiles anyway
        operands += [
            jnp.broadcast_to(t[..., :1].astype(jnp.float32),
                             (num_blocks, block_size, _LANES))
            for t in (k_scales, v_scales)]
        name = "ragged_attention_int8"
    buffers = _walk_scratch(kv_step, block_size, lanes, k_pool.dtype,
                            int8_kv)

    with _kernel_span(name, "fwd") as kernel_name:
        out = pl.pallas_call(
            functools.partial(
                _ragged_attn_kernel, scalars=len(prefetched),
                int8_kv=int8_kv, block_size=block_size, block_q=block_q,
                kv_step=kv_step, scale=float(scale), **options),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=len(prefetched),
                grid=(nqb, H // heads),
                in_specs=in_specs,
                out_specs=q_spec,
                scratch_shapes=softmax_scratch(heads * block_q, D) + [
                    pltpu.VMEM(shape, dtype) for shape, dtype in buffers
                ] + [pltpu.SemaphoreType.DMA((len(buffers), 2))],
            ),
            out_shape=jax.ShapeDtypeStruct((H, T, D), q.dtype),
            interpret=_interpret(),
            name=kernel_name,
        )(*prefetched, *operands)
    return jnp.swapaxes(out, 0, 1)                      # [T, H, D]


def ragged_block_plan(num_heads, head_dim, block_size, num_q_blocks=4,
                      block_q=None, num_blocks=64, table_width=8,
                      dtype=jnp.float32, kv_dtype=None):
    """The ragged mixed-batch attention block plan (see
    `ragged_paged_attention`).  Scalar-prefetch operands (block tables,
    context lens, segment descriptors) live whole in SMEM, have no
    BlockSpec to audit, and are omitted.

    The pools stay in HBM as they lie, ``[num_blocks, block_size, H *
    D]``: their entries give the window one async copy of the walk
    names (a table slot's block, the lanes of one head or of the heads
    that share 128), and ``scratch`` holds the two halves of ``kv_step``
    such windows that the copies fill, after the softmax's accumulators
    (a program computes the heads of its window).

    ``kv_dtype=int8`` exports the int8-pool variant: int8 k/v blocks
    plus the two f32 per-slot scale tables, a whole row of lanes wide;
    q/out stay ``dtype`` (the compute precision).
    """
    dtype = jnp.dtype(dtype)
    f32 = jnp.dtype(jnp.float32)
    kvdt = jnp.dtype(kv_dtype) if kv_dtype is not None else dtype
    int8_kv = kvdt == jnp.dtype(jnp.int8)
    if block_q is None:
        block_q = ragged_q_block(dtype)
    D = head_dim
    T = num_q_blocks * block_q
    lanes = _lane_window(D)
    heads = lanes // D                  # a program's: its window's
    kv_step = _kv_step(block_q, block_size, lanes, kvdt.itemsize,
                       table_width)
    pool = (num_blocks, block_size, num_heads * D)
    operands = [
        ("q", (heads, block_q, D), (num_heads, T, D), dtype),
        ("k_pool", (1, block_size, lanes), pool, kvdt),
        ("v_pool", (1, block_size, lanes), pool, kvdt),
    ]
    if int8_kv:
        scales = (num_blocks, block_size, _LANES)
        operands += [
            ("k_scales", (1, block_size, _LANES), scales, f32),
            ("v_scales", (1, block_size, _LANES), scales, f32),
        ]
    operands.append(("out", (heads, block_q, D), (num_heads, T, D), dtype))
    return {
        "grid": (num_q_blocks, num_heads // heads),
        "block_q": block_q,
        "kv_step": kv_step,
        "kv_dtype": str(kvdt),
        "operands": operands,
        "scratch": (
            ((heads * block_q, D), f32),
            ((heads * block_q, _STAT_LANES), f32),
            ((heads * block_q, _STAT_LANES), f32),
        ) + tuple((shape, jnp.dtype(dt)) for shape, dt in _walk_scratch(
            kv_step, block_size, lanes, kvdt, int8_kv)),
    }
