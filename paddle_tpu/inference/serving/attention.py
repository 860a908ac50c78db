"""The engine's attention over a paged K/V pool: scatter, ragged
attention, and the cache view the model sees.

Device ops, all pure-jnp impls routed through ``core.dispatch`` so they
work identically in eager mode, under ``jit.to_static`` replay, and in
the engine's compiled step function:

  ``kv_cache_scatter``         write this step's freshly projected K/V
  ``kv_cache_scatter_quant``   into the block pool ``[num_blocks,
                               block_size, H * D]`` at ``slot_mapping``:
                               a token is one row of the pool, so the
                               functional ``.at[slots].set`` is a row
                               scatter over the pool as it lies — the
                               engine's to_static step donates the pool,
                               and the compiled update is in place at 1x
                               memory with no copy of a pool before or
                               after it; the int8 form quantizes per
                               token and writes the per-slot scale
                               tables beside it
  ``ragged_attention``         every scheduled token of a step — one
                               prefill chunk and the decode rows — in
                               one flat buffer of block-aligned
                               segments, each attending through its
                               sequence's block table.  On TPU the
                               Pallas kernel (``ops/pallas_ragged``)
                               runs behind the ``pallas_gate`` probe;
                               everywhere else (and whenever the gate
                               declines) ``_ragged_ref`` below executes
                               the IDENTICAL semantics, so tier-1 CPU
                               tests exercise the same math the TPU
                               serves.  Both read the pool in the
                               layout the scatter wrote.

``_ragged_ref`` replicates ``_sdpa_ref``'s numerics op-for-op (f32 score
einsum, -1e30 mask, f32 softmax, ``any_visible`` zeroing, f32 output
einsum) so greedy decoding through the pool is token-for-token identical
to the dense-cache path.

``RaggedCacheView`` adapts a PagedKVCache to the model's ``cache``
argument (``models/gpt.py`` detects it by its ``attend`` /
``position_ids`` attributes) and owns the per-step driving Tensors.  It
hands each layer one of four layer caches, chosen from the cache's
``layer_specs``: ``RaggedLayerCache`` (paged K/V, scatter then ragged
attention; with grouped KV heads or a ``window`` in its spec, the decode
rows and the chunk apart, over its group's table), ``LatentLayerCache``
(latent attention: one row a token for all heads, scatter then the
absorbed form through ``ops/pallas_latent``, the decode rows and the
chunk apart), ``SparseLayerCache`` (paged K/V with grouped heads plus the
selector's pooled keys in a state slot) and ``RecurrentLayerCache`` (no
K/V, the layer's named states in the request's slot).  ``kv_blocks_gather`` /
``kv_blocks_scatter`` move whole pool blocks to and from the host
(tiering, disaggregation).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from ...core.dispatch import dispatch
from ...core.tensor import Tensor
from ...observability import block

__all__ = ["kv_cache_scatter", "kv_cache_scatter_quant",
           "ragged_attention", "RaggedCacheView",
           "grouped_chunk_attention",
           "RaggedLayerCache", "LatentLayerCache", "SparseLayerCache",
           "RecurrentLayerCache", "latent_chunk_tokens",
           "grouped_decode_attention", "kv_blocks_gather",
           "kv_blocks_scatter"]

_NEG_INF = -1e30


# ---------------------------------------------------------------------
# whole-block DMA: pool blocks <-> host bytes (tiering / disaggregation)
# ---------------------------------------------------------------------
def kv_blocks_gather(cache, blocks):
    """Dispatch device gathers of whole pool blocks across all layers
    of a PagedKVCache: ``(k, v, k_scales, v_scales)`` lists (per layer)
    of ``[nb, bs, H * D]`` / ``[nb, bs, lanes]`` device arrays (a block
    as it lies in the pool), in ``blocks`` order.  The gathers are async
    — the caller decides when (and whether) to sync them to host, so
    spills/exports overlap with compute.  Scale tables ride along for int8 pools (None otherwise):
    block bytes without their dequant scales are garbage."""
    import numpy as np
    idx = jnp.asarray(np.asarray(blocks, np.int32))
    k = [kp._value[idx] for kp, _ in cache._pools]
    v = [vp._value[idx] for _, vp in cache._pools]
    ks = [s._value[idx] for s, _ in cache._scales] or None
    vs = [s._value[idx] for _, s in cache._scales] or None
    return k, v, ks, vs


def kv_blocks_scatter(cache, blocks, k_parts, v_parts, ks_parts=None,
                      vs_parts=None):
    """Device-put host block bytes into pool blocks (promotion /
    import): per-layer ``[nb, bs, H * D]`` host arrays land in
    ``blocks`` via one ``.at[idx].set`` per layer per side, through
    ``_inplace_update`` so compiled step functions see the new
    buffers.  Returns the updated pool values for pipeline-window
    admission."""
    import numpy as np
    idx = jnp.asarray(np.asarray(blocks, np.int32))
    puts = []
    for i, (kp, vp) in enumerate(cache._pools):
        kp._inplace_update(
            kp._value.at[idx].set(jnp.asarray(k_parts[i])))
        vp._inplace_update(
            vp._value.at[idx].set(jnp.asarray(v_parts[i])))
        puts.extend((kp._value, vp._value))
    for i, (ksp, vsp) in enumerate(cache._scales):
        ksp._inplace_update(
            ksp._value.at[idx].set(jnp.asarray(ks_parts[i])))
        vsp._inplace_update(
            vsp._value.at[idx].set(jnp.asarray(vs_parts[i])))
        puts.extend((ksp._value, vsp._value))
    return puts


# ---------------------------------------------------------------------
# scatter: new K/V -> pool slots
# ---------------------------------------------------------------------
def _kv_scatter_impl(k_pool, v_pool, k_new, v_new, slots):
    """k_pool/v_pool: [nb, bs, H * D]; k_new/v_new: [B, S, H, D];
    slots: [B*S] int32 flat pool slots (pad tokens -> slot 0, the pad
    block — duplicate pad writes race benignly, block 0 is never read
    unmasked).  A token's heads are one row of the pool, so the write is
    a row scatter over the pool as it lies: in place on a donated pool,
    with no copy into a layout of the scatter's own."""
    with block("kv_write"):
        return (_scatter_rows(k_pool, k_new, slots),
                _scatter_rows(v_pool, v_new, slots))


def _scatter_rows(pool, new, slots):
    """Rows ``new`` [..., H, D] (or [T, lanes]) into flat slots of
    ``pool`` [nb, bs, lanes]."""
    nb, bs, lanes = pool.shape
    return pool.reshape(nb * bs, lanes).at[slots].set(
        new.reshape(-1, lanes).astype(pool.dtype)).reshape(pool.shape)


def kv_cache_scatter(k_pool, v_pool, k_new, v_new, slot_mapping):
    """Returns the updated (k_pool, v_pool) Tensors."""
    return dispatch("kv_cache_scatter", _kv_scatter_impl,
                    (k_pool, v_pool, k_new, v_new, slot_mapping), {},
                    differentiable=False)


def _quantize_tokens(flat, lanes):
    """Per-token symmetric int8 quantization: one amax over each
    token's (H, D) slice.  Deterministic pure function of the token's
    values, so a failover replay that re-scatters the same K/V
    reproduces the pool AND the scale tables bit-identically.  Returns
    (int8 [T, H, D], scales [T, lanes] f32)."""
    f = flat.astype(jnp.float32)
    amax = jnp.max(jnp.abs(f), axis=(1, 2))            # [T]
    scale = jnp.where(amax > 0.0, amax / 127.0, 1.0)
    q = jnp.clip(jnp.round(f / scale[:, None, None]), -127.0, 127.0)
    return (q.astype(jnp.int8),
            jnp.broadcast_to(scale[:, None], (scale.shape[0], lanes)))


def _kv_scatter_quant_impl(k_pool, v_pool, k_scales, v_scales,
                           k_new, v_new, slots):
    """Int8 variant of `_kv_scatter_impl`: quantize each new token
    independently and write its dequant scale into the per-slot tables
    ``[nb, bs, lanes]`` next to the int8 block data.  A block filling
    up over many decode steps never re-scales already-written slots."""
    H, D = k_new.shape[-2:]
    lanes = k_scales.shape[-1]
    with block("kv_write"):
        qk, sk = _quantize_tokens(k_new.reshape(-1, H, D), lanes)
        qv, sv = _quantize_tokens(v_new.reshape(-1, H, D), lanes)
        return (_scatter_rows(k_pool, qk, slots),
                _scatter_rows(v_pool, qv, slots),
                _scatter_rows(k_scales, sk, slots),
                _scatter_rows(v_scales, sv, slots))


def kv_cache_scatter_quant(k_pool, v_pool, k_scales, v_scales,
                           k_new, v_new, slot_mapping):
    """Returns updated (k_pool, v_pool, k_scales, v_scales) Tensors."""
    return dispatch("kv_cache_scatter_quant", _kv_scatter_quant_impl,
                    (k_pool, v_pool, k_scales, v_scales, k_new, v_new,
                     slot_mapping), {},
                    differentiable=False)


# ---------------------------------------------------------------------
# ragged mixed prefill+decode attention (one flat token buffer)
# ---------------------------------------------------------------------
def _ragged_ref(q, k_pool, v_pool, block_tables, context_lens, seq_ids,
                q_starts, q_valids, block_q, scale,
                k_scales=None, v_scales=None, window=None,
                block_tokens=None, head_ids=None):
    """Pure-XLA segment-gather fallback for `ragged_paged_attention`
    (``window``, ``block_tokens`` and ``head_ids`` as there), over the
    same pool ``[nb, bs, H * D]``.

    q: [T, H, D] flat block-aligned ragged queries (see
    ops/pallas_ragged.py for the seq_ids/q_starts/q_valids layout;
    ``seq_ids == S`` is the null segment).  Mirrors `_sdpa_ref`'s
    numerics op-for-op (f32 score einsum, -1e30 mask, f32 softmax,
    any_visible zeroing, f32 output einsum) with per-segment causal
    masking; a fully masked row emits exact zeros.

    Int8 pools pass ``k_scales``/``v_scales`` ``[nb, bs, lanes]``: the
    gathered tiles are dequantized to f32 BEFORE the score/output
    matmuls — the same pre-dot op order as the kernel's VMEM dequant,
    so the two paths agree bitwise.
    """
    T, H, D = q.shape
    nb, bs, lanes = k_pool.shape
    S, W = block_tables.shape
    nqb = T // block_q
    # null-segment row: zero table (pad block) + zero context
    bt = jnp.concatenate([block_tables.astype(jnp.int32),
                          jnp.zeros((1, W), jnp.int32)], axis=0)
    cl = jnp.concatenate([context_lens.astype(jnp.int32),
                          jnp.zeros((1,), jnp.int32)], axis=0)
    sid = seq_ids.astype(jnp.int32)
    bt_q = bt[sid]                                 # [nqb, W]

    def gather(pool, scales):
        """The q-blocks' keys, [nqb, H, W * bs, D]."""
        if head_ids is None:
            x = pool[bt_q].reshape(nqb, W * bs, H, D)
        else:                      # a q-block's own head of the pool
            x = pool.reshape(nb, bs, lanes // D, D)[
                bt_q, :, head_ids.astype(jnp.int32)[:, None]]
            x = x.reshape(nqb, W * bs, 1, D)
        if scales is not None:
            # per-slot dequant, broadcast over heads and lanes; mirrors
            # the kernel's dequant of a window before the dots
            x = x.astype(jnp.float32) * scales[bt_q][..., :1].reshape(
                nqb, W * bs, 1, 1)
        return jnp.swapaxes(x, 1, 2)

    k, v = gather(k_pool, k_scales), gather(v_pool, v_scales)
    qt = jnp.swapaxes(q.reshape(nqb, block_q, H, D), 1, 2)
    scores = jnp.einsum("nhqd,nhkd->nhqk", qt, k,
                        preferred_element_type=jnp.float32) * scale
    row = jnp.arange(block_q, dtype=jnp.int32)
    if block_tokens is not None:
        row = row % block_tokens       # head groups of block_tokens rows
    col = jnp.arange(W * bs, dtype=jnp.int32)
    pos = q_starts.astype(jnp.int32)[:, None] + row[None, :]
    visible = ((row[None, :, None] < q_valids.astype(jnp.int32)
                [:, None, None])
               & (col[None, None, :] <= pos[:, :, None])
               & (col[None, None, :] < cl[sid][:, None, None]))
    if window is not None:
        visible &= col[None, None, :] > pos[:, :, None] - window
    scores = jnp.where(visible[:, None, :, :], scores,
                       jnp.asarray(_NEG_INF, scores.dtype))
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    any_visible = jnp.any(scores > -1e29, axis=-1, keepdims=True)
    probs = jnp.where(any_visible, probs, jnp.zeros((), probs.dtype))
    out = jnp.einsum("nhqk,nhkd->nhqd", probs, v,
                     preferred_element_type=jnp.float32).astype(q.dtype)
    return jnp.swapaxes(out, 1, 2).reshape(T, H, D)


def _ragged_attention_impl(q, k_pool, v_pool, block_tables,
                           context_lens, seq_ids, q_starts, q_valids,
                           *scales, block_q, scale, use_pallas,
                           window=None, block_tokens=None, head_ids=None):
    ks, vs = scales if scales else (None, None)
    if use_pallas:
        from ...ops.pallas_ragged import ragged_paged_attention as _krn
        out = _krn(q[0], k_pool, v_pool, block_tables, context_lens,
                   seq_ids, q_starts, q_valids, block_q=block_q,
                   scale=scale, k_scales=ks, v_scales=vs, window=window,
                   block_tokens=block_tokens, head_ids=head_ids)
    else:
        out = _ragged_ref(q[0], k_pool, v_pool, block_tables,
                          context_lens, seq_ids, q_starts, q_valids,
                          block_q, scale, k_scales=ks, v_scales=vs,
                          window=window, block_tokens=block_tokens,
                          head_ids=head_ids)
    return out[None]


def _use_pallas_ragged(head_dim, kv_heads, block_size, dtype, block_q,
                       q_dtype=None):
    jd = jnp.dtype(dtype)
    int8_kv = jd == jnp.dtype(jnp.int8)
    if not int8_kv and jd not in (jnp.dtype(jnp.float32),
                                  jnp.dtype(jnp.bfloat16)):
        return False
    if head_dim > 256 or block_size % 8 != 0:
        return False
    from ...ops.pallas_ragged import pool_copyable
    if not pool_copyable(head_dim, kv_heads):
        return False
    from ...ops.pallas_kernels import _min_rows
    # block_q tiles the QUERY buffer, whose dtype is the compute
    # precision — an int8 pool does not force 32-row q blocks
    if block_q % _min_rows(jnp.dtype(q_dtype) if q_dtype is not None
                           else jd):
        return False
    from ...ops.pallas_gate import pallas_enabled
    return pallas_enabled("ragged_attention_int8" if int8_kv
                          else "ragged_attention")


def ragged_attention(q, k_pool, v_pool, block_tables, context_lens,
                     seq_ids, q_starts, q_valids, block_q, scale=None,
                     k_scales=None, v_scales=None):
    """Mixed prefill+decode attention for q [1, T, H, D] over paged
    K/V, where T packs every scheduled token of a serving step into
    block-aligned ragged segments (ops/pallas_ragged.py).  Int8 pools
    pass their per-slot dequant tables as ``k_scales``/``v_scales``."""
    head_dim = q.shape[-1]
    if scale is None:
        scale = 1.0 / math.sqrt(head_dim)
    kv = k_pool._value if isinstance(k_pool, Tensor) else k_pool
    qv_ = q._value if isinstance(q, Tensor) else q
    use_pallas = _use_pallas_ragged(head_dim, qv_.shape[2], kv.shape[1],
                                    kv.dtype, int(block_q), qv_.dtype)
    args = (q, k_pool, v_pool, block_tables, context_lens,
            seq_ids, q_starts, q_valids)
    if k_scales is not None:
        args += (k_scales, v_scales)
    # chunk and decode rows in one call: the blocks call it "decode"
    with block("attention/decode"):
        return dispatch("ragged_paged_attention", _ragged_attention_impl,
                        args,
                        dict(block_q=int(block_q), scale=float(scale),
                             use_pallas=use_pallas),
                        differentiable=False)


# ---------------------------------------------------------------------
# the model-facing cache adapter
# ---------------------------------------------------------------------
class _LayerCache:
    """What every kind of layer cache gives a model's layer beside its
    own state: which rows of the step carry a token, and a way to hand
    the engine what the layer counted."""

    __slots__ = ("_view", "_layer")

    def __init__(self, view, layer):
        self._view = view
        self._layer = layer

    def carried_rows(self):
        """``[T]`` bool: the rows of the flat buffer that carry a token
        (a decode row's first, a chunk's valid ones)."""
        view = self._view
        return dispatch("ragged_carried_rows", _carried_rows_impl,
                        (view.q_valids,),
                        dict(block_q=view.block_q), differentiable=False)

    def report(self, name, value):
        """Hand the engine a small per-step array (the expert layers'
        plan counters): it leaves the step's program with the sampled
        tokens and is read where they are."""
        self._view.reports.setdefault(name, []).append(value)


class RaggedLayerCache(_LayerCache):
    """One layer's view of the ragged mixed-batch step."""

    __slots__ = ("_window", "_grouped")

    def __init__(self, view, layer):
        super().__init__(view, layer)
        spec = view.cache.layer_specs[layer]
        self._window = spec.get("window")
        #: query heads that share KV heads, or a window: the decode rows
        #: and the chunk go through the kernel apart (`_attend_grouped`)
        self._grouped = layer_is_grouped(spec)

    @property
    def lora(self):
        """The multi-LoRA segment state (serving.lora), or None."""
        return self._view.lora

    def _attend_grouped(self, q, k, v):
        """Grouped KV heads, or a window: scatter, then the decode rows
        through `grouped_decode_attention` and the chunk through the
        kernel's head-group form, each over this layer's group's table
        (a windowed group's holds what the rows still hold, from their
        context base)."""
        view = self._view
        cache = view.cache
        k_pool, v_pool = cache.layer_pools(self._layer)
        slots, tables, base = view.group_inputs(
            cache.layer_group(self._layer))
        kv, qv_ = k_pool._value, q._value
        kv_heads, head_dim = cache.num_heads, cache.head_dim
        group = qv_.shape[2] // kv_heads
        dec_rows = decode_block_q(group, qv_.dtype)
        chunk_bq = view.chunk_block_q
        bs = cache.block_size
        dec_width = tables.shape[1] if self._window is None else min(
            tables.shape[1], self._window // bs + 2)
        out, new_k, new_v = dispatch(
            "grouped_paged_attention", _grouped_attend_impl,
            (q, k, v, k_pool, v_pool, slots, tables, base,
             view.dec_index, view.row_pos, view.chunk_meta),
            dict(window=self._window, chunk_rows=view.chunk_rows,
                 chunk_bq=chunk_bq, dec_width=int(dec_width),
                 dec_rows=dec_rows,
                 pallas_rows=_use_pallas_ragged(
                     head_dim, kv_heads, bs, kv.dtype, dec_rows,
                     qv_.dtype),
                 pallas_chunk=_use_pallas_ragged(
                     head_dim, kv_heads, bs, kv.dtype, group * chunk_bq,
                     qv_.dtype)),
            differentiable=False)
        k_pool._inplace_update(new_k._value)
        v_pool._inplace_update(new_v._value)
        return out

    def attend(self, q, k, v, use_flash=True):
        """Scatter this step's K/V into the pool, then run ragged
        attention over every segment — prefill chunks and decode rows
        share one kernel call.  q/k/v: [1, T, H, D] Tensors.  Int8
        pools quantize per token at scatter time and thread the
        per-slot scale tables into the attention call."""
        if self._grouped:
            return self._attend_grouped(q, k, v)
        view = self._view
        k_pool, v_pool = view.cache.layer_pools(self._layer)
        scales = view.cache.layer_scales(self._layer)
        if scales is not None:
            ks_t, vs_t = scales
            new_k, new_v, new_ks, new_vs = kv_cache_scatter_quant(
                k_pool, v_pool, ks_t, vs_t, k, v, view.slot_mapping)
            k_pool._inplace_update(new_k._value)
            v_pool._inplace_update(new_v._value)
            ks_t._inplace_update(new_ks._value)
            vs_t._inplace_update(new_vs._value)
            return ragged_attention(q, new_k, new_v, view.block_tables,
                                    view.context_lens, view.seq_ids,
                                    view.q_starts, view.q_valids,
                                    view.block_q, k_scales=new_ks,
                                    v_scales=new_vs)
        new_k, new_v = kv_cache_scatter(k_pool, v_pool, k, v,
                                        view.slot_mapping)
        k_pool._inplace_update(new_k._value)
        v_pool._inplace_update(new_v._value)
        return ragged_attention(q, new_k, new_v, view.block_tables,
                                view.context_lens, view.seq_ids,
                                view.q_starts, view.q_valids,
                                view.block_q)


# ---------------------------------------------------------------------
# latent attention: one row a token for every head
# ---------------------------------------------------------------------
#: rows of a chunk's q-block in the latent kernel: its heads times
#: `latent_chunk_tokens` tokens
_LATENT_CHUNK_ROWS = 1024


def latent_chunk_tokens(heads, chunk_rows):
    """Tokens of a chunk's q-block in the latent kernel: all ``heads``
    over the most tokens, a power of two that divides ``chunk_rows``,
    that keep the q-block at `_LATENT_CHUNK_ROWS` rows (16 at 64
    heads)."""
    tokens = 1
    while (2 * tokens * heads <= _LATENT_CHUNK_ROWS
           and chunk_rows % (2 * tokens) == 0):
        tokens *= 2
    return tokens


def _latent_ref(q, pool, block_tables, context_lens, seq_ids, q_starts,
                q_valids, *, block_q, block_tokens, value_lanes, scale):
    """Pure-XLA gather form of `pallas_latent.latent_attention`, the
    same arguments and semantics: each q-block's table gathered whole,
    float32 scores, ``-1e30`` mask, float32 softmax, a row that sees
    nothing emits zeros."""
    T, width = q.shape
    nb, bs, _ = pool.shape
    S, W = block_tables.shape
    nqb = T // block_q
    bt = jnp.concatenate([block_tables.astype(jnp.int32),
                          jnp.zeros((1, W), jnp.int32)], axis=0)
    cl = jnp.concatenate([context_lens.astype(jnp.int32),
                          jnp.zeros((1,), jnp.int32)], axis=0)
    sid = seq_ids.astype(jnp.int32)
    rows = pool[bt[sid]].reshape(nqb, W * bs, width)
    scores = jnp.einsum("nqc,nkc->nqk", q.reshape(nqb, block_q, width),
                        rows, preferred_element_type=jnp.float32) * scale
    token = jnp.arange(block_q, dtype=jnp.int32) % block_tokens
    col = jnp.arange(W * bs, dtype=jnp.int32)
    pos = q_starts.astype(jnp.int32)[:, None] + token[None, :]
    visible = ((token[None, :, None]
                < q_valids.astype(jnp.int32)[:, None, None])
               & (col[None, None, :] <= pos[:, :, None])
               & (col[None, None, :] < cl[sid][:, None, None]))
    scores = jnp.where(visible, scores, jnp.asarray(_NEG_INF, scores.dtype))
    probs = jax.nn.softmax(scores, axis=-1)
    probs = jnp.where(jnp.any(visible, axis=-1, keepdims=True), probs, 0.0)
    out = jnp.einsum("nqk,nkc->nqc", probs.astype(pool.dtype),
                     rows[..., :value_lanes],
                     preferred_element_type=jnp.float32)
    return out.reshape(T, value_lanes).astype(q.dtype)


def _latent_call(use_pallas, *args, **kw):
    if use_pallas:
        from ...ops.pallas_latent import latent_attention
        return latent_attention(*args, **kw)
    return _latent_ref(*args, **kw)


def _latent_attend_impl(q, rows, pool, slots, tables, dec_index, row_pos,
                        meta, *, chunk_rows, chunk_tokens, value_lanes,
                        scale, use_pallas):
    """One latent layer of the ragged step.  ``q`` [1, T, H, width] (the
    absorbed queries), ``rows`` [1, T, width] (this step's latent rows).
    Scatter the rows; the decode rows read their table through the
    latent kernel, a row's heads as the rows of one q-block; the chunk
    (skipped when the step carries none) in q-blocks of all its heads
    over ``chunk_tokens`` tokens.  Returns ``([1, T, H, value_lanes],
    pool)``.  The pool's rows are whole 128-lane tiles: rows and queries
    are padded with zero lanes to its width."""
    lanes = pool.shape[-1]

    def padded(x):
        return jnp.pad(x, [(0, 0)] * (x.ndim - 1)
                       + [(0, lanes - x.shape[-1])])
    with block("kv_write"):
        pool = _scatter_rows(pool, padded(rows), slots)
    q0 = padded(q[0])
    T, H, width = q0.shape
    S = tables.shape[0]
    tables = tables.astype(jnp.int32)
    call = functools.partial(_latent_call, use_pallas, value_lanes=value_lanes,
                             scale=scale)
    with block("attention/decode"):
        dec_rows = decode_block_q(H, q0.dtype)
        qd = jnp.pad(q0[jnp.minimum(dec_index, T - 1)],
                     ((0, 0), (0, dec_rows - H), (0, 0)))
        ctx = jnp.where(row_pos >= 0, row_pos + 1, 0).astype(jnp.int32)
        dec_out = call(
            qd.reshape(S * dec_rows, width), pool, tables, ctx,
            jnp.where(ctx > 0, jnp.arange(S, dtype=jnp.int32), S),
            jnp.maximum(ctx - 1, 0), jnp.ones((S,), jnp.int32),
            block_q=dec_rows, block_tokens=1)
        dec_out = dec_out.reshape(S, dec_rows, value_lanes)[:, :H]

    def chunk(_):
        with block("attention/chunk"):
            ct = chunk_tokens
            nqb = chunk_rows // ct
            # [C, H, width] -> rows ordered (q-block, head, token)
            qc = _chunk_rows(q0, meta, chunk_rows).reshape(
                nqb, ct, H, width).transpose(0, 2, 1, 3)
            first = jnp.arange(nqb, dtype=jnp.int32) * ct
            valids = jnp.clip(meta[1] - first, 0, ct).astype(jnp.int32)
            out = call(
                qc.reshape(nqb * H * ct, width), pool,
                tables[jnp.minimum(meta[4], S - 1)][None],
                jnp.reshape(meta[5] + meta[1], (1,)),
                jnp.where(valids > 0, 0, 1).astype(jnp.int32),
                (meta[5] + first).astype(jnp.int32), valids,
                block_q=H * ct, block_tokens=ct)
            return out.reshape(nqb, H, ct, value_lanes) \
                .transpose(0, 2, 1, 3).reshape(chunk_rows, H, value_lanes)

    chunk_out = jax.lax.cond(
        meta[1] > 0, chunk,
        lambda _: jnp.zeros((chunk_rows, H, value_lanes), q.dtype), None)
    like = jnp.zeros((T, H, value_lanes), q.dtype)
    return _merge_rows(like, chunk_out, dec_out, meta, dec_index)[None], pool


class LatentLayerCache(_LayerCache):
    """A latent attention layer: one row a token for all its heads in
    the block pool, read in the absorbed form."""

    __slots__ = ()

    def attend(self, q, rows, scale):
        """``q`` [1, T, H, width] absorbed queries, ``rows`` [1, T,
        width] this step's latent rows -> [1, T, H, value_lanes]; the
        layer's pool is updated in place."""
        from ...ops.pallas_gate import pallas_enabled
        view = self._view
        cache = view.cache
        (pool,) = cache.layer_pools(self._layer)
        spec = cache.layer_specs[self._layer]
        out, new_pool = dispatch(
            "latent_paged_attention", _latent_attend_impl,
            (q, rows, pool, view.slot_mapping, view.block_tables,
             view.dec_index, view.row_pos, view.chunk_meta),
            dict(chunk_rows=view.chunk_rows,
                 chunk_tokens=view.latent_chunk_tokens,
                 value_lanes=int(spec["value_lanes"]), scale=float(scale),
                 use_pallas=pool._value.dtype in (jnp.bfloat16, jnp.float32)
                 and pallas_enabled("latent_attention")),
            differentiable=False)
        pool._inplace_update(new_pool._value)
        return out


# ---------------------------------------------------------------------
# layers that keep per-request state: block-sparse and recurrent
# ---------------------------------------------------------------------
def layer_is_grouped(spec):
    """Whether a paged layer's spec asks for the grouped path: a window,
    or more query heads than KV heads."""
    return bool(spec.get("window")) or (
        int(spec.get("query_heads") or spec["num_kv_heads"])
        != int(spec["num_kv_heads"]))


def decode_block_q(group, dtype):
    """Rows of a decode row's q-block when its ``group`` query heads go
    as rows: the group, in whole sublane tiles of ``dtype``."""
    from ...ops.pallas_tiles import _min_rows, _round_up
    return _round_up(group, _min_rows(jnp.dtype(dtype)))


def grouped_decode_attention(q, k_pool, v_pool, sel_tables, sel_ctx,
                             use_pallas, window=None, block_q=None):
    """Decode rows with grouped KV heads over a block table of their
    own, through the ragged kernel: ``q`` [S, H, D] (one token a row,
    heads grouped by KV head), pools [nb, bs, Hkv * D], ``sel_tables``
    [S, Hkv, W], ``sel_ctx`` [S, Hkv] (0: an idle row).

    The kernel learns grouped heads by layout: a (row, KV head) pair is
    a sequence of its own, with its own table, whose q-block holds the
    group's ``G`` query heads as rows, all at the same position
    (``q_starts = ctx - 1`` lets every row see the whole context), and
    the q-block names the KV head whose lanes of the pool it reads
    (``head_ids``).  Sixteen MXU rows a token where one head a row
    gives one.

    With ``block_q`` (at least ``G``: `decode_block_q`) the q-block is
    padded to that many rows and the kernel is told that its rows are
    one token (``block_tokens = 1``), which a ``window`` needs: the
    rows then share the position that the window is counted from."""
    S, H, D = q.shape
    kv_heads = sel_tables.shape[1]
    G, n = H // kv_heads, S * kv_heads
    tables = sel_tables.reshape(n, -1)
    ctx = sel_ctx.reshape(n).astype(jnp.int32)
    seq = jnp.where(ctx > 0, jnp.arange(n, dtype=jnp.int32), n)
    if block_q is None:
        if window is not None:
            raise ValueError("a window needs the one-token q-block form "
                             "(block_q)")
        rows, valid, options = G, G, {}
        qg = q.reshape(1, n * G, 1, D)
    else:
        rows, valid = int(block_q), 1
        options = dict(window=window, block_tokens=1)
        qg = jnp.pad(q.reshape(n, G, D), ((0, 0), (0, rows - G), (0, 0))) \
            .reshape(1, n * rows, 1, D)
    out = _ragged_attention_impl(
        qg, k_pool, v_pool, tables, ctx, seq,
        jnp.maximum(ctx - 1, 0), jnp.full((n,), valid, jnp.int32),
        block_q=rows, scale=1.0 / math.sqrt(D), use_pallas=use_pallas,
        head_ids=jnp.tile(jnp.arange(kv_heads, dtype=jnp.int32), S),
        **options)
    return out.reshape(n, rows, D)[:, :G].reshape(S, H, D)


def grouped_chunk_attention(q, k_pool, v_pool, table, context, start,
                            valid, *, window, chunk_bq, use_pallas):
    """A prefill chunk with grouped KV heads through the ragged kernel's
    head-group form: ``q`` [C, H, D] at positions ``start ...`` (``valid``
    of them real) of one sequence with ``table`` [W] and ``context``
    tokens.  A q-block is ``chunk_bq`` tokens by the ``G`` query heads
    of one KV head, so the kernel's head axis is the KV heads and a
    K/V block is read once for the ``G`` heads that share it."""
    C, H, D = q.shape
    kv_heads = k_pool.shape[2] // D
    G, nqb = H // kv_heads, C // chunk_bq
    # [C, H, D] -> rows ordered (q-block, head of the group, token)
    qg = q.reshape(nqb, chunk_bq, kv_heads, G, D).transpose(0, 3, 1, 2, 4) \
        .reshape(1, nqb * G * chunk_bq, kv_heads, D)
    first = jnp.arange(nqb, dtype=jnp.int32) * chunk_bq
    valids = jnp.clip(valid - first, 0, chunk_bq).astype(jnp.int32)
    out = _ragged_attention_impl(
        qg, k_pool, v_pool, table[None], jnp.reshape(context, (1,)),
        jnp.where(valids > 0, 0, 1).astype(jnp.int32),   # 1: null segment
        (start + first).astype(jnp.int32), valids,
        block_q=G * chunk_bq, scale=1.0 / math.sqrt(D),
        use_pallas=use_pallas, window=window, block_tokens=chunk_bq)
    return out[0].reshape(nqb, G, chunk_bq, kv_heads, D) \
        .transpose(0, 2, 3, 1, 4).reshape(C, H, D)


def _stack_impl(*xs):
    return jnp.stack(xs)


def _carried_rows_impl(q_valids, *, block_q):
    row = jnp.arange(q_valids.shape[0] * block_q, dtype=jnp.int32)
    return (row % block_q) < jnp.repeat(q_valids, block_q)


def _grouped_attend_impl(q, k, v, k_pool, v_pool, slots, tables, base,
                         dec_index, row_pos, meta, *, window, chunk_rows,
                         chunk_bq, dec_width, dec_rows, pallas_rows,
                         pallas_chunk):
    """One layer with grouped KV heads (and perhaps a window) of the
    ragged step.  Scatter K/V; the decode rows read their group's table
    through the kernel, ``dec_width`` entries of it (all that a
    windowed row still holds) in q-blocks of ``dec_rows`` rows; the chunk goes through the kernel's
    head-group form (skipped when the step carries none).  ``base`` [S]
    is each row's context base: positions in a windowed group's table
    count from it."""
    k_pool, v_pool = _kv_scatter_impl(k_pool, v_pool, k, v, slots)
    q0 = q[0]
    T, H, D = q0.shape
    S, kv_heads = tables.shape[0], k_pool.shape[2] // D
    tables = tables.astype(jnp.int32)
    with block("attention/decode"):
        qd = q0[jnp.minimum(dec_index, T - 1)]               # [S, H, D]
        ctx = jnp.where(row_pos >= 0, row_pos + 1 - base, 0)
        dec_out = grouped_decode_attention(
            qd, k_pool, v_pool,
            jnp.broadcast_to(tables[:, None, :dec_width],
                             (S, kv_heads, dec_width)),
            jnp.broadcast_to(ctx[:, None], (S, kv_heads)), pallas_rows,
            window=window, block_q=dec_rows)

    def chunk(_):
        with block("attention/chunk"):
            row = jnp.minimum(meta[4], S - 1)
            start = meta[5] - base[row]
            return grouped_chunk_attention(
                _chunk_rows(q0, meta, chunk_rows), k_pool, v_pool,
                tables[row], start + meta[1], start, meta[1],
                window=window, chunk_bq=chunk_bq, use_pallas=pallas_chunk)

    chunk_out = jax.lax.cond(
        meta[1] > 0, chunk,
        lambda _: jnp.zeros((chunk_rows, H, D), q.dtype), None)
    out = _merge_rows(q0, chunk_out, dec_out, meta, dec_index)
    return out[None], k_pool, v_pool


def _chunk_rows(x, meta, chunk_rows):
    """The step's prefill chunk: ``chunk_rows`` rows of ``x`` [T, ...]
    from the chunk's flat offset."""
    return jax.lax.dynamic_slice_in_dim(x, meta[0], chunk_rows, 0)


def _merge_rows(like, chunk_out, dec_out, meta, dec_index):
    """Chunk rows and decode rows back into the flat buffer (rows that
    carry nothing stay zero; idle decode rows are dropped)."""
    out = jax.lax.dynamic_update_slice_in_dim(
        jnp.zeros_like(like), chunk_out.astype(like.dtype), meta[0], 0)
    return out.at[dec_index].set(dec_out.astype(like.dtype), mode="drop")


def _sparse_attend_impl(q, k, v, k_pool, v_pool, ck_pool, slots, tables,
                        dec_index, row_slots, row_pos, meta, ck_seq, ck_j,
                        ck_slot, *, sizes, chunk_rows, sel_width,
                        pallas_attn, pallas_select):
    """One block-sparse layer of the ragged step.  Scatter K/V, pool the
    keys this step completed, then: decode rows score their slot's
    pooled keys, select, and read the selected blocks through the ragged
    kernel; the chunk's tokens each select for themselves and run the
    masked composite (skipped when the step carries no chunk)."""
    from ...ops import pallas_sparse as pls
    k_pool, v_pool = _kv_scatter_impl(k_pool, v_pool, k, v, slots)
    S, W = tables.shape
    tables_ext = jnp.concatenate(
        [tables.astype(jnp.int32), jnp.zeros((1, W), jnp.int32)], axis=0)
    with block("kv_write"):              # the pooled keys' writes
        ck_pool = pls.compress_keys(k_pool, ck_pool, tables_ext, ck_seq,
                                    ck_j, ck_slot, sizes)
    q0 = q[0]
    T, H, D = q0.shape
    kv_heads, bs = k_pool.shape[2] // D, k_pool.shape[1]
    with block("attention/decode"):
        qd = q0[jnp.minimum(dec_index, T - 1)]               # [S, H, D]
        scores = pls.sparse_select_scores(qd, ck_pool, row_slots, row_pos,
                                          sizes, use_pallas=pallas_select)
        sel_tables, sel_ctx = pls.selected_tables(scores, row_pos, tables,
                                                  sizes, sel_width)
        dec_out = grouped_decode_attention(qd, k_pool, v_pool, sel_tables,
                                           sel_ctx, pallas_attn)

    def chunk(_):
        with block("attention/chunk"):
            qc = _chunk_rows(q0, meta, chunk_rows)
            r = jnp.arange(chunk_rows, dtype=jnp.int32)
            t = jnp.where(r < meta[1], meta[5] + r, -1)
            table = tables_ext[meta[4]]
            gather = lambda pool: pool[table].reshape(    # noqa: E731
                W * bs, kv_heads, D)
            out = pls.sparse_block_attention(
                qc.reshape(chunk_rows, kv_heads, H // kv_heads, D), t,
                gather(k_pool), gather(v_pool), ck_pool[meta[2]], sizes)
            return out.reshape(chunk_rows, H, D)

    chunk_out = jax.lax.cond(
        meta[1] > 0, chunk,
        lambda _: jnp.zeros((chunk_rows, H, D), q.dtype), None)
    out = _merge_rows(q0, chunk_out, dec_out, meta, dec_index)
    return out[None], k_pool, v_pool, ck_pool


def _lightning_update_impl(q, k, v, pool, dec_index, row_slots, meta, *,
                           slopes, chunk_rows, use_pallas):
    """One lightning layer of the ragged step: the one-step form for
    the decode rows, the chunked form for the prefill chunk (``meta``:
    flat offset, valid rows, state slot, first-chunk flag), each
    against its request's slot of ``pool``, in place."""
    from ...ops import pallas_lightning as pll
    q0, k0, v0 = q[0], k[0], v[0]
    T = q0.shape[0]
    idx = jnp.minimum(dec_index, T - 1)
    sl = lambda x: _chunk_rows(x, meta, chunk_rows)       # noqa: E731
    if use_pallas:
        dec_out, pool = pll.lightning_attention_step(
            q0[idx], k0[idx], v0[idx], pool, row_slots, slopes)
        chunk_out, pool = pll.lightning_attention_fwd(
            sl(q0), sl(k0), sl(v0), pool, meta[2], meta[1], meta[3],
            slopes)
    else:
        dec_out, pool = pll.lightning_step_ref(
            q0[idx], k0[idx], v0[idx], pool, row_slots, slopes)
        start = jnp.where(meta[3] > 0, 0.0, 1.0) * pool[meta[2]]
        chunk_out, state = pll.lightning_chunk_ref(
            sl(q0), sl(k0), sl(v0), start, slopes, meta[1])
        pool = pool.at[meta[2]].set(state.astype(pool.dtype))
    return _merge_rows(q0, chunk_out, dec_out, meta, dec_index)[None], pool


def _gated_delta_update_impl(x, g, beta, conv_w, pool, conv_pool,
                             dec_index, row_slots, meta, *, key_heads,
                             value_heads, key_dim, value_dim, chunk_rows,
                             use_pallas):
    """One gated delta rule layer of the ragged step.  ``x`` [1, T,
    channels] is ``[q; k; v]`` before the convolution, ``g`` and
    ``beta`` [1, T, Hv] float32.  The decode rows and the prefill chunk
    (``meta``: flat offset, valid rows, state slot, first-chunk flag)
    each convolve after their request's last inputs (``conv_pool``) and
    run their form of the rule against its state (``pool``), both in
    place; a first chunk starts from zeros in both."""
    from ...ops import pallas_gated_delta as pgd
    x0, g0, b0 = x[0], g[0], beta[0]
    T, width = x0.shape[0], conv_w.shape[0]
    idx = jnp.minimum(dec_index, T - 1)
    sl = lambda a: _chunk_rows(a, meta, chunk_rows)       # noqa: E731
    heads = lambda y: pgd.split_heads(                    # noqa: E731
        y, key_heads, value_heads, key_dim, value_dim)
    # decode rows: one token after the slot's last inputs
    yd, window = jax.vmap(
        lambda prev, row: pgd.causal_conv(row[None], prev, conv_w))(
        conv_pool[row_slots], x0[idx])
    yd = yd[:, 0]
    conv_pool = conv_pool.at[row_slots].set(
        window[:, 1:].astype(conv_pool.dtype))
    # the chunk: after the slot's last inputs, or after nothing
    prev = jnp.where(meta[3] > 0, 0, 1).astype(conv_pool.dtype) \
        * conv_pool[meta[2]]
    yc, padded = pgd.causal_conv(sl(x0), prev, conv_w)
    conv_pool = conv_pool.at[meta[2]].set(jax.lax.dynamic_slice_in_dim(
        padded, meta[1], width - 1, 0).astype(conv_pool.dtype))
    if use_pallas:
        dec_out, pool = pgd.gated_delta_rule_step_fwd(
            *heads(yd), g0[idx], b0[idx], pool, row_slots)
        chunk_out, pool = pgd.gated_delta_rule_fwd(
            *heads(yc), sl(g0), sl(b0), pool, meta[2], meta[1], meta[3])
    else:
        dec_out, pool = pgd.gated_delta_step_ref(
            *heads(yd), g0[idx], b0[idx], pool, row_slots)
        start = jnp.where(meta[3] > 0, 0.0, 1.0) * pool[meta[2]]
        chunk_out, state = pgd.gated_delta_chunk_ref(
            *heads(yc), sl(g0), sl(b0), start, meta[1])
        pool = pool.at[meta[2]].set(state.astype(pool.dtype))
    like = jnp.zeros((T, value_heads, value_dim), x.dtype)
    out = _merge_rows(like, chunk_out, dec_out, meta, dec_index)
    return out[None], pool, conv_pool


class _StatefulLayerCache(_LayerCache):
    """One layer's view of the ragged step, for a layer that keeps
    per-request state in a slot pool."""

    __slots__ = ()


class SparseLayerCache(_StatefulLayerCache):
    """A block-sparse layer: paged K/V with grouped KV heads, and the
    selector's pooled keys in the request's state slot."""

    __slots__ = ()

    @staticmethod
    def stage(view, sizes, layers, row_slots, row_pos, meta):
        """What the sparse layers read beside the rows and the chunk:
        the pooled keys that this step's tokens complete, as (batch
        row, key index, slot) to a fixed width (a key a decode row and
        a chunk's share; unused entries name the null sequence and the
        pad slot).  Returns the step's selected and visible blocks
        (summed over decode rows, the ``layers`` sparse layers and KV
        heads) and its dense rows: host arithmetic, since how many
        blocks a row reads follows from its position, not its scores."""
        from ...ops.pallas_sparse import selected_count
        S = len(row_pos)

        def completed(start, n):
            # key j is whole once token stride * j + kernel - 1 is in
            t = np.arange(start, start + n) - (sizes.kernel - 1)
            return t[(t >= 0) & (t % sizes.stride == 0)] // sizes.stride

        keys = [(r, j, row_slots[r]) for r in np.flatnonzero(row_pos >= 0)
                for j in completed(row_pos[r], 1)]
        if meta[1]:
            keys += [(meta[4], j, meta[2])
                     for j in completed(meta[5], meta[1])]
        ck = np.zeros((3, S + -(-view.chunk_rows // sizes.stride) + 1),
                      np.int32)
        ck[0] = S                                # the null sequence
        if keys:
            ck[:, :len(keys)] = np.asarray(keys, np.int32).T
        for name, value in zip(("ck_seq", "ck_j", "ck_slot"), ck):
            setattr(view, name, view._stage(name, getattr(view, name),
                                            value, jnp.int32))
        contexts = row_pos[row_pos >= 0] + 1
        counts = [selected_count(c, sizes) for c in contexts]
        per_row = layers * view.cache.num_heads
        return {"sparse_blocks_selected": per_row * sum(c[0] for c in counts),
                "sparse_blocks_visible": per_row * sum(c[1] for c in counts),
                "sparse_dense_rows": int((contexts <= sizes.dense_len).sum())}

    def attend(self, q, k, v, sizes):
        """q [1, T, H, D], k/v [1, T, Hkv, D] -> [1, T, H, D]."""
        from ...ops.pallas_gate import pallas_enabled
        view = self._view
        cache = view.cache
        k_pool, v_pool = cache.layer_pools(self._layer)
        ck_pool = cache.layer_compressed(self._layer)
        kv, qv_ = k_pool._value, q._value
        group = qv_.shape[2] // cache.num_heads
        out, new_k, new_v, new_ck = dispatch(
            "sparse_paged_attention", _sparse_attend_impl,
            (q, k, v, k_pool, v_pool, ck_pool, view.slot_mapping,
             view.block_tables, view.dec_index, view.row_slots,
             view.row_pos, view.chunk_meta, view.ck_seq, view.ck_j,
             view.ck_slot),
            dict(sizes=sizes, chunk_rows=view.chunk_rows,
                 sel_width=sizes.table_width(
                     cache.table_width * cache.block_size),
                 pallas_attn=_use_pallas_ragged(
                     cache.head_dim, cache.num_heads, cache.block_size,
                     kv.dtype, group, qv_.dtype),
                 pallas_select=pallas_enabled("sparse_select")),
            differentiable=False)
        k_pool._inplace_update(new_k._value)
        v_pool._inplace_update(new_v._value)
        ck_pool._inplace_update(new_ck._value)
        return out


class RecurrentLayerCache(_StatefulLayerCache):
    """A layer with a recurrence: no K/V, its named states in the
    request's slot.  `update` is the lightning layer's form (one decayed
    state a head), `delta_update` the gated delta rule's (a state a
    value head and the convolution's last inputs)."""

    __slots__ = ()

    def update(self, q, k, v, slopes):
        """q/k/v [1, T, H, D] -> [1, T, H, D]; the state pool is
        updated in place."""
        from ...ops.pallas_gate import pallas_enabled
        view = self._view
        pool = view.cache.layer_state(self._layer, "state")
        out, new_pool = dispatch(
            "lightning_state_update", _lightning_update_impl,
            (q, k, v, pool, view.dec_index, view.row_slots,
             view.chunk_meta),
            dict(slopes=tuple(slopes), chunk_rows=view.chunk_rows,
                 use_pallas=pallas_enabled("lightning_attention")),
            differentiable=False)
        pool._inplace_update(new_pool._value)
        return out

    def delta_update(self, x, g, beta, conv_weight, *, key_heads,
                     value_heads, key_dim, value_dim):
        """``x`` [1, T, channels] (``[q; k; v]`` before the
        convolution), ``g`` and ``beta`` [1, T, Hv] float32 -> [1, T,
        Hv, Dv]; the layer's ``delta`` and ``conv`` pools are updated in
        place."""
        from ...ops import pallas_gated_delta as pgd
        from ...ops.pallas_gate import pallas_enabled
        view = self._view
        pool = view.cache.layer_state(self._layer, "delta")
        conv_pool = view.cache.layer_state(self._layer, "conv")
        out, new_pool, new_conv = dispatch(
            "gated_delta_state_update", _gated_delta_update_impl,
            (x, g, beta, conv_weight, pool, conv_pool, view.dec_index,
             view.row_slots, view.chunk_meta),
            dict(key_heads=key_heads, value_heads=value_heads,
                 key_dim=key_dim, value_dim=value_dim,
                 chunk_rows=view.chunk_rows,
                 use_pallas=pgd.kernel_shapes_ok(
                     view.chunk_rows, key_dim, value_dim, key_heads,
                     value_heads) and pallas_enabled("gated_delta_rule")),
            differentiable=False)
        pool._inplace_update(new_pool._value)
        conv_pool._inplace_update(new_conv._value)
        return out


class RaggedCacheView:
    """Adapts PagedKVCache to the model for the unified ragged step.

    The view owns the per-step driving Tensors whose VALUES the engine
    swaps before every compiled call — under to_static they are
    discovered as read-only state and re-read at each dispatch, so the
    single compiled executable serves every step.  Beside the pool's
    slots, tables and positions they carry the per-q-block segment
    descriptors and the sampling indices the engine's in-graph sampler
    reads (``last_index`` into the flat token dim, ``sample_pos``
    absolute positions for schedule-invariant keys).  Both sampling
    arrays are ``[S, C]``: C sampling *columns* per row — C = 1 for
    plain decode, C = k + 1 under speculative decoding, where column j
    samples the target token following draft j (serving/speculative.py).
    """

    mode = "ragged"

    def __init__(self, cache, block_q, chunk_rows=None):
        self.cache = cache
        self.block_q = int(block_q)
        #: rows the step's prefill chunk takes in the flat buffer (the
        #: layers that treat the chunk apart from the decode rows)
        self.chunk_rows = chunk_rows
        self.slot_mapping = None   # [T] int32 flat pool slots
        self.block_tables = None   # [S, W] int32
        self.context_lens = None   # [S] int32
        self.position_ids = None   # [1, T] int64 absolute positions
        self.seq_ids = None        # [T // block_q] int32 (S = null)
        self.q_starts = None       # [T // block_q] int32
        self.q_valids = None       # [T // block_q] int32
        self.last_index = None     # [S, C] int32 flat sampling indices
        self.sample_pos = None     # [S, C] int64 absolute sampling pos
        self.lora = None           # SegmentAdapterState when multi-LoRA on
        # per-request state (models with sparse or recurrent layers)
        self.dec_index = None      # [S] int32 flat row of a decode token
        self.row_slots = None      # [S] int32 state slot (0 = pad)
        self.row_pos = None        # [S] int32 decode position (-1 idle)
        self.chunk_meta = None     # [6] int32 offset, rows, slot, first,
        #                            batch row (S = none), start position
        self.ck_seq = None         # [N] int32 pooled keys to write: row,
        self.ck_j = None           # [N] int32 index,
        self.ck_slot = None        # [N] int32 and slot (0 = none)
        #: what the layers hand the engine beside the tokens (`report`)
        self.reports = {}
        #: a windowed group's slots, table and context base a row,
        #: staged beside the full group's (`set_group_inputs`)
        self._group_inputs = {}
        #: layers whose decode rows and chunk go through the kernel
        #: apart (grouped K/V heads, a window, latent rows); they read
        #: the per-row arrays of `stage_state`
        self.grouped = cache.latent or any(
            spec["kind"] == "paged_kv" and layer_is_grouped(spec)
            for spec in cache.layer_specs)
        #: tokens of a q-block of the chunk's head-group form
        self.chunk_block_q = math.gcd(int(chunk_rows or block_q), 128)
        #: tokens of a chunk's q-block in the latent kernel
        self.latent_chunk_tokens = latent_chunk_tokens(
            max([int(s["query_heads"]) for s in cache.layer_specs
                 if s["kind"] == "latent"] or [1]),
            int(chunk_rows or block_q))
        self._zero_base = None
        sparse = [spec["sparse_sizes"] for spec in cache.layer_specs
                  if spec.get("sparse_sizes")]
        if len(set(sparse)) > 1:
            raise ValueError("the sparse layers of a model share one "
                             f"selector's sizes; got {sorted(set(sparse))}")
        #: the selector's sizes and how many layers select with them
        self._sparse = (sparse[0], len(sparse)) if sparse else None
        self._layers = [
            RecurrentLayerCache(self, i) if spec["kind"] == "recurrent"
            else LatentLayerCache(self, i) if spec["kind"] == "latent"
            else SparseLayerCache(self, i) if spec.get("sparse_sizes")
            else RaggedLayerCache(self, i)
            for i, spec in enumerate(cache.layer_specs)]

    def set_lora(self, state):
        """Attach the multi-LoRA segment state (serving.lora); model
        layers reach it through their layer cache as ``cache.lora``."""
        self.lora = state

    def __getitem__(self, layer):
        return self._layers[layer]

    def __len__(self):
        return len(self._layers)

    def set_inputs(self, slot_mapping, block_tables, context_lens,
                   position_ids, seq_ids, q_starts, q_valids,
                   last_index, sample_pos):
        """Stage this step's driving arrays (shapes fixed for the
        lifetime of the engine — ONE compiled executable)."""
        self.slot_mapping = self._stage(
            "slot_mapping", self.slot_mapping, slot_mapping, jnp.int32)
        self.block_tables = self._stage(
            "block_tables", self.block_tables, block_tables, jnp.int32)
        self.context_lens = self._stage(
            "context_lens", self.context_lens, context_lens, jnp.int32)
        self.position_ids = self._stage(
            "position_ids", self.position_ids, position_ids, jnp.int64)
        self.seq_ids = self._stage(
            "seq_ids", self.seq_ids, seq_ids, jnp.int32)
        self.q_starts = self._stage(
            "q_starts", self.q_starts, q_starts, jnp.int32)
        self.q_valids = self._stage(
            "q_valids", self.q_valids, q_valids, jnp.int32)
        self.last_index = self._stage(
            "last_index", self.last_index, last_index, jnp.int32)
        self.sample_pos = self._stage(
            "sample_pos", self.sample_pos, sample_pos, jnp.int64)

    def set_group_inputs(self, group, slot_mapping, block_tables, base):
        """Stage a windowed group's slots [T], tables [S, W] (what each
        row still holds) and context bases [S] (tokens before a row's
        first held block)."""
        staged = self._group_inputs.setdefault(group.window, [None] * 3)
        names = ("slot_mapping", "block_tables", "context_base")
        for n, (name, value) in enumerate(zip(
                names, (slot_mapping, block_tables, base))):
            staged[n] = self._stage(f"window{group.window}.{name}",
                                    staged[n], value, jnp.int32)

    def group_inputs(self, group):
        """``(slot_mapping, block_tables, context_base)`` of a layer's
        group (None: the full group, whose base is zero)."""
        if group is not None:
            return tuple(self._group_inputs[group.window])
        if self._zero_base is None:
            self._zero_base = self._stage(
                "context_base", None,
                np.zeros(self.block_tables.shape[0], np.int32), jnp.int32)
        return self.slot_mapping, self.block_tables, self._zero_base

    def take_reports(self):
        """What the layers reported while the step was traced, stacked
        a name: ``{name: [layers, ...]}``."""
        taken, self.reports = self.reports, {}
        return {name: dispatch("stack_reports", _stack_impl, tuple(values),
                               {}, differentiable=False)
                for name, values in taken.items()}

    def stage_state(self, dec_index, row_slots, row_pos, chunk_meta):
        """Stage what the layers with per-request state read: each
        decode row's flat index, state slot and position (-1: idle),
        and the chunk's meta.  The sparse layers stage the rest of
        their inputs from these; returns what they counted."""
        for name, value in (("dec_index", dec_index),
                            ("row_slots", row_slots), ("row_pos", row_pos),
                            ("chunk_meta", chunk_meta)):
            setattr(self, name, self._stage(name, getattr(self, name),
                                            value, jnp.int32))
        if self._sparse is None:
            return {}
        return SparseLayerCache.stage(self, *self._sparse, row_slots,
                                      row_pos, chunk_meta)

    def _stage(self, name, tensor, value, dtype):
        val = jnp.asarray(value, dtype)
        if tensor is None:
            tensor = Tensor(val, _internal=True, stop_gradient=True)
            tensor.name = f"kv_cache.{self.mode}.{name}"
            return tensor
        tensor._value = val
        return tensor
