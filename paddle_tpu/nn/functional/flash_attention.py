"""Attention functionals: scaled_dot_product_attention / flash_attention.

Reference parity: `python/paddle/nn/functional/flash_attention.py` wrapping
`third_party/flashattn` via `phi/kernels/gpu/flash_attn_kernel.cu`
[UNVERIFIED — empty reference mount].

TPU-native: the hot path is the Pallas flash-attention kernel in
paddle_tpu/ops/pallas_kernels.py (online softmax, MXU-tiled q/k blocks,
hand-written flash backward via jax.custom_vjp).  On non-TPU backends
(tests run on XLA-CPU) the XLA composite below is used — the Pallas kernel
itself is validated on CPU in interpret mode by tests/test_pallas_kernels.
Layout convention matches Paddle: [batch, seqlen, num_heads, head_dim].
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ...core.dispatch import dispatch

__all__ = ["scaled_dot_product_attention", "flash_attention",
           "flash_attn_unpadded", "sdp_kernel"]


def _sdpa_ref(q, k, v, bias, causal, scale, dropout_p=0.0, key=None,
              keep=None):
    """XLA-composite attention: [B, S, H, D] layout, f32 softmax.
    Dropout draws its [B, H, Sq, Sk] mask from `key`, or takes `keep`
    as given (the flash kernels' mask written out, for parity checks)."""
    B, Sq, H, D = q.shape
    Sk = k.shape[1]
    qt = jnp.swapaxes(q, 1, 2)  # B H S D
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)
    scores = jnp.einsum("bhqd,bhkd->bhqk", qt, kt,
                        preferred_element_type=jnp.float32) * scale
    if bias is not None:
        scores = scores + bias.astype(scores.dtype)
    if causal:
        mask = jnp.tril(jnp.ones((Sq, Sk), bool), k=Sk - Sq)
        scores = jnp.where(mask, scores, jnp.asarray(-1e30, scores.dtype))
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    if bias is not None or causal:
        # fully-masked rows: softmax returns uniform 1/Sk — zero them so
        # rows with no visible keys output 0 (matches the Pallas kernel
        # and prevents cross-sequence leakage in the varlen path)
        any_visible = jnp.any(scores > -1e29, axis=-1, keepdims=True)
        probs = jnp.where(any_visible, probs, jnp.zeros((), probs.dtype))
    if dropout_p > 0.0 and (key is not None or keep is not None):
        if keep is None:
            keep = jax.random.bernoulli(key, 1.0 - dropout_p, probs.shape)
        probs = jnp.where(keep, probs / (1.0 - dropout_p),
                          jnp.zeros((), probs.dtype))
    out = jnp.einsum("bhqk,bhkd->bhqd", probs, vt,
                     preferred_element_type=jnp.float32).astype(q.dtype)
    return jnp.swapaxes(out, 1, 2)


def _flash_refusal(head_dim, seqlen_k, dtype, dropout=False):
    """Why the Mosaic kernel cannot take a call of these shapes
    ("dtype", "vmem", "gate"), or None if it can: TPU backend,
    MXU-friendly head_dim, and a K/V working set that fits VMEM.

    head_dim does not need to be a multiple of 128 — the kernel keeps D as
    the lane dim and Mosaic pads to 128 lanes, so 64/96/128/256 all work
    (the old `head_dim % 128 == 0` gate excluded nearly every real model).
    The kernel currently stages the full K and V for one (batch, head) in
    VMEM; cap that at ~8MB so long sequences fall back to the XLA
    composite instead of failing Mosaic compilation (ring attention is
    the long-context path).
    """
    # cheap static checks first; the probe compile (pallas_enabled) last
    from ...core.dtypes import to_jax_dtype
    jd = jnp.dtype(to_jax_dtype(dtype))
    if jd not in (jnp.dtype(jnp.float32), jnp.dtype(jnp.bfloat16)):
        return "dtype"
    d_pad = max(head_dim, 128)  # Mosaic pads lanes to 128
    kv_bytes = 2 * seqlen_k * d_pad * jd.itemsize
    if head_dim > 256 or kv_bytes > 8 * 1024 * 1024:
        return "vmem"
    from ...ops.pallas_gate import pallas_enabled
    if not pallas_enabled("flash_attention_dropout" if dropout
                          else "flash_attention"):
        return "gate"
    return None


def _attention_path(query, key, attn_mask, dropout):
    """Which implementation this call builds, counted once a build in
    `attention.path.<flash|flash_dropout|composite.<reason>>`: the
    kernel takes every call it can, with dropout or without; a mask,
    `sdp_kernel(enable_flash=False)` or `_flash_refusal` leave the XLA
    composite.  Returns whether the kernel runs."""
    if attn_mask is not None:
        reason = "mask"
    elif not _flash_allowed():
        reason = "sdp_kernel"
    else:
        reason = _flash_refusal(query.shape[-1], key.shape[1], query.dtype,
                                dropout)
    path = (f"composite.{reason}" if reason
            else "flash_dropout" if dropout else "flash")
    from ... import observability as obs
    obs.get_registry().counter(f"attention.path.{path}").inc()
    return reason is None


def _kernel_seed(key_arr):
    """The flash kernels' int32[1] seed from the sub-key `_rng_op` hands
    one attention call."""
    return jax.lax.bitcast_convert_type(
        jax.random.bits(key_arr, (1,), jnp.uint32), jnp.int32)


def _attend(q, k, v, bias, causal, scale, use_pallas, dropout_p=0.0,
            key_arr=None):
    """One attention call on arrays: the flash kernel, seeded from the
    generator's sub-key when it drops, or the composite."""
    if not use_pallas:
        return _sdpa_ref(q, k, v, bias, causal, scale, dropout_p, key_arr)
    from ...ops.pallas_kernels import flash_attention
    return flash_attention(
        q, k, v, causal=causal, scale=scale, dropout_p=dropout_p,
        seed=_kernel_seed(key_arr) if dropout_p > 0.0 else None)


def scaled_dot_product_attention(query, key, value, attn_mask=None,
                                 dropout_p=0.0, is_causal=False,
                                 training=True, name=None):
    """Paddle-layout SDPA: q/k/v are [batch, seqlen, num_heads, head_dim].

    Attention dropout (dropout_p>0, training) uses the framework RNG via
    the same generator-state threading as F.dropout: the state advances
    once a call, and the flash kernel draws its masks tile by tile from
    a seed derived from the call's sub-key (the composite, taken with an
    `attn_mask` or off the TPU, draws one mask tensor from that key).
    """
    scale = 1.0 / (query.shape[-1] ** 0.5)
    drop = float(dropout_p) if training else 0.0
    use_pallas = _attention_path(query, key, attn_mask, drop > 0.0)
    args = (query, key, value) + ((attn_mask,) if attn_mask is not None
                                  else ())
    attrs = dict(causal=bool(is_causal), scale=scale,
                 use_pallas=use_pallas)

    if drop > 0.0:
        from .common import _rng_op

        def impl_drop(key_arr, q, k, v, *mask, causal, scale, use_pallas,
                      p):
            return _attend(q, k, v, mask[0] if mask else None, causal,
                           scale, use_pallas, p, key_arr)

        return _rng_op("scaled_dot_product_attention_drop", impl_drop,
                       args, dict(attrs, p=drop))

    def impl(q, k, v, *mask, causal, scale, use_pallas):
        return _attend(q, k, v, mask[0] if mask else None, causal, scale,
                       use_pallas)

    return dispatch("scaled_dot_product_attention", impl, args, attrs)


def flash_attention(query, key, value, dropout=0.0, causal=False,
                    return_softmax=False, fixed_seed_offset=None,
                    rng_name="", training=True, name=None):
    out = scaled_dot_product_attention(query, key, value, None, dropout,
                                       causal, training)
    if return_softmax:
        return out, None
    return out, None


def flash_attn_unpadded(query, key, value, cu_seqlens_q, cu_seqlens_k,
                        max_seqlen_q, max_seqlen_k, scale, dropout=0.0,
                        causal=False, return_softmax=False, training=True,
                        name=None):
    """Varlen attention over packed sequences.

    query/key/value: [total_tokens, num_heads, head_dim] with sequences
    concatenated; cu_seqlens_*: int32 [batch+1] prefix sums of lengths.
    Tokens only attend within their own sequence (block-diagonal mask
    derived from cu_seqlens), optionally causal within each sequence —
    matching the reference's flash_attn_varlen semantics.

    Memory note: this composite materializes [total_q, total_k] scores
    (the mask itself stays boolean), so very large packed batches should
    be chunked by the caller; a tiled varlen Pallas kernel is the
    long-term path.
    """
    drop = float(dropout) if training else 0.0

    tensors = (query, key, value, cu_seqlens_q, cu_seqlens_k)
    attrs = dict(causal=bool(causal), scale=float(scale), p=drop)
    if drop > 0.0:
        from .common import _rng_op
        return _rng_op("flash_attn_unpadded_drop", _varlen_attention,
                       tensors, attrs), None

    def impl(*args, **at):
        return _varlen_attention(None, *args, **at)

    return dispatch("flash_attn_unpadded", impl, tensors, attrs), None


def _varlen_attention(key_arr, q, k, v, cu_q, cu_k, *, causal, scale, p):
    tq, h, d = q.shape
    tk = k.shape[0]
    pos_q = jnp.arange(tq)
    pos_k = jnp.arange(tk)
    # sequence id of each packed token: index of the bucket it falls in
    seg_q = jnp.searchsorted(cu_q, pos_q, side="right") - 1
    seg_k = jnp.searchsorted(cu_k, pos_k, side="right") - 1
    same = seg_q[:, None] == seg_k[None, :]
    if causal:
        # position within own sequence
        off_q = pos_q - jnp.take(cu_q, seg_q)
        off_k = pos_k - jnp.take(cu_k, seg_k)
        same = jnp.logical_and(same,
                               off_k[None, :] <= off_q[:, None])
    qt = jnp.swapaxes(q[None], 1, 2)
    kt = jnp.swapaxes(k[None], 1, 2)
    vt = jnp.swapaxes(v[None], 1, 2)
    scores = jnp.einsum("bhqd,bhkd->bhqk", qt, kt,
                        preferred_element_type=jnp.float32) * scale
    scores = jnp.where(same[None, None], scores,
                       jnp.asarray(-1e30, scores.dtype))
    probs = jax.nn.softmax(scores, axis=-1)
    any_visible = jnp.any(same, axis=-1)[None, None, :, None]
    probs = jnp.where(any_visible, probs, 0.0).astype(q.dtype)
    if p > 0.0:
        keep = jax.random.bernoulli(key_arr, 1.0 - p, probs.shape)
        probs = jnp.where(keep, probs / (1.0 - p),
                          jnp.zeros((), probs.dtype))
    out = jnp.einsum("bhqk,bhkd->bhqd", probs, vt,
                     preferred_element_type=jnp.float32)
    return jnp.swapaxes(out, 1, 2)[0].astype(q.dtype)


# Program.clone(for_test=True): attention still computes, dropout off
from .common import RNG_INFER_IMPLS as _INFER  # noqa: E402

_INFER["scaled_dot_product_attention_drop"] = (
    lambda q, k, v, *mask, causal, scale, use_pallas, p: _attend(
        q, k, v, mask[0] if mask else None, causal, scale, use_pallas))
_INFER["flash_attn_unpadded_drop"] = (
    lambda q, k, v, cu_q, cu_k, *, causal, scale, p: _varlen_attention(
        None, q, k, v, cu_q, cu_k, causal=causal, scale=scale, p=0.0))


import threading as _threading

_sdp_override = _threading.local()


class sdp_kernel:
    """Backend-selection context (reference: paddle.nn.functional.
    sdp_kernel / torch.backends.cuda.sdp_kernel [UNVERIFIED]).

    ``enable_flash=False`` forces the XLA composite even where the
    Pallas kernel is eligible; with ``enable_flash=True`` (default)
    selection stays automatic (_attention_path).  ``enable_math`` /
    ``enable_mem_efficient`` are accepted for parity; the composite is
    the math path and Pallas flash is inherently memory-efficient.
    """

    def __init__(self, enable_math=True, enable_flash=True,
                 enable_mem_efficient=True):
        self._enable_flash = bool(enable_flash)

    def __enter__(self):
        self._prev = getattr(_sdp_override, "enable_flash", None)
        _sdp_override.enable_flash = self._enable_flash
        return self

    def __exit__(self, *exc):
        _sdp_override.enable_flash = self._prev
        return False


def _flash_allowed() -> bool:
    return getattr(_sdp_override, "enable_flash", None) is not False
