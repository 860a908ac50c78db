"""Fused functional ops (incubate.nn.functional parity).

Reference parity: phi `fusion/` kernels — fused_attention, fused_rope,
fused_bias_act, fused_rms_norm [UNVERIFIED — empty reference mount].
TPU-native: each is ONE dispatch so the whole composite is a single XLA
fusion (and a Pallas kernel where it matters: rms_norm/attention — see
ops/pallas_kernels.py).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ...core.dispatch import dispatch
from ...core.tensor import Tensor

__all__ = ["fused_linear", "fused_feedforward", "fused_multi_head_attention",
           "fused_bias_dropout_residual_layer_norm",
           "fused_rms_norm", "fused_layer_norm",
           "fused_rotary_position_embedding", "fused_bias_act", "swiglu",
           "fused_dropout_add", "fused_linear_activation",
           "top_p_sampling"]


def fused_linear(x, weight, bias=None, transpose_weight=False, name=None):
    def impl(v, w, *b, tw):
        if tw:
            w = w.T
        out = v @ w
        if b:
            out = out + b[0]
        return out

    args = (x, weight) + ((bias,) if bias is not None else ())
    return dispatch("fused_gemm_epilogue", impl, args,
                    dict(tw=bool(transpose_weight)))


def fused_linear_activation(x, y, bias, trans_x=False, trans_y=False,
                            activation="gelu"):
    def impl(v, w, b, *, tx, ty, act):
        if tx:
            v = v.T
        if ty:
            w = w.T
        out = v @ w + b
        if act == "gelu":
            return jax.nn.gelu(out)
        if act == "relu":
            return jnp.maximum(out, 0)
        return out

    return dispatch("fused_linear_activation", impl, (x, y, bias),
                    dict(tx=bool(trans_x), ty=bool(trans_y),
                         act=activation))


def swiglu(x, y=None, name=None):
    if y is not None:
        return dispatch("swiglu", lambda a, b: jax.nn.silu(a) * b, (x, y),
                        {})

    def impl(v):
        a, b = jnp.split(v, 2, axis=-1)
        return jax.nn.silu(a) * b

    return dispatch("swiglu", impl, (x,), {})


def fused_bias_act(x, bias=None, dequant_scales=None, shift=None,
                   smooth=None, act_method="gelu", **kwargs):
    def impl(v, *b, act):
        out = v + b[0] if b else v
        if act == "gelu":
            return jax.nn.gelu(out)
        if act in ("swiglu", "silu"):
            return jax.nn.silu(out)
        if act == "relu":
            return jnp.maximum(out, 0)
        return out

    args = (x,) + ((bias,) if bias is not None else ())
    return dispatch("fused_bias_act", impl, args, dict(act=act_method))


def fused_rms_norm(x, norm_weight, norm_bias=None, epsilon=1e-6,
                   begin_norm_axis=-1, **kwargs):
    from ...nn.functional.norm import rms_norm

    out = rms_norm(x, norm_weight, epsilon)
    if norm_bias is not None:
        from ...ops.math import add
        out = add(out, norm_bias)
    return out, None


def fused_layer_norm(x, norm_weight, norm_bias, epsilon=1e-5,
                     begin_norm_axis=-1, **kwargs):
    from ...nn.functional.norm import layer_norm

    shape = tuple(x.shape[begin_norm_axis:]) if begin_norm_axis != -1 else \
        (x.shape[-1],)
    return layer_norm(x, list(shape), norm_weight, norm_bias, epsilon), None


def fused_dropout_add(x, y, p=0.5, training=True, mode="upscale_in_train",
                      name=None):
    from ...nn.functional.common import dropout
    from ...ops.math import add

    return add(dropout(x, p, training=training, mode=mode), y)


def fused_bias_dropout_residual_layer_norm(
        x, residual, bias=None, ln_scale=None, ln_bias=None,
        dropout_rate=0.5, ln_epsilon=1e-5, training=True,
        mode="upscale_in_train", name=None):
    """layer_norm(residual + dropout(x + bias)) over the last axis: on
    the TPU one kernel with the dropout mask drawn inside it
    (`nn.functional.fused_residual_layer_norm`)."""
    from ...nn.functional.norm import fused_residual_layer_norm
    if bias is not None:
        from ...ops.math import add
        x = add(x, bias)
    return fused_residual_layer_norm(
        x, residual, [x.shape[-1]], ln_scale, ln_bias, ln_epsilon,
        dropout_p=dropout_rate, training=training, mode=mode)


def fused_rotary_position_embedding(q, k=None, v=None, sin=None, cos=None,
                                    position_ids=None, use_neox_rotary_style=True,
                                    time_major=False, rotary_emb_base=10000.0):
    """RoPE applied to q/k ([B, S, H, D] layout)."""

    def make_sincos(seq, dim, dtype, base):
        inv = 1.0 / (base ** (jnp.arange(0, dim, 2, dtype=jnp.float32) /
                              dim))
        t = jnp.arange(seq, dtype=jnp.float32)
        freqs = jnp.outer(t, inv)
        return jnp.sin(freqs).astype(dtype), jnp.cos(freqs).astype(dtype)

    def rotate(v, s, c, neox):
        """s/c already broadcastable to [B-or-1, S, 1, D/2]."""
        D = v.shape[-1]
        if neox:
            v1, v2 = v[..., :D // 2], v[..., D // 2:]
            return jnp.concatenate([v1 * c - v2 * s, v2 * c + v1 * s], -1)
        v1, v2 = v[..., 0::2], v[..., 1::2]
        out = jnp.stack([v1 * c - v2 * s, v2 * c + v1 * s], axis=-1)
        return out.reshape(v.shape)

    def rope(v, sin_, cos_, neox):  # [S, D/2] tables
        return rotate(v, sin_[None, :, None, :], cos_[None, :, None, :],
                      neox)

    if time_major:
        raise NotImplementedError(
            "fused_rotary_position_embedding: time_major layout is not "
            "supported (use [B, S, H, D])")

    def impl(qv, *rest, has_k, has_v, has_sc, has_pos, neox, base):
        i = 0
        kv = rest[i] if has_k else None
        i += 1 if has_k else 0
        vv = rest[i] if has_v else None
        i += 1 if has_v else 0
        S, D = qv.shape[1], qv.shape[-1]
        if has_sc:
            # user-supplied tables: accept [S, D/2] or paddle's
            # [1, S, 1, D/2] (squeeze the broadcast dims)
            sin_, cos_ = rest[i], rest[i + 1]
            i += 2
            sin_ = sin_.reshape(sin_.shape[-3], sin_.shape[-1]) \
                if sin_.ndim == 4 else sin_
            cos_ = cos_.reshape(cos_.shape[-3], cos_.shape[-1]) \
                if cos_.ndim == 4 else cos_
            sin_ = sin_.astype(qv.dtype)
            cos_ = cos_.astype(qv.dtype)
        else:
            sin_, cos_ = make_sincos(S, D, qv.dtype, base)
        if has_pos:
            pos = rest[i]
            if has_sc:
                # user table: clamp (table assumed to cover positions;
                # jnp.take's default fill mode would emit NaN)
                sin_p = jnp.take(sin_, pos, axis=0, mode="clip")
                cos_p = jnp.take(cos_, pos, axis=0, mode="clip")
            else:
                # no table: compute the angle directly from the
                # position — exact for ANY id (KV-cache decode reaches
                # positions >= this call's seq_len)
                inv = 1.0 / (base ** (
                    jnp.arange(0, D, 2, dtype=jnp.float32) / D))
                fr = pos.astype(jnp.float32)[..., None] * inv
                sin_p = jnp.sin(fr).astype(qv.dtype)
                cos_p = jnp.cos(fr).astype(qv.dtype)

            def apply(v, s_, c_, nx, _sp=sin_p, _cp=cos_p):
                del s_, c_
                return rotate(v, _sp[:, :, None, :], _cp[:, :, None, :],
                              nx)
        else:
            apply = rope
        outs = [apply(qv, sin_, cos_, neox)]
        if kv is not None:
            outs.append(apply(kv, sin_, cos_, neox))
        if vv is not None:
            outs.append(vv)
        return tuple(outs) if len(outs) > 1 else outs[0]

    has_sc = sin is not None and cos is not None
    args = (q,) + tuple(t for t in (k, v) if t is not None)
    if has_sc:
        args += (sin, cos)
    if position_ids is not None:
        args += (position_ids,)
    out = dispatch("fused_rope", impl, args,
                   dict(has_k=k is not None, has_v=v is not None,
                        has_sc=has_sc,
                        has_pos=position_ids is not None,
                        neox=bool(use_neox_rotary_style),
                        base=float(rotary_emb_base)))
    if isinstance(out, tuple):
        res = list(out)
        while len(res) < 3:
            res.append(None)
        return tuple(res)
    return out, None, None


def fused_feedforward(x, linear1_weight, linear2_weight, linear1_bias=None,
                      linear2_bias=None, ln1_scale=None, ln1_bias=None,
                      ln2_scale=None, ln2_bias=None, dropout1_rate=0.5,
                      dropout2_rate=0.5, activation="relu",
                      ln1_epsilon=1e-5, ln2_epsilon=1e-5,
                      pre_layer_norm=False, training=True, mode=None,
                      name=None):
    from ...nn import functional as F
    from ...ops.math import add

    residual = x
    if pre_layer_norm and ln1_scale is not None:
        x = F.layer_norm(x, [x.shape[-1]], ln1_scale, ln1_bias, ln1_epsilon)
    h = F.linear(x, linear1_weight, linear1_bias)
    h = F.gelu(h) if activation == "gelu" else F.relu(h)
    h = F.dropout(h, dropout1_rate, training=training)
    h = F.linear(h, linear2_weight, linear2_bias)
    h = F.dropout(h, dropout2_rate, training=training)
    out = add(residual, h)
    if not pre_layer_norm and ln2_scale is not None:
        out = F.layer_norm(out, [out.shape[-1]], ln2_scale, ln2_bias,
                           ln2_epsilon)
    return out


def fused_multi_head_attention(x, qkv_weight, linear_weight,
                               pre_layer_norm=False, pre_ln_scale=None,
                               pre_ln_bias=None, ln_scale=None, ln_bias=None,
                               pre_ln_epsilon=1e-5, qkv_bias=None,
                               linear_bias=None, cache_kv=None,
                               attn_mask=None, dropout_rate=0.5,
                               attn_dropout_rate=0.5, ln_epsilon=1e-5,
                               training=True, mode=None,
                               num_heads=None, **kwargs):
    from ...nn import functional as F
    from ...ops.math import add
    from ...ops.manipulation import reshape, transpose

    residual = x
    if pre_layer_norm and pre_ln_scale is not None:
        x = F.layer_norm(x, [x.shape[-1]], pre_ln_scale, pre_ln_bias,
                         pre_ln_epsilon)
    B, S, E = x.shape
    # qkv_weight: [3, num_heads, head_dim, E]
    nh = qkv_weight.shape[1]
    hd = qkv_weight.shape[2]
    from ...ops.linalg import einsum
    qkv = einsum("bse,thde->bsthd", x, qkv_weight)
    if qkv_bias is not None:
        qkv = add(qkv, qkv_bias)
    q = qkv[:, :, 0]
    k = qkv[:, :, 1]
    v = qkv[:, :, 2]
    out = F.scaled_dot_product_attention(
        q, k, v, attn_mask=attn_mask,
        dropout_p=attn_dropout_rate if training else 0.0)
    out = reshape(out, [B, S, nh * hd])
    out = F.linear(out, linear_weight, linear_bias)
    out = F.dropout(out, dropout_rate, training=training)
    out = add(residual, out)
    if not pre_layer_norm and ln_scale is not None:
        out = F.layer_norm(out, [out.shape[-1]], ln_scale, ln_bias,
                           ln_epsilon)
    return out


def _nucleus_mask(probs, top_p):
    """Keep-mask of each row's smallest prefix of descending-probability
    tokens whose cumulative mass reaches ``top_p[row]`` (rows with
    ``top_p >= 1`` keep everything).  Boundary rule: a token stays while
    the cumulative mass *before* it is < top_p — matching
    models/generation._sample_logits and the serving engine's in-graph
    sampler, which imports this helper."""
    order = jnp.argsort(-probs, axis=-1)
    sorted_p = jnp.take_along_axis(probs, order, axis=-1)
    cum = jnp.cumsum(sorted_p, axis=-1)
    keep_sorted = (cum - sorted_p) < top_p[:, None]
    rows = jnp.arange(probs.shape[0])[:, None]
    keep = jnp.zeros(probs.shape, bool).at[rows, order].set(keep_sorted)
    return keep | (top_p[:, None] >= 1.0)


def top_p_sampling(x, ps, threshold=None, seed=-1, name=None):
    """Nucleus (top-p) sampling over a batch of probability rows.

    x: [B, V] probabilities (renormalized internally); ps: [B] or [B, 1]
    per-row nucleus thresholds.  ``threshold`` additionally drops
    candidates whose filtered probability falls below it.  ``seed >= 0``
    draws with a fixed PRNG key — repeated calls with the same inputs
    and seed return identical tokens; ``seed == -1`` (default) threads
    the global generator like ``paddle.multinomial``.

    Returns ``(next_scores [B, 1], next_ids [B, 1] int64)`` where the
    score is the (pre-filter, renormalized) probability of the chosen
    token.
    """
    thr = None if threshold is None else float(threshold)

    def impl(key, probs, p_row, *, thr, stateful):
        if stateful:
            new, sub = jax.random.split(key)
        else:
            new, sub = key, key
        pr = probs.astype(jnp.float32)
        pr = pr / jnp.sum(pr, axis=-1, keepdims=True)
        p_flat = p_row.reshape(-1).astype(jnp.float32)
        filt = jnp.where(_nucleus_mask(pr, p_flat), pr, 0.0)
        if thr is not None:
            filt = jnp.where(filt >= thr, filt, 0.0)
        filt = filt / jnp.sum(filt, axis=-1, keepdims=True)
        ids = jax.random.categorical(
            sub, jnp.log(jnp.maximum(filt, 1e-30)), axis=-1)
        scores = jnp.take_along_axis(
            pr, ids[:, None], axis=-1).astype(probs.dtype)
        return scores, ids[:, None].astype(jnp.int64), new

    if seed is None or int(seed) < 0:
        from ...framework.random import default_generator
        g = default_generator()
        scores, ids, newk = dispatch(
            "top_p_sampling", impl, (g.state_tensor, x, ps),
            dict(thr=thr, stateful=True), differentiable=False)
        if isinstance(newk, Tensor):
            g.state_tensor._inplace_update(newk._value)
        return scores, ids

    key = Tensor(jax.random.PRNGKey(int(seed)), _internal=True,
                 stop_gradient=True)
    scores, ids, _ = dispatch(
        "top_p_sampling", impl, (key, x, ps),
        dict(thr=thr, stateful=False), differentiable=False)
    return scores, ids
