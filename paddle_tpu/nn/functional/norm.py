"""Normalization functionals.

Reference parity: `python/paddle/nn/functional/norm.py` → phi
layer_norm/batch_norm kernels [UNVERIFIED — empty reference mount].
TPU-native: these compile to fused XLA reductions; a Pallas fused
layer_norm/rms_norm for long rows lives in paddle_tpu/ops/pallas_kernels.py
and is used automatically for large hidden sizes.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ...core.dispatch import dispatch
from ...core.tensor import Tensor

__all__ = ["layer_norm", "batch_norm", "fused_residual_layer_norm",
           "instance_norm", "group_norm", "local_response_norm",
           "normalize", "rms_norm", "spectral_norm"]


def layer_norm(x, normalized_shape, weight=None, bias=None, epsilon=1e-05,
               name=None):
    if isinstance(normalized_shape, int):
        normalized_shape = (normalized_shape,)
    naxes = len(tuple(normalized_shape))
    from ...ops.pallas_gate import pallas_enabled
    use_pallas = (naxes == 1 and weight is not None and bias is not None
                  and pallas_enabled("layer_norm"))

    def impl(v, *wb, eps, naxes, has_w, has_b, use_pallas=False):
        if use_pallas:
            from ...ops.pallas_kernels import fused_layer_norm
            return fused_layer_norm(v, wb[0], wb[1], eps=eps)
        axes = tuple(range(v.ndim - naxes, v.ndim))
        # accumulate stats in f32 for bf16 inputs (TPU numerics)
        vf = v.astype(jnp.float32) if v.dtype in (jnp.bfloat16,
                                                  jnp.float16) else v
        mean = jnp.mean(vf, axis=axes, keepdims=True)
        var = jnp.mean(jnp.square(vf - mean), axis=axes, keepdims=True)
        out = (vf - mean) * jax.lax.rsqrt(var + eps)
        out = out.astype(v.dtype)
        i = 0
        if has_w:
            out = out * wb[i]
            i += 1
        if has_b:
            out = out + wb[i]
        return out

    args = (x,) + tuple(t for t in (weight, bias) if t is not None)
    return dispatch("layer_norm", impl, args,
                    dict(eps=float(epsilon), naxes=naxes,
                         has_w=weight is not None, has_b=bias is not None,
                         use_pallas=use_pallas))


def _ln_residual_composite(v, r, wb, eps, naxes, has_w, has_b):
    """layer_norm(v + r) in XLA with the kernel's semantics."""
    axes = tuple(range(v.ndim - naxes, v.ndim))
    # the add itself runs in f32 (matching the kernel) so bf16
    # residual streams don't round twice
    vf = v.astype(jnp.float32) + r.astype(jnp.float32)
    mean = jnp.mean(vf, axis=axes, keepdims=True)
    var = jnp.mean(jnp.square(vf - mean), axis=axes, keepdims=True)
    out = (vf - mean) * jax.lax.rsqrt(var + eps)
    out = out.astype(v.dtype)
    i = 0
    if has_w:
        out = out * wb[i]
        i += 1
    if has_b:
        out = out + wb[i]
    return out


def _ln_residual_impl(v, r, *wb, eps, naxes, has_w, has_b,
                      use_pallas=False):
    if use_pallas:
        from ...ops.pallas_fused import fused_layer_norm_residual
        return fused_layer_norm_residual(v, r, wb[0], wb[1], eps=eps)
    return _ln_residual_composite(v, r, wb, eps, naxes, has_w, has_b)


def _ln_residual_drop_impl(key, v, r, *wb, eps, naxes, has_w, has_b, p,
                           use_pallas=False):
    """The op with the draw in it: the kernel, seeded from the call's
    sub-key, or one bernoulli mask from that sub-key and the composite."""
    if use_pallas:
        from ...ops.pallas_fused import fused_layer_norm_residual
        from .flash_attention import _kernel_seed
        return fused_layer_norm_residual(v, r, wb[0], wb[1], eps=eps,
                                         dropout_p=p,
                                         seed=_kernel_seed(key))
    keep = jax.random.bernoulli(key, 1.0 - p, v.shape)
    v = jnp.where(keep, v / (1.0 - p), jnp.zeros((), v.dtype))
    return _ln_residual_composite(v, r, wb, eps, naxes, has_w, has_b)


def _ln_residual_path(dropout, kernel_shaped):
    """Which implementation this call builds, counted once a build in
    `layer_norm_residual.path.<dropout|plain|composite.<reason>>`: the
    kernel takes a call normalized over one axis with weight and bias,
    with the draw in it or without; anything else ("shape"), or a
    closed gate, leaves the XLA composite.  Returns whether the kernel
    runs."""
    from ...ops.pallas_gate import pallas_enabled
    if not kernel_shaped:
        reason = "shape"
    elif not pallas_enabled("layer_norm_residual_dropout" if dropout
                            else "layer_norm_residual"):
        reason = "gate"
    else:
        reason = None
    path = (f"composite.{reason}" if reason
            else "dropout" if dropout else "plain")
    from ... import observability as obs
    obs.get_registry().counter(f"layer_norm_residual.path.{path}").inc()
    return reason is None


def fused_residual_layer_norm(x, residual, normalized_shape, weight=None,
                              bias=None, epsilon=1e-05, dropout_p=0.0,
                              training=True, mode="upscale_in_train",
                              name=None):
    """layer_norm(dropout(x) + residual) with dropout and add fused into
    the norm.

    The post-norm transformer sublayer epilogue.  On TPU (behind the
    ``layer_norm_residual`` gate) a single Pallas kernel streams x and
    the residual once, adds in f32 and normalizes in the same pass; the
    XLA fallback computes the identical f32 add + f32-stat composite so
    both paths agree bitwise-closely for bf16 inputs.

    With ``dropout_p`` > 0 in training the generator advances once a
    call, exactly as the `F.dropout` it stands for would, and the
    kernel draws its keep mask block by block from a seed derived from
    the call's sub-key, forward and backward: no mask tensor exists.
    The composite, taken off the TPU, draws one mask from that key.
    Dropout that is not plain ``upscale_in_train``, or a norm the kernel
    does not take, runs as `F.dropout` and then this op without it.
    """
    if isinstance(normalized_shape, int):
        normalized_shape = (normalized_shape,)
    naxes = len(tuple(normalized_shape))
    kernel_shaped = naxes == 1 and weight is not None and bias is not None
    drop = float(dropout_p)
    if drop > 0.0 and not (training and kernel_shaped
                           and mode == "upscale_in_train"):
        from .common import dropout
        x, drop = dropout(x, drop, training=training, mode=mode), 0.0
    args = (x, residual) + tuple(t for t in (weight, bias)
                                 if t is not None)
    attrs = dict(eps=float(epsilon), naxes=naxes,
                 has_w=weight is not None, has_b=bias is not None,
                 use_pallas=_ln_residual_path(drop > 0.0, kernel_shaped))
    if drop > 0.0:
        from .common import _rng_op
        return _rng_op("fused_residual_layer_norm_drop",
                       _ln_residual_drop_impl, args, dict(attrs, p=drop))
    return dispatch("fused_residual_layer_norm", _ln_residual_impl, args,
                    attrs)


# Program.clone(for_test=True): the norm still computes, dropout off
from .common import RNG_INFER_IMPLS as _INFER  # noqa: E402

_INFER["fused_residual_layer_norm_drop"] = (
    lambda v, r, *wb, p, **attrs: _ln_residual_impl(v, r, *wb, **attrs))


def rms_norm(x, weight=None, epsilon=1e-6, name=None):
    from ...ops.pallas_gate import pallas_enabled
    use_pallas = weight is not None and pallas_enabled("rms_norm")

    def impl(v, *wb, eps, use_pallas=False):
        if use_pallas:
            from ...ops.pallas_kernels import fused_rms_norm
            return fused_rms_norm(v, wb[0], eps=eps)
        vf = v.astype(jnp.float32) if v.dtype in (jnp.bfloat16,
                                                  jnp.float16) else v
        ms = jnp.mean(jnp.square(vf), axis=-1, keepdims=True)
        out = (vf * jax.lax.rsqrt(ms + eps)).astype(v.dtype)
        if wb:
            out = out * wb[0]
        return out

    args = (x,) + ((weight,) if weight is not None else ())
    return dispatch("rms_norm", impl, args,
                    dict(eps=float(epsilon), use_pallas=use_pallas))


def batch_norm(x, running_mean, running_var, weight=None, bias=None,
               training=False, momentum=0.9, epsilon=1e-05,
               data_format="NCHW", use_global_stats=None, name=None):
    cf = data_format.startswith("NC")
    caxis = 1 if (cf and x.ndim > 1) else x.ndim - 1
    if use_global_stats is None:
        use_global_stats = not training

    if not use_global_stats:
        # training path: compute batch stats, update running stats in-place
        def impl(v, rm, rv, *wb, eps, mom, caxis, has_w, has_b):
            axes = tuple(i for i in range(v.ndim) if i != caxis)
            vf = v.astype(jnp.float32) if v.dtype in (
                jnp.bfloat16, jnp.float16) else v
            mean = jnp.mean(vf, axis=axes)
            var = jnp.var(vf, axis=axes)
            shape = [1] * v.ndim
            shape[caxis] = v.shape[caxis]
            out = (vf - mean.reshape(shape)) * jax.lax.rsqrt(
                var.reshape(shape) + eps)
            out = out.astype(v.dtype)
            i = 0
            if has_w:
                out = out * wb[i].reshape(shape)
                i += 1
            if has_b:
                out = out + wb[i].reshape(shape)
            n = 1
            for a in axes:
                n *= v.shape[a]
            unbiased = var * (n / max(n - 1, 1))
            new_rm = mom * rm + (1 - mom) * mean.astype(rm.dtype)
            new_rv = mom * rv + (1 - mom) * unbiased.astype(rv.dtype)
            return out, new_rm, new_rv

        args = (x, running_mean, running_var) + tuple(
            t for t in (weight, bias) if t is not None)
        out, new_rm, new_rv = dispatch(
            "batch_norm", impl, args,
            dict(eps=float(epsilon), mom=float(momentum), caxis=caxis,
                 has_w=weight is not None, has_b=bias is not None))
        running_mean._inplace_update(new_rm._value)
        running_var._inplace_update(new_rv._value)
        return out

    def impl_infer(v, rm, rv, *wb, eps, caxis, has_w, has_b):
        shape = [1] * v.ndim
        shape[caxis] = v.shape[caxis]
        out = (v - rm.reshape(shape).astype(v.dtype)) * jax.lax.rsqrt(
            rv.reshape(shape).astype(v.dtype) + eps)
        i = 0
        if has_w:
            out = out * wb[i].reshape(shape)
            i += 1
        if has_b:
            out = out + wb[i].reshape(shape)
        return out

    args = (x, running_mean, running_var) + tuple(
        t for t in (weight, bias) if t is not None)
    return dispatch("batch_norm_infer", impl_infer, args,
                    dict(eps=float(epsilon), caxis=caxis,
                         has_w=weight is not None, has_b=bias is not None))


def instance_norm(x, running_mean=None, running_var=None, weight=None,
                  bias=None, use_input_stats=True, momentum=0.9,
                  epsilon=1e-05, data_format="NCHW", name=None):
    """Channels-last formats normalize over their own spatial axes (they
    were silently treated as channels-first); use_input_stats=False
    normalizes with the provided running statistics (per paddle; the
    running stats are not updated here — InstanceNorm layers don't
    track them by default)."""
    if not use_input_stats and (running_mean is None
                                or running_var is None):
        raise ValueError(
            "instance_norm(use_input_stats=False) requires both "
            "running_mean and running_var")
    use_running = not use_input_stats

    def impl(v, *rest, eps, has_w, has_b, cl, use_running):
        if cl:
            v = jnp.moveaxis(v, -1, 1)
        i = 0
        shape = [1, v.shape[1]] + [1] * (v.ndim - 2)
        if use_running:
            mean = rest[i].reshape(shape).astype(v.dtype)
            var = rest[i + 1].reshape(shape).astype(v.dtype)
            i += 2
        else:
            axes = tuple(range(2, v.ndim))
            vf = v.astype(jnp.float32)  # f32 accumulation for bf16/f16
            mean = jnp.mean(vf, axis=axes, keepdims=True).astype(v.dtype)
            var = jnp.var(vf, axis=axes, keepdims=True).astype(v.dtype)
        out = (v - mean) * jax.lax.rsqrt(var + eps)
        if has_w:
            out = out * rest[i].reshape(shape)
            i += 1
        if has_b:
            out = out + rest[i].reshape(shape)
        if cl:
            out = jnp.moveaxis(out, 1, -1)
        return out

    args = (x,)
    if use_running:
        args += (running_mean, running_var)
    args += tuple(t for t in (weight, bias) if t is not None)
    return dispatch("instance_norm", impl, args,
                    dict(eps=float(epsilon), has_w=weight is not None,
                         has_b=bias is not None,
                         cl=not data_format.startswith("NC"),
                         use_running=use_running))


def group_norm(x, num_groups, epsilon=1e-05, weight=None, bias=None,
               data_format="NCHW", name=None):
    cf = data_format.startswith("NC")

    def impl(v, *wb, eps, groups, cf, has_w, has_b):
        if not cf:
            v = jnp.moveaxis(v, -1, 1)
        n, c = v.shape[:2]
        rest = v.shape[2:]
        g = v.reshape((n, groups, c // groups) + rest)
        axes = tuple(range(2, g.ndim))
        mean = jnp.mean(g, axis=axes, keepdims=True)
        var = jnp.var(g, axis=axes, keepdims=True)
        out = ((g - mean) * jax.lax.rsqrt(var + eps)).reshape(v.shape)
        shape = [1, c] + [1] * (v.ndim - 2)
        i = 0
        if has_w:
            out = out * wb[i].reshape(shape)
            i += 1
        if has_b:
            out = out + wb[i].reshape(shape)
        if not cf:
            out = jnp.moveaxis(out, 1, -1)
        return out

    args = (x,) + tuple(t for t in (weight, bias) if t is not None)
    return dispatch("group_norm", impl, args,
                    dict(eps=float(epsilon), groups=int(num_groups), cf=cf,
                         has_w=weight is not None, has_b=bias is not None))


def local_response_norm(x, size, alpha=1e-4, beta=0.75, k=1.0,
                        data_format="NCHW", name=None):
    def impl(v, *, size, alpha, beta, k, caxis):
        ch = caxis % v.ndim
        sq = jnp.square(v)
        half = size // 2
        pad_width = [(0, 0)] * v.ndim
        pad_width[ch] = (half, size - 1 - half)
        padded = jnp.pad(sq, pad_width)
        acc = jnp.zeros_like(v)
        for i in range(size):
            acc = acc + jax.lax.slice_in_dim(
                padded, i, i + v.shape[ch], axis=ch)
        div = jnp.power(k + alpha * acc / size, beta)
        return v / div

    # channels-last formats normalize across their LAST axis (it was
    # silently always axis 1)
    caxis = 1 if data_format.startswith("NC") else -1
    return dispatch("lrn", impl, (x,),
                    dict(size=int(size), alpha=float(alpha),
                         beta=float(beta), k=float(k), caxis=caxis))


def normalize(x, p=2, axis=1, epsilon=1e-12, name=None):
    return dispatch(
        "normalize",
        lambda v, *, p, axis, eps: v / jnp.maximum(
            jnp.power(jnp.sum(jnp.power(jnp.abs(v), p), axis=axis,
                              keepdims=True), 1.0 / p), eps),
        (x,), dict(p=float(p), axis=int(axis), eps=float(epsilon)))


def spectral_norm(x, weight_u, weight_v, dim=0, power_iters=1,
                  epsilon=1e-12, name=None):
    """Normalize weight x by its largest singular value (power
    iteration with the given u/v state); functional form of the
    SpectralNorm layer."""
    def impl(w, u, v, *, dim, iters, eps):
        perm = (dim,) + tuple(i for i in range(w.ndim) if i != dim)
        mat = jnp.transpose(w, perm).reshape(w.shape[dim], -1)
        for _ in range(iters):
            v = mat.T @ u
            v = v / (jnp.linalg.norm(v) + eps)
            u = mat @ v
            u = u / (jnp.linalg.norm(u) + eps)
        sigma = u @ mat @ v
        return w / sigma

    return dispatch("spectral_norm", impl, (x, weight_u, weight_v),
                    dict(dim=int(dim), iters=int(power_iters),
                         eps=float(epsilon)))
