"""Loss functionals.

Reference parity: `python/paddle/nn/functional/loss.py` → phi
softmax_with_cross_entropy etc. [UNVERIFIED — empty reference mount].
cross_entropy uses a single fused log-softmax+gather impl (one XLA fusion,
like phi's fused kernel); the vocab-parallel variant is in
distributed/fleet/meta_parallel.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ...core.dispatch import dispatch
from ...core.tensor import Tensor

__all__ = [
    "cross_entropy", "softmax_with_cross_entropy", "mse_loss", "l1_loss",
    "nll_loss", "binary_cross_entropy", "binary_cross_entropy_with_logits",
    "smooth_l1_loss", "kl_div", "margin_ranking_loss", "hinge_embedding_loss",
    "cosine_embedding_loss", "triplet_margin_loss", "log_loss", "square_error_cost",
    "sigmoid_focal_loss", "dice_loss", "npair_loss", "poisson_nll_loss",
    "multi_label_soft_margin_loss", "soft_margin_loss", "ctc_loss",
    "multi_margin_loss", "triplet_margin_with_distance_loss",
    "hsigmoid_loss",
    "huber_loss", "gaussian_nll_loss",
]


def _reduce(out, reduction):
    if reduction == "mean":
        return jnp.mean(out)
    if reduction == "sum":
        return jnp.sum(out)
    return out


def _xent_path(input, weight, soft_label, axis, use_softmax,
               label_smoothing):
    """Which implementation this call builds, counted once a build in
    `softmax_cross_entropy.path.<fused|composite.<reason>>`: the kernel
    takes hard labels over the last axis of float logits, unweighted
    and unsmoothed; anything else, or a closed gate, leaves the XLA
    composite.  The kernel is vocab-tiled (bounded VMEM at any V), so
    `vocab_cap` only keeps absurd widths off it.  Returns whether the
    kernel runs."""
    from ...ops.pallas_gate import pallas_enabled
    if soft_label:
        reason = "soft_label"
    elif weight is not None:
        reason = "weight"
    elif label_smoothing != 0.0:
        reason = "label_smoothing"
    elif not use_softmax:
        reason = "no_softmax"
    elif axis not in (-1, input.ndim - 1):
        reason = "axis"
    elif input.dtype not in ("float32", "bfloat16", "float16"):
        reason = "dtype"
    elif input.shape[-1] > 128 * 1024:
        reason = "vocab_cap"
    elif not pallas_enabled("softmax_cross_entropy"):
        reason = "gate"
    else:
        reason = None
    from ... import observability as obs
    obs.get_registry().counter(
        "softmax_cross_entropy.path."
        + (f"composite.{reason}" if reason else "fused")).inc()
    return reason is None


def cross_entropy(input, label, weight=None, ignore_index=-100,
                  reduction="mean", soft_label=False, axis=-1,
                  use_softmax=True, label_smoothing=0.0, name=None):
    # Hard-label fast path → Pallas fused softmax-xent on TPU (the
    # reference's fused c_softmax_with_cross_entropy kernel role).
    use_fused = _xent_path(input, weight, soft_label, axis, use_softmax,
                           label_smoothing)

    def impl(logits, lab, *w, ignore_index, reduction, soft_label, axis,
             use_softmax, smooth, use_fused=False):
        # s64 class indices are a pure TPU tax (global x64 mode keeps
        # paddle's int64 labels); any real class count fits int32
        if not soft_label and lab.dtype in (jnp.int64, jnp.uint64):
            lab = lab.astype(jnp.int32)
        if use_fused:
            from ...ops.pallas_kernels import fused_softmax_cross_entropy
            lab_i = lab
            if lab_i.ndim == logits.ndim and lab_i.shape[-1] == 1:
                lab_i = jnp.squeeze(lab_i, -1)
            valid = lab_i != ignore_index
            relabeled = jnp.where(valid, lab_i, -1)  # kernel ignores <0
            loss = fused_softmax_cross_entropy(logits, relabeled)
            if reduction == "mean":
                denom = jnp.sum(valid.astype(loss.dtype))
                return jnp.sum(loss) / jnp.maximum(denom, 1.0)
            if reduction == "sum":
                return jnp.sum(loss)
            return loss
        if use_softmax:
            logp = jax.nn.log_softmax(
                logits.astype(jnp.float32)
                if logits.dtype in (jnp.bfloat16, jnp.float16) else logits,
                axis=axis)
        else:
            logp = jnp.log(jnp.maximum(logits, 1e-30))
        n_classes = logits.shape[axis]
        if soft_label:
            lab_s = lab
            if smooth > 0:
                lab_s = lab_s * (1 - smooth) + smooth / n_classes
            loss = -jnp.sum(lab_s * logp, axis=axis)
            valid = None
        else:
            lab_i = lab
            if lab_i.ndim == logits.ndim and lab_i.shape[axis] == 1:
                lab_i = jnp.squeeze(lab_i, axis)
            valid = lab_i != ignore_index
            safe = jnp.where(valid, lab_i, 0)
            picked = jnp.take_along_axis(
                logp, jnp.expand_dims(safe, axis), axis=axis)
            loss = -jnp.squeeze(picked, axis)
            if smooth > 0:
                uniform = -jnp.mean(logp, axis=axis)
                loss = (1 - smooth) * loss + smooth * uniform
            if w:
                wt = jnp.take(w[0], safe)
                loss = loss * wt
            loss = jnp.where(valid, loss, 0.0)
        if reduction == "mean":
            if valid is not None:
                if w:
                    wt = jnp.take(w[0], jnp.where(valid, lab_i, 0))
                    denom = jnp.sum(jnp.where(valid, wt, 0.0))
                else:
                    denom = jnp.sum(valid.astype(loss.dtype))
                return jnp.sum(loss) / jnp.maximum(denom, 1.0)
            return jnp.mean(loss)
        if reduction == "sum":
            return jnp.sum(loss)
        return loss

    args = (input, label) + ((weight,) if weight is not None else ())
    return dispatch("cross_entropy", impl, args,
                    dict(ignore_index=int(ignore_index), reduction=reduction,
                         soft_label=bool(soft_label), axis=int(axis),
                         use_softmax=bool(use_softmax),
                         smooth=float(label_smoothing),
                         use_fused=use_fused))


def softmax_with_cross_entropy(logits, label, soft_label=False,
                               ignore_index=-100, numeric_stable_mode=True,
                               return_softmax=False, axis=-1):
    loss = cross_entropy(logits, label, soft_label=soft_label,
                         ignore_index=ignore_index, reduction="none",
                         axis=axis)
    # paddle keeps the reduced axis with size 1
    from ...ops.manipulation import unsqueeze
    loss = unsqueeze(loss, axis if axis >= 0 else loss.ndim + 1 + axis)
    if return_softmax:
        from .activation import softmax as _softmax
        return loss, _softmax(logits, axis=axis)
    return loss


def mse_loss(input, label, reduction="mean", name=None):
    return dispatch(
        "mse_loss",
        lambda a, b, *, reduction: _reduce(jnp.square(a - b), reduction),
        (input, label), dict(reduction=reduction))


def square_error_cost(input, label):
    return dispatch("square_error_cost",
                    lambda a, b: jnp.square(a - b), (input, label), {})


def l1_loss(input, label, reduction="mean", name=None):
    return dispatch(
        "l1_loss",
        lambda a, b, *, reduction: _reduce(jnp.abs(a - b), reduction),
        (input, label), dict(reduction=reduction))


def nll_loss(input, label, weight=None, ignore_index=-100, reduction="mean",
             name=None):
    def impl(logp, lab, *w, ignore_index, reduction):
        valid = lab != ignore_index
        safe = jnp.where(valid, lab, 0)
        picked = jnp.take_along_axis(logp, safe[:, None], axis=1)[:, 0]
        loss = -picked
        if w:
            wt = jnp.take(w[0], safe)
            loss = loss * wt
        loss = jnp.where(valid, loss, 0.0)
        if reduction == "mean":
            denom = jnp.sum(jnp.take(w[0], safe) * valid) if w else \
                jnp.sum(valid.astype(loss.dtype))
            return jnp.sum(loss) / jnp.maximum(denom, 1e-12)
        if reduction == "sum":
            return jnp.sum(loss)
        return loss

    args = (input, label) + ((weight,) if weight is not None else ())
    return dispatch("nll_loss", impl, args,
                    dict(ignore_index=int(ignore_index),
                         reduction=reduction))


def binary_cross_entropy(input, label, weight=None, reduction="mean",
                         name=None):
    def impl(p, y, *w, reduction):
        p = jnp.clip(p, 1e-12, 1.0 - 1e-12)
        loss = -(y * jnp.log(p) + (1 - y) * jnp.log(1 - p))
        if w:
            loss = loss * w[0]
        return _reduce(loss, reduction)

    args = (input, label) + ((weight,) if weight is not None else ())
    return dispatch("bce_loss", impl, args, dict(reduction=reduction))


def binary_cross_entropy_with_logits(logit, label, weight=None,
                                     reduction="mean", pos_weight=None,
                                     name=None):
    def impl(z, y, *extra, reduction, has_w, has_pw):
        # numerically stable: max(z,0) - z*y + log(1+exp(-|z|))
        loss = jnp.maximum(z, 0) - z * y + jnp.log1p(jnp.exp(-jnp.abs(z)))
        if has_pw:
            pw_arr = extra[1] if has_w else extra[0]
            log_sig = jax.nn.log_sigmoid(z)
            log_sig_neg = jax.nn.log_sigmoid(-z)
            loss = -(pw_arr * y * log_sig + (1 - y) * log_sig_neg)
        if has_w:
            loss = loss * extra[0]
        return _reduce(loss, reduction)

    extras = tuple(t for t in (weight, pos_weight) if t is not None)
    return dispatch("bce_logits", impl, (logit, label) + extras,
                    dict(reduction=reduction, has_w=weight is not None,
                         has_pw=pos_weight is not None))


def smooth_l1_loss(input, label, reduction="mean", delta=1.0, name=None):
    def impl(a, b, *, reduction, delta):
        d = a - b
        ad = jnp.abs(d)
        loss = jnp.where(ad < delta, 0.5 * d * d / delta, ad - 0.5 * delta)
        return _reduce(loss, reduction)

    return dispatch("smooth_l1", impl, (input, label),
                    dict(reduction=reduction, delta=float(delta)))


def kl_div(input, label, reduction="mean", log_target=False, name=None):
    def impl(logp, y, *, reduction, log_target):
        if log_target:
            loss = jnp.exp(y) * (y - logp)
        else:
            loss = y * (jnp.log(jnp.maximum(y, 1e-30)) - logp)
        if reduction == "batchmean":
            return jnp.sum(loss) / logp.shape[0]
        return _reduce(loss, reduction)

    return dispatch("kldiv_loss", impl, (input, label),
                    dict(reduction=reduction, log_target=bool(log_target)))


def margin_ranking_loss(input, other, label, margin=0.0, reduction="mean",
                        name=None):
    return dispatch(
        "margin_ranking_loss",
        lambda a, b, y, *, margin, reduction: _reduce(
            jnp.maximum(-y * (a - b) + margin, 0.0), reduction),
        (input, other, label),
        dict(margin=float(margin), reduction=reduction))


def hinge_embedding_loss(input, label, margin=1.0, reduction="mean",
                         name=None):
    return dispatch(
        "hinge_embedding_loss",
        lambda x, y, *, margin, reduction: _reduce(
            jnp.where(y == 1, x, jnp.maximum(margin - x, 0.0)), reduction),
        (input, label), dict(margin=float(margin), reduction=reduction))


def cosine_embedding_loss(input1, input2, label, margin=0.0,
                          reduction="mean", name=None):
    def impl(a, b, y, *, margin, reduction):
        cos = jnp.sum(a * b, -1) / jnp.maximum(
            jnp.linalg.norm(a, axis=-1) * jnp.linalg.norm(b, axis=-1),
            1e-12)
        loss = jnp.where(y == 1, 1 - cos, jnp.maximum(cos - margin, 0.0))
        return _reduce(loss, reduction)

    return dispatch("cosine_embedding_loss", impl, (input1, input2, label),
                    dict(margin=float(margin), reduction=reduction))


def triplet_margin_loss(input, positive, negative, margin=1.0, p=2.0,
                        epsilon=1e-6, swap=False, reduction="mean",
                        name=None):
    def impl(a, pos, neg, *, margin, p, eps, swap, reduction):
        def dist(u, v):
            return jnp.power(
                jnp.sum(jnp.power(jnp.abs(u - v) + eps, p), -1), 1.0 / p)

        d_ap = dist(a, pos)
        d_an = dist(a, neg)
        if swap:
            d_pn = dist(pos, neg)
            d_an = jnp.minimum(d_an, d_pn)
        return _reduce(jnp.maximum(d_ap - d_an + margin, 0.0), reduction)

    return dispatch("triplet_margin_loss", impl, (input, positive, negative),
                    dict(margin=float(margin), p=float(p),
                         eps=float(epsilon), swap=bool(swap),
                         reduction=reduction))


def log_loss(input, label, epsilon=1e-4, name=None):
    return dispatch(
        "log_loss",
        lambda p, y, *, eps: -y * jnp.log(p + eps) - (1 - y) * jnp.log(
            1 - p + eps),
        (input, label), dict(eps=float(epsilon)))


def sigmoid_focal_loss(logit, label, normalizer=None, alpha=0.25, gamma=2.0,
                       reduction="sum", name=None):
    def impl(z, y, *norm, alpha, gamma, reduction):
        p = jax.nn.sigmoid(z)
        ce = jnp.maximum(z, 0) - z * y + jnp.log1p(jnp.exp(-jnp.abs(z)))
        p_t = p * y + (1 - p) * (1 - y)
        a_t = alpha * y + (1 - alpha) * (1 - y)
        loss = a_t * jnp.power(1 - p_t, gamma) * ce
        if norm:
            loss = loss / norm[0]
        return _reduce(loss, reduction)

    args = (logit, label) + ((normalizer,) if normalizer is not None else ())
    return dispatch("sigmoid_focal_loss", impl, args,
                    dict(alpha=float(alpha), gamma=float(gamma),
                         reduction=reduction))


def dice_loss(input, label, epsilon=1e-5, name=None):
    def impl(p, y, *, eps):
        y1 = jax.nn.one_hot(y[..., 0], p.shape[-1], dtype=p.dtype)
        reduce_dims = tuple(range(1, p.ndim))
        inter = jnp.sum(p * y1, axis=reduce_dims)
        union = jnp.sum(p, axis=reduce_dims) + jnp.sum(y1, axis=reduce_dims)
        dice = (2 * inter + eps) / (union + eps)
        return jnp.mean(1 - dice)

    return dispatch("dice_loss", impl, (input, label),
                    dict(eps=float(epsilon)))


def npair_loss(anchor, positive, labels, l2_reg=0.002):
    def impl(a, p, y, *, l2):
        sim = a @ p.T
        y_ = y.reshape(-1, 1)
        same = (y_ == y_.T).astype(sim.dtype)
        same = same / jnp.sum(same, axis=1, keepdims=True)
        logp = jax.nn.log_softmax(sim, axis=1)
        xent = -jnp.mean(jnp.sum(same * logp, axis=1))
        reg = l2 * 0.25 * (jnp.mean(jnp.sum(a * a, 1)) +
                           jnp.mean(jnp.sum(p * p, 1)))
        return xent + reg

    return dispatch("npair_loss", impl, (anchor, positive, labels),
                    dict(l2=float(l2_reg)))


def poisson_nll_loss(input, label, log_input=True, full=False, epsilon=1e-8,
                     reduction="mean", name=None):
    def impl(x, y, *, log_input, full, eps, reduction):
        if log_input:
            loss = jnp.exp(x) - y * x
        else:
            loss = x - y * jnp.log(x + eps)
        if full:
            stirling = y * jnp.log(y + eps) - y + 0.5 * jnp.log(
                2 * jnp.pi * (y + eps))
            loss = loss + jnp.where(y > 1, stirling, 0.0)
        return _reduce(loss, reduction)

    return dispatch("poisson_nll_loss", impl, (input, label),
                    dict(log_input=bool(log_input), full=bool(full),
                         eps=float(epsilon), reduction=reduction))


def multi_label_soft_margin_loss(input, label, weight=None,
                                 reduction="mean", name=None):
    def impl(x, y, *w, reduction):
        loss = -(y * jax.nn.log_sigmoid(x) +
                 (1 - y) * jax.nn.log_sigmoid(-x))
        loss = jnp.mean(loss, axis=-1)
        if w:
            loss = loss * w[0]
        return _reduce(loss, reduction)

    args = (input, label) + ((weight,) if weight is not None else ())
    return dispatch("multi_label_soft_margin_loss", impl, args,
                    dict(reduction=reduction))


def soft_margin_loss(input, label, reduction="mean", name=None):
    return dispatch(
        "soft_margin_loss",
        lambda x, y, *, reduction: _reduce(
            jnp.log1p(jnp.exp(-y * x)), reduction),
        (input, label), dict(reduction=reduction))


def ctc_loss(log_probs, labels, input_lengths, label_lengths, blank=0,
             reduction="mean", norm_by_times=False):
    """CTC via dynamic-programming in pure JAX (replaces warpctc)."""
    def impl(lp, lab, in_len, lab_len, *, blank, reduction,
             norm_by_times):
        # lp: [T, B, C] logits (paddle convention); normalize
        lp = jax.nn.log_softmax(lp, axis=-1)
        T, B, C = lp.shape
        S = lab.shape[1]
        # extended labels with blanks: [B, 2S+1]
        ext = jnp.full((B, 2 * S + 1), blank, dtype=lab.dtype)
        ext = ext.at[:, 1::2].set(lab)
        ext_len = 2 * lab_len + 1
        neg_inf = jnp.asarray(-1e30, lp.dtype)
        alpha = jnp.full((B, 2 * S + 1), neg_inf)
        alpha = alpha.at[:, 0].set(lp[0, :, blank])
        alpha = alpha.at[:, 1].set(
            jnp.take_along_axis(lp[0], ext[:, 1:2], axis=1)[:, 0])

        def logaddexp(a, b):
            m = jnp.maximum(a, b)
            return m + jnp.log(
                jnp.exp(a - m) + jnp.exp(b - m) + 1e-30) * (m > neg_inf)

        same_as_prev2 = jnp.concatenate(
            [jnp.ones((B, 2), bool),
             ext[:, 2:] == ext[:, :-2]], axis=1)

        def step(alpha, lp_t):
            a_prev = alpha
            a_shift1 = jnp.concatenate(
                [jnp.full((B, 1), neg_inf), alpha[:, :-1]], axis=1)
            a_shift2 = jnp.concatenate(
                [jnp.full((B, 2), neg_inf), alpha[:, :-2]], axis=1)
            a = jnp.logaddexp(a_prev, a_shift1)
            a = jnp.where(same_as_prev2, a, jnp.logaddexp(a, a_shift2))
            emit = jnp.take_along_axis(lp_t, ext, axis=1)
            return a + emit, None

        def masked_step(carry, x):
            alpha, t = carry
            new_alpha, _ = step(alpha, x)
            keep = (t < in_len)[:, None]
            return (jnp.where(keep, new_alpha, alpha), t + 1), None

        (alpha, _), _ = jax.lax.scan(masked_step, (alpha, jnp.ones((),
                                     jnp.int32)), lp[1:])
        idx_last = ext_len - 1
        idx_prev = ext_len - 2
        ll = jnp.logaddexp(
            jnp.take_along_axis(alpha, idx_last[:, None], axis=1)[:, 0],
            jnp.take_along_axis(alpha, idx_prev[:, None], axis=1)[:, 0])
        loss = -ll
        if norm_by_times:
            # paddle/warpctc: normalize the GRADIENTS by the number of
            # time steps — the forward loss value stays unchanged
            # (forward(a - a/T + a/T) == a; grad flows only via a/T)
            t = jnp.maximum(in_len.astype(loss.dtype), 1.0)
            loss = jax.lax.stop_gradient(loss - loss / t) + loss / t
        if reduction == "mean":
            return jnp.mean(loss / jnp.maximum(lab_len, 1))
        if reduction == "sum":
            return jnp.sum(loss)
        return loss

    return dispatch("ctc_loss", impl,
                    (log_probs, labels, input_lengths, label_lengths),
                    dict(blank=int(blank), reduction=reduction,
                         norm_by_times=bool(norm_by_times)))


def huber_loss(input, label, delta=1.0, reduction="mean", name=None):
    def impl(x, y, *, delta, reduction):
        d = x - y
        ad = jnp.abs(d)
        out = jnp.where(ad <= delta, 0.5 * d * d,
                        delta * (ad - 0.5 * delta))
        return _reduce(out, reduction)
    return dispatch("huber_loss", impl, (input, label),
                    dict(delta=float(delta), reduction=reduction))


def gaussian_nll_loss(input, label, variance, full=False, epsilon=1e-6,
                      reduction="mean", name=None):
    def impl(mu, y, var, *, full, eps, reduction):
        var = jnp.clip(var, eps)
        out = 0.5 * (jnp.log(var) + jnp.square(y - mu) / var)
        if full:
            out = out + 0.5 * jnp.log(
                jnp.asarray(2 * jnp.pi, var.dtype))
        return _reduce(out, reduction)
    return dispatch("gaussian_nll_loss", impl,
                    (input, label, variance),
                    dict(full=bool(full), eps=float(epsilon),
                         reduction=reduction))


def multi_margin_loss(input, label, p=1, margin=1.0, weight=None,
                      reduction="mean", name=None):
    """Multi-class margin loss: mean_j max(0, margin - x_y + x_j)^p / C
    over j != y (reference multi_margin_loss semantics)."""
    def impl(x, lab, *w, p, margin, reduction):
        n, c = x.shape
        if lab.ndim == 2 and lab.shape[-1] == 1:
            lab = jnp.squeeze(lab, -1)
        lab = lab.astype(jnp.int32)
        x_y = jnp.take_along_axis(x, lab[:, None], axis=1)
        viol = jnp.maximum(margin - x_y + x, 0.0) ** p
        if w:
            viol = viol * jnp.take(w[0], lab)[:, None]
        mask = jnp.arange(c)[None, :] != lab[:, None]
        loss = jnp.sum(jnp.where(mask, viol, 0.0), axis=1) / c
        return _reduce(loss, reduction)

    args = (input, label) + ((weight,) if weight is not None else ())
    return dispatch("multi_margin_loss", impl, args,
                    dict(p=int(p), margin=float(margin),
                         reduction=reduction))


def triplet_margin_with_distance_loss(input, positive, negative,
                                      distance_function=None, margin=1.0,
                                      swap=False, reduction="mean",
                                      name=None):
    """Triplet margin loss with a caller-supplied distance (defaults to
    L2, matching triplet_margin_loss)."""
    if distance_function is None:
        return triplet_margin_loss(input, positive, negative,
                                   margin=margin, swap=swap,
                                   reduction=reduction)
    d_ap = distance_function(input, positive)
    d_an = distance_function(input, negative)
    if swap:
        d_pn = distance_function(positive, negative)
        from ...ops._generated import minimum
        d_an = minimum(d_an, d_pn)
    from ...ops._generated import maximum
    from ...ops.math import scale
    from ...ops.creation import zeros_like
    viol = maximum(d_ap - d_an + margin, zeros_like(d_ap))
    from ...ops.reduction import mean as _mean, sum as _sum
    if reduction == "mean":
        return _mean(viol)
    if reduction == "sum":
        return _sum(viol)
    return viol


def hsigmoid_loss(input, label, num_classes, weight, bias=None,
                  path_table=None, path_code=None, is_sparse=False,
                  name=None):
    """Hierarchical sigmoid loss over the default complete binary tree
    (word2vec-style hierarchical softmax).  `is_sparse` is accepted for
    parity and runs dense by design (sparse grads are a GPU scatter
    optimization; XLA fuses the dense scatter-add).  Leaf l sits at heap node
    l + num_classes; the path to the root visits internal nodes
    idx // 2 with left/right codes idx % 2; internal node n uses
    weight[n - 1].  Custom trees ride path_table/path_code (per-sample
    [steps] int arrays; -1 padding)."""
    import numpy as np
    depth = max(1, int(np.ceil(np.log2(max(num_classes, 2)))) + 1)

    def impl(x, lab, w, *rest, num_classes, depth, has_bias, has_path):
        if lab.ndim == 2 and lab.shape[-1] == 1:
            lab = jnp.squeeze(lab, -1)
        lab = lab.astype(jnp.int32)
        if has_path:
            table, code = rest[-2], rest[-1]
            nodes = table.astype(jnp.int32)
            codes = code.astype(jnp.float32)
            valid = nodes >= 0
            nodes = jnp.maximum(nodes, 0)
        else:
            # heap walk from leaf to root, padded to fixed depth
            idx = lab + num_classes
            steps = []
            for _ in range(depth):
                parent = idx // 2
                steps.append((parent, (idx % 2).astype(jnp.float32)))
                idx = parent
            nodes = jnp.stack([s[0] for s in steps], 1)   # [N, depth]
            codes = jnp.stack([s[1] for s in steps], 1)
            valid = nodes >= 1
            nodes = jnp.maximum(nodes, 1)
            nodes = nodes - 1  # internal node n -> row n-1
        logits = jnp.einsum("nd,nsd->ns", x.astype(jnp.float32),
                            w[nodes].astype(jnp.float32))
        if has_bias:
            logits = logits + rest[0][nodes][..., 0] \
                if rest[0].ndim == 2 else logits + rest[0][nodes]
        # code 1 -> right child: P = sigmoid(-z); 0 -> sigmoid(z)
        sign = 1.0 - 2.0 * codes
        logp = jax.nn.log_sigmoid(sign * logits)
        return -jnp.sum(jnp.where(valid, logp, 0.0), axis=1,
                        keepdims=True)

    args = [input, label, weight]
    has_bias = bias is not None
    if has_bias:
        args.append(bias)
    has_path = path_table is not None and path_code is not None
    if has_path:
        args += [path_table, path_code]
    return dispatch("hsigmoid_loss", impl, tuple(args),
                    dict(num_classes=int(num_classes), depth=depth,
                         has_bias=has_bias, has_path=has_path))
