"""Ring flash attention: the Pallas flash kernel blockwise over a ring.

Role of PaddleNLP's `ring_flash_attention` (per-rank KV rotation via
P2P, blockwise softmax accumulation [UNVERIFIED — empty reference
mount; SURVEY.md §2.3 SEP/CP row, §5 long-context]).

TPU-native: each device keeps its Q shard; K/V shards rotate around the
ICI ring with `jax.lax.ppermute`.  Every resident block is processed by
the SAME Mosaic flash-attention kernels used for local attention
(ops/pallas_kernels.py) — MXU-tiled, online-softmax — and the per-block
(out, lse) pairs are combined exactly via logsumexp reweighting.  The
backward is the true ring flash backward: the dq/dkv Pallas kernels run
per resident block against the GLOBAL lse/delta, dk/dv partials rotate
along with their K/V block, and one final ppermute delivers them home.

Causal structure on the ring (P shards, this device = `me`, ring step
r holds the block of device `src = (me - r) mod P`):
  r == 0           → the diagonal block: ordinary causal attention;
  1 <= r <= me     → a fully visible block (causal=False);
  r > me           → fully masked: contributes nothing (lax.cond skips
                     the kernel and yields -inf lse / zero grads).
Non-causal rings use the full flavor at every step.

Call `ring_flash_attention_local` inside shard_map (layout [B, S_local,
H, D]); `paddle_tpu.distributed...context_parallel.ring_attention`
routes here when the Pallas gate is open, with the jnp blockwise
implementation as the fallback.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .pallas_kernels import (_NEG_INF, _STAT_LANES, _flash_bwd,
                             _flash_fwd, _pad_dim, _pick_block,
                             _round_up, _demote_f64)

__all__ = ["ring_flash_attention_local"]


def _combine(out_run, lse_run, out_r, lse_r):
    """Merge a new normalized block result via logsumexp reweighting.

    lse arrays are in the (BH, S_pad, _STAT_LANES) stat-lane layout;
    `_NEG_INF` marks rows/blocks with no visible keys."""
    lse_new = jnp.logaddexp(lse_run, lse_r)
    dead_run = lse_run <= _NEG_INF / 2
    dead_r = lse_r <= _NEG_INF / 2
    w_run = jnp.where(dead_run, 0.0, jnp.exp(lse_run - lse_new))[..., :1]
    w_r = jnp.where(dead_r, 0.0, jnp.exp(lse_r - lse_new))[..., :1]
    out_new = (out_run.astype(jnp.float32) * w_run
               + out_r.astype(jnp.float32) * w_r)
    # rows dead in BOTH stay dead (lse ~ 2*_NEG_INF after logaddexp)
    lse_new = jnp.where(dead_run & dead_r, _NEG_INF, lse_new)
    return out_new, lse_new


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _ring_flash_bhsd(q, k, v, scale, causal, axis, axis_size):
    out, _ = _ring_flash_bhsd_fwd(q, k, v, scale, causal, axis,
                                  axis_size)
    return out


def _ring_flash_bhsd_fwd(q, k, v, scale, causal, axis, axis_size):
    bh, s, d = q.shape
    bq = _pick_block(s, 0, q.dtype, d)
    bk = _pick_block(s, 1, q.dtype, d)
    s_pad = _round_up(s, bq)
    qp = _pad_dim(q, 1, s_pad)
    me = jax.lax.axis_index(axis)
    perm = [(i, (i + 1) % axis_size) for i in range(axis_size)]

    k_cur, v_cur = k, v
    out_run = jnp.zeros((bh, s_pad, d), jnp.float32)
    lse_run = jnp.full((bh, s_pad, _STAT_LANES), _NEG_INF, jnp.float32)

    for r in range(axis_size):
        kp = _pad_dim(k_cur, 1, _round_up(s, bk))
        vp = _pad_dim(v_cur, 1, _round_up(s, bk))

        def _block(kp=kp, vp=vp, diag=(r == 0)):
            return _flash_fwd(qp, kp, vp, scale, causal and diag,
                              s, s, bq, bk)

        if causal and r > 0:
            o_r, lse_r = jax.lax.cond(
                me >= r, lambda: _block(),
                lambda: (jnp.zeros((bh, s_pad, d), q.dtype),
                         jnp.full((bh, s_pad, _STAT_LANES), _NEG_INF,
                                  jnp.float32)))
        else:
            o_r, lse_r = _block()
        out_run, lse_run = _combine(out_run, lse_run, o_r, lse_r)
        if r != axis_size - 1:
            k_cur = jax.lax.ppermute(k_cur, axis, perm)
            v_cur = jax.lax.ppermute(v_cur, axis, perm)

    out = out_run.astype(q.dtype)
    return out[:, :s], (q, k, v, out, lse_run)


def _ring_flash_bhsd_bwd(scale, causal, axis, axis_size, res, g):
    q, k, v, out_pad, lse_tot = res
    bh, s, d = q.shape
    bq = _pick_block(s, 0, q.dtype, d)
    bk = _pick_block(s, 1, q.dtype, d)
    s_pad = _round_up(s, bq)
    qp = _pad_dim(q, 1, s_pad)
    gp = _pad_dim(g, 1, s_pad)
    me = jax.lax.axis_index(axis)
    perm = [(i, (i + 1) % axis_size) for i in range(axis_size)]

    k_cur, v_cur = k, v
    dq = jnp.zeros((bh, s, d), jnp.float32)
    dk_cur = jnp.zeros((bh, s, d), jnp.float32)
    dv_cur = jnp.zeros((bh, s, d), jnp.float32)

    for r in range(axis_size):
        kp = _pad_dim(k_cur, 1, _round_up(s, bk))
        vp = _pad_dim(v_cur, 1, _round_up(s, bk))

        def _block(kp=kp, vp=vp, diag=(r == 0)):
            # global out/lse → _flash_bwd's internal delta and p are the
            # GLOBAL softmax restricted to this block: the exact ring
            # flash backward decomposition
            dq_p, dk_p, dv_p = _flash_bwd(
                qp, kp, vp, gp, out_pad, lse_tot, scale,
                causal and diag, s, s, bq, bk)
            return dq_p[:, :s], dk_p[:, :s], dv_p[:, :s]

        if causal and r > 0:
            dq_r, dk_r, dv_r = jax.lax.cond(
                me >= r, lambda: _block(),
                lambda: (jnp.zeros((bh, s, d), q.dtype),
                         jnp.zeros((bh, s, d), k.dtype),
                         jnp.zeros((bh, s, d), v.dtype)))
        else:
            dq_r, dk_r, dv_r = _block()
        dq = dq + dq_r.astype(jnp.float32)
        dk_cur = dk_cur + dk_r.astype(jnp.float32)
        dv_cur = dv_cur + dv_r.astype(jnp.float32)
        if r != axis_size - 1:
            k_cur = jax.lax.ppermute(k_cur, axis, perm)
            v_cur = jax.lax.ppermute(v_cur, axis, perm)
            dk_cur = jax.lax.ppermute(dk_cur, axis, perm)
            dv_cur = jax.lax.ppermute(dv_cur, axis, perm)

    # dk_cur on device i now holds the full grads of block (i+1) mod P;
    # one more hop delivers every block's grads to its owner
    dk_home = jax.lax.ppermute(dk_cur, axis, perm)
    dv_home = jax.lax.ppermute(dv_cur, axis, perm)
    return (dq.astype(q.dtype), dk_home.astype(k.dtype),
            dv_home.astype(v.dtype))


_ring_flash_bhsd.defvjp(_ring_flash_bhsd_fwd, _ring_flash_bhsd_bwd)


def ring_flash_attention_local(q, k, v, *, axis, axis_size,
                               causal=False, scale=None):
    """Pallas ring flash attention; call inside shard_map.

    q/k/v: local shards [B, S_local, H, D]; returns [B, S_local, H, D].
    """
    if scale is None:
        scale = 1.0 / (q.shape[-1] ** 0.5)
    q, k, v = _demote_f64(q, k, v)
    b, s, h, d = q.shape
    qt = jnp.swapaxes(q, 1, 2).reshape(b * h, s, d)
    kt = jnp.swapaxes(k, 1, 2).reshape(b * h, s, d)
    vt = jnp.swapaxes(v, 1, 2).reshape(b * h, s, d)
    out = _ring_flash_bhsd(qt, kt, vt, float(scale), bool(causal),
                           axis, int(axis_size))
    return jnp.swapaxes(out.reshape(b, h, s, d), 1, 2).astype(q.dtype)
