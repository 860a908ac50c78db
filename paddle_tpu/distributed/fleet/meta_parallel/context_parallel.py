"""Context parallelism: ring attention + Ulysses over a `sep` mesh axis.

Reference parity: the hybrid topology's `sep` degree
(`fleet/base/topology.py`) with ring/Ulysses attention implementations
historically shipped in PaddleNLP (`ring_flash_attention`) [UNVERIFIED —
empty reference mount; SURVEY.md §2.3 SEP/CP row, §5 "first-class
here"].

TPU-native design (SURVEY.md §5): the sequence dim is sharded over the
`sep` mesh axis.

* **Ring attention**: each device holds its Q shard permanently and the
  K/V shards rotate around the ICI ring with `jax.lax.ppermute`, one hop
  per step; a blockwise online-softmax accumulates (m, l, acc) so the
  result is exact attention over the full sequence with only
  S_local-sized K/V resident per step.  Causal masking uses global
  positions, so arbitrary shard counts work.  The per-step block matmuls
  are MXU-shaped einsums; compute of step r overlaps the permute of step
  r+1 under XLA's latency-hiding scheduler.
* **Ulysses**: two `all_to_all`s redistribute heads↔sequence so each
  device runs full-sequence attention over H/sep heads locally (the
  local attention can take the Pallas flash path).

Both are exposed as
  - `*_local` functions to call INSIDE shard_map / pjit-sharded code;
  - global convenience wrappers that shard_map over the current mesh.
"""
from __future__ import annotations

import functools

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ....core.tensor import Tensor
from ...env import global_mesh

__all__ = ["ring_attention_local", "ring_attention",
           "ulysses_attention_local", "ulysses_attention"]

_NEG_INF = -1e30


def ring_attention_local(q, k, v, *, axis="sep", axis_size, causal=False,
                         scale=None, use_pallas=None):
    """Exact blockwise attention; call inside shard_map.

    q/k/v: local shards [B, S_local, H, D] (Paddle layout).  Returns the
    local output shard [B, S_local, H, D].

    On TPU (Pallas gate open) each resident KV block runs through the
    Mosaic flash kernels with an exact ring backward
    (ops/ring_flash_attention.py); this jnp blockwise path is the
    fallback and the numerics oracle.
    """
    if scale is None:
        scale = 1.0 / (q.shape[-1] ** 0.5)
    if use_pallas is None:
        from ....ops.pallas_gate import pallas_enabled
        use_pallas = pallas_enabled("flash_attention", manual=True)
    if use_pallas:
        from ....ops.ring_flash_attention import ring_flash_attention_local
        return ring_flash_attention_local(
            q, k, v, axis=axis, axis_size=axis_size, causal=causal,
            scale=scale)
    me = jax.lax.axis_index(axis)
    B, S_loc, H, D = q.shape
    qs = jnp.swapaxes(q, 1, 2).astype(jnp.float32)      # B H Sq D
    k_cur = jnp.swapaxes(k, 1, 2).astype(jnp.float32)
    v_cur = jnp.swapaxes(v, 1, 2).astype(jnp.float32)

    row = me * S_loc + jnp.arange(S_loc)                # global q rows
    m = jnp.full((B, H, S_loc, 1), _NEG_INF, jnp.float32)
    l = jnp.zeros((B, H, S_loc, 1), jnp.float32)
    acc = jnp.zeros((B, H, S_loc, D), jnp.float32)

    perm = [(i, (i + 1) % axis_size) for i in range(axis_size)]
    for r in range(axis_size):
        src = (me - r) % axis_size                      # owner of k_cur
        col = src * S_loc + jnp.arange(S_loc)           # global kv cols
        s = jnp.einsum("bhqd,bhkd->bhqk", qs, k_cur,
                       preferred_element_type=jnp.float32) * scale
        if causal:
            mask = col[None, :] <= row[:, None]         # (Sq, Sk) global
            s = jnp.where(mask[None, None], s, _NEG_INF)
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m, m_cur)
        p = jnp.exp(s - m_new)
        if causal:
            p = jnp.where(mask[None, None], p, 0.0)
        alpha = jnp.exp(m - m_new)
        l = alpha * l + jnp.sum(p, axis=-1, keepdims=True)
        acc = acc * alpha + jnp.einsum(
            "bhqk,bhkd->bhqd", p, v_cur,
            preferred_element_type=jnp.float32)
        m = m_new
        if r != axis_size - 1:
            k_cur = jax.lax.ppermute(k_cur, axis, perm)
            v_cur = jax.lax.ppermute(v_cur, axis, perm)

    l_safe = jnp.where(l == 0.0, 1.0, l)
    out = (acc / l_safe).astype(q.dtype)
    return jnp.swapaxes(out, 1, 2)                      # B S H D


def ulysses_attention_local(q, k, v, *, axis="sep", axis_size,
                            causal=False, scale=None, dropout_p=0.0):
    """Ulysses: all_to_all heads↔sequence, full-seq attention locally.

    Requires num_heads % axis_size == 0.  Call inside shard_map with
    local shards [B, S_local, H, D]; returns [B, S_local, H, D].
    """
    B, S_loc, H, D = q.shape
    if H % axis_size != 0:
        raise ValueError(f"num_heads {H} not divisible by sep={axis_size}")
    a2a = functools.partial(jax.lax.all_to_all, axis_name=axis,
                            split_axis=2, concat_axis=1, tiled=True)
    qg, kg, vg = a2a(q), a2a(k), a2a(v)                 # B S_glob H/P D
    from ....nn.functional.flash_attention import _sdpa_ref
    out = _sdpa_ref(qg, kg, vg, None, causal,
                    scale or 1.0 / (D ** 0.5))
    return jax.lax.all_to_all(out, axis_name=axis, split_axis=1,
                              concat_axis=2, tiled=True)


_WRAPPER_CACHE: dict = {}


def _global_wrapper(local_fn, q, k, v, sep_axis, causal, scale, mesh):
    mesh = mesh or global_mesh()
    if mesh is None or sep_axis not in mesh.axis_names:
        raise ValueError(
            f"ring/ulysses attention needs a mesh with a '{sep_axis}' "
            f"axis (got {mesh and mesh.axis_names})")
    axis_size = mesh.shape[sep_axis]
    # cache the shard_mapped callable so repeated eager calls hit jax's
    # trace/compile cache instead of re-tracing the ring loop each step
    key = (local_fn, mesh, sep_axis, axis_size, causal, scale)
    fn = _WRAPPER_CACHE.get(key)
    if fn is None:
        spec = P(None, sep_axis, None, None)            # shard seq dim
        fn = jax.shard_map(
            functools.partial(local_fn, axis=sep_axis,
                              axis_size=axis_size, causal=causal,
                              scale=scale),
            mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
            check_vma=False)
        _WRAPPER_CACHE[key] = fn
    if any(isinstance(x, Tensor) for x in (q, k, v)):
        # through the dispatch layer so the eager tape records a grad
        # node (jax.vjp differentiates through shard_map/ppermute)
        from ....core.dispatch import dispatch
        from ....core.tensor import Tensor as T
        args = tuple(x if isinstance(x, T)
                     else T(jnp.asarray(x), _internal=True,
                            stop_gradient=True)
                     for x in (q, k, v))
        return dispatch(getattr(local_fn, "__name__", "ring_attention"),
                        lambda qv, kv, vv: fn(qv, kv, vv), args, {})
    return fn(*(jnp.asarray(x) for x in (q, k, v)))


def ring_attention(q, k, v, *, causal=False, scale=None, sep_axis="sep",
                   mesh=None):
    """Global-view ring attention: q/k/v [B, S, H, D] get seq-sharded
    over the sep axis; output is the global [B, S, H, D]."""
    return _global_wrapper(ring_attention_local, q, k, v, sep_axis,
                           causal, scale, mesh)


def ulysses_attention(q, k, v, *, causal=False, scale=None,
                      sep_axis="sep", mesh=None):
    """Global-view Ulysses attention (two all_to_alls + local SDPA)."""
    return _global_wrapper(ulysses_attention_local, q, k, v, sep_axis,
                           causal, scale, mesh)
