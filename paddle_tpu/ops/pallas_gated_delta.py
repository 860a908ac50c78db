"""The gated delta rule (Gated DeltaNet, arXiv:2412.06464) with a
float32 recurrent state, and the causal convolution in front of it.

Per value head, with ``q``, ``k`` L2-normalised over their lanes and
``q`` scaled by ``d_k ** -0.5``, a token at a time:

    S <- exp(g_t) S                       S in R^{d_k x d_v}, float32
    delta = beta_t (v_t - S^T k_t)
    S <- S + k_t delta^T
    o_t = S^T q_t

so the state forgets under an input-dependent decay and, before it
writes ``v_t`` under ``k_t``, takes out what it already predicts for
``k_t``.  Key head ``h // (H_v / H_k)`` serves value head ``h``.

Two forms of the same recurrence, each as a Pallas kernel and as the
``jax.numpy`` composite of the same semantics (the CPU's path and the
declined gate's):

  * **chunk** (`gated_delta_rule_fwd`): ``C`` consecutive tokens of one
    sequence after state ``S_0``, in sub-chunks of ``block`` rows.  With
    ``b_t = sum_{s<=t} g_s`` inside a sub-chunk, ``D_tj = exp(b_t - b_j)``
    for ``t >= j`` and ``A_tj = beta_t D_tj (k_t . k_j)`` for ``t > j``,
    the sub-chunk's deltas solve the unit lower-triangular system

        (I + A) U = diag(beta) (V - diag(exp b) K S_0)

    (the WY / UT form), and then ``O = diag(exp b) Q S_0 + ((Q K^T) * D)
    U`` and ``S_n = exp(b_n) S_0 + (K * exp(b_n - b))^T U``.  The state
    is read from its slot of the pool once and written once, in place;
    ``first`` starts from zero instead (a request's first chunk); rows
    past ``n_valid`` get ``g = 0`` and ``beta = 0``, which leaves the
    state as their last valid token left it;
  * **step** (`gated_delta_rule_step_fwd`): one token each for ``S``
    decode rows, every row against its own slot.

The kernel inverts ``I + A`` by blocks: the 16 x 16 diagonal blocks by
their Neumann series, which ends at the 15th power (``(I + M)(I + M^2)(I
+ M^4)(I + M^8)`` with ``M = -A``), and a pair of inverted blocks joined
through ``[[X1, 0], [-X2 E X1, X2]]``, twice: matrix products only, and
no power of a block wider than 16.

`causal_conv` is the depthwise convolution in front (XLA): what a
request carries between steps is its last ``width - 1`` inputs.

Slot 0 of a pool is the pad slot: rows that carry nothing this step
point at it, as padded tokens point at block 0 of the KV pool.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .pallas_tiles import _interpret, _kernel_span, _x32

__all__ = ["causal_conv", "split_heads", "gated_delta_block",
           "gated_delta_chunk_ref",
           "gated_delta_step_ref", "gated_delta_dense",
           "gated_delta_rule_fwd", "gated_delta_rule_step_fwd",
           "kernel_shapes_ok"]

_NT = (((1,), (1,)), ((), ()))      # a @ b.T
_NN = (((1,), (0,)), ((), ()))      # a @ b
_TN = (((0,), (0,)), ((), ()))      # a.T @ b
_BLOCK = 64                          # rows of a sub-chunk
_BASE = 16                           # a diagonal block inverted by series
_SUBLANES = 8
_EPS = 1e-6                          # the L2 norm's


def gated_delta_block(chunk_rows):
    """Rows a sub-chunk holds: the largest power of two up to 64 that
    divides the chunk's padded length."""
    b = _BLOCK
    while b > 1 and chunk_rows % b:
        b //= 2
    return b


def kernel_shapes_ok(chunk_rows, key_dim, value_dim, key_heads,
                     value_heads):
    """Whether the kernels take these shapes: whole sub-chunks of 64
    rows, heads of whole 128-lane tiles."""
    return (chunk_rows % _BLOCK == 0 and key_dim % 128 == 0
            and value_dim % 128 == 0 and value_heads % key_heads == 0)


def _l2norm(x):
    return x * jax.lax.rsqrt((x * x).sum(-1, keepdims=True) + _EPS)


def causal_conv(x, prev, weight):
    """Depthwise causal convolution and SiLU.  ``x`` [C, channels];
    ``prev`` [width - 1, channels], the inputs before ``x``; ``weight``
    [width, channels], tap ``i`` on the input ``width - 1 - i`` tokens
    back.  Returns ``(y [C, channels] in x's type, padded [C + width -
    1, channels])``; the inputs that follow token ``n`` are
    ``padded[n:n + width - 1]``."""
    width, C = weight.shape[0], x.shape[0]
    padded = jnp.concatenate([prev.astype(x.dtype), x], 0)
    w = weight.astype(jnp.float32)
    y = sum(w[i] * padded[i:i + C].astype(jnp.float32)
            for i in range(width))
    return jax.nn.silu(y).astype(x.dtype), padded


def split_heads(y, key_heads, value_heads, key_dim, value_dim):
    """``(q, k, v)`` by head from the convolution's channels ``y`` [...,
    2 Hk Dk + Hv Dv], laid out ``[q; k; v]``."""
    nq, lead = key_heads * key_dim, y.shape[:-1]
    return (y[..., :nq].reshape(lead + (key_heads, key_dim)),
            y[..., nq:2 * nq].reshape(lead + (key_heads, key_dim)),
            y[..., 2 * nq:].reshape(lead + (value_heads, value_dim)))


# ---------------------------------------------------------------------
# composites
# ---------------------------------------------------------------------
def _prepared(q, k, v, value_heads):
    """float32 ``q`` (normalised, scaled), ``k`` (normalised), each key
    head repeated to its value heads, and ``v``."""
    rep = value_heads // q.shape[-2]
    q = _l2norm(q.astype(jnp.float32)) * q.shape[-1] ** -0.5
    k = _l2norm(k.astype(jnp.float32))
    return (jnp.repeat(q, rep, -2), jnp.repeat(k, rep, -2),
            v.astype(jnp.float32))


def _masked(g, beta, n_valid):
    live = (jnp.arange(g.shape[0]) < n_valid)[:, None]
    return (jnp.where(live, g.astype(jnp.float32), 0.0),
            jnp.where(live, beta.astype(jnp.float32), 0.0))


def gated_delta_chunk_ref(q, k, v, g, beta, state, n_valid, block=None):
    """``q, k`` [C, Hk, Dk]; ``v`` [C, Hv, Dv]; ``g, beta`` [C, Hv];
    ``state`` [Hv, Dk, Dv] float32; ``n_valid`` rows are real.  Returns
    ``(o [C, Hv, Dv] in v's type, new_state)``."""
    C, H, Dv = v.shape
    block = block or gated_delta_block(C)
    q, k, vf = _prepared(q, k, v, H)
    g, beta = _masked(g, beta, n_valid)
    i = jnp.arange(block)
    lower = (i[:, None] >= i[None, :])[None]                 # [1, n, n]
    strict = (i[:, None] > i[None, :])[None]
    eye = jnp.eye(block, dtype=jnp.float32)

    def one(st, xs):
        qb, kb, vb, gb, bb = xs                              # [n, H, ...]
        b = jnp.cumsum(gb, 0).T                              # [H, n]
        diff = b[:, :, None] - b[:, None, :]
        dec = jnp.where(lower, jnp.exp(jnp.where(lower, diff, 0.0)), 0.0)
        kk = jnp.einsum("ihd,jhd->hij", kb, kb,
                        preferred_element_type=jnp.float32)
        a = jnp.where(strict, bb.T[:, :, None] * kk * dec, 0.0)
        eb = jnp.exp(b).T[:, :, None]                        # [n, H, 1]
        rhs = bb[:, :, None] * (vb - eb * jnp.einsum(
            "ihd,hde->ihe", kb, st, preferred_element_type=jnp.float32))
        u = jax.scipy.linalg.solve_triangular(
            eye + a, rhs.transpose(1, 0, 2), lower=True,
            unit_diagonal=True)                              # [H, n, Dv]
        qk = jnp.einsum("ihd,jhd->hij", qb, kb,
                        preferred_element_type=jnp.float32) * dec
        o = eb * jnp.einsum("ihd,hde->ihe", qb, st,
                            preferred_element_type=jnp.float32) \
            + jnp.einsum("hij,hje->ihe", qk, u,
                         preferred_element_type=jnp.float32)
        w = jnp.exp(b[:, -1:] - b).T[:, :, None]             # [n, H, 1]
        st = jnp.exp(b[:, -1])[:, None, None] * st + jnp.einsum(
            "jhd,hje->hde", kb * w, u, preferred_element_type=jnp.float32)
        return st, o

    nb = C // block
    cut = lambda a: a.reshape((nb, block) + a.shape[1:])    # noqa: E731
    st, o = jax.lax.scan(one, state.astype(jnp.float32),
                         tuple(map(cut, (q, k, vf, g, beta))))
    return o.reshape(C, H, Dv).astype(v.dtype), st


def gated_delta_step_ref(q, k, v, g, beta, pool, slots):
    """One token a row: ``q, k`` [S, Hk, Dk]; ``v`` [S, Hv, Dv]; ``g,
    beta`` [S, Hv]; ``pool`` [N, Hv, Dk, Dv] float32; ``slots`` [S].
    Returns ``(o [S, Hv, Dv], new_pool)``."""
    qf, kf, vf = _prepared(q, k, v, v.shape[1])
    st = jnp.exp(g.astype(jnp.float32))[..., None, None] * pool[slots]
    delta = beta.astype(jnp.float32)[..., None] * (vf - jnp.einsum(
        "shd,shde->she", kf, st, preferred_element_type=jnp.float32))
    st = st + kf[..., :, None] * delta[..., None, :]
    o = jnp.einsum("shd,shde->she", qf, st,
                   preferred_element_type=jnp.float32)
    return o.astype(v.dtype), pool.at[slots].set(st.astype(pool.dtype))


def gated_delta_dense(q, k, v, g, beta):
    """No cache: whole sequences ``[B, S, ...]`` from a zero state."""
    B, S, H, Dv = v.shape
    block = min(_BLOCK, max(8, 1 << (S - 1).bit_length()))
    pad = -S % block
    if pad:
        q, k, v, g, beta = (
            jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
            for a in (q, k, v, g, beta))
    zero = jnp.zeros((H, q.shape[-1], Dv), jnp.float32)
    o = jax.vmap(lambda *a: gated_delta_chunk_ref(
        *a, zero, S, block)[0])(q, k, v, g, beta)
    return o[:, :S]


# ---------------------------------------------------------------------
# kernels
# ---------------------------------------------------------------------
def _dot(a, b, dims=_NN):
    return jax.lax.dot_general(a, b, dims,
                               preferred_element_type=jnp.float32)


def _unit_lower_inverse(a, i, j):
    """``(I + a)^-1`` for ``a`` strictly lower triangular ``[n, n]``
    (``i``, ``j``: its row and column indices): the diagonal blocks of
    `_BASE` rows by their Neumann series, then pairs of inverted blocks
    joined until one block is left."""
    n = a.shape[0]
    base = min(_BASE, n)
    m = -jnp.where(i // base == j // base, a, 0.0)
    x = jnp.where(i == j, 1.0, 0.0) + m
    power, p = 1, m
    while 2 * power < base:              # (I + M)(I + M^2)(I + M^4) ...
        p = _dot(p, p)
        x = x + _dot(x, p)
        power *= 2
    size = base
    while size < n:
        joins = (i // (2 * size) == j // (2 * size)) \
            & (i // size != j // size)
        x = x - _dot(x, _dot(jnp.where(joins, a, 0.0), x))
        size *= 2
    return x


def _chunk_kernel(meta_ref, q_ref, k_ref, v_ref, b_ref, beta_ref, w_ref,
                  brow_ref, last_ref, pool_ref, o_ref, pool_out_ref,
                  st_ref, *, block, scale, c_last):
    c = pl.program_id(1)

    @pl.when(c == 0)
    def _load():
        keep = (meta_ref[1] == 0).astype(jnp.float32)
        st_ref[...] = pool_ref[0, 0] * keep

    q = _l2norm(q_ref[...].astype(jnp.float32)) * scale      # (block, Dk)
    k = _l2norm(k_ref[...].astype(jnp.float32))
    v = v_ref[...].astype(jnp.float32)                       # (block, Dv)
    b, beta = b_ref[...], beta_ref[...]                      # (block, 1)
    i = jax.lax.broadcasted_iota(jnp.int32, (block, block), 0)
    j = jax.lax.broadcasted_iota(jnp.int32, (block, block), 1)
    # Mosaic broadcasts along one axis at a time
    diff = jnp.broadcast_to(b, (block, block)) \
        - jnp.broadcast_to(brow_ref[0], (block, block))
    dec = jnp.where(i >= j, jnp.exp(jnp.where(i >= j, diff, 0.0)), 0.0)
    a = jnp.where(i > j, beta * _dot(k, k, _NT) * dec, 0.0)
    st = st_ref[...]
    eb = jnp.exp(b)
    u = _dot(_unit_lower_inverse(a, i, j),
             beta * (v - eb * _dot(k, st)))                  # (block, Dv)
    o = eb * _dot(q, st) + _dot(_dot(q, k, _NT) * dec, u)
    o_ref[...] = o.astype(o_ref.dtype)
    st_ref[...] = last_ref[0] * st + _dot(k * w_ref[...], u, _TN)

    @pl.when(c == c_last)
    def _store():
        pool_out_ref[0, 0] = st_ref[...]


@functools.partial(jax.jit, static_argnames=("block",))
@_x32
def _chunk_call(q, k, v, g, beta, pool, meta, *, block):
    C, Hk, Dk = q.shape
    _, H, Dv = v.shape
    nb, rep = C // block, H // Hk
    # per sub-chunk and head: the running sum of g as a column and as a
    # row, beta, exp(b_n - b), and exp(b_n) along the state's lanes
    b = jnp.cumsum(g.reshape(nb, block, H), 1)               # [nb, n, H]
    by_head = lambda a: a.transpose(2, 0, 1)                # noqa: E731
    col = lambda a: by_head(a).reshape(H * C, 1)            # noqa: E731
    last = by_head(b[:, -1:])                                # [H, nb, 1]
    cols = (col(b), col(beta.reshape(nb, block, H)),
            col(jnp.exp(b[:, -1:] - b)))
    brow = by_head(b).reshape(H * nb, 1, block)
    elast = jnp.broadcast_to(jnp.exp(last)[..., None],
                             (H, nb, 1, Dv)).reshape(H * nb, 1, Dv)
    key_row = pl.BlockSpec((block, Dk), lambda h, c, m: (c, h // rep))
    val_row = pl.BlockSpec((block, Dv), lambda h, c, m: (c, h))
    column = pl.BlockSpec((block, 1), lambda h, c, m: (h * nb + c, 0))
    row = lambda n: pl.BlockSpec(                           # noqa: E731
        (1, 1, n), lambda h, c, m: (h * nb + c, 0, 0))
    state = pl.BlockSpec((1, 1, Dk, Dv), lambda h, c, m: (m[0], h, 0, 0))
    with _kernel_span("gated_delta_rule", "fwd") as name:
        o, pool = pl.pallas_call(
            functools.partial(_chunk_kernel, block=block,
                              scale=Dk ** -0.5, c_last=nb - 1),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1,
                grid=(H, nb),
                in_specs=[key_row, key_row, val_row, column, column,
                          column, row(block), row(Dv), state],
                out_specs=[val_row, state],
                scratch_shapes=[pltpu.VMEM((Dk, Dv), jnp.float32)]),
            out_shape=[jax.ShapeDtypeStruct((C, H * Dv), v.dtype),
                       jax.ShapeDtypeStruct(pool.shape, pool.dtype)],
            # operands count the scalar-prefetch one: pool is the 10th
            input_output_aliases={9: 1},
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary")),
            interpret=_interpret(),
            name=name,
        )(meta, q.reshape(C, Hk * Dk), k.reshape(C, Hk * Dk),
          v.reshape(C, H * Dv), *cols, brow, elast, pool)
    return o.reshape(C, H, Dv), pool


def gated_delta_rule_fwd(q, k, v, g, beta, pool, slot, n_valid, first):
    """The chunk form against slot ``slot`` of ``pool`` [N, Hv, Dk, Dv],
    in place.  ``q, k`` [C, Hk, Dk], ``v`` [C, Hv, Dv], ``g, beta`` [C,
    Hv]; ``slot``, ``n_valid``, ``first`` int32 scalars (traced).
    Returns ``(o, new_pool)``.  Built through a jitted function of its
    shapes, so that the layers of a model trace and lower it once."""
    g, beta = _masked(g, beta, n_valid)
    meta = jnp.stack([jnp.asarray(x, jnp.int32).reshape(())
                      for x in (slot, first)])
    return _chunk_call(q, k, v, g, beta, pool, meta, block=_BLOCK)


def _step_kernel(slot_ref, eg_ref, beta_ref, q_ref, k_ref, v_ref,
                 pool_ref, o_ref, pool_out_ref, *, heads, rep, scale,
                 all_heads):
    row0 = jax.lax.broadcasted_iota(jnp.int32, (_SUBLANES, 1), 0) == 0
    at = pl.program_id(0) * all_heads + pl.program_id(1) * heads
    for h in range(heads):
        eg, beta = eg_ref[at + h], beta_ref[at + h]          # scalars
        q = _l2norm(q_ref[0, h // rep]) * scale              # (8, Dk)
        k = _l2norm(k_ref[0, h // rep])
        st = eg * pool_ref[0, h]
        delta = beta * (v_ref[0, h] - _dot(k, st))           # (8, Dv)
        st = st + _dot(jnp.where(row0, k, 0.0), delta, _TN)
        pool_out_ref[0, h] = st
        o_ref[0, h] = _dot(q, st)


@jax.jit
@_x32
def _step_call(q, k, v, g, beta, pool, slots):
    S, Hk, Dk = q.shape
    _, H, Dv = v.shape
    rep = H // Hk
    hb = rep * (2 if Hk % 2 == 0 else 1)     # value heads a program
    # a row's vector as 8 equal sublanes: every operand a whole tile
    wide = lambda a: jnp.broadcast_to(                      # noqa: E731
        a.astype(jnp.float32)[:, :, None, :],
        a.shape[:2] + (_SUBLANES, a.shape[2]))
    key_vec = pl.BlockSpec((1, hb // rep, _SUBLANES, Dk),
                           lambda r, n, *_: (r, n, 0, 0))
    val_vec = pl.BlockSpec((1, hb, _SUBLANES, Dv),
                           lambda r, n, *_: (r, n, 0, 0))
    state = pl.BlockSpec((1, hb, Dk, Dv),
                         lambda r, n, sl, *_: (sl[r], n, 0, 0))
    with _kernel_span("gated_delta_rule_step", "fwd") as name:
        o, pool = pl.pallas_call(
            functools.partial(_step_kernel, heads=hb, rep=rep,
                              scale=Dk ** -0.5, all_heads=H),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=3,
                grid=(S, H // hb),
                in_specs=[key_vec, key_vec, val_vec, state],
                out_specs=[val_vec, state]),
            out_shape=[jax.ShapeDtypeStruct((S, H, _SUBLANES, Dv),
                                            jnp.float32),
                       jax.ShapeDtypeStruct(pool.shape, pool.dtype)],
            input_output_aliases={6: 1},
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary", "arbitrary")),
            interpret=_interpret(),
            name=name,
        )(slots.astype(jnp.int32),
          jnp.exp(g.astype(jnp.float32)).reshape(S * H),
          beta.astype(jnp.float32).reshape(S * H),
          wide(q), wide(k), wide(v), pool)
    return o[:, :, 0, :].astype(v.dtype), pool


def gated_delta_rule_step_fwd(q, k, v, g, beta, pool, slots):
    """The one-step form: ``q, k`` [S, Hk, Dk], ``v`` [S, Hv, Dv], ``g,
    beta`` [S, Hv]; row ``r`` against slot ``slots[r]`` of ``pool``, in
    place.  Returns ``(o, new_pool)``."""
    return _step_call(q, k, v, g, beta, pool, slots)
