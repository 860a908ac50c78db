"""Pallas kernel parity vs XLA reference compositions (interpret mode on
CPU; same code compiles via Mosaic on TPU)."""
import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.ops import pallas_kernels as pk


def _sdpa_ref(q, k, v, causal, scale):
    b, sq, h, d = q.shape
    sk = k.shape[1]
    qt = jnp.swapaxes(q, 1, 2).astype(jnp.float32)
    kt = jnp.swapaxes(k, 1, 2).astype(jnp.float32)
    vt = jnp.swapaxes(v, 1, 2).astype(jnp.float32)
    s = jnp.einsum("bhqd,bhkd->bhqk", qt, kt) * scale
    if causal:
        mask = jnp.tril(jnp.ones((sq, sk), bool), k=sk - sq)
        s = jnp.where(mask, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhqk,bhkd->bhqd", p, vt)
    return jnp.swapaxes(o, 1, 2).astype(q.dtype)


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("shape", [
    (2, 64, 2, 32),      # small, uneven vs 128 blocks
    (1, 100, 1, 64),     # non-multiple seq, head_dim 64
])
def test_flash_attention_forward(shape, causal):
    b, s, h, d = shape
    key = jax.random.PRNGKey(0)
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, shape, jnp.float32)
    k = jax.random.normal(kk, shape, jnp.float32)
    v = jax.random.normal(kv, shape, jnp.float32)
    out = pk.flash_attention(q, k, v, causal=causal)
    ref = _sdpa_ref(q, k, v, causal, 1.0 / d ** 0.5)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_flash_attention_cross_lengths():
    key = jax.random.PRNGKey(1)
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (1, 24, 2, 32), jnp.float32)
    k = jax.random.normal(kk, (1, 40, 2, 32), jnp.float32)
    v = jax.random.normal(kv, (1, 40, 2, 32), jnp.float32)
    out = pk.flash_attention(q, k, v, causal=True)
    ref = _sdpa_ref(q, k, v, True, 1.0 / 32 ** 0.5)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_flash_attention_causal_sq_gt_sk_grad():
    """Sq > Sk causal: leading rows see no keys; grads must be 0 there,
    not garbage (regression for the empty-row lse backward bug)."""
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(11), 3)
    q = jax.random.normal(kq, (1, 48, 1, 32), jnp.float32)
    k = jax.random.normal(kk, (1, 16, 1, 32), jnp.float32)
    v = jax.random.normal(kv, (1, 16, 1, 32), jnp.float32)
    out = pk.flash_attention(q, k, v, causal=True)
    # rows 0..31 attend to nothing → output 0 (flash-attn convention)
    np.testing.assert_allclose(np.asarray(out[:, :32]), 0.0, atol=1e-6)

    def f(q, k, v):
        o = pk.flash_attention(q, k, v, causal=True)
        return jnp.sum(o[:, 32:] ** 2)  # only rows with visible keys

    def f_ref(q, k, v):
        o = _sdpa_ref(q, k, v, True, 1.0 / 32 ** 0.5)
        return jnp.sum(o[:, 32:] ** 2)

    g = jax.grad(f, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    np.testing.assert_allclose(np.asarray(g[0][:, :32]), 0.0, atol=1e-6)
    for a, b in zip(g, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_attention_grad(causal):
    shape = (1, 48, 2, 32)
    key = jax.random.PRNGKey(2)
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, shape, jnp.float32)
    k = jax.random.normal(kk, shape, jnp.float32)
    v = jax.random.normal(kv, shape, jnp.float32)

    def f_pl(q, k, v):
        return jnp.sum(pk.flash_attention(q, k, v, causal=causal) ** 2)

    def f_ref(q, k, v):
        return jnp.sum(_sdpa_ref(q, k, v, causal, 1.0 / 32 ** 0.5) ** 2)

    g_pl = jax.grad(f_pl, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_pl, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-4, rtol=1e-4)


# -- attention dropout inside the flash kernels (interpret mode: the
# hash bit source; chip_smoke.py checks the chip's generator) ----------

def _masked_ref(q, k, v, keep, causal, p):
    """nn.functional's composite under an explicit keep mask."""
    from paddle_tpu.nn.functional.flash_attention import _sdpa_ref as ref
    return ref(q, k, v, None, causal, 1.0 / q.shape[-1] ** 0.5, p,
               keep=keep)


def _qkv(shape, dtype, seed=5):
    return [jax.random.normal(kk, shape, jnp.float32).astype(dtype)
            for kk in jax.random.split(jax.random.PRNGKey(seed), 3)]


@pytest.mark.parametrize("p", [0.1, 0.5])
def test_flash_dropout_keep_rate(p):
    """Each element keeps with probability 1 - p: the rate over 2 x 2 x
    200 x 200 draws is within 4 sigma, and so is every tile's."""
    b, s, h, d = 2, 200, 2, 32
    with jax.enable_x64(False):
        keep = np.asarray(pk.flash_dropout_keep(
            jnp.array([7], jnp.int32), b, s, s, h, d, dropout_p=p))
    assert keep.shape == (b, h, s, s) and keep.dtype == bool
    sigma = (p * (1 - p) / keep.size) ** 0.5
    assert abs(keep.mean() - (1 - p)) < 4 * sigma
    tile = keep[:, :, :128, :128]
    sigma = (p * (1 - p) / (128 * 128)) ** 0.5
    assert np.abs(tile.mean(axis=(2, 3)) - (1 - p)).max() < 4 * sigma
    # no row, column, head or tile repeats another
    assert not (keep[0, 0] == keep[0, 1]).all()
    assert not (keep[0, 0, :64, :64] == keep[0, 0, 128:192, 128:192]).all()
    assert not (keep[0, 0, 0] == keep[0, 0, 1]).all()


def test_flash_dropout_seed_reproduces():
    q, k, v = _qkv((1, 100, 2, 32), jnp.float32)
    with jax.enable_x64(False):
        a, b, c = (np.asarray(pk.flash_attention(
            q, k, v, dropout_p=0.5, seed=jnp.array([sd], jnp.int32)))
            for sd in (3, 3, 4))
    np.testing.assert_array_equal(a, b)
    assert not np.allclose(a, c)
    with pytest.raises(ValueError, match="needs a seed"):
        pk.flash_attention(q, k, v, dropout_p=0.5)


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("dtype,tol,gtol", [
    (jnp.float32, dict(atol=1e-5, rtol=1e-5), dict(atol=1e-5, rtol=1e-5)),
    (jnp.bfloat16, dict(atol=3e-2, rtol=3e-2), dict(atol=1e-1, rtol=6e-2)),
])
def test_flash_dropout_matches_masked_composite(dtype, tol, gtol, causal):
    """Forward, dq, dk, dv equal `_sdpa_ref` under the written-out mask
    of the same tile stream; 200 is no multiple of the 128 block."""
    shape, p = (2, 200, 2, 32), 0.1
    q, k, v = _qkv(shape, dtype)
    seed = jnp.array([1234], jnp.int32)
    f32 = lambda x: np.asarray(x, np.float32)  # noqa: E731
    with jax.enable_x64(False):
        keep = pk.flash_dropout_keep(seed, 2, 200, 200, 2, 32,
                                     dropout_p=p, dtype=dtype)
        out = pk.flash_attention(q, k, v, causal=causal, dropout_p=p,
                                 seed=seed)
        ref = _masked_ref(q, k, v, keep, causal, p)
        assert out.dtype == dtype
        np.testing.assert_allclose(f32(out), f32(ref), **tol)
        # mean, not sum, of squares keeps float32 gradients near 1
        g = jax.grad(lambda q, k, v: jnp.mean(pk.flash_attention(
            q, k, v, causal=causal, dropout_p=p,
            seed=seed).astype(jnp.float32) ** 2) * 1e3, (0, 1, 2))(q, k, v)
        g_ref = jax.grad(lambda q, k, v: jnp.mean(_masked_ref(
            q, k, v, keep, causal, p).astype(jnp.float32) ** 2) * 1e3,
            (0, 1, 2))(q, k, v)
    for a, b in zip(g, g_ref):
        np.testing.assert_allclose(f32(a), f32(b), **gtol)


def _primitives(jaxpr, out):
    """Count of every primitive in `jaxpr`, nested jaxprs included
    (custom_vjp, pjit, pallas_call, loops)."""
    for eqn in jaxpr.eqns:
        out[eqn.primitive.name] += 1
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else [v]):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    _primitives(sub, out)
    return out


def test_flash_without_dropout_traces_no_prng(monkeypatch):
    """dropout_p == 0: no generator primitive and no hash in any of the
    three kernels; dropout_p > 0 on the chip's path: prng_seed and
    prng_random_bits in all three."""
    import collections
    monkeypatch.setattr(pk, "_interpret", lambda: False)  # the chip's body
    # the jitted builders key their traces on shapes, not on _interpret
    jax.clear_caches()
    q, k, v = _qkv((1, 128, 1, 64), jnp.bfloat16)
    seed = jnp.array([1], jnp.int32)

    def grads(p):
        return _primitives(jax.make_jaxpr(jax.grad(
            lambda q, k, v: pk.flash_attention(
                q, k, v, dropout_p=p, seed=seed if p else None).astype(
                    jnp.float32).sum(), (0, 1, 2)))(q, k, v).jaxpr,
            collections.Counter())

    with jax.enable_x64(False):
        plain, dropped = grads(0.0), grads(0.1)
    jax.clear_caches()
    assert plain["pallas_call"] == dropped["pallas_call"] == 3
    assert not {"prng_seed", "prng_random_bits", "shift_right_logical",
                "random_bits", "threefry2x32"} & set(plain)
    # fwd, dq, dkv each reseed and draw once per tile
    assert dropped["prng_seed"] == dropped["prng_random_bits"] == 3


@pytest.mark.parametrize("seq,dtype,head_dim,want", [
    (512, jnp.bfloat16, 64, 512),     # the BERT cells: one block
    (1024, jnp.bfloat16, 64, 512),
    (384, jnp.bfloat16, 64, 384),     # no padding beyond 128-row blocks
    (200, jnp.bfloat16, 64, 256),
    (640, jnp.bfloat16, 64, 128),     # 5 x 128: nothing larger divides
    (512, jnp.float32, 64, 128),      # not swept: as before
    (512, jnp.bfloat16, 128, 128),
    (100, jnp.bfloat16, 64, 112),
    (48, jnp.float32, 32, 48),
])
def test_flash_block_table(seq, dtype, head_dim, want):
    assert pk._pick_block(seq, 0, dtype, head_dim) == want
    assert pk._pick_block(seq, 1, dtype, head_dim) == want
    plan = pk.flash_block_plan(1, seq, seq, 1, head_dim, dtype=dtype)
    assert (plan["block_q"], plan["block_k"]) == (want, want)
    pk.set_flash_block_sizes(64, 32)          # the sweep's override wins
    try:
        assert pk._pick_block(seq, 0, dtype, head_dim) == min(
            64, pk._round_up(seq, 16))
    finally:
        pk.set_flash_block_sizes(None, None)


def test_flash_attention_bf16_one_block_of_384():
    """A length the table serves with one 384-row block, with dropout:
    forward against the composite under the written-out mask."""
    q, k, v = _qkv((1, 384, 1, 64), jnp.bfloat16)
    seed = jnp.array([9], jnp.int32)
    with jax.enable_x64(False):
        keep = pk.flash_dropout_keep(seed, 1, 384, 384, 1, 64,
                                     dropout_p=0.1, dtype=jnp.bfloat16)
        out = pk.flash_attention(q, k, v, causal=True, dropout_p=0.1,
                                 seed=seed)
        ref = _masked_ref(q, k, v, keep, True, 0.1)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               atol=3e-2, rtol=3e-2)


def test_fused_layer_norm():
    key = jax.random.PRNGKey(3)
    x = jax.random.normal(key, (37, 96), jnp.float32) * 3 + 1
    gamma = jax.random.normal(jax.random.PRNGKey(4), (96,)) + 1
    beta = jax.random.normal(jax.random.PRNGKey(5), (96,))

    def ref(x, g, b):
        mu = jnp.mean(x, -1, keepdims=True)
        var = jnp.var(x, -1, keepdims=True)
        return (x - mu) * jax.lax.rsqrt(var + 1e-5) * g + b

    out = pk.fused_layer_norm(x, gamma, beta, eps=1e-5)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref(x, gamma,
                               beta)), atol=1e-5, rtol=1e-5)

    def loss_pl(x, g, b):
        return jnp.sum(jnp.sin(pk.fused_layer_norm(x, g, b)))

    def loss_ref(x, g, b):
        return jnp.sum(jnp.sin(ref(x, g, b)))

    gp = jax.grad(loss_pl, argnums=(0, 1, 2))(x, gamma, beta)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(x, gamma, beta)
    for a, b in zip(gp, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-4, rtol=1e-4)


def test_fused_rms_norm():
    x = jax.random.normal(jax.random.PRNGKey(6), (20, 64), jnp.float32)
    gamma = jax.random.normal(jax.random.PRNGKey(7), (64,)) + 1

    def ref(x, g):
        ms = jnp.mean(x * x, -1, keepdims=True)
        return x * jax.lax.rsqrt(ms + 1e-6) * g

    out = pk.fused_rms_norm(x, gamma, eps=1e-6)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref(x, gamma)),
                               atol=1e-5, rtol=1e-5)
    gp = jax.grad(lambda x, g: jnp.sum(pk.fused_rms_norm(x, g) ** 2),
                  argnums=(0, 1))(x, gamma)
    gr = jax.grad(lambda x, g: jnp.sum(ref(x, g) ** 2),
                  argnums=(0, 1))(x, gamma)
    for a, b in zip(gp, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-4, rtol=1e-4)


def _xent_ref(x, y):
    """Float32 logsumexp reference; labels < 0 are ignored."""
    x = x.astype(jnp.float32)
    lse = jax.nn.logsumexp(x, axis=-1)
    picked = jnp.take_along_axis(x, jnp.maximum(y, 0)[:, None], 1)[:, 0]
    return jnp.where(y >= 0, lse - picked, 0.0)


def test_fused_softmax_cross_entropy():
    logits = jax.random.normal(jax.random.PRNGKey(8), (33, 50),
                               jnp.float32)
    labels = jax.random.randint(jax.random.PRNGKey(9), (33,), 0, 50)
    ref = _xent_ref
    loss = pk.fused_softmax_cross_entropy(logits, labels)
    np.testing.assert_allclose(np.asarray(loss),
                               np.asarray(ref(logits, labels)),
                               atol=1e-5, rtol=1e-5)
    gp = jax.grad(lambda x: jnp.mean(
        pk.fused_softmax_cross_entropy(x, labels)))(logits)
    gr = jax.grad(lambda x: jnp.mean(ref(x, labels)))(logits)
    np.testing.assert_allclose(np.asarray(gp), np.asarray(gr),
                               atol=1e-5, rtol=1e-5)


def test_xent_ignore_index():
    logits = jax.random.normal(jax.random.PRNGKey(10), (8, 10))
    labels = jnp.array([1, 2, -1, 3, -1, 0, 9, 4])
    loss = pk.fused_softmax_cross_entropy(logits, labels)
    assert float(loss[2]) == 0.0 and float(loss[4]) == 0.0
    g = jax.grad(lambda x: jnp.sum(
        pk.fused_softmax_cross_entropy(x, labels)))(logits)
    assert float(jnp.abs(g[2]).sum()) == 0.0


def test_xent_multi_vocab_block():
    """V=3000 > block_v=2048 → exercises the online-logsumexp scratch
    accumulator across vocab grid steps, the -inf vocab padding, and
    the per-block label column offset (the r3 kernel rewrite; a single
    vocab block cannot catch a regression there)."""
    v = 3000
    logits = jax.random.normal(jax.random.PRNGKey(11), (37, v),
                               jnp.float32)
    labels = jax.random.randint(jax.random.PRNGKey(12), (37,), 0, v)
    # labels on both sides of the 2048 block boundary
    labels = labels.at[0].set(2047).at[1].set(2048).at[2].set(v - 1)
    labels = labels.at[3].set(-1)  # ignore row
    ref = _xent_ref
    loss = pk.fused_softmax_cross_entropy(logits, labels)
    np.testing.assert_allclose(np.asarray(loss),
                               np.asarray(ref(logits, labels)),
                               atol=1e-5, rtol=1e-5)
    gp = jax.grad(lambda x: jnp.sum(
        pk.fused_softmax_cross_entropy(x, labels)))(logits)
    gr = jax.grad(lambda x: jnp.sum(ref(x, labels)))(logits)
    np.testing.assert_allclose(np.asarray(gp), np.asarray(gr),
                               atol=1e-5, rtol=1e-5)
    assert float(jnp.abs(gp[3]).sum()) == 0.0  # ignored row: zero grad


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("rows,v", [
    (33, 50),            # one partial block both ways, block wider than v
    (37, 3000),          # two vocab blocks, the second partial
    (40, 700),           # one partial vocab block past a lane multiple
    (256, 4096),         # aligned both ways: no mask is traced
    (300, 2048 + 10),    # two row blocks, ten columns in the last block
])
def test_xent_at_the_logits_own_shape(rows, v, dtype):
    """The kernels read the logits and write their gradient at [rows, v]
    with no padded copy: what a partial block holds past either edge
    must reach neither the loss nor a kept gradient.  Labels sit on both
    sides of the 2048-column block edge and in the last (partial) block;
    ignored rows sit in the first row block and in the last partial
    one."""
    bv = min(v, 2048)
    logits = (4.0 * jax.random.normal(jax.random.PRNGKey(13), (rows, v),
                                      jnp.float32)).astype(dtype)
    labels = jax.random.randint(jax.random.PRNGKey(14), (rows,), 0, v)
    labels = labels.at[0].set(bv - 1).at[1].set(min(bv, v - 1))
    labels = labels.at[2].set(v - 1).at[4].set(0)
    ignored = [3, rows - 2]
    labels = labels.at[jnp.array(ignored)].set(-1)
    weights = jax.random.uniform(jax.random.PRNGKey(15), (rows,))

    loss = pk.fused_softmax_cross_entropy(logits, labels)
    np.testing.assert_allclose(np.asarray(loss),
                               np.asarray(_xent_ref(logits, labels)),
                               atol=2e-5, rtol=1e-5)
    assert loss.dtype == jnp.float32
    assert not np.asarray(loss)[ignored].any()

    got = jax.grad(lambda x: jnp.sum(
        weights * pk.fused_softmax_cross_entropy(x, labels)))(logits)
    want = jax.grad(lambda x: jnp.sum(
        weights * _xent_ref(x, labels)))(logits.astype(jnp.float32))
    assert got.shape == (rows, v) and got.dtype == dtype
    # the gradient is rounded once, to the logits' dtype
    np.testing.assert_allclose(
        np.asarray(got.astype(jnp.float32)), np.asarray(want),
        atol=1e-6 if dtype == jnp.float32 else 4e-3, rtol=1e-5)
    assert not np.asarray(got.astype(jnp.float32))[ignored].any()


def _eqns(jaxpr):
    """Every equation of a jaxpr and of the jaxprs in its parameters
    (custom_vjp bodies, pallas_call kernels, pjit, cond branches)."""
    for eqn in jaxpr.eqns:
        yield eqn
        stack = list(eqn.params.values())
        while stack:
            p = stack.pop()
            if isinstance(p, (tuple, list)):
                stack.extend(p)
            elif hasattr(p, "eqns"):
                yield from _eqns(p)
            elif hasattr(p, "jaxpr") and hasattr(p.jaxpr, "eqns"):
                yield from _eqns(p.jaxpr)


def _xent_grad_jaxpr(rows, v):
    return jax.make_jaxpr(jax.grad(lambda x, y: jnp.sum(
        pk.fused_softmax_cross_entropy(x, y))))(
            jax.ShapeDtypeStruct((rows, v), jnp.float32),
            jax.ShapeDtypeStruct((rows,), jnp.int32)).jaxpr


def test_xent_makes_no_padded_copy_of_the_logits():
    """At BERT's head (8192 x 30522, traced and not run) the forward and
    the backward hold no `pad` and no `slice` over a matrix of the
    logits' size: the two padded copies and the slice of the padded
    gradient were 8.4 ms of an 88.9 ms step (ledger, PR 30)."""
    rows, v = 8192, 30522
    eqns = list(_eqns(_xent_grad_jaxpr(rows, v)))
    assert sum(e.primitive.name == "pallas_call" for e in eqns) == 2
    big = [(e.primitive.name, a.aval.shape) for e in eqns
           if e.primitive.name in ("pad", "slice", "dynamic_slice",
                                   "concatenate")
           for a in list(e.invars) + list(e.outvars)
           if len(getattr(a.aval, "shape", ())) == 2
           and a.aval.shape[0] >= rows and a.aval.shape[1] >= v]
    assert not big, big


def test_xent_edge_mask_is_elided_when_aligned():
    """An aligned vocabulary pays nothing: the edge mask is one
    `select_n` in each kernel at 4096 + 10 columns and is not traced at
    4096, where the kernels are the ones the padded form ran."""
    def selects(v):
        return [sum(e.primitive.name == "select_n"
                    for e in _eqns(call.params["jaxpr"]))
                for call in _eqns(_xent_grad_jaxpr(512, v))
                if call.primitive.name == "pallas_call"]
    aligned, ragged = selects(4096), selects(4096 + 10)
    assert len(aligned) == len(ragged) == 2
    assert [r - a for a, r in zip(aligned, ragged)] == [1, 1]


# ---------------------------------------------------------------------
# Ragged mixed prefill+decode attention (pallas_ragged)
# ---------------------------------------------------------------------
def _ragged_case(query_lens, context_lens, dtype, seed=30, H=4, D=32,
                 bs=16, W=4, pad_blocks=0, int8=False, window=None):
    """Build a ragged batch + paged pool ``[nb, bs, H * D]`` and return
    (kernel, fallback) outputs at the given dtype (``int8``: an int8
    pool with per-slot scales under queries of ``dtype``)."""
    from paddle_tpu.inference.serving.attention import _ragged_ref
    from paddle_tpu.ops import pallas_ragged as pr

    block_q = pr.ragged_q_block(dtype)
    S = len(query_lens)
    sid, qs, qv, _, rows = pr.ragged_segments(query_lens, context_lens,
                                              block_q)
    nqb = len(sid) + pad_blocks
    sid, qs, qv, _, _ = pr.ragged_segments(query_lens, context_lens,
                                           block_q, num_q_blocks=nqb,
                                           num_seqs=S)
    nb = S * W + 1
    kq, kk, kv, ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    q = jax.random.normal(kq, (nqb * block_q, H, D),
                          jnp.float32).astype(dtype)
    scales = {}
    if int8:
        k_pool, v_pool = (jax.random.randint(
            k, (nb, bs, H * D), -127, 128, jnp.int32).astype(jnp.int8)
            for k in (kk, kv))
        for name, k in zip(("k_scales", "v_scales"), jax.random.split(ks)):
            scales[name] = jax.random.uniform(
                k, (nb, bs, pr.KV_SCALE_LANES), jnp.float32, 0.002, 0.02)
    else:
        k_pool, v_pool = (jax.random.normal(
            k, (nb, bs, H * D), jnp.float32).astype(dtype)
            for k in (kk, kv))
    tables = np.zeros((S, W), np.int32)
    for s, ctx in enumerate(context_lens):
        for w in range(-(-int(ctx) // bs)):
            tables[s, w] = 1 + s * W + w
    bt = jnp.asarray(tables)
    cl = jnp.asarray(np.asarray(context_lens, np.int32))
    sid, qs, qv = jnp.asarray(sid), jnp.asarray(qs), jnp.asarray(qv)
    scale = 1.0 / D ** 0.5
    out = pr.ragged_paged_attention(q, k_pool, v_pool, bt, cl, sid, qs,
                                    qv, block_q=block_q, scale=scale,
                                    window=window, **scales)
    ref = _ragged_ref(q, k_pool, v_pool, bt, cl, sid, qs, qv, block_q,
                      scale, window=window, **scales)
    return np.asarray(out, np.float32), np.asarray(ref, np.float32)


def _grouped_case(form, dtype, contexts, seed=31, kv_heads=2, group=2,
                  D=64, bs=16, W=8, window=None):
    """The grouped engines' calls (`serving.attention`), kernel against
    fallback: ``decode`` rows of one token whose ``group`` heads go as
    the rows of a q-block (``block_tokens`` 1), a ``chunk`` of 48 tokens
    in q-blocks of 16 tokens x ``group`` heads, or ``selected`` decode
    rows (MiniCPM-SALA's form) over tables that hold a choice of the
    context's blocks in an order of their own.  At the default width
    the two KV heads share one 128-lane window of a pool row: a decode
    row's program picks its head's half, a chunk's computes both."""
    from paddle_tpu.inference.serving import attention as att
    S = len(contexts)
    rng = np.random.default_rng(seed)
    nb = S * W + 1
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(seed), 3)
    k_pool, v_pool = (jax.random.normal(
        k, (nb, bs, kv_heads * D), jnp.float32).astype(dtype)
        for k in (kk, kv))
    tables = 1 + np.arange(S * W, dtype=np.int32).reshape(S, W)
    if form == "chunk":
        chunk, valid, ctx = 48, 40, int(contexts[0])
        q = jax.random.normal(kq, (chunk, group * kv_heads, D),
                              jnp.float32).astype(dtype)
        return [np.asarray(att.grouped_chunk_attention(
            q, k_pool, v_pool, jnp.asarray(tables[0]), jnp.int32(ctx),
            jnp.int32(ctx - valid), jnp.int32(valid), window=window,
            chunk_bq=16, use_pallas=use), np.float32)
            for use in (True, False)]
    q = jax.random.normal(kq, (S, group * kv_heads, D),
                          jnp.float32).astype(dtype)
    sel = np.broadcast_to(tables[:, None], (S, kv_heads, W)).copy()
    ctx = np.broadcast_to(np.asarray(contexts, np.int32)[:, None],
                          (S, kv_heads)).copy()
    options = dict(window=window,
                   block_q=att.decode_block_q(group, dtype))
    if form == "selected":
        # each (row, KV head) keeps a shuffled half of its blocks, the
        # last one part full: no prefix of the context
        options = {}
        for s in range(S):
            for h in range(kv_heads):
                sel[s, h] = rng.permutation(sel[s, h])
                ctx[s, h] = (W // 2) * bs - int(rng.integers(0, bs))
    return [np.asarray(att.grouped_decode_attention(
        q, k_pool, v_pool, jnp.asarray(sel), jnp.asarray(ctx), use,
        **options), np.float32) for use in (True, False)]


_case = functools.partial
_RAGGED_CASES = {
    # every row a single-token decode step (the PR-5 steady state)
    "pure_decode": _case(_ragged_case, [1, 1, 1], [60, 17, 5]),
    # one prompt prefilled whole (query == context, multiple q-blocks)
    "pure_prefill": _case(_ragged_case, [20], [20]),
    # prefill chunk + two decode rows in ONE batch
    "mixed": _case(_ragged_case, [12, 1, 1], [30, 25, 9]),
    # chunk starting mid-prompt exactly at a q-block boundary
    # (query_len a multiple of block_q, base context > 0)
    "chunk_boundary": _case(_ragged_case, [16, 1], [48, 33]),
    # the walk's ends (blocks of 16, 32 table slots a step): a context
    # that ends mid-block, at a block's end, at a step's end (512 keys)
    # and one block past it
    "walk_ends": _case(_ragged_case, [1, 1, 1, 1], [505, 496, 512, 528],
                       W=40),
    # a chunk whose q-blocks end in different steps of the walk
    "walk_chunk": _case(_ragged_case, [40, 1], [530, 3], W=40),
    # a table four times wider than the longest context
    "wide_table": _case(_ragged_case, [1, 9, 1], [60, 33, 64], W=16),
    # 64-wide heads: two heads share a 128-lane window of a pool row
    # (GPT-2's form), and a program computes both
    "packed_lanes": _case(_ragged_case, [20, 1, 1], [52, 41, 16], D=64),
    # one window alone: an even and an odd head side by side
    "shared_window": _case(_ragged_case, [20, 1, 1], [52, 41, 16], D=64,
                           H=2),
    "shared_window_windowed": _case(_ragged_case, [40, 1], [530, 70],
                                    D=64, H=2, W=40, window=50),
    # heads of whole 128-lane tiles: a window a head
    "wide_heads_128": _case(_ragged_case, [20, 1, 1], [52, 41, 16], D=128,
                            H=2),
    "wide_heads_256": _case(_ragged_case, [12, 1], [30, 9], D=256, H=2,
                            bs=8, W=8),
    # the int8 pool: per-slot scales walked with the table
    "int8_pool": _case(_ragged_case, [12, 1, 1], [30, 25, 9], int8=True),
    "int8_pool_packed": _case(_ragged_case, [1, 1], [530, 64], int8=True,
                              D=64, W=40),
    "int8_pool_wide": _case(_ragged_case, [12, 1, 1], [30, 25, 9],
                            int8=True, D=128, H=2),
    # grouped KV heads: decode rows (block_tokens 1) and a chunk's head
    # groups (block_tokens 16), with no window and with one whose first
    # live block lies inside the table (and the context past one step)
    "grouped_decode": _case(_grouped_case, "decode", contexts=[100, 7, 128]),
    "grouped_decode_window": _case(_grouped_case, "decode", W=40,
                                   contexts=[600, 530, 20], window=70),
    "grouped_chunk": _case(_grouped_case, "chunk", contexts=[117]),
    "grouped_chunk_window": _case(_grouped_case, "chunk", contexts=[600],
                                  W=40, window=70),
    # the same at the cells' head widths, and at a width where the two
    # KV heads share a window and a decode row's program picks its half
    "grouped_decode_128": _case(_grouped_case, "decode", D=128,
                                contexts=[100, 7, 128]),
    "grouped_decode_256_window": _case(_grouped_case, "decode", D=256,
                                       W=40, contexts=[600, 530, 20],
                                       window=70),
    "grouped_chunk_128_window": _case(_grouped_case, "chunk", D=128,
                                      contexts=[600], W=40, window=70),
    "grouped_chunk_256": _case(_grouped_case, "chunk", D=256,
                               contexts=[117]),
    # four KV heads a window
    "grouped_decode_32": _case(_grouped_case, "decode", D=32, kv_heads=4,
                               contexts=[100, 7, 128]),
    "grouped_chunk_32": _case(_grouped_case, "chunk", D=32, kv_heads=4,
                              contexts=[117]),
    # MiniCPM-SALA's decode rows: a selected table a KV head, no prefix
    "selected_table": _case(_grouped_case, "selected", group=8,
                            contexts=[0, 0, 0]),
    "selected_table_128": _case(_grouped_case, "selected", group=8, D=128,
                                contexts=[0, 0, 0]),
}


@pytest.mark.parametrize("case", sorted(_RAGGED_CASES))
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ragged_attention_kernel_matches_fallback(case, dtype):
    """Ragged mixed-batch kernel vs the pure-XLA segment-gather
    fallback, at the paged-attention parity tolerance for f32."""
    out, ref = _RAGGED_CASES[case](dtype=dtype)
    assert np.abs(ref).max() > 0.1
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(out, ref, atol=tol, rtol=tol)


@pytest.mark.parametrize("window, block_tokens", [(None, None), (40, 1),
                                                  (40, 16), (None, 16)])
def test_ragged_attention_reads_no_dead_block(window, block_tokens):
    """Every pool block that no live table slot names is NaN, and every
    dead slot (past the context, after a q-block's last token, before
    its window) names such a block: the output is finite and equals the
    fallback's over a pool whose dead blocks hold zeros."""
    from paddle_tpu.inference.serving.attention import _ragged_ref
    from paddle_tpu.ops import pallas_ragged as pr
    H, D, bs, W, block_q = 4, 32, 16, 40, 16
    if block_tokens == 1:       # decode rows, the walk past one step
        contexts, lens = [600, 37, 513], [1, 1, 1]
    else:                       # a chunk of three q-blocks and a row
        contexts, lens = [570, 9], [40, 1]
    tokens = block_tokens or block_q
    sid, qs, qv, _, _ = pr.ragged_segments(lens, contexts, tokens)
    nqb, S = len(sid), len(contexts)
    tables = 1 + np.arange(S * W, dtype=np.int32).reshape(S, W)
    live = np.zeros((S, W), bool)
    for i in range(nqb):
        last = qs[i] + qv[i] - 1
        first = 0 if window is None else max(qs[i] - window + 1, 0)
        live[sid[i], first // bs:last // bs + 1] = True
    dead = S * W + 1                                    # the NaN block
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(5), 3)
    pools = [np.array(jax.random.normal(k, (S * W + 2, bs, H * D),
                                        jnp.float32))
             for k in (kk, kv)]
    for pool in pools:
        pool[0] = pool[dead] = np.nan
        pool[tables[~live]] = np.nan
    q = jax.random.normal(kq, (nqb * block_q, H, D), jnp.float32)
    args = (jnp.asarray(contexts, jnp.int32), jnp.asarray(sid),
            jnp.asarray(qs), jnp.asarray(qv))
    options = dict(window=window, block_tokens=block_tokens)
    out = pr.ragged_paged_attention(
        q, *(jnp.asarray(p) for p in pools),
        jnp.asarray(np.where(live, tables, dead)), *args,
        block_q=block_q, scale=D ** -0.5, **options)
    ref = _ragged_ref(
        q, *(jnp.asarray(np.nan_to_num(p)) for p in pools),
        jnp.asarray(tables), *args, block_q, D ** -0.5, **options)
    out = np.asarray(out)
    assert np.isfinite(out).all()
    assert live.sum() < S * W / 2 and np.abs(out).max() > 0.1
    np.testing.assert_allclose(out, np.asarray(ref), atol=2e-5, rtol=2e-5)


def test_ragged_attention_null_segments_emit_zeros():
    """ctx==0 rows: a sequence with nothing cached plus trailing pad
    q-blocks (seq_ids == S) must emit exact zeros, not NaN."""
    from paddle_tpu.ops import pallas_ragged as pr
    block_q = pr.ragged_q_block(jnp.float32)
    out, ref = _ragged_case([1, 0], [25, 0], jnp.float32, pad_blocks=2)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)
    # the ctx==0 sequence schedules no queries; blocks 1-2 are pure
    # pad segments and must come back as exact zeros
    assert out.shape[0] == 3 * block_q
    assert float(np.abs(out[block_q:]).sum()) == 0.0


@pytest.mark.parametrize("head_dim, kv_heads, ok", [
    (64, 20, True),      # GPT-2: two heads a 128-lane window
    (64, 3, False),      # the last window would pass the row's end
    (32, 4, True), (32, 2, False),
    (128, 1, True), (128, 4, True), (256, 2, True),
    (96, 4, False),      # neither whole tiles nor a divisor of one
])
def test_pool_copyable_is_the_rule_of_the_lane_window(head_dim, kv_heads,
                                                      ok):
    """What the walk's copies can name in a pool row of ``H * D`` lanes:
    a head of whole 128-lane tiles, or whole heads a tile and whole
    tiles a row; a call outside the rule is refused by its shapes."""
    from paddle_tpu.ops import pallas_ragged as pr
    assert pr.pool_copyable(head_dim, kv_heads) is ok
    if ok or head_dim == 96:
        return
    q = jnp.zeros((8, kv_heads, head_dim), jnp.float32)
    pool = jnp.zeros((3, 8, kv_heads * head_dim), jnp.float32)
    one = jnp.zeros((1,), jnp.int32)
    with pytest.raises(ValueError, match="lane"):
        pr.ragged_paged_attention(q, pool, pool, jnp.zeros((1, 2), jnp.int32),
                                  one, one, one, one, block_q=8)


def test_ragged_attention_refuses_a_pool_of_another_width():
    """``q``'s heads times its width are the pool's row, unless each
    q-block names its one head (``head_ids``, ``q`` [T, 1, D])."""
    from paddle_tpu.ops import pallas_ragged as pr
    pool = jnp.zeros((3, 8, 4 * 64), jnp.float32)
    one = jnp.zeros((1,), jnp.int32)
    table = jnp.zeros((1, 2), jnp.int32)
    for heads, ids in ((2, None), (4, one)):
        q = jnp.zeros((8, heads, 64), jnp.float32)
        with pytest.raises(ValueError, match="lane"):
            pr.ragged_paged_attention(q, pool, pool, table, one, one, one,
                                      one, block_q=8, head_ids=ids)
    out = pr.ragged_paged_attention(
        jnp.zeros((8, 1, 64), jnp.float32), pool, pool, table, one, one,
        one, one, block_q=8, head_ids=one + 3)
    assert out.shape == (8, 1, 64)


def test_ragged_segments_layout():
    """Host-side descriptor builder: segment split, padding sentinel,
    and the over-budget guard."""
    from paddle_tpu.ops import pallas_ragged as pr
    sid, qs, qv, offs, rows = pr.ragged_segments(
        [12, 1, 0, 1], [30, 25, 7, 9], 8, num_q_blocks=6)
    assert sid.tolist() == [0, 0, 1, 3, 4, 4]   # seq 2 has no queries
    assert qs.tolist() == [18, 26, 24, 8, 0, 0]
    assert qv.tolist() == [8, 4, 1, 1, 0, 0]
    assert offs.tolist() == [0, 16, 24, 24] and rows == 32
    with pytest.raises(ValueError):
        pr.ragged_segments([12], [30], 8, num_q_blocks=1)
    with pytest.raises(ValueError):
        pr.ragged_segments([31], [30], 8)       # query > context


# ---------------------------------------------------------------------
# Fused training suite (pallas_fused + bf16 flash parity)
# ---------------------------------------------------------------------
from paddle_tpu.ops import pallas_fused as pf  # noqa: E402


@pytest.mark.parametrize("causal", [False, True])
def test_flash_attention_bf16_fwd_bwd(causal):
    """bf16 parity fwd AND bwd vs the f32 reference (inputs rounded to
    bf16 first so both paths see identical operands)."""
    shape = (1, 48, 2, 32)
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(21), 3)
    q = jax.random.normal(kq, shape, jnp.float32).astype(jnp.bfloat16)
    k = jax.random.normal(kk, shape, jnp.float32).astype(jnp.bfloat16)
    v = jax.random.normal(kv, shape, jnp.float32).astype(jnp.bfloat16)

    out = pk.flash_attention(q, k, v, causal=causal)
    ref = _sdpa_ref(q, k, v, causal, 1.0 / 32 ** 0.5)
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32),
        atol=3e-2, rtol=3e-2)

    def f_pl(q, k, v):
        o = pk.flash_attention(q, k, v, causal=causal)
        return jnp.sum(o.astype(jnp.float32) ** 2)

    def f_ref(q, k, v):
        o = _sdpa_ref(q, k, v, causal, 1.0 / 32 ** 0.5)
        return jnp.sum(o.astype(jnp.float32) ** 2)

    g_pl = jax.grad(f_pl, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_pl, g_ref):
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32),
            atol=1e-1, rtol=6e-2)


def _ln_res_ref(x, r, g, b, eps=1e-5):
    """XLA reference with the kernel's semantics: residual add and
    statistics in f32, output cast back to the input dtype."""
    s = x.astype(jnp.float32) + r.astype(jnp.float32)
    mu = jnp.mean(s, -1, keepdims=True)
    var = jnp.mean(jnp.square(s - mu), -1, keepdims=True)
    out = ((s - mu) * jax.lax.rsqrt(var + eps)).astype(x.dtype)
    return out * g + b


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_fused_layer_norm_residual(dtype):
    kx, kr = jax.random.split(jax.random.PRNGKey(22))
    x = (jax.random.normal(kx, (37, 96), jnp.float32) * 2).astype(dtype)
    r = jax.random.normal(kr, (37, 96), jnp.float32).astype(dtype)
    gamma = (jax.random.normal(jax.random.PRNGKey(23), (96,)) + 1
             ).astype(dtype)
    beta = jax.random.normal(jax.random.PRNGKey(24), (96,)).astype(dtype)

    fwd_tol = 1e-5 if dtype == jnp.float32 else 6e-2
    out = pf.fused_layer_norm_residual(x, r, gamma, beta, eps=1e-5)
    ref = _ln_res_ref(x, r, gamma, beta)
    assert out.dtype == dtype
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32),
        atol=fwd_tol, rtol=fwd_tol)

    def loss_pl(x, r, g, b):
        o = pf.fused_layer_norm_residual(x, r, g, b)
        return jnp.sum(jnp.sin(o.astype(jnp.float32)))

    def loss_ref(x, r, g, b):
        return jnp.sum(jnp.sin(_ln_res_ref(x, r, g, b
                                           ).astype(jnp.float32)))

    gp = jax.grad(loss_pl, argnums=(0, 1, 2, 3))(x, r, gamma, beta)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2, 3))(x, r, gamma, beta)
    atol, rtol = ((1e-4, 1e-4) if dtype == jnp.float32
                  else (1.5e-1, 6e-2))
    for a, b in zip(gp, gr):
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32),
            atol=atol, rtol=rtol)


def test_fused_layer_norm_residual_multiblock():
    """rows > block_rows: the grid streams multiple row blocks and the
    bwd dgamma/dbeta accumulator must sum across all of them."""
    kx, kr = jax.random.split(jax.random.PRNGKey(25))
    x = jax.random.normal(kx, (300, 256), jnp.float32)
    r = jax.random.normal(kr, (300, 256), jnp.float32)
    gamma = jax.random.normal(jax.random.PRNGKey(26), (256,)) + 1
    beta = jax.random.normal(jax.random.PRNGKey(27), (256,))
    out = pf.fused_layer_norm_residual(x, r, gamma, beta)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(_ln_res_ref(x, r, gamma, beta)),
        atol=1e-5, rtol=1e-5)
    gp = jax.grad(lambda *a: jnp.sum(
        pf.fused_layer_norm_residual(*a) ** 2),
        argnums=(0, 1, 2, 3))(x, r, gamma, beta)
    gr = jax.grad(lambda *a: jnp.sum(_ln_res_ref(*a) ** 2),
                  argnums=(0, 1, 2, 3))(x, r, gamma, beta)
    for a, b in zip(gp, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-3, rtol=1e-4)


def _linear_act_ref(x, w, b, act):
    z = (x.astype(jnp.float32) @ w.astype(jnp.float32)
         + b.astype(jnp.float32))
    if act == "relu":
        z = jax.nn.relu(z)
    elif act == "gelu":
        z = jax.nn.gelu(z, approximate=False)
    elif act == "gelu_tanh":
        z = jax.nn.gelu(z, approximate=True)
    elif act == "silu":
        z = jax.nn.silu(z)
    return z.astype(x.dtype)


@pytest.mark.parametrize("act", pf.ACTIVATIONS)
def test_matmul_epilogue(act):
    kx, kw, kb = jax.random.split(jax.random.PRNGKey(28), 3)
    x = jax.random.normal(kx, (40, 96), jnp.float32)
    w = jax.random.normal(kw, (96, 64), jnp.float32) * 0.1
    b = jax.random.normal(kb, (64,), jnp.float32)
    out = pf.fused_linear_act(x, w, b, act)
    ref = _linear_act_ref(x, w, b, act)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-4, rtol=2e-4)
    gp = jax.grad(lambda *a: jnp.sum(pf.fused_linear_act(*a, act) ** 2),
                  argnums=(0, 1, 2))(x, w, b)
    gr = jax.grad(lambda *a: jnp.sum(_linear_act_ref(*a, act) ** 2),
                  argnums=(0, 1, 2))(x, w, b)
    for a, b_ in zip(gp, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   atol=2e-3, rtol=2e-4)


def test_matmul_epilogue_bf16_multiblock():
    """bf16 + shapes past one (block_m, block_n) tile: grid streaming,
    db accumulation across the minor m axis, z saved in bf16."""
    kx, kw, kb = jax.random.split(jax.random.PRNGKey(29), 3)
    x = jax.random.normal(kx, (300, 128), jnp.float32
                          ).astype(jnp.bfloat16)
    w = (jax.random.normal(kw, (128, 640), jnp.float32) * 0.1
         ).astype(jnp.bfloat16)
    b = jax.random.normal(kb, (640,), jnp.float32).astype(jnp.bfloat16)
    out = pf.fused_linear_act(x, w, b, "gelu_tanh")
    ref = _linear_act_ref(x, w, b, "gelu_tanh")
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32),
        atol=6e-2, rtol=6e-2)
    gp = jax.grad(lambda *a: jnp.sum(
        pf.fused_linear_act(*a, "gelu_tanh").astype(jnp.float32)),
        argnums=(0, 1, 2))(x, w, b)
    gr = jax.grad(lambda *a: jnp.sum(
        _linear_act_ref(*a, "gelu_tanh").astype(jnp.float32)),
        argnums=(0, 1, 2))(x, w, b)
    for a, b_ in zip(gp, gr):
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b_, np.float32),
            atol=1.5e-1, rtol=6e-2)


def test_grad_through_fused_transformer_block():
    """jax.grad through a full post-norm transformer block built from
    the fused suite (flash attention → LN+residual → matmul-epilogue
    FFN → LN+residual) vs the same block from XLA composites."""
    B, S, H, D, FF = 1, 32, 2, 16, 64
    E = H * D
    keys = jax.random.split(jax.random.PRNGKey(30), 8)
    x = jax.random.normal(keys[0], (B, S, E), jnp.float32)
    w_qkv = jax.random.normal(keys[1], (E, 3 * E)) * 0.1
    w_o = jax.random.normal(keys[2], (E, E)) * 0.1
    w1 = jax.random.normal(keys[3], (E, FF)) * 0.1
    b1 = jax.random.normal(keys[4], (FF,)) * 0.1
    w2 = jax.random.normal(keys[5], (FF, E)) * 0.1
    g1 = jax.random.normal(keys[6], (E,)) + 1
    g2 = jax.random.normal(keys[7], (E,)) + 1
    z1 = jnp.zeros((E,))

    def block(x, w_qkv, w_o, w1, b1, w2, g1, g2, fused):
        qkv = x @ w_qkv
        q, k, v = jnp.split(qkv.reshape(B, S, H, 3 * D), 3, axis=-1)
        if fused:
            a = pk.flash_attention(q, k, v, causal=True)
        else:
            a = _sdpa_ref(q, k, v, True, 1.0 / D ** 0.5)
        a = a.reshape(B, S, E) @ w_o
        if fused:
            h = pf.fused_layer_norm_residual(a, x, g1, z1)
            f = pf.fused_linear_act(h, w1, b1, "gelu_tanh") @ w2
            return pf.fused_layer_norm_residual(f, h, g2, z1)
        h = _ln_res_ref(a, x, g1, z1)
        f = _linear_act_ref(h, w1, b1, "gelu_tanh") @ w2
        return _ln_res_ref(f, h, g2, z1)

    params = (x, w_qkv, w_o, w1, b1, w2, g1, g2)
    out_f = block(*params, True)
    out_r = block(*params, False)
    np.testing.assert_allclose(np.asarray(out_f), np.asarray(out_r),
                               atol=1e-4, rtol=1e-4)
    loss = lambda *p, fused: jnp.sum(block(*p, fused) ** 2)  # noqa: E731
    gf = jax.grad(lambda *p: loss(*p, fused=True),
                  argnums=tuple(range(8)))(*params)
    gr = jax.grad(lambda *p: loss(*p, fused=False),
                  argnums=tuple(range(8)))(*params)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-3, rtol=2e-4)
