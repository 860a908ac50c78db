"""Grouped-expert Pallas matmul (ops/pallas_grouped.py): kernel vs the
bit-exact XLA composite across dtypes and ragged expert loads, the
custom_vjp backward, and the dropless dispatch/combine roundtrip."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.distributed.auto_parallel import moe_dispatch as md
from paddle_tpu.ops import pallas_grouped as pg
from paddle_tpu.ops.pallas_tiles import group_segments


def _case(seed, counts, K, N, dtype):
    """Grouped buffer + stacked weights for explicit per-expert counts:
    tokens scattered into their block-aligned rows, padding rows zero."""
    E = len(counts)
    T = int(sum(counts))
    rng = np.random.RandomState(seed)
    bm, nb, R = pg.grouped_layout(max(T, 1), E, dtype)
    gid, offsets = group_segments(jnp.asarray(counts, jnp.int32), bm, nb)
    x = np.zeros((R, K), np.float32)
    for e, c in enumerate(counts):
        x[int(offsets[e]):int(offsets[e]) + c] = rng.randn(c, K)
    w = rng.randn(E, K, N).astype(np.float32) * 0.1
    b = rng.randn(E, N).astype(np.float32) * 0.1
    return (jnp.asarray(x, dtype), jnp.asarray(w, dtype),
            jnp.asarray(b, dtype), gid, bm, offsets)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("act", ["none", "gelu_tanh"])
@pytest.mark.parametrize("counts", [
    [7, 0, 21, 4],        # ragged + an empty expert
    [16, 16, 16, 16],     # balanced
    [0, 0, 0, 50],        # all load on one expert
])
def test_grouped_forward_parity(counts, act, dtype):
    x, w, b, gid, _, _ = _case(0, counts, 32, 48, dtype)
    out = pg.grouped_linear_act(x, w, b, block_group=gid, act=act)
    ref = pg.grouped_linear_act_ref(x, w, b, block_group=gid, act=act)
    tol = 1e-6 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32),
        rtol=tol, atol=tol)


def test_grouped_forward_jit_parity_tight():
    """Same f32 math either way; under jit the only daylight is dot
    reduction order (the ref batches blocks into one 3D dot), so the
    gap stays within a few ULP of f32."""
    x, w, b, gid, _, _ = _case(1, [9, 3, 14, 6], 64, 32, jnp.float32)
    f_k = jax.jit(lambda: pg.grouped_linear_act(
        x, w, b, block_group=gid, act="gelu_tanh"))
    f_r = jax.jit(lambda: pg.grouped_linear_act_ref(
        x, w, b, block_group=gid, act="gelu_tanh"))
    np.testing.assert_allclose(np.asarray(f_k()), np.asarray(f_r()),
                               rtol=0, atol=2e-6)


def test_grouped_forward_matches_per_expert_dense():
    """Ground truth straight from per-expert dense matmuls (no shared
    code with either implementation)."""
    counts = [5, 11, 0, 8]
    x, w, b, gid, bm, offsets = _case(2, counts, 16, 24, jnp.float32)
    out = np.asarray(pg.grouped_linear_act(x, w, b, block_group=gid,
                                           act="none"))
    xn, wn, bn = np.asarray(x), np.asarray(w), np.asarray(b)
    for e, c in enumerate(counts):
        o = int(offsets[e])
        want = xn[o:o + c] @ wn[e] + bn[e]
        np.testing.assert_allclose(out[o:o + c], want,
                                   rtol=1e-5, atol=1e-5)


def test_grouped_backward_matches_ref_grads():
    counts = [6, 0, 18, 8]
    x, w, b, gid, _, _ = _case(3, counts, 32, 16, jnp.float32)

    def loss(fn):
        def f(x_, w_, b_):
            y = fn(x_, w_, b_, block_group=gid, act="gelu_tanh")
            return jnp.sum(jnp.sin(y.astype(jnp.float32)))
        return f

    gk = jax.grad(loss(pg.grouped_linear_act), argnums=(0, 1, 2))(x, w, b)
    gr = jax.grad(loss(pg.grouped_linear_act_ref),
                  argnums=(0, 1, 2))(x, w, b)
    for a, r, name in zip(gk, gr, ("dx", "dw", "db")):
        np.testing.assert_allclose(np.asarray(a), np.asarray(r),
                                   rtol=1e-4, atol=1e-5, err_msg=name)
    # the empty expert's weight gradient is exactly zero, not garbage
    # from an unvisited accumulator block
    assert (np.asarray(gk[1])[1] == 0.0).all()
    assert (np.asarray(gk[2])[1] == 0.0).all()


def test_layout_validation_errors():
    x, w, b, gid, _, _ = _case(4, [8, 8], 16, 16, jnp.float32)
    with pytest.raises(ValueError, match="block descriptors"):
        pg.grouped_linear_act(x[:-1], w, b, block_group=gid)
    with pytest.raises(ValueError, match="act must be one of"):
        pg.grouped_linear_act(x, w, b, block_group=gid, act="tanhh")
    with pytest.raises(ValueError, match="b shape"):
        pg.grouped_linear_act(x, w, b[:1], block_group=gid)


# ---------------------------------------------------------------------
# dropless dispatch/combine around the kernel
# ---------------------------------------------------------------------

def test_dropless_roundtrip_topk1_is_identity_routing():
    """top_k=1 with weight 1.0: combine(gather(scatter(x))) == expert
    output for each token's own expert."""
    rng = np.random.RandomState(5)
    N, K, Nout, E = 20, 16, 24, 4
    x = jnp.asarray(rng.randn(N, K), jnp.float32)
    topk = jnp.asarray(rng.randint(0, E, size=(N, 1)), jnp.int32)
    w = jnp.asarray(rng.randn(E, K, Nout) * 0.1, jnp.float32)
    bm, nb, R = pg.grouped_layout(N, E, x.dtype)
    rows, gid, counts = md.dropless_plan(topk, E, bm, nb)
    xd = md.dropless_dispatch(x, rows, 1, R)
    y_rows = pg.grouped_linear_act(xd, w, None, block_group=gid)
    y = md.dropless_combine(y_rows, rows, jnp.ones((N, 1), jnp.float32))
    want = np.stack([np.asarray(x)[i] @ np.asarray(w)[int(topk[i, 0])]
                     for i in range(N)])
    np.testing.assert_allclose(np.asarray(y), want, rtol=1e-5, atol=1e-5)


def test_dropless_plan_deterministic():
    rng = np.random.RandomState(6)
    topk = jnp.asarray(rng.randint(0, 8, size=(64, 2)), jnp.int32)
    bm, nb, _ = pg.grouped_layout(128, 8, jnp.float32)
    a = md.dropless_plan(topk, 8, bm, nb)
    b = md.dropless_plan(topk, 8, bm, nb)
    for u, v in zip(a, b):
        assert (np.asarray(u) == np.asarray(v)).all()


@pytest.mark.parametrize("held", [(0, 8), (0, 2), (2, 4), (6, 8)])
def test_dropless_plan_of_the_held_experts(held):
    """A plan that is told which experts it holds lays out those alone,
    as groups ``e - lo``; every other assignment gets the row past the
    buffer's end, as a token's that carries nothing."""
    rng = np.random.RandomState(7)
    topk = np.asarray(rng.randint(0, 8, size=(40, 3)), np.int32)
    lo, hi = held
    bm, nb, R = pg.grouped_layout(topk.size, hi - lo, jnp.float32)
    rows, gid, counts = md.dropless_plan(jnp.asarray(topk), hi - lo, bm,
                                         nb, held=held)
    rows, gid = np.asarray(rows).reshape(topk.shape), np.asarray(gid)
    inside = (topk >= lo) & (topk < hi)
    assert np.asarray(counts).tolist() == [
        int((topk == e).sum()) for e in range(lo, hi)]
    assert (rows[~inside] == R).all() and (rows[inside] < R).all()
    # a held assignment's row lies in a block of its expert's group
    assert (gid[rows[inside] // bm] == topk[inside] - lo).all()
    assert len(set(rows[inside].tolist())) == int(inside.sum())
    if held == (0, 8):                  # every expert held: the old plan
        plain = md.dropless_plan(jnp.asarray(topk), 8, bm, nb)
        assert (np.asarray(plain[0]).reshape(topk.shape) == rows).all()


@pytest.mark.parametrize("identity", [None, 12])
@pytest.mark.parametrize("carried", [False, True])
@pytest.mark.parametrize("held", [None, (2, 6)])
@pytest.mark.parametrize("top_k", [1, 8, 12])
def test_gathers_match_the_scatter_form(top_k, held, carried, identity):
    """The grouped buffer the gathers fill is the scattered one bit for
    bit (the same rows in the same places, padding zero); the combine
    and the layer agree with the einsum form within float32 rounding.
    The scatter and einsum forms are `chip_smoke`'s.  Choices among 16
    experts, of which 12-15 are zero-compute ones where ``identity`` is
    12."""
    from chip_smoke import _einsum_combine, _scatter_dispatch
    rng = np.random.RandomState(9 + top_k)
    N, D, W = 40, 16, 8
    lo, hi = held or (0, identity or 16)        # every routed expert
    E = hi - lo
    idx = jnp.asarray(np.stack([rng.permutation(16)[:top_k]
                                for _ in range(N)]), jnp.int32)
    live = jnp.asarray(rng.rand(N) < 0.7) if carried else None
    x = jnp.asarray(rng.randn(N, D), jnp.bfloat16)
    bm, nb, R = pg.grouped_layout(N * top_k, E, x.dtype)
    rows, gid, _ = md.dropless_plan(idx, E, bm, nb, live, (lo, hi))
    want = np.asarray(_scatter_dispatch(x, rows, top_k, R).view(jnp.uint16))
    got = md.dropless_dispatch(x, rows, top_k, R)
    assert got.shape == (R, D)
    assert (np.asarray(got.view(jnp.uint16)) == want).all()
    w = jnp.asarray(rng.rand(N, top_k), jnp.float32)
    y_rows = jnp.asarray(rng.randn(R, D), jnp.float32)
    np.testing.assert_allclose(
        np.asarray(md.dropless_combine(y_rows, rows, w)),
        np.asarray(_einsum_combine(y_rows, rows, w)), rtol=1e-6, atol=1e-6)
    # the whole layer in float32 against the scatter-and-einsum form
    xf = x.astype(jnp.float32)
    gate_up = jnp.asarray(rng.randn(E, D, 2 * W) * 0.3, jnp.float32)
    down = jnp.asarray(rng.randn(E, W, D) * 0.3, jnp.float32)
    y, _ = md.gated_experts(xf, idx, w, gate_up, down, live, held=(lo, hi),
                            identity=identity)
    h = pg.grouped_gated_act_ref(_scatter_dispatch(xf, rows, top_k, R),
                                 gate_up, block_group=gid, act="silu")
    ref = _einsum_combine(pg.grouped_linear_act_ref(h, down, block_group=gid),
                          rows, w)
    if identity is not None:
        zero = (idx >= identity) & (True if live is None
                                    else live[:, None])
        ref = ref + jnp.where(zero, w, 0.0).sum(-1)[:, None] * xf
    np.testing.assert_allclose(np.asarray(y), np.asarray(ref), rtol=1e-5,
                               atol=1e-5)


def test_gated_experts_shares_add_up():
    """The parts of the result that the holders of experts 0-1, 2-5 and
    6-7 give sum to the layer's with every expert held, and the counters
    say what each dispatched beside what was routed."""
    rng = np.random.RandomState(8)
    N, D, W, E, k = 24, 16, 8, 8, 3
    x = jnp.asarray(rng.randn(N, D), jnp.float32)
    idx = jnp.asarray(np.stack([rng.permutation(E)[:k] for _ in range(N)]),
                      jnp.int32)
    weight = jnp.asarray(rng.rand(N, k), jnp.float32)
    gate_up = jnp.asarray(rng.randn(E, D, 2 * W) * 0.3, jnp.float32)
    down = jnp.asarray(rng.randn(E, W, D) * 0.3, jnp.float32)
    whole, counted = md.gated_experts(x, idx, weight, gate_up, down)
    assert counted.tolist()[0] == counted.tolist()[4] == N * k
    total, dispatched = 0.0, 0
    for lo, hi in ((0, 2), (2, 6), (6, 8)):
        part, c = md.gated_experts(x, idx, weight, gate_up[lo:hi],
                                   down[lo:hi], held=(lo, hi))
        total, dispatched = total + part, dispatched + int(c[0])
        assert int(c[4]) == N * k and int(c[1]) <= hi - lo
    assert dispatched == N * k
    np.testing.assert_allclose(np.asarray(total), np.asarray(whole),
                               rtol=1e-5, atol=1e-6)
    with pytest.raises(ValueError, match="held, stacks"):
        md.gated_experts(x, idx, weight, gate_up, down, held=(0, 4))


def test_expert_imbalance_gauge():
    assert float(md.expert_imbalance(jnp.asarray([4, 4, 4, 4]))) \
        == pytest.approx(1.0)
    assert float(md.expert_imbalance(jnp.asarray([13, 1, 1, 1]))) \
        == pytest.approx(13 / 4)


def test_block_plan_export_matches_call_geometry():
    for direction in ("fwd", "bwd_dw"):
        plan = pg.grouped_matmul_block_plan(96, 64, 128, 4,
                                            direction=direction)
        assert plan["direction"] == direction
        bm, nb = plan["block_rows"], plan["num_blocks"]
        assert bm == pg.grouped_block_rows(96, 4, jnp.float32)
        rows = nb * bm
        names = [op[0] for op in plan["operands"]]
        ref = {"fwd": ["x", "w", "b", "out", "z"],
               "bwd_dw": ["x", "dz", "dw"]}[direction]
        assert names == ref
        for _, blk, full, _dt in plan["operands"]:
            for b_, f_ in zip(blk, full):
                assert f_ % b_ == 0, (blk, full)
        assert plan["operands"][0][2][0] == rows
    with pytest.raises(ValueError, match="direction"):
        pg.grouped_matmul_block_plan(96, 64, 128, 4, direction="bwd_dx")
