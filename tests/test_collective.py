"""Every collective in communication/ops.py exercised under shard_map on
the 8-device CPU mesh (SURVEY.md §4 fake-device strategy), plus the
eager-fallback honesty guards."""
import numpy as np
import pytest
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

import paddle_tpu as paddle
import paddle_tpu.distributed as dist
from paddle_tpu.core.tensor import Tensor
from paddle_tpu.distributed.communication import group as group_mod


N = 8


@pytest.fixture
def mesh():
    devs = np.array(jax.devices()[:N])
    m = Mesh(devs, ("x",))
    dist.env.set_global_mesh(m)
    yield m
    dist.env.set_global_mesh(None)
    group_mod._default_group = None


def _grp():
    return dist.new_group(axis_name="x")


def _run(mesh, fn, arr, in_spec, out_spec):
    return jax.shard_map(fn, mesh=mesh, in_specs=in_spec,
                         out_specs=out_spec, check_vma=False)(arr)


def test_all_reduce_shard_map(mesh):
    g = _grp()
    x = jnp.arange(N, dtype=jnp.float32)

    def f(v):
        t = Tensor(v, _internal=True)
        dist.all_reduce(t, group=g)
        return t._value

    out = _run(mesh, f, x, P("x"), P("x"))
    np.testing.assert_allclose(np.asarray(out), np.full(N, x.sum()))


def test_all_reduce_max_min(mesh):
    g = _grp()
    x = jnp.arange(N, dtype=jnp.float32)

    for op, expect in [(dist.ReduceOp.MAX, 7.0), (dist.ReduceOp.MIN, 0.0),
                       (dist.ReduceOp.AVG, 3.5)]:
        def f(v):
            t = Tensor(v, _internal=True)
            dist.all_reduce(t, op=op, group=g)
            return t._value

        out = _run(mesh, f, x, P("x"), P("x"))
        np.testing.assert_allclose(np.asarray(out), np.full(N, expect))


def test_all_gather_shard_map(mesh):
    g = _grp()
    x = jnp.arange(N, dtype=jnp.float32)

    def f(v):
        out = Tensor(jnp.zeros((N,), jnp.float32), _internal=True)
        t = Tensor(v, _internal=True)
        dist.all_gather(out, t, group=g)
        return out._value

    # result is replicated: every shard holds the full gathered vector
    out = _run(mesh, f, x, P("x"), P(None))
    np.testing.assert_allclose(np.asarray(out),
                               np.arange(N, dtype=np.float32))


def test_broadcast_shard_map(mesh):
    g = _grp()
    x = jnp.arange(N, dtype=jnp.float32)

    def f(v):
        t = Tensor(v, _internal=True)
        dist.broadcast(t, src=3, group=g)
        return t._value

    out = _run(mesh, f, x, P("x"), P("x"))
    np.testing.assert_allclose(np.asarray(out), np.full(N, 3.0))


def test_reduce_scatter_shard_map(mesh):
    g = _grp()
    x = jnp.tile(np.arange(N, dtype=np.float32), (N, 1))  # [N, N]

    def f(v):
        # v: [1, N] per shard; stacked list semantics → scalar per shard
        out = Tensor(jnp.zeros((), jnp.float32), _internal=True)
        t = Tensor(v[0], _internal=True)
        dist.reduce_scatter(out, t, group=g)
        return out._value[None]   # give rank-0 a concat axis

    out = _run(mesh, f, jnp.asarray(x), P("x", None), P("x"))
    np.testing.assert_allclose(np.asarray(out),
                               np.arange(N, dtype=np.float32) * N)


def test_alltoall_single_shard_map(mesh):
    g = _grp()
    # row r holds value r in all N slots; after all-to-all slot s holds s
    x = jnp.tile(jnp.arange(N, dtype=jnp.float32)[:, None], (1, N))

    def f(v):
        out = Tensor(jnp.zeros_like(v[0]), _internal=True)
        t = Tensor(v[0], _internal=True)
        dist.alltoall_single(out, t, group=g)
        return out._value[None]

    out = _run(mesh, f, x, P("x", None), P("x", None))
    expect = np.tile(np.arange(N, dtype=np.float32)[None, :], (N, 1))
    np.testing.assert_allclose(np.asarray(out), expect)


def test_ppermute_send_recv_shard_map(mesh):
    """send/recv pair = ppermute ring shift inside shard_map."""
    g = _grp()
    x = jnp.arange(N, dtype=jnp.float32)

    def f(v):
        t = Tensor(v, _internal=True)

        def impl(val, *, axis):
            n = jax.lax.axis_size(axis)
            perm = [(i, (i + 1) % n) for i in range(n)]
            return jax.lax.ppermute(val, axis, perm)

        from paddle_tpu.core.dispatch import dispatch
        out = dispatch("ppermute_shift", impl, (t,), dict(axis="x"))
        return out._value

    out = _run(mesh, f, x, P("x"), P("x"))
    np.testing.assert_allclose(np.asarray(out),
                               np.roll(np.arange(N, dtype=np.float32), 1))


def test_barrier_and_wait(mesh):
    g = _grp()
    dist.barrier(group=g)  # eager barrier: device sync only
    t = paddle.to_tensor([1.0])
    dist.wait(t)


# ---------------- eager honesty guards ----------------

def test_eager_all_reduce_replicated_ok(mesh):
    g = _grp()
    t = paddle.to_tensor([1.0, 2.0])  # single-device array → replicated
    out = dist.all_reduce(t, group=g)
    np.testing.assert_allclose(out.numpy(), [1.0, 2.0])


def test_eager_all_reduce_sharded_raises(mesh):
    g = _grp()
    sh = NamedSharding(mesh, P("x"))
    arr = jax.device_put(jnp.arange(8, dtype=jnp.float32), sh)
    t = Tensor(arr, _internal=True)
    with pytest.raises(RuntimeError, match="non-replicated"):
        dist.all_reduce(t, group=g)


def test_eager_send_recv_raise(mesh):
    g = _grp()
    t = paddle.to_tensor([1.0])
    with pytest.raises(RuntimeError, match="ppermute"):
        dist.send(t, dst=1, group=g)
    with pytest.raises(RuntimeError, match="ppermute"):
        dist.recv(t, src=1, group=g)


# ---------------- new_group ranks handling ----------------

def test_new_group_infers_axis_from_ranks():
    devs = np.array(jax.devices()[:8]).reshape(2, 4)
    m = Mesh(devs, ("dp", "mp"))
    dist.env.set_global_mesh(m)
    try:
        g = dist.new_group(ranks=[0, 1, 2, 3])   # row 0 along mp
        assert g.axis_name == "mp"
        g2 = dist.new_group(ranks=[0, 4])        # column along dp
        assert g2.axis_name == "dp"
        with pytest.raises(ValueError, match="single axis"):
            dist.new_group(ranks=[0, 5])         # diagonal: no axis
    finally:
        dist.env.set_global_mesh(None)
        group_mod._default_group = None


def test_object_collectives_and_monitored_barrier():
    objs = []
    dist.all_gather_object(objs, {"a": 1})
    assert objs and objs[0] == {"a": 1}
    lst = [{"x": 2}]
    assert dist.broadcast_object_list(lst) == [{"x": 2}]
    out = []
    dist.scatter_object_list(out, [{"r": 0}, {"r": 1}])
    assert out and "r" in out[0]
    dist.monitored_barrier(timeout=5)


def test_dist_split_linear_and_embedding():
    import numpy as np
    from paddle_tpu.distributed import split_api
    split_api.reset_split_cache()
    x = paddle.to_tensor(
        np.random.default_rng(0).normal(size=(2, 8)).astype(np.float32))
    y1 = dist.split(x, (8, 12), operation="linear", axis=1,
                    name="col_t")
    assert tuple(y1.shape) == (2, 12)
    y2 = dist.split(x, (8, 12), operation="linear", axis=1,
                    name="col_t")
    np.testing.assert_allclose(y1.numpy(), y2.numpy())  # cached weights
    ids = paddle.to_tensor(np.array([[0, 3], [5, 1]], np.int64))
    e = dist.split(ids, (16, 6), operation="embedding", name="emb_t")
    assert tuple(e.shape) == (2, 2, 6)


def test_dist_split_anonymous_calls_get_fresh_weights():
    import numpy as np
    from paddle_tpu.distributed import split_api
    split_api.reset_split_cache()
    x = paddle.to_tensor(
        np.random.default_rng(1).normal(size=(2, 8)).astype(np.float32))
    a = dist.split(x, (8, 12), operation="linear", axis=1)
    b = dist.split(x, (8, 12), operation="linear", axis=1)
    assert not np.allclose(a.numpy(), b.numpy())  # independent params
    import pytest as _pytest
    dist.split(x, (8, 12), operation="linear", axis=1, name="w")
    with _pytest.raises(ValueError, match="weight_attr"):
        from paddle_tpu.nn import initializer as I
        dist.split(x, (8, 12), operation="linear", axis=1, name="w",
                   weight_attr=I.Constant(0.5))


def test_unflatten_negative_axis():
    import numpy as np
    u = paddle.nn.Unflatten(-1, [2, 3])
    out = u(paddle.to_tensor(np.zeros((4, 6), np.float32)))
    assert tuple(out.shape) == (4, 2, 3)


def test_dist_split_named_reuse_with_equal_attr_config():
    import numpy as np
    from paddle_tpu.distributed import split_api
    from paddle_tpu.nn import initializer as I
    split_api.reset_split_cache()
    x = paddle.to_tensor(np.ones((2, 8), np.float32))
    a = dist.split(x, (8, 4), operation="linear", axis=1, name="eqw",
                   weight_attr=I.Constant(0.5))
    b = dist.split(x, (8, 4), operation="linear", axis=1, name="eqw",
                   weight_attr=I.Constant(0.5))  # fresh-but-equal attr
    np.testing.assert_allclose(a.numpy(), b.numpy())
