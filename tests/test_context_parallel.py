"""Sequence/context parallelism parity on the 8-device CPU mesh:
ring attention and Ulysses vs single-device full attention, plus
Megatron-SP layer helpers (SURVEY.md §2.3 SP/SEP rows)."""
import numpy as np
import pytest
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

import paddle_tpu as paddle
import paddle_tpu.distributed as dist
from paddle_tpu.distributed.fleet.meta_parallel import (
    ring_attention, ulysses_attention)
from paddle_tpu.nn.functional.flash_attention import _sdpa_ref
from paddle_tpu.distributed.communication import group as group_mod


@pytest.fixture(autouse=True)
def _cleanup():
    yield
    dist.env.set_global_mesh(None)
    group_mod._default_group = None


def _qkv(seed, B=2, S=64, H=4, D=16, dtype=jnp.float32):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return [jax.random.normal(k, (B, S, H, D), dtype) for k in ks]


@pytest.mark.parametrize("causal", [False, True])
def test_ring_attention_parity(causal):
    q, k, v = _qkv(0)
    ref = _sdpa_ref(q, k, v, None, causal, 0.25)
    mesh = Mesh(np.array(jax.devices()[:8]), ("sep",))
    got = ring_attention(q, k, v, causal=causal, mesh=mesh)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_ulysses_attention_parity(causal):
    q, k, v = _qkv(1)
    ref = _sdpa_ref(q, k, v, None, causal, 0.25)
    mesh = Mesh(np.array(jax.devices()[:4]), ("sep",))
    got = ulysses_attention(q, k, v, causal=causal, mesh=mesh)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_ring_attention_grad_parity():
    """Ring attention must train: grads vs the dense reference."""
    q, k, v = _qkv(2, S=32)
    mesh = Mesh(np.array(jax.devices()[:4]), ("sep",))

    def f_ring(q, k, v):
        return jnp.sum(jnp.square(ring_attention(
            q, k, v, causal=True, mesh=mesh)))

    def f_ref(q, k, v):
        return jnp.sum(jnp.square(_sdpa_ref(q, k, v, None, True, 0.25)))

    g_ring = jax.grad(f_ring, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_ring, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-5, rtol=5e-5)


def test_ring_attention_jit_sharded():
    """Under jit with seq-sharded inputs (the training configuration)."""
    q, k, v = _qkv(3)
    mesh = Mesh(np.array(jax.devices()[:8]), ("sep",))
    ref = _sdpa_ref(q, k, v, None, True, 0.25)
    sh = jax.sharding.NamedSharding(mesh, P(None, "sep"))
    qs, ks, vs = (jax.device_put(x, sh) for x in (q, k, v))
    got = jax.jit(lambda q, k, v: ring_attention(
        q, k, v, causal=True, mesh=mesh))(qs, ks, vs)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_sequence_parallel_linear_layers():
    """Column/RowSequenceParallelLinear match plain linears numerically
    (constraints only change placement), mp mesh present."""
    from paddle_tpu.distributed.fleet.utils.sequence_parallel_utils import (
        ColumnSequenceParallelLinear, RowSequenceParallelLinear,
        mark_as_sequence_parallel_parameter,
        register_sequence_parallel_allreduce_hooks)
    mesh = Mesh(np.array(jax.devices()[:8]).reshape(2, 4), ("dp", "mp"))
    dist.env.set_global_mesh(mesh)
    paddle.seed(11)
    col = ColumnSequenceParallelLinear(16, 32)
    row = RowSequenceParallelLinear(32, 16)
    x = paddle.to_tensor(
        np.random.RandomState(0).randn(2, 8, 16).astype(np.float32))
    y = row(col(x))
    # reference: same weights, plain matmul
    ref = (np.asarray(x._value) @ np.asarray(col.weight._value)
           + np.asarray(col.bias._value))
    ref = ref @ np.asarray(row.weight._value) + np.asarray(row.bias._value)
    np.testing.assert_allclose(np.asarray(y._value), ref, atol=1e-5,
                               rtol=1e-5)
    mark_as_sequence_parallel_parameter(col.bias)
    marked = register_sequence_parallel_allreduce_hooks(col)
    assert col.bias in marked


def test_ring_attention_tensor_autograd():
    """Paddle-Tensor inputs must keep the tape alive through the
    shard_map (grads flow to the producing layer)."""
    mesh = Mesh(np.array(jax.devices()[:4]), ("sep",))
    dist.env.set_global_mesh(mesh)
    paddle.seed(5)
    from paddle_tpu import nn
    proj = nn.Linear(16, 16)
    x = paddle.to_tensor(
        np.random.RandomState(1).randn(2, 32, 16).astype(np.float32))
    h = proj(x)
    qkv = paddle.reshape(h, [2, 32, 4, 4])
    out = ring_attention(qkv, qkv, qkv, causal=True, mesh=mesh)
    loss = paddle.sum(out * out)
    loss.backward()
    g = proj.weight.grad
    assert g is not None and float(paddle.abs(g).sum()) > 0


@pytest.mark.parametrize("causal", [False, True])
def test_ring_flash_attention_parity(causal):
    """The Pallas-blockwise ring path (interpret mode on CPU) must match
    dense attention exactly — fwd AND the ring backward with its
    rotating dk/dv accumulation."""
    import functools
    from paddle_tpu.ops.ring_flash_attention import (
        ring_flash_attention_local)

    q, k, v = _qkv(3, B=1, S=64, H=2, D=32)
    scale = 1.0 / (32 ** 0.5)
    mesh = Mesh(np.array(jax.devices()[:4]), ("sep",))
    spec = P(None, "sep", None, None)
    fn = jax.shard_map(
        functools.partial(ring_flash_attention_local, axis="sep",
                          axis_size=4, causal=causal, scale=scale),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False)

    ref_fn = lambda q, k, v: _sdpa_ref(q, k, v, None, causal, scale)
    # x32 at call time: interpret-mode lowering of the pallas grid loop
    # happens when fn() runs, and the framework's global x64 flag would
    # leak i64 loop carries into the i32 kernel body (the same
    # discipline as pallas_gate._run_probe)
    with jax.enable_x64(False):
        got = np.asarray(fn(q, k, v))
    np.testing.assert_allclose(got, np.asarray(ref_fn(q, k, v)),
                               atol=2e-5, rtol=2e-5)

    # grads: ring custom-vjp vs dense autodiff
    def loss(fn_):
        return lambda q, k, v: (fn_(q, k, v) * v.astype(
            fn_(q, k, v).dtype)).sum()
    with jax.enable_x64(False):
        g_got = jax.grad(lambda q, k, v: fn(q, k, v).sum(),
                         argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(lambda q, k, v: ref_fn(q, k, v).sum(),
                     argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(g_got, g_ref, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=3e-5, rtol=3e-5,
                                   err_msg=f"d{name}")
