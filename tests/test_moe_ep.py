"""Expert-parallel MoE on the 8-device CPU mesh: all_to_all dispatch over
the ep axis matches the dense (replicated) MoELayer (SURVEY.md §2.3 EP)."""
import numpy as np
import pytest
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

import paddle_tpu as paddle
import paddle_tpu.distributed as dist
from paddle_tpu import nn, optimizer
from paddle_tpu.distributed.communication import group as group_mod
from paddle_tpu.incubate.distributed.models.moe import MoELayer


@pytest.fixture(autouse=True)
def _cleanup():
    yield
    dist.env.set_global_mesh(None)
    group_mod._default_group = None


def _experts(seed, E=4, d=16):
    paddle.seed(seed)
    return [nn.Sequential(nn.Linear(d, 32), nn.GELU(), nn.Linear(32, d))
            for _ in range(E)]


def _moe(seed, E=4, d=16):
    paddle.seed(seed)
    return MoELayer(d_model=d, experts=_experts(seed + 1, E, d),
                    gate="naive", top_k=2, capacity_factor=8.0)


def test_global_scatter_gather_roundtrip():
    mesh = Mesh(np.array(jax.devices()[:4]), ("ep",))
    from paddle_tpu.distributed.fleet.meta_parallel import (
        global_scatter_local, global_gather_local)
    x = jnp.arange(4 * 2 * 3, dtype=jnp.float32).reshape(4, 2, 3)
    xs = jnp.stack([x + 100 * i for i in range(4)])  # per-device [E,C,D]

    def fn(xl):
        s = global_scatter_local(xl[0], axis="ep", axis_size=4)
        g = global_gather_local(s, axis="ep", axis_size=4)
        return g[None]

    out = jax.shard_map(fn, mesh=mesh, in_specs=P("ep"),
                        out_specs=P("ep"), check_vma=False)(xs)
    np.testing.assert_allclose(np.asarray(out), np.asarray(xs))


def test_moe_ep_forward_parity():
    x = np.random.RandomState(0).randn(16, 16).astype(np.float32)
    dense = _moe(5)
    y_ref = dense(paddle.to_tensor(x))
    aux_ref = float(dense.aux_loss)

    mesh = Mesh(np.array(jax.devices()[:8]).reshape(2, 4), ("dp", "ep"))
    dist.env.set_global_mesh(mesh)
    ep = _moe(5)  # same seeds → same weights
    y_ep = ep(paddle.to_tensor(x))
    assert ep._ep_engine not in (None, False), "EP engine not used"
    np.testing.assert_allclose(np.asarray(y_ep._value),
                               np.asarray(y_ref._value),
                               atol=2e-5, rtol=2e-5)


def test_moe_ep_training_loss_parity():
    def run(use_mesh):
        if use_mesh:
            mesh = Mesh(np.array(jax.devices()[:8]).reshape(2, 4),
                        ("dp", "ep"))
            dist.env.set_global_mesh(mesh)
        else:
            dist.env.set_global_mesh(None)
        m = _moe(9)
        opt = optimizer.SGD(learning_rate=0.05,
                            parameters=m.parameters())
        losses = []
        for i in range(5):
            rng = np.random.RandomState(50 + i)
            x = paddle.to_tensor(rng.randn(16, 16).astype(np.float32))
            t = paddle.to_tensor(rng.randn(16, 16).astype(np.float32))
            loss = paddle.nn.functional.mse_loss(m(x), t) + \
                m.aux_loss * 0.01
            loss.backward()
            opt.step()
            opt.clear_grad()
            losses.append(float(loss))
        return losses, m

    ref, _ = run(False)
    got, m = run(True)
    assert m._ep_engine not in (None, False), "EP engine not used"
    np.testing.assert_allclose(got, ref, rtol=2e-4, atol=2e-5)
