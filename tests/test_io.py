import os

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import nn, optimizer
from paddle_tpu.io import (DataLoader, Dataset, TensorDataset, BatchSampler,
                           DistributedBatchSampler, RandomSampler, Subset,
                           random_split)


class _SquareDS(Dataset):
    def __init__(self, n=20):
        self.n = n

    def __getitem__(self, i):
        return np.asarray([i], np.float32), np.asarray([i * i], np.float32)

    def __len__(self):
        return self.n


def test_dataloader_basic():
    dl = DataLoader(_SquareDS(), batch_size=4)
    batches = list(dl)
    assert len(batches) == 5
    x, y = batches[0]
    assert x.shape == [4, 1]
    np.testing.assert_allclose(y.numpy()[:, 0], [0, 1, 4, 9])


def test_dataloader_shuffle_drop_last():
    dl = DataLoader(_SquareDS(10), batch_size=3, shuffle=True,
                    drop_last=True)
    batches = list(dl)
    assert len(batches) == 3
    assert all(b[0].shape == [3, 1] for b in batches)


def test_dataloader_workers():
    dl = DataLoader(_SquareDS(16), batch_size=4, num_workers=2)
    xs = sorted(float(v) for b in dl for v in b[0].numpy()[:, 0])
    assert xs == [float(i) for i in range(16)]


def test_tensor_dataset_and_split():
    xs = paddle.arange(10, dtype="float32")
    ds = TensorDataset([xs.reshape([10, 1])])
    a, b = random_split(ds, [7, 3])
    assert len(a) == 7 and len(b) == 3
    sub = Subset(ds, [1, 3])
    assert len(sub) == 2


def test_distributed_batch_sampler():
    ds = _SquareDS(20)
    s0 = DistributedBatchSampler(ds, batch_size=2, num_replicas=4, rank=0)
    s1 = DistributedBatchSampler(ds, batch_size=2, num_replicas=4, rank=1)
    i0 = [i for b in s0 for i in b]
    i1 = [i for b in s1 for i in b]
    assert len(i0) == len(i1) == 5
    assert not set(i0) & set(i1)
    s0.set_epoch(1)


def test_save_load_state_dict(tmp_path):
    model = nn.Sequential(nn.Linear(4, 4), nn.LayerNorm(4))
    opt = optimizer.Adam(learning_rate=0.1,
                         parameters=model.parameters())
    model(paddle.randn([2, 4])).sum().backward()
    opt.step()
    p = str(tmp_path / "ckpt.pdparams")
    po = str(tmp_path / "ckpt.pdopt")
    paddle.save(model.state_dict(), p)
    paddle.save(opt.state_dict(), po)

    model2 = nn.Sequential(nn.Linear(4, 4), nn.LayerNorm(4))
    missing, unexpected = model2.set_state_dict(paddle.load(p))
    assert not missing and not unexpected
    np.testing.assert_allclose(model2[0].weight.numpy(),
                               model[0].weight.numpy())
    opt2 = optimizer.Adam(learning_rate=0.1,
                          parameters=model2.parameters())
    model2(paddle.randn([2, 4])).sum().backward()
    opt2.step()
    opt2.set_state_dict(paddle.load(po))


def test_save_load_bf16(tmp_path):
    t = paddle.to_tensor([1.5, 2.5], dtype="bfloat16")
    p = str(tmp_path / "t.pd")
    paddle.save({"t": t}, p)
    loaded = paddle.load(p)
    assert loaded["t"].dtype == paddle.bfloat16
    np.testing.assert_allclose(
        loaded["t"].astype("float32").numpy(), [1.5, 2.5])


def test_save_load_nested(tmp_path):
    obj = {"a": [paddle.ones([2]), 3], "b": {"c": paddle.zeros([1])},
           "s": "hello"}
    p = str(tmp_path / "n.pd")
    paddle.save(obj, p)
    loaded = paddle.load(p)
    assert loaded["s"] == "hello"
    np.testing.assert_allclose(loaded["a"][0].numpy(), [1, 1])


def test_dataloader_multiprocess_workers():
    import numpy as np
    from paddle_tpu.io import DataLoader, Dataset

    class Sq(Dataset):
        def __len__(self):
            return 17

        def __getitem__(self, i):
            return np.float32(i * i)

    dl = DataLoader(Sq(), batch_size=4, num_workers=2, shuffle=False)
    got = [np.asarray(b) for b in dl]
    flat = np.concatenate([g.ravel() for g in got])
    np.testing.assert_allclose(flat, np.arange(17, dtype=np.float32) ** 2)


def test_incubate_jacobian():
    import numpy as np
    import paddle_tpu as paddle

    x = paddle.to_tensor(np.array([1.0, 2.0, 3.0], np.float32))
    j = paddle.incubate.autograd_functional_jacobian(
        lambda t: t * t, x)
    np.testing.assert_allclose(np.asarray(j._value),
                               np.diag([2.0, 4.0, 6.0]), rtol=1e-6)


def test_native_collate_kernels():
    """The C host-runtime kernels (paddle_tpu._native) match numpy and
    back default_collate_fn."""
    import numpy as np
    from paddle_tpu import _native
    from paddle_tpu.io import default_collate_fn

    arrs = [np.random.RandomState(i).randn(3, 5).astype(np.float32)
            for i in range(4)]
    np.testing.assert_array_equal(_native.fast_stack(arrs),
                                  np.stack(arrs))
    src = np.stack(arrs)
    np.testing.assert_array_equal(_native.gather_rows(src, [3, 1, 1]),
                                  src[[3, 1, 1]])
    # ragged/mixed input falls back to np.stack semantics
    out = default_collate_fn(arrs)
    np.testing.assert_array_equal(np.asarray(out._value), src)


def test_dataloader_shared_memory_workers():
    """use_shared_memory routes worker batches through the native shm
    ring (pipe only carries tokens); values identical to in-process."""
    from paddle_tpu._native import shm_ring_available
    if not shm_ring_available():
        pytest.skip("no native shm ring on this host")
    from paddle_tpu.io import DataLoader, Dataset

    class DS(Dataset):
        def __len__(self):
            return 64

        def __getitem__(self, i):
            rng = np.random.RandomState(i)
            return (rng.randn(8, 8).astype(np.float32),
                    np.array([i], np.int64))

    ref = list(DataLoader(DS(), batch_size=16, num_workers=0))
    got = list(DataLoader(DS(), batch_size=16, num_workers=2,
                          use_shared_memory=True))
    assert len(got) == len(ref)
    for (rx, ry), (gx, gy) in zip(ref, got):
        np.testing.assert_allclose(np.asarray(gx._value),
                                   np.asarray(rx._value))
        np.testing.assert_array_equal(np.asarray(gy._value),
                                      np.asarray(ry._value))


def test_dataloader_shm_oversized_batch_falls_back():
    """A batch larger than the slot uses the pipe for that batch."""
    from paddle_tpu._native import shm_ring_available
    if not shm_ring_available():
        pytest.skip("no native shm ring on this host")
    import os
    from paddle_tpu.io import DataLoader, Dataset

    class Big(Dataset):
        def __len__(self):
            return 8

        def __getitem__(self, i):
            return (np.full((64, 1024), float(i), np.float32),)

    os.environ["PADDLE_TPU_SHM_SLOT_MB"] = "1"  # 1MB slots; batch ~2MB
    try:
        out = list(DataLoader(Big(), batch_size=8, num_workers=2,
                              use_shared_memory=True))
    finally:
        del os.environ["PADDLE_TPU_SHM_SLOT_MB"]
    assert len(out) == 1
    x = np.asarray(out[0][0]._value)
    assert x.shape == (8, 64, 1024)
    np.testing.assert_allclose(x[3, 0, 0], 3.0)


class _PlatformDS(Dataset):
    """Reports, from inside a worker, where jax would run."""

    def __getitem__(self, i):
        import os
        import jax
        plat = {"cpu": 0}.get(jax.config.jax_platforms, 1)
        env = {"cpu": 0}.get(os.environ.get("JAX_PLATFORMS"), 1)
        return np.array([plat, env, os.getpid()], np.int64)

    def __len__(self):
        return 8


def test_dataloader_workers_never_take_the_chip(monkeypatch):
    """A worker process is pinned to the host before it can initialise
    a backend — the chip belongs to the parent — whatever the parent's
    environment says; the parent's environment is left as it was."""
    import os
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    rows = np.concatenate([np.asarray(b) for b in DataLoader(
        _PlatformDS(), batch_size=4, num_workers=2)])
    assert (rows[:, :2] == 0).all()
    assert (rows[:, 2] != os.getpid()).all()      # ran in workers
    assert "JAX_PLATFORMS" not in os.environ
