import numpy as np
import pytest

import paddle_tpu as paddle


def test_to_tensor_dtypes():
    t = paddle.to_tensor([1, 2, 3])
    assert t.dtype == paddle.int64
    t = paddle.to_tensor([1.0, 2.0])
    assert t.dtype == paddle.float32
    t = paddle.to_tensor(np.zeros((2, 2), np.float64))
    assert t.dtype == paddle.float64
    t = paddle.to_tensor([1, 2], dtype="bfloat16")
    assert t.dtype == paddle.bfloat16
    assert t.dtype.name == "bfloat16"


def test_shape_props():
    t = paddle.zeros([2, 3, 4])
    assert t.shape == [2, 3, 4]
    assert t.ndim == 3
    assert t.size == 24
    assert t.numel().item() == 24
    assert isinstance(repr(t), str)


def test_arith_dunders():
    a = paddle.to_tensor([1.0, 2.0])
    b = paddle.to_tensor([3.0, 4.0])
    np.testing.assert_allclose((a + b).numpy(), [4, 6])
    np.testing.assert_allclose((a - b).numpy(), [-2, -2])
    np.testing.assert_allclose((a * b).numpy(), [3, 8])
    np.testing.assert_allclose((b / a).numpy(), [3, 2])
    np.testing.assert_allclose((a ** 2).numpy(), [1, 4])
    np.testing.assert_allclose((2.0 - a).numpy(), [1, 0])
    np.testing.assert_allclose((-a).numpy(), [-1, -2])
    np.testing.assert_allclose((a @ b).numpy(), 11)


def test_comparisons():
    a = paddle.to_tensor([1.0, 2.0, 3.0])
    assert (a > 1.5).numpy().tolist() == [False, True, True]
    assert (a == 2.0).numpy().tolist() == [False, True, False]
    assert paddle.allclose(a, a).item()


def test_indexing():
    t = paddle.arange(12).reshape([3, 4])
    assert t[0].shape == [4]
    assert t[0, 1].item() == 1
    assert t[:, 1].numpy().tolist() == [1, 5, 9]
    assert t[1:, :2].shape == [2, 2]
    # boolean mask
    m = paddle.to_tensor([True, False, True])
    assert t[m].shape == [2, 4]
    # tensor index
    idx = paddle.to_tensor([0, 2])
    assert t[idx].shape == [2, 4]


def test_setitem():
    t = paddle.zeros([3, 3])
    t[0, 0] = 5.0
    assert t[0, 0].item() == 5.0
    t[1] = paddle.ones([3])
    np.testing.assert_allclose(t[1].numpy(), [1, 1, 1])


def test_astype_cast():
    t = paddle.to_tensor([1.5, 2.5])
    i = t.astype("int32")
    assert i.dtype == paddle.int32
    b = t.cast("bfloat16")
    assert b.dtype == paddle.bfloat16


def test_item_and_float():
    t = paddle.to_tensor(3.5)
    assert float(t) == 3.5
    assert t.item() == 3.5


def test_clone_detach():
    a = paddle.to_tensor([1.0], stop_gradient=False)
    b = a.detach()
    assert b.stop_gradient
    c = a.clone()
    assert not c.stop_gradient


def test_inplace_ops():
    t = paddle.ones([3])
    t.add_(paddle.ones([3]))
    np.testing.assert_allclose(t.numpy(), [2, 2, 2])
    t.set_value(np.zeros(3, np.float32))
    np.testing.assert_allclose(t.numpy(), [0, 0, 0])


def test_iteration():
    t = paddle.arange(6).reshape([3, 2])
    rows = list(t)
    assert len(rows) == 3
    assert rows[0].shape == [2]


def test_round3_method_fills():
    t = paddle.to_tensor(np.array([-2.0, 0.5, 3.0], np.float32))
    assert t.ndimension() == 1
    s = t.sigmoid().numpy()
    np.testing.assert_allclose(s, 1 / (1 + np.exp(-t.numpy())),
                               rtol=1e-5)
    sm = t.softmax().numpy()
    np.testing.assert_allclose(sm.sum(), 1.0, rtol=1e-6)
    t.clip_(min=0.0)
    assert t.numpy().min() >= 0.0
    t.fill_(7.0)
    np.testing.assert_allclose(t.numpy(), 7.0)
    t.zero_()
    np.testing.assert_allclose(t.numpy(), 0.0)
    t.fill_(2.0)
    t.scale_(3.0, bias=1.0)
    np.testing.assert_allclose(t.numpy(), 7.0)
    a = paddle.to_tensor(np.zeros(3, np.float32))
    a.lerp_(paddle.to_tensor(np.ones(3, np.float32)), 0.25)
    np.testing.assert_allclose(a.numpy(), 0.25)
    nz = paddle.to_tensor(np.array([0.0, 1.0, 0.0, 2.0])).nonzero()
    np.testing.assert_array_equal(np.asarray(nz.numpy()).ravel(), [1, 3])


def test_device_index_past_the_count_is_an_error():
    """`TPUPlace(n)` / `.tpu(n)` used to wrap (`n % count`): device 3
    of 1 was device 0.  On the CPU backend the accelerator alias still
    resolves to the host's devices, by index."""
    import jax
    n = len(jax.devices())
    x = paddle.to_tensor([1.0, 2.0])
    assert x.tpu(n - 1).value().devices() == {jax.devices()[n - 1]}
    assert paddle.TPUPlace(0).jax_device() == jax.devices()[0]
    with pytest.raises(ValueError, match="no such device"):
        paddle.TPUPlace(n).jax_device()
    with pytest.raises(ValueError, match="no such device"):
        x.tpu(n)
