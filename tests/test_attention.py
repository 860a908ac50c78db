"""Attention functionals: SDPA masking/dropout semantics + varlen."""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.nn import functional as F


def _np_attn(q, k, v, causal):
    s, h, d = q.shape[1], q.shape[2], q.shape[3]
    out = np.zeros_like(q)
    for b in range(q.shape[0]):
        for hh in range(h):
            sc = q[b, :, hh] @ k[b, :, hh].T / np.sqrt(d)
            if causal:
                sk = k.shape[1]
                mask = np.tril(np.ones((s, sk), bool), k=sk - s)
                sc = np.where(mask, sc, -1e30)
            e = np.exp(sc - sc.max(-1, keepdims=True))
            p = e / e.sum(-1, keepdims=True)
            out[b, :, hh] = p @ v[b, :, hh]
    return out


def test_sdpa_matches_numpy():
    rng = np.random.RandomState(0)
    q = rng.randn(2, 8, 2, 16).astype(np.float32)
    k = rng.randn(2, 8, 2, 16).astype(np.float32)
    v = rng.randn(2, 8, 2, 16).astype(np.float32)
    out = F.scaled_dot_product_attention(
        paddle.to_tensor(q), paddle.to_tensor(k), paddle.to_tensor(v),
        is_causal=True)
    np.testing.assert_allclose(out.numpy(), _np_attn(q, k, v, True),
                               atol=1e-5)


def test_sdpa_dropout_runs_and_differs():
    rng = np.random.RandomState(1)
    q = paddle.to_tensor(rng.randn(1, 16, 2, 8).astype(np.float32))
    out1 = F.scaled_dot_product_attention(q, q, q, dropout_p=0.5,
                                          training=True)
    out2 = F.scaled_dot_product_attention(q, q, q, dropout_p=0.5,
                                          training=True)
    # stochastic masks differ between calls
    assert not np.allclose(out1.numpy(), out2.numpy())
    out3 = F.scaled_dot_product_attention(q, q, q, dropout_p=0.5,
                                          training=False)
    ref = F.scaled_dot_product_attention(q, q, q)
    np.testing.assert_allclose(out3.numpy(), ref.numpy(), atol=1e-6)


def test_flash_attn_unpadded_blocks_cross_sequence():
    """Packed [3+5] tokens: attention must be block-diagonal per sequence
    (regression: cu_seqlens used to be ignored entirely)."""
    rng = np.random.RandomState(2)
    lens = [3, 5]
    total = sum(lens)
    q = rng.randn(total, 2, 16).astype(np.float32)
    k = rng.randn(total, 2, 16).astype(np.float32)
    v = rng.randn(total, 2, 16).astype(np.float32)
    cu = np.array([0, 3, 8], np.int32)
    out, _ = F.flash_attn_unpadded(
        paddle.to_tensor(q), paddle.to_tensor(k), paddle.to_tensor(v),
        paddle.to_tensor(cu), paddle.to_tensor(cu),
        max_seqlen_q=5, max_seqlen_k=5, scale=1.0 / 4.0)
    # reference: each sequence attends only to itself
    ref = np.zeros_like(q)
    for a, b in zip(cu[:-1], cu[1:]):
        qb = q[None, a:b]
        ref[a:b] = _np_attn(qb, k[None, a:b], v[None, a:b], False)[0]
    np.testing.assert_allclose(out.numpy(), ref, atol=1e-5)


def test_flash_attn_unpadded_causal():
    rng = np.random.RandomState(3)
    cu = np.array([0, 4, 10], np.int32)
    q = rng.randn(10, 1, 8).astype(np.float32)
    out, _ = F.flash_attn_unpadded(
        paddle.to_tensor(q), paddle.to_tensor(q), paddle.to_tensor(q),
        paddle.to_tensor(cu), paddle.to_tensor(cu),
        max_seqlen_q=6, max_seqlen_k=6, scale=1.0 / np.sqrt(8),
        causal=True)
    ref = np.zeros_like(q)
    for a, b in zip(cu[:-1], cu[1:]):
        qb = q[None, a:b]
        ref[a:b] = _np_attn(qb, qb, qb, True)[0]
    np.testing.assert_allclose(out.numpy(), ref, atol=1e-5)


def test_flash_attention_api():
    rng = np.random.RandomState(4)
    q = paddle.to_tensor(rng.randn(2, 8, 2, 16).astype(np.float32))
    out, _ = F.flash_attention(q, q, q, causal=True)
    ref = _np_attn(q.numpy(), q.numpy(), q.numpy(), True)
    np.testing.assert_allclose(out.numpy(), ref, atol=1e-5)


def test_sdp_kernel_context_forces_composite():
    """sdp_kernel(enable_flash=False) must force the XLA composite even
    where the Pallas gate would fire; numerics stay identical."""
    import numpy as np
    import paddle_tpu as paddle
    import paddle_tpu.nn.functional as F
    import importlib
    # the functional package re-exports the flash_attention FUNCTION,
    # shadowing the submodule attribute — load the module explicitly
    fa = importlib.import_module(
        "paddle_tpu.nn.functional.flash_attention")

    rng = np.random.default_rng(0)
    q = paddle.to_tensor(rng.standard_normal((2, 16, 2, 32),
                                             ).astype(np.float32))
    k = paddle.to_tensor(rng.standard_normal((2, 16, 2, 32),
                                             ).astype(np.float32))
    v = paddle.to_tensor(rng.standard_normal((2, 16, 2, 32),
                                             ).astype(np.float32))
    base = F.scaled_dot_product_attention(q, k, v, is_causal=True).numpy()
    calls = []
    orig = fa._flash_refusal

    def spy(*a, **kw):
        calls.append(a)
        return orig(*a, **kw)

    fa._flash_refusal = spy
    try:
        with fa.sdp_kernel(enable_flash=False):
            alt = F.scaled_dot_product_attention(
                q, k, v, is_causal=True).numpy()
        assert not calls, "pallas gate consulted despite enable_flash=False"
    finally:
        fa._flash_refusal = orig
    np.testing.assert_allclose(base, alt, rtol=1e-5, atol=1e-6)


# -- attention dropout in the flash kernels: routing, counters, rng ------

import contextlib
import importlib

import jax
import jax.numpy as jnp
import pytest

from paddle_tpu import observability as obs
from paddle_tpu import static
from paddle_tpu.framework.random import default_generator
from paddle_tpu.ops import pallas_gate, pallas_kernels

fa = importlib.import_module("paddle_tpu.nn.functional.flash_attention")


@pytest.fixture
def gate_open(monkeypatch):
    """The kernel gate as on a TPU whose probes passed (the kernels
    then run in interpret mode here), with counters on."""
    monkeypatch.setattr(pallas_gate, "pallas_enabled",
                        lambda kernel, manual=False: True)
    prev = obs.enable(True)
    obs.get_registry().clear()
    yield lambda: {k[len("attention.path."):]: v for k, v in
                   obs.get_registry().snapshot()["counters"].items()
                   if k.startswith("attention.path.")}
    obs.get_registry().clear()
    obs.enable(prev)
    paddle.disable_static()


def _state():
    return np.asarray(default_generator().state_tensor._value).copy()


def _seed_of_next_call():
    """What `_rng_op` will hand the next attention call: the second
    half of the generator state's split, through `_kernel_seed`."""
    _, sub = jax.random.split(default_generator().state_tensor._value)
    return np.asarray(fa._kernel_seed(sub))


def _qkv_t(shape=(2, 48, 2, 16), dtype="float32", seed=0):
    rng = np.random.RandomState(seed)
    return [paddle.to_tensor(rng.randn(*shape).astype(np.float32))
            .astype(dtype) for _ in range(3)]


def test_sdpa_dropout_takes_the_kernel(gate_open):
    """With the gate open and no mask, dropout_p > 0 runs the flash
    kernels under the mask `flash_dropout_keep` writes out for the seed
    the call derived; same paddle.seed, same output."""
    q, k, v = _qkv_t()
    paddle.seed(11)
    seed = _seed_of_next_call()
    out = F.scaled_dot_product_attention(q, k, v, dropout_p=0.5).numpy()
    paddle.seed(11)
    again = F.scaled_dot_product_attention(q, k, v, dropout_p=0.5).numpy()
    other = F.scaled_dot_product_attention(q, k, v, dropout_p=0.5).numpy()
    np.testing.assert_array_equal(out, again)
    assert not np.allclose(out, other)
    assert gate_open() == {"flash_dropout": 3}
    with jax.enable_x64(False):
        keep = pallas_kernels.flash_dropout_keep(
            seed, 2, 48, 48, 2, 16, dropout_p=0.5)
        ref = fa._sdpa_ref(q._value, k._value, v._value, None, False,
                           0.25, 0.5, keep=keep)
    np.testing.assert_allclose(out, np.asarray(ref), atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("case,expect", [
    ("plain", "flash"),
    ("dropout", "flash_dropout"),
    ("mask", "composite.mask"),
    ("mask_dropout", "composite.mask"),
    ("sdp_kernel_off", "composite.sdp_kernel"),
    ("float16", "composite.dtype"),
    ("long_keys", "composite.vmem"),
])
def test_attention_path_counts(gate_open, case, expect):
    """One count a build, under the path taken and, for a composite,
    why: the kernel only with no mask and an open gate."""
    q, k, v = _qkv_t()
    kw = {}
    if "mask" in case:
        kw["attn_mask"] = paddle.to_tensor(np.zeros((2, 2, 48, 48),
                                                    np.float32))
    if "dropout" in case:
        kw["dropout_p"] = 0.1
    if case == "float16":
        q, k, v = (t.astype("float16") for t in (q, k, v))
    if case == "long_keys":             # 2 x 40960 x 128 lanes x 4 B > 8 MB
        assert fa._flash_refusal(16, 40960, "float32") == "vmem"
        assert fa._attention_path(q, np.zeros((2, 40960, 2, 16)), None,
                                  False) is False
    elif case == "sdp_kernel_off":
        with fa.sdp_kernel(enable_flash=False):
            F.scaled_dot_product_attention(q, k, v, **kw)
    else:
        F.scaled_dot_product_attention(q, k, v, **kw)
    assert gate_open() == {expect: 1}


def test_attention_path_gate_closed_is_composite(monkeypatch):
    """Off the TPU the gate is closed: the composite, reason `gate`,
    and the dropout form asks the gate for its own probe."""
    asked = []
    monkeypatch.setattr(pallas_gate, "pallas_enabled",
                        lambda k, manual=False: asked.append(k))
    prev = obs.enable(True)
    obs.get_registry().clear()
    try:
        q, k, v = _qkv_t()
        F.scaled_dot_product_attention(q, k, v)
        F.scaled_dot_product_attention(q, k, v, dropout_p=0.1)
        counts = obs.get_registry().snapshot()["counters"]
    finally:
        obs.get_registry().clear()
        obs.enable(prev)
    assert asked == ["flash_attention", "flash_attention_dropout"]
    assert counts == {"attention.path.composite.gate": 2}
    assert "flash_attention_dropout" in pallas_gate._PROBES


@pytest.mark.parametrize("tier", ["eager", "lazy", "static"])
def test_sdpa_dropout_advances_generator_once(gate_open, tier):
    """The generator's state moves exactly as one F.dropout call moves
    it, in every tier, whichever implementation attends."""
    q, k, v = _qkv_t()
    paddle.seed(5)
    s0 = _state()
    F.dropout(q, p=0.5).numpy()
    one_call = _state()
    assert not (s0 == one_call).all()
    paddle.seed(5)
    if tier == "static":
        paddle.enable_static()
        main = static.Program()
        with static.program_guard(main):
            x = static.data("x", [2, 48, 2, 16], "float32")
            y = F.scaled_dot_product_attention(x, x, x, dropout_p=0.5)
        exe = static.Executor()
        fd = {"x": q.numpy()}
        (a,) = exe.run(main, feed=fd, fetch_list=[y])
        np.testing.assert_array_equal(_state(), one_call)
        (b,) = exe.run(main, feed=fd, fetch_list=[y])
        assert not (a == b).all(), "same attention mask every run"
        assert gate_open() == {"flash_dropout": 1}     # one build
    else:
        cm = paddle.incubate.lazy_eager() if tier == "lazy" else \
            contextlib.nullcontext()
        with cm:
            out = F.scaled_dot_product_attention(q, k, v, dropout_p=0.5)
            out.numpy()
        np.testing.assert_array_equal(_state(), one_call)


def test_clone_for_test_drops_attention_dropout(gate_open):
    paddle.enable_static()
    paddle.seed(0)
    main = static.Program()
    with static.program_guard(main):
        x = static.data("x", [2, 48, 2, 16], "float32")
        y = F.scaled_dot_product_attention(x, x, x, dropout_p=0.5)
    test_prog = main.clone(for_test=True)
    exe = static.Executor()
    fd = {"x": _qkv_t()[0].numpy()}
    (a,) = exe.run(test_prog, feed=fd, fetch_list=[y])
    (b,) = exe.run(test_prog, feed=fd, fetch_list=[y])
    np.testing.assert_array_equal(a, b)
    paddle.disable_static()
    t = paddle.to_tensor(fd["x"])
    np.testing.assert_allclose(
        a, F.scaled_dot_product_attention(t, t, t).numpy(), atol=1e-6)
    paddle.enable_static()
    (c,) = exe.run(main, feed=fd, fetch_list=[y])
    (d,) = exe.run(main, feed=fd, fetch_list=[y])
    assert not (c == d).all()


def test_sdpa_dropout_kernel_backward_matches_masked_composite(gate_open):
    """Through the tape: dq, dk, dv of the dropout kernel equal the
    composite's under the same mask."""
    q, k, v = _qkv_t()
    for t in (q, k, v):
        t.stop_gradient = False
    paddle.seed(3)
    seed = _seed_of_next_call()
    (F.scaled_dot_product_attention(q, k, v, dropout_p=0.1,
                                    is_causal=True) ** 2).sum().backward()
    with jax.enable_x64(False):
        keep = pallas_kernels.flash_dropout_keep(
            seed, 2, 48, 48, 2, 16, dropout_p=0.1)
        want = jax.grad(lambda q, k, v: jnp.sum(fa._sdpa_ref(
            q, k, v, None, True, 0.25, 0.1, keep=keep) ** 2), (0, 1, 2))(
            q._value, k._value, v._value)
    for t, w in zip((q, k, v), want):
        np.testing.assert_allclose(t.grad.numpy(), np.asarray(w),
                                   atol=1e-4, rtol=1e-4)
