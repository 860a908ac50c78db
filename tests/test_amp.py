import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import nn, optimizer, amp
import paddle_tpu.nn.functional as F


def test_auto_cast_o1_dtypes():
    lin = nn.Linear(4, 4)
    x = paddle.randn([2, 4])
    with paddle.amp.auto_cast(dtype="bfloat16", level="O1"):
        y = lin(x)
        assert y.dtype == paddle.bfloat16
        # black-list op stays f32
        s = paddle.nn.functional.softmax(y)
        assert s.dtype == paddle.float32
    y2 = lin(x)
    assert y2.dtype == paddle.float32


def test_auto_cast_disabled():
    lin = nn.Linear(4, 4)
    x = paddle.randn([2, 4])
    with paddle.amp.auto_cast(enable=False, dtype="bfloat16"):
        y = lin(x)
    assert y.dtype == paddle.float32


def test_amp_training_bf16_converges():
    paddle.seed(3)
    model = nn.Sequential(nn.Linear(8, 32), nn.ReLU(), nn.Linear(32, 1))
    opt = optimizer.Adam(learning_rate=0.01,
                         parameters=model.parameters())
    rng = np.random.RandomState(0)
    xv = rng.rand(32, 8).astype(np.float32)
    yv = (xv @ rng.rand(8, 1)).astype(np.float32)
    x, y = paddle.to_tensor(xv), paddle.to_tensor(yv)
    losses = []
    for _ in range(30):
        with paddle.amp.auto_cast(dtype="bfloat16", level="O1"):
            pred = model(x)
            loss = F.mse_loss(pred.astype("float32"), y)
        loss.backward()
        opt.step()
        opt.clear_grad()
        losses.append(float(loss.item()))
    assert losses[-1] < losses[0] * 0.5
    # master params stay f32
    assert model[0].weight.dtype == paddle.float32


def test_grad_scaler_fp16_flow():
    model = nn.Linear(4, 4)
    opt = optimizer.SGD(learning_rate=0.1, parameters=model.parameters())
    scaler = paddle.amp.GradScaler(init_loss_scaling=1024.0)
    x = paddle.randn([4, 4])
    with paddle.amp.auto_cast(dtype="float16", level="O1"):
        loss = model(x).astype("float32").sum()
    scaled = scaler.scale(loss)
    scaled.backward()
    scaler.step(opt)
    scaler.update()
    assert np.isfinite(model.weight.numpy()).all()


def test_grad_scaler_inf_skips_step():
    model = nn.Linear(2, 2)
    w_before = model.weight.numpy().copy()
    opt = optimizer.SGD(learning_rate=0.1, parameters=model.parameters())
    scaler = paddle.amp.GradScaler(init_loss_scaling=2.0 ** 15)
    x = paddle.to_tensor(np.asarray([[3e38, 3e38]], np.float32))
    loss = model(x).sum()
    scaler.scale(loss).backward()
    scaler.step(opt)  # grads overflow → step skipped
    np.testing.assert_allclose(model.weight.numpy(), w_before)
    assert scaler._scale < 2.0 ** 15  # scale decreased


def test_amp_decorate_o2():
    model = nn.Linear(4, 4)
    model = paddle.amp.decorate(model, level="O2", dtype="bfloat16")
    assert model.weight.dtype == paddle.bfloat16


def test_amp_conv_backward_bf16():
    """Regression: conv under autocast used preferred_element_type=f32 +
    astype, whose transpose rule mixes an f32 cotangent with the bf16
    weight and raises inside lax.conv_general_dilated (r4)."""
    paddle.seed(0)
    conv = nn.Conv2D(3, 8, 3)
    x = paddle.to_tensor(
        np.random.default_rng(0).standard_normal((2, 3, 16, 16))
        .astype(np.float32))
    with paddle.amp.auto_cast(dtype="bfloat16", level="O1"):
        h = conv(x)
    assert h.dtype == paddle.bfloat16
    loss = h.sum()
    loss.backward()
    assert conv.weight.grad is not None
    assert np.isfinite(conv.weight.grad.numpy().astype(np.float32)).all()


def test_amp_conv_transpose_backward_bf16():
    paddle.seed(0)
    conv = nn.Conv2DTranspose(3, 8, 3)
    x = paddle.to_tensor(
        np.random.default_rng(0).standard_normal((2, 3, 8, 8))
        .astype(np.float32))
    with paddle.amp.auto_cast(dtype="bfloat16", level="O1"):
        h = conv(x)
    loss = h.sum()
    loss.backward()
    assert conv.weight.grad is not None


def test_static_auto_cast_records_bf16_casts():
    """auto_cast inside program_guard must actually rewrite dtypes:
    round-5 found the static hook consuming ops before the AMP caster
    ran, silently building all-f32 'AMP' programs."""
    import jax
    import numpy as np
    from paddle_tpu import static, optimizer

    paddle.enable_static()
    try:
        main = static.Program()
        with static.program_guard(main):
            x = static.data("x", [4, 8], "float32")
            y = static.data("y", [4, 1], "float32")
            lin = paddle.nn.Linear(8, 1)
            with paddle.amp.auto_cast(dtype="bfloat16", level="O1"):
                loss = paddle.nn.functional.mse_loss(lin(x), y)
            opt = optimizer.SGD(learning_rate=0.1,
                                parameters=main.all_parameters())
            opt.minimize(loss)
        exe = static.Executor()
        fd = {"x": np.ones((4, 8), np.float32),
              "y": np.ones((4, 1), np.float32)}
        call, _ = exe._prologue(main, fd, [loss], 0)
        entry, fv, pv, ov, rv, lr, st = call
        aval = lambda t: jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(np.shape(a), a.dtype), t)
        txt = jax.jit(entry["pure"]).lower(
            aval(fv), aval(pv), aval(ov), aval(rv),
            jax.ShapeDtypeStruct((), np.float32),
            jax.ShapeDtypeStruct((), np.int32)).as_text()
        assert "bf16" in txt, "static auto_cast(bfloat16) produced no bf16"
        # and the compiled step still trains — the weight itself, not
        # a bf16 copy cast once at build time in its place
        w0 = np.asarray(lin.weight.numpy()).copy()
        (l0,) = exe.run(main, feed=fd, fetch_list=[loss])
        for _ in range(5):
            (l1,) = exe.run(main, feed=fd, fetch_list=[loss])
        assert float(l1) < float(l0)
        assert any(p is lin.weight for p in entry["params"])
        assert not np.array_equal(w0, np.asarray(lin.weight.numpy()))
    finally:
        paddle.disable_static()


def test_rewrite_program_bf16_post_hoc_pass():
    """static.amp.bf16.rewrite_program_bf16: cast insertion over a
    program built WITHOUT autocast — white ops get bf16 inputs, the
    step still trains, grads stay f32 on the params."""
    import jax
    import numpy as np
    from paddle_tpu import static, optimizer
    from paddle_tpu.static import amp as samp

    paddle.enable_static()
    try:
        main = static.Program()
        with static.program_guard(main):
            x = static.data("x", [8, 16], "float32")
            y = static.data("y", [8, 1], "float32")
            h = paddle.nn.Linear(16, 32)(x)
            h = paddle.nn.functional.relu(h)
            pred = paddle.nn.Linear(32, 1)(h)
            loss = paddle.nn.functional.mse_loss(pred, y)
        n_ops = len(main.global_block().ops)
        samp.bf16.rewrite_program_bf16(main)
        assert len(main.global_block().ops) > n_ops, "no casts inserted"
        with static.program_guard(main):
            opt = optimizer.SGD(learning_rate=0.1,
                                parameters=main.all_parameters())
            opt.minimize(loss)
        exe = static.Executor()
        rng = np.random.RandomState(0)
        fd = {"x": rng.rand(8, 16).astype(np.float32),
              "y": rng.rand(8, 1).astype(np.float32)}
        call, _ = exe._prologue(main, fd, [loss], 0)
        entry, fv, pv, ov, rv, lr, st = call
        aval = lambda t: jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(np.shape(a), a.dtype), t)
        txt = jax.jit(entry["pure"]).lower(
            aval(fv), aval(pv), aval(ov), aval(rv),
            jax.ShapeDtypeStruct((), np.float32),
            jax.ShapeDtypeStruct((), np.int32)).as_text()
        assert "bf16" in txt, "rewrite produced no bf16"
        losses = [float(exe.run(main, feed=fd, fetch_list=[loss])[0])
                  for _ in range(30)]
        assert losses[-1] < losses[0] * 0.7, (losses[0], losses[-1])
        for p in main.all_parameters():  # params stayed f32 (O1 rewrite)
            assert p._value.dtype == np.float32
    finally:
        paddle.disable_static()


def test_rewrite_program_bf16_restores_f32_for_black_ops():
    """A black op downstream of a white op must get an f32 cast-back:
    the pass tracks EFFECTIVE dtypes (build-time avals go stale as it
    retargets), otherwise softmax/norm silently run in bf16."""
    import jax.numpy as jnp
    from paddle_tpu import static
    from paddle_tpu.static import amp as samp

    paddle.enable_static()
    try:
        main = static.Program()
        with static.program_guard(main):
            x = static.data("x", [4, 8], "float32")
            h = paddle.matmul(x, paddle.to_tensor(
                np.ones((8, 8), np.float32)))      # white
            s = paddle.nn.functional.softmax(h)    # black
        samp.bf16.rewrite_program_bf16(main)
        ops = main.global_block().ops
        sm = next(o for o in ops if o.type == "softmax")
        casts_to_f32 = [o for o in ops if o.type == "cast"
                        and any(o.outputs[0] is i for i in sm.inputs)
                        and o.outputs[0]._value.dtype == jnp.float32]
        assert casts_to_f32, (
            "softmax input not cast back to f32 after a white matmul")
        exe = static.Executor()
        (out,) = exe.run(main, feed={"x": np.ones((4, 8), np.float32)},
                         fetch_list=[s])
        np.testing.assert_allclose(np.asarray(out).sum(), 4.0, rtol=1e-5)
    finally:
        paddle.disable_static()
