"""Training through the static graph: one program under
``program_guard``, ``auto_cast``, the optimizer's ``minimize``, and
``Executor.run(feed=..., fetch_list=[loss])`` each step with the loss
read on the host, as a user's logging loop does."""
from benchmarks.runners import _train


def run(ctx):
    import paddle_tpu as paddle
    from paddle_tpu import optimizer, static
    from benchmarks.harness import device_object
    traffic, fam = ctx.traffic, ctx.family
    paddle.seed(ctx.seed % (2 ** 31))
    paddle.enable_static()
    main, startup = static.Program(), static.Program()
    with static.program_guard(main, startup):
        feeds = {name: static.data(name, [traffic["batch"], traffic["seq"]],
                                   "int64") for name in fam.FEEDS}
        model = fam.build(ctx.config)
        with paddle.amp.auto_cast(**traffic["amp"]):
            loss = fam.loss(model, feeds)
        opt = getattr(optimizer, traffic["optimizer"]["name"])(
            learning_rate=traffic["optimizer"]["learning_rate"],
            parameters=model.parameters())
        opt.minimize(loss)
    exe = static.Executor()

    def step(feed):
        return float(exe.run(main, feed=feed, fetch_list=[loss])[0])

    samples, losses = _train.measure(ctx, step, "exe.run")
    device = device_object()
    paddle.disable_static()
    rel_l2 = _train.forward_check(ctx, model)
    return _train.result(ctx, samples, losses, rel_l2, device)
