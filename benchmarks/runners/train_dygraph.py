"""Training written in dygraph under ``paddle.incubate.lazy_eager()``,
the auto-trace tier: forward, ``backward``, ``opt.step``,
``clear_grad`` and the loss read on the host each step."""
from benchmarks.runners import _train


def run(ctx):
    import paddle_tpu as paddle
    from paddle_tpu import optimizer
    from paddle_tpu.core import lazy
    from benchmarks.harness import device_object
    traffic, fam = ctx.traffic, ctx.family
    paddle.seed(ctx.seed % (2 ** 31))
    model = fam.build(ctx.config)
    opt = getattr(optimizer, traffic["optimizer"]["name"])(
        learning_rate=traffic["optimizer"]["learning_rate"],
        parameters=model.parameters())

    def step(feed):
        feeds = {k: paddle.to_tensor(v) for k, v in feed.items()}
        with paddle.amp.auto_cast(**traffic["amp"]):
            loss = fam.loss(model, feeds)
        loss.backward()
        opt.step()
        opt.clear_grad()
        return float(loss)

    with paddle.incubate.lazy_eager():
        samples, losses = _train.measure(
            ctx, step, "lazy.step",
            counters={"flushes": lambda: lazy.stats["flushes"]})
        device = device_object()
    rel_l2 = _train.forward_check(ctx, model)
    return _train.result(ctx, samples, losses, rel_l2, device)
