"""What the training runners share: the measured loop around one
``step(feed) -> loss`` callable, and the checks that decide ``correct``."""
import math

import jax
import numpy as np

from benchmarks import generate
from benchmarks.families import _plain


def measure(ctx, step, span, counters=None):
    """Warm up, then run ``step`` on a fresh batch each time until the
    window is over.  Every step ends in the loss read on the host, so
    the window ends in a device sync.  ``counters`` are callables that
    read a program counter; each one's rise over the window goes into
    the samples under its name.  Returns the samples and every loss."""
    traffic, counters = ctx.traffic, counters or {}
    batches = generate.mlm_batches(traffic, ctx.config["vocab_size"],
                                   ctx.seed)
    losses = [step(next(batches)) for _ in range(traffic["warmup_steps"])]
    setup_s, n0, c0 = ctx.setup_done(), ctx.compiles.n, ctx.compiles.seconds
    at_open = {name: read() for name, read in counters.items()}
    t_open = ctx.clock()
    step_s, t_prev = [], t_open
    while t_prev - t_open < ctx.seconds:
        with ctx.span("feed"):
            feed = next(batches)
        with ctx.span(span):
            losses.append(step(feed))
        now = ctx.clock()
        step_s.append(now - t_prev)
        t_prev = now
    samples = {
        "setup_s": setup_s, "window_s": t_prev - t_open,
        "steps": len(step_s), "step_s": step_s,
        "tokens": len(step_s) * traffic["batch"] * traffic["seq"],
        "tokens_per_step": traffic["batch"] * traffic["seq"],
        "setup_compiles": n0, "setup_compile_s": c0,
        "compiles_in_window": ctx.compiles.n - n0,
        **{name: read() - at_open[name] for name, read in counters.items()}}
    if ctx.trace:
        with ctx.device_trace():
            for _ in range(traffic["traced_steps"]):
                with ctx.span("feed"):
                    feed = next(batches)
                with ctx.span(span):
                    losses.append(step(feed))
        samples["traced_steps"] = traffic["traced_steps"]
    return samples, losses


def forward_check(ctx, model):
    """The program's forward (dropout off, the cell's ``auto_cast``,
    one ``to_static`` program) against the family's plain reference on
    the same parameters: relative L2 of the logits of a few seeded
    sequences."""
    import paddle_tpu as paddle
    traffic, fam, cfg = ctx.traffic, ctx.family, ctx.config
    ids = np.random.default_rng(ctx.seed).integers(
        0, cfg["vocab_size"], (traffic["check"]["sequences"],
                               traffic["seq"]), dtype=np.int64)
    was_training = model.training
    model.eval()
    try:
        def forward(x):
            with paddle.amp.auto_cast(**traffic["amp"]):
                return fam.logits(model, x)
        with paddle.no_grad():
            got = paddle.jit.to_static(forward)(paddle.to_tensor(ids))
        got = np.asarray(got.numpy(), np.float64)
    finally:
        if was_training:
            model.train()
    ref = jax.jit(lambda p, x: fam.reference_logits(p, cfg, x))(
        _plain.arrays(model), ids)
    ref = np.asarray(ref, np.float64)
    return float(np.linalg.norm(got - ref) / np.linalg.norm(ref))


def verdict(ctx, samples, losses, rel_l2):
    """``attempted``, ``failed``, ``correct`` and the check's numbers."""
    window = losses[ctx.traffic["warmup_steps"]:][:samples["steps"]]
    failed = sum(not math.isfinite(x) for x in window)
    ln_v = math.log(ctx.config["vocab_size"])
    limit = ctx.traffic["check"]["logits_rel_l2_limit"]
    rtol = ctx.traffic["check"]["loss_step0_rtol"]
    rise = ctx.traffic["check"]["loss_rise_rtol"] * losses[0]
    last = sum(losses[-5:]) / len(losses[-5:])     # of the last five steps
    check = {"loss_step0": losses[0], "ln_vocab": ln_v,
             "loss_last": last, "loss_rise_limit": rise,
             "non_finite_steps": failed,
             "logits_rel_l2": rel_l2, "logits_rel_l2_limit": limit}
    correct = (failed == 0 and all(math.isfinite(x) for x in losses)
               and abs(losses[0] - ln_v) <= rtol * ln_v
               and last <= losses[0] + rise and rel_l2 <= limit)
    return {"attempted": samples["steps"], "failed": failed,
            "correct": correct, "check": check}


def result(ctx, samples, losses, rel_l2, device):
    out = verdict(ctx, samples, losses, rel_l2)
    return {**out, "samples": samples, "device": device,
            "info": [{"setup_s": samples["setup_s"],
                      "setup_compiles": samples["setup_compiles"],
                      "setup_compile_s": samples["setup_compile_s"],
                      "steps": samples["steps"]},
                     {"check": out["check"]}]}
