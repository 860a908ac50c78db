"""``serve_closed_long``'s closed loop with the engine's counters named
by the traffic file.

The loop, the ramp, the window's samples and the margin check are
``serve_closed``'s and ``serve_closed_long``'s, by import; the check's
limits are the cell's own (its traffic file's ``check``).  What differs:
the cumulative counters of ``engine.stats()`` that are sampled around
the window and around the traced steps are the traffic file's
``counters`` (each becomes a sample under its own name, and
``traced_<name>`` over the traced steps), and the ``engine.stats()``
keys printed as the engine's geometry are its ``geometry``.  A later
configuration with counters of its own needs a traffic file, not a
runner.
"""
import gc

from benchmarks import generate
from benchmarks.runners.serve_closed import (ClosedLoop, carried_tokens,
                                             window_samples)
from benchmarks.runners.serve_closed_long import margin_check


def run(ctx):
    import paddle_tpu as paddle
    from paddle_tpu import observability as obs
    from paddle_tpu.inference.serving import GenerationEngine
    from benchmarks.harness import device_object
    traffic, cfg = ctx.traffic, ctx.config
    names = tuple(traffic["counters"])

    def counters():
        stats = engine.stats()
        return {k: stats.get(k, 0) for k in names}

    paddle.seed(ctx.seed % (2 ** 31))
    model = ctx.family.build(cfg)
    engine = GenerationEngine(model, **traffic["engine"])
    source = generate.requests(traffic, cfg["vocab_size"], ctx.seed)
    loop = ClosedLoop(ctx, engine, source)
    try:
        # set-up: fill every slot, visiting each decode-row count
        first = [next(source) for _ in range(traffic["clients"])]
        lengths = generate.ramp(first, traffic, engine.prefill_chunk)
        ramp = []
        for (prompt, _), asked in zip(first, lengths):
            loop.submit(prompt, asked)
            ramp.append(loop.live[-1])
        while not all(c.times for c in ramp):
            loop.step()
        samples = {"setup_s": ctx.setup_done(),
                   "setup_compiles": ctx.compiles.n,
                   "setup_compile_s": ctx.compiles.seconds,
                   "ramp_steps": len(loop.step_s),
                   "ramp_ended": len(loop.ended)}
        if ctx.trace:                # the engine's spans: tokens carried
            obs.enable(True)
            obs.get_timeline().clear()
        opened = counters()
        first_step, t_open = len(loop.step_s), ctx.clock()
        while loop.step_end[-1] - t_open < ctx.seconds:
            loop.step()
        t_close = loop.step_end[-1]
        samples.update(window_samples(loop, t_open, t_close, first_step))
        samples.update({k: v - opened[k] for k, v in counters().items()})
        samples["compiles_in_window"] = (ctx.compiles.n
                                         - samples["setup_compiles"])
        samples["budget_tokens"] = samples["steps"] * engine.token_budget
        if ctx.trace:
            samples["carried_tokens"] = carried_tokens(
                obs.get_timeline().events())
            obs.enable(False)
            before = counters()
            with ctx.device_trace():
                for _ in range(traffic["traced_steps"]):
                    loop.step()
            samples["traced_steps"] = traffic["traced_steps"]
            samples.update({"traced_" + k: v - before[k]
                            for k, v in counters().items()})
        stats = engine.stats()
        device = device_object()
        ended = samples.pop("ended")
        successors = [c for c in loop.live if c not in ramp]
    finally:
        engine.close()
    del engine, loop.engine          # the pools' memory, for the check
    gc.collect()
    check = margin_check(ctx, model, ended, successors) if ended else {}
    failed = loop.rejected + samples["short"]
    correct = (len(ended) > 0 and failed == 0
               and check["worst_margin_std"] <= check["margin_limit_std"]
               and check["mean_margin_std"]
               <= check["mean_margin_limit_std"])
    return {"attempted": len(ended) + loop.rejected, "failed": failed,
            "correct": correct, "samples": samples, "device": device,
            "info": [{"engine": {k: stats.get(k)
                                 for k in traffic["geometry"]}},
                     {k: samples[k] for k in (
                         "setup_s", "setup_compiles", "setup_compile_s",
                         "ramp_steps", "ramp_ended", "steps", "tokens",
                         *names)},
                     {"check": check,
                      "requests_in_flight_at_close": len(loop.live)}]}
