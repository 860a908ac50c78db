"""``serve_closed``'s closed loop for contexts too long for its check.

The loop, the window's samples, the carried tokens and the ramp are
``serve_closed``'s, by import.  What differs is the margin check: at
20,480 positions by 73,448 words the full ``[1, pad_to, vocab]`` float32
logits that ``serve_closed.margin_check`` asks of the family are 6 GB
beside 7.9 GB of weights.  Here the engine is closed first, so that its
pools are free; the reference gives its final hidden states for the
whole sequence (``family.reference_hidden``, computed in query blocks)
and its logits at the emitted positions only
(``family.reference_head``).  The same four seeded requests, and two of
their successors still in flight at the close: the window's ended
requests are all first users of their state slot and blocks.  The
limits are this cell's own, each set between the sound program's
readings and the reference's at float8 in the program's place
(``scripts/sala_margin_control.py``; PERF.md section 6, PR 28).

It also samples the engine's cumulative counters around the window and
around the traced steps (``engine.stats()``): what the steps carried
and what the sparse layers selected, for the per-layer metrics.
"""
import gc

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks import generate
from benchmarks.families import _plain
from benchmarks.runners.serve_closed import (ClosedLoop, carried_tokens,
                                             window_samples)

COUNTERS = ("decode_rows_carried", "prompt_tokens_carried",
            "prefill_chunks", "sparse_blocks_selected",
            "sparse_blocks_visible", "sparse_dense_rows")


def emitted(ctx, params, client, ids, rows):
    """The tokens under the check: what the program emitted.  (The
    check's control, ``scripts/sala_margin_control.py``, lays what the
    reference at a lower precision would emit at ``rows`` here.)"""
    return client.tokens


def margin_check(ctx, model, ended, successors):
    """How far each emitted greedy token's reference logit lies under
    its position's maximum, in units of the position's logit standard
    deviation.  The worst position and the mean over all each have a
    limit: the mean is the steadier and by far the finer of the two.
    Checked: a seeded sample of the window's ended requests, and one of
    the ``successors`` still in flight at the close, on the tokens they
    have emitted so far (they run in a state slot and in blocks that an
    ended request freed, which no ended request of the window does)."""
    check, cfg = ctx.traffic["check"], ctx.config
    rng = np.random.default_rng(ctx.seed)

    def sample(clients, n):
        return [clients[i] for i in rng.permutation(len(clients))[:n]]

    picked = sample(ended, check["requests"])
    in_flight = sample([c for c in successors
                        if len(c.tokens) >= check["in_flight_min_tokens"]],
                       check["in_flight"])
    params = _plain.arrays(model)
    head = {"lm_head.weight": params["lm_head.weight"]}

    @jax.jit
    def margins(head, hidden, chosen):
        logits = ctx.family.reference_head(head, cfg, hidden)
        picked_logit = jnp.take_along_axis(logits, chosen[:, None], 1)[:, 0]
        return (logits.max(-1) - picked_logit) / logits.std(-1)

    def of(c):
        seq = c.prompt + c.tokens
        # whole multiples, so that the reference compiles a few shapes
        ids = np.zeros(-(-len(seq) // check["pad_multiple"])
                       * check["pad_multiple"], np.int64)
        ids[:len(seq)] = seq
        hidden = ctx.family.reference_hidden(params, cfg, jnp.asarray(ids))
        # the row before each emitted token predicts it
        rows = np.arange(len(c.prompt) - 1, len(seq) - 1)
        chosen = jnp.asarray(emitted(ctx, params, c, ids, rows))
        return np.asarray(margins(head, hidden[rows], chosen))

    done = [of(c) for c in picked]
    flying = [of(c) for c in in_flight]
    every = np.concatenate(done + flying)
    return {"requests_checked": len(picked),
            "in_flight_checked": len(in_flight),
            "positions_checked": len(every),
            "in_flight_positions": sum(map(len, flying)),
            "worst_margin_std": float(every.max()),
            "worst_in_flight_margin_std": max(
                (float(m.max()) for m in flying), default=None),
            "mean_margin_std": float(every.mean()),
            "mismatch_share": float((every > 0).mean()),
            "margin_limit_std": check["margin_limit_std"],
            "mean_margin_limit_std": check["mean_margin_limit_std"]}


def counters(engine):
    stats = engine.stats()
    return {k: stats.get(k, 0) for k in COUNTERS}


def run(ctx):
    import paddle_tpu as paddle
    from paddle_tpu import observability as obs
    from paddle_tpu.inference.serving import GenerationEngine
    from benchmarks.harness import device_object
    traffic, cfg = ctx.traffic, ctx.config
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
        opened = counters(engine)
        first_step, t_open = len(loop.step_s), ctx.clock()
        while loop.step_end[-1] - t_open < ctx.seconds:
            loop.step()
        t_close = loop.step_end[-1]
        samples.update(window_samples(loop, t_open, t_close, first_step))
        samples.update({k: v - opened[k]
                        for k, v in counters(engine).items()})
        samples["compiles_in_window"] = (ctx.compiles.n
                                         - samples["setup_compiles"])
        samples["budget_tokens"] = samples["steps"] * engine.token_budget
        if ctx.trace:
            samples["carried_tokens"] = carried_tokens(
                obs.get_timeline().events())
            obs.enable(False)
            before = counters(engine)
            with ctx.device_trace():
                for _ in range(traffic["traced_steps"]):
                    loop.step()
            samples["traced_steps"] = traffic["traced_steps"]
            samples.update({"traced_" + k: v - before[k]
                            for k, v in counters(engine).items()})
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
    geometry = {k: stats.get(k) for k in (
        "token_budget", "num_blocks", "block_size", "step_compiles",
        "pool_bytes", "state_slots", "state_pool_bytes",
        "compressed_pool_bytes", "prefix_bypassed_recurrent",
        "state_resets", "high_water")}
    return {"attempted": len(ended) + loop.rejected, "failed": failed,
            "correct": correct, "samples": samples, "device": device,
            "info": [{"engine": geometry},
                     {k: samples[k] for k in (
                         "setup_s", "setup_compiles", "setup_compile_s",
                         "ramp_steps", "ramp_ended", "steps", "tokens",
                         *COUNTERS)},
                     {"check": check,
                      "requests_in_flight_at_close": len(loop.live)}]}
