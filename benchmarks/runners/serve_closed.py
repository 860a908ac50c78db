"""Serving under a closed loop: ``clients`` callers, each of which
submits its next request the moment its last one finished.  The engine
is driven through its public entry points (``add_request``, ``step``,
``open_stream``), and every time is the benchmark's own clock: a
request starts when ``add_request`` is called, and a token arrives when
the client drains it from its stream after a step."""
import jax
import jax.numpy as jnp
import numpy as np

from benchmarks import generate
from benchmarks.families import _plain
from paddle_tpu.inference.serving.errors import ServingError


class Client:
    """One request as its caller sees it."""

    def __init__(self, prompt, asked, t_submit, stream):
        self.prompt, self.asked, self.t_submit = prompt, asked, t_submit
        self.stream, self.tokens, self.times = stream, [], []
        self.t_end = None


class ClosedLoop:
    def __init__(self, ctx, engine, source):
        self.ctx, self.engine, self.source = ctx, engine, source
        self.live, self.ended, self.rejected = [], [], 0
        self.step_s, self.step_end = [], []

    def submit(self, prompt, asked):
        t = self.ctx.clock()
        try:
            rid = self.engine.add_request(prompt, max_new_tokens=asked)
        except (ServingError, ValueError):  # rejected: a failed request
            self.rejected += 1
            return
        self.live.append(Client(prompt, asked, t,
                                self.engine.open_stream(rid)))

    def step(self):
        """One engine step, then every client drains its stream; a
        client whose request ended submits its next one at once."""
        t0 = self.ctx.clock()
        with self.ctx.span("engine.step"):
            self.engine.step()
        now = self.ctx.clock()
        self.step_s.append(now - t0)
        self.step_end.append(now)
        with self.ctx.span("drain"):
            done = []
            for c in self.live:
                for ev in c.stream.drain():
                    if ev.token is not None:
                        c.tokens.append(ev.token)
                        c.times.append(now)
                    if ev.finished:
                        c.t_end = now
                        done.append(c)
        with self.ctx.span("add_request"):
            for c in done:
                self.live.remove(c)
                self.ended.append(c)
                self.submit(*next(self.source))


def window_samples(loop, t_open, t_close, first_step):
    """What happened inside ``(t_open, t_close]``, from the clients'
    records."""
    inside = lambda t: t_open < t <= t_close                # noqa: E731
    clients = loop.ended + loop.live
    gaps, ttft, tokens = [], [], 0
    for c in clients:
        tokens += sum(inside(t) for t in c.times)
        gaps += [(b - a) * 1e3 for a, b in zip(c.times, c.times[1:])
                 if inside(b)]
        if c.times and inside(c.times[0]):
            ttft.append((c.times[0] - c.t_submit) * 1e3)
    ended = [c for c in loop.ended if inside(c.t_end)]
    return {"window_s": t_close - t_open, "tokens": tokens,
            "gaps_ms": gaps, "ttft_ms": ttft,
            "step_s": loop.step_s[first_step:],
            "steps": len(loop.step_s) - first_step,
            "ended": ended,
            "short": sum(len(c.tokens) < c.asked for c in ended)}


def carried_tokens(events):
    """Tokens the engine's steps carried, from its own spans: decode
    rows (``decode``'s ``batch``) and prompt tokens (``prefill:chunk``'s
    ``tokens``)."""
    return sum(e.attrs["batch"] for e in events if e.name == "decode") \
        + sum(e.attrs["tokens"] for e in events
              if e.name == "prefill:chunk")


def margin_check(ctx, model, ended):
    """For a seeded sample of ended requests: the reference's full
    forward over prompt + output, and for every emitted greedy token
    how far its reference logit lies under that position's maximum, in
    units of the position's logit standard deviation."""
    check, cfg = ctx.traffic["check"], ctx.config
    rng = np.random.default_rng(ctx.seed)
    picked = [ended[i] for i in rng.permutation(len(ended))[
        :check["requests"]]]

    @jax.jit
    def margins(params, ids):
        logits = ctx.family.reference_logits(params, cfg, ids)[0, :-1]
        chosen = jnp.take_along_axis(logits, ids[0, 1:, None], 1)[:, 0]
        return (logits.max(-1) - chosen) / logits.std(-1)

    params, worst, positions = _plain.arrays(model), 0.0, 0
    for c in picked:
        seq = c.prompt + c.tokens
        ids = np.zeros((1, check["pad_to"]), np.int64)
        ids[0, :len(seq)] = seq
        m = np.asarray(margins(params, ids))[len(c.prompt) - 1:len(seq) - 1]
        worst, positions = max(worst, float(m.max())), positions + len(m)
    return {"requests_checked": len(picked), "positions_checked": positions,
            "worst_margin_std": worst,
            "margin_limit_std": check["margin_limit_std"]}


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
        first_step, t_open = len(loop.step_s), ctx.clock()
        while loop.step_end[-1] - t_open < ctx.seconds:
            loop.step()
        t_close = loop.step_end[-1]
        samples.update(window_samples(loop, t_open, t_close, first_step))
        samples["compiles_in_window"] = (ctx.compiles.n
                                         - samples["setup_compiles"])
        samples["budget_tokens"] = samples["steps"] * engine.token_budget
        if ctx.trace:
            samples["carried_tokens"] = carried_tokens(
                obs.get_timeline().events())
            obs.enable(False)
            with ctx.device_trace():
                for _ in range(traffic["traced_steps"]):
                    loop.step()
            samples["traced_steps"] = traffic["traced_steps"]
        stats = engine.stats()
        device = device_object()
        ended = samples.pop("ended")
        check = margin_check(ctx, model, ended) if ended else {}
    finally:
        engine.close()
    failed = loop.rejected + samples["short"]
    correct = (len(ended) > 0 and failed == 0
               and check["worst_margin_std"] <= check["margin_limit_std"])
    geometry = {k: stats.get(k) for k in (
        "token_budget", "num_blocks", "block_size", "step_compiles")}
    return {"attempted": len(ended) + loop.rejected, "failed": failed,
            "correct": correct, "samples": samples, "device": device,
            "info": [{"engine": geometry},
                     {k: samples[k] for k in (
                         "setup_s", "setup_compiles", "setup_compile_s",
                         "ramp_steps",
                         "ramp_ended", "steps", "tokens")},
                     {"check": check,
                      "requests_in_flight_at_close": len(loop.live)}]}
