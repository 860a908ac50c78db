"""The one traffic generator: a traffic file's parameters and a seed in,
batches or requests out.  A later mix is a new data file; only a mix
of a new *kind* needs code here."""
import math

import numpy as np


def mlm_batches(traffic, vocab_size, seed):
    """Endless masked-LM batches ``{"ids", "labels"}`` (int64,
    ``[batch, seq]``): uniform token ids; ``label_share`` of the
    positions are labelled with the original id (``-100`` elsewhere),
    and of those ``to_mask`` become ``mask_id``, ``to_random`` a random
    id and the rest stay (Devlin et al. 2018, section 3.1)."""
    rng = np.random.default_rng(seed)
    shape = (traffic["batch"], traffic["seq"])
    m = traffic["mlm"]
    while True:
        original = rng.integers(0, vocab_size, shape, dtype=np.int64)
        chosen = rng.random(shape) < m["label_share"]
        how = rng.random(shape)
        ids = original.copy()
        ids[chosen & (how < m["to_mask"])] = m["mask_id"]
        swap = chosen & (how >= m["to_mask"]) \
            & (how < m["to_mask"] + m["to_random"])
        ids[swap] = rng.integers(0, vocab_size, int(swap.sum()))
        yield {"ids": ids, "labels": np.where(chosen, original, -100)}


def draw_lengths(spec, n, rng):
    """``n`` lengths from one ``{"dist": "lognormal", "median", "sigma",
    "min", "max"}`` entry, clipped."""
    if spec["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    x = rng.lognormal(math.log(spec["median"]), spec["sigma"], n)
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(int)


def request_pool(traffic):
    """The mix's fixed schedule of ``(prompt_len, output_len)`` pairs,
    drawn from the seed *in the traffic file*.  Every ``--seed`` serves
    these sizes in this order: a window holds some tens of requests,
    too few for a random order to average out (PR 23: one order of the
    same sizes read 5 % fewer tokens per second than three others, and
    repeated to four digits)."""
    rng = np.random.default_rng(traffic["pool"]["seed"])
    n = traffic["pool"]["size"]
    return list(zip(draw_lengths(traffic["prompt_len"], n, rng).tolist(),
                    draw_lengths(traffic["output_len"], n, rng).tolist()))


def requests(traffic, vocab_size, seed):
    """Endless ``(prompt_tokens, output_len)``: the pool's sizes, round
    and round; the seed draws the token ids, uniform, so no two prompts
    share a prefix."""
    rng = np.random.default_rng(seed)
    pool = request_pool(traffic)
    while True:
        for prompt_len, output_len in pool:
            yield (rng.integers(0, vocab_size, prompt_len).tolist(),
                   output_len)


def ramp(first, traffic, chunk):
    """Output lengths for the requests that fill the slots during
    set-up.  One prefill chunk runs a step and admission is serial, so
    request ``i`` decodes from a step that its predecessors' prompt
    lengths fix.  Each is kept alive until the last one decodes (plus
    ``ramp_slack_steps``), so that the ramp visits every decode-row
    count, and then runs on for a uniform fraction of its drawn length
    (fractions from the traffic file's seed, the same in every run), so
    that completions are spread from the window's first step as in
    steady state."""
    rng = np.random.default_rng(traffic["pool"]["seed"] + 1)
    chunks = [-(-len(prompt) // chunk) for prompt, _ in first]
    total, done, out = sum(chunks), 0, []
    for (_, output_len), c in zip(first, chunks):
        done += c
        out.append(total - done + traffic["ramp_slack_steps"] + 1
                   + int(rng.random() * output_len))
    return out
