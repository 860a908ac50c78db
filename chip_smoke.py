"""Prove that the trainer and the serving engine start on the chip.

    python chip_smoke.py            # one TPU chip: fifteen phases
    python chip_smoke.py --chips 4  # four chips: the sharded phase only
    python chip_smoke.py --phase attention_dropout   # that phase alone

One process, JAX imported once, no platform forced in code: the script
refuses to start unless ``jax.devices()[0].platform == "tpu"``, so no
kernel can run interpreted and no phase can quietly compute on the
host.  Each phase drives a main path through the entry points a user
calls, at the published width of a model the repo ships, with weights
and data made from a seed, and checks the result by the repo's own
means.  Phases print one JSON line each; any failed check raises.  The
last line of stdout is ``{"ok": true, "device": {...}}``.

Every phase is a function of the model configuration and sizes:
``tests/test_chip_smoke.py`` runs the same code on the CPU at a
2-layer width-128 model, and a rehearsal compiles it for a described
chip before chip time is spent.  The command line takes no size.

This is a start-up proof, not a benchmark: the seconds it prints are
wall time of whole phases, compilation included (`sampler_gate`,
`ragged_walk`, `latent_walk` and `moe_dispatch` alone read device times
of single programs from a profiler trace).
"""
import argparse
import contextlib
import dataclasses
import gc
import glob
import json
import math
import os
import re
import sys
import tempfile
import time
from collections import Counter

import jax
import numpy as np

import paddle_tpu as paddle
from paddle_tpu import observability as obs
from paddle_tpu import optimizer, static
from paddle_tpu.core import dispatch as eager_dispatch
from paddle_tpu.core import lazy
from paddle_tpu.distributed.auto_parallel.sharding import (
    BERT_RULES, MeshPlan, annotate_params, clear_mesh_plan, set_mesh_plan)
from paddle_tpu.inference.serving import GenerationEngine
from paddle_tpu.models import (BertConfig, BertForMaskedLM, GPTConfig,
                               GPTForCausalLM)
from paddle_tpu.ops.pallas_gate import probe_report

SEED = 0
# Two runs of one program that differ only in kernel vs XLA composite
# round bf16 operands at different points: 2^-8 per rounding, a few
# roundings deep.  Held to 3% of the reference's norm (1% for a loss,
# which averages thousands of tokens).
BF16_REL_L2 = 3e-2
BF16_LOSS_RTOL = 1e-2

_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
_compiles = {"n": 0, "seconds": 0.0}


def _on_compile(event, duration, **_):
    if event == _BACKEND_COMPILE:
        _compiles["n"] += 1
        _compiles["seconds"] += duration


jax.monitoring.register_event_duration_secs_listener(_on_compile)


def check(ok, what):
    if not ok:
        raise AssertionError(what)


def mosaic_kernels(hlo_text):
    """Names of the Pallas kernels behind the ``tpu_custom_call``s of a
    compiled program, with their counts.  Every ``pallas_call`` of
    ``paddle_tpu/ops`` is named ``<kernel>_<direction>``
    (``pallas_tiles._kernel_span``), and XLA names the call's HLO
    instruction after it: ``%layer_norm_fwd.3 = ... custom-call(...)``."""
    found = Counter()
    for line in hlo_text.splitlines():
        if 'custom_call_target="tpu_custom_call"' not in line:
            continue
        name = re.match(r"\s*(?:ROOT )?%([\w\-]+?)(?:\.\d+)* = ", line)
        found[name.group(1) if name else "unnamed"] += 1
    return dict(found)


def pool_sized_moves(hlo_text, elements):
    """The instructions of a compiled program that relay a K/V pool:
    opcode ``copy``, ``copy-start`` or ``transpose``
    (`observability.parse_hlo_blocks`' opcodes, the ones
    ``layout_copy_ms.serve`` sums) whose result holds an array of at
    least ``elements`` elements, as ``"block: line"``.  Since PR 38 a
    serving step has none: the scatter writes the pool as it lies and
    the ragged kernel reads it so."""
    table = obs.parse_hlo_blocks(hlo_text)["instructions"]
    moves = []
    for line in hlo_text.splitlines():
        name = re.match(r"\s*(?:ROOT )?%?([\w.\-]+) = (.*)", line)
        entry = name and table.get(name.group(1))
        if not entry or entry["opcode"] not in ("copy", "copy-start",
                                                "transpose"):
            continue
        result = name.group(2).split(f" {entry['opcode']}(")[0]
        if any(math.prod(int(n) for n in dims.split(",")) >= elements
               for dims in re.findall(r"\[([\d,]+)\]", result)):
            moves.append(f"{entry['block'] or 'no block'}: "
                         f"{line.strip()[:160]}")
    return moves


def _rel_l2(got, ref):
    got = np.asarray(got, np.float64)
    ref = np.asarray(ref, np.float64)
    return float(np.linalg.norm(got - ref) / np.linalg.norm(ref))


@contextlib.contextmanager
def composites():
    """``FLAGS_use_pallas_kernels`` off: every call site that is
    recorded or dispatched inside takes the XLA composite."""
    paddle.set_flags({"FLAGS_use_pallas_kernels": False})
    try:
        yield
    finally:
        paddle.set_flags({"FLAGS_use_pallas_kernels": True})


def _probed():
    """Gate outcome of every kernel asked for so far in this process:
    on a TPU each was probe-compiled before its first use, and a failed
    probe raises in the gate, so anything listed here is ``ok``."""
    return {k: r["ok"] for k, r in probe_report().items()
            if r.get("probed")}


# ---------------------------------------------------------------------
# training, static graph
# ---------------------------------------------------------------------
def _bert_batch(cfg, batch, seq):
    ids = np.random.default_rng(SEED).integers(
        0, cfg.vocab_size, (batch, seq)).astype(np.int64)
    return {"ids": ids, "labels": ids}


def bert_program(cfg, batch, seq):
    """The BERT MLM train program: bf16 O1 ``auto_cast``, AdamW, built
    under ``program_guard`` (static mode and any mesh plan are the
    caller's).  Returns ``(program, loss, model)``."""
    paddle.seed(SEED)
    main_prog, startup = static.Program(), static.Program()
    with static.program_guard(main_prog, startup):
        ids = static.data("ids", [batch, seq], "int64")
        labels = static.data("labels", [batch, seq], "int64")
        model = BertForMaskedLM(cfg)
        annotate_params(model)
        with paddle.amp.auto_cast(dtype="bfloat16", level="O1"):
            loss, _ = model(ids, labels=labels)
        opt = optimizer.AdamW(learning_rate=1e-4,
                              parameters=model.parameters())
        opt.minimize(loss)
    return main_prog, loss, model


def _bert_static_run(cfg, batch, seq, steps, fused_steps=0, plan=None):
    """Run the train program: ``steps`` x ``Executor.run`` on one fixed
    batch, then ``run_steps(fused_steps)``.  Returns the losses and
    what the compiled step holds."""
    paddle.enable_static()
    set_mesh_plan(plan)
    try:
        main_prog, loss, model = bert_program(cfg, batch, seq)
        exe = static.Executor()
        feed = _bert_batch(cfg, batch, seq)
        losses, compiles_after_first = [], 0
        for i in range(steps):
            before = _compiles["n"]
            (lv,) = exe.run(main_prog, feed=feed, fetch_list=[loss])
            losses.append(float(lv))
            if i:
                compiles_after_first += _compiles["n"] - before
        out = {"losses": losses,
               "compiles_after_first": compiles_after_first}
        (entry,) = exe._cache.values()
        text = entry["compiled"].as_text()
        out["kernels"] = mosaic_kernels(text)
        out["collectives"] = {
            op: len(re.findall(rf"\b{op}(?:-start)?\(", text))
            for op in ("all-reduce", "all-gather", "reduce-scatter",
                       "collective-permute", "all-to-all")}
        if fused_steps:
            (lv,) = exe.run_steps(fused_steps, main_prog, feed=feed,
                                  fetch_list=[loss])
            out["fused_loss"] = float(lv)
        per_dev = Counter()
        for p in model.parameters():
            for sh in p._value.addressable_shards:
                per_dev[sh.device.id] += sh.data.nbytes
        out["param_bytes_per_device"] = dict(sorted(per_dev.items()))
        out["param_bytes"] = sum(
            p._value.nbytes for p in model.parameters())
        return out
    finally:
        clear_mesh_plan()
        paddle.disable_static()


def train_static(cfg, batch, seq, steps=8, fused_steps=4):
    on = _bert_static_run(cfg, batch, seq, steps, fused_steps)
    losses, ln_v = on["losses"], math.log(cfg.vocab_size)
    check(abs(losses[0] - ln_v) <= 0.05 * ln_v,
          f"step-0 loss {losses[0]} not within 5% of ln(vocab) {ln_v}")
    check(all(math.isfinite(x) for x in losses)
          and losses[-1] < losses[0],
          f"loss not finite and falling over {steps} steps: {losses}")
    check(math.isfinite(on["fused_loss"]) and on["fused_loss"] < losses[0],
          f"run_steps loss {on['fused_loss']} vs step 0 {losses[0]}")
    check(on["compiles_after_first"] == 0,
          f"{on['compiles_after_first']} compilations after step 0")
    with composites():
        off = _bert_static_run(cfg, batch, seq, 1)
    check(not off["kernels"], f"flag off, kernels ran: {off['kernels']}")
    check(abs(losses[0] - off["losses"][0])
          <= BF16_LOSS_RTOL * abs(off["losses"][0]),
          f"step-0 loss {losses[0]} vs composites {off['losses'][0]}")
    return {"kernels": on["kernels"],
            "checked": {
                "loss_step0": losses[0], "ln_vocab": ln_v,
                "loss_last": losses[-1], "loss_run_steps": on["fused_loss"],
                "loss_step0_composites": off["losses"][0],
                "loss_rtol": BF16_LOSS_RTOL,
                "compiles_after_first_step": 0,
                "probe_ok": _probed()}}


# ---------------------------------------------------------------------
# attention dropout inside the flash kernels
# ---------------------------------------------------------------------
def attention_dropout(cfg, batch, seq, depth=2):
    """The flash kernels with the chip's bit source at the width of
    ``cfg``: the keep rate of the written-out tile stream, forward and
    backward against ``_sdpa_ref`` under that mask in bf16, and the
    step-0 loss of a ``depth``-layer BERT whose compiled step holds
    the three flash kernels."""
    import jax.numpy as jnp
    from paddle_tpu.nn.functional.flash_attention import _sdpa_ref
    from paddle_tpu.ops import pallas_kernels as pk
    heads, p = cfg.num_attention_heads, cfg.attention_probs_dropout_prob
    hd = cfg.hidden_size // heads
    scale, seed = hd ** -0.5, jnp.array([20260930], jnp.int32)
    with jax.enable_x64(False):
        keep = pk.flash_dropout_keep(seed, batch, seq, seq, heads, hd,
                                     dropout_p=p, dtype=jnp.bfloat16)
        other = pk.flash_dropout_keep(seed + 1, batch, seq, seq, heads, hd,
                                      dropout_p=p, dtype=jnp.bfloat16)
        rate = float(jnp.mean(keep, dtype=jnp.float32))
        sigma = math.sqrt(p * (1 - p) / keep.size)
        # two draws are independent: both keep with probability (1-p)^2
        both = float(jnp.mean(keep & other, dtype=jnp.float32))
        shifted = float(jnp.mean(keep[..., 1:, :] & keep[..., :-1, :],
                                 dtype=jnp.float32))
        q, k, v = (jax.random.normal(kk, (batch, seq, heads, hd),
                                     jnp.bfloat16)
                   for kk in jax.random.split(jax.random.PRNGKey(SEED), 3))

        def loss_of(attend):
            return lambda q, k, v: jnp.sum(
                attend(q, k, v).astype(jnp.float32) ** 2)

        flash = lambda q, k, v: pk.flash_attention(  # noqa: E731
            q, k, v, scale=scale, dropout_p=p, seed=seed)
        ref = lambda q, k, v: _sdpa_ref(  # noqa: E731
            q, k, v, None, False, scale, p, keep=keep)
        errs = {"fwd": _rel_l2(jax.jit(flash)(q, k, v),
                               jax.jit(ref)(q, k, v))}
        got = jax.jit(jax.grad(loss_of(flash), (0, 1, 2)))(q, k, v)
        want = jax.jit(jax.grad(loss_of(ref), (0, 1, 2)))(q, k, v)
        for name, a, b in zip(("dq", "dk", "dv"), got, want):
            errs[name] = _rel_l2(a, b)
    check(abs(rate - (1 - p)) < 4 * sigma,
          f"keep rate {rate} not within 4 sigma ({sigma}) of {1 - p}")
    pair = (1 - p) ** 2
    pair_sigma = math.sqrt(pair * (1 - pair) / keep.size)
    for name, got_share in (("another seed", both), ("next row", shifted)):
        check(abs(got_share - pair) < 5 * pair_sigma,
              f"keep and keep of {name} are both set on {got_share}, "
              f"independent draws on {pair} (sigma {pair_sigma})")
    for name, err in errs.items():
        check(err <= BF16_REL_L2, f"flash dropout {name} rel L2 {err}")
    on = _bert_static_run(
        dataclasses.replace(cfg, num_hidden_layers=depth), batch, seq, 1)
    ln_v = math.log(cfg.vocab_size)
    check(abs(on["losses"][0] - ln_v) <= 0.05 * ln_v,
          f"step-0 loss {on['losses'][0]} not within 5% of ln(vocab)")
    return {"kernels": on["kernels"],
            "checked": {"dropout_p": p, "keep_rate": rate,
                        "keep_rate_sigma": sigma,
                        "keep_and_other_seed": both,
                        "keep_and_next_row": shifted,
                        "independent": pair, "pair_sigma": pair_sigma,
                        "rel_l2_vs_masked_composite": errs,
                        "rel_l2_limit": BF16_REL_L2,
                        "bert_layers": depth,
                        "loss_step0": on["losses"][0], "ln_vocab": ln_v,
                        "probe_ok": _probed()}}


# ---------------------------------------------------------------------
# hidden dropout inside the LayerNorm-residual kernels
# ---------------------------------------------------------------------
def hidden_dropout(cfg, batch, seq, depth=2):
    """The LN+residual kernels with the chip's bit source, at ``batch *
    seq`` rows of ``cfg.hidden_size`` in bf16: the keep rate of the
    written-out tile stream, forward and the four gradients against the
    float32 composite under that mask, and a ``depth``-layer BERT step
    whose every post-norm sublayer took the dropout form.  The cells'
    ``correct`` runs the forward with dropout off and reads the loss:
    dropout's own correctness is held here and by the CPU tests."""
    import jax.numpy as jnp
    from paddle_tpu.ops import pallas_fused as pf
    rows, n, p = batch * seq, cfg.hidden_size, cfg.hidden_dropout_prob
    seed = jnp.array([20261001], jnp.int32)
    f32 = jnp.float32
    with jax.enable_x64(False):
        keep = pf.layer_norm_residual_dropout_keep(seed, rows, n, p,
                                                   jnp.bfloat16)
        other = pf.layer_norm_residual_dropout_keep(seed + 1, rows, n, p,
                                                    jnp.bfloat16)
        rate = float(jnp.mean(keep, dtype=f32))
        sigma = math.sqrt(p * (1 - p) / keep.size)
        # two draws are independent: both keep with probability (1-p)^2
        both = float(jnp.mean(keep & other, dtype=f32))
        br = pf._ln_res_block_rows(rows, n, True, jnp.bfloat16)
        next_tile = float(jnp.mean(keep[br:] & keep[:-br], dtype=f32)
                          if rows > br else (1 - p) ** 2)
        x, r = (jax.random.normal(k, (rows, n), jnp.bfloat16)
                for k in jax.random.split(jax.random.PRNGKey(SEED)))
        g = jnp.linspace(0.5, 1.5, n).astype(jnp.bfloat16)
        b = jnp.linspace(-1.0, 1.0, n).astype(jnp.bfloat16)

        def ref(x, r, g, b):
            s = jnp.where(keep, x.astype(f32) / (1 - p), 0.0) + r.astype(f32)
            mu = jnp.mean(s, -1, keepdims=True)
            var = jnp.mean(jnp.square(s - mu), -1, keepdims=True)
            return (s - mu) * jax.lax.rsqrt(var + cfg.layer_norm_eps) \
                * g.astype(f32) + b.astype(f32)

        def fused(x, r, g, b):
            return pf.fused_layer_norm_residual(
                x, r, g, b, cfg.layer_norm_eps, dropout_p=p, seed=seed)

        def loss_of(fn):
            return lambda *a: jnp.sum(jnp.sin(fn(*a).astype(f32)))

        errs = {"fwd": _rel_l2(jax.jit(fused)(x, r, g, b),
                               jax.jit(ref)(x, r, g, b))}
        got = jax.jit(jax.grad(loss_of(fused), (0, 1, 2, 3)))(x, r, g, b)
        want = jax.jit(jax.grad(loss_of(ref), (0, 1, 2, 3)))(x, r, g, b)
        for name, a, w in zip(("d_x", "d_residual", "d_gamma", "d_beta"),
                              got, want):
            errs[name] = _rel_l2(a, w)
        leaked = float(jnp.sum(jnp.where(keep, 0.0, got[0].astype(f32)) != 0))
    check(abs(rate - (1 - p)) < 3 * sigma,
          f"keep rate {rate} not within 3 sigma ({sigma}) of {1 - p}")
    pair = (1 - p) ** 2
    pair_sigma = math.sqrt(pair * (1 - pair) / keep.size)
    for name, got_share in (("another seed", both), ("next tile", next_tile)):
        check(abs(got_share - pair) < 5 * pair_sigma,
              f"keep and keep of {name} are both set on {got_share}, "
              f"independent draws on {pair} (sigma {pair_sigma})")
    for name, err in errs.items():
        check(err <= BF16_REL_L2, f"hidden dropout {name} rel L2 {err}")
    check(leaked == 0, f"{leaked} dropped elements carry a gradient")
    def path_counts():
        return {k.rpartition("path.")[2]: v for k, v in
                obs.get_registry().snapshot()["counters"].items()
                if k.startswith("layer_norm_residual.path.")}

    prev = obs.enable(True)
    try:
        # the counters are the process's: what this step's build added
        before = path_counts()
        on = _bert_static_run(
            dataclasses.replace(cfg, num_hidden_layers=depth), batch, seq, 1)
        paths = {k: v - before.get(k, 0) for k, v in path_counts().items()
                 if v != before.get(k, 0)}
    finally:
        obs.enable(prev)
    ln_v = math.log(cfg.vocab_size)
    check(abs(on["losses"][0] - ln_v) <= 0.05 * ln_v,
          f"step-0 loss {on['losses'][0]} not within 5% of ln(vocab)")
    if on["kernels"]:       # on the chip: every sublayer drew in-kernel
        check(paths == {"dropout": 2 * depth}, f"paths taken: {paths}")
        check(on["kernels"].get("layer_norm_residual_fwd") == 2 * depth
              and on["kernels"].get("layer_norm_residual_bwd") == 2 * depth,
              f"kernels of the step: {on['kernels']}")
    return {"kernels": on["kernels"],
            "checked": {"dropout_p": p, "rows": rows, "hidden": n,
                        "block_rows": br, "keep_rate": rate,
                        "keep_rate_sigma": sigma,
                        "keep_and_other_seed": both,
                        "keep_and_next_tile": next_tile,
                        "independent": pair, "pair_sigma": pair_sigma,
                        "rel_l2_vs_masked_composite": errs,
                        "rel_l2_limit": BF16_REL_L2,
                        "dropped_with_gradient": leaked,
                        "paths": paths, "bert_layers": depth,
                        "loss_step0": on["losses"][0], "ln_vocab": ln_v,
                        "probe_ok": _probed()}}


# ---------------------------------------------------------------------
# the loss kernels at the MLM head's own shape
# ---------------------------------------------------------------------
XENT_REL_L2 = 1e-5      # float32 in, float32 arithmetic on both sides


def loss_head(cfg, batch, seq):
    """The softmax-cross-entropy kernels on ``batch * seq`` rows of
    ``cfg.vocab_size`` float32 logits from a seeded bf16 matmul, as amp
    O1 hands them over: the kernels read the matrix and write its
    gradient at their own shape (30522 is no multiple of the 2048-column
    block, so the last block is masked in the kernel), against the
    float32 composite: loss, ``d_logits`` under a per-row cotangent, and
    no gradient in an ignored row."""
    import jax.numpy as jnp
    from paddle_tpu.ops import pallas_kernels as pk
    rows, n, v = batch * seq, cfg.hidden_size, cfg.vocab_size
    f32 = jnp.float32
    with jax.enable_x64(False):
        kh, kw, kl, kc = jax.random.split(jax.random.PRNGKey(SEED), 4)
        h = jax.random.normal(kh, (rows, n), jnp.bfloat16)
        w = (jax.random.normal(kw, (v, n), f32) * n ** -0.5).astype(
            jnp.bfloat16)
        logits = jax.jit(lambda h, w: jnp.dot(h, w.T).astype(f32))(h, w)
        labels = jax.random.randint(kl, (rows,), 0, v)
        labels = labels.at[0].set(v - 1)            # in the masked block
        ignored = jnp.arange(rows) % 7 == 3
        labels = jnp.where(ignored, -1, labels)
        cot = jax.random.uniform(kc, (rows,), f32, 0.5, 1.5)

        def composite(x):
            lse = jax.nn.logsumexp(x, axis=-1)
            picked = jnp.take_along_axis(
                x, jnp.maximum(labels, 0)[:, None], 1)[:, 0]
            return jnp.where(labels >= 0, lse - picked, 0.0)

        def kernel(x):
            return pk.fused_softmax_cross_entropy(x, labels)

        def loss_and_grad(fn):
            return jax.jit(jax.value_and_grad(
                lambda x: jnp.sum(cot * fn(x)))
            ).lower(logits).compile()

        step = loss_and_grad(kernel)
        kernels = mosaic_kernels(step.as_text())
        errs = {"loss": _rel_l2(jax.jit(kernel)(logits),
                                jax.jit(composite)(logits))}
        (total, dx), (total_ref, dx_ref) = (
            step(logits), loss_and_grad(composite)(logits))
        errs["d_logits"] = _rel_l2(dx, dx_ref)
        leaked = int(jnp.sum(jnp.any(dx != 0, axis=-1) & ignored))
        shape_ok = dx.shape == (rows, v) and dx.dtype == f32
    for name, err in errs.items():
        check(err <= XENT_REL_L2, f"loss head {name} rel L2 {err}")
    check(leaked == 0, f"{leaked} ignored rows carry a gradient")
    check(shape_ok, f"d_logits is {dx.dtype}{list(dx.shape)}")
    if kernels:             # on the chip: both directions are Mosaic's
        check(kernels == {"softmax_cross_entropy_fwd": 1,
                          "softmax_cross_entropy_bwd": 1},
              f"kernels of the step: {kernels}")
    return {"kernels": kernels,
            "checked": {"rows": rows, "vocab": v,
                        "blocks": list(pk._xent_blocks(rows, v)),
                        "ignored_rows": int(jnp.sum(ignored)),
                        "ignored_rows_with_gradient": leaked,
                        "rel_l2_vs_composite": errs,
                        "rel_l2_limit": XENT_REL_L2,
                        "weighted_loss": float(total),
                        "weighted_loss_composite": float(total_ref)}}


# ---------------------------------------------------------------------
# training, dygraph
# ---------------------------------------------------------------------
def _eager_kernels():
    """Kernels in the per-op executables of the eager tier.  A jitted
    op keeps no text, so each cached op that took a kernel is lowered
    again from the shapes in its cache key."""
    found = Counter()
    for cache in (eager_dispatch._eager_fwd_cache,
                  eager_dispatch._eager_vjp_cache):
        for key, fn in list(cache.items()):
            attrs, avals = dict(key[3]), key[4]
            if attrs.get("use_pallas") != ("bool", True):
                continue
            args = [jax.ShapeDtypeStruct(s, np.dtype(d)) for s, d in avals]
            found.update(mosaic_kernels(
                fn.lower(*args).compile().as_text()))
    return dict(found)


def train_eager(cfg, batch, seq, steps=4, lazy_tier=False):
    """The same model family and batch in dygraph: in whatever tier
    ``import paddle_tpu`` selected (per-op dispatch unless
    ``PADDLE_TPU_LAZY=1``), or with ``lazy_tier`` under
    ``paddle.incubate.lazy_eager()``, the auto-trace tier that flushes
    a whole step as one or two compiled segments."""
    with (paddle.incubate.lazy_eager() if lazy_tier
          else contextlib.nullcontext()):
        return _train_eager(cfg, batch, seq, steps)


def _train_eager(cfg, batch, seq, steps):
    paddle.seed(SEED)
    model = BertForMaskedLM(cfg)
    opt = optimizer.AdamW(learning_rate=1e-4,
                          parameters=model.parameters())
    feed = _bert_batch(cfg, batch, seq)
    ids = paddle.to_tensor(feed["ids"])
    labels = paddle.to_tensor(feed["labels"])
    tier = "lazy" if lazy.lazy_enabled() else "per-op"
    dispatches = obs.get_registry().histogram("eager.dispatch_us")
    obs_was = obs.enabled()
    obs.enable(True)          # eager.dispatch_us counts op dispatches
    losses, launches = [], []
    try:
        for _ in range(steps):
            n0, f0 = dispatches.count, lazy.stats["flushes"]
            with paddle.amp.auto_cast(dtype="bfloat16", level="O1"):
                loss, _ = model(ids, labels=labels)
            loss.backward()
            opt.step()
            opt.clear_grad()
            losses.append(float(loss))
            launches.append(lazy.stats["flushes"] - f0 if tier == "lazy"
                            else dispatches.count - n0)
    finally:
        obs.enable(obs_was)
    check(all(math.isfinite(x) for x in losses)
          and losses[-1] < losses[0],
          f"loss not finite and falling over {steps} steps: {losses}")
    if tier == "lazy":
        kernels = Counter()
        for segment in lazy._segment_cache.values():
            kernels.update(mosaic_kernels(segment.compiled.as_text()))
        kernels = dict(kernels)
    else:
        kernels = _eager_kernels()
    return {"kernels": kernels,
            "checked": {
                "tier": tier, "losses": losses,
                # per-op: forward + optimizer op dispatches (backward
                # adds one launch per grad node); lazy: segment flushes
                "launches_per_steady_step": launches[-1],
                "probe_ok": _probed()}}


# ---------------------------------------------------------------------
# serving
# ---------------------------------------------------------------------
def _serve_run(model, prompts, new_tokens, arrivals):
    """Drain ``prompts`` through a fresh ``GenerationEngine``
    (``add_request`` + ``step``), request ``i`` arriving before step
    ``arrivals[i]``.  Returns the generated tokens, the logits of the
    first decode row, and step counts.

    The engine samples on the device and hands back tokens only.  To
    compare logits, the step's device dispatch is wrapped: on the first
    step that carries a decode row, the same model call the step makes
    is repeated on the inputs the engine just staged, returning logits
    instead of samples (its K/V writes land on the slots the step
    itself wrote, with the same values)."""
    gc.collect()          # an earlier engine's KV pool, held by cycles
    engine = GenerationEngine(model)
    logits_fn = paddle.jit.to_static(
        lambda ids: model(ids, cache=engine._view, use_cache=False))
    seen = {"mixed": 0, "decode_logits": None, "tap_compiles": 0,
            "shape": None}
    dispatch_step = engine._checked_dispatch

    def tapped(ids_t, args, chunk, decodes, appended):
        tok = dispatch_step(ids_t, args, chunk, decodes, appended)
        seen["mixed"] += bool(decodes and chunk is not None)
        seen["shape"] = (chunk is not None, len(decodes))
        if decodes and seen["decode_logits"] is None:
            before = _compiles["n"]
            with paddle.no_grad():
                # decode rows are packed first: row 0 of the flat
                # buffer is the first decoding request's new token
                seen["decode_logits"] = np.asarray(
                    logits_fn(ids_t).numpy()[0, 0], np.float32)
            seen["tap_compiles"] = _compiles["n"] - before
        return tok

    engine._checked_dispatch = tapped
    try:
        # The step program has one shape and must compile once.  The
        # host side of a step also runs small jnp programs (feeding the
        # previous tokens in, draining) whose shapes follow the number
        # of decode rows, so a step may compile only the first time its
        # (prefill chunk?, decode rows) shape is seen.
        ids, step, shapes, host_compiles, repeat_compiles = {}, 0, set(), 0, 0
        while engine.has_unfinished() or len(ids) < len(prompts):
            for i, at in enumerate(arrivals):
                if at == step:
                    ids[i] = engine.add_request(
                        prompts[i], max_new_tokens=new_tokens)
            before, seen["shape"], seen["tap_compiles"] = \
                _compiles["n"], None, 0
            engine.step()
            n = _compiles["n"] - before - seen["tap_compiles"]
            if step:
                host_compiles += n
            if seen["shape"] in shapes:
                repeat_compiles += n
            shapes.add(seen["shape"])
            step += 1
        (entry,) = engine._step_fn._cache.values()
        return {
            "tokens": [engine.result(ids[i])[len(prompts[i]):]
                       for i in range(len(prompts))],
            "decode_logits": seen["decode_logits"],
            "mixed_steps": seen["mixed"], "steps": step,
            "host_compiles_after_first_step": host_compiles,
            "repeat_shape_compiles": repeat_compiles,
            "token_budget": engine.token_budget,
            "table_width": engine.cache.table_width,
            "kv_blocks": engine.cache.num_blocks,
            "kernels": mosaic_kernels(entry["compiled"].as_text())}
    finally:
        engine.close()


def serve(cfg, prompt_lens, new_tokens=32, arrivals=None):
    """Greedy requests through the paged-KV engine, kernels on and then
    off; later requests arrive while earlier ones decode, so prefill
    chunks and decode rows share steps."""
    arrivals = arrivals or [0, 0, 2, 4][:len(prompt_lens)]
    paddle.seed(SEED)
    model = GPTForCausalLM(cfg).bfloat16()
    model.eval()
    rng = np.random.default_rng(SEED)
    prompts = [rng.integers(0, cfg.vocab_size, n).tolist()
               for n in prompt_lens]
    on = _serve_run(model, prompts, new_tokens, arrivals)
    with composites():
        off = _serve_run(model, prompts, new_tokens, arrivals)
    for run in (on, off):
        check([len(t) for t in run["tokens"]] == [new_tokens] * len(prompts),
              f"not every request finished with {new_tokens} tokens: "
              f"{[len(t) for t in run['tokens']]}")
    check(on["mixed_steps"] > 0, "no step mixed a prefill chunk with "
          "decode rows")
    check(on["repeat_shape_compiles"] == 0,
          f"{on['repeat_shape_compiles']} compilations in steps whose "
          "shape had run before")
    check(not off["kernels"], f"flag off, kernels ran: {off['kernels']}")
    rel = _rel_l2(on["decode_logits"], off["decode_logits"])
    check(np.isfinite(on["decode_logits"]).all() and rel <= BF16_REL_L2,
          f"first-decode logits differ from composites by {rel} (rel L2)")
    diverge = [next((j for j, (a, b) in enumerate(zip(x, y)) if a != b),
                    None)
               for x, y in zip(on["tokens"], off["tokens"])]
    return {"kernels": on["kernels"],
            "checked": {
                "requests": len(prompts), "new_tokens_each": new_tokens,
                "steps": on["steps"], "mixed_steps": on["mixed_steps"],
                "token_budget": on["token_budget"],
                "table_width": on["table_width"],
                "kv_blocks": on["kv_blocks"],
                "step_program_compiles": 1,
                "compiles_in_repeated_step_shapes": 0,
                "host_path_compiles_after_first_step":
                    on["host_compiles_after_first_step"],
                "decode_logits_rel_l2_vs_composites": rel,
                "rel_l2_tolerance": BF16_REL_L2,
                "first_diverging_greedy_position": diverge,
                "probe_ok": _probed()}}


# ---------------------------------------------------------------------
# serving a model with two kinds of state
# ---------------------------------------------------------------------
# bfloat16 weights and activations against the float32 reference,
# through four layers and two kinds of cache: a rounding of 2^-8 at
# every product, a few dozen products deep, and a head over 4,096
# values.  A flipped block (below) moves single rows further than
# rounding does, so the bound holds for the worst row, flips included.
SALA_REL_L2 = 5e-2


def serve_sala(config, prompt_lens, new_tokens=8, engine=None):
    """MiniCPM-SALA at the published widths, two layers of each kind:
    prefill in chunks then decode through the ``GenerationEngine``
    against the plain reference's full forward, on logits.  The step is
    compiled with one more output, the logits row each request samples
    from.  Also counted, in the reference alone: how many block choices
    change when the selector's scores are taken from bfloat16-rounded q
    and pooled keys (what the program scores with), and what those
    flips do to the logits."""
    import jax.numpy as jnp
    from benchmarks.families import _plain, minicpm_sala as family
    from paddle_tpu.core.dispatch import dispatch
    from paddle_tpu.inference.serving.engine import ragged_sample_next
    paddle.seed(SEED)
    model = family.build(config)
    rng = np.random.default_rng(SEED)
    prompts = [rng.integers(0, config["vocab_size"], n).tolist()
               for n in prompt_lens]
    gc.collect()
    eng = GenerationEngine(model, **(engine or {
        "max_batch": 4, "block_size": 64, "num_blocks": 512,
        "max_model_len": 12288, "prefill_chunk": 1024}))
    view, rows = eng._view, {}

    def tapped(ids, seeds, *controls):
        with paddle.no_grad():
            logits = model(ids, cache=view, use_cache=False)
            picked = dispatch(
                "tap_rows", lambda z, i: z[0, i].astype(jnp.float32),
                (logits, view.last_index), {}, differentiable=False)
            return ragged_sample_next(logits, view.last_index, seeds,
                                      view.sample_pos, *controls), picked

    step_fn = paddle.jit.to_static(tapped)

    def step(ids, *args):
        tok, picked = step_fn(ids, *args)
        where = np.asarray(view.sample_pos._value)
        for r, req in enumerate(eng._rows):
            if req is not None and where[r] > 0:
                rows[(req.id, int(where[r]))] = (r, picked._value)
        return tok

    step._cache = step_fn._cache
    eng._step_fn = step
    try:
        ids = [eng.add_request(p, max_new_tokens=new_tokens)
               for p in prompts]
        while eng.has_unfinished():
            eng.step()
        outs = [eng.result(i) for i in ids]
        stats = eng.stats()
        (entry,) = step_fn._cache.values()
        kernels = mosaic_kernels(entry["compiled"].as_text())
    finally:
        eng.close()
    params = _plain.arrays(model)
    worst, flips, flip_rel, positions = 0.0, 0, 0.0, 0
    for rid, out, prompt in zip(ids, outs, prompts):
        check(len(out) == len(prompt) + new_tokens,
              f"{rid} ended with {len(out) - len(prompt)} tokens")
        seq = jnp.asarray(np.asarray(out))
        at = np.arange(len(prompt) - 1, len(out) - 1)
        ref = np.asarray(family.reference_head(
            params, config, family.reference_hidden(params, config, seq)[at]))
        hidden, n = family.reference_hidden(params, config, seq,
                                            score_dtype="bfloat16")
        flips += int(n)
        flip_rel = max(flip_rel, _rel_l2(np.asarray(family.reference_head(
            params, config, hidden[at])), ref))
        for j, pos in enumerate(range(len(prompt), len(out))):
            r, picked = rows[(rid, pos)]
            worst = max(worst, _rel_l2(np.asarray(picked[r]), ref[j]))
            positions += 1
    check(np.isfinite(worst) and worst <= SALA_REL_L2,
          f"served logits differ from the reference by {worst} (rel L2)")
    check(stats["sparse_blocks_selected"] < stats["sparse_blocks_visible"],
          "no decode row pruned: the contexts are too short")
    return {"kernels": kernels,
            "checked": {
                "requests": len(prompts), "prompt_lens": list(prompt_lens),
                "positions": positions,
                "worst_row_rel_l2_vs_reference": worst,
                "rel_l2_tolerance": SALA_REL_L2,
                "block_choices_flipped_by_bf16_scores": flips,
                "logits_rel_l2_moved_by_those_flips": flip_rel,
                "sparse_blocks_selected": stats["sparse_blocks_selected"],
                "sparse_blocks_visible": stats["sparse_blocks_visible"],
                "state_resets": stats["state_resets"],
                "state_pool_bytes": stats["state_pool_bytes"],
                "compressed_pool_bytes": stats["compressed_pool_bytes"],
                "step_program_compiles": len(step_fn._cache),
                "probe_ok": _probed()}}


#: served against reference logits, worst row and median row.  In
#: bfloat16 a token whose 8th and 9th router scores lie within rounding
#: takes another expert than the float32 reference does, and that row
#: moves by tenths (PERF.md section 6, PR 32): the median row holds the
#: program to bfloat16's rounding, and float32 weights to the kernels'.
AFMOE_REL_L2 = {"bfloat16": (0.5, 3e-2), "float32": (1e-3, 1e-3)}


def _served_against_reference(family, config, prompt_lens, new_tokens,
                              engine):
    """Prefill in chunks then decode through the ``GenerationEngine``
    against ``family``'s plain reference's full forward, on logits (the
    step compiled with one more output, the logits row each request
    samples from, as `serve_sala` does).  Returns the step's Mosaic
    kernels, the relative L2 of every sampled row, how many greedy
    tokens are the reference's argmax, the engine's stats and how often
    the step compiled."""
    import jax.numpy as jnp
    from benchmarks.families import _plain
    from paddle_tpu.core.dispatch import dispatch
    from paddle_tpu.inference.serving.engine import ragged_sample_next
    paddle.seed(SEED)
    model = family.build(config)
    rng = np.random.default_rng(SEED)
    prompts = [rng.integers(0, config["vocab_size"], n).tolist()
               for n in prompt_lens]
    gc.collect()
    eng = GenerationEngine(model, **(engine or {
        "max_batch": 4, "block_size": 64, "num_blocks": 512,
        "max_model_len": 8192, "prefill_chunk": 1024}))
    view, rows = eng._view, {}

    def tapped(ids, seeds, *controls):
        with paddle.no_grad():
            logits = model(ids, cache=view, use_cache=False)
            picked = dispatch(
                "tap_rows", lambda z, i: z[0, i].astype(jnp.float32),
                (logits, view.last_index), {}, differentiable=False)
            tok = ragged_sample_next(logits, view.last_index, seeds,
                                     view.sample_pos, *controls)
            # the plan counters leave the step as they do the engine's
            return tok, picked, view.take_reports()

    step_fn = paddle.jit.to_static(tapped)

    def step(ids, *args):
        tok, picked, reports = step_fn(ids, *args)
        where = np.asarray(view.sample_pos._value)
        for r, req in enumerate(eng._rows):
            if req is not None and where[r] > 0:
                rows[(req.id, int(where[r]))] = (r, picked._value)
        return tok, reports

    step._cache = step_fn._cache
    eng._step_fn = step
    try:
        ids = [eng.add_request(p, max_new_tokens=new_tokens)
               for p in prompts]
        while eng.has_unfinished():
            eng.step()
        outs = [eng.result(i) for i in ids]
        stats = eng.stats()
        (entry,) = step_fn._cache.values()
        kernels = mosaic_kernels(entry["compiled"].as_text())
    finally:
        eng.close()
    params = _plain.arrays(model)
    rel, agree = [], 0
    for rid, out, prompt in zip(ids, outs, prompts):
        check(len(out) == len(prompt) + new_tokens,
              f"{rid} ended with {len(out) - len(prompt)} tokens")
        at = np.arange(len(prompt) - 1, len(out) - 1)
        ref = np.asarray(family.reference_head(
            params, config, family.reference_hidden(
                params, config, jnp.asarray(np.asarray(out)))[at]))
        for j, pos in enumerate(range(len(prompt), len(out))):
            r, picked = rows[(rid, pos)]
            rel.append(_rel_l2(np.asarray(picked[r]), ref[j]))
            agree += int(ref[j].argmax() == out[pos])
    worst, median = float(np.max(rel)), float(np.median(rel))
    worst_limit, median_limit = AFMOE_REL_L2[config["dtype"]]
    check(np.isfinite(worst) and worst <= worst_limit
          and median <= median_limit,
          f"served logits differ from the reference by {sorted(rel)} "
          "(rel L2 a row)")
    return kernels, stats, {
        "requests": len(prompts), "prompt_lens": list(prompt_lens),
        "positions": len(rel),
        "worst_row_rel_l2_vs_reference": worst,
        "median_row_rel_l2_vs_reference": median,
        "rel_l2_tolerance_worst_median": [worst_limit, median_limit],
        "greedy_tokens_the_reference_agrees_with": agree,
        "step_program_compiles": len(step_fn._cache),
        "probe_ok": _probed()}


def serve_afmoe(config, prompt_lens, new_tokens=8, engine=None):
    """The AFMoE family at the published widths, a dense sliding, an
    expert sliding and an expert full layer, through the engine against
    the plain reference (`_served_against_reference`).  Both groups of
    block tables, the grouped expert kernel and the ragged kernel's
    window and head-group forms run; a prompt longer than the window
    makes its group release."""
    from benchmarks.families import afmoe as family
    kernels, stats, checked = _served_against_reference(
        family, config, prompt_lens, new_tokens, engine)
    check(stats["window_blocks_released"] > 0,
          "no block was released: the contexts are inside the window")
    return {"kernels": kernels, "checked": {**checked, **{k: stats[k] for k in (
        "window_blocks_released", "window_high_water",
        "kv_blocks_read_window", "kv_blocks_context")}}}


def serve_qwen3_next(config, prompt_lens, new_tokens=8, engine=None):
    """The Qwen3-Next family at the published widths, one period (delta,
    delta, delta, attention) with a share of the experts held, through
    the engine against the plain reference given the same share
    (`_served_against_reference`).  Both gated delta rule kernels, the
    state and convolution pools, the grouped expert kernel over the held
    experts and the ragged kernel at head width 256 run; the prompts
    cross chunk boundaries, and two requests share the step."""
    from benchmarks.families import qwen3_next as family
    kernels, stats, checked = _served_against_reference(
        family, config, prompt_lens, new_tokens, engine)
    check({"gated_delta_rule_fwd", "gated_delta_rule_step_fwd"}
          <= set(kernels) or jax.devices()[0].platform != "tpu",
          f"the gated delta rule kernels are not in the step: {kernels}")
    check(0 < stats["moe_assignments"] < stats["moe_assignments_routed"],
          "every routed assignment was dispatched here: no share is held")
    return {"kernels": kernels, "checked": {**checked, **{k: stats[k] for k in (
        "state_resets", "state_pool_bytes", "moe_assignments",
        "moe_assignments_routed", "kv_blocks_read_full")}}}


def _device_ms(fn, *args, calls=5, donated=()):
    """Median device milliseconds of one call of the compiled ``fn``
    (already run once), from the profiler's ``XLA Modules`` line; None
    where the trace holds no TPU plane (the CPU rehearsal).  Arguments
    ``donated`` are replaced by the results after the first, call after
    call."""
    from jax.profiler import ProfileData
    args = list(args)
    with tempfile.TemporaryDirectory() as trace_dir:
        with jax.profiler.trace(trace_dir):
            for _ in range(calls):
                out = jax.block_until_ready(fn(*args))
                for n, i in enumerate(donated):
                    args[i] = out[1 + n]
        (path,) = glob.glob(os.path.join(
            trace_dir, "plugins", "profile", "*", "*.xplane.pb"))
        ms = [e.duration_ns / 1e6
              for plane in ProfileData.from_file(path).planes
              if plane.name.startswith("/device:TPU:")
              for line in plane.lines if line.name == "XLA Modules"
              for e in line.events]
    return float(np.median(ms)) if ms else None


def sampler_gate(rows, vocab):
    """The in-graph sampler on ``rows`` float32 rows of ``vocab``
    logits, as the engine's step reads them: a greedy batch, then the
    same batch with one sampling row.  Tokens against the ungated
    `_filter_and_draw` both times, and what each costs on the device:
    the first takes the argmax alone, the second pays the whole filter
    for every row."""
    import jax.numpy as jnp
    from paddle_tpu.inference.serving.engine import (_filter_and_draw,
                                                     _ragged_sample_impl)
    key = jax.random.PRNGKey(SEED)
    logits = 4.0 * jax.random.normal(key, (1, rows, vocab), jnp.float32)
    last = jnp.asarray(np.random.default_rng(SEED).permutation(rows)
                       .astype(np.int32))
    greedy = (jnp.arange(rows, dtype=jnp.int32),                # seeds
              jnp.arange(50, 50 + rows, dtype=jnp.int64),    # positions
              jnp.zeros(rows, bool), jnp.full(rows, 50, jnp.int32),
              jnp.full(rows, 0.9, jnp.float32),
              jnp.full(rows, 0.8, jnp.float32))
    one = greedy[:2] + (greedy[2].at[rows // 2].set(True),) + greedy[3:]

    def every_row_filtered(logits, last, *controls):
        return _filter_and_draw(logits[0, last].astype(jnp.float32),
                                *controls)

    gated, ungated = (jax.jit(f).lower(logits, last, *greedy).compile()
                      for f in (_ragged_sample_impl, every_row_filtered))
    ms, drawn = {}, {}
    for name, controls in (("greedy", greedy), ("one_sampling_row", one)):
        got = np.asarray(gated(logits, last, *controls))
        want = np.asarray(ungated(logits, last, *controls))
        check((got == want).all(), f"{name}: gated tokens {got.tolist()}"
              f" against ungated {want.tolist()}")
        drawn[name] = int((got != np.asarray(
            jnp.argmax(logits[0, last], axis=-1))).sum())
        ms[name] = _device_ms(gated, logits, last, *controls)
    ms["ungated_greedy"] = _device_ms(ungated, logits, last, *greedy)
    check(drawn["greedy"] == 0, "a greedy batch left the argmax")
    check(drawn["one_sampling_row"] <= 1,
          "a greedy row beside the sampling row left the argmax")
    return {"checked": {"rows": rows, "vocab": vocab,
                        "tokens_off_the_argmax": drawn,
                        "device_ms": ms}}


#: what a v5e's HBM delivers (`benchmarks/peaks.json`), for
#: `ragged_walk`'s floor
HBM_BYTES_PER_S = 819e9

#: `ragged_walk`'s calls at the serving cells' geometry: (name, form,
#: query heads a KV head, KV heads, head width, block size, table
#: width, window, chunk tokens).  GPT-2 makes one call a layer for a
#: 256-token chunk and 31 decode rows; the grouped engines one for
#: their 32 decode rows and one for a 1,024-token chunk
#: (`attention._grouped_attend_impl`).
RAGGED_WALK_CALLS = (
    ("gpt2-large.step", "mixed", 1, 20, 64, 16, 64, None, 256),
    ("Trinity-Mini.decode.window", "decode", 8, 4, 128, 64, 34, 2048, 0),
    ("Trinity-Mini.decode.full", "decode", 8, 4, 128, 64, 224, None, 0),
    ("Trinity-Mini.chunk.window", "chunk", 8, 4, 128, 64, 50, 2048, 1024),
    ("Trinity-Mini.chunk.full", "chunk", 8, 4, 128, 64, 224, None, 1024),
    ("Qwen3-Next.decode", "decode", 8, 2, 256, 64, 416, None, 0),
    ("Qwen3-Next.chunk", "chunk", 8, 2, 256, 64, 416, None, 1024),
)


def _ragged_walk_call(form, group, kv_heads, d, bs, width, window, chunk,
                      fill, rows=32, chunk_bq=128):
    """One attention call of a serving step as a function of (q, k_pool,
    v_pool, use_pallas), its arguments, the K/V blocks its rows have
    to read (all KV heads), for tables filled to ``fill`` of their
    width, and what the step's scatter writes before it (new K, new V
    and their flat slots: the chunk's tokens into row 0's last blocks,
    a token a decode row at its context's end).  Every row owns its
    blocks; block 0 is the null block.  The pools have the layout of the
    tree this file runs in: a parent of PR 38 keeps ``[nb, H, bs, D]``."""
    import jax.numpy as jnp
    from paddle_tpu.inference.serving import attention as att
    from paddle_tpu.ops import pallas_ragged as pr
    ctx = max(int(fill * width) * bs - bs // 2, 1)      # ends mid-block
    tables = 1 + np.arange(rows * width, dtype=np.int32).reshape(rows, width)
    pool = (rows * width + 1, bs, kv_heads * d)
    if hasattr(pr, "_lane_parts"):                      # before PR 38
        pool = (rows * width + 1, kv_heads, bs, d)
    key = jax.random.PRNGKey(SEED)
    kk, kv, kq = jax.random.split(key, 3)
    k_pool = jax.random.normal(kk, pool, jnp.bfloat16)
    v_pool = jax.random.normal(kv, pool, jnp.bfloat16)
    span = ctx if window is None else min(ctx, window)

    def blocks(first, last):        # table slots that hold keys first..last
        return last // bs - first // bs + 1

    if form == "decode":
        q = jax.random.normal(kq, (rows, group * kv_heads, d), jnp.bfloat16)
        tab = jnp.asarray(np.broadcast_to(
            tables[:, None], (rows, kv_heads, width)))
        lens = jnp.full((rows, kv_heads), ctx, jnp.int32)
        bq = att.decode_block_q(group, jnp.bfloat16)

        def fn(q, kp, vp, use_pallas):
            return att.grouped_decode_attention(
                q, kp, vp, tab, lens, use_pallas, window=window, block_q=bq)
        need = rows * kv_heads * blocks(ctx - span, ctx - 1)
    elif form == "chunk":
        take = min(chunk, ctx)
        q = jax.random.normal(kq, (chunk, group * kv_heads, d),
                              jnp.bfloat16)
        tab = jnp.asarray(tables[0])

        def fn(q, kp, vp, use_pallas):
            return att.grouped_chunk_attention(
                q, kp, vp, tab, jnp.int32(ctx), jnp.int32(ctx - take),
                jnp.int32(take), window=window, chunk_bq=chunk_bq,
                use_pallas=use_pallas)
        need = 0
        for first in range(ctx - take, ctx, chunk_bq):
            last = min(first + chunk_bq, ctx) - 1
            low = 0 if window is None else max(first - window + 1, 0)
            need += kv_heads * blocks(low, last)
    else:
        # the ungrouped engine's call: a chunk of one sequence in
        # q-blocks of `ragged_q_block` rows, then the decode rows
        bq = pr.ragged_q_block(jnp.bfloat16)
        take = min(chunk, ctx)
        lens = [ctx] * rows
        sid, qs, qv, _, total = pr.ragged_segments(
            [take] + [1] * (rows - 1), lens, bq)
        q = jax.random.normal(kq, (total, kv_heads, d), jnp.bfloat16)
        tab, cl = jnp.asarray(tables), jnp.asarray(lens, jnp.int32)
        sid, qs, qv = jnp.asarray(sid), jnp.asarray(qs), jnp.asarray(qv)

        def fn(q, kp, vp, use_pallas):
            return att._ragged_attention_impl(
                q[None], kp, vp, tab, cl, sid, qs, qv, block_q=bq,
                scale=1.0 / math.sqrt(d), use_pallas=use_pallas)[0]
        need = kv_heads * ((rows - 1) * blocks(0, ctx - 1) + sum(
            blocks(0, min(first + bq, ctx) - 1)
            for first in range(ctx - take, ctx, bq)))
    # the step's scatter: every token of the budget, as the engine's
    # one scatter a layer writes them
    slot = lambda r, t: int(tables[r, t // bs]) * bs + t % bs  # noqa: E731
    new = min(chunk or 1024, ctx)
    slots = np.asarray([slot(0, t) for t in range(ctx - new, ctx)]
                       + [slot(r, ctx - 1) for r in range(1, rows)],
                       np.int32)
    fresh = jax.random.normal(key, (2, 1, len(slots), kv_heads, d),
                              jnp.bfloat16)
    return (fn, (q, k_pool, v_pool), need,
            (fresh[0], fresh[1], jnp.asarray(slots)))


def ragged_walk(calls, fills=(0.25, 1.0), **sizes):
    """The ragged attention kernel alone, one call of a serving step at
    a time (``calls``: `RAGGED_WALK_CALLS`' form), over block tables
    filled to each of ``fills``: the kernel against the XLA fallback at
    the first fill, then its device time, the share of that time
    that the bytes of the K/V blocks its rows need would take at the
    HBM's rate, and the device time of the step's scatter followed by
    the kernel on donated pools (``scatter_kernel_us``: what a layer of
    a step pays, copies between the two layouts included where a tree
    has two)."""
    from paddle_tpu.inference.serving import attention as att
    checked, kernels = {}, Counter()
    for name, *call in calls:
        _, _, kv_heads, d, bs, *_ = call
        for n, fill in enumerate(fills):
            fn, args, need, written = _ragged_walk_call(*call, fill,
                                                        **sizes)
            run = jax.jit(lambda q, kp, vp: fn(q, kp, vp, True))
            compiled = run.lower(*args).compile()
            kernels.update(mosaic_kernels(compiled.as_text()))
            got = jax.block_until_ready(compiled(*args))
            entry = {"kv_blocks": need}
            if n == 0:
                ref = jax.jit(lambda q, kp, vp: fn(q, kp, vp, False))(*args)
                entry["rel_l2_vs_fallback"] = _rel_l2(got, ref)
                check(entry["rel_l2_vs_fallback"] < BF16_REL_L2,
                      f"{name}: kernel against fallback "
                      f"{entry['rel_l2_vs_fallback']:.3g}")
                del ref
            ms = _device_ms(compiled, *args)
            if ms is not None:
                floor_us = need * 2 * bs * d * 2 / HBM_BYTES_PER_S * 1e6
                entry.update(us=round(ms * 1e3, 1),
                             floor_us=round(floor_us, 1),
                             floor_share=round(floor_us / (ms * 1e3), 4))
            del compiled, got

            def layer(q, kp, vp, kn, vn, slots):
                kp, vp = att._kv_scatter_impl(kp, vp, kn, vn, slots)
                return fn(q, kp, vp, True), kp, vp
            both = jax.jit(layer, donate_argnums=(1, 2)).lower(
                *args, *written).compile()
            out = jax.block_until_ready(both(*args, *written))
            ms = _device_ms(both, args[0], *out[1:], *written,
                            donated=(1, 2))
            if ms is not None:
                entry["scatter_kernel_us"] = round(ms * 1e3, 1)
            checked[f"{name}@{fill}"] = entry
            del fn, args, run, both, out, written
    return {"kernels": dict(kernels), "checked": checked}


#: `latent_walk`'s geometry: the LongCat-Flash cell's (32 rows, tables
#: of 272 blocks of 64 tokens, 64 heads, a latent row of 512 + 64 lanes
#: in five 128-lane tiles, 128 + 64 query lanes a head, values of 128)
LATENT_WALK = dict(rows=32, width=272, bs=64, heads=64, rank=512, rope=64,
                   nope=128, value=128, chunk=1024)


def latent_walk(fills=(0.25, 1.0), rows=32, width=272, bs=64, heads=64,
                rank=512, rope=64, nope=128, value=128, chunk=1024):
    """The latent attention kernel alone at the LongCat-Flash cell's
    shapes, over tables filled to each of ``fills``: the decode rows'
    call and a 1,024-token chunk at the table's end in the absorbed form
    (``latent_attention_fwd``) against `attention._latent_ref`, their
    device times, the scatter then the decode call on a donated pool,
    and the chunk in the other form, the context's latents up-projected
    a head (``W_UK``, ``W_UV``) and attended at widths ``nope + rope`` /
    ``value`` by XLA, for the same chunk: the like-for-like pair the
    chunk's form is chosen by."""
    import jax.numpy as jnp
    from paddle_tpu.inference.serving import attention as att
    lanes = -(-(rank + rope) // 128) * 128
    tables = 1 + np.arange(rows * width, dtype=np.int32).reshape(rows, width)
    kp, kq, kw, kn = jax.random.split(jax.random.PRNGKey(SEED), 4)
    w_ukv = (0.05 * jax.random.normal(
        kw, (rank, heads, nope + value), jnp.float32)).astype(jnp.bfloat16)
    scale = (nope + rope) ** -0.5
    ct = att.latent_chunk_tokens(heads, chunk)
    checked, kernels = {}, Counter()
    for fill in fills:
        # fresh each fill: the last call of a fill donates it
        pool = jnp.pad(jax.random.normal(
            kp, (rows * width + 1, bs, rank + rope), jnp.bfloat16),
            ((0, 0), (0, 0), (0, lanes - rank - rope)))
        ctx = max(int(fill * width) * bs - bs // 2, chunk)
        used = -(-ctx // bs)
        full = jnp.asarray(tables)
        cut = jnp.asarray(tables[:, :used])

        def decode(q, pool, tab, use_pallas):
            c = jnp.full((rows,), ctx, jnp.int32)
            return att._latent_call(
                use_pallas, q, pool, tab, c, jnp.arange(rows, dtype=jnp.int32),
                c - 1, jnp.ones((rows,), jnp.int32), block_q=heads,
                block_tokens=1, value_lanes=rank, scale=scale)

        def absorbed(q, pool, tab, use_pallas):
            nqb = chunk // ct
            first = jnp.arange(nqb, dtype=jnp.int32) * ct
            return att._latent_call(
                use_pallas, q, pool, tab[:1], jnp.full((1,), ctx, jnp.int32),
                jnp.zeros((nqb,), jnp.int32), ctx - chunk + first,
                jnp.full((nqb,), ct, jnp.int32), block_q=heads * ct,
                block_tokens=ct, value_lanes=rank, scale=scale)

        def projected(q, pool, tab):
            """The chunk's queries ``[C, H, nope + rope]`` against the
            context's keys and values up-projected a head."""
            rows_ = pool[tab[0]].reshape(-1, lanes)[:ctx]
            c, kr = rows_[:, :rank], rows_[:, rank:rank + rope]
            kv = jnp.einsum("sc,chd->shd", c, w_ukv,
                            preferred_element_type=jnp.float32) \
                .astype(jnp.bfloat16)
            k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(
                kr[:, None], (ctx, heads, rope))], -1)
            v = kv[..., nope:]
            pos = jnp.arange(ctx)

            def block(args):
                qb, tb = args
                s = jnp.einsum("qhd,shd->hqs", qb, k,
                               preferred_element_type=jnp.float32) * scale
                p = jax.nn.softmax(jnp.where(pos[None, None, :]
                                             <= tb[None, :, None], s,
                                             -1e30), -1)
                return jnp.einsum("hqs,shv->qhv", p.astype(jnp.bfloat16), v,
                                  preferred_element_type=jnp.float32)
            qb = min(128, chunk)
            t = ctx - chunk + jnp.arange(chunk)
            return jax.lax.map(block, (q.reshape(-1, qb, heads, nope + rope),
                                       t.reshape(-1, qb))).reshape(
                chunk, heads, value).astype(jnp.bfloat16)

        qd = jax.random.normal(kq, (rows * heads, lanes), jnp.bfloat16)
        qc = jax.random.normal(kq, (chunk * heads, lanes), jnp.bfloat16)
        qp = jax.random.normal(kn, (chunk, heads, nope + rope), jnp.bfloat16)
        need = {"decode": rows * used,
                "chunk": sum(min(used, (a + ct - 1) // bs + 1)
                             for a in range(ctx - chunk, ctx, ct))}
        for form, fn, q in (("decode", decode, qd),
                            ("chunk", absorbed, qc)):
            compiled = jax.jit(lambda q, p, t: fn(q, p, t, True)).lower(
                q, pool, full).compile()
            kernels.update(mosaic_kernels(compiled.as_text()))
            got = jax.block_until_ready(compiled(q, pool, full))
            entry = {"latent_blocks": need[form]}
            if fill == fills[0]:
                ref = jax.jit(lambda q, p, t: fn(q, p, t, False))(q, pool,
                                                                  cut)
                entry["rel_l2_vs_jnp"] = _rel_l2(got, ref)
                check(entry["rel_l2_vs_jnp"] < BF16_REL_L2,
                      f"latent {form}: kernel against its jnp form "
                      f"{entry['rel_l2_vs_jnp']:.3g}")
                del ref
            ms = _device_ms(compiled, q, pool, full)
            if ms is not None:
                floor_us = need[form] * bs * (rank + rope) * 2 \
                    / HBM_BYTES_PER_S * 1e6
                entry.update(us=round(ms * 1e3, 1),
                             floor_us=round(floor_us, 1))
            checked[f"latent.{form}@{fill}"] = entry
            del compiled, got
        # the other form of the chunk, like for like
        proj = jax.jit(projected).lower(qp, pool, full).compile()
        jax.block_until_ready(proj(qp, pool, full))
        ms = _device_ms(proj, qp, pool, full)
        checked[f"projected.chunk@{fill}"] = {
            "us": None if ms is None else round(ms * 1e3, 1)}
        # the step's scatter (a chunk and 31 decode rows) then the
        # decode rows' call, on the donated pool
        slot = lambda r, t: int(tables[r, t // bs]) * bs + t % bs  # noqa: E731
        slots = jnp.asarray([slot(0, t) for t in range(ctx - chunk, ctx)]
                            + [slot(r, ctx - 1) for r in range(1, rows)],
                            jnp.int32)
        new = jax.random.normal(kn, (len(slots), lanes), jnp.bfloat16)

        def layer(q, pool, new, slots):
            pool = att._scatter_rows(pool, new, slots)
            return decode(q, pool, full, True), pool
        both = jax.jit(layer, donate_argnums=(1,)).lower(
            qd, pool, new, slots).compile()
        out = jax.block_until_ready(both(qd, pool, new, slots))
        ms = _device_ms(both, qd, out[1], new, slots, donated=(1,))
        if ms is not None:
            checked[f"latent.decode@{fill}"]["scatter_kernel_us"] = round(
                ms * 1e3, 1)
        del both, out, proj, pool
    return {"kernels": dict(kernels), "checked": checked}


#: `moe_dispatch`' expert layers, the three MoE cells' at their
#: published widths over the engine's 1,520-row budget: (name, hidden,
#: router outputs, experts held, top-k, expert width, router, the
#: std of the router's bias as the cell's file draws it, first
#: zero-compute expert)
MOE_DISPATCH_LAYERS = (
    ("Trinity-Mini", 2048, 128, 128, 8, 1024, "sigmoid", 0.05, None),
    ("Qwen3-Next", 2048, 512, 128, 10, 512, "softmax", 0.0, None),
    ("LongCat-Flash", 6144, 768, 8, 12, 2048, "softmax_bias", 0.0002, 512),
)


def _scatter_dispatch(x, rows, top_k, padded_rows):
    """The dispatch the gathers replaced: every token repeated ``top_k``
    times, then scattered whole into a zeroed buffer."""
    import jax.numpy as jnp
    return jnp.zeros((padded_rows, x.shape[1]), x.dtype).at[rows].set(
        jnp.repeat(x, top_k, axis=0), mode="drop")


def _einsum_combine(y_rows, rows, topk_val):
    """The combine the reduction replaced: the rows widened to float32
    and contracted with the weights over ``k``."""
    import jax.numpy as jnp
    N, k = topk_val.shape
    g = y_rows.at[rows].get(mode="fill", fill_value=0).reshape(N, k, -1)
    return jnp.einsum("nk,nkd->nd", topk_val.astype(jnp.float32),
                      g.astype(jnp.float32)).astype(y_rows.dtype)


def moe_dispatch(layers, tokens=1520, carried=1056):
    """An expert layer of each MoE cell, split: the router, the plan,
    the dispatch, the grouped kernel pair and the combine, each its own
    program, and the whole layer; the dispatch and the combine (and so
    the layer) in the scatter-and-einsum form and in the gather form
    `moe_dispatch.gated_experts` runs.  ``tokens`` rows of which the
    first ``carried`` carry something (a chunk step: 1,024 chunk tokens
    and 32 decode rows).  Checks that the two forms fill the grouped
    buffer bit for bit alike and agree on the combine and the layer;
    reports device µs of each program and the dispatch's bytes (the
    buffer written, each source row read once) over its time as a share
    of the HBM's rate."""
    import jax.numpy as jnp
    from paddle_tpu.distributed.auto_parallel import moe_dispatch as md
    from paddle_tpu.ops import pallas_grouped as pg
    checked, kernels = {}, Counter()
    for (name, hidden, routed, held, k, width, router, bias_std,
         identity) in layers:
        ks = jax.random.split(jax.random.PRNGKey(SEED), 5)
        x = jax.random.normal(ks[0], (tokens, hidden), jnp.bfloat16)
        w_router = jax.random.normal(ks[1], (hidden, routed),
                                     jnp.float32) * hidden ** -0.5
        bias = bias_std * jax.random.normal(ks[2], (routed,), jnp.float32)
        gate_up = (0.02 * jax.random.normal(
            ks[3], (held, hidden, 2 * width), jnp.float32)) \
            .astype(jnp.bfloat16)
        down = (0.02 * jax.random.normal(
            ks[4], (held, width, hidden), jnp.float32)).astype(jnp.bfloat16)
        live = jnp.arange(tokens) < carried
        bm, nb, R = pg.grouped_layout(tokens * k, held, jnp.bfloat16)
        use_pallas = bool(pg.gated_block_n(bm, hidden, width, jnp.bfloat16))
        gated, lin = (pg.grouped_gated_act, pg.grouped_linear_act) \
            if use_pallas else (pg.grouped_gated_act_ref,
                                pg.grouped_linear_act_ref)

        def route(x, w_router, bias):
            logits = jnp.dot(x.astype(jnp.float32), w_router,
                             precision="highest")
            if router == "sigmoid":
                return md.sigmoid_topk_router(logits, bias, k, 2.826)
            if router == "softmax":
                return md.softmax_topk_router(logits, k)
            return md.softmax_bias_topk_router(logits, bias, k, 6.0)

        def plan(idx, live):
            return md.dropless_plan(idx, held, bm, nb, live, (0, held))[:2]

        def pair(xd, gid, gate_up, down):
            return lin(gated(xd, gate_up, block_group=gid, act="silu"),
                       down, block_group=gid)

        def zero_term(y, idx, w, x, live):
            if identity is None:
                return y
            kept = jnp.where((idx >= identity) & live[:, None], w,
                             0.0).sum(-1)
            return (y.astype(jnp.float32) + kept[:, None]
                    * x.astype(jnp.float32)).astype(y.dtype)

        def layer_scatter(x, w_router, bias, gate_up, down, live):
            idx, w = route(x, w_router, bias)
            rows, gid, _ = md.dropless_plan(idx, held, bm, nb, live,
                                            (0, held))
            y_rows = pair(_scatter_dispatch(x, rows, k, R), gid, gate_up,
                          down)
            return zero_term(_einsum_combine(y_rows, rows, w), idx, w, x,
                             live)

        def layer_gather(x, w_router, bias, gate_up, down, live):
            idx, w = route(x, w_router, bias)
            return md.gated_experts(x, idx, w, gate_up, down, live,
                                    use_pallas=use_pallas, held=(0, held),
                                    identity=identity)[0]

        entry = {"buffer_rows": R, "block_rows": bm}
        timed = {}

        def run(label, fn, *args):
            compiled = jax.jit(fn).lower(*args).compile()
            kernels.update(mosaic_kernels(compiled.as_text()))
            out = jax.block_until_ready(compiled(*args))
            timed[label] = _device_ms(compiled, *args)
            return out

        idx, w = run("router", route, x, w_router, bias)
        rows, gid = run("plan", plan, idx, live)
        old = run("dispatch.scatter", lambda x, r: _scatter_dispatch(
            x, r, k, R), x, rows)
        new = run("dispatch.gather", lambda x, r: md.dropless_dispatch(
            x, r, k, R), x, rows)
        check((np.asarray(old.view(jnp.uint16))
               == np.asarray(new.view(jnp.uint16))).all(),
              f"{name}: the gathered buffer differs from the scattered")
        y_rows = run("kernels", pair, new, gid, gate_up, down)
        del old, new
        c_old = run("combine.einsum", _einsum_combine, y_rows, rows, w)
        c_new = run("combine.reduce", md.dropless_combine, y_rows, rows, w)
        entry["combine_rel_l2"] = _rel_l2(c_new, c_old)
        check(entry["combine_rel_l2"] < 1e-3,
              f"{name}: combines differ by {entry['combine_rel_l2']:.3g}")
        del y_rows, c_old, c_new
        args = (x, w_router, bias, gate_up, down, live)
        l_old = run("layer.scatter", layer_scatter, *args)
        l_new = run("layer.gather", layer_gather, *args)
        entry["layer_rel_l2"] = _rel_l2(l_new, l_old)
        check(entry["layer_rel_l2"] < 1e-3,
              f"{name}: layers differ by {entry['layer_rel_l2']:.3g}")
        if timed["dispatch.gather"] is not None:
            entry["us"] = {n: round(ms * 1e3, 1) for n, ms in timed.items()}
            # the least either form moves: the buffer written once, the
            # rows of the tokens that reach a held expert read once, and
            # the one zero row
            sources = int((np.asarray(rows).reshape(tokens, k) < R)
                          .any(1).sum())
            moved = (R + sources + 1) * hidden * 2
            entry["dispatch_hbm_share"] = {
                form: round(moved / (timed[f"dispatch.{form}"] * 1e-3)
                            / HBM_BYTES_PER_S, 4)
                for form in ("scatter", "gather")}
        checked[name] = entry
        del x, gate_up, down, l_old, l_new, args
    return {"kernels": dict(kernels), "checked": checked}


def afmoe_config(layers=(0, 1, 4), dtype=None):
    """The benchmark's Trinity-Mini file with the layer list cut to a
    dense sliding, an expert sliding and an expert full layer (depth is
    what a smoke may cut; no width is); ``layers=(1, 4)`` leaves the
    two expert layers, which fit the chip in float32."""
    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "benchmarks", "configs",
                           "Trinity-Mini.json")) as f:
        config = json.load(f)
    return {**config, "num_hidden_layers": len(layers),
            "num_dense_layers": sum(i < config["num_dense_layers"]
                                    for i in layers),
            "dtype": dtype or config["dtype"],
            "layer_types": [config["layer_types"][i] for i in layers]}


def qwen3_next_config(layers=4, chips=32, dtype="float32"):
    """The benchmark's Qwen3-Next file cut to one period of layers and
    to one of ``chips`` chips' share of the experts (16 of 512), which
    fits the chip in float32 (depth and the share are what a smoke may
    cut; no width is)."""
    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "benchmarks", "configs",
                           "Qwen3-Next-80B-A3B-Instruct.json")) as f:
        config = json.load(f)
    return {**config, "num_hidden_layers": layers, "dtype": dtype,
            "num_experts": config["published_num_experts"] // chips,
            "expert_shard": {"chips": chips, "index": 0}}


def sala_config(layers=("minicpm4", "lightning-attn", "lightning-attn",
                        "minicpm4")):
    """The benchmark's configuration file with the layer list cut to
    two of each kind (depth is what a smoke may cut; no width is)."""
    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "benchmarks", "configs",
                           "MiniCPM-SALA.json")) as f:
        config = json.load(f)
    return {**config, "mixer_types": list(layers),
            "num_hidden_layers": len(layers)}


# ---------------------------------------------------------------------
# training across four chips
# ---------------------------------------------------------------------
def train_static_mesh(cfg, batch, seq, mesh="dp=2,tp=2", steps=4):
    """The ``train_static`` program under a ``MeshPlan`` with the
    repo's BERT rules, against the same program, seed and batch on
    device 0 alone, in this process.  XLA partitions the sharded step,
    and a Mosaic call cannot be partitioned automatically, so that
    step runs the XLA composites (``pallas_gate._auto_partitioned``):
    the kernels are live in the one-device run it is compared with."""
    one = _bert_static_run(cfg, batch, seq, steps)
    plan = MeshPlan(mesh, rules=BERT_RULES())
    many = _bert_static_run(cfg, batch, seq, steps, plan=plan)
    for i, (a, b) in enumerate(zip(many["losses"], one["losses"])):
        check(math.isfinite(a) and abs(a - b) <= BF16_LOSS_RTOL * abs(b),
              f"step {i}: loss {a} on {mesh} vs {b} on one device")
    check(many["collectives"]["all-reduce"] > 0,
          f"no all-reduce in the sharded step: {many['collectives']}")
    per_dev = many["param_bytes_per_device"]
    check(len(per_dev) == plan.size and min(per_dev.values()) > 0,
          f"parameters live on devices {sorted(per_dev)} of {plan.size}")
    check(max(per_dev.values()) < many["param_bytes"],
          "no parameter is sharded: every device holds all "
          f"{many['param_bytes']} bytes")
    check(max(per_dev.values()) <= 1.01 * min(per_dev.values()),
          f"uneven parameter bytes across devices: {per_dev}")
    check(not many["kernels"], "Mosaic calls in an XLA-partitioned "
          f"step: {many['kernels']}")
    return {"kernels": one["kernels"],
            "checked": {
                "mesh": mesh, "losses": many["losses"],
                "losses_one_device": one["losses"],
                "loss_rtol": BF16_LOSS_RTOL,
                "collectives": many["collectives"],
                "param_bytes_per_device": per_dev,
                "param_bytes_total": many["param_bytes"],
                "kernels_in_sharded_step": many["kernels"]}}


# ---------------------------------------------------------------------
def run_phase(name, fn, *args, **kwargs):
    """Run one phase; print its JSON line; free what it held."""
    n0, s0, t0 = _compiles["n"], _compiles["seconds"], time.perf_counter()
    result = fn(*args, **kwargs)
    line = {"phase": name,
            "seconds": round(time.perf_counter() - t0, 2),
            "compile_seconds": round(_compiles["seconds"] - s0, 2),
            "compiles": _compiles["n"] - n0, **result}
    stats = jax.devices()[0].memory_stats() or {}   # None on the CPU
    if "peak_bytes_in_use" in stats:
        # the process's high-water mark so far, not this phase's alone
        line["hbm_peak_gb_so_far"] = round(
            stats["peak_bytes_in_use"] / 1e9, 2)
    print(json.dumps(line), flush=True)
    static.Executor.clear_shared_cache()
    gc.collect()
    jax.clear_caches()
    return line


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--phase", action="append", metavar="NAME",
                    help="run only this phase (repeatable); default all")
    args = ap.parse_args(argv)
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke needs a TPU: JAX found {dev.platform!r} "
                 f"({dev.device_kind})")
    if jax.device_count() != args.chips:
        sys.exit(f"--chips {args.chips} but JAX sees "
                 f"{jax.device_count()} device(s)")
    bert, batch, seq = BertConfig(), 16, 512
    # The per-op tier keeps every op's residuals until backward: at 12
    # layers this batch ran out of the chip's 16 GB at the loss op
    # (PERF.md, PR 21).  Depth is what a smoke may cut; width and
    # batch stay.
    bert_eager = BertConfig(num_hidden_layers=6)
    if args.chips == 4:
        phases = [("train_static_mesh", train_static_mesh,
                   (bert, batch, seq), {})]
    else:
        phases = [
            ("attention_dropout", attention_dropout, (bert, batch, seq), {}),
            ("hidden_dropout", hidden_dropout, (bert, batch, seq), {}),
            ("loss_head", loss_head, (bert, batch, seq), {}),
            ("train_static", train_static, (bert, batch, seq), {}),
            ("train_eager", train_eager, (bert_eager, batch, seq), {}),
            ("train_lazy", train_eager, (bert, batch, seq),
             {"lazy_tier": True}),
            ("serve", serve, (GPTConfig(), [37, 200, 513, 900]), {}),
            # 9,500 tokens prune (64 of 115 candidate blocks), 3,000
            # attend densely; both cross chunk boundaries
            ("serve_sala", serve_sala, (sala_config(), [9500, 3000]), {}),
            # 5,000 tokens pass the 2,048-token window twice; 1,500 fit
            ("serve_afmoe", serve_afmoe, (afmoe_config(), [5000, 1500]),
             {}),
            # the same kernels on float32 weights: two expert layers
            ("serve_afmoe_f32", serve_afmoe,
             (afmoe_config((1, 4), "float32"), [3000, 700]), {}),
            # 2,500 tokens cross two chunk boundaries, 700 fit one chunk
            ("serve_qwen3_next_f32", serve_qwen3_next,
             (qwen3_next_config(), [2500, 700]), {}),
            # the sampler alone at Trinity-Mini's batch and vocabulary:
            # what a step pays for its first sampling row
            ("sampler_gate", sampler_gate,
             (32, afmoe_config()["vocab_size"]), {}),
            # the ragged kernel alone, a serving step's call at a time
            ("ragged_walk", ragged_walk, (RAGGED_WALK_CALLS,), {}),
            # the latent kernel alone and the chunk's other form, at the
            # LongCat-Flash cell's shapes
            ("latent_walk", latent_walk, (), LATENT_WALK),
            # an expert layer of each MoE cell split into its programs,
            # the scatter form beside the gathers
            ("moe_dispatch", moe_dispatch, (MOE_DISPATCH_LAYERS,), {})]
    unknown = set(args.phase or ()) - {name for name, *_ in phases}
    if unknown:
        sys.exit(f"no such phase with --chips {args.chips}: "
                 f"{sorted(unknown)}")
    lines = [run_phase(name, fn, *a, **kw) for name, fn, a, kw in phases
             if not args.phase or name in args.phase]
    for line in lines:
        # `sampler_gate` is XLA's alone and reports no kernels
        check(line.get("kernels", True), f"phase {line['phase']}: no "
              "Pallas tpu_custom_call in its compiled program")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": jax.device_count()}}), flush=True)


if __name__ == "__main__":
    main()
