#!/usr/bin/env python
"""tpu_lint: static TPU-readiness lint for paddle_tpu programs.

    python scripts/tpu_lint.py --models [--fail-on {error,warning,never}]
                               [--json] [--only lenet,bert,gpt]

Lints the bundled models without needing a TPU:

  * **lenet** — dygraph train step through ``jit.to_static`` +
    ``analyze_program()`` (the trace-cache / recompile-risk path);
  * **bert**  — static-graph MLM step (AMP bf16) through
    ``Executor.analyze_program`` (the fingerprint-cache path);
  * **gpt**   — static-graph causal-LM step (AMP bf16 + recompute);
  * **moe**   — bundled moe_gpt routing balance at init (TPU508),
    capacity-router headroom at the measured skew (TPU507), and the
    grouped expert matmul's block plans vs the Mosaic tiling rules;
  * **lora**  — multi-LoRA serving: adapter-store working set replayed
    through the LRU policy (TPU509), rank vs the dtype sublane floor
    (TPU510), and the segmented SGMV epilogue's fwd/bwd block plans;
  * **pallas** — flash / ragged attention block plans checked against the
    Mosaic tiling rules (``analysis.tiling``), no kernel launch;
  * **sharding** — built-in BERT/GPT partition-rule sets audited against
    virtual ``dp=2,tp=2`` / ``fsdp=2`` meshes (TPU501 rule miss,
    TPU502 large-replicated), no multi-device runtime needed;
  * **faults** — fault-site registry audit (TPU601 unregistered site
    reference, TPU602 registered-but-uninstrumented site), pure AST
    over the whole tree.

Every finding is a structured ``Diagnostic`` (stable TPUxxx code,
severity, site, fix hint).  Exit code is 1 iff any diagnostic at or
above ``--fail-on`` severity was found (default: error).  Runs in the
tier-1 suite via tests/test_analysis.py so new error-severity findings
on the bundled models break the build.  CPU-only.
"""
import argparse
import json
import os
import sys
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import numpy as np  # noqa: E402

MODELS = ("lenet", "eager", "bert", "gpt", "moe", "lora", "pallas",
          "sharding", "fabric", "faults")


def lint_lenet():
    """Dygraph LeNet step via to_static — exercises the jit trace path."""
    import paddle_tpu as paddle
    from paddle_tpu import optimizer
    from paddle_tpu.vision.models import LeNet
    import paddle_tpu.nn.functional as F

    paddle.disable_static()
    paddle.seed(0)
    model = LeNet(num_classes=10)
    opt = optimizer.Adam(learning_rate=1e-3,
                         parameters=model.parameters())

    def train_step(img, label):
        loss = F.cross_entropy(model(img), label)
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    traced = paddle.jit.to_static(train_step)
    rng = np.random.default_rng(0)
    img = paddle.to_tensor(
        rng.standard_normal((8, 1, 28, 28)).astype(np.float32))
    label = paddle.to_tensor(rng.integers(0, 10, (8,)).astype(np.int64))
    traced(img, label)  # discovery trace
    return traced.analyze_program(img, label)


def lint_eager():
    """LeNet train steps under the lazy eager tier — asserts whole-step
    capture (1 flush/step), fingerprint reuse (steady-state cache hit),
    and runs the TPU205 segment-thrash audit over the compile history."""
    import paddle_tpu as paddle
    from paddle_tpu import optimizer
    from paddle_tpu.core import lazy
    from paddle_tpu.vision.models import LeNet
    import paddle_tpu.nn.functional as F
    from paddle_tpu.analysis.diagnostics import (Diagnostic,
                                                 DiagnosticReport)
    from paddle_tpu.analysis.recompile import audit_segment_cache

    paddle.disable_static()
    paddle.seed(0)
    model = LeNet(num_classes=10)
    opt = optimizer.Adam(learning_rate=1e-3,
                         parameters=model.parameters())
    rng = np.random.default_rng(0)
    img = paddle.to_tensor(
        rng.standard_normal((8, 1, 28, 28)).astype(np.float32))
    label = paddle.to_tensor(rng.integers(0, 10, (8,)).astype(np.int64))

    rep = DiagnosticReport(label="lint:eager")
    deltas = []
    with paddle.incubate.lazy_eager():
        for _ in range(3):
            before = dict(lazy.stats)
            loss = F.cross_entropy(model(img), label)
            loss.backward()
            opt.step()
            opt.clear_grad()
            float(loss)  # the step's one sync point
            deltas.append({k: lazy.stats[k] - before[k]
                           for k in before})
    steady = deltas[-1]
    if steady["flushes"] > 2:
        rep.add(Diagnostic(
            "TPU205", severity="error", site="lint:eager",
            message=f"steady-state lazy LeNet step flushed "
                    f"{steady['flushes']} segments (expected <= 2): "
                    "whole-step capture is broken",
            hint="look for a host read inside the train step"))
    if steady["cache_hits"] < steady["flushes"]:
        rep.add(Diagnostic(
            "TPU205", severity="error", site="lint:eager",
            message="third lazy LeNet iteration was not a pure "
                    f"fingerprint cache hit ({steady['cache_hits']} "
                    f"hits / {steady['flushes']} flushes, "
                    f"{steady['compiles']} compiles)",
            hint="a node key or leaf signature varies per step; run "
                 "analysis.recompile.audit_segment_cache for the node"))
    rep.extend(audit_segment_cache())
    return rep


def _lint_static(build):
    import paddle_tpu as paddle
    from paddle_tpu import static

    paddle.enable_static()
    try:
        main, startup = static.Program(), static.Program()
        with static.program_guard(main, startup):
            feed, fetch = build(static)
        exe = static.Executor()
        exe.run(startup)
        return exe.analyze_program(main, feed=feed, fetch_list=fetch)
    finally:
        paddle.disable_static()


def lint_bert():
    """Static BERT MLM step (AMP bf16) — exercises the executor path."""
    import paddle_tpu as paddle
    from paddle_tpu import optimizer
    from paddle_tpu.models import BertConfig, BertForMaskedLM

    B, S = 4, 64
    rng = np.random.default_rng(1)

    def build(static):
        ids = static.data("ids", [B, S], "int64")
        labels = static.data("labels", [B, S], "int64")
        model = BertForMaskedLM(BertConfig(
            hidden_size=128, num_hidden_layers=2, num_attention_heads=2,
            intermediate_size=256))
        with paddle.amp.auto_cast(dtype="bfloat16", level="O1"):
            loss, _ = model(ids, labels=labels)
        opt = optimizer.AdamW(learning_rate=1e-4,
                              parameters=model.parameters())
        opt.minimize(loss)
        feed = {"ids": rng.integers(0, 1000, (B, S)).astype(np.int64),
                "labels": rng.integers(0, 1000, (B, S)).astype(np.int64)}
        return feed, [loss]

    return _lint_static(build)


def lint_gpt():
    """Static GPT causal-LM step (AMP bf16 + recompute)."""
    import paddle_tpu as paddle
    from paddle_tpu import optimizer
    from paddle_tpu.models import (GPTConfig, GPTForCausalLM,
                                   GPTPretrainingCriterion)

    B, S = 4, 64
    rng = np.random.default_rng(2)

    def build(static):
        ids = static.data("ids", [B, S], "int64")
        labels = static.data("labels", [B, S], "int64")
        model = GPTForCausalLM(GPTConfig(
            vocab_size=256, hidden_size=128, num_hidden_layers=2,
            num_attention_heads=2, use_flash_attention=False,
            use_recompute=True, max_position_embeddings=128))
        criterion = GPTPretrainingCriterion()
        with paddle.amp.auto_cast(dtype="bfloat16", level="O1"):
            loss = criterion(model(ids), labels)
        opt = optimizer.AdamW(learning_rate=1e-4,
                              parameters=model.parameters())
        opt.minimize(loss)
        feed = {"ids": rng.integers(0, 256, (B, S)).astype(np.int64),
                "labels": rng.integers(0, 256, (B, S)).astype(np.int64)}
        return feed, [loss]

    return _lint_static(build)


def lint_moe():
    """MoE subsystem lint: measured routing balance of the bundled
    moe_gpt at init (TPU508), capacity headroom of the incubate
    capacity router at that measured skew (TPU507), and the grouped
    expert matmul's block plans vs the Mosaic tiling rules — all
    CPU-only, no expert matmul is launched for the plan checks."""
    import jax.numpy as jnp
    import paddle_tpu as paddle
    from paddle_tpu import analysis
    from paddle_tpu.analysis.diagnostics import DiagnosticReport, record
    from paddle_tpu.analysis.moe_audit import (audit_expert_capacity,
                                               audit_routing_balance)
    from paddle_tpu.models import MoEGPTConfig, MoEGPTForCausalLM
    from paddle_tpu.models.moe_gpt import _moe_mlp_compute
    from paddle_tpu.ops.pallas_grouped import grouped_block_rows

    paddle.disable_static()
    paddle.seed(0)
    cfg = MoEGPTConfig(vocab_size=256, hidden_size=128,
                       num_hidden_layers=2, num_attention_heads=2,
                       use_flash_attention=False,
                       max_position_embeddings=128,
                       num_experts=4, top_k=2)
    model = MoEGPTForCausalLM(cfg)
    report = DiagnosticReport(label="moe routing + grouped plans")
    rng = np.random.default_rng(3)
    tokens = 512
    x = jnp.asarray(rng.standard_normal(
        (tokens, cfg.hidden_size)).astype(np.float32))
    bm = grouped_block_rows(tokens * cfg.top_k, cfg.num_experts,
                            jnp.float32)
    worst = 1.0
    for i, blk in enumerate(model.gpt.h):
        mlp = blk.mlp
        _, _, counts = _moe_mlp_compute(
            x, mlp.router._value, mlp.w1._value, mlp.b1._value,
            mlp.w2._value, mlp.b2._value, top_k=cfg.top_k,
            num_experts=cfg.num_experts, act="gelu_tanh")
        counts = np.asarray(counts)
        worst = max(worst, counts.max() / max(counts.mean(), 1.0))
        audit_routing_balance(counts, block_rows=bm,
                              site=f"moe_gpt.h.{i}.mlp",
                              report=report)
    # the incubate capacity router at its default factor must hold the
    # skew the bundled router actually shows at init
    cap = max(int(1.2 * tokens * cfg.top_k / cfg.num_experts), 1)
    audit_expert_capacity(tokens, cfg.num_experts, cfg.top_k, cap,
                          imbalance=worst,
                          site="incubate.moe_layer[capacity_factor=1.2]",
                          report=report)
    for dtype in (jnp.float32, jnp.bfloat16):
        for direction in ("fwd", "bwd_dw"):
            r = analysis.audit_grouped_matmul(
                1024, 768, 3072, 8, dtype=dtype, direction=direction)
            report.extend(r.diagnostics)
    for d in report.diagnostics:
        record(d)
    return report


def lint_lora():
    """Multi-LoRA serving lint: the planned tenant mix replayed through
    the adapter store's LRU policy (TPU509), the configured rank vs the
    stack dtype's sublane floor (TPU510), and the segmented SGMV
    epilogue's block plans vs the Mosaic tiling rules — all CPU-only,
    no kernel launch and no model build."""
    import jax.numpy as jnp
    from paddle_tpu import analysis
    from paddle_tpu.analysis.diagnostics import DiagnosticReport, record
    from paddle_tpu.analysis.lora_audit import (audit_adapter_working_set,
                                                audit_lora_rank)

    report = DiagnosticReport(label="lora store + sgmv plans")
    # the bench's serving shape: Zipf tenant mix over a pool sized to
    # the default (num_slots = max_batch); must not thrash
    rng = np.random.default_rng(0)
    trace = [f"t{min(int(z), 63)}" for z in rng.zipf(1.3, 512)]
    audit_adapter_working_set(trace, 16, site="bench.gpt_multilora",
                              report=report)
    for dtype in (jnp.float32, jnp.bfloat16):
        audit_lora_rank(16, dtype, site=f"lora.rank[{jnp.dtype(dtype).name}]",
                        report=report)
        for direction in ("fwd", "bwd_dw"):
            r = analysis.audit_lora_sgmv(
                1024, 768, 3072, 16, 64, dtype=dtype, direction=direction)
            report.extend(r.diagnostics)
        # the serving epilogue rides the engine's ragged q-block height
        r = analysis.audit_lora_sgmv(
            256, 768, 768, 16, 64, dtype=dtype,
            block_rows=16 if jnp.dtype(dtype).itemsize == 2 else 8)
        report.extend(r.diagnostics)
    for d in report.diagnostics:
        record(d)
    return report


def lint_pallas():
    """Fused-suite block plans vs the Mosaic tiling rules: flash
    attention (fwd + both backward passes), layernorm+residual (plain
    and with its dropout drawn in-kernel) and matmul-epilogue fusion
    (fwd + bwd, float and int8-weight), ragged mixed prefill+decode
    attention (float and int8 KV)."""
    import jax.numpy as jnp
    from paddle_tpu import analysis
    from paddle_tpu.analysis.diagnostics import DiagnosticReport, record

    report = DiagnosticReport(label="pallas block plans")
    for dtype in (jnp.float32, jnp.bfloat16):
        for seq in (64, 128, 1024):
            for direction in ("fwd", "bwd_dq", "bwd_dkv"):
                r = analysis.audit_flash_attention(
                    batch=1, seq_q=seq, seq_k=seq, heads=4, head_dim=64,
                    dtype=dtype, causal=True, direction=direction)
                report.extend(r.diagnostics)
        for direction in ("fwd", "bwd"):
            for dropout in (False, True):
                r = analysis.audit_layer_norm_residual(
                    8192, 768, dtype=dtype, direction=direction,
                    dropout=dropout)
                report.extend(r.diagnostics)
            r = analysis.audit_matmul_epilogue(
                512, 768, 3072, dtype=dtype, direction=direction)
            report.extend(r.diagnostics)
            r = analysis.audit_matmul_epilogue(
                512, 768, 3072, dtype=dtype, direction=direction,
                weight_dtype=jnp.int8)
            report.extend(r.diagnostics)
    for dtype in (jnp.float32, jnp.bfloat16):
        r = analysis.audit_ragged_attention(num_heads=8, head_dim=64,
                                            block_size=16,
                                            num_q_blocks=8,
                                            num_blocks=64,
                                            dtype=dtype)
        report.extend(r.diagnostics)
        r = analysis.audit_ragged_attention(num_heads=8, head_dim=64,
                                            block_size=16,
                                            num_q_blocks=8,
                                            num_blocks=64,
                                            dtype=dtype,
                                            kv_dtype=jnp.int8)
        report.extend(r.diagnostics)
    for d in report.diagnostics:
        record(d)
    return report


def lint_sharding():
    """Partition-rule coverage for the built-in BERT/GPT rule sets on
    virtual meshes (TPU501/502) — no multi-device runtime needed.

    Builds each bundled model dygraph, stamps structural param names
    (``annotate_params``), and audits the inventory against virtual
    ``dp=2,tp=2`` and ``fsdp=2`` MeshPlans: a param no rule matches is
    TPU501; a large param the plan leaves replicated under a model-
    parallel mesh is TPU502; a TP matmul weight whose collective can't
    overlap with compute (ragged token tiling or overlap forced off)
    is TPU504."""
    import paddle_tpu as paddle
    from paddle_tpu.analysis.diagnostics import DiagnosticReport, record
    from paddle_tpu.analysis.sharding_audit import (audit_overlap,
                                                    audit_sharding)
    from paddle_tpu.distributed.auto_parallel.sharding import (
        BERT_RULES, GPT_RULES, MeshPlan, annotate_params)
    from paddle_tpu.models import (BertConfig, BertForMaskedLM,
                                   GPTConfig, GPTForCausalLM)

    paddle.disable_static()
    paddle.seed(0)
    builds = {
        "bert": (BERT_RULES(), lambda: BertForMaskedLM(BertConfig(
            hidden_size=128, num_hidden_layers=2, num_attention_heads=2,
            intermediate_size=256))),
        "gpt": (GPT_RULES(), lambda: GPTForCausalLM(GPTConfig(
            vocab_size=256, hidden_size=128, num_hidden_layers=2,
            num_attention_heads=2, use_flash_attention=False,
            max_position_embeddings=128))),
    }
    report = DiagnosticReport(label="sharding rules")
    for model_name, (rules, build) in builds.items():
        named = annotate_params(build())
        inventory = [(name, tuple(p.shape),
                      int(getattr(p._value, "nbytes", 0)))
                     for name, p in named.items()]
        for mesh_spec in ("dp=2,tp=2", "fsdp=2"):
            plan = MeshPlan(mesh_spec, rules=rules, virtual=True)
            diags = audit_sharding(
                plan, inventory,
                site=f"{model_name}[{mesh_spec}]")
            # hot-path tokens per device step for the bundled minis:
            # batch 8 x seq 16, divisible by every tp tile count here,
            # so a TPU504 means a rule/flag regression, not the hint
            diags += audit_overlap(
                plan, inventory, tokens_hint=128,
                site=f"{model_name}[{mesh_spec}]")
            for d in diags:
                record(d)
            report.extend(diags)
    return report


def lint_fabric():
    """Cross-host KV handoff geometry vs the decode window (TPU506) —
    pure arithmetic over the *configured* serving geometry (block size
    and prefill chunk from the env knobs), no engine, no fabric.

    Audits representative handoff payloads for a GPT-2-class decode
    replica in both f32 and int8 KV: a single-chunk handoff (the
    steady-state disaggregated case) must hide behind the decode
    window; the full-prompt failover spill is checked at 4x that size
    so a geometry that only hides the happy path still surfaces."""
    from paddle_tpu.analysis.fabric_audit import (audit_fabric_handoff,
                                                  handoff_bytes_per_block)
    from paddle_tpu.analysis.diagnostics import DiagnosticReport
    from paddle_tpu.inference.serving import (kv_block_size,
                                              prefill_chunk_size)

    block = kv_block_size()
    chunk = prefill_chunk_size()
    layers, heads, head_dim = 12, 12, 64
    report = DiagnosticReport(label="fabric handoff")
    for kv, itemsize, lanes in (("f32", 4, 0), ("int8", 1, heads)):
        bpb = handoff_bytes_per_block(layers, heads, block, head_dim,
                                      itemsize, scale_lanes=lanes)
        # steady state: one admission chunk's worth of blocks in flight
        chunk_blocks = max(1, chunk // block)
        audit_fabric_handoff(chunk_blocks, bpb, chunk, block,
                             site=f"gpt[{kv}] chunk handoff",
                             report=report)
        # failover spill: a long-lived request's whole prefix at once
        audit_fabric_handoff(4 * chunk_blocks, bpb, chunk, block,
                             site=f"gpt[{kv}] failover spill",
                             report=report)
    return report


def lint_faults():
    """Fault-site registry audit (TPU601/602) — every literal site the
    tree references through fault_point()/FaultEvent/FaultPlan.add or a
    compact parse()/inject() spec must match a FAULT_SITES registry
    pattern, and every registry pattern must have at least one
    fault_point() behind it.  Pure AST over paddle_tpu/, scripts/
    and tests/ — no scanned module is imported."""
    from paddle_tpu.analysis.fault_lint import audit_fault_sites
    return audit_fault_sites()


LINTERS = {"lenet": lint_lenet, "eager": lint_eager, "bert": lint_bert,
           "gpt": lint_gpt, "moe": lint_moe, "lora": lint_lora,
           "pallas": lint_pallas,
           "sharding": lint_sharding, "fabric": lint_fabric,
           "faults": lint_faults}


def run_models(names):
    from paddle_tpu.analysis.diagnostics import (Diagnostic,
                                                 DiagnosticReport, record)
    results, combined = {}, DiagnosticReport(label="tpu_lint --models")
    for name in names:
        t = time.time()
        try:
            rep = LINTERS[name]()
        except Exception as exc:  # lint must not crash the gate silently
            diag = Diagnostic(
                code="TPU110", severity="error",
                message=f"linting {name} raised "
                        f"{type(exc).__name__}: {exc}",
                site=f"tpu_lint:{name}",
                hint="fix the model build/trace before trusting the "
                     "lint result for this model")
            record(diag)
            rep = DiagnosticReport(label=name)
            rep.add(diag)
        results[name] = rep
        combined.extend(rep.diagnostics)
        print(f"[tpu_lint] {name}: {len(rep.diagnostics)} finding(s), "
              f"{len(rep.errors())} error(s)  "
              f"({time.time() - t:.1f}s)", file=sys.stderr)
    return results, combined


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--models", action="store_true",
                    help="lint the bundled models (lenet, bert, gpt) "
                         "and the Pallas block plans")
    ap.add_argument("--only", default=",".join(MODELS),
                    help="comma-separated subset of: %s" % (MODELS,))
    ap.add_argument("--fail-on", default="error",
                    choices=("error", "warning", "never"),
                    help="exit 1 when a diagnostic at/above this "
                         "severity is found (default: error)")
    ap.add_argument("--json", action="store_true",
                    help="print machine-readable JSON instead of text")
    args = ap.parse_args(argv)

    if not args.models:
        ap.error("nothing to do: pass --models")
    names = [n.strip() for n in args.only.split(",") if n.strip()]
    unknown = [n for n in names if n not in LINTERS]
    if unknown:
        ap.error(f"unknown model(s) {unknown}; choose from {MODELS}")

    results, combined = run_models(names)

    if args.json:
        print(json.dumps({
            "models": {n: [d.to_dict() for d in r]
                       for n, r in results.items()},
            "counts": combined.counts(),
            "ok": combined.ok(fail_on=args.fail_on),
        }, indent=2, default=str))
    else:
        for name in names:
            print(results[name].render())
        counts = combined.counts()
        tally = ", ".join(f"{c}×{k}" for k, c in sorted(counts.items()))
        print(f"tpu_lint: {len(combined.diagnostics)} finding(s)"
              + (f" ({tally})" if tally else ""))

    return 0 if combined.ok(fail_on=args.fail_on) else 1


if __name__ == "__main__":
    sys.exit(main())
