#!/usr/bin/env python
"""Serving chaos smoke: kill/hang/starve the fleet, demand bit-parity.

    python scripts/chaos_smoke.py [--seed N] [--requests N]

Drives a 2-replica :class:`DataParallelEngine` through the seeded
fault-injection plans of ``fault_tolerance/plan.py`` and validates the
serving fault-tolerance story end to end:

  * **replica kill mid-burst** (the acceptance criterion): killing 1 of
    2 replicas halfway through a shared-prefix burst completes EVERY
    request with outputs bit-identical to a no-fault run — greedy and
    seeded sampling — with ``replays > 0`` recorded and the replayed
    prefills hitting the surviving replica's prefix cache;
  * **hung step**: an injected stall trips the decode watchdog
    (``ServingStepTimeout``), the batch rolls back through the
    refcount-aware truncate/requeue path, and the run still finishes
    bit-identical;
  * **admission alloc failure**: injected allocation faults leak no
    blocks (pool physical/in-use counts return to baseline) and the
    burst still completes;
  * **overload shedding**: a queue-depth bound turns the overflow of a
    flood into structured 429-style rejections while everything
    admitted completes;
  * **cluster fabric kill + preemption** (separate
    ``CLUSTER_SCENARIOS`` registry, subprocess on a forced 8-device
    host mesh): a 4-host :class:`ClusterRouter` burst survives a hard
    host kill (harvest + replay, bit-identical) AND a preemption
    notice (graceful drain: KV ships over the fabric transport and
    the transfer hides behind decode — ``fabric_hidden_ratio > 0``),
    with exactly-once streams, zero lost requests, zero leaked blocks
    on surviving pools, and the attached ``dp=8`` mesh plan shrunk;
    plus a **control-plane outage** phase: the rendezvous store master
    is killed mid-burst (with one host partitioned away from it), a
    standby is promoted (``ResilientStore`` epoch fence), routing
    rides its cached digests (degraded mode) and a stale pre-outage
    lease is rejected with ``StoreEpochError`` — greedy AND seeded
    runs stay bit-identical to fault-free;
  * **device lost mid-training** (separate ``TRAIN_SCENARIOS``
    registry, subprocess on a forced 8-device host mesh): an injected
    ``dist.device_lost`` kill triggers mesh shrink dp 4->2, async
    snapshot restore, and a resume bit-identical to a clean run from
    the same checkpoint on the shrunk mesh, leaking no pipeline
    buffers or staging bytes.

``run()`` / ``run_training()`` return ``(ok, report)`` for the tier-1
gate tests; the CLI runs both registries, prints a PASS/FAIL line per
scenario and exits 0 iff all pass.  CPU-only, no TPU required.
"""
import argparse
import contextlib
import logging
import os
import sys
import time
import traceback

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import numpy as np  # noqa: E402

import paddle_tpu as paddle  # noqa: E402
from paddle_tpu.inference.serving import (DataParallelEngine,  # noqa: E402
                                          GenerationEngine,
                                          RequestRejected,
                                          ServingStepTimeout)
from paddle_tpu.models import GPTConfig, GPTForCausalLM  # noqa: E402
from paddle_tpu.distributed.fault_tolerance import (FaultPlan,  # noqa: E402
                                                    inject)

SCENARIOS = []
VOCAB = 97


def scenario(name):
    def deco(fn):
        SCENARIOS.append((name, fn))
        return fn
    return deco


def build_model(seed):
    paddle.seed(seed)
    cfg = GPTConfig(vocab_size=VOCAB, hidden_size=32,
                    num_hidden_layers=2, num_attention_heads=4,
                    max_position_embeddings=64)
    model = GPTForCausalLM(cfg)
    model.eval()
    return model


def shared_prefix_prompts(seed, n):
    """A burst sharing one 16-token system prompt (2 full 8-tok blocks)
    with short per-request tails — the shape that makes failover replay
    a prefix-cache hit on the survivor."""
    rng = np.random.RandomState(seed)
    shared = list(rng.randint(1, VOCAB, size=16))
    return [shared + list(rng.randint(1, VOCAB, size=2 + i % 4))
            for i in range(n)]


def _dp_engine(model):
    return DataParallelEngine(model, dp=2, num_blocks=128, max_batch=4,
                              block_size=8, max_model_len=64)


@scenario("replica kill mid-burst: bit-identical, replays hit the "
          "survivor's prefix cache")
def _replica_kill(args, report):
    model = build_model(args.seed)
    prompts = shared_prefix_prompts(args.seed, args.requests)
    for label, kwargs in (("greedy", {}),
                          ("seeded", {"do_sample": True, "seed": 11,
                                      "top_k": 20, "temperature": 0.8})):
        ref = _dp_engine(model)
        try:
            want = ref.generate(prompts, max_new_tokens=8, **kwargs)
        finally:
            ref.close()
        plan = FaultPlan.parse(
            "serve.replica_down.dp0:kill:after=2,count=1")
        dp = _dp_engine(model)
        try:
            with inject(plan):
                got = dp.generate(prompts, max_new_tokens=8, **kwargs)
            s = dp.stats()
        finally:
            dp.close()
        assert got == want, f"{label}: outputs diverge after failover"
        assert s["failovers"] >= 1, f"{label}: no failover recorded"
        assert s["replays"] > 0, f"{label}: no replays recorded"
        hit = s["per_shard"]["dp1"]["prefix_hit_rate"]
        assert hit > 0, (
            f"{label}: replayed prefills missed the survivor's prefix "
            f"cache (hit rate {hit})")
        assert s["replica_health"]["dp0"]["state"] != "healthy"
        report[f"kill_{label}"] = {
            "replays": s["replays"], "failovers": s["failovers"],
            "survivor_prefix_hit_rate": round(hit, 4)}


@scenario("hung step: watchdog timeout -> rollback/requeue -> "
          "bit-identical finish")
def _hung_step(args, report):
    model = build_model(args.seed)
    prompts = shared_prefix_prompts(args.seed + 1, 4)
    ref = GenerationEngine(model, num_blocks=128, max_batch=4,
                           block_size=8, max_model_len=64)
    try:
        want = ref.generate(prompts, max_new_tokens=6)
    finally:
        ref.close()
    eng = GenerationEngine(model, num_blocks=128, max_batch=4,
                           block_size=8, max_model_len=64,
                           step_deadline_ms=250.0)
    plan = FaultPlan.parse(
        "serve.step_hang:stall:after=3,count=1,delay=0.5")
    try:
        ids = [eng.add_request(p, max_new_tokens=6) for p in prompts]
        timeouts = 0
        with inject(plan):
            while eng.has_unfinished():
                try:
                    eng.step()
                except ServingStepTimeout as e:
                    timeouts += 1
                    assert e.elapsed_ms > e.deadline_ms
                    assert e.requests, "timeout rolled back nothing"
        got = [eng.result(i) for i in ids]
        s = eng.stats()
    finally:
        eng.close()
    assert timeouts >= 1, "injected stall never tripped the watchdog"
    assert got == want, "outputs diverge after watchdog rollback"
    assert s["blocks_in_use"] == 0, "rollback leaked KV blocks"
    report["hang"] = {"timeouts": timeouts,
                      "step_timeouts": s["step_timeouts"]}


@scenario("admission alloc fault: no leaked blocks, burst completes")
def _alloc_fail(args, report):
    model = build_model(args.seed)
    prompts = shared_prefix_prompts(args.seed + 2, 4)
    eng = GenerationEngine(model, num_blocks=128, max_batch=4,
                           block_size=8, max_model_len=64)
    try:
        base = eng.cache.stats()
        plan = FaultPlan.parse("serve.alloc_fail:oom:after=0,count=3")
        ids = [eng.add_request(p, max_new_tokens=4) for p in prompts]
        with inject(plan):
            while eng.has_unfinished():
                eng.step()
        got = [eng.result(i) for i in ids]
        s = eng.cache.stats()
        fails = eng.stats()["alloc_fails"]
    finally:
        eng.close()
    assert fails >= 3, f"only {fails} alloc faults fired (want 3)"
    assert all(len(g) > 0 for g in got)
    assert s["physical_blocks"] == base["physical_blocks"], (
        "alloc fault changed the physical block count")
    assert s["blocks_in_use"] == base["blocks_in_use"], (
        f"leaked blocks: {s['blocks_in_use']} in use after drain "
        f"(baseline {base['blocks_in_use']})")
    report["alloc_fail"] = {"alloc_fails": fails,
                            "blocks_in_use": s["blocks_in_use"]}


@scenario("overload: shed bound returns structured rejections, "
          "admitted work completes")
def _shed(args, report):
    model = build_model(args.seed)
    prompts = shared_prefix_prompts(args.seed + 3, 12)
    eng = GenerationEngine(model, num_blocks=128, max_batch=2,
                           block_size=8, max_model_len=64,
                           shed_depth=3)
    try:
        admitted, rejections = [], []
        for p in prompts:
            try:
                admitted.append(eng.add_request(p, max_new_tokens=4))
            except RequestRejected as e:
                resp = e.to_response()
                assert resp["code"] == 429
                assert resp["reason"] == "overloaded"
                assert resp["queue_depth"] >= resp["shed_depth"]
                rejections.append(resp)
        while eng.has_unfinished():
            eng.step()
        got = [eng.result(i) for i in admitted]
        shed = eng.stats()["shed_requests"]
    finally:
        eng.close()
    assert rejections, "flood never tripped the shed bound"
    assert shed == len(rejections)
    assert all(len(g) > 0 for g in got), "admitted request lost"
    report["shed"] = {"admitted": len(admitted),
                      "rejected": len(rejections)}


# ---------------------------------------------------------------------
# Cluster chaos: the multi-host fabric drill (ClusterRouter over 4
# hosts).  A separate registry so the serving gate pays only for the
# single-process drills and the cluster gate runs this one in a
# subprocess on a forced 8-device host mesh (so mesh-plan shrink is
# exercised with real devices, like the PR-15 elastic drill).
# ---------------------------------------------------------------------
CLUSTER_SCENARIOS = []


def cluster_scenario(name):
    def deco(fn):
        CLUSTER_SCENARIOS.append((name, fn))
        return fn
    return deco


def _check_streams(events, got, prompts):
    """Exactly-once streaming despite at-least-once replay: contiguous
    indices from 0, no duplicates, one terminal marker, and the
    streamed tokens byte-equal the final completion."""
    for k, (rid, evs) in enumerate(sorted(events.items())):
        toks = [(e.index, e.token) for e in evs if e.index >= 0]
        idx = [i for i, _ in toks]
        assert idx == sorted(set(idx)), f"{rid}: duplicate stream index"
        assert idx == list(range(len(idx))), f"{rid}: stream gap {idx}"
        finals = [e for e in evs if e.finished]
        assert len(finals) == 1, (
            f"{rid}: {len(finals)} terminal events (want exactly 1)")
        tail = got[k][len(prompts[k]):]
        assert [t for _, t in toks] == tail, (
            f"{rid}: streamed tokens diverge from the completion")


def run_cluster_drill(seed=7, requests=8):
    """Inner body of the cluster drill: a 4-host ClusterRouter under a
    hard host kill (greedy burst) and a preemption notice (seeded
    burst), each demanding bit-parity with a single-engine reference —
    the cluster's outputs are schedule-independent because sampling is
    keyed by fold_in(seed, absolute position).  Returns a JSON-able
    report; every assertion failure surfaces as ``ok: False``."""
    import jax
    from paddle_tpu import observability as obs
    from paddle_tpu.observability import phase_breakdown
    from paddle_tpu.inference.serving import ClusterRouter
    from paddle_tpu.distributed.auto_parallel.sharding import MeshPlan

    obs.enable(True)
    model = build_model(seed)
    prompts = shared_prefix_prompts(seed, requests)
    rep = {"ok": True}

    def reference(**kw):
        eng = GenerationEngine(model, num_blocks=128, max_batch=4,
                               block_size=8, max_model_len=64)
        try:
            return eng.generate(prompts, max_new_tokens=8, **kw)
        finally:
            eng.close()

    def cluster_run(plan_str, store=None, **kw):
        devs = jax.devices()
        mesh_plan = MeshPlan("dp=8", devices=devs) \
            if len(devs) >= 8 else None
        obs.get_timeline().clear()
        cl = ClusterRouter(model, hosts=4, num_blocks=64, max_batch=4,
                           block_size=8, max_model_len=64,
                           mesh_plan=mesh_plan, store=store)
        events = {}
        try:
            ids = [cl.add_request(p, max_new_tokens=8, **kw)
                   for p in prompts]
            streams = {r: cl.open_stream(r) for r in ids}
            ctx = inject(FaultPlan.parse(plan_str)) if plan_str \
                else contextlib.nullcontext()
            with ctx:
                while cl.has_unfinished():
                    cl.step()
                    for r, st in streams.items():
                        events.setdefault(r, []).extend(st.drain())
            for r, st in streams.items():
                events[r].extend(st.drain())
            got = [cl.result(r) for r in ids]
            stats = cl.stats()
            mesh_after = cl.mesh_plan.describe() if cl.mesh_plan \
                else None
            pb = phase_breakdown()
        finally:
            cl.close()
        return got, stats, events, mesh_after, pb

    # hard kill mid-burst: host0's HBM (and KV) is gone; harvest +
    # replay on the survivors, bit-identical, zero lost requests
    want_greedy = reference()
    got, s, events, mesh_after, _ = cluster_run(
        "fabric.host_down.h0:kill:after=1,count=100")
    assert got == want_greedy, \
        "host kill: outputs diverge from no-kill run"
    assert s["failovers"] >= 1 and s["replays"] > 0, s
    assert s["replica_health"]["host0"]["state"] != "healthy"
    _check_streams(events, got, prompts)
    survivors_in_use = sum(
        h["blocks_in_use"] for name, h in s["per_host"].items()
        if name != "host0")
    assert survivors_in_use == 0, (
        f"leaked {survivors_in_use} blocks on surviving pools")
    rep["kill"] = {"failovers": s["failovers"], "replays": s["replays"],
                   "hosts_active": s["hosts_active"],
                   "ttft_p99_ms": round(s["ttft_p99_ms"], 3),
                   "mesh_after": mesh_after}

    # preemption notice mid-burst (seeded sampling): the host drains
    # gracefully — decodable KV ships over the fabric transport, the
    # transfer hides behind the survivors' decode steps
    kw = {"do_sample": True, "seed": 11, "top_k": 20,
          "temperature": 0.8}
    want_seeded = reference(**kw)
    got, s, events, mesh_after, pb = cluster_run(
        "fabric.preempt.h1:kill:after=2,count=1", **kw)
    assert got == want_seeded, \
        "preempt: outputs diverge from no-fault run"
    assert s["preemptions"] >= 1 and s["scale_downs"] >= 1, s
    assert s["hosts_active"] == 3, s["hosts_active"]
    _check_streams(events, got, prompts)
    assert s["blocks_in_use"] == 0, (
        f"leaked {s['blocks_in_use']} blocks after preemption drain")
    assert pb.get("fabric_bytes", 0) > 0, (
        "preemption drain shipped nothing over the fabric")
    assert pb.get("fabric_hidden_ratio", 0) > 0, (
        "fabric transfer never overlapped decode dispatch")
    rep["preempt"] = {
        "ttft_p99_ms": round(s["ttft_p99_ms"], 3),
        "preemptions": s["preemptions"],
        "scale_downs": s["scale_downs"],
        "hosts_active": s["hosts_active"],
        "fabric_bytes": pb["fabric_bytes"],
        "fabric_hidden_ratio": pb["fabric_hidden_ratio"],
        "cluster_failover_ms": pb.get("cluster_failover_ms"),
        "mesh_after": mesh_after}

    # control-plane outage mid-burst: the rendezvous store master is
    # killed while host3 is also partitioned away from it.  A standby
    # is promoted (epoch bumps), routing keeps serving on cached
    # digests (degraded mode — hints only, never answers), and a lease
    # from the dead epoch can never write again (split-brain fence).
    from paddle_tpu.distributed.store import (ResilientStore,
                                              StoreEpochError)
    outage_plan = ("store.master_down:kill:after=2,count=1;"
                   "store.partition.h3:drop:after=0,count=6")

    t0 = time.perf_counter()
    got, s, events, _, _ = cluster_run(None)  # fault-free baseline
    baseline_ms = (time.perf_counter() - t0) * 1e3
    assert got == want_greedy, "baseline cluster run diverged"

    outage = {}
    for label, want, skw in (("greedy", want_greedy, {}),
                             ("seeded", want_seeded, kw)):
        store = ResilientStore(timeout=1.0)
        stale = store.acquire_lease(owner="pre-outage-writer")
        try:
            t0 = time.perf_counter()
            got, s, events, _, pb = cluster_run(outage_plan,
                                                store=store, **skw)
            outage_ms = (time.perf_counter() - t0) * 1e3
            assert got == want, (
                f"store outage ({label}): outputs diverge from "
                "fault-free run")
            _check_streams(events, got, prompts)
            assert s["blocks_in_use"] == 0, (
                f"leaked {s['blocks_in_use']} blocks through the "
                "outage")
            assert store.promotions >= 1 and store.epoch() >= 2, (
                store.stats())
            assert s["degraded_events"] >= 1 and s["degraded_ms"] > 0, s
            assert "degraded_ms" in pb, (
                "degraded lane missing from phase_breakdown()")
            try:
                store.set("__outage_probe__", b"x", lease=stale)
                raise AssertionError(
                    "stale pre-outage lease wrote past the epoch "
                    "fence")
            except StoreEpochError:
                pass
            outage[label] = {
                "wall_ms": round(outage_ms, 1),
                "stall_ms": round(max(0.0, outage_ms - baseline_ms), 1),
                "degraded_ms": round(s["degraded_ms"], 1),
                "degraded_ratio": round(
                    min(1.0, s["degraded_ms"] / outage_ms), 4),
                "degraded_events": s["degraded_events"],
                "fenced_writes": s["fenced_writes"],
                "promotions": store.promotions,
                "epoch": store.epoch()}
        finally:
            store.close()
    rep["store_outage"] = {"baseline_ms": round(baseline_ms, 1),
                           **outage["greedy"],
                           **{f"seeded_{k}": v
                              for k, v in outage["seeded"].items()}}
    return rep


_CLUSTER_DRILL_SUB = r"""
import os, sys, json
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=8")
import jax
jax.config.update("jax_platforms", "cpu")
sys.path.insert(0, %ROOT%)
import importlib.util
spec = importlib.util.spec_from_file_location(
    "chaos_smoke_sub", os.path.join(%ROOT%, "scripts", "chaos_smoke.py"))
mod = importlib.util.module_from_spec(spec)
spec.loader.exec_module(mod)
print("CLUSTER_DRILL_JSON: " +
      json.dumps(mod.run_cluster_drill(seed=%SEED%), default=str))
"""


@cluster_scenario("cluster fabric: host kill + preemption drain over "
                  "4 hosts, bit-identical, exactly-once streams")
def _cluster_kill_preempt(args, report):
    import json
    import subprocess
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "JAX_PLATFORMS")}
    src = (_CLUSTER_DRILL_SUB
           .replace("%ROOT%", repr(root))
           .replace("%SEED%", str(args.seed)))
    p = subprocess.run([sys.executable, "-c", src], cwd=root,
                       capture_output=True, text=True, timeout=900,
                       env=env)
    rep = None
    for line in p.stdout.splitlines():
        if line.startswith("CLUSTER_DRILL_JSON:"):
            rep = json.loads(line[len("CLUSTER_DRILL_JSON:"):])
    if rep is None:
        raise RuntimeError("cluster drill subprocess produced no "
                           "report: " + (p.stderr or "")[-800:])
    assert rep["ok"], rep
    assert rep["kill"]["failovers"] >= 1
    assert rep["preempt"]["fabric_hidden_ratio"] > 0
    # the forced 8-device mesh shrank when hosts left (dp=8 -> a
    # divisor that fits the survivors' device share)
    assert rep["kill"]["mesh_after"] not in (None, "dp=8"), rep["kill"]
    # the store-outage phase promoted a standby and stayed correct
    outage = rep["store_outage"]
    assert outage["promotions"] >= 1 and outage["epoch"] >= 2, outage
    assert outage["degraded_ms"] > 0, outage
    report["cluster"] = {**rep["kill"],
                         **{f"preempt_{k}": v
                            for k, v in rep["preempt"].items()},
                         **{f"outage_{k}": v
                            for k, v in outage.items()}}


def run_cluster(seed=7):
    """Execute the cluster chaos scenarios; ``(ok, report)`` like
    :func:`run` (the tier-1 gate in tests/test_serving_faults.py)."""
    args = argparse.Namespace(seed=seed, requests=8)
    report = {}
    ok = True
    for name, fn in CLUSTER_SCENARIOS:
        try:
            fn(args, report)
        except Exception:
            ok = False
            report[f"FAIL: {name}"] = traceback.format_exc()
    return ok, report


# ---------------------------------------------------------------------
# Training chaos: a separate registry so the serving gate
# (tests/test_serving_faults.py) and the elastic-training gate
# (tests/test_elastic_train.py) each pay only for their own drills.
# ---------------------------------------------------------------------
TRAIN_SCENARIOS = []


def train_scenario(name):
    def deco(fn):
        TRAIN_SCENARIOS.append((name, fn))
        return fn
    return deco


_ELASTIC_DRILL_SUB = r"""
import os, json
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=8")
import jax
jax.config.update("jax_platforms", "cpu")
from paddle_tpu import observability as obs
obs.enable(True)
from paddle_tpu.distributed.elastic_train import run_elastic_drill
print("ELASTIC_DRILL_JSON: " + json.dumps(run_elastic_drill(seed=%SEED%),
                                          default=str))
"""


@train_scenario("device lost mid-training: shrink dp 4->2, restore, "
                "resume bit-identical to clean-from-checkpoint")
def _elastic_device_lost(args, report):
    import json
    import subprocess
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "JAX_PLATFORMS")}
    p = subprocess.run(
        [sys.executable, "-c",
         _ELASTIC_DRILL_SUB.replace("%SEED%", str(args.seed))],
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        capture_output=True, text=True, timeout=900, env=env)
    rep = None
    for line in p.stdout.splitlines():
        if line.startswith("ELASTIC_DRILL_JSON:"):
            rep = json.loads(line[len("ELASTIC_DRILL_JSON:"):])
    if rep is None:
        raise RuntimeError("elastic drill subprocess produced no "
                           "report: " + (p.stderr or "")[-800:])
    phases = rep.get("phases", {})
    assert rep["ok"], f"drill not ok: {rep}"
    assert rep["parity"], f"resume NOT bit-identical: {rep}"
    assert rep["mesh_after"] == "dp=2", rep["mesh_after"]
    assert rep["restarts"] == 1 and rep["lost_steps"] >= 1, rep
    assert rep["window_len"] == 0, "leaked in-flight pipeline buffers"
    assert not rep["leaked_host_items"], "leaked snapshot staging bytes"
    assert rep["mttr_ms"], "elastic.mttr_ms not populated"
    assert phases.get("recovery_count", 0) >= 1, phases
    assert phases.get("ckpt_count", 0) >= 1, phases
    report["elastic_device_lost"] = {
        "mesh": f"{rep['mesh_before']} -> {rep['mesh_after']}",
        "resume_step": rep["resume_step"],
        "replayed_steps": rep["replayed_steps"],
        "lost_steps": rep["lost_steps"],
        "mttr_ms": rep["mttr_ms"],
        "recovery_to_first_step_ms": rep["recovery_to_first_step_ms"],
        "recovery_ms": phases.get("recovery_ms"),
        "ckpt_ms": phases.get("ckpt_ms")}


def run_training(seed=7):
    """Execute the training chaos scenarios; ``(ok, report)`` like
    :func:`run` (the tier-1 gate in tests/test_elastic_train.py)."""
    args = argparse.Namespace(seed=seed, requests=0)
    report = {}
    ok = True
    for name, fn in TRAIN_SCENARIOS:
        try:
            fn(args, report)
        except Exception:
            ok = False
            report[f"FAIL: {name}"] = traceback.format_exc()
    return ok, report


def run(seed=7, requests=6):
    """Execute every chaos scenario; returns ``(ok, report)`` where
    ``report`` maps scenario keys to recorded evidence (replay counts,
    hit rates, rejection counts) plus per-scenario errors on failure."""
    args = argparse.Namespace(seed=seed, requests=requests)
    report = {}
    ok = True
    for name, fn in SCENARIOS:
        try:
            fn(args, report)
        except Exception:
            ok = False
            report[f"FAIL: {name}"] = traceback.format_exc()
    return ok, report


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--requests", type=int, default=6)
    cli = ap.parse_args()
    logging.basicConfig(level=logging.WARNING)
    failures = 0
    report = {}
    walls = []
    for name, fn in SCENARIOS + CLUSTER_SCENARIOS + TRAIN_SCENARIOS:
        args = argparse.Namespace(seed=cli.seed, requests=cli.requests)
        t0 = time.perf_counter()
        try:
            fn(args, report)
            print(f"PASS  {name}")
        except Exception:
            failures += 1
            print(f"FAIL  {name}")
            traceback.print_exc()
        walls.append((name, time.perf_counter() - t0))
    for k, v in report.items():
        if not str(k).startswith("FAIL"):
            print(f"      {k}: {v}")
    total = (len(SCENARIOS) + len(CLUSTER_SCENARIOS)
             + len(TRAIN_SCENARIOS))
    print("\nper-scenario wall time:")
    for name, wall in sorted(walls, key=lambda kv: -kv[1]):
        print(f"  {wall:8.1f}s  {name}")
    print(f"  {sum(w for _, w in walls):8.1f}s  TOTAL")
    print(f"\nchaos smoke: {total - failures}/{total} scenarios passed "
          f"(seed={cli.seed})")
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
