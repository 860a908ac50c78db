"""Compile `chip_smoke.py`'s step programs, at its real sizes, for a
described (not attached) TPU v5e:2x2 — the third rehearsal of the
`on-chip-measurement` guide.  Nothing runs; no chip time is spent.

    JAX_PLATFORMS=cpu python scripts/aot_check_smoke.py [name ...]

Programs: ``static`` (BERT-base train step, one chip), ``loop`` (its
``run_steps`` loop), ``mesh`` (the same step under dp=2,tp=2 over the
four described chips), ``serve`` (the GPT engine's unified step),
``serve_afmoe`` (the engine's step for `benchmarks/configs/Trinity-
Mini.json` at the cell's geometry, depth cut to a dense, a sliding
expert and a full expert layer: both groups of block tables, the
grouped expert kernel at 128 x 2048 x 1024, the 200,192-word head),
``serve_qwen3_next`` (the same for `benchmarks/configs/Qwen3-Next-80B-
A3B-Instruct.json`, depth cut to one period: both gated delta rule
kernels, the held experts at 128 x 2048 x 512, attention at width 256),
``serve_longcat`` (the same for `benchmarks/configs/LongCat-Flash-
Omni.json`, depth cut to one double layer: two latent layers over the
latent pool, the held experts at 8 x 6144 x 2048 beside the zero
experts, the dense MLPs at 12,288),
``serve_sala`` (the same for `benchmarks/configs/MiniCPM-SALA.json`,
depth cut to two sparse and two lightning layers: the selected tables a
KV head, the pooled keys, the sparse chunk's gather).  The four engine
steps also assert that no instruction copies or transposes a whole K/V
pool (`assert_pools_stay`).
Each prints its compile seconds, the Mosaic kernels and collectives in
the compiled text, and ``memory_analysis()`` against the chip's 16 GB.

`tests/test_tpu_compile.py` keeps the kernels alone compiling in
tier-1; this is the whole-program version, minutes long, run by hand
before a chip call.  A compile that passes here is not a chip run.

The program under test asks ``jax.default_backend()`` and would take
its CPU branches, so this script steers it from outside: the kernel
gate is opened and the kernels lower through Mosaic, not interpreted.
"""
import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

import chip_smoke as cs  # noqa: E402
import paddle_tpu as paddle  # noqa: E402
from paddle_tpu import static  # noqa: E402
from paddle_tpu.distributed.auto_parallel.sharding import (  # noqa: E402
    BERT_RULES, MeshPlan, clear_mesh_plan, set_mesh_plan)
from paddle_tpu.models import BertConfig, GPTConfig  # noqa: E402
from paddle_tpu.ops import (pallas_fused, pallas_gate,  # noqa: E402
                            pallas_gated_delta, pallas_grouped,
                            pallas_kernels, pallas_latent,
                            pallas_lightning, pallas_ragged,
                            pallas_sparse, pallas_tiles)

HBM_BYTES = 16e9
BATCH, SEQ = 16, 512


def open_gate():
    """Lower the Mosaic path: every call site asks the gate by name at
    trace time, and each kernel module binds ``_interpret`` by name.
    The gate's own rule stays: under a MeshPlan only ``shard_map``
    bodies keep their kernels."""
    pallas_gate.pallas_enabled = lambda name, manual=False: (
        manual or not pallas_gate._auto_partitioned())
    for mod in (pallas_kernels, pallas_fused, pallas_ragged,
                pallas_grouped, pallas_gated_delta, pallas_lightning,
                pallas_sparse, pallas_tiles, pallas_latent):
        mod._interpret = lambda: False


def report(name, lowered, kernels=True):
    t0 = time.perf_counter()
    compiled = lowered.compile()
    secs = time.perf_counter() - t0
    text = compiled.as_text()
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    print(f"{name}: compiled in {secs:.1f}s; per-device bytes: "
          f"args {mem.argument_size_in_bytes/1e9:.2f}G "
          f"out {mem.output_size_in_bytes/1e9:.2f}G "
          f"temp {mem.temp_size_in_bytes/1e9:.2f}G "
          f"aliased {mem.alias_size_in_bytes/1e9:.2f}G "
          f"= {total/1e9:.2f}G of {HBM_BYTES/1e9:.0f}G", flush=True)
    print(f"{name}: kernels {cs.mosaic_kernels(text)}", flush=True)
    print(f"{name}: collectives "
          f"{ {op: text.count(op + '(') + text.count(op + '-start(') for op in ('all-reduce', 'all-gather', 'reduce-scatter', 'collective-permute', 'all-to-all')} }",
          flush=True)
    assert total < HBM_BYTES, f"{name} does not fit one chip"
    assert ("tpu_custom_call" in text) == kernels, (
        f"{name}: Mosaic kernels {'missing' if kernels else 'present'}")
    return text


def assert_pools_stay(name, engine, text):
    """No instruction of the step copies or transposes an array as
    large as a layer's K/V pool (the smallest group's): the scatter
    writes the pool as it lies and the ragged kernel reads it so."""
    pool = min(int(t._value.size) for layer in engine.cache._layer_pool
               for t in engine.cache.layer_pools(layer))
    moves = cs.pool_sized_moves(text, pool)
    assert not moves, f"{name}: a K/V pool is relaid in the step: {moves}"
    print(f"{name}: no copy or transpose of {pool} elements (a pool) "
          f"or more", flush=True)


def _on(sharding, avals):
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                       sharding=sharding), avals)


def static_entry(plan=None):
    paddle.enable_static()
    set_mesh_plan(plan)
    try:
        prog, loss, _ = cs.bert_program(BertConfig(), BATCH, SEQ)
        feed = cs._bert_batch(BertConfig(), BATCH, SEQ)
        return static.Executor()._build(prog, feed, [loss])
    finally:
        clear_mesh_plan()
        paddle.disable_static()


def check_static(topo, loop=False):
    entry = static_entry()
    chip = SingleDeviceSharding(topo.devices[0])
    avals = _on(chip, entry["avals"])
    fn, name = entry["pure"], "static"
    if loop:
        pure, name = entry["pure"], "loop"

        # the body of Executor.run_steps
        def fn(feed, params, opts, rngs, lr, step0, n):
            def body(i, carry):
                params, opts, rngs = carry
                _, params, opts, rngs = pure(feed, params, opts, rngs,
                                             lr, step0 + i)
                return params, opts, rngs
            params, opts, rngs = jax.lax.fori_loop(
                0, n - 1, body, (params, opts, rngs))
            return pure(feed, params, opts, rngs, lr, step0 + n - 1)
        avals += (jax.ShapeDtypeStruct((), jnp.int32, sharding=chip),)
    report(name, jax.jit(fn, donate_argnums=(1, 2)).lower(*avals))


def check_mesh(topo):
    plan = MeshPlan("dp=2,tp=2", rules=BERT_RULES(),
                    devices=topo.devices)
    entry = static_entry(plan)
    text = report("mesh", jax.jit(
        entry["pure"], donate_argnums=(1, 2),
        in_shardings=entry["in_shardings"],
        out_shardings=entry["out_shardings"]).lower(*entry["avals"]),
        kernels=False)   # XLA partitions this program: composites
    assert "all-reduce" in text, "mesh: no all-reduce"


def check_serve(topo):
    """Trace the engine's step once on the CPU at the real size (that
    is how ``to_static`` discovers its state), then re-trace the same
    pure function with the gate open and lower it for the chip."""
    pallas_gate.pallas_enabled = lambda name, manual=False: False
    cfg = GPTConfig()
    paddle.seed(cs.SEED)
    from paddle_tpu.inference.serving import GenerationEngine
    from paddle_tpu.models import GPTForCausalLM
    model = GPTForCausalLM(cfg).bfloat16()
    model.eval()
    # 0.3 of the chip's memory, as the engine sizes its pool there
    blocks = int(0.3 * HBM_BYTES) // (
        2 * cfg.num_hidden_layers * cfg.hidden_size * 16 * 2)
    engine = GenerationEngine(model, num_blocks=blocks)
    engine.add_request(list(range(1, 301)), max_new_tokens=2)
    engine.step()
    (entry,) = engine._step_fn._cache.values()
    print(f"serve: token_budget {engine.token_budget} table_width "
          f"{engine.cache.table_width} kv_blocks {engine.cache.num_blocks}",
          flush=True)
    open_gate()
    chip = SingleDeviceSharding(topo.devices[0])
    # a fresh function object: jax keeps the first trace of `pure_fn`
    # for these shapes and would hand back the CPU-branch jaxpr
    text = report("serve", jax.jit(
        lambda *a: entry["pure_fn"](*a), donate_argnums=(2,)).lower(
        *_on(chip, entry["avals"])))
    assert_pools_stay("serve", engine, text)
    engine.close()


def _check_serve_cell(topo, name, family, config, traffic, cut, stacks):
    """`check_serve` for a family at its benchmark cell's sizes (one CPU
    step at real widths first: a minute or two).  ``cut`` lays the cut
    in depth over the config file; ``stacks`` is the regular expression
    of an expert stack's shape, which the step may not move."""
    import json
    import re
    pallas_gate.pallas_enabled = lambda name, manual=False: False
    from paddle_tpu.inference.serving import GenerationEngine
    with open(os.path.join(ROOT, "benchmarks/configs", config)) as f:
        cfg = json.load(f)
    with open(os.path.join(ROOT, "benchmarks/traffic", traffic)) as f:
        traffic = json.load(f)
    cfg.update(cut(cfg))
    paddle.seed(cs.SEED)
    engine = GenerationEngine(family.build(cfg), **traffic["engine"])
    engine.add_request(list(range(1, 1301)), max_new_tokens=2)
    engine.step()
    (entry,) = engine._step_fn._cache.values()
    stats = engine.stats()
    print(f"{name}: token_budget {engine.token_budget} table_width "
          f"{engine.cache.table_width} pool {stats['full_pool_bytes']/1e9:.2f}G"
          f" + {stats['window_pool_bytes']/1e9:.2f}G "
          f"{stats['window_groups']} state "
          f"{stats['state_pool_bytes']/1e9:.2f}G", flush=True)
    open_gate()
    chip = SingleDeviceSharding(topo.devices[0])
    text = report(name, jax.jit(
        lambda *a: entry["pure_fn"](*a), donate_argnums=(2,)).lower(
        *_on(chip, entry["avals"])))
    moved = [line.strip()[:160] for line in text.splitlines()
             if stacks and re.search(stacks, line)
             and re.search(r" (?:copy|pad|concatenate|transpose)\(", line)]
    assert not moved, f"an expert stack is moved in the step: {moved}"
    assert_pools_stay(name, engine, text)
    engine.close()
    return text


def check_serve_afmoe(topo):
    from benchmarks.families import afmoe
    return _check_serve_cell(
        topo, "serve_afmoe", afmoe, "Trinity-Mini.json",
        "mixedlen-closed32.json",
        lambda cfg: dict(num_hidden_layers=3, layer_types=[
            cfg["layer_types"][i] for i in (0, 1, -1)]),
        r"bf16\[12[89],(?:2048|1024),(?:2048|1024)\]")


def check_serve_qwen3_next(topo):
    from benchmarks.families import qwen3_next
    return _check_serve_cell(
        topo, "serve_qwen3_next", qwen3_next,
        "Qwen3-Next-80B-A3B-Instruct.json", "longctx24k-closed32.json",
        lambda cfg: dict(num_hidden_layers=4),
        r"bf16\[128,(?:2048|512),(?:2048|1024)\]")


def check_serve_longcat(topo):
    from benchmarks.families import longcat_flash
    from paddle_tpu.inference.serving import attention
    # the one CPU step only discovers the step's state: the latent
    # layers' gather form would hold a [64, 1024, 17408] float32 score
    # block of a 1,024-token chunk on the CPU, so it stands aside there
    # (the chip's program, traced again with the gate open, is the
    # kernel's)
    real = attention._latent_ref
    attention._latent_ref = lambda q, *a, value_lanes, **kw: jnp.zeros(
        (q.shape[0], value_lanes), q.dtype)
    try:
        return _check_serve_cell(
            topo, "serve_longcat", longcat_flash, "LongCat-Flash-Omni.json",
            "longctx16k-closed32.json", lambda cfg: dict(num_layers=1),
            r"bf16\[8,(?:6144|2048),(?:6144|4096)\]")
    finally:
        attention._latent_ref = real


def check_serve_sala(topo):
    from benchmarks.families import minicpm_sala
    return _check_serve_cell(
        topo, "serve_sala", minicpm_sala, "MiniCPM-SALA.json",
        "longdoc-closed32.json",
        lambda cfg: dict(num_hidden_layers=4, mixer_types=[
            "minicpm4", "lightning-attn", "lightning-attn", "minicpm4"]),
        None)


def main(names):
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    # a compile-only entry cannot be read back without a chip
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    names = names or ["serve", "static", "loop", "mesh"]
    if "serve" in names:        # before the gate opens for good
        check_serve(topo)
    if "serve_afmoe" in names:
        check_serve_afmoe(topo)
    if "serve_qwen3_next" in names:
        check_serve_qwen3_next(topo)
    if "serve_sala" in names:
        check_serve_sala(topo)
    if "serve_longcat" in names:
        check_serve_longcat(topo)
    open_gate()
    for name in names:
        if name == "static":
            check_static(topo)
        elif name == "loop":
            check_static(topo, loop=True)
        elif name == "mesh":
            check_mesh(topo)
        elif name not in ("serve", "serve_afmoe", "serve_qwen3_next",
                          "serve_sala", "serve_longcat"):
            raise SystemExit(f"unknown program {name!r}")
    print("AOT_SMOKE_OK", flush=True)


if __name__ == "__main__":
    main(sys.argv[1:])
