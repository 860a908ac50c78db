#!/usr/bin/env python
"""Async-pipeline smoke check: overlap proof, end to end, one command.

    python scripts/pipeline_smoke.py [--seed N] [--out DIR] [--overhead]

Runs a BERT-mini static training loop through the async step pipeline
(`Executor.run(..., return_numpy=False)` behind a `DeviceFeeder`) under
PADDLE_TPU_OBS=1 and validates the whole story from the recorded trace:

  * the chrome trace carries h2d / d2h / pipeline lanes, and
    `pipeline_stats` measures depth >= 2 with a nonzero h2d overlap
    ratio — device prefetch really runs while a step is in flight;
  * PADDLE_TPU_PIPELINE_DEPTH=1 + use_program_cache=False reproduces
    the fully synchronous per-step losses bit-for-bit;
  * with a fresh persistent compilation cache, the second compile of
    the same program (after jax.clear_caches()) is measurably warmer.

``--overhead`` additionally times the disabled path (depth=1,
return_numpy=True — the pre-pipeline external semantics) against the
async path.  Exits 0 iff every scenario passes.  CPU-only, no TPU.
"""
import argparse
import json
import logging
import os
import sys
import tempfile
import time
import traceback

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ["PADDLE_TPU_OBS"] = "1"
sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import numpy as np  # noqa: E402

import paddle_tpu as paddle  # noqa: E402
from paddle_tpu import observability as obs  # noqa: E402
from paddle_tpu import optimizer, static  # noqa: E402
from paddle_tpu.io import DeviceFeeder  # noqa: E402

RESULTS = []

B, S = 4, 32
N_BATCHES = 6


def scenario(name):
    def deco(fn):
        RESULTS.append((name, fn))
        return fn
    return deco


def build_bert_mini(seed):
    """A small static MLM training program: heavy enough that a step
    dwarfs its own h2d, deterministic under the seed."""
    from paddle_tpu.models import BertConfig, BertForMaskedLM
    paddle.seed(seed)
    cfg = BertConfig(vocab_size=256, hidden_size=128,
                     num_hidden_layers=2, num_attention_heads=2,
                     intermediate_size=256,
                     max_position_embeddings=S)
    main_prog = static.Program()
    startup = static.Program()
    with static.program_guard(main_prog, startup):
        ids = static.data("ids", [B, S], "int64")
        labels = static.data("labels", [B, S], "int64")
        model = BertForMaskedLM(cfg)
        loss, _ = model(ids, labels=labels)
        opt = optimizer.SGD(learning_rate=1e-3,
                            parameters=model.parameters())
        opt.minimize(loss)
    return main_prog, loss, cfg


def batches(seed, cfg, n=N_BATCHES):
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(n):
        x = rng.integers(0, cfg.vocab_size, (B, S)).astype(np.int64)
        out.append({"ids": x, "labels": x})
    return out


@scenario("prefetch overlaps in-flight compute (trace-measured)")
def _overlap(seed, out_dir):
    os.environ["PADDLE_TPU_PIPELINE_DEPTH"] = "2"
    paddle.enable_static()
    try:
        prog, loss, cfg = build_bert_mini(seed)
        exe = static.Executor()
        obs.get_timeline().clear()
        handles = []
        with DeviceFeeder(batches(seed, cfg)) as feeder:
            for fb in feeder:
                handles.append(exe.run(prog, feed=fb, fetch_list=[loss],
                                       return_numpy=False)[0])
        vals = [float(h) for h in handles]  # the sync points
        assert all(np.isfinite(v) for v in vals), vals

        stats = obs.pipeline_stats()
        assert stats["dispatch_count"] >= N_BATCHES, stats
        assert stats["measured_depth"] >= 2, \
            f"pipeline never went >1 step deep: {stats}"
        assert stats["overlap_ratio"] > 0.0, \
            f"no h2d hidden behind in-flight compute: {stats}"

        path = obs.export_chrome_trace(
            os.path.join(out_dir, "pipeline_smoke.json"))
        with open(path) as f:
            evs = json.load(f)["traceEvents"]
        spans = [e for e in evs if e.get("ph") == "X"]
        cats = {e["cat"] for e in spans}
        assert "h2d" in cats and "dispatch" in cats, cats
        assert any(e["name"].startswith("h2d:prefetch")
                   for e in spans), "no DeviceFeeder prefetch span"
        print(f"      depth={stats['measured_depth']} "
              f"overlap={stats['overlap_ratio']:.2f} "
              f"({stats['overlap_ms']:.2f}/{stats['h2d_ms']:.2f} ms) "
              f"-> {path}")
        return path
    finally:
        paddle.disable_static()
        os.environ.pop("PADDLE_TPU_PIPELINE_DEPTH", None)


@scenario("depth=1 + cache-off reproduces synchronous results bit-for-bit")
def _sync_parity(seed, out_dir):
    paddle.enable_static()
    try:
        # baseline: default synchronous semantics (return_numpy=True)
        prog, loss, cfg = build_bert_mini(seed)
        exe = static.Executor()
        feeds = batches(seed, cfg)
        base = [exe.run(prog, feed=fb, fetch_list=[loss])[0]
                for fb in feeds]

        # async machinery forced to its degenerate config: depth=1
        # blocks every dispatch before run() returns, cache-off
        # rebuilds the executable every step
        os.environ["PADDLE_TPU_PIPELINE_DEPTH"] = "1"
        try:
            prog2, loss2, _ = build_bert_mini(seed)  # same seed: same init
            exe2 = static.Executor()
            async_vals = []
            for fb in feeds:
                (h,) = exe2.run(prog2, feed=fb, fetch_list=[loss2],
                                return_numpy=False,
                                use_program_cache=False)
                assert h.is_ready(), "depth=1 must block before returning"
                async_vals.append(h.numpy())
        finally:
            os.environ.pop("PADDLE_TPU_PIPELINE_DEPTH", None)

        for i, (a, b) in enumerate(zip(base, async_vals)):
            assert a.dtype == b.dtype, (a.dtype, b.dtype)
            assert np.array_equal(a, b), \
                f"step {i}: sync {a!r} != depth-1 async {b!r}"
        print(f"      {len(base)} steps bit-for-bit identical "
              f"(last loss {float(base[-1]):.4f})")
    finally:
        paddle.disable_static()


@scenario("persistent compile cache: disk-warm recompile is faster")
def _compile_cache(seed, out_dir):
    import jax
    from jax.experimental.compilation_cache import compilation_cache
    from paddle_tpu.device import ensure_compile_cache
    # a fresh cache, placed the way a user places it: what
    # `JAX_COMPILATION_CACHE_DIR=... python` gives (jax reads the
    # variable into its config at import)
    cache_dir = os.path.join(out_dir, "xla_cache")
    saved_dir = jax.config.jax_compilation_cache_dir
    os.environ["JAX_COMPILATION_CACHE_DIR"] = cache_dir
    jax.config.update("jax_compilation_cache_dir", cache_dir)
    compilation_cache.reset_cache()
    try:
        assert ensure_compile_cache() == cache_dir
        paddle.enable_static()
        try:
            prog, loss, cfg = build_bert_mini(seed)
            exe = static.Executor()
            fb = batches(seed, cfg, n=1)[0]

            def compile_ms(run):
                before = obs.phase_breakdown()["compile_ms"]
                run()
                return obs.phase_breakdown()["compile_ms"] - before

            cold = compile_ms(lambda: exe.run(
                prog, feed=fb, fetch_list=[loss],
                use_program_cache=False))
            entries = sum(len(fs) for _, _, fs in os.walk(cache_dir))
            assert entries > 0, f"nothing persisted under {cache_dir}"
            jax.clear_caches()  # drop the in-memory executable
            warm = compile_ms(lambda: exe.run(
                prog, feed=fb, fetch_list=[loss],
                use_program_cache=False))
            assert warm < cold * 0.8, \
                f"warm compile not faster: cold={cold:.0f}ms warm={warm:.0f}ms"
            print(f"      cold={cold:.0f} ms -> warm={warm:.0f} ms "
                  f"({entries} cache file(s))")
        finally:
            paddle.disable_static()
    finally:
        os.environ.pop("JAX_COMPILATION_CACHE_DIR", None)
        jax.config.update("jax_compilation_cache_dir", saved_dir)
        compilation_cache.reset_cache()


def measure_overhead(seed):
    """Disabled-path cost: depth=1 + return_numpy=True is externally
    identical to the pre-pipeline executor — time it against the async
    path on the same program and batches."""
    paddle.enable_static()
    try:
        prog, loss, cfg = build_bert_mini(seed)
        exe = static.Executor()
        feeds = batches(seed, cfg, n=20)
        exe.run(prog, feed=feeds[0], fetch_list=[loss])  # compile

        obs.disable()

        def sync_loop():
            t0 = time.perf_counter()
            for fb in feeds:
                exe.run(prog, feed=fb, fetch_list=[loss])
            return time.perf_counter() - t0

        def async_loop():
            t0 = time.perf_counter()
            hs = []
            with DeviceFeeder(feeds) as feeder:
                for fb in feeder:
                    hs.append(exe.run(prog, feed=fb, fetch_list=[loss],
                                      return_numpy=False)[0])
            for h in hs:
                float(h)
            return time.perf_counter() - t0

        os.environ["PADDLE_TPU_PIPELINE_DEPTH"] = "1"
        sync_loop()  # warm
        t_sync = min(sync_loop() for _ in range(3))
        os.environ["PADDLE_TPU_PIPELINE_DEPTH"] = "2"
        t_async = min(async_loop() for _ in range(3))
        os.environ.pop("PADDLE_TPU_PIPELINE_DEPTH", None)
        obs.enable(True)
        n = len(feeds)
        print(f"{n}-step loop: sync depth=1 {t_sync/n*1e3:.2f} ms/step, "
              f"async depth=2 {t_async/n*1e3:.2f} ms/step "
              f"({(t_sync/t_async - 1)*100:+.1f}%)")
    finally:
        paddle.disable_static()


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--out", default=None,
                    help="export dir (default: a fresh tempdir)")
    ap.add_argument("--overhead", action="store_true",
                    help="also time the disabled (fully sync) path")
    args = ap.parse_args()
    logging.basicConfig(level=logging.WARNING)
    out_dir = args.out or tempfile.mkdtemp(prefix="paddle_tpu_pipe_")
    failures = 0
    trace_path = None
    for name, fn in RESULTS:
        t0 = time.monotonic()
        try:
            r = fn(args.seed, out_dir)
            if r:
                trace_path = r
            print(f"PASS  {name}  ({time.monotonic() - t0:.1f}s)")
        except Exception:
            failures += 1
            print(f"FAIL  {name}")
            traceback.print_exc()
    if trace_path:
        print(f"\ntrace: {trace_path}  (load in ui.perfetto.dev)")
    if args.overhead:
        measure_overhead(args.seed)
    total = len(RESULTS)
    print(f"\npipeline smoke: {total - failures}/{total} scenarios passed "
          f"(seed={args.seed})")
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
