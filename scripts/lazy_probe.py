"""Eager / lazy-eager / static 3-way step-time probe (VERDICT r4 #4).

Measures the SAME train step under the three execution modes at two
scales — a 2-layer GPT and LeNet — and writes the ratios to
``chiprun_out/lazy_probe.json``.  The file is a report: nothing reads
it at run time, the dygraph tier is whatever ``import paddle_tpu``
selects.  Only a run on the chip says anything about the chip; off it
the script runs toy sizes to check its own control flow.

Usage: python scripts/lazy_probe.py
"""
import contextlib
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def log(msg):
    print(f"[lazy_probe] {msg}", flush=True)


def _sync(t):
    t.numpy()


def measure_dygraph(build, n_iters, lazy):
    import paddle_tpu as paddle
    cm = paddle.incubate.lazy_eager() if lazy \
        else contextlib.nullcontext()
    with cm:
        step = build()
        t0 = time.time()
        _sync(step())                 # warm-up / compile
        warm = time.time() - t0
        # sync EVERY iter: the warm-up compiled the 1-step segment, so
        # steady state reuses it (unsynced steps would fuse into one
        # never-seen N-step mega-segment and recompile)
        t0 = time.time()
        for _ in range(n_iters):
            _sync(step())
        dt = (time.time() - t0) / n_iters
    return dt, warm


def gpt_builders(on_tpu):
    import numpy as np
    import paddle_tpu as paddle
    from paddle_tpu import optimizer
    from paddle_tpu.models import (GPTConfig, GPTForCausalLM,
                                   GPTPretrainingCriterion)

    cfg = GPTConfig(hidden_size=512 if on_tpu else 128,
                    num_hidden_layers=2,
                    num_attention_heads=8 if on_tpu else 2,
                    use_flash_attention=False, use_recompute=False,
                    max_position_embeddings=512)
    B, S = (8, 256) if on_tpu else (2, 64)
    rng = np.random.default_rng(0)
    ids_np = rng.integers(0, cfg.vocab_size, (B, S)).astype(np.int64)

    def build_dygraph():
        paddle.seed(0)
        model = GPTForCausalLM(cfg)
        crit = GPTPretrainingCriterion()
        opt = optimizer.AdamW(learning_rate=1e-4,
                              parameters=model.parameters())
        ids = paddle.to_tensor(ids_np)

        def step():
            logits = model(ids)
            if isinstance(logits, (tuple, list)):
                logits = logits[0]
            loss = crit(logits, ids)
            loss.backward()
            opt.step()
            opt.clear_grad()
            return loss
        return step

    def static_run(n_iters):
        from paddle_tpu import static
        paddle.enable_static()
        try:
            main_prog, startup = static.Program(), static.Program()
            with static.program_guard(main_prog, startup):
                ids = static.data("ids", [B, S], "int64")
                paddle.seed(0)
                model = GPTForCausalLM(cfg)
                crit = GPTPretrainingCriterion()
                logits = model(ids)
                if isinstance(logits, (tuple, list)):
                    logits = logits[0]
                loss = crit(logits, ids)
                opt = optimizer.AdamW(learning_rate=1e-4,
                                      parameters=model.parameters())
                opt.minimize(loss)
            exe = static.Executor()
            fd = {"ids": ids_np}
            t0 = time.time()
            exe.run(main_prog, feed=fd, fetch_list=[loss])
            warm = time.time() - t0
            t0 = time.time()
            for _ in range(n_iters):
                (lv,) = exe.run(main_prog, feed=fd, fetch_list=[loss])
            return (time.time() - t0) / n_iters, warm
        finally:
            paddle.disable_static()

    return build_dygraph, static_run, B * S


def lenet_builders(on_tpu):
    import numpy as np
    import paddle_tpu as paddle
    from paddle_tpu import optimizer
    from paddle_tpu.vision.models import LeNet
    import paddle_tpu.nn.functional as F

    B = 64 if on_tpu else 8
    rng = np.random.default_rng(0)
    img_np = rng.standard_normal((B, 1, 28, 28)).astype("float32")
    lbl_np = rng.integers(0, 10, (B,)).astype("int64")

    def build_dygraph():
        paddle.seed(0)
        model = LeNet(num_classes=10)
        opt = optimizer.Adam(learning_rate=1e-3,
                             parameters=model.parameters())
        img = paddle.to_tensor(img_np)
        lbl = paddle.to_tensor(lbl_np)

        def step():
            loss = F.cross_entropy(model(img), lbl)
            loss.backward()
            opt.step()
            opt.clear_grad()
            return loss
        return step

    def static_run(n_iters):
        from paddle_tpu import static
        paddle.enable_static()
        try:
            main_prog, startup = static.Program(), static.Program()
            with static.program_guard(main_prog, startup):
                img = static.data("img", [B, 1, 28, 28], "float32")
                lbl = static.data("lbl", [B], "int64")
                paddle.seed(0)
                model = LeNet(num_classes=10)
                loss = F.cross_entropy(model(img), lbl)
                opt = optimizer.Adam(learning_rate=1e-3,
                                     parameters=model.parameters())
                opt.minimize(loss)
            exe = static.Executor()
            fd = {"img": img_np, "lbl": lbl_np}
            t0 = time.time()
            exe.run(main_prog, feed=fd, fetch_list=[loss])
            warm = time.time() - t0
            t0 = time.time()
            for _ in range(n_iters):
                exe.run(main_prog, feed=fd, fetch_list=[loss])
            return (time.time() - t0) / n_iters, warm
        finally:
            paddle.disable_static()

    return build_dygraph, static_run, B


def main():
    import jax
    on_tpu = jax.devices()[0].platform == "tpu"
    n_iters = 10 if on_tpu else 3
    log(f"backend={jax.devices()[0].platform} n_iters={n_iters}")

    results = {"platform": jax.devices()[0].platform,
               "captured_unix": int(time.time()), "models": {}}
    for name, builders in (("gpt2l", gpt_builders),
                           ("lenet", lenet_builders)):
        build_dygraph, static_run, work = builders(on_tpu)
        entry = {}
        for mode in ("eager", "lazy"):
            try:
                dt, warm = measure_dygraph(
                    build_dygraph, n_iters, lazy=(mode == "lazy"))
                entry[mode + "_step_ms"] = round(dt * 1e3, 2)
                entry[mode + "_warm_s"] = round(warm, 2)
                log(f"{name} {mode}: {dt*1e3:.1f} ms/step "
                    f"(warm {warm:.1f}s)")
            except Exception as e:
                log(f"{name} {mode} FAILED: {type(e).__name__}: {e}")
                entry[mode + "_error"] = str(e)[:200]
        try:
            dt, warm = static_run(n_iters)
            entry["static_step_ms"] = round(dt * 1e3, 2)
            entry["static_warm_s"] = round(warm, 2)
            log(f"{name} static: {dt*1e3:.1f} ms/step (warm {warm:.1f}s)")
        except Exception as e:
            log(f"{name} static FAILED: {type(e).__name__}: {e}")
            entry["static_error"] = str(e)[:200]
        if "eager_step_ms" in entry and "lazy_step_ms" in entry:
            entry["lazy_over_eager"] = round(
                entry["lazy_step_ms"] / entry["eager_step_ms"], 3)
        results["models"][name] = entry

    out = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "chiprun_out", "lazy_probe.json")
    os.makedirs(os.path.dirname(out), exist_ok=True)
    with open(out, "w") as f:
        json.dump(results, f, indent=1)
    log(f"wrote {out}")


if __name__ == "__main__":
    main()
