"""Flash-attention block-size autotune sweep (VERDICT r3 weak #2: the
1.17x Pallas margin was never block-retuned at bench shapes).

Runs on the chip only (it fails without one).  Times fwd+bwd through
the custom-vjp kernel for each (block_q, block_k) candidate at the
benchmark shapes and writes the winners to
`chiprun_out/flash_blocks.json` — a report, not a runtime input: the
kernels' tiling comes from committed code alone, so a winning table is
committed into `ops/pallas_kernels._pick_block` by the PR that measured
it.

Usage: python scripts/flash_block_sweep.py
"""
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def log(msg):
    print(f"[sweep] {msg}", flush=True)


# (name, batch*heads, seq, head_dim) — BERT-base and GPT bench shapes
SHAPES = [
    ("bert_b32", 32 * 12, 128, 64),
    ("gpt_s1024", 8 * 16, 1024, 64),
]
CANDIDATES = [32, 64, 128, 256, 512]


def main():
    import jax
    import jax.numpy as jnp
    import numpy as np
    from paddle_tpu.ops import pallas_kernels as pk

    log(f"devices: {jax.devices()}")
    if jax.default_backend() != "tpu":
        raise SystemExit("flash_block_sweep times Mosaic kernels: it "
                         "needs a TPU backend")
    results = {}
    for name, bh, seq, d in SHAPES:
        key = jax.random.PRNGKey(0)
        q, k, v = (jax.random.normal(kk, (bh, seq, d), jnp.bfloat16)
                   for kk in jax.random.split(key, 3))

        def loss(q, k, v):
            o = pk._flash_attention_bhsd(q, k, v, d ** -0.5, True)
            return jnp.sum(o.astype(jnp.float32))

        step = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))
        best, best_t = None, float("inf")
        for bq in CANDIDATES:
            if bq > seq or seq % bq:
                continue
            for bk in CANDIDATES:
                if bk > seq or seq % bk:
                    continue
                pk.set_flash_block_sizes(bq, bk)
                jax.clear_caches()
                try:
                    out = step(q, k, v)
                    jax.block_until_ready(out)
                    t = time.time()
                    for _ in range(5):
                        out = step(q, k, v)
                    jax.block_until_ready(out)
                    dt = (time.time() - t) / 5
                except Exception as e:
                    log(f"{name} bq={bq} bk={bk}: FAILED "
                        f"{type(e).__name__}: {str(e)[:80]}")
                    continue
                log(f"{name} bq={bq} bk={bk}: {dt*1e3:.2f} ms")
                if dt < best_t:
                    best, best_t = (bq, bk), dt
        pk.set_flash_block_sizes(None, None)
        if best:
            log(f"{name}: best blocks {best} ({best_t*1e3:.2f} ms)")
            results[str(seq)] = list(best)

    if results:
        path = os.path.join(ROOT, "chiprun_out", "flash_blocks.json")
        os.makedirs(os.path.dirname(path), exist_ok=True)
        with open(path, "w") as f:
            json.dump(results, f)
        log(f"wrote {path}: {results}")


if __name__ == "__main__":
    main()
