"""Flash-attention block-size sweep at the benchmark's shapes.

Runs on the chip only (it fails without one).  Times forward plus
backward through the custom-vjp kernels for each (block_q, block_k)
candidate and writes the table to `chiprun_out/flash_blocks.json` — a
report, not a runtime input: the kernels' tiling comes from committed
code alone, so a winning table is committed into
`ops/pallas_kernels._pick_block` by the PR that measured it.  Beside
each sweep it times the XLA composite on the same inputs, and at the
winning blocks the dropout kernels with the interpret-mode hash in
place of the core's generator.

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


# (name, batch*heads, seq, head_dim, causal, dropout_p)
SHAPES = [
    # bert-base-uncased.pretrain-*: 16 x 512, 12 heads of 64, p 0.1
    ("bert_b16_s512_p0.1", 16 * 12, 512, 64, False, 0.1),
    ("bert_b16_s512_p0", 16 * 12, 512, 64, False, 0.0),
    # GPT training: causal, no attention dropout
    ("gpt_s1024_causal", 8 * 16, 1024, 64, True, 0.0),
]
CANDIDATES = [128, 256, 512, 1024]
REPEATS = 10


def sweep(shapes, candidates):
    import jax
    import jax.numpy as jnp
    from paddle_tpu.nn.functional.flash_attention import _sdpa_ref
    from paddle_tpu.ops import pallas_kernels as pk

    def timed(step, *args):
        jax.block_until_ready(step(*args))
        t = time.perf_counter()
        for _ in range(REPEATS):
            out = step(*args)
        jax.block_until_ready(out)
        return (time.perf_counter() - t) / REPEATS * 1e3

    results = {}
    for name, bh, seq, d, causal, p in shapes:
        q, k, v = (jax.random.normal(kk, (bh, seq, d), jnp.bfloat16)
                   for kk in jax.random.split(jax.random.PRNGKey(0), 3))
        seed = jnp.array([1], jnp.int32) if p else None
        rng = jax.random.PRNGKey(1)

        def flash_grad():
            # a new function object each time: the blocks are read at trace
            return jax.jit(jax.grad(lambda q, k, v: jnp.sum(
                pk._flash_attention_bhsd(q, k, v, seed, d ** -0.5, causal,
                                         p).astype(jnp.float32)),
                argnums=(0, 1, 2)))

        def layout(x):      # (bh, seq, d) as _sdpa_ref's [B, S, H, D]
            return jnp.swapaxes(x, 0, 1)[None]

        composite = jax.jit(jax.grad(lambda q, k, v: jnp.sum(_sdpa_ref(
            layout(q), layout(k), layout(v), None, causal, d ** -0.5, p,
            rng).astype(jnp.float32)), argnums=(0, 1, 2)))
        row = {"composite_ms": timed(composite, q, k, v)}
        log(f"{name} composite: {row['composite_ms']:.2f} ms")
        table = {}
        with jax.enable_x64(False):
            for bq in candidates:
                for bk in candidates:
                    if seq % bq or seq % bk:
                        continue
                    pk.set_flash_block_sizes(bq, bk)
                    try:
                        ms = timed(flash_grad(), q, k, v)
                    except Exception as e:
                        log(f"{name} bq={bq} bk={bk}: FAILED "
                            f"{type(e).__name__}: {str(e)[:200]}")
                        continue
                    log(f"{name} bq={bq} bk={bk}: {ms:.2f} ms")
                    table[f"{bq}x{bk}"] = ms
            pk.set_flash_block_sizes(None, None)
            row["committed_ms"] = timed(flash_grad(), q, k, v)
            log(f"{name} committed table: {row['committed_ms']:.2f} ms")
            if table and p:
                best = min(table, key=table.get)
                pk.set_flash_block_sizes(*map(int, best.split("x")))
                hw_bits, pk._tile_bits = pk._tile_bits, pk._hash_bits
                jax.clear_caches()   # the jitted builders key on shapes
                try:
                    row["hash_bits_ms_at_best"] = timed(flash_grad(), q, k,
                                                        v)
                    log(f"{name} {best} hash bits: "
                        f"{row['hash_bits_ms_at_best']:.2f} ms")
                finally:
                    pk._tile_bits = hw_bits
                    jax.clear_caches()
                    pk.set_flash_block_sizes(None, None)
        row["blocks_ms"] = table
        if table:
            row["best"] = min(table, key=table.get)
            log(f"{name}: best blocks {row['best']} "
                f"({table[row['best']]:.2f} ms)")
        results[name] = row
    return results


def main():
    import jax
    log(f"devices: {jax.devices()}")
    if jax.default_backend() != "tpu":
        raise SystemExit("flash_block_sweep times Mosaic kernels: it "
                         "needs a TPU backend")
    results = sweep(SHAPES, CANDIDATES)
    path = os.path.join(ROOT, "chiprun_out", "flash_blocks.json")
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(results, f, indent=1)
    log(f"wrote {path}")


if __name__ == "__main__":
    main()
