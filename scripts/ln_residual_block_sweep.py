"""Row-block sweep of the LayerNorm-residual kernels at the benchmark's
shape.

Runs on the chip only (it fails without one).  Times forward plus
backward through the custom-vjp kernels at (8192, 768), bfloat16 and
float32, with the dropout mask drawn in the kernels and without, for
each candidate cap on the rows of a block, and writes the table to
`chiprun_out/ln_residual_blocks.json` — a report, not a runtime input:
the kernels' tiling comes from committed code alone, so a winning row
is committed into `ops/pallas_fused._ln_res_block_rows` by the PR that
measured it.  Beside each sweep it times what the dropout form
replaces: `jax.random.bernoulli` and a select in XLA in front of the
plain kernels.

Each timing is a chain of `CHAIN` sublayers in one jitted gradient, as
a model calls them, so that the host's dispatch is not what is timed.

Usage: python scripts/ln_residual_block_sweep.py
"""
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def log(msg):
    print(f"[sweep] {msg}", flush=True)


ROWS, HIDDEN, P = 16 * 512, 768, 0.1   # bert-base-uncased.pretrain-*
CANDIDATES = [64, 128, 256, 512]
CHAIN, REPEATS = 8, 20


def sweep():
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops import pallas_fused as pf

    def timed(step, *args):
        jax.block_until_ready(step(*args))
        t = time.perf_counter()
        for _ in range(REPEATS):
            out = step(*args)
        jax.block_until_ready(out)
        return (time.perf_counter() - t) / REPEATS / CHAIN * 1e3

    def chain_grad(sublayer):
        """Gradient of CHAIN sublayers in a row; a new function object
        each time, because the block rows are read at trace."""
        def loss(x, r, g, b, key):
            for k in jax.random.split(key, CHAIN):
                x = sublayer(x, r, g, b, k)
            return jnp.sum(x.astype(jnp.float32))
        return jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3)))

    def in_kernel(p):
        def sublayer(x, r, g, b, key):
            seed = jax.lax.bitcast_convert_type(
                jax.random.bits(key, (1,), jnp.uint32), jnp.int32)
            return pf.fused_layer_norm_residual(
                x, r, g, b, dropout_p=p, seed=seed if p else None)
        return sublayer

    def in_xla(x, r, g, b, key):
        keep = jax.random.bernoulli(key, 1.0 - P, x.shape)
        x = jnp.where(keep, x / (1.0 - P), jnp.zeros((), x.dtype))
        return pf.fused_layer_norm_residual(x, r, g, b)

    committed = pf._ln_res_block_rows
    results = {}
    for dtype in (jnp.bfloat16, jnp.float32):
        x, r = (jax.random.normal(k, (ROWS, HIDDEN), dtype)
                for k in jax.random.split(jax.random.PRNGKey(0)))
        g, b = jnp.ones((HIDDEN,), dtype), jnp.zeros((HIDDEN,), dtype)
        args = (x, r, g, b, jax.random.PRNGKey(1))
        name = jnp.dtype(dtype).name
        with jax.enable_x64(False):
            row = {"mask_in_xla_ms": timed(chain_grad(in_xla), *args)}
            log(f"{name} bernoulli + select in XLA, plain kernels: "
                f"{row['mask_in_xla_ms']:.4f} ms a sublayer")
            for p in (P, 0.0):
                table = {}
                for rows in CANDIDATES:
                    # builders, plan and keep writer all read the module's
                    # function: the candidate stands in for it
                    pf._ln_res_block_rows = lambda *_, rows=rows: rows
                    jax.clear_caches()   # the jitted builders key on shapes
                    try:
                        ms = timed(chain_grad(in_kernel(p)), *args)
                    except Exception as e:
                        log(f"{name} p={p} rows={rows}: FAILED "
                            f"{type(e).__name__}: {str(e)[:200]}")
                        continue
                    finally:
                        pf._ln_res_block_rows = committed
                    log(f"{name} p={p} rows={rows}: {ms:.4f} ms a sublayer")
                    table[str(rows)] = ms
                jax.clear_caches()
                key = "dropout" if p else "plain"
                row[key] = {
                    "rows_ms": table,
                    "best": min(table, key=table.get) if table else None,
                    "committed_rows": committed(ROWS, HIDDEN, bool(p), dtype),
                    "committed_ms": timed(chain_grad(in_kernel(p)), *args)}
                log(f"{name} {key}: best {row[key]['best']}, committed "
                    f"{row[key]['committed_rows']} rows "
                    f"{row[key]['committed_ms']:.4f} ms")
        results[name] = row
    return results


def main():
    import jax
    log(f"devices: {jax.devices()}")
    if jax.default_backend() != "tpu":
        raise SystemExit("ln_residual_block_sweep times Mosaic kernels: "
                         "it needs a TPU backend")
    results = sweep()
    log("dtype     form      " + "".join(f"{c:>9}" for c in CANDIDATES)
        + "   mask in XLA")
    for name, row in results.items():
        for form in ("dropout", "plain"):
            cells = "".join(
                f"{row[form]['rows_ms'].get(str(c), float('nan')):9.4f}"
                for c in CANDIDATES)
            log(f"{name:<9} {form:<9} {cells}   "
                + (f"{row['mask_in_xla_ms']:.4f}" if form == "dropout"
                   else ""))
    path = os.path.join(ROOT, "chiprun_out", "ln_residual_blocks.json")
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(results, f, indent=1)
    log(f"wrote {path}")


if __name__ == "__main__":
    main()
