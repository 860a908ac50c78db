"""The control behind the limits of ``Trinity-Mini.mixedlen-closed32``'s
check (PERF.md section 6, PR 32): the cell as ``benchmarks/run.py`` runs
it, with the plain reference computed in float8 (e4m3, a scale a tensor:
the nearest precision below the configuration's bfloat16) standing in
the program's place: at every checked position, the token it would
emit.  It has to come out as not ``correct`` (exit code 1).

    python3 scripts/trinity_margin_control.py fp8 --workload \\
        Trinity-Mini.mixedlen-closed32 --seed <n> --seconds 51 --trace 0
"""
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmarks import run  # noqa: E402  (set-up is counted from here)


def fp8():
    import jax.numpy as jnp
    from benchmarks.runners import serve_closed_long as runner

    def emitted(ctx, params, client, ids, rows):
        low = {"compute_dtype": jnp.float8_e4m3fn}
        hidden = ctx.family.reference_hidden(params, ctx.config,
                                             jnp.asarray(ids), **low)
        return ctx.family.reference_head(params, ctx.config, hidden[rows],
                                         **low).argmax(-1)

    # the counted runner takes its margin check from this module
    runner.emitted = emitted


if __name__ == "__main__":
    {"fp8": fp8}[sys.argv[1]]()
    run.main(sys.argv[2:])
