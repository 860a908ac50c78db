"""The controls behind the limits of ``MiniCPM-SALA.longdoc-closed32``'s
check (PERF.md section 6, PR 28): the cell as ``benchmarks/run.py``
runs it, with one fault laid over the program.  A control has to come
out as not ``correct``.

    python3 scripts/sala_margin_control.py <control> --workload \\
        MiniCPM-SALA.longdoc-closed32 --seed <n> --seconds 51 --trace 0

``fp8``         the plain reference computed in float8 (e4m3, a scale a
                tensor: the nearest precision below the configuration's
                bfloat16) stands in the program's place: at every
                checked position, the token it would emit
``bf16_state``  the lightning layers' state rounded to bfloat16 after
                every step: what a bfloat16 state pool would hold
``no_reset``    a request's first chunk does not zero its state slot,
                so a reused slot starts from its last user's state
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

    runner.emitted = emitted


def bf16_state():
    import jax.numpy as jnp
    from paddle_tpu.inference.serving import attention
    sound = attention._lightning_update_impl

    def rounded(*args, **kwargs):
        out, pool = sound(*args, **kwargs)
        return out, pool.astype(jnp.bfloat16).astype(pool.dtype)

    attention._lightning_update_impl = rounded


def no_reset():
    from paddle_tpu.inference.serving.attention import RaggedCacheView
    sound = RaggedCacheView.stage_state

    def stage_state(self, dec_index, row_slots, row_pos, meta):
        meta = meta.copy()
        meta[3] = 0                  # the first-chunk flag
        return sound(self, dec_index, row_slots, row_pos, meta)

    RaggedCacheView.stage_state = stage_state


if __name__ == "__main__":
    {"fp8": fp8, "bf16_state": bf16_state, "no_reset": no_reset}[
        sys.argv[1]]()
    run.main(sys.argv[2:])
