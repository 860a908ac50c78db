"""Static-graph AMP (bf16/fp16 program rewrite parity).

Reference parity: `python/paddle/static/amp/` — cast-insertion passes with
white/black lists (arlesniak's specialty per SURVEY.md) [UNVERIFIED — empty
reference mount].  TPU-native: the same dispatch-level caster used by eager
AMP is active while the program is being *built* (ops are appended through
dispatch), so enabling `paddle.amp.auto_cast` around program construction
inserts the casts into the program — a build-time rewrite, like the
reference pass, with bf16 as the native dtype.
"""
from __future__ import annotations

from ...amp import auto_cast, GradScaler, WHITE_LIST, BLACK_LIST

__all__ = ["decorate", "cast_model_to_fp16", "bf16", "fp16_guard",
           "CustomOpLists"]


class CustomOpLists:
    def __init__(self, custom_white_list=None, custom_black_list=None,
                 custom_black_varnames=None):
        self.white_list = set(WHITE_LIST) | set(custom_white_list or ())
        self.black_list = set(BLACK_LIST) | set(custom_black_list or ())


def decorate(optimizer, amp_lists=None, init_loss_scaling=2.0 ** 15,
             use_dynamic_loss_scaling=True, use_pure_fp16=False,
             use_fp16_guard=None, use_bf16=False, **kwargs):
    """Returns the optimizer wrapped for amp; with bf16 no scaling is
    needed so the optimizer passes through."""
    return optimizer


def cast_model_to_fp16(program, amp_lists=None, use_fp16_guard=True):
    """Pure-fp16 (O2) pass like the reference's cast_model_to_fp16:
    parameters go to fp16, black-list ops keep f32 inputs.  (fp16
    works on TPU but bf16 is the native dtype — same exponent range
    as f32, no loss scaling needed; see bf16.cast_model_to_bf16.)"""
    import jax.numpy as jnp
    for p in program.all_parameters():
        if p._value.dtype == jnp.float32:
            p._value = p._value.astype(jnp.float16)
    lists = amp_lists or CustomOpLists()
    return _rewrite_program(program, set(), lists.black_list,
                            jnp.float16)


def fp16_guard():
    import contextlib

    @contextlib.contextmanager
    def guard():
        yield

    return guard()


def _rewrite_program(program, white, black, low):
    """Post-hoc cast-insertion pass over an already-built Program (the
    reference's rewrite_program_bf16 role): inputs of white-list ops
    are cast to ``low``, inputs of black-list ops back to f32.  Cast
    ops are recorded OpDescs, so the Executor compiles them like any
    other op and jax autodiff produces f32 grads for f32 params.

    Note: downstream Variable avals keep their build-time dtypes; the
    Executor evaluates actual values, so the avals are cosmetic after
    this pass (same as the build-time auto_cast path, where the caster
    rewrites dtypes as ops are appended).
    """
    import jax.numpy as jnp
    from ..framework import OpDesc
    from ...core.tensor import Tensor

    f32 = jnp.dtype(jnp.float32)
    lowd = jnp.dtype(low)

    for block in program.blocks:
        new_ops = []
        cast_cache = {}   # (id(src), str(dtype)) -> cast output Variable
        # build-time Variable avals go stale as the pass retargets
        # dtypes, so the EFFECTIVE runtime dtype is tracked here —
        # without it, a black op downstream of a white op would
        # silently run in low precision (its aval still says f32)
        eff = {}          # id(tensor) -> effective runtime dtype

        def eff_dtype(t):
            return eff.get(id(t), jnp.dtype(t._value.dtype))

        def casted(src, dtype, scope):
            # the cast is recorded in the name scope of the op that
            # first asks for it
            key = (id(src), str(dtype))
            cv = cast_cache.get(key)
            if cv is None:
                shape = list(src._value.shape)
                cv = block.create_var(
                    shape, dtype,
                    name=f"{getattr(src, 'name', 'capt')}_cast_"
                         f"{jnp.dtype(dtype).name}",
                    stop_gradient=getattr(src, "stop_gradient", True))
                new_ops.append(OpDesc(
                    "cast", lambda v, _d=dtype: v.astype(_d),
                    [src], {}, [cv], scope=scope))
                cast_cache[key] = cv
                eff[id(cv)] = jnp.dtype(dtype)
            return cv

        for op in block.ops:
            target = None
            if op.type in white:
                target = lowd
            elif op.type in black:
                target = f32
            if target is not None:
                op.inputs = [
                    casted(i, target, op.scope)
                    if (isinstance(i, Tensor)
                        and eff_dtype(i) in (f32, lowd)
                        and eff_dtype(i) != target)
                    else i
                    for i in op.inputs]
            new_ops.append(op)
            # propagate effective dtypes: white/black force their
            # target; untouched ops follow jnp promotion (all-low
            # float inputs stay low, any f32 promotes back)
            float_ins = [eff_dtype(i) for i in op.inputs
                         if isinstance(i, Tensor)
                         and jnp.issubdtype(eff_dtype(i), jnp.floating)]
            out_d = target
            if out_d is None and float_ins and all(
                    d == lowd for d in float_ins):
                out_d = lowd
            if out_d is not None:
                for o in op.outputs:
                    if jnp.issubdtype(jnp.dtype(o._value.dtype),
                                      jnp.floating):
                        eff[id(o)] = out_d
        block.ops = new_ops
    return program


class bf16:
    """Static bf16 rewrite passes (the reference's
    `static/amp/bf16/amp_utils.py` rewrite_program_bf16 role
    [UNVERIFIED]): post-hoc cast insertion over a built Program with
    white/black lists.  The build-time path (auto_cast inside
    program_guard) covers most uses; this pass serves programs built
    without autocast (e.g. loaded/translated ones)."""

    @staticmethod
    def rewrite_program_bf16(program, amp_lists=None):
        import jax.numpy as jnp
        lists = amp_lists or CustomOpLists()
        return _rewrite_program(program, lists.white_list,
                                lists.black_list, jnp.bfloat16)

    @staticmethod
    def cast_model_to_bf16(program, amp_lists=None, use_bf16_guard=True):
        """Pure-bf16 mode: parameters themselves go to bf16; black-list
        ops keep f32 inputs via the rewrite pass."""
        import jax.numpy as jnp
        for p in program.all_parameters():
            if p._value.dtype == jnp.float32:
                p._value = p._value.astype(jnp.bfloat16)
        lists = amp_lists or CustomOpLists()
        return _rewrite_program(program, set(), lists.black_list,
                                jnp.bfloat16)

    AutoMixedPrecisionListsBF16 = CustomOpLists
