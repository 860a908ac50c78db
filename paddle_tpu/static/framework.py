"""Static graph: Program / Block / Variable IR.

Reference parity: `paddle/fluid/framework/framework.proto` (ProgramDesc /
BlockDesc / OpDesc / VarDesc) + `python/paddle/base/framework.py`
[UNVERIFIED — empty reference mount].

TPU-native design (SURVEY.md §7 "one IR, one executor"): the Program is a
linear SSA-ish record of ops whose impls are the same pure-JAX callables the
eager engine uses.  The Executor lowers a (program, feeds, fetches) triple
to ONE jitted XLA callable — XLA plays the roles of Paddle's
stream_analyzer, memory planner, and CINN.  Ops are appended by the same
`dispatch()` the eager engine uses: when any input is a static Variable the
dispatcher routes here instead of executing.
"""
from __future__ import annotations

import contextlib
import itertools
from typing import Any, Callable, Optional

import numpy as np
import jax
import jax.numpy as jnp

from ..core.dispatch import get_dispatch_state
from ..core.dtypes import convert_dtype, to_jax_dtype
from ..core.tensor import Tensor
from .. import observability as _obs

__all__ = ["Program", "Block", "Variable", "OpDesc", "program_guard",
           "default_main_program", "default_startup_program",
           "enable_static", "disable_static", "in_dynamic_mode",
           "in_static_mode", "data", "InputSpec", "name_scope", "global_scope"]

_var_counter = itertools.count()


class Variable(Tensor):
    """Symbolic tensor in a Program.  ``_value`` holds a ShapeDtypeStruct."""

    def __init__(self, block, shape, dtype, name=None, is_data=False,
                 stop_gradient=True):
        aval = jax.ShapeDtypeStruct(tuple(shape), to_jax_dtype(dtype))
        super().__init__(aval, _internal=True, stop_gradient=stop_gradient)
        self.block = block
        self.name = name or f"var_{next(_var_counter)}"
        self.is_data = is_data
        self.desc = self

    @property
    def shape(self):
        return list(self._value.shape)

    def numpy(self):
        raise RuntimeError(
            f"Variable '{self.name}' has no value in static-graph mode; "
            "run it with an Executor.")

    def __repr__(self):
        return (f"Variable(name={self.name}, shape={self.shape}, "
                f"dtype={self.dtype.name})")


class OpDesc:
    __slots__ = ("type", "impl", "inputs", "attrs", "outputs", "scope")

    def __init__(self, type, impl, inputs, attrs, outputs, scope=None):
        self.type = type
        self.impl = impl          # pure-JAX callable
        self.inputs = inputs      # list of Variable | Tensor (captured const)
        self.attrs = attrs
        self.outputs = outputs    # list of Variable
        # the name scopes open where the op is recorded
        # (``static.name_scope``, ``observability.block``), "/"-joined,
        # unless a copy or a rewrite hands over another op's: the
        # Executor's walker re-enters them as ``jax.named_scope``
        self.scope = _obs.scope_path() if scope is None else scope

    def __repr__(self):
        ins = ", ".join(getattr(i, "name", "<const>") for i in self.inputs)
        outs = ", ".join(o.name for o in self.outputs)
        return f"{{{outs}}} = {self.type}({ins})"


class Block:
    def __init__(self, program, idx, parent_idx=-1):
        self.program = program
        self.idx = idx
        self.parent_idx = parent_idx
        self.ops = []
        self.vars = {}

    def create_var(self, shape, dtype, name=None, is_data=False,
                   stop_gradient=True):
        v = Variable(self, shape, dtype, name, is_data, stop_gradient)
        self.vars[v.name] = v
        return v

    def append_op(self, desc):
        self.ops.append(desc)

    def var(self, name):
        return self.vars[name]

    def has_var(self, name):
        return name in self.vars


class Program:
    def __init__(self):
        self.blocks = [Block(self, 0)]
        self.current_block_idx = 0
        self._seed = None
        self.random_seed = 0
        # optimizer attachment (minimize() in static mode)
        self._optimize_info = None
        self._loss_var = None

    def global_block(self):
        return self.blocks[0]

    def current_block(self):
        return self.blocks[self.current_block_idx]

    def block(self, idx):
        return self.blocks[idx]

    def list_vars(self):
        for b in self.blocks:
            yield from b.vars.values()

    def all_parameters(self):
        from ..nn.layer.layers import Parameter

        seen_ids = set()
        out = []
        for b in self.blocks:
            for op in b.ops:
                for i in op.inputs:
                    if isinstance(i, Parameter) and id(i) not in seen_ids:
                        seen_ids.add(id(i))
                        out.append(i)
        return out

    def clone(self, for_test=False):
        """Copy the Program (ops are copied, Variables/captured tensors
        shared).  ``for_test=True`` additionally rewrites train-only
        rng ops (dropout family, rrelu, attention dropout) to their
        inference impls via nn.functional's RNG_INFER_IMPLS registry —
        the reference's test-program derivation role, which matters
        here because static dropout is real (the Executor threads the
        generator state)."""
        from ..nn.functional.common import RNG_INFER_IMPLS

        p = Program()
        p.random_seed = self.random_seed
        p._seed = self._seed
        p.blocks = []
        for blk in self.blocks:
            nb = Block(p, blk.idx, blk.parent_idx)
            nb.vars = dict(blk.vars)
            for op in blk.ops:
                impl = op.impl
                if for_test and op.type in RNG_INFER_IMPLS:
                    infer = RNG_INFER_IMPLS[op.type]
                    attrs = dict(op.attrs)

                    def impl(key, *vs, _infer=infer, _at=attrs):
                        # state passes through untouched: inference
                        # consumes no randomness
                        return _infer(*vs, **_at), key
                nb.ops.append(OpDesc(op.type, impl, list(op.inputs),
                                     dict(op.attrs), list(op.outputs),
                                     scope=op.scope))
            p.blocks.append(nb)
        p.current_block_idx = min(self.current_block_idx,
                                  len(p.blocks) - 1)
        # the rng chain always transfers: rewritten inference ops pass
        # the state through untouched, and unregistered stochastic ops
        # (gumbel_softmax) must keep threading or their key would bake
        # as a constant (identical noise every run)
        if getattr(self, "_rng_chain", None):
            p._rng_chain = dict(self._rng_chain)
        if not for_test:
            # a training clone keeps its attached optimizer; for_test
            # drops it (the reference prunes backward+update ops)
            p._optimize_info = self._optimize_info
            p._loss_var = self._loss_var
        return p

    def __str__(self):
        lines = [f"Program(blocks={len(self.blocks)})"]
        for op in self.global_block().ops:
            lines.append("  " + repr(op))
        return "\n".join(lines)


_main_program = Program()
_startup_program = Program()
_static_mode = False


def default_main_program():
    return _main_program


def default_startup_program():
    return _startup_program


def in_dynamic_mode():
    return not _static_mode


def in_dygraph_mode():
    return not _static_mode


def in_static_mode():
    return _static_mode


def _static_dispatch_hook(name, impl, args, attrs):
    """Installed on dispatch when static mode is on: append an OpDesc if
    any input is a symbolic Variable — or, while a ``program_guard`` is
    building a program, a trainable tensor — else execute eagerly (e.g.
    initializers).

    Under the guard an op on a parameter alone belongs to the program
    too: run eagerly, its result is a build-time constant that takes
    the parameter's place, and the parameter is never trained.  That is
    what AMP O1's ``weight.astype(bf16)`` and an embedding lookup of
    constant positions did.  Outside a guard, arithmetic on parameters
    stays host-side surgery (loading, rescaling).  A tensor that holds
    a tracer is not build time either: it is an op's own body running
    inside the compiled step (``recompute`` re-runs its function
    there)."""
    from ..core.dispatch import dispatch, _state

    symbolic = any(isinstance(a, Variable)
                   or (_guard_depth and isinstance(a, Tensor)
                       and not a.stop_gradient
                       and not isinstance(a._value, jax.core.Tracer))
                   for a in args)
    if not symbolic:
        prev = _state.static_hook
        _state.static_hook = None
        try:
            return dispatch(name, impl, args, attrs)
        finally:
            _state.static_hook = prev

    block = default_main_program().current_block()
    tensor_inputs = [a for a in args if isinstance(a, Tensor)]
    # infer output shapes/dtypes with eval_shape (the InferMeta role)
    def absfn(*avals):
        full = list(args)
        it = iter(avals)
        for i, a in enumerate(full):
            if isinstance(a, Tensor):
                full[i] = next(it)
        return impl(*full, **attrs)

    avals = [jax.ShapeDtypeStruct(tuple(t._value.shape), t._value.dtype)
             for t in tensor_inputs]
    out_avals = jax.eval_shape(absfn, *avals)
    is_multi = isinstance(out_avals, (tuple, list))
    outs_t = tuple(out_avals) if is_multi else (out_avals,)
    out_vars = []
    stop_grad = all(t.stop_gradient for t in tensor_inputs)
    for oa in outs_t:
        out_vars.append(block.create_var(oa.shape, oa.dtype,
                                         name=f"{name}_{next(_var_counter)}",
                                         stop_gradient=stop_grad))
    block.append_op(OpDesc(name, _make_positional_impl(impl, args, attrs),
                           tensor_inputs, attrs, out_vars))
    return tuple(out_vars) if is_multi else out_vars[0]


def _make_positional_impl(impl, args, attrs):
    """Close over non-tensor positional args so the interpreter can call
    fn(*tensor_values)."""
    slots = [isinstance(a, Tensor) for a in args]
    frozen = list(args)

    def run(*tensor_vals):
        full = list(frozen)
        it = iter(tensor_vals)
        for i, is_t in enumerate(slots):
            if is_t:
                full[i] = next(it)
        return impl(*full, **attrs)

    return run


def enable_static():
    global _static_mode
    _static_mode = True
    get_dispatch_state().static_hook = _static_dispatch_hook


def disable_static():
    global _static_mode
    _static_mode = False
    get_dispatch_state().static_hook = None


_guard_depth = 0  # program_guard nesting: a program is being built


@contextlib.contextmanager
def program_guard(main_program, startup_program=None):
    global _main_program, _startup_program, _guard_depth
    prev_main, prev_startup = _main_program, _startup_program
    _main_program = main_program
    if startup_program is not None:
        _startup_program = startup_program
    _guard_depth += 1
    try:
        yield
    finally:
        _guard_depth -= 1
        _main_program = prev_main
        _startup_program = prev_startup


def name_scope(prefix):
    """paddle.static.name_scope: the ops recorded inside carry
    ``prefix`` in ``OpDesc.scope``, and the compiled step in their
    ``op_name`` metadata (``observability/blocks.py``)."""
    return _obs.name_scope(prefix)


def data(name, shape, dtype="float32", lod_level=0):
    """paddle.static.data — a feed placeholder."""
    shape = [1 if (s is None or s == -1) else s for s in shape]
    block = default_main_program().global_block()
    v = block.create_var(shape, dtype, name=name, is_data=True,
                        stop_gradient=True)
    return v


class InputSpec:
    def __init__(self, shape, dtype="float32", name=None,
                 stop_gradient=False):
        self.shape = tuple(shape)
        self.dtype = convert_dtype(dtype)
        self.name = name
        self.stop_gradient = stop_gradient

    @classmethod
    def from_tensor(cls, tensor, name=None):
        return cls(tuple(tensor.shape), tensor.dtype, name or tensor.name)

    def __repr__(self):
        return (f"InputSpec(shape={self.shape}, dtype={self.dtype.name}, "
                f"name={self.name})")


class _Scope:
    def __init__(self):
        self._vars = {}

    def var(self, name):
        return self._vars.setdefault(name, None)

    def find_var(self, name):
        return self._vars.get(name)


_global_scope = _Scope()


def global_scope():
    return _global_scope
