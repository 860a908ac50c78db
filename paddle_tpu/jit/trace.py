"""to_static: compile the imperative training step into one XLA executable.

Reference parity: `python/paddle/jit/` dy2static + SOT [UNVERIFIED — empty
reference mount].  Paddle captures Python bytecode / AST to build a static
program.  TPU-native redesign (SURVEY.md §7): because every eager op in this
framework bottoms out in pure JAX, the imperative step function can be
*re-traced under jax.jit directly* — state (parameters, optimizer moments,
RNG key, BN stats) is discovered on a first eager run and threaded as
inputs/outputs of a pure function.  That single executable includes forward,
tape backward, and the fused optimizer update — XLA fuses and schedules the
whole step (the StandaloneExecutor + CINN role).

Mechanics per call signature (cache key = pytree structure + shapes/dtypes):
  1. discovery run: execute eagerly, recording every external Tensor read
     (captured state) and every Tensor whose buffer is swapped (mutations).
  2. compile: jit a pure fn (args, state_in) -> (outs, state_out, grads).
  3. steady state: one compiled call per step + host-side buffer swaps.
"""
from __future__ import annotations

import functools
import re
from typing import Any, Callable

import numpy as np
import jax
import jax.numpy as jnp

from .. import observability as obs
from ..core.lazy import concrete, concrete_values
from ..core.tensor import Tensor, get_trace_ctx, set_trace_ctx


class _DiscoveryCtx:
    """Records reads/writes during the eager discovery run."""

    def __init__(self):
        self.created = set()
        self.read_order = []
        self.read_ids = set()
        self.written = []
        self.written_ids = set()

    def on_create(self, t):
        self.created.add(id(t))

    def on_read(self, t):
        if id(t) not in self.created and id(t) not in self.read_ids:
            self.read_ids.add(id(t))
            self.read_order.append(t)
        return t._value

    def on_write(self, t, old_value=None, old_node=None):
        if id(t) not in self.written_ids:
            self.written_ids.add(id(t))
            self.written.append(t)


class _ReplayCtx:
    """Substitutes tracers for captured state during jit re-trace."""

    def __init__(self, sub):
        self.sub = sub  # id(tensor) -> traced value
        self.created = set()
        self.missing = []
        # first-write snapshot of external tensors, so an aborted or
        # completed trace never leaves tracers behind in live objects
        self.write_snapshot = {}

    def on_create(self, t):
        self.created.add(id(t))

    def on_read(self, t):
        v = self.sub.get(id(t))
        if v is not None:
            return v
        if id(t) not in self.created:
            self.missing.append(t)
        return t._value

    def on_write(self, t, old_value=None, old_node=None):
        if id(t) not in self.created and id(t) not in self.write_snapshot:
            self.write_snapshot[id(t)] = (t, old_value, old_node)


class _RetraceNeeded(Exception):
    def __init__(self, missing):
        super().__init__(
            f"{len(missing)} state tensors discovered only during replay")
        self.missing = missing


def _is_tensor_leaf(x):
    return isinstance(x, Tensor)


def _tree_key(tree):
    leaves, treedef = jax.tree.flatten(tree, is_leaf=_is_tensor_leaf)
    parts = [str(treedef)]
    for leaf in leaves:
        if isinstance(leaf, Tensor):
            parts.append(f"T{tuple(leaf._value.shape)}:{leaf._value.dtype}")
        elif isinstance(leaf, jax.Array):
            parts.append(f"A{tuple(leaf.shape)}:{leaf.dtype}")
        else:
            parts.append(f"V{leaf!r}")
    return "|".join(parts)


def _tensor_arg_values(args, kwargs):
    leaves = jax.tree.flatten((args, kwargs), is_leaf=_is_tensor_leaf)[0]
    return tuple(concrete(l._value) for l in leaves
                 if isinstance(l, Tensor))


def _bind_args(args, kwargs, tensor_vals):
    """Rebuild (args, kwargs) with fresh Tensor wrappers around traced
    values; non-tensor leaves pass through unchanged (static)."""
    leaves, treedef = jax.tree.flatten((args, kwargs),
                                       is_leaf=_is_tensor_leaf)
    it = iter(tensor_vals)
    new_leaves = []
    for l in leaves:
        if isinstance(l, Tensor):
            new_leaves.append(Tensor(next(it), _internal=True,
                                     stop_gradient=l.stop_gradient))
        else:
            new_leaves.append(l)
    return jax.tree.unflatten(treedef, new_leaves)


class TracedFunction:
    """The callable returned by paddle.jit.to_static."""

    #: set on a step program: what ``observability.program_blocks()``
    #: calls its programs (a map of each is kept where it compiles),
    #: and (non-word characters as ``_``) the name of their modules in
    #: a device trace: the serving engine's step is ``engine:step`` /
    #: ``jit_engine_step``.  None: no map is kept, and the module is
    #: ``jit_<the function's name>``
    program_label = None

    def __init__(self, fn, input_spec=None, jit_kwargs=None):
        from .dy2static import convert_function
        # AST pass first (SURVEY.md:134): python if/while over traced
        # tensors become static.nn.cond/while_loop; unconvertible
        # functions keep trace semantics with a logged reason
        self._fn = convert_function(fn)
        self._orig_fn = fn  # pre-conversion python fn, for mode switches
        self._input_spec = input_spec
        self._cache = {}
        self._jit_kwargs = jit_kwargs or {}
        functools.update_wrapper(self, fn, updated=[])

    @property
    def forward(self):
        return self

    def __call__(self, *args, **kwargs):
        if get_trace_ctx() is not None:
            return self._fn(*args, **kwargs)  # nested: already tracing
        from ..memory.guard import remat_enabled
        from ..distributed.auto_parallel.sharding import plan_cache_token
        # the ladder's remat flip changes the traced program: a cached
        # no-remat executable must not serve a remat-enabled retry; the
        # mesh token keeps executables from crossing plan switches
        key = (_tree_key((args, kwargs)), remat_enabled(),
               plan_cache_token())
        comp = self._cache.get(key)
        if comp is None:
            first_result, comp = self._discover_and_compile(args, kwargs)
            self._cache[key] = comp
            return first_result
        return self._run_compiled(comp, args, kwargs)

    def analyze_program(self, *args, **kwargs):
        """Static analysis (tpu_lint) of the compiled step for a call
        signature: re-trace the cached pure function to a jaxpr (no XLA
        compile) and run the dtype/amp + weak-type audits, plus the
        recompile-risk audit over this function's trace cache.

        With arguments, analyzes that signature (it must have been
        called once already); with no arguments, analyzes the most
        recently compiled one.  Returns a
        ``paddle_tpu.analysis.DiagnosticReport``.
        """
        from ..analysis import analyze_traced
        from ..memory.guard import remat_enabled
        from ..distributed.auto_parallel.sharding import plan_cache_token
        if args or kwargs:
            key = (_tree_key((args, kwargs)), remat_enabled(),
                   plan_cache_token())
            comp = self._cache.get(key)
            if comp is None:
                raise RuntimeError(
                    "analyze_program: this call signature has not been "
                    "traced yet; call the function once first")
        else:
            if not self._cache:
                raise RuntimeError(
                    "analyze_program: nothing traced yet; call the "
                    "function once first")
            comp = next(reversed(self._cache.values()))
        with obs.span("analyze:" + comp["label"], cat="analysis"):
            jaxpr = jax.make_jaxpr(comp["pure_fn"])(*comp["avals"])
            return analyze_traced(jaxpr, label=comp["label"],
                                  trace_cache=self._cache,
                                  mesh_plan=comp.get("plan"),
                                  named_params=comp.get("spmd_named"))

    # ------------------------------------------------------------------
    def _discover_and_compile(self, args, kwargs):
        ctx = _DiscoveryCtx()
        set_trace_ctx(ctx)
        try:
            result = self._fn(*args, **kwargs)
        finally:
            set_trace_ctx(None)

        arg_leaves = [l for l in jax.tree.flatten(
            (args, kwargs), is_leaf=_is_tensor_leaf)[0]
            if isinstance(l, Tensor)]
        arg_ids = {id(l) for l in arg_leaves}
        state = [t for t in ctx.read_order if id(t) not in arg_ids]
        mutated = [t for t in ctx.written
                   if id(t) not in ctx.created and id(t) not in arg_ids]
        # params whose .grad was freshly created during the step and kept
        grad_slots = [t for t in state
                      if t.grad is not None and id(t.grad) in ctx.created]
        # Tensors created during discovery but still referenced afterwards
        # (e.g. optimizer accumulators born on the first step) surface as
        # "missing" when the replay trace reads them; the compile loop below
        # promotes them into state/mutated and re-traces (no re-execution).
        written_ids = set(ctx.written_ids)
        while True:
            try:
                comp = self._compile(args, kwargs, state, mutated,
                                     grad_slots)
                break
            except _RetraceNeeded as e:
                state_ids = {id(t) for t in state}
                mutated_ids = {id(t) for t in mutated}
                progress = False
                for t in e.missing:
                    if id(t) not in state_ids:
                        state.append(t)
                        state_ids.add(id(t))
                        progress = True
                        if id(t) in written_ids and \
                                id(t) not in mutated_ids:
                            mutated.append(t)
                            mutated_ids.add(id(t))
                if not progress:
                    raise
        return result, comp

    def _compile(self, args, kwargs, state, mutated, grad_slots):
        fn = self._fn
        touched = {id(t): t for t in state}
        for t in mutated:
            touched.setdefault(id(t), t)

        # split state into read-only vs read+written: only the latter is
        # donated to XLA (its Tensors are rebound to the outputs after
        # every call), so params/opt-state cost 1x HBM in the compiled
        # step (VERDICT r2 weak #6); read-only state buffers are reused
        # across calls and must survive.
        mutated_ids = {id(t) for t in mutated}
        rw_state = [t for t in state if id(t) in mutated_ids]
        ro_state = [t for t in state if id(t) not in mutated_ids]
        state = ro_state + rw_state

        meta = {}

        state_ids = {id(t) for t in state}

        def pure_fn(tensor_arg_vals, ro_vals, rw_vals):
            from ..core.tensor import swapped_values
            state_vals = tuple(ro_vals) + tuple(rw_vals)
            sub = {id(t): v for t, v in zip(state, state_vals)}
            rctx = _ReplayCtx(sub)
            extra = [t for t in touched.values()
                     if id(t) not in state_ids]
            with swapped_values(zip(state, state_vals),
                                save_extra=extra, save_grad=True):
                set_trace_ctx(rctx)
                try:
                    new_args, new_kwargs = _bind_args(args, kwargs,
                                                      tensor_arg_vals)
                    for t in grad_slots:
                        t.grad = None  # discovery initial conditions
                    result = fn(*new_args, **new_kwargs)
                    if rctx.missing:
                        raise _RetraceNeeded(rctx.missing)
                    out_leaves, out_treedef = jax.tree.flatten(
                        result, is_leaf=_is_tensor_leaf)
                    out_vals = tuple(
                        l._value if isinstance(l, Tensor) else l
                        for l in out_leaves)
                    mut_vals = tuple(t._value for t in mutated)
                    grad_vals = tuple(
                        t.grad._value if t.grad is not None
                        else jnp.zeros_like(t._value)
                        for t in grad_slots)
                    meta["out_treedef"] = out_treedef
                    meta["out_is_tensor"] = [isinstance(l, Tensor)
                                             for l in out_leaves]
                    meta["has_grad"] = [t.grad is not None
                                        for t in grad_slots]
                    return out_vals, mut_vals, grad_vals
                finally:
                    set_trace_ctx(None)
                    for t, ov, on in rctx.write_snapshot.values():
                        t._value = ov
                        t._grad_node = on

        from ..framework.flags import get_flags
        jit_kwargs = dict(self._jit_kwargs)
        if get_flags("FLAGS_buffer_donation")["FLAGS_buffer_donation"]:
            jit_kwargs.setdefault("donate_argnums", (2,))
        arg_vals = _tensor_arg_values(args, kwargs)
        # pending lazy values cannot cross a jit boundary as arguments
        ro_vals = concrete_values(ro_state)
        rw_vals = concrete_values(rw_state)
        # SPMD mesh plan: tensor args batch-shard over the data axes,
        # state lays out by partition rule (all-replicated with no rules
        # — pure DP); output shardings are left to the partitioner so
        # donated rw state keeps its input layout
        from ..distributed.auto_parallel import sharding as spmd
        plan = spmd.get_mesh_plan()
        arg_shardings = state_shardings = None
        if plan is not None:
            ns = plan.sharding
            arg_shardings = tuple(ns(plan.batch_spec(v.shape))
                                  for v in arg_vals)
            ro_sh = tuple(ns(plan.spec_for(spmd.spmd_name(t),
                                           tuple(t._value.shape)))
                          for t in ro_state)
            rw_sh = tuple(ns(plan.spec_for(spmd.spmd_name(t),
                                           tuple(t._value.shape)))
                          for t in rw_state)
            state_shardings = (ro_sh, rw_sh)
            jit_kwargs["in_shardings"] = (arg_shardings, ro_sh, rw_sh)
            # place once: state buffers then stay sharded across calls
            for tensors, shs in ((ro_state, ro_sh), (rw_state, rw_sh)):
                for t, sh in zip(tensors, shs):
                    if getattr(t._value, "sharding", None) != sh:
                        t._value = jax.device_put(concrete(t._value), sh)
            ro_vals = concrete_values(ro_state)
            rw_vals = concrete_values(rw_state)
            arg_vals = tuple(jax.device_put(v, sh) for v, sh in
                             zip(arg_vals, arg_shardings))
        label = f"jit:{getattr(self._orig_fn, '__qualname__', self._fn)}"
        # the module's name is what a device trace knows the program by
        pure_fn.__name__ = re.sub(r"\W", "_", self.program_label or getattr(
            self._orig_fn, "__name__", "pure_fn"))
        jitted = jax.jit(pure_fn, **jit_kwargs)
        flow = obs.next_flow_id()
        from ..device.compile_cache import (compile_keyed_by_metadata,
                                            ensure_compile_cache,
                                            record_compile_metrics)
        ensure_compile_cache()
        import time as _time
        t0 = _time.perf_counter()
        with obs.span("compile:" + label, cat="compile", flow_out=flow,
                      n_state=len(state)):
            lowered = jitted.lower(arg_vals, ro_vals, rw_vals)
            compiled = compile_keyed_by_metadata(lowered) \
                if self.program_label else lowered.compile()
        record_compile_metrics((_time.perf_counter() - t0) * 1e3,
                               kind="to_static")
        if self.program_label:
            obs.note_program(self.program_label, compiled)
        # memory guard pre-flight: hold the fresh executable to the HBM
        # budget before its first dispatch (raises HbmBudgetError).  The
        # async window keeps up to depth-1 extra steps' args/outputs
        # live; the guard accounts for them.
        from ..core.pipeline import pipeline_depth
        from ..memory.estimator import named_buffer_sizes
        from ..memory.guard import preflight_check

        def _nbytes(vals):
            n = 0
            for v in vals:
                try:
                    n += int(v.size) * v.dtype.itemsize
                except Exception:
                    pass
            return n

        named_buffers = named_buffer_sizes(
            [(f"state:{t.name or ('tensor_%d' % i)}", t)
             for i, t in enumerate(state)])
        if plan is not None:
            # per-DEVICE charge: sharded state divides by its axis-size
            # product, replicated state is charged whole
            flat_sh = dict(zip(
                (f"state:{t.name or ('tensor_%d' % i)}"
                 for i, t in enumerate(state)),
                (plan.spec_for(spmd.spmd_name(t), tuple(t._value.shape))
                 for t in state)))
            named_buffers = [
                (n, sz // plan.shard_factor(flat_sh.get(n)))
                for n, sz in named_buffers]
        estimate = preflight_check(
            compiled, program=label,
            named_buffers=named_buffers,
            pipeline_depth=pipeline_depth(),
            per_step_io_bytes=_nbytes(arg_vals),
            # state this step already carries (e.g. the serving KV pool
            # as donated rw_state) is in argument_bytes; don't let a
            # registered resident charge it twice
            resident_skip_ids={id(v) for v in (*ro_vals, *rw_vals)})
        def _avalize(vals):
            return tuple(jax.ShapeDtypeStruct(tuple(v.shape), v.dtype)
                         for v in vals)

        return {
            "compiled": compiled,
            "label": label,
            "flow": flow,
            "estimate": estimate,
            # for analyze_program: re-trace to a jaxpr without compiling
            "pure_fn": pure_fn,
            "avals": (_avalize(arg_vals), _avalize(ro_vals),
                      _avalize(rw_vals)),
            "ro_state": ro_state,
            "rw_state": rw_state,
            "mutated": mutated,
            "grad_slots": grad_slots,
            "plan": plan,
            "arg_shardings": arg_shardings,
            "spmd_named": [(spmd.spmd_name(t), tuple(t._value.shape),
                            int(np.prod(t._value.shape))
                            * t._value.dtype.itemsize)
                           for t in state] if plan is not None else None,
            "out_treedef": meta["out_treedef"],
            "out_is_tensor": meta["out_is_tensor"],
            "has_grad": meta["has_grad"],
        }

    def _run_compiled(self, comp, args, kwargs):
        arg_vals = _tensor_arg_values(args, kwargs)
        if comp.get("arg_shardings"):
            arg_vals = tuple(
                v if getattr(v, "sharding", None) == sh
                else jax.device_put(v, sh)
                for v, sh in zip(arg_vals, comp["arg_shardings"]))
        ro_vals = concrete_values(comp["ro_state"])
        rw_vals = concrete_values(comp["rw_state"])
        from ..memory.guard import oom_context
        with obs.span(comp["label"], cat="dispatch",
                      flow_in=comp["flow"],
                      **({"mesh": comp["plan"].describe()}
                         if comp.get("plan") is not None else {})), \
                oom_context(program=comp["label"],
                            estimate=comp["estimate"]):
            out_vals, mut_vals, grad_vals = comp["compiled"](
                arg_vals, ro_vals, rw_vals)
        # bound the async dispatch pipeline: at most depth-1 older steps
        # stay un-synchronized (PADDLE_TPU_PIPELINE_DEPTH); outputs stay
        # live device arrays — reading them is still the sync point.
        # mut_vals are not admitted: they get donated to the next call.
        from ..core.pipeline import get_window
        get_window().admit(
            tuple(v for v in out_vals if isinstance(v, jax.Array)),
            label=comp["label"])
        for t, v in zip(comp["mutated"], mut_vals):
            t._value = v
            t._grad_node = None
        for t, v, hg in zip(comp["grad_slots"], grad_vals,
                            comp["has_grad"]):
            if hg:
                if t.grad is None:
                    t.grad = Tensor(v, _internal=True, stop_gradient=True)
                else:
                    t.grad._value = v
            else:
                t.grad = None
        out_leaves = [
            Tensor(v, _internal=True, stop_gradient=True) if is_t else v
            for v, is_t in zip(out_vals, comp["out_is_tensor"])]
        return jax.tree.unflatten(comp["out_treedef"], out_leaves)


def to_static(function=None, input_spec=None, build_strategy=None,
              backend=None, full_graph=True, **kwargs):
    """paddle.jit.to_static — decorator or call form.

    ``full_graph=True`` (default): AST translation + jax trace — one
    whole-program compile, Python-free steady state (jit/dy2static.py).
    ``full_graph=False``: SOT-mode piecewise capture with graph breaks
    at data-dependent Python (jit/sot.py — the reference's `jit/sot/`
    bytecode translator role, rebuilt on the lazy-eager engine).
    """

    def decorate(fn):
        from ..nn.layer.layers import Layer
        from .sot import SotFunction, sot_capture

        if not full_graph or backend == "sot":
            if isinstance(fn, SotFunction):
                return fn
            if isinstance(fn, TracedFunction):
                # mode switch: unwrap back to the python function so the
                # SOT request isn't silently ignored
                fn = fn._orig_fn
            if isinstance(fn, Layer):
                fwd = fn.forward
                fn.forward = sot_capture(
                    fwd._orig_fn if isinstance(fwd, TracedFunction)
                    else fwd)
                return fn
            return sot_capture(fn)

        if isinstance(fn, SotFunction):
            fn = fn._fn  # mode switch: SOT -> full-graph AST trace
        if isinstance(fn, TracedFunction):
            if input_spec is None:
                return fn
            fn = fn._orig_fn  # re-trace under the new input_spec

        if isinstance(fn, Layer):
            fwd = fn.forward
            if isinstance(fwd, SotFunction):
                fwd = fwd._fn  # mode switch on a SOT-captured Layer
            if isinstance(fwd, TracedFunction):
                if input_spec is None:
                    return fn
                fwd = fwd._orig_fn  # re-trace under the new input_spec
            fn.forward = TracedFunction(fwd, input_spec)
            return fn
        return TracedFunction(fn, input_spec)

    if function is not None:
        return decorate(function)
    return decorate


def not_to_static(fn):
    fn._not_to_static = True
    return fn
