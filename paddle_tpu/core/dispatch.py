"""Op dispatch: the KernelFactory equivalent, TPU-native.

Reference parity: Paddle routes every op through generated ``*_ad_func`` →
phi KernelFactory (backend, layout, dtype) → kernel (`paddle/phi/core/
kernel_factory.h`, `paddle/fluid/eager/` [UNVERIFIED — empty reference
mount]).  Here there is exactly ONE backend — XLA — so "kernel selection"
collapses: every op has a pure-JAX ``impl(*arrays, **attrs)``; dispatch
decides only (a) eager vs static-graph capture and (b) whether to record a
GradNode via ``jax.vjp``.

AMP hook: like the generated AMP branch in Paddle's dygraph functions, the
amp module installs a caster that rewrites input dtypes per op white/black
lists before the impl runs.
"""
from __future__ import annotations

import os
import threading
import time
from collections import OrderedDict
from typing import Any, Callable, Optional, Sequence

import jax
import jax.numpy as jnp

from . import autograd
from . import lazy as _lazy
from .dtypes import to_paddle_dtype
from ..observability.timeline import enabled as _obs_enabled

__all__ = ["dispatch", "OpDef", "OP_REGISTRY", "register_op"]


class OpDef:
    __slots__ = ("name", "impl", "n_outputs", "differentiable")

    def __init__(self, name, impl, n_outputs=1, differentiable=True):
        self.name = name
        self.impl = impl
        self.n_outputs = n_outputs
        self.differentiable = differentiable


OP_REGISTRY: dict[str, OpDef] = {}


def register_op(name, impl, n_outputs=1, differentiable=True):
    op = OpDef(name, impl, n_outputs, differentiable)
    OP_REGISTRY[name] = op
    return op


class _DispatchState(threading.local):
    def __init__(self):
        # static-graph capture hook: fn(name, impl, args, attrs) -> outputs
        self.static_hook = None
        # AMP caster: fn(name, tensor_args) -> tensor_args
        self.amp_caster = None


_state = _DispatchState()

# ---------------------------------------------------------------------
# Eager per-op executable cache (SURVEY.md §3.1: per-op dispatch is THE
# dygraph bottleneck).  Instead of tracing jax.vjp anew and executing
# the op primitive-by-primitive on every eager call, each (op, impl
# code, static args, input avals) signature gets ONE jitted
# forward(+vjp) executable; jax.vjp's returned function is a pytree
# (residual arrays + static structure), so it crosses the jit boundary
# and a single shared jitted applier runs the backward.  Ops whose impl
# closes over free variables, or with unhashable statics, fall back to
# the plain eager path (the cache must key all behavior).
# ---------------------------------------------------------------------
_EAGER_JIT_MAX = 4096
# Bounded LRUs: a long-running dynamic workload must keep caching its
# CURRENT working set.  The old insert-stop policy froze the cache at
# the first _EAGER_JIT_MAX signatures — every later op silently lost
# caching forever (re-traced per call).  Hits refresh recency; inserts
# past the cap evict the least-recently-dispatched signature and count
# into stats/`eager.cache_evictions`.
_eager_fwd_cache: OrderedDict = OrderedDict()
_eager_vjp_cache: OrderedDict = OrderedDict()
cache_evictions = {"fwd": 0, "vjp": 0}
_bwd_apply = None


def _cache_get(cache, key):
    v = cache.get(key)
    if v is not None:
        cache.move_to_end(key)
    return v


def _cache_put(cache, key, val, lane):
    cache[key] = val
    if len(cache) > _EAGER_JIT_MAX:
        cache.popitem(last=False)
        cache_evictions[lane] += 1
        if _obs_enabled():
            from ..observability.registry import get_registry
            get_registry().counter("eager.cache_evictions").inc()

# dtype -> str(dtype) memo: numpy dtype __str__ allocates on every call
# and _jit_key stringifies every operand's dtype on every eager dispatch
# — at trace-cache-hit steady state that was a measurable slice of the
# 1000x eager overhead (lenet_dygraph triage).
_DTYPE_STR: dict = {}


def _dtype_str(dt):
    s = _DTYPE_STR.get(dt)
    if s is None:
        s = _DTYPE_STR[dt] = str(dt)
    return s


# Live per-op cache-fragmentation watch at the insert sites: an op
# accumulating many jitted variants is quietly recompiling instead of
# hitting its cache.  Crossing the threshold records the TPU202/TPU203
# classification from analysis.audit_eager_cache once per op.
_FRAG_THRESHOLD = int(os.environ.get(
    "PADDLE_TPU_EAGER_FRAG_THRESHOLD", "16"))
_frag_counts: dict = {}
_frag_flagged: set = set()


def _note_cache_insert(name):
    n = _frag_counts.get(name, 0) + 1
    _frag_counts[name] = n
    if n != _FRAG_THRESHOLD or name in _frag_flagged:
        return
    _frag_flagged.add(name)
    from ..analysis.diagnostics import record
    from ..analysis.recompile import audit_eager_cache
    merged = {**_eager_fwd_cache, **_eager_vjp_cache}
    for d in audit_eager_cache(cache=merged, per_op_threshold=1):
        if d.site == f"eager:{name}":
            record(d)


def _get_bwd_apply():
    global _bwd_apply
    if _bwd_apply is None:
        _bwd_apply = jax.jit(lambda vjp_fn, cts: vjp_fn(cts))
    return _bwd_apply


_HASHABLE = (bool, int, float, str, bytes, type(None), slice,
             type(Ellipsis))


def _static_sig(v):
    import numpy as _np
    if isinstance(v, slice):
        return ("slice", v.start, v.stop, v.step)
    if isinstance(v, _HASHABLE):
        # type tag: 2, 2.0 and True hash/compare equal but trace to
        # different graphs (dtype promotion)
        return (type(v).__name__, v)
    if isinstance(v, _np.generic):
        return (type(v).__name__, v.item())
    if isinstance(v, _np.dtype):
        # dtype-valued attrs (cast's target dtype): without this, cast
        # had no cache key at all — every AMP cast re-traced per call
        # and, under the lazy tier, forced a segment flush
        return ("dtype", v.str)
    if isinstance(v, type) and issubclass(v, _np.generic):
        return ("dtype", v.__name__)
    if isinstance(v, (tuple, list)):
        return tuple(_static_sig(x) for x in v)
    raise TypeError


def _cell_sig(v, _depth=0):
    """Hashable signature for one closure cell; TypeError when the cell
    holds anything whose behavior the key could not capture."""
    if callable(v) and hasattr(v, "__code__"):
        cells = v.__closure__ or ()
        if _depth > 3:
            raise TypeError
        return ("fn", v.__code__, tuple(
            _cell_sig(c.cell_contents, _depth + 1) for c in cells))
    return _static_sig(v)


def _jit_key(name, impl, args, tensor_idx, arrays, attrs):
    from ..framework.flags import get_flags
    if not get_flags("FLAGS_eager_op_jit")["FLAGS_eager_op_jit"]:
        return None
    code = getattr(impl, "__code__", None)
    if code is None:
        # builtins / jnp ufuncs: no closure to worry about; key on the
        # (hashable) callable itself
        try:
            hash(impl)
        except TypeError:
            return None
        code = impl
    elif code.co_freevars:
        # closures over hashable config (conv dimension specs etc.) are
        # cacheable: the cell values ride in the key.  Function-valued
        # cells (the _rng_op wrapper around dropout impls) key by their
        # code objects.  Anything else (tensors, mutable state) keeps
        # the op out of the caches.  Empty cells raise ValueError.
        try:
            free = tuple(_cell_sig(c.cell_contents)
                         for c in impl.__closure__)
        except (TypeError, ValueError):
            return None
        code = (code, free)
    tset = set(tensor_idx)
    try:
        statics = tuple(
            (i, _static_sig(a)) for i, a in enumerate(args)
            if i not in tset)
        attr_sig = tuple(sorted(
            (k, _static_sig(v)) for k, v in attrs.items()))
    except TypeError:
        return None
    aval_sig = tuple((v.shape, _dtype_str(v.dtype)) for v in arrays)
    return (name, code, statics, attr_sig, aval_sig)


def get_dispatch_state():
    return _state


def _is_float(v) -> bool:
    return jnp.issubdtype(v.dtype, jnp.floating) or jnp.issubdtype(
        v.dtype, jnp.complexfloating
    )


def dispatch(name: str, impl: Callable, args: Sequence[Any], attrs=None,
             differentiable: bool = True):
    """Run op ``name``.

    ``args`` may mix Tensors and raw python values (scalars keep JAX weak-type
    promotion).  Returns Tensor or tuple of Tensors mirroring impl's output.

    With observability on, eager dispatches feed the
    ``eager.dispatch_us`` histogram (host-side overhead per op — the
    metric behind the lenet_dygraph 1000x triage); off, the timing
    costs one global read.
    """
    if _obs_enabled() and _state.static_hook is None:
        t0 = time.perf_counter()
        try:
            return _dispatch(name, impl, args, attrs, differentiable)
        finally:
            from ..observability.registry import get_registry
            get_registry().histogram("eager.dispatch_us").observe(
                (time.perf_counter() - t0) * 1e6)
    return _dispatch(name, impl, args, attrs, differentiable)


def _dispatch(name: str, impl: Callable, args: Sequence[Any], attrs,
              differentiable: bool):
    from .tensor import Tensor

    attrs = attrs or {}

    # AMP runs BEFORE the static hook: auto_cast inside program_guard
    # must record cast ops into the Program (the reference's static AMP
    # pass role).  Variables are Tensors with aval _values, so the
    # caster's dtype checks work symbolically.  Round-5 window-3 found
    # the opposite order silently building all-f32 "AMP" programs.
    if _state.amp_caster is not None:
        args = _state.amp_caster(name, args)

    if _state.static_hook is not None:
        return _state.static_hook(name, impl, args, attrs)

    tensor_idx = [i for i, a in enumerate(args) if isinstance(a, Tensor)]
    tensors = [args[i] for i in tensor_idx]
    arrays = [t.value() for t in tensors]

    needs = [
        (not t.stop_gradient) and _is_float(v)
        for t, v in zip(tensors, arrays)
    ]
    record = (
        differentiable
        and autograd.is_grad_enabled()
        and any(needs)
    )

    key = _jit_key(name, impl, args, tensor_idx, arrays, attrs)

    # ---- lazy eager (SURVEY §7): record instead of dispatching ----
    if _lazy._EVER_ENABLED:  # keep the default hot path untouched
        if (key is not None and _lazy.lazy_enabled()
                and not any(isinstance(a, jax.core.Tracer)
                            for a in arrays)):
            out = _lazy_dispatch(name, impl, args, attrs, tensor_idx,
                                 tensors, arrays, needs, record, key)
            if out is not _LAZY_UNSUPPORTED:
                return out
        # fallback paths need concrete arrays (jax.vjp rejects LazyValue)
        arrays = [_lazy.concrete(a) for a in arrays]

    if not record:
        if key is not None:
            cached = _cache_get(_eager_fwd_cache, key)
            if cached is None:
                # None at tensor slots: the closure must not pin the
                # first call's Tensors (and their autograd graphs)
                template = [None if i in set(tensor_idx) else a
                            for i, a in enumerate(args)]

                def pure_fwd(*arrs, _t=template, _ti=tuple(tensor_idx),
                             _impl=impl, _attrs=attrs):
                    full = list(_t)
                    for i, v in zip(_ti, arrs):
                        full[i] = v
                    return _impl(*full, **_attrs)

                cached = jax.jit(pure_fwd)
                _cache_put(_eager_fwd_cache, key, cached, "fwd")
                _note_cache_insert(name)
            if cached is not None:
                return _wrap(cached(*arrays), name, node=None)
        full = list(args)
        for i, v in zip(tensor_idx, arrays):
            full[i] = v
        outs = impl(*full, **attrs)
        return _wrap(outs, name, node=None)

    def fn(*arrs):
        full = list(args)
        for i, v in zip(tensor_idx, arrs):
            full[i] = v
        return impl(*full, **attrs)

    if key is not None:
        cached = _cache_get(_eager_vjp_cache, key)
        if cached is None:
            template = [None if i in set(tensor_idx) else a
                        for i, a in enumerate(args)]

            def pure_pair(*arrs, _t=template, _ti=tuple(tensor_idx),
                          _impl=impl, _attrs=attrs):
                def f(*inner):
                    full = list(_t)
                    for i, v in zip(_ti, inner):
                        full[i] = v
                    return _impl(*full, **_attrs)
                return jax.vjp(f, *arrs)

            cached = jax.jit(pure_pair)
            _cache_put(_eager_vjp_cache, key, cached, "vjp")
            _note_cache_insert(name)
        if cached is not None:
            outs, raw_vjp = cached(*arrays)
            apply = _get_bwd_apply()

            def vjp_fn(cts, _raw=raw_vjp, _apply=apply):
                return _apply(_raw, cts)

            is_multi = isinstance(outs, (tuple, list))
            outs_t = tuple(outs) if is_multi else (outs,)
            node = autograd.GradNode(
                name, vjp_fn, tensors, needs, len(outs_t),
                [(o.shape, o.dtype) for o in outs_t])
            return _wrap(outs, name, node=node)

    outs, vjp_fn = jax.vjp(fn, *arrays)
    is_multi = isinstance(outs, (tuple, list))
    outs_t = tuple(outs) if is_multi else (outs,)
    node = autograd.GradNode(
        name,
        vjp_fn,
        tensors,
        needs,
        len(outs_t),
        [(o.shape, o.dtype) for o in outs_t],
    )
    return _wrap(outs, name, node=node)


_LAZY_UNSUPPORTED = object()


class _NoneOutputs(Exception):
    pass


# (name, code-sig) pairs whose python scalars must stay static: hoisting
# them to traced leaves made abstract eval fail (shape-/value-dependent
# scalars — axis args, output sizes).  Learned once, then permanent.
_NO_HOIST: set = set()


def _lazy_dispatch(name, impl, args, attrs, tensor_idx, tensors, arrays,
                   needs, record, key):
    """Record the op into the lazy segment buffer; no device dispatch.

    Bare python int/float positionals (scale factors, loop counters —
    ``x * lr_t``) are hoisted to weak-typed traced leaves so a changing
    scalar does NOT change the node key, and a training loop whose only
    per-step difference is a counter fingerprints to the SAME segment.
    Ops whose scalars are load-bearing for shapes fail the hoisted
    abstract eval once, land in _NO_HOIST, and keep them static.

    Returns _LAZY_UNSUPPORTED when the op cannot be abstractly
    evaluated at all (host-value-dependent impls) — caller falls
    through to the immediate path."""
    from . import lazy as _lazy

    name_, code, statics, attr_sig, aval_sig = key
    tset = set(tensor_idx)
    hoist = tuple(i for i, a in enumerate(args)
                  if i not in tset and type(a) in (int, float))
    if hoist and (name, code) not in _NO_HOIST:
        try:
            hvals = [jnp.asarray(args[i]) for i in hoist]
            hset = set(hoist)
            lkey = (name_, code,
                    tuple(s for s in statics if s[0] not in hset),
                    attr_sig,
                    aval_sig + tuple(
                        ((), _dtype_str(v.dtype), True) for v in hvals),
                    True)
            return _lazy_record(name, impl, args, attrs, tensor_idx,
                                tensors, arrays, needs, record, lkey,
                                hoist, hvals)
        except Exception:
            _NO_HOIST.add((name, code))
    try:
        return _lazy_record(name, impl, args, attrs, tensor_idx,
                            tensors, arrays, needs, record, key, (), [])
    except Exception:
        return _LAZY_UNSUPPORTED


def _lazy_record(name, impl, args, attrs, tensor_idx, tensors, arrays,
                 needs, record, lkey, hoist, hvals):
    from . import lazy as _lazy

    # ONE big-tuple hash per dispatch: the structural key is interned to
    # an int here; the abs_eval cache, the node key and the segment
    # fingerprint all ride on the int
    kid = _lazy._intern_key(lkey)
    tset = set(tensor_idx) | set(hoist)
    template = [None if i in tset else a for i, a in enumerate(args)]
    ext_idx = tuple(tensor_idx) + hoist
    ext_arrays = list(arrays) + hvals
    in_avals = [_lazy._aval_of(a) for a in ext_arrays]
    meta = _lazy.abs_eval(kid, record, template, ext_idx, attrs,
                          impl, in_avals, n_diff=len(tensor_idx))
    if record and any(meta["none_mask"]):
        raise _NoneOutputs(name)

    lazy_outs = _lazy.record_node(meta["run"], ext_arrays,
                                  meta["all_avals"],
                                  ("fwd", kid, record),
                                  label=name, raw_key=lkey)
    n_out = len(meta["out_avals"])
    outs = lazy_outs[:n_out]

    if not record:
        if meta["is_multi"]:
            full, it = [], iter(outs)
            for isnone in meta["none_mask"]:
                full.append(None if isnone else next(it))
            return _wrap(full, name, node=None)
        return _wrap(outs[0], name, node=None)

    res_vals = lazy_outs[n_out:]
    vjp_fn = _lazy.make_lazy_vjp(kid, res_vals, meta["treedef"],
                                 meta["out_struct"])
    node = autograd.GradNode(
        name, vjp_fn, tensors, needs, n_out,
        [(o.shape, o.dtype) for o in outs])
    return _wrap(tuple(outs) if meta["is_multi"] else outs[0], name,
                 node=node)


_cpu_mesh = None  # resolved once: CPU backend with several devices


def settle_cpu_collectives(outs):
    """Wait for eager results that span several CPU devices.

    jaxlib 0.9's CPU client runs the partitions of an SPMD program on a
    pool of max(cores, devices) threads, and a partition of the NEXT
    program can hold a thread while it waits for its inputs.  With as
    many virtual devices as cores, the last partition of a program with
    an all-reduce then never gets a thread: the rendezvous deadlocks
    and XLA aborts the process after 40 s ("Expected 8 threads to join
    the rendezvous, but only 7 of them arrived").  Two jitted functions
    dispatched back to back in a loop reproduce it with no paddle code;
    waiting on the first one's result makes it go away.  Eager per-op
    dispatch is exactly that pattern, so on the CPU backend a
    multi-device result is waited for before the next op goes out.  A
    chip runs each device's programs in order on its own stream and is
    not affected: there this returns at once.
    """
    global _cpu_mesh
    if _cpu_mesh is None:
        _cpu_mesh = (jax.default_backend() == "cpu"
                     and jax.device_count() > 1)
    if not _cpu_mesh:
        return
    for o in outs:
        if (isinstance(o, jax.Array)
                and not isinstance(o, jax.core.Tracer)
                and len(o.sharding.device_set) > 1):
            o.block_until_ready()


def _wrap(outs, name, node):
    from .tensor import Tensor

    is_multi = isinstance(outs, (tuple, list))
    outs_t = tuple(outs) if is_multi else (outs,)
    settle_cpu_collectives(outs_t)
    wrapped = []
    for i, o in enumerate(outs_t):
        if o is None:
            wrapped.append(None)
            continue
        t = Tensor(o, stop_gradient=(node is None), _internal=True)
        if node is not None:
            t._grad_node = node
            t._out_index = i
        wrapped.append(t)
    if is_multi:
        return tuple(wrapped)
    return wrapped[0]
