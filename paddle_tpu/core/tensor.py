"""The eager Tensor: Paddle semantics over a jax.Array.

Reference parity: eager Tensor / DenseTensor (`paddle/phi/core/dense_tensor.h`,
`paddle/fluid/eager/` eager tensor wrapper, pybind `eager_method.cc`
[UNVERIFIED — empty reference mount]).

Design (SURVEY.md §7): a Tensor owns a ``jax.Array`` (device HBM buffer via
PJRT) plus autograd metadata (``stop_gradient``, ``grad``, ``_grad_node``).
Mutation (``set_value``, in-place ops, optimizer updates) swaps the underlying
buffer — under ``paddle.jit.to_static`` tracing these swaps are captured as
state outputs, which is how the imperative surface compiles to one pure XLA
program.
"""
from __future__ import annotations

import threading
from typing import Optional

import numpy as np
import jax
import jax.numpy as jnp

from ..observability.timeline import span as _span
from . import autograd
from . import lazy as _lazy
from .dtypes import DType, convert_dtype, to_jax_dtype, to_paddle_dtype, default_dtype
from .place import CPUPlace, Place, TPUPlace, current_place

__all__ = ["Tensor", "to_tensor"]


class _TraceState(threading.local):
    def __init__(self):
        self.ctx = None  # set by paddle_tpu.jit tracing


_trace_state = _TraceState()


def get_trace_ctx():
    return _trace_state.ctx


def set_trace_ctx(ctx):
    _trace_state.ctx = ctx


_tensor_counter = [0]

# Serializes every "swap tensor._value for traced values, run, restore"
# region (jit/trace.py, the pipeline engines' pure sections): the trick
# temporarily puts tracers into LIVE layer objects, so a second thread
# touching the same layers mid-trace would read tracers.  All swap
# users must hold this lock for the whole swap-run-restore span.
value_swap_lock = threading.RLock()


import contextlib as _contextlib


@_contextlib.contextmanager
def swapped_values(swap, save_extra=(), save_grad=False):
    """THE swap-run-restore protocol, shared by every user of the
    tensor._value substitution trick (to_static tracing, the pipeline
    engines' pure sections, scan_layer_stack).

    ``swap``: iterable of (tensor, new_value) pairs substituted for the
    body.  ``save_extra``: additional tensors whose value/grad linkage
    must survive the body (mutation targets).  ``save_grad``: also
    snapshot/restore ``.grad``.  Everything happens under
    ``value_swap_lock`` with no pre-try window, so an exception anywhere
    restores state and releases the lock."""
    with value_swap_lock:
        swap = list(swap)
        tensors = [t for t, _ in swap] + list(save_extra)
        saved = [(t, t._value, t._grad_node,
                  t.grad if save_grad else None) for t in tensors]
        try:
            for t, v in swap:
                t._value = v
            yield
        finally:
            for t, v, gn, gr in saved:
                t._value = v
                t._grad_node = gn
                if save_grad:
                    t.grad = gr


class Tensor:
    __slots__ = (
        "_value", "stop_gradient", "grad", "_grad_node", "_out_index",
        "name", "persistable", "_backward_hooks", "is_leaf_param",
        "__weakref__", "__dict__",
    )

    def __init__(self, data, dtype=None, place=None, stop_gradient=True,
                 _internal=False):
        if _internal:
            self._value = data
        else:
            self._value = _to_array(data, dtype, place)
        self.stop_gradient = stop_gradient
        self.grad = None
        self._grad_node = None
        self._out_index = 0
        _tensor_counter[0] += 1
        self.name = f"generated_tensor_{_tensor_counter[0]}"
        self.persistable = False
        self._backward_hooks = None
        self.is_leaf_param = False
        ctx = _trace_state.ctx
        if ctx is not None:
            ctx.on_create(self)

    # ---- value access (trace-capture aware) ----
    def value(self):
        ctx = _trace_state.ctx
        if ctx is not None:
            from .lazy import LazyValue
            if isinstance(self._value, LazyValue):
                # a to_static trace must capture the concrete buffer,
                # not a half-built lazy segment
                self._value = self._value.force()
            return ctx.on_read(self)
        return self._value

    def _local_value_update(self, new_value):
        """Internal buffer swap that bypasses autograd (grad accumulation)."""
        self._value = new_value

    def _inplace_update(self, new_value, node=None, out_index=0):
        """In-place semantic update: swaps buffer and autograd metadata."""
        ctx = _trace_state.ctx
        if ctx is not None:
            ctx.on_write(self, self._value, self._grad_node)
        if _lazy._ENABLED:
            # optimizer param updates replace concrete buffers with
            # pending LazyValues: the old buffer is a donation candidate
            # for the flushed segment (params cost 1x HBM per step)
            _lazy.note_donation(self._value, new_value)
        self._value = new_value
        self._grad_node = node
        self._out_index = out_index

    def set_value(self, value):
        if isinstance(value, Tensor):
            v = value.value()
        else:
            v = _to_array(value, self.dtype, None)
        v = jnp.asarray(v, self._value.dtype)
        if tuple(v.shape) != tuple(self._value.shape):
            v = jnp.broadcast_to(v, self._value.shape)
        self._inplace_update(v)
        return self

    # ---- basic properties ----
    @property
    def shape(self):
        return list(self._value.shape)

    @property
    def ndim(self):
        return self._value.ndim

    @property
    def dim(self):
        return self._value.ndim

    @property
    def rank(self):
        return self._value.ndim

    @property
    def size(self):
        return int(np.prod(self._value.shape)) if self._value.shape else 1

    @property
    def dtype(self) -> DType:
        return to_paddle_dtype(self._value.dtype)

    @property
    def place(self) -> Place:
        try:
            dev = list(self._value.devices())[0]
            if dev.platform == "cpu":
                return CPUPlace()
            return TPUPlace(dev.id)
        except Exception:
            return current_place()

    @property
    def is_leaf(self):
        return self._grad_node is None

    @property
    def T(self):
        from .. import ops
        return ops.linalg.t(self)

    @property
    def mT(self):
        from .. import ops
        perm = list(range(self.ndim))
        perm[-2], perm[-1] = perm[-1], perm[-2]
        return ops.manipulation.transpose(self, perm)

    def numel(self):
        return to_tensor(self.size, dtype="int64")

    def element_size(self):
        return self.dtype.itemsize

    # ---- host interop ----
    def numpy(self):
        # every host read: forces a lazy value, waits for the device
        # and copies (the ``sync:read`` boundary span)
        with _span("sync:read", cat="d2h", boundary=True):
            return np.asarray(self._value)

    def __array__(self, dtype=None):
        a = np.asarray(self._value)
        return a.astype(dtype) if dtype is not None else a

    def item(self, *args):
        if args:
            return self.numpy().item(*args)
        return self.numpy().item()

    def tolist(self):
        return self.numpy().tolist()

    # ---- autograd ----
    def backward(self, grad_tensor=None, retain_graph=False):
        autograd.backward([self], [grad_tensor], retain_graph=retain_graph)

    def clear_grad(self, set_to_zero=False):
        if set_to_zero and self.grad is not None:
            self.grad._local_value_update(jnp.zeros_like(self.grad._value))
        else:
            self.grad = None

    clear_gradient = clear_grad

    def detach(self):
        t = Tensor(self._value, _internal=True, stop_gradient=True)
        t.name = self.name + "@detach"
        return t

    def detach_(self):
        self._grad_node = None
        self.stop_gradient = True
        return self

    def register_hook(self, hook):
        if self._backward_hooks is None:
            self._backward_hooks = []
        self._backward_hooks.append(hook)

        class _Removable:
            def __init__(self, hooks, h):
                self._hooks, self._h = hooks, h

            def remove(self):
                if self._h in self._hooks:
                    self._hooks.remove(self._h)

        return _Removable(self._backward_hooks, hook)

    @property
    def gradient(self):
        return None if self.grad is None else self.grad.numpy()

    # ---- conversion / device ----
    def astype(self, dtype):
        from .. import ops
        return ops.manipulation.cast(self, dtype)

    def cast(self, dtype):
        return self.astype(dtype)

    def cast_(self, dtype):
        self._inplace_update(
            jnp.asarray(self._value, to_jax_dtype(dtype)),
            self._grad_node, self._out_index)
        return self

    def cpu(self):
        cpu_dev = jax.devices("cpu")[0]
        return Tensor(jax.device_put(self._value, cpu_dev), _internal=True,
                      stop_gradient=self.stop_gradient)

    def cuda(self, device_id=None):
        return self.to_tpu(device_id)

    def tpu(self, device_id=None):
        return self.to_tpu(device_id)

    def to_tpu(self, device_id=None):
        from .place import TPUPlace
        dev = TPUPlace(device_id or 0).jax_device()
        return Tensor(jax.device_put(self._value, dev), _internal=True,
                      stop_gradient=self.stop_gradient)

    def pin_memory(self):
        return self

    def to(self, *args, **kwargs):
        t = self
        for a in list(args) + list(kwargs.values()):
            if isinstance(a, (str, Place)) and not isinstance(a, DType):
                if isinstance(a, str) and a in (
                        "float32", "float64", "float16", "bfloat16", "int32",
                        "int64", "int16", "int8", "uint8", "bool"):
                    t = t.astype(a)
                elif isinstance(a, Place):
                    t = t.cpu() if a.is_cpu_place() else t.to_tpu(a.device_id)
                else:
                    t = t.cpu() if a == "cpu" else t.to_tpu()
            elif isinstance(a, DType):
                t = t.astype(a)
        return t

    def clone(self):
        from .. import ops
        return ops.manipulation.clone(self)

    def contiguous(self):
        return self

    def is_contiguous(self):
        return True

    # ---- indexing ----
    def __getitem__(self, idx):
        from .. import ops
        return ops.manipulation.getitem(self, idx)

    def __setitem__(self, idx, value):
        from .. import ops
        ops.manipulation.setitem(self, idx, value)

    def __len__(self):
        if self.ndim == 0:
            raise TypeError("len() of a 0-d tensor")
        return self._value.shape[0]

    def __iter__(self):
        for i in range(len(self)):
            yield self[i]

    def __bool__(self):
        try:
            return bool(self.numpy())
        except (jax.errors.TracerBoolConversionError,
                jax.errors.TracerArrayConversionError) as e:
            # jax's traceback filtering re-raises from its own
            # sentinel, clobbering any __cause__ we chain — put the
            # advice in the message itself so it survives
            advice = (
                "python control flow on a traced Tensor (inside "
                "to_static / jit).  Use paddle.static.nn.cond / "
                "while_loop / switch_case, which lower to XLA control "
                "flow and stay traceable.")
            e.args = ((f"{e.args[0]}\n{advice}",) + e.args[1:]
                      if e.args else (advice,))
            raise

    def __int__(self):
        return int(self.numpy())

    def __float__(self):
        return float(self.numpy())

    def __index__(self):
        return int(self.numpy())

    def __hash__(self):
        return id(self)

    def __format__(self, spec):
        if self.size == 1:
            return format(self.item(), spec)
        return str(self)

    def __repr__(self):
        try:
            vals = np.asarray(self._value)
            body = np.array2string(vals, precision=8, separator=", ")
        except Exception:
            body = "<uninitialized>"
        return (
            f"Tensor(shape={self.shape}, dtype={self.dtype.name}, "
            f"place={self.place}, stop_gradient={self.stop_gradient},\n"
            f"       {body})"
        )

    # Arithmetic dunders and ~200 methods (add, sum, reshape, ...) are
    # attached by paddle_tpu.ops at import time — see ops/__init__.py.


def _to_array(data, dtype=None, place=None):
    if isinstance(data, Tensor):
        arr = data._value
        if dtype is not None:
            arr = jnp.asarray(arr, to_jax_dtype(dtype))
        return arr
    if isinstance(data, jax.Array):
        if dtype is not None:
            return jnp.asarray(data, to_jax_dtype(dtype))
        return data
    if isinstance(data, np.ndarray):
        from .dtypes import _X32_MAP, _X32_MODE
        jd = to_jax_dtype(dtype) if dtype is not None else data.dtype
        if dtype is None and data.dtype == np.float64 and not _X32_MODE:
            jd = np.float64  # paddle keeps float64 numpy arrays as float64
        if _X32_MODE:
            # canonicalize 64-bit inputs here so jnp neither warns nor
            # truncates per call under PADDLE_TPU_X32
            jd = _X32_MAP.get(np.dtype(jd), jd)
        return jnp.asarray(data, jd)
    # python scalars / nested lists
    if dtype is not None:
        return jnp.asarray(np.asarray(data), to_jax_dtype(dtype))
    arr = np.asarray(data)
    if arr.dtype == np.float64:
        # python floats default to the framework default dtype (float32)
        arr = arr.astype(to_jax_dtype(default_dtype()))
    return jnp.asarray(arr)


def to_tensor(data, dtype=None, place=None, stop_gradient=True):
    """paddle.to_tensor."""
    arr = _to_array(data, dtype, place)
    if place is not None:
        if isinstance(place, str):
            from .place import set_device  # parse without mutating global
            p = Place("cpu", 0) if place == "cpu" else Place("tpu", 0)
        else:
            p = place
        arr = jax.device_put(arr, p.jax_device())
    return Tensor(arr, _internal=True, stop_gradient=stop_gradient)
