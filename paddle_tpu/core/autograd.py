"""Eager autograd: a Paddle-semantics tape over JAX VJPs.

Reference parity: the eager engine (`paddle/fluid/eager/` — GradNodeBase,
backward.cc topo-queue executor [UNVERIFIED paths; reference mount empty]).

TPU-native design (SURVEY.md §7): each traced op records a ``GradNode`` whose
``vjp_fn`` comes from ``jax.vjp`` of the op's pure-JAX implementation.
``Tensor.backward()`` walks the recorded graph in reverse creation order and
materializes gradients into ``param.grad`` — Paddle's imperative semantics on
a functional core.  Because every vjp_fn is a pure JAX callable, the whole
tape (forward + backward + optimizer) is re-traceable under ``jax.jit``:
``paddle.jit.to_static`` compiles exactly this same code path.
"""
from __future__ import annotations

import contextlib
import itertools
import threading
from typing import Any, Callable, List, Optional, Sequence

import jax.numpy as jnp

from ..observability.timeline import span as _span
from .lazy import concrete as _lazy_concrete, lazy_add

__all__ = [
    "GradNode", "backward", "grad", "no_grad", "enable_grad",
    "set_grad_enabled", "is_grad_enabled",
]


def grad(outputs, inputs, grad_outputs=None, retain_graph=None,
         create_graph=False, only_inputs=True, allow_unused=False,
         no_grad_vars=None):
    """paddle.grad — gradients of outputs w.r.t. inputs, not touching .grad.

    Implemented by running the tape walker with accumulation redirected
    into a side dict keyed by the requested input tensors.
    """
    from .tensor import Tensor

    if isinstance(outputs, Tensor):
        outputs = [outputs]
    if isinstance(inputs, Tensor):
        inputs = [inputs]
    if grad_outputs is not None and isinstance(grad_outputs, Tensor):
        grad_outputs = [grad_outputs]

    keep = bool(retain_graph) or bool(create_graph)
    stops = []
    if no_grad_vars:
        for t in no_grad_vars:
            stops.append((t, t.stop_gradient))
            t.stop_gradient = True
    # temporarily make requested inputs grad-eligible leaves
    for t in inputs:
        stops.append((t, t.stop_gradient))
        t.stop_gradient = False
    sink: dict = {}
    removers = []
    for t in inputs:
        if t._grad_node is not None:
            # non-leaf input: capture its cotangent via a backward hook
            def make_hook(tt):
                def hook(g):
                    _sink_accumulate(sink, tt, g._value)
                    return None
                return hook
            removers.append(t.register_hook(make_hook(t)))
    try:
        backward(outputs, grad_outputs, retain_graph=keep, grad_sink=sink)
        results = []
        for t in inputs:
            g = sink.get(id(t))
            if g is None:
                if not allow_unused:
                    from ..ops.creation import zeros_like
                    results.append(zeros_like(t))
                else:
                    results.append(None)
            else:
                results.append(Tensor(g, _internal=True,
                                      stop_gradient=True))
        return results
    finally:
        for r in removers:
            r.remove()
        for t, sg in stops:
            t.stop_gradient = sg


_node_counter = itertools.count()


class _GradState(threading.local):
    def __init__(self):
        self.enabled = True


_grad_state = _GradState()


def is_grad_enabled() -> bool:
    return _grad_state.enabled


@contextlib.contextmanager
def set_grad_enabled(mode: bool):
    prev = _grad_state.enabled
    _grad_state.enabled = bool(mode)
    try:
        yield
    finally:
        _grad_state.enabled = prev


class no_grad:
    """paddle.no_grad — usable as context manager or decorator."""

    def __enter__(self):
        self._prev = _grad_state.enabled
        _grad_state.enabled = False
        return self

    def __exit__(self, *exc):
        _grad_state.enabled = self._prev
        return False

    def __call__(self, fn):
        import functools

        @functools.wraps(fn)
        def wrapper(*args, **kwargs):
            with no_grad():
                return fn(*args, **kwargs)

        return wrapper


class enable_grad(no_grad):
    def __enter__(self):
        self._prev = _grad_state.enabled
        _grad_state.enabled = True
        return self


class GradNode:
    """One recorded op in the autograd graph.

    ``vjp_fn(cotangents_tuple) -> tuple(input_cotangents)`` — straight from
    ``jax.vjp``.  ``inputs`` holds the input Tensors (keeps upstream graph
    alive); per-input ``needs_grad`` masks stop_gradient inputs.
    """

    __slots__ = (
        "id", "name", "vjp_fn", "inputs", "needs_grad", "n_outputs",
        "out_shapes_dtypes",
    )

    def __init__(self, name, vjp_fn, inputs, needs_grad, n_outputs,
                 out_shapes_dtypes):
        self.id = next(_node_counter)
        self.name = name
        self.vjp_fn = vjp_fn
        self.inputs = list(inputs)
        self.needs_grad = list(needs_grad)
        self.n_outputs = n_outputs
        self.out_shapes_dtypes = out_shapes_dtypes

    def release(self):
        self.vjp_fn = None
        self.inputs = []

    def __repr__(self):
        return f"GradNode<{self.name}#{self.id}>"


def _sink_accumulate(sink, t, g):
    k = id(t)
    sink[k] = g if k not in sink else lazy_add(sink[k], g)


def _accumulate(t, g):
    """Accumulate cotangent ``g`` (a raw jax array) into tensor ``t``'s .grad.

    Reads/writes go through the trace-aware accessors so that gradient
    accumulation across steps is captured as state by to_static.
    """
    from .tensor import Tensor

    if t.grad is None:
        t.grad = Tensor(g, stop_gradient=True, _internal=True)
        t.grad.name = (t.name or "tensor") + "@GRAD"
    else:
        t.grad._inplace_update(lazy_add(t.grad.value(), g))


def backward(tensors, grad_tensors=None, retain_graph: bool = False,
             grad_sink: Optional[dict] = None):
    """Run reverse-mode from ``tensors`` (list of roots).

    Paddle semantics: leaf tensors with stop_gradient=False receive ``.grad``
    (accumulated across calls); non-leaf grads are not retained.

    The tape walk is one ``autograd:backward`` boundary span.
    """
    with _span("autograd:backward", boundary=True):
        _backward(tensors, grad_tensors, retain_graph, grad_sink)


def _backward(tensors, grad_tensors, retain_graph, grad_sink):
    from .dispatch import settle_cpu_collectives
    from .tensor import Tensor

    if isinstance(tensors, Tensor):
        tensors = [tensors]
    if grad_tensors is None:
        grad_tensors = [None] * len(tensors)
    elif isinstance(grad_tensors, Tensor):
        grad_tensors = [grad_tensors]

    # pending cotangents: node.id -> [cotangent-or-None per output]
    pending: dict[int, list] = {}
    nodes: dict[int, GradNode] = {}

    def seed(node, idx, cot):
        lst = pending.setdefault(node.id, [None] * node.n_outputs)
        lst[idx] = cot if lst[idx] is None else lazy_add(lst[idx], cot)
        nodes[node.id] = node

    for t, g in zip(tensors, grad_tensors):
        if t.stop_gradient:
            continue
        if g is None:
            if t._value.size != 1:
                raise ValueError(
                    "grad can be implicitly created only for scalar outputs; "
                    f"got shape {tuple(t._value.shape)}"
                )
            v = t._value
            gv = jnp.ones(getattr(v, "shape", ()), v.dtype)
        else:
            gv = g._value if isinstance(g, Tensor) else jnp.asarray(g)
        node = t._grad_node
        if node is None:
            if grad_sink is not None:
                _sink_accumulate(grad_sink, t, gv)
            else:
                _accumulate(t, gv)
        else:
            seed(node, t._out_index, gv)

    # Reverse-topological by creation id: a node's inputs were always created
    # before the node, so descending id order is a valid reverse topo order.
    import heapq

    heap = [-nid for nid in nodes]
    heapq.heapify(heap)
    inheap = set(nodes)
    visited = []
    while heap:
        nid = -heapq.heappop(heap)
        inheap.discard(nid)
        node = nodes[nid]
        cots = pending.pop(nid)
        if node.vjp_fn is None:
            raise RuntimeError(
                f"Trying to backward through the graph a second time "
                f"(node {node.name}); set retain_graph=True."
            )
        # fill missing output cotangents with zeros
        full = tuple(
            c if c is not None else jnp.zeros(s, d)
            for c, (s, d) in zip(cots, node.out_shapes_dtypes)
        )
        if not getattr(node.vjp_fn, "_lazy_ok", False):
            # jitted/plain vjp closures reject LazyValue arguments
            full = tuple(_lazy_concrete(c) for c in full)
        if node.n_outputs == 1:
            in_cots = node.vjp_fn(full[0])
        else:
            in_cots = node.vjp_fn(full)
        settle_cpu_collectives(in_cots)
        visited.append(node)
        for t, ng, ic in zip(node.inputs, node.needs_grad, in_cots):
            if not ng or ic is None:
                continue
            if t._backward_hooks:
                from .tensor import Tensor as _T

                for h in list(t._backward_hooks):
                    res = h(_T(ic, _internal=True, stop_gradient=True))
                    if res is not None:
                        ic = res._value if isinstance(res, _T) else ic
            child = t._grad_node
            if child is None:
                if not t.stop_gradient:
                    if grad_sink is not None:
                        _sink_accumulate(grad_sink, t, ic)
                    else:
                        _accumulate(t, ic)
            else:
                lst = pending.setdefault(child.id, [None] * child.n_outputs)
                i = t._out_index
                lst[i] = ic if lst[i] is None else lazy_add(lst[i], ic)
                if child.id not in nodes:
                    nodes[child.id] = child
                if child.id not in inheap and child.id in pending:
                    heapq.heappush(heap, -child.id)
                    inheap.add(child.id)

    if not retain_graph:
        for node in visited:
            node.release()
