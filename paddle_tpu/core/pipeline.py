"""Async step pipeline primitives: lazy fetch handles + the bounded
in-flight window.

The dispatch stack (static ``Executor.run`` and ``jit.to_static``) used
to synchronize at every step boundary: feeds were converted on the
host, the executable dispatched, and every fetch pulled back to numpy
before the next step could start — h2d, compute, and d2h serialized,
so the chip idled for a host round trip every step.

This module is the synchronization policy for the async redesign:

  * ``FetchHandle`` — what ``Executor.run(..., return_numpy=False)``
    returns.  Holds the LIVE device array; the d2h transfer and
    ``block_until_ready`` happen on first read (``.numpy()``,
    ``float()``, ``np.asarray``), not inside ``run()``.  Reading is the
    sync point now.
  * ``InFlightWindow`` — a process-wide bound on un-synchronized
    dispatched steps (``PADDLE_TPU_PIPELINE_DEPTH``, default 2).  Every
    dispatch admits its outputs; when the window is full the OLDEST
    step is blocked on before the newest returns, so steps pipeline
    without unbounded HBM growth (the memory guard's pre-flight
    accounts for the extra in-flight buffers).  Depth 1 reproduces the
    fully synchronous semantics: each dispatch is blocked on before
    control returns to the caller.

Import discipline: this module may import only observability, jax, and
numpy — executor, jit, and io all import it and none of them may cycle.
"""
from __future__ import annotations

import os
import threading
from collections import deque

import numpy as np
import jax

from .. import observability as obs

__all__ = ["ENV_PIPELINE_DEPTH", "pipeline_depth", "FetchHandle",
           "InFlightWindow", "get_window", "drain"]

ENV_PIPELINE_DEPTH = "PADDLE_TPU_PIPELINE_DEPTH"
_DEFAULT_DEPTH = 2


def pipeline_depth():
    """Max dispatched-but-unsynchronized steps (>=1).  Read per call so
    tests (and the degradation ladder) can flip the env var live."""
    try:
        d = int(os.environ.get(ENV_PIPELINE_DEPTH, _DEFAULT_DEPTH))
    except ValueError:
        return _DEFAULT_DEPTH
    return max(1, d)


def _nbytes_of(values):
    n = 0
    for v in values:
        try:
            n += int(v.size) * v.dtype.itemsize
        except Exception:
            pass
    return n


class FetchHandle:
    """A fetch that has been dispatched but not synchronized.

    Wraps the live device array; the first host read (``numpy()``,
    ``__array__``, ``float()``, ``item()``) blocks until the step
    producing it completes and pays the d2h transfer, recorded as a
    ``d2h`` span.  ``shape``/``dtype`` never synchronize.
    """

    __slots__ = ("_value", "label", "step", "_host")

    def __init__(self, value, label=None, step=None):
        self._value = value
        self.label = label
        self.step = step
        self._host = None

    @property
    def shape(self):
        return tuple(self._value.shape)

    @property
    def dtype(self):
        return self._value.dtype

    @property
    def value(self):
        """The live device array (no synchronization)."""
        return self._value

    def is_ready(self):
        try:
            return bool(self._value.is_ready())
        except Exception:
            return True

    def block_until_ready(self):
        jax.block_until_ready(self._value)
        return self

    def numpy(self):
        """The sync point: d2h + block_until_ready on first read."""
        if self._host is None:
            with obs.span("d2h:" + (self.label or "fetch"), cat="d2h",
                          step=self.step,
                          d2h_bytes=_nbytes_of((self._value,))):
                self._host = np.asarray(self._value)
        return self._host

    def tensor(self):
        """Wrap the device array as an eager Tensor (no host transfer)."""
        from .tensor import Tensor
        return Tensor(self._value, _internal=True, stop_gradient=True)

    def item(self):
        return self.numpy().item()

    def __array__(self, dtype=None):
        h = self.numpy()
        return h.astype(dtype) if dtype is not None else h

    def __float__(self):
        return float(self.numpy())

    def __int__(self):
        return int(self.numpy())

    def __bool__(self):
        return bool(self.numpy())

    def __len__(self):
        return int(self._value.shape[0])

    def __repr__(self):
        state = "ready" if self.is_ready() else "in-flight"
        return (f"FetchHandle({self.label or 'fetch'}, "
                f"shape={self.shape}, dtype={self.dtype}, {state})")


class InFlightWindow:
    """Bounded window of dispatched-but-unsynchronized steps.

    ``admit(values)`` registers one dispatch's output arrays; while
    more than ``depth - 1`` older dispatches remain unsynchronized the
    oldest is blocked on (recorded as a ``pipeline.wait`` span).  With
    depth 1 the admitted dispatch itself is blocked before ``admit``
    returns — bit-for-bit synchronous semantics.
    """

    def __init__(self, depth=None):
        self._depth = depth  # None → read the env per admit
        self._lock = threading.Lock()
        self._tickets = deque()

    def _resolve_depth(self):
        return self._depth if self._depth is not None else pipeline_depth()

    def __len__(self):
        with self._lock:
            return len(self._tickets)

    def admit(self, values, label=None, step=None):
        """Register one dispatch; blocks oldest steps past the bound."""
        depth = self._resolve_depth()
        values = tuple(values)
        with self._lock:
            self._tickets.append((values, label, step))
            n = len(self._tickets)
        if obs.enabled():
            obs.get_registry().gauge("pipeline.in_flight").set(n)
        while True:
            with self._lock:
                if len(self._tickets) <= depth - 1:
                    break
                oldest, olabel, ostep = self._tickets.popleft()
            with obs.span("pipeline.wait:" + (olabel or "step"),
                          cat="pipeline", step=ostep,
                          depth=depth):
                try:
                    jax.block_until_ready(oldest)
                except Exception:
                    pass  # deleted/donated buffer: already consumed
        if obs.enabled():
            obs.get_registry().gauge("pipeline.in_flight").set(len(self))

    def drain(self):
        """Block every outstanding step (loop exit / shutdown)."""
        while True:
            with self._lock:
                if not self._tickets:
                    break
                values, label, step = self._tickets.popleft()
            with obs.span("pipeline.wait:" + (label or "step"),
                          cat="pipeline", step=step):
                try:
                    jax.block_until_ready(values)
                except Exception:
                    pass  # deleted/donated buffer: already consumed
        if obs.enabled():
            obs.get_registry().gauge("pipeline.in_flight").set(0)


_window = None
_window_lock = threading.Lock()


def get_window():
    """The process-wide in-flight window every dispatcher admits into."""
    global _window
    if _window is None:
        with _window_lock:
            if _window is None:
                _window = InFlightWindow()
    return _window


def drain():
    """Synchronize all in-flight steps (module-level convenience)."""
    if _window is not None:
        _window.drain()
