"""Step-timeline core: spans, instant events, and the bounded event buffer.

The runtime telemetry substrate every other layer reports into:

  * ``span(name, cat=...)`` — a context manager recording a timed region
    (start/duration, step id, rank, free-form attrs).  The static
    ``Executor`` wraps XLA compilation (``cat="compile"``) and dispatch
    (``cat="dispatch"``); ``jit.to_static`` does the same for traced
    functions; collectives record ``cat="collective"`` with a ``bytes``
    attr.
  * ``instant(name, cat=...)`` — a zero-duration marker (memory-guard
    preflight estimates, ladder rungs, fault injections, watchdog
    timeouts, NaN sentinels).
  * flow ids — ``flow_out`` on a compile span and ``flow_in`` on its
    dispatch spans link compile→dispatch arrows in the chrome trace.

  * ``span(name, boundary=True)`` — a **boundary span**: the same
    record, and a ``jax.profiler.TraceAnnotation`` of the same name and
    attrs entered whether or not the gate is on.  The annotation puts
    the span into the ``/host:CPU`` plane of a profiler trace, on the
    device's clock; with no profiler session it is dropped in C++.
    Boundary spans sit only where a step crosses a layer (Executor,
    lazy flush, backward, optimizer, host read, engine step), never on
    a per-op path.

Gating: ``PADDLE_TPU_OBS`` (unset/0/off → disabled).  Disabled, every
timeline entry point is one module-global read returning a shared no-op
object — instrumented hot loops pay effectively nothing; a boundary
span then costs its annotation alone.  ``enable()`` / ``disable()``
override the env var at runtime (the Profiler enables for the duration
of a session).

This module must import nothing from paddle_tpu: executor, collectives,
fault plan, and memory guard all import it, and it must never create an
import cycle (same rule as fault_tolerance/plan.py).
"""
from __future__ import annotations

import itertools
import os
import threading
import time
from collections import deque

__all__ = ["Event", "Timeline", "get_timeline", "span", "instant",
           "enabled", "enable", "disable", "enabled_scope", "set_step",
           "current_step", "next_flow_id", "obs_dir", "ENV_OBS",
           "ENV_OBS_DIR", "ENV_OBS_CAPACITY"]

ENV_OBS = "PADDLE_TPU_OBS"
ENV_OBS_DIR = "PADDLE_TPU_OBS_DIR"
ENV_OBS_CAPACITY = "PADDLE_TPU_OBS_CAPACITY"

_DEFAULT_CAPACITY = 65536

# -- enable gate ---------------------------------------------------------
# tri-state: None = env not consulted yet; True/False = resolved (either
# from the env var or an explicit enable()/disable() override)
_enabled = None


def enabled():
    """One global read on the hot path (after first resolution)."""
    global _enabled
    if _enabled is None:
        v = os.environ.get(ENV_OBS, "").strip().lower()
        _enabled = v not in ("", "0", "off", "false", "no")
    return _enabled


def enable(on=True):
    """Turn collection on (or off); returns the previous state so
    callers (the Profiler) can restore it."""
    global _enabled
    prev = enabled()
    _enabled = bool(on)
    return prev


def disable():
    return enable(False)


class enabled_scope:
    """``with enabled_scope(): ...`` — enable for one dynamic extent."""

    def __init__(self, on=True):
        self._on = on
        self._prev = None

    def __enter__(self):
        self._prev = enable(self._on)
        return self

    def __exit__(self, *exc):
        enable(self._prev)
        return False


def obs_dir():
    """Export directory: ``PADDLE_TPU_OBS_DIR`` or a per-user tmpdir."""
    d = os.environ.get(ENV_OBS_DIR) or os.path.join(
        "/tmp", f"paddle_tpu_obs_{os.getuid() if hasattr(os, 'getuid') else 0}")
    os.makedirs(d, exist_ok=True)
    return d


def _rank():
    try:
        return int(os.environ.get("PADDLE_TRAINER_ID", "0"))
    except ValueError:
        return 0


# -- events --------------------------------------------------------------
class Event:
    """One timeline record.  ``dur`` is None for instant events."""

    __slots__ = ("name", "cat", "ts", "dur", "step", "rank", "attrs",
                 "flow_in", "flow_out")

    def __init__(self, name, cat, ts, dur=None, step=None, rank=0,
                 attrs=None, flow_in=None, flow_out=None):
        self.name = name
        self.cat = cat
        self.ts = ts
        self.dur = dur
        self.step = step
        self.rank = rank
        self.attrs = attrs
        self.flow_in = flow_in
        self.flow_out = flow_out

    def to_dict(self):
        d = {"type": "span" if self.dur is not None else "instant",
             "name": self.name, "cat": self.cat,
             "ts": round(self.ts, 9), "rank": self.rank}
        if self.dur is not None:
            d["dur"] = round(self.dur, 9)
        if self.step is not None:
            d["step"] = self.step
        if self.flow_in is not None:
            d["flow_in"] = self.flow_in
        if self.flow_out is not None:
            d["flow_out"] = self.flow_out
        if self.attrs:
            d["attrs"] = self.attrs
        return d

    def __repr__(self):
        kind = "span" if self.dur is not None else "instant"
        return (f"Event<{kind} {self.cat}:{self.name} ts={self.ts:.6f}"
                + (f" dur={self.dur:.6f}" if self.dur is not None else "")
                + (f" step={self.step}" if self.step is not None else "")
                + ">")


class Timeline:
    """Thread-safe bounded event buffer (oldest events are evicted when
    ``capacity`` is reached; ``dropped`` counts evictions so truncation
    is visible, never silent)."""

    def __init__(self, capacity=None):
        if capacity is None:
            try:
                capacity = int(os.environ.get(ENV_OBS_CAPACITY,
                                              _DEFAULT_CAPACITY))
            except ValueError:
                capacity = _DEFAULT_CAPACITY
        self.capacity = max(1, int(capacity))
        self._events = deque(maxlen=self.capacity)
        self._lock = threading.Lock()
        self.dropped = 0
        self.t0 = time.perf_counter()
        self._step = None
        self.rank = _rank()

    # -- recording -------------------------------------------------------
    def record(self, event):
        with self._lock:
            if len(self._events) == self.capacity:
                self.dropped += 1
            self._events.append(event)
        return event

    def add_span(self, name, cat, ts, dur, step=None, attrs=None,
                 flow_in=None, flow_out=None):
        return self.record(Event(
            name, cat, ts, dur,
            step=self._step if step is None else step,
            rank=self.rank, attrs=attrs or None,
            flow_in=flow_in, flow_out=flow_out))

    def add_instant(self, name, cat, step=None, attrs=None):
        return self.record(Event(
            name, cat, time.perf_counter() - self.t0, None,
            step=self._step if step is None else step,
            rank=self.rank, attrs=attrs or None))

    # -- step attribution ------------------------------------------------
    def set_step(self, n):
        self._step = None if n is None else int(n)
        return self._step

    def current_step(self):
        return self._step

    # -- reading ---------------------------------------------------------
    def events(self):
        """Snapshot list (safe to iterate while recording continues)."""
        with self._lock:
            return list(self._events)

    def clear(self):
        with self._lock:
            self._events.clear()
            self.dropped = 0
            self.t0 = time.perf_counter()
            self._step = None

    def __len__(self):
        with self._lock:
            return len(self._events)


# -- process-wide singleton ----------------------------------------------
_timeline = None
_timeline_lock = threading.Lock()
_flow_counter = itertools.count(1)


def get_timeline():
    global _timeline
    if _timeline is None:
        with _timeline_lock:
            if _timeline is None:
                _timeline = Timeline()
    return _timeline


def next_flow_id():
    """Monotonic id linking a compile span to its dispatch spans."""
    return next(_flow_counter)


def set_step(n):
    return get_timeline().set_step(n)


def current_step():
    return get_timeline().current_step()


# -- ambient span attrs ---------------------------------------------------
# a stack of attr dicts every span/instant opened inside inherits —
# the serving DP engine tags each replica's work ``shard="dp<i>"`` so
# the inner prefill/decode/dispatch spans land on per-shard lanes
# without the emitting code knowing it runs inside a shard
_ambient_attrs = []


class _TagCM:
    __slots__ = ("attrs",)

    def __init__(self, attrs):
        self.attrs = attrs

    def __enter__(self):
        _ambient_attrs.append(self.attrs)
        return self

    def __exit__(self, *exc):
        _ambient_attrs.pop()
        return False


def tag(**attrs):
    """Ambient attrs: spans/instants opened inside inherit them
    (explicit attrs win on key collision)."""
    return _TagCM(attrs)


def ambient_attrs():
    if not _ambient_attrs:
        return None
    out = {}
    for d in _ambient_attrs:
        out.update(d)
    return out


# -- span context managers -----------------------------------------------
class _NullSpan:
    """Shared do-nothing span: the disabled-mode fast path."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, key, value):
        return self

    begin = __enter__

    def end(self):
        pass


_NULL_SPAN = _NullSpan()


class _SpanCM:
    """Live span: records one Event on exit."""

    __slots__ = ("name", "cat", "step", "attrs", "flow_in", "flow_out",
                 "_t0", "_tl")

    def __init__(self, name, cat, step, attrs, flow_in, flow_out):
        self.name = name
        self.cat = cat
        self.step = step
        self.attrs = attrs
        self.flow_in = flow_in
        self.flow_out = flow_out
        self._t0 = None
        self._tl = get_timeline()

    def set(self, key, value):
        """Attach/overwrite an attr while the span is open."""
        if self.attrs is None:
            self.attrs = {}
        self.attrs[key] = value
        return self

    def __enter__(self):
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter()
        self._tl.add_span(self.name, self.cat, self._t0 - self._tl.t0,
                          t1 - self._t0, step=self.step, attrs=self.attrs,
                          flow_in=self.flow_in, flow_out=self.flow_out)
        return False

    # manual begin/end (profiler.RecordEvent drives spans this way)
    begin = __enter__

    def end(self):
        self.__exit__(None, None, None)


_annotation_cls = None


def _annotation():
    """``jax.profiler.TraceAnnotation`` with the span surface (``set``,
    ``begin``, ``end``).  Built on first use: importing this module
    imports neither jax nor anything of paddle_tpu."""
    global _annotation_cls
    if _annotation_cls is None:
        from jax.profiler import TraceAnnotation

        class _Annotation(TraceAnnotation):
            __slots__ = ()

            def set(self, key, value):
                self.set_metadata(**{key: value})
                return self

            begin = TraceAnnotation.__enter__

            def end(self):
                self.__exit__(None, None, None)

        _annotation_cls = _Annotation
    return _annotation_cls


class _BoundarySpan:
    """A live span with both sinks: the profiler's annotation and the
    timeline's record."""

    __slots__ = ("_ann", "_span")

    def __init__(self, ann, span):
        self._ann = ann
        self._span = span

    def set(self, key, value):
        self._ann.set(key, value)
        self._span.set(key, value)
        return self

    def __enter__(self):
        self._ann.__enter__()
        self._span.__enter__()
        return self

    def __exit__(self, *exc):
        self._span.__exit__(*exc)
        self._ann.__exit__(*exc)
        return False

    begin = __enter__

    def end(self):
        self.__exit__(None, None, None)


def span(name, cat="host", step=None, flow_in=None, flow_out=None,
         boundary=False, **attrs):
    """Timed region.  Disabled → the shared no-op singleton.

    ``boundary=True`` also enters a ``jax.profiler.TraceAnnotation``
    named ``name`` with the same attrs (and ``step``), gate on or off;
    a string names the annotation where the timeline's name varies
    (the Executor's dispatch span is named after its program, and is
    ``exe:dispatch`` with a ``program`` attr on the profiler's clock).
    Disabled, a boundary span is that annotation alone."""
    on = enabled()
    if not (on or boundary):
        return _NULL_SPAN
    amb = ambient_attrs()
    if amb:
        attrs = {**amb, **attrs}
    if not boundary:
        return _SpanCM(name, cat, step, attrs or None, flow_in, flow_out)
    cls = _annotation_cls or _annotation()
    ann_name = name if boundary is True else boundary
    if step is None:
        ann = cls(ann_name, **attrs)
    else:
        ann = cls(ann_name, step=step, **attrs)
    if not on:
        return ann
    return _BoundarySpan(ann, _SpanCM(name, cat, step, attrs or None,
                                      flow_in, flow_out))


def instant(name, cat="host", step=None, **attrs):
    """Zero-duration marker.  Disabled → no-op."""
    if not enabled():
        return None
    amb = ambient_attrs()
    if amb:
        attrs = {**amb, **attrs}
    return get_timeline().add_instant(name, cat, step=step,
                                      attrs=attrs or None)
