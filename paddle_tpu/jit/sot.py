"""SOT-mode graph capture: ``to_static(full_graph=False)``.

Reference parity: `jit/sot/` — the Symbolic Opcode Translator captures
dygraph code at BYTECODE level with guards and eager fallback
(torchdynamo-style; graph breaks at unsupported constructs, subgraphs
compiled, Python resumes between them) [UNVERIFIED — empty reference
mount; SURVEY.md:134].

TPU-native redesign: bytecode rewriting exists to avoid tracing Python
— but this framework already HAS a capture machine with exactly SOT's
observable semantics, the lazy-eager engine (`core/lazy.py`):

  * the wrapped function executes as REAL Python every call — any
    construct works, nothing is unsupported;
  * ops record into the segment buffer instead of dispatching; a
    data-dependent use (``if float(loss) > ...``) forces ONLY the value
    it needs — precisely where SOT breaks its graph — and everything
    between breaks flushes as one compiled, cached segment;
  * the segment cache key (structural wiring + input avals + liveness)
    IS the guard set: any change in dtypes/shapes/op sequence lands on
    a different key and compiles exactly once — there is no stale-guard
    wrong-replay case by construction;
  * backward and optimizer steps record into the same buffer (deferred
    VJPs), so whole train steps replay as ~one executable.

Tradeoff vs the reference: SOT skips Python on guard hit; here Python
re-executes every call and the WIN is batched dispatch (one
executable launch per segment instead of one per op, against
microseconds of Python per op).  The AST path (``full_graph=True``, jit/trace.py + dy2static.py)
remains the zero-Python-per-step compile.
"""
from __future__ import annotations

import functools

__all__ = ["SotFunction", "sot_capture"]


def _force_tree(obj):
    """Leave outputs LAZY (the pipelining win) but make sure errors in
    the captured segment surface at the call boundary for scalars the
    caller will inevitably branch on: zero-dim outputs force eagerly."""
    from ..core.tensor import Tensor
    from ..core.lazy import LazyValue

    if isinstance(obj, Tensor) and isinstance(obj._value, LazyValue) \
            and obj._value.aval.shape == ():
        obj._value = obj._value.force()
    elif isinstance(obj, (tuple, list)):
        for o in obj:                      # Tensors force IN PLACE, so
            _force_tree(o)                 # containers (incl. named-
    elif isinstance(obj, dict):            # tuples) keep their identity
        for v in obj.values():
            _force_tree(v)
    return obj


class SotFunction:
    """Callable wrapper: run under lazy capture, report segment stats.

    ``last_report``: {"flushes", "cache_hits", "compiles", "nodes"}
    deltas of the most recent call — a cache_hits == flushes steady
    state means every captured segment replayed a compiled executable
    (the SOT 'all guards hit' case).
    """

    def __init__(self, fn, name=None):
        self._fn = fn
        self.__name__ = name or getattr(fn, "__name__", "sot_fn")
        functools.update_wrapper(self, fn, updated=())
        self.last_report = None

    def __call__(self, *args, **kwargs):
        from ..core import lazy

        before = dict(lazy.stats)
        with lazy.lazy_guard(True):
            out = self._fn(*args, **kwargs)
            out = _force_tree(out)
        self.last_report = {k: lazy.stats[k] - before[k]
                            for k in lazy.stats}
        return out

    # reference-API compat shims (TracedFunction look-alikes)
    @property
    def code(self):
        import inspect
        try:
            return inspect.getsource(self._fn)
        except OSError:
            return f"<sot capture of {self.__name__}>"

    def concrete_program_specify_input_spec(self, *a, **k):
        raise RuntimeError(
            "SOT mode has no static Program; use "
            "to_static(full_graph=True) for program export")


def sot_capture(fn):
    if isinstance(fn, SotFunction):
        return fn
    return SotFunction(fn)
