"""A model's blocks in the device trace.

The device trace names an HLO instruction (``%fusion.348``) and carries
nothing of its ``op_name`` path, so a ``jax.named_scope`` alone lands
nowhere a profile can read.  The program holds the other half: a step
is compiled ahead of time, and the optimised module's text gives every
instruction its ``metadata={op_name="jit(engine_step)/blk.attention/
..."}``.  Three pieces:

  * ``block(name)`` — a **block scope**, entered where a model does the
    work of one of ``BLOCKS`` (a closed vocabulary).  Inside a JAX
    trace it is ``jax.named_scope("blk." + name)``; while a static
    ``Program`` is built it is an entry of the name stack that
    ``paddle.static.name_scope`` keeps too (``name_scope`` below), which
    an ``OpDesc`` stores as it is recorded and the Executor's
    walker re-enters.  It times nothing and adds no operation: the
    optimised HLO is the same with and without it but for ``metadata``.
    Outside both it is a list push and pop.
  * ``note_program(label, compiled)`` — called where a step program is
    compiled (a ``jit.to_static`` function with a ``program_label``,
    the static ``Executor``): parses the
    module's text there, once, and keeps no reference to the
    executable, so nothing is parsed inside a timed or traced stretch
    and a map outlives its program's owner.
  * ``program_blocks()`` — per noted program ``{"module", "label",
    "instructions": {name: {"block", "opcode"}}}``: the innermost
    block of each instruction's ``op_name`` path (``jvp(...)`` /
    ``transpose(...)`` wrappers fold away, so forward and backward
    land in one block).  An instruction XLA made itself (a layout
    copy of a kernel's operand, an async copy's start and done, a
    prefetch's slices) has no ``op_name`` of its own: inside a
    conditional's branch or a while's body that lies in one block it
    is that block's, and elsewhere it takes the block of the producer
    of its first operand, the data it moves.  The instruction name is
    the join key to a trace's ``XLA Ops`` events, the module name to
    its ``XLA Modules`` events.

Like the rest of this package it imports nothing of paddle_tpu, and jax
only when a scope is entered or a map is asked for.
"""
from __future__ import annotations

import collections
import json
import os
import re
import threading

__all__ = ["BLOCKS", "block", "name_scope", "scope_path", "note_program",
           "program_blocks", "parse_hlo_blocks", "write_blocks"]

BLOCKS = ("embed", "attention", "attention/chunk", "attention/decode",
          "kv_write", "recurrent", "ffn", "experts", "head", "sampler",
          "optimizer")
PREFIX = "blk."
#: maps kept: a process that compiles programs all day keeps the newest
_KEEP = 64

_trace_clean = None


def _tracing():
    """Whether a JAX trace is in flight on this thread."""
    global _trace_clean
    if _trace_clean is None:
        try:
            from jax._src.core import trace_state_clean
        except ImportError:          # no such probe: always enter the scope
            def trace_state_clean():
                return False
        _trace_clean = trace_state_clean
    return not _trace_clean()


class _Stack(threading.local):
    def __init__(self):
        self.names = []


_stack = _Stack()


class name_scope:
    """``paddle.static.name_scope``: ops recorded inside carry
    ``prefix`` in their scope path (``OpDesc.scope``), and the compiled
    step carries it in their ``op_name``.  Inside a JAX trace it is a
    ``jax.named_scope``."""

    __slots__ = ("_name", "_scope")

    def __init__(self, prefix):
        self._name, self._scope = str(prefix), None

    def __enter__(self):
        _stack.names.append(self._name)
        if _tracing():
            import jax
            self._scope = jax.named_scope(self._name)
            self._scope.__enter__()
        return self

    def __exit__(self, *exc):
        if self._scope is not None:
            self._scope.__exit__(*exc)
            self._scope = None
        _stack.names.pop()
        return False


def block(name):
    """The scope of one of ``BLOCKS`` (module docstring)."""
    if name not in BLOCKS:
        raise ValueError(f"no block {name!r}: the vocabulary is {BLOCKS}")
    return name_scope(PREFIX + name)


def scope_path():
    """The scopes open on this thread, outermost first, ``/``-joined:
    what an ``OpDesc`` stores as it is recorded."""
    return "/".join(_stack.names)


# -- the map -------------------------------------------------------------
# one instruction a line: ``[ROOT ]%name = <shape> opcode(%operand, ...),
# ..., metadata={op_name="..." ...}``.  A shape may hold parentheses
# (tuples, a TPU layout's ``T(8,128)(2,1)``) but no lower-case word
# before one.
_INSTRUCTION = re.compile(
    r'^\s*(?:ROOT )?%?([\w.\-]+) = [^\n]*?\s([a-z][a-z0-9\-]*)\('
    r'(?:%([\w.\-]+))?(?:[^\n]*?op_name="([^"\n]*)")?', re.MULTILINE)
_MODULE = re.compile(r"^HloModule ([\w.\-]+)")
# a computation's first line, ``[ENTRY ]%name (parameters) -> shape {``;
# a blank line parts one computation from the next
_COMPUTATION = re.compile(r"(?:ENTRY )?%?([\w.\-]+) \(")
# the computations a conditional or a while runs, as its line names them
_BRANCHES = re.compile(
    r"branch_computations=\{([^}\n]*)\}"
    r"|(?:true_computation|false_computation|body)=%?([\w.\-]+)")
_BLOCK = re.compile(
    r"(?:^|[/(])" + re.escape(PREFIX) + "("
    + "|".join(re.escape(b) for b in sorted(BLOCKS, key=len, reverse=True))
    + r")(?=[/)]|$)")
#: how far an instruction of XLA's own making is followed back
_HOPS = 16


def _innermost(path):
    found = _BLOCK.findall(path)
    return found[-1] if found else ""


def _parse(text):
    """``(module, {instruction: (block, opcode)})``."""
    m = _MODULE.match(text)
    blocks, table, path_of, made_by_xla = {}, {}, {}, {}
    members, callers = {}, []
    for computation in text.split("\n\n"):
        head = _COMPUTATION.match(computation)
        inside = members.setdefault(head.group(1) if head else "", [])
        for name, opcode, operand, path in _INSTRUCTION.findall(
                computation):
            block = blocks.get(path)
            if block is None:
                block = blocks[path] = _innermost(path)
            table[name] = (block, opcode)
            path_of[name] = path
            inside.append(name)
            if not path and operand:
                made_by_xla[name] = operand
            if path and opcode in ("conditional", "while"):
                callers.append((name, computation))
    # what XLA itself puts into a conditional's branch or a while's body
    # (a layout copy of a kernel's operand) has its caller's op_name cut
    # short, or none, where JAX's instructions there go on through the
    # caller's (".../cond" + "/branch_1_fun/blk.attention/chunk/..."):
    # where all of those lie in one block that the caller does not, the
    # branch is that block's, XLA's instructions in it too
    for caller, computation in callers:
        line = re.search(r"^\s*(?:ROOT )?%?" + re.escape(caller)
                         + r" = [^\n]*", computation, re.MULTILINE).group()
        through = path_of[caller] + "/"
        for called in re.findall(r"[\w.\-]+", " ".join(
                a or b for a, b in _BRANCHES.findall(line))):
            inside = members.get(called, ())
            own = [path_of[n].split("/") for n in inside
                   if path_of[n].startswith(through)]
            block = _innermost("/".join(os.path.commonprefix(own)))
            if own and block != table[caller][0]:
                for n in inside:
                    if not path_of[n].startswith(through):
                        table[n] = (block, table[n][1])
                        made_by_xla.pop(n, None)
    # any other instruction with no op_name is XLA's own too (a layout
    # copy, an async copy's start and done, a prefetch's slices and
    # their ConcatBitcast): it moves what its first operand made, and
    # takes that producer's block, followed back through others of its
    # kind
    for name, source in made_by_xla.items():
        for _ in range(_HOPS):
            if source not in made_by_xla:
                break
            source = made_by_xla[source]
        if source in table and source not in made_by_xla:
            table[name] = (table[source][0], table[name][1])
    return (m.group(1) if m else ""), table


def parse_hlo_blocks(text):
    """``{"module", "instructions"}`` of an optimised module's text
    (``compiled.as_text()``): every instruction of every computation
    with the innermost block of its ``op_name`` (``""`` where it has
    none) and its opcode.  An instruction XLA made itself takes the
    block of the branch or loop body it lies in, where that has one
    of its own, else of the producer of its first operand."""
    module, table = _parse(text)
    return {"module": module, "instructions": _as_dicts(table)}


def _as_dicts(table):
    return {name: {"block": block, "opcode": opcode}
            for name, (block, opcode) in table.items()}


# (label, module, {instruction: (block, opcode)}): tuples of
# strings, which the garbage collector does not track, while a program
# runs; `program_blocks` builds the dicts a caller reads
_programs = collections.deque(maxlen=_KEEP)
_lock = threading.Lock()


def note_program(label, compiled):
    """Keep what a freshly compiled step program says of itself: its
    optimised module's text, parsed here, once (57 ms a megabyte of
    text for the printing, 11 for the parse: a 12-layer engine step is
    1.4 MB).  No reference to ``compiled`` is kept; a program that
    cannot print itself is skipped."""
    try:
        text = compiled.as_text()
    except Exception:
        return
    if text:
        entry = (label,) + _parse(text)
        with _lock:
            _programs.append(entry)


def program_blocks():
    """The maps of the noted programs, oldest first (module docstring).
    Built anew each call: ask once, outside a timed stretch."""
    with _lock:
        entries = list(_programs)
    return [{"label": label, "module": module,
             "instructions": _as_dicts(table)}
            for label, module, table in entries]


def write_blocks(path):
    """``program_blocks()`` as JSON at ``path`` (``paddle.profiler``
    writes it beside a trace it stops)."""
    with open(path, "w") as f:
        json.dump(program_blocks(), f)
    return path
