"""Device time by the model's blocks: the trace joined with the program's
own map of its instructions (``observability.program_blocks()``,
SPANS-PR37.md).

The device trace knows an instruction by name and nothing of where the
model did that work.  The program notes every step program it compiles
and gives, per program, ``{"module", "instructions": {name: {"block",
"kernel", "opcode"}}}``.  The join: an ``XLA Modules`` event inside
``bench:window`` whose name, less the ``(<id>)`` behind it, is a noted
module; the ``XLA Ops`` events it contains; each one's instruction name
(``%fusion.348 = ...``) looked up in that module's map.  Of several
noted programs with one module name the one that knows most of the
event's instructions is taken.  Device time is **self time**: a
``conditional`` or ``while`` event encloses its body's events and each
nanosecond goes to the innermost event (``span_reduce.
innermost_segments``), so the blocks and the rest add up to the busy
time of the noted programs.

``read(run, blocks, opcodes=None, per="step")``: the self time of the
instructions whose block matches one of the patterns ``blocks``
(``fnmatch``: ``"attention*"`` takes ``attention``, ``attention/chunk``
and ``attention/decode``; ``"*"`` every instruction, scoped or not;
``"?*"`` every scoped one) and, with ``opcodes``, whose opcode is one
of them; in milliseconds a traced step (``per="step"``) or in percent
of the noted programs' busy time (``per="busy"``).  ``None`` where
there is no trace, the program has no ``program_blocks`` (an older
tree), no noted program ran in the window, none of them has a scoped
instruction (an executable from a compile cache that a tree without
scopes filled), or no instruction of theirs matches (a model without
that block); all but the last say so on stderr, once a trace.  A block
whose instructions did not run reads 0.
"""
import fnmatch
import os
import sys

from benchmarks import span_reduce
from benchmarks.readers.trace_span_attr import window

_cache = {}                # (path, mtime_ns) -> reduction


def program_maps():
    """The running program's maps, or ``None`` where it keeps none."""
    try:
        from paddle_tpu import observability
        return observability.program_blocks()
    except (ImportError, AttributeError):
        return None


def instruction_of(text):
    """The instruction an ``XLA Ops`` event names: its text up to
    `` = ``, less the ``%``."""
    return text.split(" = ", 1)[0].lstrip("%")


def _pick(candidates, names):
    """Of the noted programs with one module name, the one whose map
    holds most of ``names``."""
    return max(candidates, key=lambda m: sum(
        n in m["instructions"] for n in names))


def reduce(path, programs):
    """``{"busy_s", "self_s": {(block, opcode): s}, "known": {(block,
    opcode)}, "modules": {name: events}}`` of the noted ``programs`` in
    the trace at ``path``, a chip's share each; ``"known"`` is what the
    maps of the programs that ran hold, run or not.  Instructions the
    map lacks go under ``("", "?")``."""
    from jax.profiler import ProfileData
    planes = list(ProfileData.from_file(path).planes)
    devices = [p for p in planes
               if p.name.startswith(span_reduce.DEVICE_PLANE)]
    lo, hi = window(planes)
    by_module = {}
    for program in programs:
        by_module.setdefault(program["module"], []).append(program)
    out = {"busy_s": 0.0, "self_s": {}, "known": set(), "modules": {}}
    ran = {}                   # id -> the maps of the programs that ran
    for plane in devices:
        lines = {line.name: line for line in plane.lines}
        if "XLA Modules" not in lines or "XLA Ops" not in lines:
            continue
        ops = sorted((e.start_ns, e.start_ns + e.duration_ns,
                      instruction_of(e.name))
                     for e in lines["XLA Ops"].events
                     if e.start_ns >= lo
                     and e.start_ns + e.duration_ns <= hi)
        cursor = 0
        for e in sorted(lines["XLA Modules"].events,
                        key=lambda e: e.start_ns):
            module = e.name.split("(", 1)[0]
            a, b = max(e.start_ns, lo), min(e.start_ns + e.duration_ns, hi)
            if module not in by_module or b <= a:
                continue
            while cursor < len(ops) and ops[cursor][0] < a:
                cursor += 1
            end = cursor
            while end < len(ops) and ops[end][0] < b:
                end += 1
            inside = [op for op in ops[cursor:end] if op[1] <= b]
            cursor = end
            table = _pick(by_module[module],
                          {n for _, _, n in inside})["instructions"]
            ran[id(table)] = table
            out["busy_s"] += (b - a) / 1e9 / len(devices)
            out["modules"][module] = out["modules"].get(module, 0) + 1
            for x, y, name in span_reduce.innermost_segments(
                    [(n, x, y) for x, y, n in inside]):
                entry = table.get(name)
                key = (entry["block"], entry["opcode"]) if entry \
                    else ("", "?")
                out["self_s"][key] = out["self_s"].get(key, 0.0) \
                    + (y - x) / 1e9 / len(devices)
    out["known"] = {(i["block"], i["opcode"])
                    for table in ran.values() for i in table.values()}
    return out


def reduction():
    """``reduce`` of the newest trace and the running program's maps,
    once a trace file; ``None``, and a line on stderr that says why,
    where no block can be read of them."""
    path = span_reduce.newest_trace()
    if path is None:
        return None
    key = (path, os.stat(path).st_mtime_ns)
    if key not in _cache:
        programs = program_maps()
        t = reduce(path, programs) if programs else None
        why = ("the program kept no map of a step "
               "(observability.program_blocks)" if not programs
               else "no noted program ran inside bench:window"
               if not t["busy_s"]
               else "the steps that ran carry no block scope: executables "
               "from a compile cache that a tree without scopes filled"
               if not any(b for b, _ in t["known"]) else None)
        if why:
            print(f"trace_block_ms: no block metric of {path}: {why}",
                  file=sys.stderr)
        _cache.clear()
        _cache[key] = None if why else t
    return _cache[key]


def _matches(key, blocks, opcodes):
    return any(fnmatch.fnmatchcase(key[0], b) for b in blocks) \
        and (opcodes is None or key[1] in opcodes)


def read(run, blocks, opcodes=None, per="step"):
    t, steps = reduction(), run["samples"].get("traced_steps")
    if not t or not any(_matches(k, blocks, opcodes) for k in t["known"]):
        return None
    seconds = sum(s for k, s in t["self_s"].items()
                  if _matches(k, blocks, opcodes))
    if per == "busy":
        return 100.0 * seconds / t["busy_s"]
    return 1e3 * seconds / steps if steps else None
