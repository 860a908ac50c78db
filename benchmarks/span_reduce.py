"""A profiler trace (``.xplane.pb``) reduced by the program's own names:
device time by kernel, host time by boundary span, and the chip's idle
time by what the host was doing.  ``trace_reduce.py`` beside it reduces
the same file from outside the program (busy time, custom-call or not,
``bench:`` spans); this one reads what the program writes into it.

**Kernel names.**  Every ``pallas_call`` of ``paddle_tpu/ops`` is built
under ``pallas_tiles._kernel_span(name, direction)``, which passes
``name="<name>_<direction>"`` to it.  XLA names the custom call's HLO
instruction after that, so the text of its ``XLA Ops`` event opens with
``%<name>_<direction>.<n> = ... custom-call(...),
custom_call_target="tpu_custom_call"``.  That is the one place the name
lands (looked at on a v5e from a cold compile cache, PR 26): the
event's stats hold ``device_offset_ps``, ``device_duration_ps`` and a
time scale, nothing of the ``op_name`` path, and the event's text stops
before ``backend_config``.  A warm cache can hand back an executable
compiled before the names existed, because JAX leaves names out of the
cache's key.  The rule, written down once, here: a custom-call event whose
target is ``tpu_custom_call`` and whose instruction name, less the
trailing ``.<n>``, ends in one of ``DIRECTIONS`` is that kernel, with
the direction folded away; ``KERNELS`` lists the names the program
gives, and a Mosaic call under another name (``%pure_fwd.242`` is the
dispatcher's jitted function, not a kernel) and XLA's own custom calls
(``ConcatBitcast``, ``X64Combine``) go under ``"unnamed"``.

**Spans.**  The program's boundary spans (``observability.span(...,
boundary=True)``) are ``TraceAnnotation`` events in the ``/host:CPU``
plane, on the device's clock, beside the harness's ``bench:`` spans.
Per name: count, total time and self time (its duration less what its
child spans cover) inside ``bench:window``.  The first chip's idle gaps
are each charged to the innermost span that covers most of the gap, a
program span before a ``bench:`` span.

The runners do not hand the trace's path to the readers, so
``reduction()`` takes the newest ``.xplane.pb`` under
``harness.OUT_DIR``, the one the harness has just written (earlier runs
leave theirs there), and keeps what it reduced in a module-level cache
so that the metrics of one run parse the file once.  A later
``benchmark`` PR hands the path over properly.

    python3 benchmarks/span_reduce.py <file.xplane.pb>   # prints the reduction
"""
import glob
import json
import os
import re
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:           # run as a script, like run.py
    sys.path.insert(0, ROOT)

from benchmarks import harness     # noqa: E402
from benchmarks.trace_reduce import (  # noqa: E402
    clip, is_custom_call, union)

DEVICE_PLANE, HOST_PLANE = "/device:TPU:", "/host:CPU"
BENCH_PREFIX, WINDOW_SPAN = "bench:", "bench:window"
# the boundary spans of paddle_tpu (PERF.md section 3 has the catalogue)
PROGRAM_PREFIXES = ("exe:", "lazy:", "compile:lazy:", "autograd:", "opt:",
                    "sync:", "engine:")
# what _kernel_span's callers pass, longest suffix first
DIRECTIONS = ("_bwd_dkv", "_bwd_dq", "_bwd_dw", "_bwd_dx", "_bwd", "_fwd")
KERNELS = frozenset((
    "flash_attention", "layer_norm", "layer_norm_residual", "rms_norm",
    "softmax_cross_entropy", "paged_attention", "matmul_epilogue",
    "matmul_epilogue_int8", "grouped_matmul", "lora_sgmv",
    "ragged_attention", "ragged_attention_int8"))
UNNAMED = "unnamed"
_INSTRUCTION = re.compile(r"^%([A-Za-z0-9_\-]+?)(?:\.\d+)*(?:\.clone)* = ")

_cache = {}                # (path, mtime_ns) -> reduction


def kernel_of(hlo_text):
    """The kernel an ``XLA Ops`` event belongs to: ``None`` if it is no
    custom call, ``UNNAMED`` if it is one that carries no kernel name,
    else the name with its direction folded away."""
    if not is_custom_call(hlo_text):
        return None
    m = _INSTRUCTION.match(hlo_text)
    if m and 'custom_call_target="tpu_custom_call"' in hlo_text:
        for suffix in DIRECTIONS:
            if m.group(1).endswith(suffix):
                name = m.group(1)[:-len(suffix)]
                return name if name in KERNELS else UNNAMED
    return UNNAMED


def is_program_span(name):
    return name.startswith(PROGRAM_PREFIXES)


def innermost_segments(spans):
    """One thread's ``(name, start, end)`` spans, nested as context
    managers nest, cut into ``(start, end, name)`` pieces that do not
    overlap, each under the innermost span open there.  A span's self
    time is the sum of its pieces."""
    pieces, stack, cursor = [], [], None

    def close_until(t):
        nonlocal cursor
        while stack and stack[-1][1] <= t:
            name, end = stack.pop()
            if end > cursor:
                pieces.append((cursor, end, name))
                cursor = end

    for name, a, b in sorted(spans, key=lambda s: (s[1], -s[2])):
        close_until(a)
        if stack:
            if a > cursor:
                pieces.append((cursor, a, stack[-1][0]))
            b = min(b, stack[-1][1])     # a child ends with its parent
        cursor = a
        stack.append((name, b))
    close_until(float("inf"))
    return pieces


def overlap(pieces, intervals):
    """Time of each name's pieces inside the sorted, disjoint
    ``intervals``: ``{name: ns}``."""
    out = {}
    for a, b, name in pieces:
        inside = sum(min(b, y) - max(a, x) for x, y in intervals
                     if min(b, y) > max(a, x))
        if inside:
            out[name] = out.get(name, 0.0) + inside
    return out


def gap_owner(covering):
    """Who an idle gap is charged to, from ``{name: ns}`` of innermost
    cover: the program span that covers most, else the ``bench:`` span
    that does, else nobody."""
    for names in ([n for n in covering if is_program_span(n)],
                  list(covering)):
        if names:
            return max(names, key=covering.get)
    return "no span"


def _events(plane, line_name=None):
    return [[(e.name, e.start_ns, e.start_ns + e.duration_ns)
             for e in line.events]
            for line in plane.lines
            if line_name is None or line.name == line_name]


def reduce(path):
    from jax.profiler import ProfileData
    planes = list(ProfileData.from_file(path).planes)
    devices = [p for p in planes if p.name.startswith(DEVICE_PLANE)]
    threads = [[s for s in line if s[0].startswith(BENCH_PREFIX)
                or is_program_span(s[0])]
               for p in planes if p.name == HOST_PLANE
               for line in _events(p)]
    modules = [m for p in devices for m in _events(p, "XLA Modules")]
    windows = [(a, b) for t in threads for n, a, b in t if n == WINDOW_SPAN]
    edges = windows or [(a, b) for m in modules + threads for _, a, b in m]
    if not edges:
        raise ValueError(f"neither a device event nor a span in {path}")
    lo, hi = min(a for a, _ in edges), max(b for _, b in edges)

    kernels, custom_ns = {}, 0.0
    for p in devices:
        for ops in _events(p, "XLA Ops"):
            for text, a, b in ops:
                name = None if a < lo or b > hi else kernel_of(text)
                if name is not None:
                    row = kernels.setdefault(name, {"count": 0, "s": 0.0})
                    row["count"] += 1
                    row["s"] += (b - a) / 1e9 / len(devices)
                    custom_ns += (b - a) / len(devices)

    # the first chip's idle gaps inside the window
    busy = union(clip([(a, b) for _, a, b in modules[0]], lo, hi)) \
        if modules else []
    gaps, edge = [], lo
    for a, b in busy + [[hi, hi]]:
        if a > edge:
            gaps.append((edge, a))
        edge = max(edge, b)

    spans, pieces = {}, []
    for thread in threads:
        inside = [(n, max(a, lo), min(b, hi)) for n, a, b in thread
                  if n != WINDOW_SPAN and min(b, hi) > max(a, lo)]
        for n, a, b in inside:
            row = spans.setdefault(n, {"count": 0, "total_s": 0.0,
                                       "self_s": 0.0, "self_idle_s": 0.0})
            row["count"] += 1
            row["total_s"] += (b - a) / 1e9
        pieces += innermost_segments(inside)
    for a, b, n in pieces:
        spans[n]["self_s"] += (b - a) / 1e9
    if devices:
        for n, ns in overlap(pieces, gaps).items():
            spans[n]["self_idle_s"] = ns / 1e9

    idle_gaps = {}
    for gap in gaps:
        owner = gap_owner(overlap(pieces, [gap]))
        idle_gaps[owner] = idle_gaps.get(owner, 0.0) \
            + (gap[1] - gap[0]) / 1e9
    return {"window_s": (hi - lo) / 1e9, "chips": len(devices),
            "custom_call_s": custom_ns / 1e9, "kernels": kernels,
            "spans": spans,
            "idle_s": sum(b - a for a, b in gaps) / 1e9 if devices else None,
            "idle_gaps": idle_gaps}


def newest_trace():
    """The trace the harness has just written: the newest
    ``.xplane.pb`` under ``harness.OUT_DIR``, or ``None``."""
    found = glob.glob(os.path.join(harness.OUT_DIR, "*", "plugins",
                                   "profile", "*", "*.xplane.pb"))
    return max(found, key=os.path.getmtime) if found else None


def reduction():
    """``reduce(newest_trace())``, parsed once per file; ``None`` where
    there is no trace."""
    path = newest_trace()
    if path is None:
        return None
    key = (path, os.stat(path).st_mtime_ns)
    if key not in _cache:
        _cache.clear()
        _cache[key] = reduce(path)
    return _cache[key]


if __name__ == "__main__":
    print(json.dumps(reduce(sys.argv[1]), indent=1))
