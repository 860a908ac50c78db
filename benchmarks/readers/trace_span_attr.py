"""What the traced steps carried: the sum of the attribute ``attr`` over
the boundary spans named ``span`` inside ``bench:window``, a traced step
(``engine:dispatch`` records ``decode_rows``, ``chunk_tokens``,
``chunk_start`` and ``context_tokens``: SPANS-PR37.md).  The attributes
are the stats of the span's events in the ``/host:CPU`` plane of the
trace, not the program's timeline, which a runner switches off before
the traced steps.  ``None`` where there is no trace, no traced step, or
no such span with that attribute (an older tree)."""
import functools
import os

from benchmarks import span_reduce


def window(planes):
    """``(start, end)`` in ns of the trace's ``bench:window`` spans; the
    whole trace without one."""
    spans = [(e.start_ns, e.start_ns + e.duration_ns)
             for p in planes if p.name == span_reduce.HOST_PLANE
             for line in p.lines for e in line.events
             if e.name == span_reduce.WINDOW_SPAN]
    return (min((a for a, _ in spans), default=float("-inf")),
            max((b for _, b in spans), default=float("inf")))


@functools.lru_cache(maxsize=1)
def _spans_in_window(path, mtime_ns):
    """``[(name, stats)]`` of the host plane's events that start inside
    the window; one parse a trace file."""
    from jax.profiler import ProfileData
    planes = list(ProfileData.from_file(path).planes)
    lo, hi = window(planes)
    return [(e.name, dict(e.stats))
            for p in planes if p.name == span_reduce.HOST_PLANE
            for line in p.lines for e in line.events
            if lo <= e.start_ns < hi]


def attribute_values(path, span, attr):
    """``attr`` of every ``span`` event that starts inside the window."""
    return [stats[attr]
            for name, stats in _spans_in_window(
                path, os.stat(path).st_mtime_ns)
            if name == span and attr in stats]


def read(run, span, attr):
    path, steps = span_reduce.newest_trace(), \
        run["samples"].get("traced_steps")
    if path is None or not steps:
        return None
    values = attribute_values(path, span, attr)
    return sum(values) / steps if values else None
