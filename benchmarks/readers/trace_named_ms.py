"""Device time of the Mosaic calls whose HLO instruction carries one of
the names ``names`` (as ``pallas_tiles._kernel_span`` gives them:
``<kernel>_<direction>``), in milliseconds per traced step.

``span_reduce.KERNELS`` is a closed list, and a kernel it lacks folds
into ``unnamed`` there.  This reader matches the names itself, in the
newest trace (``span_reduce.newest_trace()``), inside the
``bench:window`` span: an ``XLA Ops`` event is one of the kernel's calls
if its text opens with ``%<name>.<n> = `` and it is a
``tpu_custom_call``.  ``None`` where there is no trace, no traced step
or no such call (a program without the kernel)."""
from benchmarks import span_reduce

_cache = {}                # (path, mtime_ns) -> [(instruction, seconds)]


def custom_calls(path):
    """``[(instruction name, seconds)]`` of every Mosaic call inside the
    traced window, a chip's share each."""
    from jax.profiler import ProfileData
    planes = list(ProfileData.from_file(path).planes)
    devices = [p for p in planes
               if p.name.startswith(span_reduce.DEVICE_PLANE)]
    windows = [(e.start_ns, e.start_ns + e.duration_ns)
               for p in planes if p.name == span_reduce.HOST_PLANE
               for line in p.lines for e in line.events
               if e.name == span_reduce.WINDOW_SPAN]
    lo = min((a for a, _ in windows), default=float("-inf"))
    hi = max((b for _, b in windows), default=float("inf"))
    calls = []
    for p in devices:
        for line in p.lines:
            if line.name != "XLA Ops":
                continue
            for e in line.events:
                a, b = e.start_ns, e.start_ns + e.duration_ns
                m = span_reduce._INSTRUCTION.match(e.name)
                if m and a >= lo and b <= hi and \
                        'custom_call_target="tpu_custom_call"' in e.name:
                    calls.append((m.group(1), (b - a) / 1e9 / len(devices)))
    return calls


def newest_calls():
    import os
    path = span_reduce.newest_trace()
    if path is None:
        return None
    key = (path, os.stat(path).st_mtime_ns)
    if key not in _cache:
        _cache.clear()
        _cache[key] = custom_calls(path)
    return _cache[key]


def seconds(calls, names):
    """Total time of the calls named in ``names``; ``None`` if none is."""
    hits = [s for name, s in calls or () if name in names]
    return sum(hits) if hits else None


def read(run, names):
    steps = run["samples"].get("traced_steps")
    total = seconds(newest_calls(), names)
    if not steps or total is None:
        return None
    return 1e3 * total / steps
