"""A profiler trace (``.xplane.pb``) reduced to what the metrics read:
device busy time, the traced window, time per device operation, time in
custom calls, and the idle gaps by what the host was doing.

Read with nothing but JAX (``jax.profiler.ProfileData``).  What a v5e
trace holds, as looked at by hand: one plane ``/device:TPU:<n>`` per
chip with the lines ``XLA Modules`` (one event per executed program)
and ``XLA Ops`` (one per HLO instruction, named by its HLO text), and a
plane ``/host:CPU`` whose line ``python3`` holds the process's
``TraceAnnotation`` spans.  All start times are on one clock.

    python3 benchmarks/trace_reduce.py <file.xplane.pb>   # prints the reduction
"""
import json
import sys

DEVICE_PLANE = "/device:TPU:"
HOST_PLANE, HOST_LINE = "/host:CPU", "python3"
SPAN_PREFIX, WINDOW_SPAN = "bench:", "bench:window"
NAME_LENGTH = 120          # HLO text is cut to this many characters
TOP = 10


def is_custom_call(hlo_text):
    """Whether an ``XLA Ops`` event is a ``custom-call`` instruction:
    ``%name = <shape> custom-call(<operands>), custom_call_target=...``.
    Fusions that merely consume one (``fusion(... %custom-call.3)``) are
    not."""
    return " custom-call(" in hlo_text.split(", custom_call_target")[0] \
        .split(" fusion(")[0]


def union(intervals):
    """Sorted, merged copies of ``(start, end)`` intervals."""
    merged = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return merged


def clip(intervals, lo, hi):
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


def _line(plane, name):
    for line in plane.lines:
        if line.name == name:
            return [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                    for e in line.events]
    return []


def reduce(path):
    from jax.profiler import ProfileData
    planes = list(ProfileData.from_file(path).planes)
    devices = [p for p in planes if p.name.startswith(DEVICE_PLANE)]
    if not devices:
        raise ValueError(f"no {DEVICE_PLANE}* plane in {path}")
    spans = [s for p in planes if p.name == HOST_PLANE
             for s in _line(p, HOST_LINE) if s[0].startswith(SPAN_PREFIX)]
    modules = [_line(p, "XLA Modules") for p in devices]
    windows = [(a, b) for name, a, b in spans if name == WINDOW_SPAN]
    if windows:
        lo, hi = min(a for a, _ in windows), max(b for _, b in windows)
    else:                  # no window span: first to last device event
        lo = min(a for m in modules for _, a, _ in m)
        hi = max(b for m in modules for _, _, b in m)
    busy = [union(clip([(a, b) for _, a, b in m], lo, hi)) for m in modules]
    busy_ns = sum(b - a for u in busy for a, b in u) / len(devices)

    ops, custom_ns = {}, 0.0
    for p in devices:
        for name, a, b in _line(p, "XLA Ops"):
            if a < lo or b > hi:
                continue
            key = name[:NAME_LENGTH]
            ops[key] = ops.get(key, 0.0) + (b - a) / len(devices)
            if is_custom_call(name):
                custom_ns += (b - a) / len(devices)

    # idle gaps of the first chip, each charged to the bench: span
    # (the window's own aside) that covers most of it
    gaps, edge = {}, lo
    for a, b in busy[0] + [[hi, hi]]:
        if a > edge:
            cover = {}
            for name, s, e in spans:
                if name != WINDOW_SPAN and min(e, a) > max(s, edge):
                    cover[name] = cover.get(name, 0.0) \
                        + min(e, a) - max(s, edge)
            owner = max(cover, key=cover.get) if cover else "no bench span"
            gaps[owner] = gaps.get(owner, 0.0) + a - edge
        edge = max(edge, b)

    def top(table):
        return [[k, v / 1e9] for k, v in sorted(
            table.items(), key=lambda kv: -kv[1])[:TOP]]
    return {"window_s": (hi - lo) / 1e9, "busy_s": busy_ns / 1e9,
            "custom_call_s": custom_ns / 1e9, "chips": len(devices),
            "modules": sum(len(clip([(a, b) for _, a, b in m], lo, hi))
                           for m in modules),
            "breakdown": {"device_ops": top(ops), "idle_gaps": top(gaps)}}


if __name__ == "__main__":
    print(json.dumps(reduce(sys.argv[1]), indent=1))
