"""Host time under the boundary spans ``spans``, in milliseconds per
traced step: their self time (``kind="self"``: a span's duration less
what its child spans cover) or their whole duration (``"total"``).  Of
the spans in ``idle_only`` only the self time in which the first chip
ran nothing counts: a span that waits for the device is host time only
while the device is not what it waits for.  ``None`` unless the trace
holds one of the listed spans that the program itself emits: beside a
program without boundary spans the harness's ``bench:`` span alone
would read as the whole step."""
from benchmarks import span_reduce


def read(run, spans, kind="self", idle_only=()):
    t, steps = span_reduce.reduction(), run["samples"].get("traced_steps")
    rows = [(s, t["spans"][s]) for s in spans if s in t["spans"]] \
        if t else []
    if not steps or not any(span_reduce.is_program_span(s) for s, _ in rows):
        return None
    if any(s in idle_only for s, _ in rows) and not t["chips"]:
        return None
    return 1e3 * sum(r["self_idle_s"] if s in idle_only
                     else r[kind + "_s"] for s, r in rows) / steps
