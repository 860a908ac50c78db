"""Of the time the first chip ran nothing inside the traced window, the
share charged to one of the program's own boundary spans (the rest
falls to the harness's ``bench:`` spans or to no span), in percent.
``None`` where the chip is idle for under ``min_idle_percent`` of the
window, or where the trace holds no program span."""
from benchmarks import span_reduce


def read(run, min_idle_percent=0.1):
    t = span_reduce.reduction()
    if not t or not t["idle_s"] \
            or 100.0 * t["idle_s"] < min_idle_percent * t["window_s"] \
            or not any(map(span_reduce.is_program_span, t["spans"])):
        return None
    return 100.0 * sum(s for owner, s in t["idle_gaps"].items()
                       if span_reduce.is_program_span(owner)) / t["idle_s"]
