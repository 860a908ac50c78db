"""Device time of the custom calls that carry one of the kernel names
``kernels`` (``span_reduce.kernel_of``: directions folded, so forward
and backward add up), in milliseconds per traced step."""
from benchmarks import span_reduce


def read(run, kernels):
    t, steps = span_reduce.reduction(), run["samples"].get("traced_steps")
    rows = [t["kernels"][k] for k in kernels if k in t["kernels"]] \
        if t else []
    if not rows or not steps:
        return None
    return 1e3 * sum(r["s"] for r in rows) / steps
