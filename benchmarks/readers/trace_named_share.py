"""Device time of the custom calls that carry a kernel name over that
of all custom calls, in percent.  What is left is XLA's own custom
calls and any kernel the program left unnamed."""
from benchmarks import span_reduce


def read(run):
    t = span_reduce.reduction()
    if not t or not t["custom_call_s"]:
        return None
    named = sum(r["s"] for k, r in t["kernels"].items()
                if k != span_reduce.UNNAMED)
    return 100.0 * named / t["custom_call_s"] if named else None
