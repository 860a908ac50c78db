"""Device time of the ``XLA Ops`` events whose HLO text is a
``custom-call`` (today: the Mosaic kernels; the trace gives them no
stabler name).  ``per="busy"``: percent of the device's busy time.
``per="step"``: milliseconds per traced step."""


def read(run, per):
    t = run.get("trace")
    if not t or not t["busy_s"]:
        return None
    if per == "busy":
        return 100.0 * t["custom_call_s"] / t["busy_s"]
    steps = run["samples"].get("traced_steps")
    return 1e3 * t["custom_call_s"] / steps if steps else None
