"""A kernel's share of its roofline over the traced steps, in percent:
the least time the chip could take for the work the steps carried (the
larger of operations over the peak FLOP/s and bytes over the peak
bytes/s, ``peaks.json``) over the device time of the calls named
``names`` (``trace_named_ms``).

The work comes from the family's functions named ``flops`` and
``bytes``, called with the runner's samples named in ``work`` (the
program's counters over the traced steps) and the cell's config.  A
share above 100 % means the work is counted too high or the time
leaves calls out: an error, not a reading."""
from benchmarks.readers import trace_named_ms


def share(flops, nbytes, seconds, peaks):
    floor = max(flops / peaks["bf16_flops_per_s"],
                nbytes / peaks["hbm_bytes_per_s"])
    value = 100.0 * floor / seconds
    if value > 100.0:
        raise ValueError(
            f"a roofline share of {value:.1f} %: {flops:.3g} FLOP and "
            f"{nbytes:.3g} B cannot take {seconds:.3g} s on this chip")
    return value


def read(run, names, flops, bytes, work):
    samples = run["samples"]
    seconds = trace_named_ms.seconds(trace_named_ms.newest_calls(), names)
    if not samples.get("traced_steps") or seconds is None \
            or any(samples.get(k) is None for k in work):
        return None
    args = [samples[k] for k in work]
    family, config = run["family"], run["config"]
    return share(getattr(family, flops)(*args, config),
                 getattr(family, bytes)(*args, config), seconds,
                 run["peaks"][run["device"]["kind"]])
