"""One number the runner returned, as it is (``of``), times ``scale``."""


def read(run, of, scale=1.0):
    value = run["samples"].get(of)
    return None if value is None else value * scale
