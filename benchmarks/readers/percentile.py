"""The ``q``-th percentile (linearly interpolated, numpy's default) of
the list ``samples[of]``, which holds all of the window's samples,
times ``scale``."""
import numpy as np


def read(run, of, q, scale=1.0):
    values = run["samples"].get(of)
    return float(np.percentile(values, q)) * scale if values else None
