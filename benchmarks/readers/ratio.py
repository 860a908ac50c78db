"""``samples[num] / samples[den]``, times ``scale``.  With ``den`` the
window's seconds it is a rate over all the work and all the time of the
window."""


def read(run, num, den, scale=1.0):
    s = run["samples"]
    if s.get(num) is None or not s.get(den):
        return None
    return scale * s[num] / s[den]
