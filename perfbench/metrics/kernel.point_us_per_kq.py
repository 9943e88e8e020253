"""Device microseconds of the point-route program per 1,000 point
queries of the traced window."""

from perfbench.trace import POINT_PROGRAM


def read(run):
    t = run.trace
    n = sum(len(k) for k in run.traced.get("point_keys", []))
    if t is None or not n:
        return None
    s = t.program_seconds(POINT_PROGRAM)
    return s * 1e6 / (n / 1e3) if s > 0 else None
