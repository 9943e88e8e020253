"""Device microseconds of the range-route program per 1,000 ranges of
the traced window."""

from perfbench.trace import RANGE_PROGRAM


def read(run):
    t = run.trace
    n = sum(len(k) for k in run.traced.get("range_lo", []))
    if t is None or not n:
        return None
    s = t.program_seconds(RANGE_PROGRAM)
    return s * 1e6 / (n / 1e3) if s > 0 else None
