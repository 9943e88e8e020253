"""Share of the HBM roofline the range route reached in the traced
window: the bytes the scans need (``roofline.range_bytes``) over the
route's device time, over the chip's HBM bandwidth."""

from perfbench.trace import RANGE_PROGRAM


def read(run):
    t = run.trace
    if t is None or not run.range_bytes:
        return None
    s = t.program_seconds(RANGE_PROGRAM)
    if s <= 0:
        return None
    return 100.0 * run.range_bytes / s / run.peaks["hbm_bytes_per_s"]
