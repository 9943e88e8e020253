"""Share of the HBM roofline the point route reached in the traced
window: the bytes the traversal needs for the queried keys
(``roofline.point_bytes``) over the route's device time, over the
chip's HBM bandwidth (``peaks.json``)."""

from perfbench.trace import POINT_PROGRAM


def read(run):
    t = run.trace
    if t is None or not run.point_bytes:
        return None
    s = t.program_seconds(POINT_PROGRAM)
    if s <= 0:
        return None
    return 100.0 * run.point_bytes / s / run.peaks["hbm_bytes_per_s"]
