"""Device microseconds of the NF forward kernel (``nf_forward_pallas``)
per 1,000 point keys of the traced window.  Nothing to read where the
flow is off."""

from perfbench.trace import NF_KERNEL


def read(run):
    t = run.trace
    n = sum(len(k) for k in run.traced.get("point_keys", []))
    if t is None or not run.use_flow or not n:
        return None
    s = t.op_seconds(NF_KERNEL)
    return s * 1e6 / (n / 1e3) if s > 0 else None
