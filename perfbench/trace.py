"""Reduce a profiler trace (``.xplane.pb``) to the numbers the per-layer
metrics read.

* **busy**: the union of the intervals in which an operation ran on a
  device (the ``XLA Ops`` line of each ``/device:TPU:<n>`` plane),
  clipped to the traced window and averaged over the devices;
* **window**: from the first to the last of the benchmark's host spans;
* **device time by stable name**: per program (the ``XLA Modules``
  line, the jit name without its ``(<id>)`` suffix) and per operation
  (``<program>:<op>``, the op's HLO name without its numeric suffix);
* **idle gaps**: the stretches of the window in which no device
  operation ran, each named by what the host was doing, i.e. by the
  benchmark span (``perfbench.harness.SPANS``) that covers most of it;
  ``fe.step`` is named only for what no inner span covers.

``ProfileData`` comes with JAX; the reduction reads nothing else.
"""

from __future__ import annotations

import glob
import os
import re
from collections import defaultdict

import numpy as np

# stable names of what the per-layer metrics read
NF_KERNEL = "nf_forward_pallas"
POINT_PROGRAM = "jit_xla_lookup"
RANGE_PROGRAM = "jit_xla_range_scan"

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
OUTER_SPAN = "fe.step"

_SUFFIX = re.compile(r"(\(\d+\)|\.\d+)+$")
_HLO = re.compile(r"^%?([^\s=]+)\s*=")


def stable(name: str) -> str:
    """A trace event's name without its per-compile numeric suffix: an
    op given as HLO text (``%fusion.12 = f32[...] ...``) by its
    instruction name (``fusion``), a program (``jit_f(1234)``) by its
    jit name (``jit_f``)."""
    name = name.strip()
    m = _HLO.match(name)
    if m:
        name = m.group(1)
    return _SUFFIX.sub("", name)


def union(iv: np.ndarray) -> np.ndarray:
    """Merge ``[start, end)`` rows into disjoint sorted intervals."""
    if not iv.size:
        return iv.reshape(0, 2)
    iv = iv[np.argsort(iv[:, 0], kind="stable")]
    out = [list(iv[0])]
    for s, e in iv[1:]:
        if s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return np.asarray(out, dtype=np.float64)


def clip(iv: np.ndarray, lo: float, hi: float) -> np.ndarray:
    if not iv.size:
        return iv.reshape(0, 2)
    c = np.stack([np.maximum(iv[:, 0], lo), np.minimum(iv[:, 1], hi)], 1)
    return c[c[:, 1] > c[:, 0]]


def gaps(busy: np.ndarray, lo: float, hi: float) -> np.ndarray:
    """The complement of disjoint sorted ``busy`` within ``[lo, hi)``."""
    edges = [lo]
    for s, e in busy:
        edges += [s, e]
    edges.append(hi)
    g = np.asarray(edges, np.float64).reshape(-1, 2)
    return g[g[:, 1] > g[:, 0]]


def overlap(iv: np.ndarray, s: float, e: float) -> float:
    """Length of ``[s, e)`` covered by disjoint sorted ``iv``."""
    if not iv.size:
        return 0.0
    a = np.searchsorted(iv[:, 1], s, side="right")
    b = np.searchsorted(iv[:, 0], e, side="left")
    part = iv[a:b]
    if not part.size:
        return 0.0
    return float((np.minimum(part[:, 1], e) - np.maximum(part[:, 0], s)).sum())


class Reduced:
    """A reduced trace.  Times in seconds."""

    def __init__(self, window: tuple, busy_s: float, op_s: dict,
                 program_s: dict, span_s: dict, idle_by_span: dict,
                 n_devices: int):
        self.window = window
        self.window_s = (window[1] - window[0]) * 1e-9
        self.busy_s = busy_s
        self.op_s = op_s
        self.program_s = program_s
        self.span_s = span_s
        self.idle_by_span = idle_by_span
        self.n_devices = n_devices

    def op_seconds(self, name: str) -> float:
        """Device seconds of the operations whose stable name contains
        ``name``."""
        return sum(v for k, v in self.op_s.items() if name in k)

    def program_seconds(self, name: str) -> float:
        """Device seconds of the programs whose stable name is ``name``."""
        return self.program_s.get(name, 0.0)

    def span_seconds(self, name: str) -> float:
        """Host seconds inside the benchmark span ``name``."""
        return self.span_s.get(name, 0.0)

    def breakdown(self, top: int = 10) -> dict:
        ops = sorted(self.op_s.items(), key=lambda kv: -kv[1])[:top]
        idle = sorted(self.idle_by_span.items(), key=lambda kv: -kv[1])[:top]
        return {"device_ops": [[k, v] for k, v in ops],
                "idle_gaps": [[k, v] for k, v in idle]}


def _owner(mods: list, starts: np.ndarray, t: float) -> str:
    """``<program>:`` of the program running at ``t``, or ``""``."""
    i = int(np.searchsorted(starts, t, side="right")) - 1
    if i >= 0 and t < mods[i][1] + mods[i][2]:
        return stable(mods[i][0]) + ":"
    return ""


def self_times(ops: list, lo: float, hi: float) -> list:
    """``(name, start, self time)`` of each op inside ``[lo, hi)``: its
    time less the time of the ops nested inside it (a ``while`` op spans
    the ops of its body), so the times add up to the busy time."""
    evs = sorted(((s, min(s + d, hi), name) for name, s, d in ops
                  if s + d > lo and s < hi), key=lambda t: (t[0], -t[1]))
    own = [max(e - max(s, lo), 0.0) for s, e, _ in evs]
    stack: list = []
    for i, (s, e, _) in enumerate(evs):
        while stack and evs[stack[-1]][1] <= s:
            stack.pop()
        if stack:
            j = stack[-1]
            own[j] -= max(min(e, evs[j][1]) - max(s, lo), 0.0)
        stack.append(i)
    return [(name, s, max(t, 0.0)) for (s, _, name), t in zip(evs, own)]


def _events(line):
    for e in line.events:
        yield e.name, float(e.start_ns), float(e.duration_ns)


def reduce_planes(planes, spans) -> Reduced | None:
    """Reduce ``ProfileData.planes`` (or any objects with ``name``,
    ``lines[].name`` and ``lines[].events[]`` with ``name``,
    ``start_ns``, ``duration_ns``)."""
    spans = set(spans)
    host_iv = defaultdict(list)
    devices = []
    for p in planes:
        if p.name.startswith("/device:TPU:") and p.name[12:].isdigit():
            ops, mods = [], []
            for ln in p.lines:
                if ln.name == OPS_LINE:
                    ops += list(_events(ln))
                elif ln.name == MODULES_LINE:
                    mods += list(_events(ln))
            devices.append((ops, mods))
        elif p.name.startswith("/host:"):
            for ln in p.lines:
                for name, s, d in _events(ln):
                    if name in spans:
                        host_iv[name].append((s, s + d))
    all_host = [iv for v in host_iv.values() for iv in v]
    if not devices or not all_host:
        return None
    lo = min(s for s, _ in all_host)
    hi = max(e for _, e in all_host)
    op_s: dict = defaultdict(float)
    program_s: dict = defaultdict(float)
    busy_total = 0.0
    busy_sets = []
    for ops, mods in devices:
        iv = np.asarray([(s, s + d) for _, s, d in ops], np.float64)
        b = clip(union(iv.reshape(-1, 2)), lo, hi)
        busy_sets.append(b)
        busy_total += float((b[:, 1] - b[:, 0]).sum()) if b.size else 0.0
        mods.sort(key=lambda m: m[1])
        starts = np.asarray([m[1] for m in mods], np.float64)
        for name, s, e in self_times(ops, lo, hi):
            op_s[_owner(mods, starts, s) + stable(name)] += e * 1e-9
        for name, s, d in mods:
            c = min(s + d, hi) - max(s, lo)
            if c > 0:
                program_s[stable(name)] += c * 1e-9
    host_u = {k: union(np.asarray(v, np.float64)) for k, v in host_iv.items()}
    span_s = {k: float((v[:, 1] - v[:, 0]).sum()) * 1e-9
              for k, v in host_u.items()}
    # idle of the first device, named by the innermost span covering it
    idle: dict = defaultdict(float)
    inner = [k for k in host_u if k != OUTER_SPAN]
    for s, e in gaps(busy_sets[0], lo, hi):
        length = e - s
        best, cover = None, 0.0
        for k in inner:
            c = overlap(host_u[k], s, e)
            if c > cover:
                best, cover = k, c
        if best is None or cover < 0.5 * length:
            c = overlap(host_u.get(OUTER_SPAN, np.empty((0, 2))), s, e)
            best = OUTER_SPAN if c >= 0.5 * length else "none"
        idle[best] += length * 1e-9
    return Reduced((lo, hi), busy_total / len(devices) * 1e-9, dict(op_s),
                   dict(program_s), span_s, dict(idle), len(devices))


def find_xplane(path: str) -> str | None:
    hits = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                            recursive=True))
    return hits[-1] if hits else None


def reduce_trace(path: str, spans) -> Reduced | None:
    """Reduce the newest ``.xplane.pb`` under ``path``."""
    from jax.profiler import ProfileData

    f = find_xplane(path)
    if f is None:
        return None
    return reduce_planes(ProfileData.from_file(f).planes, spans)
