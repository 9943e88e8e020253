"""Cut a small test trace out of a recorded profile.

  python3 perfbench/tests/data/slice_trace.py <in.xplane.pb> <out.xplane.pb> \\
      <start_ms> <length_ms>

Keeps, from ``start_ms`` after the first benchmark span for
``length_ms``, the ``XLA Ops`` and ``XLA Modules`` events of each
``/device:TPU:<n>`` plane and the benchmark's host spans, with their
recorded names and times; everything else is dropped.  The cut is
written as a serialized XSpace that ``ProfileData.from_file`` reads.
"""

import json
import sys

from jax.profiler import ProfileData

SPANS = ("gen", "fe.step", "index.lookup_async", "index.finish",
         "index.scan", "index.insert", "index.delete")
DEVICE_LINES = ("XLA Ops", "XLA Modules")


def main(src, dst, start_ms, length_ms):
    pd = ProfileData.from_file(src)
    spans = [e.start_ns for p in pd.planes if p.name.startswith("/host:")
             for ln in p.lines for e in ln.events if e.name in SPANS]
    lo = min(spans) + start_ms * 1e6
    hi = lo + length_ms * 1e6
    out = []
    for pid, p in enumerate(pd.planes, 1):
        if p.name.startswith("/device:TPU:"):
            keep = lambda ln, e: ln.name in DEVICE_LINES  # noqa: E731
        elif p.name.startswith("/host:"):
            keep = lambda ln, e: e.name in SPANS  # noqa: E731
        else:
            continue
        meta, lines = {}, []
        for lid, ln in enumerate(p.lines, 1):
            evs = []
            for e in ln.events:
                if keep(ln, e) and lo <= e.start_ns < hi:
                    mid = meta.setdefault(e.name, len(meta) + 1)
                    evs.append(f"events {{ metadata_id: {mid} offset_ps: "
                               f"{int(round(e.start_ns * 1000))} duration_ps: "
                               f"{int(round(e.duration_ns * 1000))} }}")
            if evs:
                lines.append(f"lines {{ id: {lid} name: {json.dumps(ln.name)} "
                             f"timestamp_ns: 0 {' '.join(evs)} }}")
        md = " ".join(f"event_metadata {{ key: {i} value {{ id: {i} name: "
                      f"{json.dumps(n)} }} }}" for n, i in meta.items())
        out.append(f"planes {{ id: {pid} name: {json.dumps(p.name)} "
                   f"{' '.join(lines)} {md} }}")
    data = ProfileData.text_proto_to_serialized_xspace(" ".join(out))
    with open(dst, "wb") as f:
        f.write(data)


if __name__ == "__main__":
    main(sys.argv[1], sys.argv[2], float(sys.argv[3]), float(sys.argv[4]))
