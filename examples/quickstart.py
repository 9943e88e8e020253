"""Quickstart: the paper's NFL index end to end in ~40 lines.

  PYTHONPATH=src python examples/quickstart.py
"""

import numpy as np

from repro.core.nfl import NFL, NFLConfig
from repro.data.datasets import make_dataset
from repro.index import make_index
from repro.kernels.backend import enable_compile_cache


def main():
    enable_compile_cache()
    # 1. a hard key distribution (the paper's longlat composite keys)
    keys = make_dataset("longlat", 100_000)
    payloads = np.arange(len(keys), dtype=np.int64)

    # 2. two-stage NFL: Numerical NF transform -> AFLI
    nfl = NFL(NFLConfig())
    nfl.bulkload(keys[::2], payloads[::2])
    print("NF enabled:", nfl.use_flow)
    print("tail conflict degree: "
          f"{nfl.metrics['tail_conflict_original']:.0f} -> "
          f"{nfl.metrics['tail_conflict_transformed']:.0f} (paper Table 3)")

    # 3. batched queries + inserts (paper workloads are batched)
    hits = nfl.lookup_batch(keys[::2][:10_000])
    assert (hits == payloads[::2][:10_000]).all()
    nfl.insert_batch(keys[1::2][:10_000], payloads[1::2][:10_000])
    assert (nfl.lookup_batch(keys[1::2][:10_000])
            == payloads[1::2][:10_000]).all()
    print("index stats:", nfl.stats().as_dict())

    # 4. compare against a classic B-Tree on the same workload
    bt = make_index("btree")
    bt.bulkload(keys[::2], payloads[::2])
    assert (bt.lookup_batch(keys[::2][:1000]) == payloads[::2][:1000]).all()
    print("btree height:", bt.stats()["height"],
          " vs AFLI height:", nfl.stats().height)

    # 5. the fused flat backend: range scans + deletes (DESIGN.md §12).
    # A batch of [lo, hi) ranges is ONE kernel dispatch; deletes are
    # tombstones that vanish from point and range reads immediately.
    # (flow off: ranges then follow plain key order — with a flow they
    # follow the transformed positioning order, see DESIGN.md §12)
    flat = NFL(NFLConfig(backend="flat", force_flow=False))
    flat.bulkload(keys[::2], payloads[::2])
    lo, hi = keys[::2][1000], keys[::2][1040]
    pv, cnt, tot = flat.scan_batch([lo], [hi])
    assert cnt[0] == 40 and (np.sort(pv[0, :40])
                             == payloads[::2][1000:1040]).all()
    ok = flat.delete_batch(keys[::2][1000:1010])
    assert ok.all() and (flat.lookup_batch(keys[::2][1000:1010]) == -1).all()
    pv, cnt, tot = flat.scan_batch([lo], [hi])
    print("range [1000:1040) after deleting 10:", int(cnt[0]), "hits,",
          "dispatch:", flat.index.last_scan_dispatch["path"])
    assert cnt[0] == 30


if __name__ == "__main__":
    main()
