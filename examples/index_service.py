"""NFL as a standalone key-value index service handling the paper's four
workload mixes in request batches — the 'serving' shape of the paper.

  PYTHONPATH=src python examples/index_service.py --dataset facebook

``--backend flat`` serves through the fused Pallas kernels instead of
the paper tree and additionally drives the beyond-paper request types:
batched range scans (one ``pallas_call`` per batch of [lo, hi) ranges,
DESIGN.md §12) and tombstone deletes, mixed into every workload.
"""

import argparse
import time

import numpy as np

from repro.core.nfl import NFL, NFLConfig
from repro.data.datasets import dataset_names, make_dataset
from repro.data.workloads import MIXES, WorkloadConfig, make_workload
from repro.kernels.backend import enable_compile_cache


def _serve_mix(nfl, wl, *, ranges: bool, n_scans: int = 8):
    """Drive one workload; returns (seconds, wrong).  With ``ranges``,
    every batch additionally answers a small batch of range scans and
    retires a few keys with tombstone deletes."""
    rng = np.random.default_rng(11)
    deleted = set()
    wrong = 0
    t0 = time.perf_counter()
    for op, k, v in wl.batches:
        reads = op == 0
        if reads.any():
            res = nfl.lookup_batch(k[reads])
            exp = np.where(np.isin(k[reads], list(deleted)) if deleted
                           else np.zeros(int(reads.sum()), bool),
                           -1, v[reads])
            wrong += int((res != exp).sum())
        if (~reads).any():
            nfl.insert_batch(k[~reads], v[~reads])
            deleted.difference_update(k[~reads].tolist())
        if ranges:
            lo = rng.choice(wl.load_keys, n_scans)
            hi = lo * (1 + rng.uniform(1e-4, 1e-2, n_scans))
            pv, cnt, tot = nfl.scan_batch(lo, hi)  # one fused dispatch
            dk = rng.choice(wl.load_keys, 2, replace=False)
            ok = nfl.delete_batch(dk)
            deleted.update(dk[ok].tolist())
    return time.perf_counter() - t0, wrong


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--dataset", default="facebook", choices=dataset_names())
    ap.add_argument("--backend", default="afli", choices=["afli", "flat"])
    ap.add_argument("--n-keys", type=int, default=200_000)
    ap.add_argument("--n-ops", type=int, default=100_000)
    ap.add_argument("--batch-size", type=int, default=256)
    args = ap.parse_args()
    enable_compile_cache()

    keys = make_dataset(args.dataset, args.n_keys)
    flat = args.backend == "flat"
    for mix in MIXES:
        if flat:  # per-mix counters: the dispatch stats are process-global
            from repro.kernels import ops

            ops.reset_fused_lookup_stats()
        wl = make_workload(keys, WorkloadConfig(
            mix=mix, n_ops=args.n_ops, batch_size=args.batch_size))
        nfl = NFL(NFLConfig(backend=args.backend))
        t0 = time.perf_counter()
        nfl.bulkload(wl.load_keys, wl.load_payloads)
        t_load = time.perf_counter() - t0

        dt, wrong = _serve_mix(nfl, wl, ranges=flat)
        line = (f"{args.dataset:10s} {mix:11s} load={t_load:5.1f}s "
                f"run={dt:6.2f}s {args.n_ops / dt / 1e6:6.3f} Mops/s "
                f"flow={'on' if nfl.use_flow else 'off'} wrong={wrong}")
        if flat:
            d = nfl.dispatch_stats()["dispatch"]
            line += (f" scans={d['scan_dispatch_count']}"
                     f" scan_fallbacks={d['scan_fallback_count']}"
                     f" retraces={d['retrace_count']}")
        print(line)


if __name__ == "__main__":
    main()
