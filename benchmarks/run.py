"""Benchmark runner — one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows after each module's own
human-readable logging.  ``--full`` widens to all 7 datasets and larger op
counts; the default profile finishes on a laptop-class CPU.

  PYTHONPATH=src python -m benchmarks.run [--full] [--only fig7,table2,...]

``--compare BENCH_x.json`` re-runs the bench that produced the baseline
JSON at its recorded workload and diffs the two: exit nonzero on any
``wrong > 0`` in the fresh run or a >15% regression on any shared
throughput metric (``throughput_mops`` lower, ``us_per_query`` higher) —
the perf trajectory is machine-checkable against committed baselines.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

REGRESSION_FRAC = 0.15  # tolerated throughput slack vs the baseline


def _walk_numeric(obj, path=""):
    """Yield (path, key, value) for every numeric leaf of a BENCH json."""
    if isinstance(obj, dict):
        for k, v in obj.items():
            if isinstance(v, bool):
                continue
            if isinstance(v, (int, float)):
                yield f"{path}/{k}", k, float(v)
            else:
                yield from _walk_numeric(v, f"{path}/{k}")
    elif isinstance(obj, list):
        for i, v in enumerate(obj):
            yield from _walk_numeric(v, f"{path}[{i}]")


def _compare_rerun(name: str, base: dict, path: str):
    """Re-run the bench behind a baseline JSON at its recorded workload
    (no artifact emitted — the committed baseline stays untouched)."""
    w = base.get("workload", {})
    n_keys = int(w.get("n_keys", 65_536))
    if name.startswith("BENCH_fused_lookup"):
        from benchmarks import bench_fused_lookup

        return bench_fused_lookup.run(
            n_keys=n_keys, n_queries=int(w.get("n_queries", 4_096)),
            repeats=int(w.get("repeats", 9)), out_json=None)
    if name.startswith("BENCH_range_scan"):
        from benchmarks import bench_range_scan

        return bench_range_scan.run(
            n_keys=n_keys, n_queries=int(w.get("n_queries", 4_096)),
            repeats=int(w.get("repeats", 7)),
            span_keys=int(w.get("span_keys", 24)),
            n_steady=int(w.get("n_steady", 4_096)),
            n_steady_warmup=int(w.get("n_steady_warmup", 6_144)),
            batch_size=int(w.get("batch_size", 256)), out_json=None)
    if name.startswith("BENCH_mixed_workload"):
        from benchmarks import bench_mixed_workload

        # n_warmup is recorded per mix, uniformly — adopt the first's
        mixes = base.get("mixes", {})
        warm = next((m.get("n_warmup") for m in mixes.values()
                     if isinstance(m, dict) and "n_warmup" in m), None)
        return bench_mixed_workload.run(
            n_keys=n_keys, n_ops=int(w.get("n_ops", 12_288)),
            batch_size=int(w.get("batch_size", 256)),
            n_warmup=int(warm) if warm is not None else None,
            out_json=None)
    if name.startswith("BENCH_serving_state"):
        from benchmarks import bench_serving_state

        return bench_serving_state.run(
            n_keys=n_keys, n_ops=int(w.get("n_ops", 8_192)),
            n_warmup=int(w.get("n_warmup", 6_144)),
            batch_size=int(w.get("batch_size", 256)), out_json=None)
    if name.startswith("BENCH_drift"):
        from benchmarks import bench_drift

        return bench_drift.run(
            n_keys=n_keys, n_drift=int(w.get("n_drift", 12_288)),
            n_settle=int(w.get("n_settle", 6_144)),
            n_steady=int(w.get("n_steady", 16_384)),
            batch_size=int(w.get("batch_size", 256)), out_json=None)
    if name.startswith("BENCH_resharding"):
        from benchmarks import bench_resharding

        return bench_resharding.run(
            n_keys=n_keys, n_storm=int(w.get("n_storm", 12_288)),
            n_settle_batches=int(w.get("n_settle_batches", 48)),
            n_steady=int(w.get("n_steady", 16_384)),
            batch_size=int(w.get("batch_size", 256)), out_json=None)
    if name.startswith("BENCH_service"):
        from benchmarks import bench_service

        return bench_service.run(
            n_keys=n_keys, n_reqs=int(w.get("n_reqs", 2_000)),
            n_fault_reqs=int(w.get("n_fault_reqs", 600)),
            batch_size=int(w.get("batch_size", 128)), out_json=None)
    if name.startswith("BENCH_streamed"):
        from benchmarks import bench_streamed

        return bench_streamed.run_at_workload(w, out_json=None)
    if name.startswith("BENCH_sharded"):
        import jax

        if jax.default_backend() != "cpu":
            # a chip belongs to one process: run on this process's
            # devices, never in a child that would contend for them
            from benchmarks import bench_sharded

            return bench_sharded.run_at_workload(w, out_json=None)
        # the CPU rerun needs the baseline's forced device topology, and
        # XLA_FLAGS must land before jax initializes — jax is already up
        # in this process, so rerun in a subprocess and read its JSON
        import subprocess
        import tempfile

        with tempfile.NamedTemporaryFile(suffix=".json") as tmp:
            rc = subprocess.call(
                [sys.executable, "-m", "benchmarks.bench_sharded",
                 "--compare-rerun", path, "--out", tmp.name],
                env=dict(os.environ))
            if rc:
                raise AssertionError(
                    f"sharded compare rerun failed (exit {rc})")
            with open(tmp.name) as f:
                return json.load(f)
    raise SystemExit(f"--compare: no runner known for {name}")


def compare(paths) -> int:
    """Diff fresh re-runs against committed baselines; returns the
    number of failures (regressions + nonzero wrong counts)."""
    failures = 0
    for path in paths:
        with open(path) as f:
            base = json.load(f)
        try:
            fresh = _compare_rerun(os.path.basename(path), base, path)
        except AssertionError as e:
            # the benches self-assert correctness (wrong>0, oracle
            # divergence) and raise before returning — count it as a
            # comparison failure and keep going with the next baseline
            print(f"COMPARE FAIL {path}: fresh run failed its own "
                  f"correctness gate: {e}")
            failures += 1
            print(f"# compared {path}: 1 failure(s)")
            continue
        base_vals = {p: (k, v) for p, k, v in _walk_numeric(base)}
        failures_before = failures
        for p, k, v in _walk_numeric(fresh):
            if k == "wrong" and v > 0:
                print(f"COMPARE FAIL {path}{p}: wrong={v:g}")
                failures += 1
                continue
            # baselines predating newly added counter fields simply
            # lack those paths: a missing key reads as 0 (ungated for
            # the ratio metrics below), never a KeyError — old
            # committed BENCH_*.json stay comparable as benches grow
            bv = base_vals.get(p, (k, 0.0))[1]
            if k == "throughput_mops" and v < bv * (1 - REGRESSION_FRAC):
                print(f"COMPARE FAIL {path}{p}: {v:.4g} Mops/s vs "
                      f"baseline {bv:.4g} (>{REGRESSION_FRAC:.0%} slower)")
                failures += 1
            elif k == "us_per_query" and "/fused" in p and bv > 0 \
                    and v > bv / (1 - REGRESSION_FRAC):
                # gate the optimized path's latency only: the reference
                # variants (two_dispatch, per_key_loop, host_oracle) are
                # informational baselines, not the protected trajectory
                print(f"COMPARE FAIL {path}{p}: {v:.4g} us/query vs "
                      f"baseline {bv:.4g} (>{REGRESSION_FRAC:.0%} slower)")
                failures += 1
        here = failures - failures_before
        print(f"# compared {path}: "
              f"{'OK' if not here else f'{here} failure(s)'}")
    return failures


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--only", action="append", default=None,
                    help="tag filter, repeatable and/or comma-separated: "
                         "fig7,fig8,fig10,fig11,table1,table2,table3,"
                         "roofline,fused,mixed,serving,range,sharded,"
                         "drift,resharding,service,streamed")
    ap.add_argument("--n-keys", type=int, default=None)
    ap.add_argument("--repeats", type=int, default=None,
                    help="timed repeats per variant in the repeat-based "
                         "benches (fused)")
    ap.add_argument("--smoke", action="store_true",
                    help="seconds-scale sizes (CI smoke; see "
                         "scripts/verify.sh)")
    ap.add_argument("--compare", action="append", default=None,
                    metavar="BENCH_JSON",
                    help="re-run the bench behind this committed baseline "
                         "JSON and exit nonzero on >15%% throughput "
                         "regression or any wrong > 0 (repeatable)")
    args = ap.parse_args()
    import jax

    from repro.kernels.backend import enable_compile_cache

    enable_compile_cache()
    if args.compare:
        sys.exit(1 if compare(args.compare) else 0)
    only = (set(t for part in args.only for t in part.split(","))
            if args.only else None)

    from benchmarks import (bench_alex_nf, bench_bulkload, bench_conflict,
                            bench_fused_lookup, bench_index_size,
                            bench_latency, bench_mixed_workload,
                            bench_nf_latency, bench_probe_batch,
                            bench_range_scan, bench_roofline,
                            bench_serving_state, bench_throughput)
    from benchmarks.common import ALL_DATASETS, DEFAULT_DATASETS

    n_keys = args.n_keys or (400_000 if args.full else 100_000)
    if args.smoke and args.n_keys is None:
        n_keys = 8_192
    datasets = ALL_DATASETS if args.full else DEFAULT_DATASETS
    rows = []

    def want(tag):
        return only is None or tag in only

    t0 = time.time()
    if want("fig7"):
        rows += bench_throughput.rows(bench_throughput.run(
            n_keys=n_keys, n_ops=60_000 if args.full else 30_000,
            datasets=datasets))
    if want("fig8"):
        rows += bench_latency.rows(bench_latency.run(n_keys=n_keys))
    if want("fig10"):
        rows += bench_bulkload.rows(bench_bulkload.run(n_keys=2 * n_keys))
    if want("fig11"):
        rows += bench_index_size.rows(bench_index_size.run(n_keys=n_keys))
    if want("table1"):
        rows += bench_alex_nf.rows(bench_alex_nf.run(n_keys=n_keys))
    if want("table2"):
        rows += bench_nf_latency.rows(bench_nf_latency.run())
    if want("probe_batch"):
        rows += bench_probe_batch.rows(bench_probe_batch.run())
    if want("table3"):
        rows += bench_conflict.rows(bench_conflict.run(
            n_keys=n_keys, datasets=datasets if not args.full else None))
    if want("fused"):
        # also emits machine-readable BENCH_fused_lookup.json
        if args.smoke:
            # smoke: no artifact — don't clobber the committed full-size
            # BENCH json with seconds-scale numbers
            rows += bench_fused_lookup.rows(bench_fused_lookup.run(
                n_keys=n_keys, n_queries=1_024,
                repeats=args.repeats or 2, out_json=None))
        else:
            rows += bench_fused_lookup.rows(bench_fused_lookup.run(
                n_keys=max(n_keys, 65_536) if args.full else 65_536,
                **({"repeats": args.repeats} if args.repeats else {})))
    if want("mixed"):
        # read/insert mixes; emits BENCH_mixed_workload.json
        if args.smoke:
            rows += bench_mixed_workload.rows(bench_mixed_workload.run(
                n_keys=n_keys, n_ops=1_024, batch_size=256,
                n_warmup=1_024, out_json=None))
        else:
            rows += bench_mixed_workload.rows(bench_mixed_workload.run(
                n_keys=max(n_keys, 65_536) if args.full else 65_536))
    if want("serving"):
        # §11 zero-repack serving: steady-state tails + retrace/upload
        # telemetry + legacy before/after; emits BENCH_serving_state.json
        if args.smoke:
            rows += bench_serving_state.rows(bench_serving_state.run(
                n_keys=n_keys, n_ops=1_024, n_warmup=1_024,
                batch_size=256, out_json=None, legacy=False))
        else:
            rows += bench_serving_state.rows(bench_serving_state.run(
                n_keys=max(n_keys, 65_536) if args.full else 65_536))
    if want("range"):
        # §12 fused tier-merged range scans + tombstone deletes; emits
        # BENCH_range_scan.json (smoke: a .smoke.json artifact so the
        # verify.sh correctness gate still sees the wrong counts without
        # clobbering the committed full-size baseline)
        if args.smoke:
            rows += bench_range_scan.rows(bench_range_scan.run(
                n_keys=n_keys, n_queries=512, repeats=2,
                n_steady=768, n_steady_warmup=512,
                out_json="BENCH_range_scan.smoke.json"))
        else:
            rows += bench_range_scan.rows(bench_range_scan.run(
                n_keys=max(n_keys, 65_536) if args.full else 65_536,
                **({"repeats": args.repeats} if args.repeats else {})))
    if want("drift"):
        # §14 drift-robust serving: re-flow on/off/forced-failure under a
        # drifting insert storm; emits BENCH_drift.json (smoke: a
        # .smoke.json artifact so the verify.sh correctness gate sees the
        # wrong counts without clobbering the committed baseline)
        from benchmarks import bench_drift

        if args.smoke:
            rows += bench_drift.rows(bench_drift.run(
                n_keys=n_keys, n_drift=4_096, n_settle=2_048,
                n_steady=4_096, batch_size=128,
                out_json="BENCH_drift.smoke.json"))
        else:
            rows += bench_drift.rows(bench_drift.run(
                n_keys=max(n_keys, 32_768) if args.full else 32_768))
    if want("resharding"):
        # §18 dynamic resharding: hot-shard split with online boundary
        # migration vs balanced/off/forced-failure; emits
        # BENCH_resharding.json (smoke: a .smoke.json artifact so the
        # verify.sh correctness gate sees the wrong counts without
        # clobbering the committed baseline)
        from benchmarks import bench_resharding

        if args.smoke:
            rows += bench_resharding.rows(bench_resharding.run(
                n_keys=n_keys, n_storm=3_072, n_settle_batches=24,
                n_steady=4_096, batch_size=128,
                out_json="BENCH_resharding.smoke.json"))
        else:
            rows += bench_resharding.rows(bench_resharding.run(
                n_keys=max(n_keys, 32_768) if args.full else 32_768,
                assert_perf=True))
    if want("service"):
        # §16 SLO front-end: goodput-vs-SLO curves, 2x-overload admission
        # contrast, injected-fault degradation; emits BENCH_service.json
        # (smoke: a .smoke.json artifact so the verify.sh correctness
        # gate sees the wrong counts without clobbering the committed
        # baseline)
        from benchmarks import bench_service

        if args.smoke:
            rows += bench_service.rows(bench_service.run(
                n_keys=n_keys, n_reqs=384, n_fault_reqs=192,
                batch_size=64, out_json="BENCH_service.smoke.json",
                fault_modes=("forced_fallback", "transient_errors")))
        else:
            rows += bench_service.rows(bench_service.run(
                n_keys=max(n_keys, 32_768) if args.full else 32_768))
    if want("streamed"):
        # §17 HBM-streaming lookup tier: pool/budget ratio sweep with
        # streamed-vs-oracle margins; emits BENCH_streamed.json
        from benchmarks import bench_streamed

        if args.smoke:
            rows += bench_streamed.rows(bench_streamed.run(
                n_keys=max(n_keys, 16_384), n_reads=1_024, repeats=2,
                ratios=(1, 4), out_json=None))
        else:
            rows += bench_streamed.rows(bench_streamed.run(
                n_keys=max(n_keys, 131_072) if args.full else 131_072))
    if want("sharded") and jax.default_backend() != "cpu":
        # §13 sharded serving at P=1 vs P=4 on this host's chips, in
        # this process: a chip belongs to one process, and this one
        # already holds them (it prints its rows, emits BENCH_sharded.json)
        from benchmarks import bench_sharded

        bench_sharded.run_profile(args.smoke, args.n_keys)
    elif want("sharded"):
        # on the CPU the bench needs a forced multi-device host, and
        # XLA_FLAGS must land before jax initializes — jax is already up
        # in this process, so the bench runs as a subprocess
        import subprocess

        env = dict(os.environ)
        flags = env.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            env["XLA_FLAGS"] = (
                f"{flags} --xla_force_host_platform_device_count=4"
            ).strip()
        cmd = [sys.executable, "-m", "benchmarks.bench_sharded"]
        if args.smoke:
            cmd.append("--smoke")
        if args.n_keys is not None:
            cmd += ["--n-keys", str(args.n_keys)]
        rc = subprocess.call(cmd, env=env)
        if rc:
            raise SystemExit(rc)
    if want("roofline"):
        rows += bench_roofline.rows(bench_roofline.run())

    print(f"\n# benchmarks completed in {time.time() - t0:.1f}s")
    print("name,us_per_call,derived")
    for name, us, derived in rows:
        print(f"{name},{us:.3f},{derived}")


if __name__ == "__main__":
    main()
