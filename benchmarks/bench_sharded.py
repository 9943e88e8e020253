"""Sharded key-space serving: P=1 vs P=4 on a forced multi-device host
(DESIGN.md §13).

Measures the scaling axis sharding actually buys (Marcus et al.:
credible throughput claims must report scaling behavior):

* **read window** — balanced batched point lookups, best-of-N wall
  clock.  The workload is sized so the UNSHARDED pools exceed the
  real-TPU per-core VMEM budget (``ops.DEFAULT_VMEM_BUDGET``, 12 MiB)
  and fall off the fused single-dispatch path — since §17 that means
  onto the HBM-streaming tier (``path == "streamed"``, still one
  kernel dispatch, pool tiles double-buffered through VMEM), not the
  host oracle — while each shard's pools still fit fully resident, so
  sharding restores fused serving, which is exactly the mechanism that
  scales on real hardware (per-device pools stay VMEM-resident as the
  keyset grows);
* **steady mixed window** — 80/20 read/insert traffic balanced across
  shards, checked against a dict oracle (wrong must be 0), with the
  per-shard §11 guarantees asserted: zero tier repacks and zero XLA
  retraces per shard inside the measurement window, delta appends and
  delta->run merges included (fold-under-traffic is the serving-state
  bench's and tests/test_sharded.py's territory — a fold's wall-clock
  scales with the keyset, which would turn this throughput window into
  a latency bench).

Run on a forced multi-device host (the flag must land before jax
initializes, so ``run.py --only sharded`` spawns this module as a
subprocess):

  XLA_FLAGS=--xla_force_host_platform_device_count=4 \
      PYTHONPATH=src python -m benchmarks.bench_sharded

Emits ``BENCH_sharded.json`` (``--smoke``: small sizes, no artifact).

Scaling caveat, stated in the JSON: the CPU validation platform shares
one physical core pool across the forced devices, so cross-device
kernel *overlap* does not materialize here — the P=4 win comes from the
VMEM-residency mechanism above, and the fan-out/gather plumbing is what
the multi-device placement exercises.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

N_KEYS = 262_144
N_READS = 8_192
N_OPS = 8_192
N_WARMUP = 16_384
BATCH = 2_048
REPEATS = 5
SHARDS = (1, 4)


def run(n_keys: int = N_KEYS, n_reads: int = N_READS, n_ops: int = N_OPS,
        n_warmup: int = N_WARMUP, batch_size: int = BATCH,
        repeats: int = REPEATS, shard_counts=SHARDS,
        vmem_budget: int | None = None, delta_cap: int = 1024,
        out_json: str | None = "BENCH_sharded.json"):
    import numpy as np

    from benchmarks.common import best_s
    from repro.data.datasets import make_dataset
    from repro.core.flat_afli import FlatAFLIConfig
    from repro.core.flow import FlowConfig
    from repro.core.nfl import NFL, NFLConfig
    from repro.core.train_flow import FlowTrainConfig
    from repro.kernels import ops

    if vmem_budget is None:
        # the real-TPU per-core budget, NOT the loose interpret soft
        # cap: the whole point is to measure the pool-residency
        # crossover the way a TPU would see it
        vmem_budget = ops.DEFAULT_VMEM_BUDGET

    import jax

    n_devices = len(jax.devices())
    rng = np.random.default_rng(0)
    keys = make_dataset("lognormal", n_keys + n_warmup + n_ops)
    rng.shuffle(keys)
    build_keys = np.sort(keys[:n_keys])
    insertable = keys[n_keys:]
    payloads = np.arange(n_keys, dtype=np.int64)

    # the write volume is sized to exercise delta appends and delta->
    # run merges in the steady window while keeping the fold
    # reorganization out of it (a fold's wall-clock scales with the
    # keyset, so an in-window fold at this size is a latency bench, not
    # a throughput one — fold-under-traffic is covered by
    # bench_serving_state and tests/test_sharded.py's busy-shard test)
    cfg = FlatAFLIConfig(vmem_budget=vmem_budget, delta_cap=delta_cap)

    result = {
        "workload": {
            "n_keys": n_keys, "n_reads": n_reads, "n_ops": n_ops,
            "n_warmup": n_warmup, "batch_size": batch_size,
            "repeats": repeats, "mix": "read_window + 80/20 steady",
            "dataset": "lognormal", "use_flow": True,
            "vmem_budget": int(vmem_budget), "n_devices": n_devices,
            "shard_counts": list(shard_counts),
        },
        "configs": {},
    }

    for P in shard_counts:
        t0 = time.perf_counter()
        nfl = NFL(NFLConfig(backend="flat", shards=P, force_flow=True,
                            flow=FlowConfig(dim=3),
                            flow_train=FlowTrainConfig(epochs=1),
                            flat_index=cfg))
        nfl.bulkload(build_keys, payloads)
        bulkload_s = time.perf_counter() - t0
        oracle = dict(zip(build_keys.tolist(), payloads.tolist()))

        # ---- balanced per-shard traffic: partition the query and
        # insert pools by routed shard once, then draw equal counts per
        # shard so per-shard batch shapes are deterministic (the §11
        # zero-retrace property is about data movement, not about
        # riding out binomial routing noise)
        shards = nfl.index.shards if P > 1 else [nfl.index]
        if P > 1:
            sid_built = nfl.index._route_points(
                nfl._pkeys(build_keys).astype(np.float32))
            sid_ins = nfl.index._route_points(
                nfl._pkeys(insertable).astype(np.float32))
        else:
            sid_built = np.zeros(len(build_keys), np.int32)
            sid_ins = np.zeros(len(insertable), np.int32)
        built_by = [build_keys[sid_built == s] for s in range(P)]
        ins_by = [list(insertable[sid_ins == s][::-1]) for s in range(P)]

        def read_keys(total):
            per = total // P
            return np.concatenate([
                rng.choice(built_by[s], per, replace=True)
                for s in range(P)])

        def insert_keys(total):
            per = total // P
            return np.array([ins_by[s].pop() for s in range(P)
                             for _ in range(per)])

        # ---------------------------------------------------- read window
        q = read_keys(n_reads)
        expect = np.array([oracle[k] for k in q.tolist()])
        res = nfl.lookup_batch(q)
        read_wrong = int((res != expect).sum())
        # the shared warm/measure/compile-count protocol (common.best_s)
        best, warm_c, meas_c = best_s(lambda: nfl.lookup_batch(q),
                                      repeats)
        shard0 = shards[0]
        read = {
            "wall_s": best,
            "throughput_mops": n_reads / best / 1e6,
            "us_per_query": best / n_reads * 1e6,
            "path": shard0.last_dispatch.get("path"),
            "pool_bytes_per_shard": shard0.last_dispatch.get("pool_bytes"),
            "stream_tile": shard0.last_dispatch.get("stream_tile"),
            "tiles_streamed": shard0.last_dispatch.get("tiles_streamed"),
            "compiles_warmup": warm_c,
            "compiles_measure": meas_c,
            "wrong": read_wrong,
        }

        # ------------------------------------------------- steady window
        def drive(n, measure_lat=False):
            """One 80/20 window; per-batch serving latencies exclude the
            dict-oracle bookkeeping (the serving window is what is
            measured, as in the other serving benches)."""
            wrong = 0
            lat = []
            n_read_b = int(batch_size * 0.8)
            n_ins_b = batch_size - n_read_b
            for _ in range(n // batch_size):
                rk = read_keys(n_read_b)
                ik = insert_keys(n_ins_b)
                iv = np.arange(len(ik)) + 50_000_000
                t0 = time.perf_counter()
                res = nfl.lookup_batch(rk)
                t1 = time.perf_counter()
                nfl.insert_batch(ik, iv)
                t2 = time.perf_counter()
                exp = np.array([oracle[k] for k in rk.tolist()])
                wrong += int((res != exp).sum())
                oracle.update(zip(ik.tolist(), iv.tolist()))
                if measure_lat:
                    lat.append((t1 - t0, t2 - t1))
            return wrong, lat

        warm_wrong, _ = drive(n_warmup)
        # reset every counter the steady gates read
        ops.reset_fused_lookup_stats()
        for s in shards:
            s._serving.reset_stats()
        rebuilds0 = [s.n_rebuilds for s in shards]
        host_probes0 = sum(s.n_host_tier_probes for s in shards)

        steady_wrong, lat = drive(n_ops, measure_lat=True)
        run_s = float(sum(r + w for r, w in lat))  # serving time only
        stats = ops.fused_lookup_stats()
        per_shard = []
        for i, s in enumerate(shards):
            sv = s._serving.stats()
            per_shard.append({
                "tier_repacks": sv["tier_repacks"],
                "tier_uploads": sv["tier_uploads"],
                "rebuilds_in_window": s.n_rebuilds - rebuilds0[i],
                "fold_active_at_end": s._fold is not None,
            })
        read_lat = np.array([l[0] for l in lat]) / (batch_size * 0.8)
        steady = {
            "n_ops": n_ops, "run_s": run_s,
            "throughput_mops": n_ops / run_s / 1e6,
            "wrong": steady_wrong, "warmup_wrong": warm_wrong,
            "retrace_count": stats["retrace_count"],
            "read_p50_us": float(np.percentile(read_lat, 50) * 1e6),
            "read_p99_us": float(np.percentile(read_lat, 99) * 1e6),
            "host_tier_probes_in_window":
                sum(s.n_host_tier_probes for s in shards) - host_probes0,
            "per_shard": per_shard,
        }

        entry = {"bulkload_s": bulkload_s, "read": read, "steady": steady}
        if P > 1:
            entry["router"] = {
                k: (list(v) if isinstance(v, list) else v)
                for k, v in nfl.index._router.items()}
        result["configs"][f"P{P}"] = entry
        print(f"P={P}: bulkload {bulkload_s:.1f}s | read "
              f"{read['throughput_mops']:.3f} Mops/s ({read['path']}, "
              f"{read['pool_bytes_per_shard']/2**20:.1f} MiB/shard) | "
              f"steady {steady['throughput_mops']:.4f} Mops/s, "
              f"wrong={steady_wrong}, retraces={stats['retrace_count']}, "
              f"repacks={[p['tier_repacks'] for p in per_shard]}, "
            f"folds={[p['rebuilds_in_window'] for p in per_shard]}")

        # hard gates (mirrors verify.sh's wrong>0 rule + the §11/§13
        # zero-retrace/zero-repack acceptance)
        assert read_wrong == 0 and steady_wrong == 0 and warm_wrong == 0, \
            f"P={P}: wrong answers in serving windows"
        assert stats["retrace_count"] == 0, \
            f"P={P}: {stats['retrace_count']} retraces in steady window"
        assert all(p["tier_repacks"] == 0 for p in per_shard), \
            f"P={P}: tier repacks in steady window"
        # §17 regression gate: every dispatch route (fused when the
        # pools fit, streamed when they don't) probes the write tiers
        # in-kernel — a host-side tier probe in the steady window means
        # a read left the kernel path (the pre-§17 P=1 behavior: 4
        # oracle read batches x 1 host probe each)
        assert steady["host_tier_probes_in_window"] == 0, \
            (f"P={P}: {steady['host_tier_probes_in_window']} host tier "
             "probes in steady window — reads left the kernel path")

    ps = [f"P{p}" for p in shard_counts]
    if len(ps) >= 2:
        r0 = result["configs"][ps[0]]["read"]
        r1 = result["configs"][ps[-1]]["read"]
        s0 = result["configs"][ps[0]]["steady"]
        s1 = result["configs"][ps[-1]]["steady"]
        result["scaling"] = {
            "read_speedup": r1["throughput_mops"] / r0["throughput_mops"],
            "steady_speedup":
                s1["throughput_mops"] / s0["throughput_mops"],
            "p_lo_path": r0["path"], "p_hi_path": r1["path"],
            "mechanism": "per-shard pools fit the per-device VMEM "
                         "budget and serve fully resident (fused); the "
                         "unsharded pools do not and stream tiles "
                         "through VMEM (streamed, §17)",
        }
        print(f"scaling {ps[0]} -> {ps[-1]}: read "
              f"{result['scaling']['read_speedup']:.2f}x "
              f"({r0['path']} -> {r1['path']}), steady "
              f"{result['scaling']['steady_speedup']:.2f}x")

    if out_json:
        with open(out_json, "w") as f:
            json.dump(result, f, indent=1)
        print(f"wrote {out_json}")
    return result


def rows(result):
    out = []
    for name, cfg in result["configs"].items():
        out.append((f"sharded_read_{name}", cfg["read"]["us_per_query"],
                    f"{cfg['read']['throughput_mops']:.3f}Mops_"
                    f"{cfg['read']['path']}"))
        out.append((f"sharded_steady_{name}",
                    cfg["steady"]["run_s"] / cfg["steady"]["n_ops"] * 1e6,
                    f"wrong={cfg['steady']['wrong']}_retrace="
                    f"{cfg['steady']['retrace_count']}"))
    if "scaling" in result:
        out.append(("sharded_read_speedup", 0.0,
                    f"{result['scaling']['read_speedup']:.2f}x"))
    return out


def run_at_workload(w: dict, out_json: str | None = None):
    """Re-run at a recorded baseline's workload block (``--compare``)."""
    return run(
        n_keys=int(w.get("n_keys", N_KEYS)),
        n_reads=int(w.get("n_reads", N_READS)),
        n_ops=int(w.get("n_ops", N_OPS)),
        n_warmup=int(w.get("n_warmup", N_WARMUP)),
        batch_size=int(w.get("batch_size", BATCH)),
        repeats=int(w.get("repeats", REPEATS)),
        shard_counts=tuple(w.get("shard_counts", SHARDS)),
        vmem_budget=w.get("vmem_budget"), out_json=out_json)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="seconds-scale sizes, no JSON artifact")
    ap.add_argument("--n-keys", type=int, default=None)
    ap.add_argument("--devices", type=int, default=None,
                    help="forced host device count (set before jax init; "
                         "default: max of the shard counts run)")
    ap.add_argument("--compare-rerun", metavar="BASELINE_JSON",
                    help="re-run at this baseline's recorded workload "
                         "(and its device topology) instead of the "
                         "default workload")
    ap.add_argument("--out", default=None,
                    help="result JSON path (with --compare-rerun: where "
                         "the fresh result lands for the caller to diff)")
    args = ap.parse_args()

    base_w = None
    if args.compare_rerun:
        with open(args.compare_rerun) as f:
            base_w = json.load(f).get("workload", {})
    devices = args.devices
    if devices is None:
        counts = (base_w or {}).get("shard_counts", SHARDS)
        devices = max(int(p) for p in counts)

    # must land before jax initializes — this module delays every
    # jax-importing import into run() for exactly this reason.  The flag
    # only adds host (CPU) devices: on a TPU host jax.devices() is still
    # the chips
    if "jax" not in sys.modules:
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                f"{flags} --xla_force_host_platform_device_count="
                f"{devices}").strip()

    if base_w is not None:
        run_at_workload(base_w, out_json=args.out)
    else:
        run_profile(args.smoke, args.n_keys, args.out)


def run_profile(smoke: bool, n_keys: int | None = None,
                out_json: str | None = None):
    """The ``--smoke`` or default profile (``benchmarks.run --only
    sharded`` calls this in its own process on a TPU host)."""
    if smoke:
        return run(n_keys=n_keys or 16_384, n_reads=2_048, n_ops=2_048,
                   n_warmup=4_096, batch_size=1_024, repeats=2,
                   delta_cap=256, out_json=out_json)
    return run(**{**({"n_keys": n_keys} if n_keys else {}),
                  **({"out_json": out_json} if out_json else {})})


if __name__ == "__main__":
    main()
