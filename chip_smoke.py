#!/usr/bin/env python3
"""Chip smoke test: the flat NFL serve path, end to end, on a TPU.

Drives the index the way a user does — ``NFL(NFLConfig(backend="flat"))``
and the ``serve.frontend.FrontEnd`` — and checks every answer against a
dict / sorted-array oracle.  Each phase builds an index from seeded
lognormal keys with the paper's switching mechanism, then runs two
rounds of every op type (the first warms up every shape, the second is
the window whose compiles are counted): point lookups (hits and misses),
an insert batch and its re-read, a delete batch re-read as misses, a
batch of ``scan_batch`` ranges, and a few hundred ``ServiceRequest``s
through the front end with a slack deadline.

  python3 chip_smoke.py              # one chip: phase A then phase B
  python3 chip_smoke.py --chips 4    # four chips: the sharded path only
  JAX_PLATFORMS=cpu python3 chip_smoke.py --n-keys 16384 --n-small 4096

* Phase A (HBM scale): ``--n-keys`` (2^22) keys; the pools are far past
  the VMEM budget.  If the switch declines the flow, the phase rebuilds
  with the flow forced and says so.
* Phase B (VMEM scale): ``--n-small`` (65,536) keys.
* ``--chips 4``: ``NFL(backend="flat", shards=4)``, one shard per chip,
  against the same keys and ops served by ``shards=1``; the two must
  answer alike and both must equal the oracle.

The run fails (exit 1, no result line) on any wrong answer, any request
served in interpret mode, any host tier probe or host range scan, any
fallback reason, any retrain failure or reshard abort, and a platform
other than ``tpu`` — a CPU rehearsal (sizes given) runs every phase and
check, then fails there; at full size a host without a TPU fails at
once.  On success the last line of stdout is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

N_KEYS = 1 << 22      # phase A: HBM scale, far past the VMEM budget
N_SMALL = 65_536      # phase B: VMEM scale
BATCH = 4096          # points / inserts / deletes per direct batch
N_RANGES = 512        # ranges per scan batch
MAX_SPAN = 64         # live keys per range, at most (scan_cap is 128)
FE_DEADLINE_S = 3600.0  # slack: nothing may be shed or expire


def log(msg: str) -> None:
    print(msg, flush=True)


class Oracle:
    """Live identity key -> payload, plus each key's positioning key z
    (f32, the transform every stored copy was placed by) for ranges."""

    def __init__(self, zfn, ulp_slack: int):
        self.zfn = zfn
        self.ulp_slack = ulp_slack
        self.pay: dict = {}
        self.z: dict = {}
        self._view = None

    def put(self, keys, payloads) -> None:
        z = self.zfn(keys)
        for k, p, zz in zip(keys.tolist(), payloads.tolist(), z.tolist()):
            self.pay[k] = int(p)
            self.z[k] = zz
        self._view = None

    def delete(self, keys) -> None:
        for k in keys.tolist():
            self.pay.pop(k, None)
            self.z.pop(k, None)
        self._view = None

    def points(self, keys):
        import numpy as np

        return np.fromiter((self.pay.get(k, -1) for k in keys.tolist()),
                           np.int64, count=len(keys))

    def by_z(self):
        """(keys, z, payloads) of the live keys, sorted by z."""
        import numpy as np

        if self._view is None:
            n = len(self.pay)
            k = np.fromiter(self.pay.keys(), np.float64, count=n)
            z = np.fromiter(self.z.values(), np.float32, count=n)
            p = np.fromiter(self.pay.values(), np.int64, count=n)
            order = np.argsort(z, kind="stable")
            self._view = (k[order], z[order], p[order])
        return self._view

    def ranges(self, lo, hi):
        """Per range, the payload sets of the live keys whose z lies in
        ``[z(lo), z(hi))`` — the index's range semantics (DESIGN.md §12)
        — as ``(surely in, maybe in)``.  They are equal unless
        ``ulp_slack`` > 0: the interpreter's NF (plain XLA:CPU, fused
        into each caller differently) may place an endpoint that many
        ulps off, so keys that close to an endpoint may fall either
        side.  On the chip the NF is one Mosaic kernel in every caller
        and the slack is 0."""
        import numpy as np

        _, zs, ps = self.by_z()
        zlo, zhi = self.zfn(lo), self.zfn(hi)

        def cut(z, toward):
            for _ in range(self.ulp_slack):
                z = np.nextafter(z, np.float32(toward))
            return np.searchsorted(zs, z, side="left")

        a_in, b_in = cut(zlo, np.inf), cut(zhi, -np.inf)
        a_may, b_may = cut(zlo, -np.inf), cut(zhi, np.inf)
        return [(set(ps[i:j].tolist()), set(ps[k:m].tolist()))
                for i, j, k, m in zip(a_in, b_in, a_may, b_may)]


def range_ok(got, want, truncated: bool) -> bool:
    """A range answer matches ``want = (surely in, maybe in)``: exact
    when untruncated, a subset of the range when truncated."""
    sure, maybe = want
    got = set(got)
    return got <= maybe and (truncated or sure <= got)


def make_zfn(nfl):
    """The positioning transform the index stores keys under: the NF
    kernel when the flow is on (``nf_transform_keys``, the build's own
    call), the f32 cast of the key when it is off."""
    import numpy as np

    from repro.kernels.ops import nf_transform_keys

    def zfn(keys):
        keys = np.asarray(keys, np.float64)
        if nfl.use_flow:
            z = nf_transform_keys(nfl.flow_params, nfl.normalizer, keys,
                                  nfl.cfg.flow)
            return np.asarray(z, np.float64).astype(np.float32)
        return keys.astype(np.float32)

    return zfn


def indexes(nfl):
    return list(getattr(nfl.index, "shards", [nfl.index]))


def route_of(nfl, attr: str) -> str:
    """The route the last dispatch of ``attr`` took on every shard that
    served one (``last_dispatch`` / ``last_scan_dispatch``)."""
    seen = []
    for idx in indexes(nfl):
        d = getattr(idx, attr, None) or {}
        if d:
            seen.append(f"{d.get('path')}/n_dispatch={d.get('n_dispatch')}")
    return ",".join(sorted(set(seen))) or "none"


class CompileCounter:
    """Backend compiles and persistent-cache hits/misses in this process
    (``jax.monitoring`` events)."""

    def __init__(self):
        import jax

        self.compiles = 0
        self.cache_hits = 0
        self.cache_misses = 0

        def on_duration(event, _secs, **_kw):
            if event == "/jax/core/compile/backend_compile_duration":
                self.compiles += 1

        def on_event(event, **_kw):
            if event == "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1
            elif event == "/jax/compilation_cache/cache_misses":
                self.cache_misses += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)


def dispatch_delta(before: dict, after: dict) -> dict:
    keys = ("xla_count", "scan_xla_count", "fused_count", "streamed_count",
            "scan_fused_count", "fallback_count", "scan_fallback_count",
            "interpret_count", "host_probe_count", "tier_kernel_count",
            "retrace_count")
    return {k: after[k] - before[k] for k in keys if after[k] != before[k]}


def run_round(nfl, oracle: Oracle, rng, fresh, tag: str, answers: list,
              report: dict, batch: int) -> int:
    """One round of every op type against ``oracle``; returns the wrong
    count and appends every answer to ``answers`` (for cross-index
    comparison).  ``fresh`` yields keys never inserted before."""
    import numpy as np

    from repro.kernels.ops import fused_lookup_stats
    from repro.serve.frontend import FrontEnd, FrontEndConfig, ServiceRequest

    wrong = {}

    def step(op, fn):
        before = fused_lookup_stats()
        t0 = time.perf_counter()
        out = fn()
        dt = time.perf_counter() - t0
        delta = dispatch_delta(before, fused_lookup_stats())
        attr = "last_scan_dispatch" if op == "range" else "last_dispatch"
        report.setdefault(op, {})[tag] = {
            "route": route_of(nfl, attr), "counters": delta,
            "wall_s": round(dt, 4)}
        return out

    def check(op, got, want):
        bad = np.asarray(got) != np.asarray(want)
        wrong[op] = wrong.get(op, 0) + int(bad.sum())
        if bad.any():
            report[op][tag]["mismatches"] = [
                [np.asarray(got)[i].item(), np.asarray(want)[i].item()]
                for i in np.flatnonzero(bad)[:3]]
        answers.append(np.asarray(got))

    live = np.fromiter(oracle.pay.keys(), np.float64)

    # point lookups: 3/4 hits, 1/4 misses
    hits = rng.choice(live, batch * 3 // 4, replace=False)
    q = np.concatenate([hits, fresh(batch // 4, miss=True)])
    rng.shuffle(q)
    got = step("point", lambda: nfl.lookup_batch(q))
    check("point", got, oracle.points(q))

    # an insert batch, then its re-read
    ins = fresh(batch)
    pv = rng.integers(0, 2 ** 30, ins.shape[0])
    step("insert", lambda: nfl.insert_batch(ins, pv))
    oracle.put(ins, pv)
    got = step("insert-reread", lambda: nfl.lookup_batch(ins))
    check("insert-reread", got, pv)

    # a delete batch (built and just-inserted keys), re-read as misses
    live = np.fromiter(oracle.pay.keys(), np.float64)
    dk = np.concatenate([rng.choice(live, batch // 2, replace=False),
                         ins[:batch // 2]])
    dk = np.unique(dk)
    ok = step("delete", lambda: nfl.delete_batch(dk))
    check("delete", np.asarray(ok, bool), np.ones(dk.shape[0], bool))
    oracle.delete(dk)
    got = step("delete-reread", lambda: nfl.lookup_batch(dk))
    check("delete-reread", got, np.full(dk.shape[0], -1))

    # a batch of ranges, each spanning up to MAX_SPAN live keys in z order
    live_by_z = oracle.by_z()[0]
    start = rng.integers(0, live_by_z.shape[0] - MAX_SPAN - 1, N_RANGES)
    span = rng.integers(1, MAX_SPAN + 1, N_RANGES)
    lo, hi = live_by_z[start], live_by_z[start + span]
    pv_r, cnt, tot = step("range", lambda: nfl.scan_batch(lo, hi))
    want = oracle.ranges(lo, hi)
    got_r = [sorted(pv_r[i, :cnt[i]].tolist()) for i in range(len(lo))]
    cap = pv_r.shape[1]
    bad = [i for i, (g, w, t) in enumerate(zip(got_r, want, tot.tolist()))
           if not range_ok(g, w, t > cap)]
    wrong["range"] = wrong.get("range", 0) + len(bad)
    report["range"][tag]["mismatches"] = [
        {"lo": float(lo[i]), "hi": float(hi[i]), "total": int(tot[i]),
         "extra": sorted(set(got_r[i]) - want[i][1])[:8],
         "missing": sorted(want[i][0] - set(got_r[i]))[:8]}
        for i in bad[:3]]
    report["range"][tag]["truncated"] = int((tot > cap).sum())
    answers.append(np.asarray([len(g) for g in got_r]))
    answers.append(np.asarray([p for g in got_r for p in g]))

    # a few hundred requests through the front end, in FIFO op blocks;
    # the oracle replays them in submission order
    fe = FrontEnd(nfl, FrontEndConfig(max_batch=128))
    live = np.fromiter(oracle.pay.keys(), np.float64)
    fe_ins = fresh(64)
    plan = ([("point", k) for k in rng.choice(live, 96, replace=False)]
            + [("point", k) for k in fresh(32, miss=True)]
            + [("insert", k) for k in fe_ins]
            + [("range", i) for i in range(64)]
            + [("delete", k) for k in np.concatenate(
                [rng.choice(live, 32, replace=False), fe_ins[:32]])]
            + [("point", k) for k in fe_ins])
    fe_lo = live_by_z[start[:64]]
    fe_hi = live_by_z[start[:64] + span[:64]]
    reqs, expect, fe_want = [], [], None
    for rid, (op, k) in enumerate(plan):
        if op == "range":
            r = ServiceRequest(rid, op, float(fe_lo[k]), hi=float(fe_hi[k]),
                               deadline_s=FE_DEADLINE_S)
            if fe_want is None:  # the range block sees one oracle state
                fe_want = oracle.ranges(fe_lo, fe_hi)
            expect.append(("range", fe_want[k]))
        elif op == "insert":
            p = int(rng.integers(0, 2 ** 30))
            r = ServiceRequest(rid, op, float(k), payload=p,
                               deadline_s=FE_DEADLINE_S)
            oracle.put(np.array([k]), np.array([p]))
            expect.append(("write", True))
        elif op == "delete":
            r = ServiceRequest(rid, op, float(k), deadline_s=FE_DEADLINE_S)
            expect.append(("write", k in oracle.pay))
            oracle.delete(np.array([k]))
        else:
            r = ServiceRequest(rid, op, float(k), deadline_s=FE_DEADLINE_S)
            expect.append(("point", oracle.pay.get(float(k), -1)))
        reqs.append(r)
    before = fused_lookup_stats()
    for r in reqs:
        fe.submit(r)
    fe.drain()
    s = fe.stats()
    report.setdefault("frontend", {})[tag] = {
        "admitted": s["admitted"], "completed": s["completed"],
        "shed": s["shed"], "expired": s["expired"],
        "batches": s["batches"],
        "counters": dispatch_delta(before, fused_lookup_stats())}
    n_bad = 0 if s["admitted"] == s["completed"] == len(reqs) else 1
    fe_ans = []
    for r, (kind, want) in zip(reqs, expect):
        if kind == "range":
            n_bad += int(not range_ok(r.result[0], want, r.result[1] > cap))
            fe_ans.append(len(r.result[0]))
        else:
            n_bad += int(r.result != want)
            fe_ans.append(int(r.result))
    wrong["frontend"] = n_bad
    answers.append(np.asarray(fe_ans))
    report.setdefault("wrong", {})[tag] = wrong
    return sum(wrong.values())


def key_source(keys, seed: int):
    """Keys never seen before: ``k + 0.5`` (inserts) / ``k + 0.25``
    (misses) of the built keys, in a seeded order — lognormal integers,
    so neither collides with a built key or with each other."""
    import numpy as np

    rng = np.random.default_rng(seed)
    order = {True: rng.permutation(keys.shape[0]),
             False: rng.permutation(keys.shape[0])}
    pos = {True: 0, False: 0}

    def fresh(n: int, miss: bool = False):
        i = pos[miss]
        pos[miss] = i + n
        return keys[order[miss][i:i + n]] + (0.25 if miss else 0.5)

    return fresh


def build(keys, shards: int, force_flow=None):
    import numpy as np

    from repro.core.nfl import NFL, NFLConfig

    nfl = NFL(NFLConfig(backend="flat", shards=shards,
                        force_flow=force_flow))
    t0 = time.perf_counter()
    nfl.bulkload(keys, np.arange(keys.shape[0]))
    return nfl, time.perf_counter() - t0


def run_phase(name: str, n: int, seed: int, shards: int, need_flow: bool,
              counter: CompileCounter, ulp_slack: int,
              answers: list | None = None) -> dict:
    import numpy as np

    from repro.data.datasets import make_dataset
    from repro.kernels.ops import DEFAULT_VMEM_BUDGET

    keys = make_dataset("lognormal", n, seed=seed)
    nfl, t_bulk = build(keys, shards)
    forced = False
    if need_flow and not nfl.use_flow:
        log(f"[{name}] the switch declined the flow "
            f"(tail {nfl.metrics['tail_conflict_original']:.0f} vs "
            f"{nfl.metrics['tail_conflict_transformed']:.0f}); "
            "rebuilding with the flow forced")
        nfl, t_bulk = build(keys, shards, force_flow=True)
        forced = True
    m = nfl.metrics
    log(f"[{name}] keys={n} shards={shards} use_flow={nfl.use_flow} "
        f"flow_forced={forced} bulkload_s={t_bulk:.3f} "
        f"flow_train_s={m['flow_train_s']:.3f} "
        f"transform_s={m['transform_s']:.3f} "
        f"index_build_s={m['index_build_s']:.3f} "
        f"serve_verify_shadowed={m['serve_verify_shadowed']:.0f}")

    oracle = Oracle(make_zfn(nfl), ulp_slack=ulp_slack)
    oracle.put(keys, np.arange(n))
    fresh = key_source(keys, seed + 1)
    rng = np.random.default_rng(seed + 2)
    report: dict = {}
    answers = [] if answers is None else answers
    batch = min(BATCH, n // 8)
    wrong = run_round(nfl, oracle, rng, fresh, "warmup", answers, report,
                      batch)
    nfl.dispatch_stats(reset=True)
    c0 = counter.compiles
    wrong += run_round(nfl, oracle, rng, fresh, "window", answers, report,
                       batch)
    window_compiles = counter.compiles - c0
    stats = nfl.dispatch_stats()

    pool = sum(int((idx.last_dispatch or {}).get("pool_bytes") or 0)
               for idx in indexes(nfl))
    scan = sum(int((idx.last_scan_dispatch or {}).get("pool_bytes") or 0)
               for idx in indexes(nfl))
    log(f"[{name}] point_pool_bytes={pool} range_pool_bytes={scan} "
        f"vmem_budget_bytes={DEFAULT_VMEM_BUDGET} "
        f"x_budget={pool / DEFAULT_VMEM_BUDGET:.1f}")
    for op, per in report.items():
        if op != "wrong":
            log(f"[{name}] {op}: {json.dumps(per, sort_keys=True)}")
    d = stats["dispatch"]
    log(f"[{name}] dispatch_stats (window): " + json.dumps(
        {k: v for k, v in d.items() if k != "fallback_reasons"},
        sort_keys=True))
    log(f"[{name}] fallback_reasons: {json.dumps(d['fallback_reasons'])}")
    drift = stats.get("drift", {})
    aborts = int(getattr(nfl.index, "n_reshard_aborts", 0))
    log(f"[{name}] host_tier_probes={stats['host_tier_probes']} "
        f"host_scans={stats['host_scans']} "
        f"retrain_failures={drift.get('retrain_failures', 0)} "
        f"n_reshard_aborts={aborts} window_compiles={window_compiles} "
        f"wrong={json.dumps(report['wrong'], sort_keys=True)}")

    failures = []
    if wrong:
        failures.append(f"{wrong} wrong answers")
    if d["interpret_count"]:
        failures.append(f"{d['interpret_count']} dispatches in interpret "
                        "mode")
    for k in ("host_probe_count", "scan_fallback_count", "fallback_count"):
        if d[k]:
            failures.append(f"{k}={d[k]}")
    if stats["host_tier_probes"] or stats["host_scans"]:
        failures.append("host tier probes / host scans served requests")
    set_reasons = [k for k, v in d["fallback_reasons"].items() if v]
    if set_reasons:
        failures.append(f"fallback reasons set: {set_reasons}")
    if drift.get("retrain_failures", 0):
        failures.append(f"retrain_failures={drift['retrain_failures']}")
    if aborts:
        failures.append(f"n_reshard_aborts={aborts}")
    if need_flow and not nfl.use_flow:
        failures.append("use_flow is false")
    if n > 0 and pool <= DEFAULT_VMEM_BUDGET and need_flow:
        failures.append("phase A pools fit the VMEM budget")
    return {"nfl": nfl, "failures": failures}


def shard_devices(nfl) -> list:
    """The device each shard's serving pools sit on."""
    out = []
    for idx in indexes(nfl):
        devs = {d for a in idx._kernel_pools() for d in a.devices()}
        out.append(sorted(str(d) for d in devs))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: the sharded path (P=4 vs P=1) and nothing else")
    ap.add_argument("--n-keys", type=int, default=None,
                    help="phase A keys (and the --chips 4 phase); "
                         f"default {N_KEYS}")
    ap.add_argument("--n-small", type=int, default=None,
                    help=f"phase B keys; default {N_SMALL}")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    try:
        import jax

        from repro.kernels.backend import enable_compile_cache
    except ImportError as e:
        print(f"chip_smoke: cannot import the index package ({e}); run it "
              "from the root of a checkout", file=sys.stderr)
        return 1
    cache_dir = enable_compile_cache()
    counter = CompileCounter()
    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    log(f"platform={dev['platform']} device_kind={dev['kind']} "
        f"device_count={dev['count']} compile_cache={cache_dir}")
    if dev["platform"] != "tpu" and args.n_keys is args.n_small is None:
        # full size is for the chip; a rehearsal elsewhere names its sizes
        print(f"FAIL: platform is {dev['platform']!r}, not 'tpu' (pass "
              "--n-keys/--n-small for a CPU rehearsal)", file=sys.stderr)
        return 1
    n_keys = N_KEYS if args.n_keys is None else args.n_keys
    n_small = N_SMALL if args.n_small is None else args.n_small
    t_start = time.perf_counter()
    slack = 0 if dev["platform"] == "tpu" else 1
    failures = []
    if args.chips == 4:
        if len(devs) < 4:
            failures.append(f"--chips 4 needs 4 devices, found {len(devs)}")
        else:
            ans4, ans1 = [], []
            p4 = run_phase("P4", n_keys, args.seed, 4, True, counter,
                           slack, ans4)
            placed = shard_devices(p4["nfl"])
            log(f"[P4] shard devices: {placed}")
            if len({tuple(p) for p in placed}) != 4 or any(
                    len(p) != 1 for p in placed):
                failures.append(f"shards not on 4 distinct devices: "
                                f"{placed}")
            del p4["nfl"]
            p1 = run_phase("P1", n_keys, args.seed, 1, True, counter,
                           slack, ans1)
            same = len(ans4) == len(ans1) and all(
                a.shape == b.shape and bool((a == b).all())
                for a, b in zip(ans4, ans1))
            log(f"[P4 vs P1] answers equal: {same} ({len(ans4)} batches)")
            if not same:
                failures.append("P=4 and P=1 answers differ")
            failures += [f"P4: {f}" for f in p4["failures"]]
            failures += [f"P1: {f}" for f in p1["failures"]]
            dev["count"] = 4
    else:
        a = run_phase("A", n_keys, args.seed, 1, True, counter, slack)
        del a["nfl"]
        b = run_phase("B", n_small, args.seed + 7, 1, False, counter,
                      slack)
        failures += [f"A: {f}" for f in a["failures"]]
        failures += [f"B: {f}" for f in b["failures"]]
    log(f"total_s={time.perf_counter() - t_start:.1f} "
        f"compiles={counter.compiles} cache_hits={counter.cache_hits} "
        f"cache_misses={counter.cache_misses}")
    if dev["platform"] != "tpu":
        failures.append(f"platform is {dev['platform']!r}, not 'tpu'")
    if failures:
        for f in failures:
            print(f"FAIL: {f}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": dev}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
