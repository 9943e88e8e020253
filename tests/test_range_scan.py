"""Fused tier-merged range scans + tombstone deletes (DESIGN.md §12).

Range semantics are over positioning-key order: without a flow that is
the key order itself (the f32 cast is monotone), with a flow it is the
NF-transformed order.  Every oracle here is therefore built in z-space —
live identities filtered by ``zlo <= z(k) < zhi`` — which holds across
flow on/off, mid-fold, tombstoned, and tier-resident states.
"""

import numpy as np
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ModuleNotFoundError:  # optional dep: seeded-random fallback
    from _hyp_fallback import given, settings, st

from repro.core.flat_afli import FlatAFLI, FlatAFLIConfig, split_key_bits

_TIGHT = dict(rebuild_frac=0.1, delta_cap=24, fold_step_keys=48,
              fold_work_factor=4.0)


def _expect(oracle_kz, zlo, zhi):
    """Sorted payloads of live entries with z in [zlo, zhi)."""
    return np.sort(np.array([p for (z, p) in oracle_kz.values()
                             if zlo <= z < zhi], dtype=np.int64))


def _check_scan(idx_or_nfl, oracle_kz, lo_keys, hi_keys, zfn, cap):
    """scan_batch vs the z-space dict oracle (multiset equality; counts
    and totals consistent).  Skips truncated queries (asserted on
    separately)."""
    pv, cnt, tot = idx_or_nfl.scan_batch(np.asarray(lo_keys, np.float64),
                                         np.asarray(hi_keys, np.float64),
                                         cap=cap)
    zlo = zfn(np.asarray(lo_keys, np.float64))
    zhi = zfn(np.asarray(hi_keys, np.float64))
    for i in range(len(lo_keys)):
        if tot[i] > cap:
            continue
        exp = _expect(oracle_kz, zlo[i], zhi[i])
        got = np.sort(pv[i, :cnt[i]])
        assert np.array_equal(got, exp), (
            f"range {i}: [{lo_keys[i]}, {hi_keys[i]}) -> {got} != {exp}")
        assert (pv[i, cnt[i]:] == -1).all()
    return pv, cnt, tot


def _z32(keys):
    return np.asarray(keys, np.float64).astype(np.float32)


def test_scan_basic_and_empty_ranges():
    rng = np.random.default_rng(0)
    keys = np.unique(rng.uniform(0, 1e9, 3000))
    pv = np.arange(len(keys), dtype=np.int64)
    idx = FlatAFLI()
    idx.build(keys, pv)
    oracle = {k: (z, p) for k, z, p in zip(keys, _z32(keys), pv)}

    los = rng.choice(keys, 40)
    his = los + rng.uniform(1e4, 1e7, 40)
    _check_scan(idx, oracle, los, his, _z32, cap=128)

    # empty ranges: lo == hi, inverted, and a gap between two keys
    gap_lo = (keys[10] + keys[11]) / 2
    pv_e, cnt_e, tot_e = idx.scan_batch(
        np.array([keys[5], keys[99], gap_lo]),
        np.array([keys[5], keys[50], np.nextafter(keys[11], 0)]), cap=64)
    assert (cnt_e == 0).all() and (tot_e == 0).all()
    assert (pv_e == -1).all()


def test_scan_spans_node_boundaries():
    """Ranges covering large key stretches cross model/dense node
    boundaries of the flattened tree; the rank-ordered scan pool must
    emit one contiguous run regardless."""
    rng = np.random.default_rng(1)
    keys = np.unique(np.floor(rng.lognormal(0, 2, 4000) * 1e9))
    pv = np.arange(len(keys), dtype=np.int64)
    idx = FlatAFLI()
    idx.build(keys, pv)
    oracle = {k: (z, p) for k, z, p in zip(keys, _z32(keys), pv)}
    # spans of hundreds of keys at several tree regions
    starts = np.array([0, len(keys) // 3, 2 * len(keys) // 3,
                       len(keys) - 600])
    los = keys[starts]
    his = keys[starts + 500]
    pv_r, cnt_r, _ = _check_scan(idx, oracle, los, his, _z32, cap=1024)
    assert (cnt_r == 500).all()
    # in-range results arrive in positioning-key (== key) order
    for i in range(len(los)):
        row = pv_r[i, :cnt_r[i]]
        assert np.array_equal(row, np.sort(row))


def test_scan_duplicate_pkeys():
    """Distinct f64 identities colliding to one f32 positioning key must
    all be emitted by a range covering the collision run."""
    base = 1.0e9  # f32 ulp at 1e9 is 64: consecutive ints collide
    keys = base + np.arange(48, dtype=np.float64)
    pv = np.arange(len(keys), dtype=np.int64)
    assert len(np.unique(_z32(keys))) < len(keys)  # real collisions
    idx = FlatAFLI()
    idx.build(keys, pv)
    oracle = {k: (z, p) for k, z, p in zip(keys, _z32(keys), pv)}
    _check_scan(idx, oracle, [base - 1e3], [base + 1e3], _z32, cap=128)


def test_scan_cap_truncation():
    rng = np.random.default_rng(2)
    keys = np.unique(rng.uniform(0, 1e9, 2000))
    pv = np.arange(len(keys), dtype=np.int64)
    idx = FlatAFLI()
    idx.build(keys, pv)
    cap = 16
    lo, hi = keys[100], keys[400]  # 300 members >> cap
    pv_r, cnt_r, tot_r = idx.scan_batch([lo], [hi], cap=cap)
    assert tot_r[0] == 300 and tot_r[0] > cap
    assert cnt_r[0] == cap  # no tiers -> every candidate is live
    # truncation keeps the FIRST cap candidates in key order
    assert np.array_equal(pv_r[0], pv[100:100 + cap])
    # the dispatch counters saw the truncation
    from repro.kernels import ops

    assert ops.fused_lookup_stats()["scan_trunc_count"] >= 1


def test_scan_kernel_vs_host_oracle_bit_parity():
    """The fused kernel and the host fallback must agree bit-for-bit
    with every tier live: static tree + compacted run + active delta +
    tombstones, mid-fold included."""
    rng = np.random.default_rng(3)
    keys = np.unique(rng.uniform(0, 1e9, 1500))
    pv = np.arange(len(keys), dtype=np.int64)
    idx = FlatAFLI(FlatAFLIConfig(**_TIGHT))
    idx.build(keys[::2], pv[::2])
    idx.insert_batch(keys[1::2][:300], pv[1::2][:300] + 1_000_000)
    idx.delete_batch(keys[::2][:150])
    assert idx._delta_pk.shape[0] or idx._run_pk.shape[0]

    los = rng.choice(keys, 64)
    his = los + rng.uniform(1e5, 1e8, 64)
    got = idx.scan_batch(los, his, cap=96)
    assert idx.last_scan_dispatch["path"] == "fused"
    exp = idx._range_scan_host(_z32(los), _z32(his), 96)
    for g, e in zip(got, exp):
        assert np.array_equal(g, e)


def test_tombstone_point_and_range_through_fold():
    """Deleted keys are invisible to point and range reads before and
    after folds; re-insert after delete resurrects with the new
    payload."""
    rng = np.random.default_rng(4)
    keys = np.unique(rng.uniform(0, 1e9, 1200))
    pv = np.arange(len(keys), dtype=np.int64)
    idx = FlatAFLI(FlatAFLIConfig(**_TIGHT))
    idx.build(keys, pv)
    n0 = idx.n_keys

    dk = keys[200:260]
    ok = idx.delete_batch(dk)
    assert ok.all() and idx.n_keys == n0 - 60
    assert (idx.lookup_batch(dk) == -1).all()
    assert not idx.contains_batch(dk).any()
    oracle = {k: (z, p) for k, z, p in zip(keys, _z32(keys), pv)
              if k not in set(dk.tolist())}
    _check_scan(idx, oracle, [keys[150]], [keys[300]], _z32, cap=256)

    # fold: tombstoned identities are physically dropped
    idx.rebuild()
    assert (idx.lookup_batch(dk) == -1).all()
    _check_scan(idx, oracle, [keys[150]], [keys[300]], _z32, cap=256)
    assert idx.stats()["scan_pool_len"] == n0 - 60

    # resurrect a deleted key with a new payload
    idx.insert_batch(dk[:10], np.arange(10) + 5_000_000)
    assert np.array_equal(idx.lookup_batch(dk[:10]),
                          np.arange(10) + 5_000_000)
    for k, p in zip(dk[:10], np.arange(10) + 5_000_000):
        oracle[k] = (np.float32(k), p)
    _check_scan(idx, oracle, [keys[150]], [keys[300]], _z32, cap=256)


def _drive_scan_interleaving(obj, rng, pool, n_ops, zfn, cap,
                             exact_endpoints=True):
    """Random insert/delete/lookup/scan/rebuild interleavings vs the
    z-space dict oracle at every step (the §12 analog of the mixed
    property harness): crosses delta merges, incremental folds, and
    tombstone drops.

    ``exact_endpoints=False`` perturbs scan endpoints off the stored
    keys — required under a flow, where a fold re-keys serve-path-
    divergent identities at their in-kernel z (§8 shadows, 1 ulp from
    the build z the oracle knows), making an endpoint exactly equal to
    a stored key's build z ambiguous by construction."""
    oracle = {}
    n0 = len(pool) // 2
    build_keys, build_pv = pool[:n0], np.arange(n0, dtype=np.int64)
    if isinstance(obj, FlatAFLI):
        obj.build(build_keys, build_pv)
    else:
        obj.bulkload(build_keys, build_pv)
    zb = zfn(build_keys)
    for k, z, p in zip(build_keys, zb, build_pv):
        oracle[k] = (z, p)
    for step in range(n_ops):
        op = rng.choice(["insert", "delete", "lookup", "scan", "rebuild"],
                        p=[0.3, 0.15, 0.2, 0.3, 0.05])
        if op == "rebuild":
            (obj.index if hasattr(obj, "index") else obj).rebuild()
            continue
        size = int(rng.integers(1, 20))
        if op == "insert":
            k = rng.choice(pool, size, replace=False)
            v = np.arange(size, dtype=np.int64) + (step + 1) * 10_000
            obj.insert_batch(k, v)
            for kk, zz, vv in zip(k, zfn(k), v):
                oracle[kk] = (zz, vv)
        elif op == "delete":
            live = np.array(sorted(oracle))
            k = rng.choice(live, min(size, len(live)), replace=False)
            if rng.random() < 0.3:  # definite misses must report False
                k = np.concatenate([k, k + 0.123])
            ok = obj.delete_batch(k)
            for kk, o in zip(k, ok):
                assert o == (kk in oracle)
                oracle.pop(kk, None)
        elif op == "lookup":
            k = rng.choice(pool, size, replace=False)
            res = obj.lookup_batch(k)
            exp = np.array([oracle[x][1] if x in oracle else -1
                            for x in k])
            assert np.array_equal(res, exp), f"step {step} point lookup"
        else:  # scan
            lo = rng.choice(pool, 3)
            if not exact_endpoints:
                lo = lo * (1 + rng.uniform(1e-7, 1e-5, 3))
            hi = np.where(rng.random(3) < 0.15, lo,  # some empties
                          lo * (1 + rng.uniform(0.001, 0.3, 3)))
            _check_scan(obj, oracle, lo, hi, zfn, cap)
    # closing sweep: a wide scan checked against the z-space oracle (a
    # key-space "whole domain" range does NOT cover all of z-space when
    # the flow is non-monotone — membership is always by z)
    live = np.array(sorted(oracle))
    if len(live):
        lo = live[:1] if exact_endpoints else live[:1] * (1 + 1e-7)
        _check_scan(obj, oracle, lo, live[-1:] * 1.01, zfn, cap)


@settings(max_examples=6, deadline=None)
@given(st.integers(min_value=0, max_value=10**6))
def test_scan_interleaving_flat_direct(seed):
    """FlatAFLI alone (no flow): tight tiers, many boundary crossings."""
    rng = np.random.default_rng(seed)
    pool = np.unique(rng.uniform(1.0, 1e9, 360))
    idx = FlatAFLI(FlatAFLIConfig(**_TIGHT))
    _drive_scan_interleaving(idx, rng, pool, n_ops=12, zfn=_z32, cap=1024)
    assert idx.stats()["n_keys"] == len(idx._id_set)  # delete bookkeeping


@pytest.mark.parametrize("force_flow", [False, True])
def test_scan_interleaving_nfl(force_flow):
    """NFL(backend='flat'), flow forced on/off: the full serving stack
    (kernel NF on endpoints + scan-pool merge + tier probes) against the
    z-space dict oracle, deletes included."""
    from repro.core.nfl import NFL, NFLConfig
    from repro.core.train_flow import FlowTrainConfig

    rng = np.random.default_rng(53 + int(force_flow))
    pool = np.unique(np.floor(rng.lognormal(0, 2, 500) * 1e9))
    nfl = NFL(NFLConfig(flow_train=FlowTrainConfig(epochs=1),
                        backend="flat", force_flow=force_flow,
                        flat_index=FlatAFLIConfig(**_TIGHT)))

    def zfn(keys):
        keys = np.asarray(keys, np.float64)
        if not nfl.use_flow:
            return keys.astype(np.float32)
        return nfl._transform(nfl.flow_params, nfl.normalizer,
                              keys).astype(np.float32)

    _drive_scan_interleaving(nfl, rng, pool, n_ops=10, zfn=zfn, cap=1024,
                             exact_endpoints=not force_flow)
    assert nfl.use_flow == force_flow
    # lookup_range is the same entry point
    lo = np.array([pool[0]])
    hi = np.array([pool[-1] * 1.01])
    a = nfl.scan_batch(lo, hi, cap=1024)
    b = nfl.lookup_range(lo, hi, cap=1024)
    for x, y in zip(a, b):
        assert np.array_equal(x, y)


def test_scan_before_build_serves_from_tiers():
    """Insert-before-build: ranges resolve from the write tiers alone
    over an empty scan pool."""
    idx = FlatAFLI(FlatAFLIConfig(**_TIGHT))
    keys = np.array([10.0, 20.0, 30.0, 40.0])
    idx.insert_batch(keys, np.array([1, 2, 3, 4]))
    pv_r, cnt_r, tot_r = idx.scan_batch([15.0], [45.0], cap=16)
    assert cnt_r[0] == 3 and tot_r[0] == 3
    assert np.array_equal(np.sort(pv_r[0, :3]), np.array([2, 3, 4]))
    idx.delete_batch(np.array([30.0]))
    pv_r, cnt_r, _ = idx.scan_batch([15.0], [45.0], cap=16)
    assert np.array_equal(np.sort(pv_r[0, :cnt_r[0]]), np.array([2, 4]))


def test_scan_zero_retrace_steady_state():
    """Steady-state range traffic reuses one traced kernel: after the
    first scan warmed the shape, further scans (including across a fold
    swap) must not grow any serving jit cache or repack a pool."""
    from repro.kernels import ops

    rng = np.random.default_rng(6)
    keys = np.unique(rng.uniform(0, 1e9, 6000))
    pv = np.arange(len(keys), dtype=np.int64)
    idx = FlatAFLI(FlatAFLIConfig(rebuild_frac=0.05, delta_cap=128,
                                  fold_step_keys=2048))
    idx.build(keys[::2], pv[::2])
    # warm every route: scans with tiers empty AND live, plus folds
    idx.insert_batch(keys[1::2][:200], pv[1::2][:200])
    los = rng.choice(keys, 64)
    idx.scan_batch(los, los + 1e6)
    idx.delete_batch(keys[::2][:50])
    idx.scan_batch(los, los + 1e6)
    while idx._fold is not None:
        idx.insert_batch(keys[1::2][200:210], pv[1::2][200:210])
    idx.scan_batch(los, los + 1e6)

    ops.reset_fused_lookup_stats()
    idx._serving.reset_stats()
    for i in range(6):
        q = rng.choice(keys, 64)
        idx.scan_batch(q, q + rng.uniform(1e4, 1e7))
        idx.insert_batch(keys[1::2][220 + 10 * i:230 + 10 * i],
                         np.arange(10) + i)
        idx.delete_batch(rng.choice(keys[::2][100:], 5, replace=False))
    stats = ops.fused_lookup_stats()
    assert stats["scan_fused_count"] == stats["scan_dispatch_count"] > 0
    assert stats["scan_fallback_count"] == 0
    assert stats["retrace_count"] == 0, "steady-state scan retraced"
    assert idx._serving.stats()["tier_repacks"] == 0
    assert idx.n_host_scans == 0


def test_afli_delete_batch_vectorized_semantics():
    """NFL afli-backend delete_batch keeps per-key ok semantics after
    the loop tightening: present -> True (and gone), absent -> False."""
    from repro.core.nfl import NFL, NFLConfig
    from repro.core.train_flow import FlowTrainConfig

    rng = np.random.default_rng(8)
    keys = np.unique(rng.uniform(0, 1e9, 2500))
    pv = np.arange(len(keys), dtype=np.int64)
    nfl = NFL(NFLConfig(flow_train=FlowTrainConfig(epochs=1),
                        backend="afli"))
    nfl.bulkload(keys, pv)
    mixed = np.concatenate([keys[:40], keys[:20] + 0.5])
    ok = nfl.delete_batch(mixed)
    assert ok[:40].all() and not ok[40:].any()
    assert (nfl.lookup_batch(keys[:40]) == -1).all()
    assert not nfl.delete_batch(keys[:40]).any()


def _tiered(static, run=(), delta=(), delete=()):
    """FlatAFLI with ``static`` built into the tree and scan pool, ``run``
    (key, payload) pairs retired into the compacted run, then ``delta``
    pairs and ``delete`` tombstones left in the active delta; no fold."""
    idx = FlatAFLI(FlatAFLIConfig(delta_cap=4096, rebuild_frac=4.0))
    static = np.asarray(static, np.float64)
    idx.build(static, np.arange(static.shape[0], dtype=np.int64))
    for pairs in (run, delta):
        if len(pairs):
            k, v = zip(*pairs)
            idx.insert_batch(np.array(k, np.float64), np.array(v))
        if pairs is run:
            idx._merge_delta_into_run()
    if len(delete):
        assert idx.delete_batch(np.asarray(delete, np.float64)).all()
    assert idx._fold is None
    return idx


_BASE = 2.0 ** 30   # f32 ulp 128: base + 256*g + i (|i| < 64) collide


def _scan_case(case):
    """(index, lo keys, hi keys, cap) for one tie/truncation edge."""
    if case == "equal-keys":
        # every group's identities share one positioning key and are
        # spread over all three tiers; some identities re-inserted
        # across tiers, some deleted
        keys = _BASE + 256.0 * np.arange(12)[:, None] + np.arange(18)
        tier = np.arange(18) % 3
        idx = _tiered(keys[:, tier == 0].ravel(),
                      run=[(k, 10_000 + i) for i, k in
                           enumerate(np.concatenate(
                               [keys[:, tier == 1].ravel(),
                                keys[::2, 0]]))],
                      delta=[(k, 20_000 + i) for i, k in
                             enumerate(np.concatenate(
                                 [keys[:, tier == 2].ravel(),
                                  keys[1::3, 1], keys[::4, 3]]))],
                      delete=keys[::5, 6])
        lo = _BASE + 256.0 * np.array([0, 2, 5, 0, 11]) - 128
        hi = _BASE + 256.0 * np.array([1, 5, 6, 12, 12]) + 128
        return idx, lo, hi, 512
    if case == "tombstone":
        # a delta tombstone masks the run's copy and the pool's copy
        keys = np.arange(1, 401, dtype=np.float64) * 1000.0
        idx = _tiered(keys, run=[(k, 50_000 + i) for i, k in
                                 enumerate(keys[10:300:3])],
                      delete=np.union1d(keys[10:300:6], keys[11:300:7]))
        lo, hi = keys[[0, 5, 100, 250]], keys[[60, 120, 320, 399]]
        return idx, lo, hi, 512
    if case == "reinsert":
        # re-inserts supersede the pool's copy (from the run and the
        # delta) and the run's copy (from the delta)
        keys = np.arange(1, 301, dtype=np.float64) * 1000.0
        idx = _tiered(keys, run=[(k, 60_000 + i) for i, k in
                                 enumerate(keys[::4])],
                      delta=[(k, 70_000 + i) for i, k in
                             enumerate(np.concatenate([keys[::8],
                                                       keys[1::5]]))])
        lo, hi = keys[[0, 40, 150]], keys[[90, 130, 299]]
        return idx, lo, hi, 512
    if case == "truncation-mixed":
        # interleaved tiers with ties, superseded copies and tombstones:
        # the cap falls among candidates of all three tiers
        keys = np.arange(1, 601, dtype=np.float64) * 1000.0
        ties = _BASE + np.arange(30)
        idx = _tiered(np.concatenate([keys[0::3], ties[0::3]]),
                      run=[(k, 80_000 + i) for i, k in
                           enumerate(np.concatenate(
                               [keys[1::3], ties[1::3], keys[0:90:9]]))],
                      delta=[(k, 90_000 + i) for i, k in
                             enumerate(np.concatenate(
                                 [keys[2::3], ties[2::3], keys[1:90:7]]))],
                      delete=keys[3:90:11])
        lo = np.array([keys[0], keys[17], keys[40], _BASE - 128])
        hi = np.array([keys[80], keys[70], keys[500], _BASE + 128])
        return idx, lo, hi, 16
    if case == "wide-cap":
        # a scan_cap of over a thousand, many lane chunks' worth of
        # compares, with the same ties, superseded copies, tombstones
        # and truncation
        keys = np.arange(1, 3001, dtype=np.float64) * 1000.0
        ties = _BASE + np.arange(60)
        idx = _tiered(np.concatenate([keys[0::3], ties[0::3]]),
                      run=[(k, 80_000 + i) for i, k in
                           enumerate(np.concatenate(
                               [keys[1::3], ties[1::3], keys[0:900:9]]))],
                      delta=[(k, 90_000 + i) for i, k in
                             enumerate(np.concatenate(
                                 [keys[2::3], ties[2::3], keys[1:900:7]]))],
                      delete=keys[3:900:11])
        lo = np.array([keys[0], keys[17], keys[400], _BASE - 128])
        hi = np.array([keys[1500], keys[900], keys[2999], _BASE + 128])
        return idx, lo, hi, 1100
    if case == "one-tier-over-cap":
        # the run alone, then the pool alone, holds more than scan_cap
        keys = np.arange(1, 1001, dtype=np.float64) * 1000.0
        run = keys[200:500] + 500.0
        idx = _tiered(keys, run=[(k, 40_000 + i) for i, k in
                                 enumerate(run)],
                      delta=[(k, 30_000 + i) for i, k in
                             enumerate(keys[600:604] + 250.0)])
        lo = np.array([run[0], keys[600], keys[100], run[50] - 1.0])
        hi = np.array([run[200], keys[900], keys[210], run[52]])
        return idx, lo, hi, 32
    assert case == "empty-and-padding"
    # inverted, empty and gap ranges, and lanes equal to the batch's own
    # zero padding (lo == hi == 0), in a batch padded to a power of two
    keys = np.arange(1, 201, dtype=np.float64) * 1000.0
    idx = _tiered(keys, run=[(k, 1_000 + i) for i, k in
                             enumerate(keys[::3] + 500.0)],
                  delta=[(k, 2_000 + i) for i, k in
                         enumerate(keys[1::4] + 250.0)],
                  delete=keys[::7])
    lo = np.array([keys[90], keys[20], keys[5] + 600.0, 0.0, 0.0])
    hi = np.array([keys[10], keys[20], keys[6] - 100.0, 0.0, keys[199]])
    return idx, lo, hi, 128


@pytest.mark.parametrize("case", ["equal-keys", "tombstone", "reinsert",
                                  "truncation-mixed", "one-tier-over-cap",
                                  "empty-and-padding", "wide-cap"])
@pytest.mark.parametrize("route", ["fused", "xla"])
def test_scan_routes_match_host_oracle_at_edges(route, case, monkeypatch):
    """Both lowerings of ``scan_merge`` (the interpret-mode Pallas kernel
    and the XLA range route) against ``_range_scan_host``, bit for bit in
    payloads, counts and totals, at the merge's tie and truncation
    edges: ties across tiers break newest tier first, then by index, and
    truncation keeps the first ``cap`` candidates of that order."""
    from repro.kernels import ops

    idx, lo, hi, cap = _scan_case(case)
    assert idx._run_pk.shape[0] and idx._delta_pk.shape[0]
    if route == "xla":
        monkeypatch.setattr(ops, "traversal_route", lambda interpret: "xla")
    got = idx.scan_batch(lo, hi, cap=cap)
    assert idx.last_scan_dispatch["path"] == route
    assert idx.last_scan_dispatch["tier_path"] != "none"
    want = idx._range_scan_host(_z32(lo), _z32(hi), cap)
    for g, w in zip(got, want):
        assert g.dtype == w.dtype and np.array_equal(g, w)
    _, cnt, tot = got
    if case in ("truncation-mixed", "one-tier-over-cap", "wide-cap"):
        assert (tot > cap).sum() >= 2
    elif case == "empty-and-padding":
        assert (cnt[:4] == 0).all() and (tot[:4] == 0).all() and cnt[4] > 0
    else:
        assert (tot <= cap).all() and (cnt > 0).all()


def test_range_route_has_no_scan_cap_loop():
    """The rank merge has no loop over ``scan_cap``: tracing the XLA
    range route at ``scan_cap=128`` with live tiers for a 64-lane batch
    finds no ``while`` and no ``scan`` of ``scan_cap`` or more trips (the
    only loops are the endpoint binary searches, ``*_iters`` rounds)."""
    import jax
    import jax.numpy as jnp

    from repro.analysis.jaxpr_checks import walk_jaxpr
    from repro.kernels.fused_lookup import TierPools
    from repro.kernels.range_scan import ScanPool, xla_range_scan

    def pool(n):
        return (jnp.zeros((n,), jnp.float32), jnp.zeros((n,), jnp.uint32),
                jnp.zeros((n,), jnp.uint32), jnp.zeros((n,), jnp.int32),
                jnp.zeros((128,), jnp.int32))

    feats = jnp.zeros((64, 1), jnp.float32)

    def route(flo, fhi, sp, tiers):
        return xla_range_scan(flo, fhi, jnp.zeros((1, 1), jnp.float32),
                              sp, tiers, dim=1, scan_cap=128,
                              scan_iters=24, use_flow=False,
                              probe_tiers=True, run_iters=22, run_window=16,
                              delta_iters=17, delta_window=4)

    closed = jax.make_jaxpr(route)(feats, feats, ScanPool(*pool(1 << 12)),
                                   TierPools(*pool(1 << 10), *pool(1 << 10)))
    loops = []
    walk_jaxpr(closed.jaxpr, lambda eqn, _: loops.append(
        (eqn.primitive.name, eqn.params.get("length")))
        if eqn.primitive.name in ("scan", "while") else None)
    assert loops and all(p == "scan" for p, _ in loops)
    assert max(n for _, n in loops) < 128, loops
