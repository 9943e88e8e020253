"""SLO front-end semantics (DESIGN.md §16): exact terminal accounting,
dict-oracle correctness under overload / mid-fold / mid-re-flow write
storms (flat + sharded), fault injection, and the concurrent telemetry
reset (§16 satellite of §11).

The oracle seam is ``FrontEnd.on_batch_dispatched``: the hook fires
once per batch in dispatch order, which is exactly the serialization
order the index applies, so a dict oracle driven from the hook is
bit-exact even while read batches are still in flight behind writes.
"""

import threading
import time

import numpy as np
import pytest

from repro.core.drift import DriftConfig, ReshardConfig
from repro.core.flat_afli import FlatAFLIConfig
from repro.core.nfl import NFL, NFLConfig
from repro.core.train_flow import FlowTrainConfig
from repro.kernels import ops
from repro.serve import faults
from repro.serve.frontend import (COMPLETED, EXPIRED, SHED, FrontEnd,
                                  FrontEndConfig, ServiceRequest)

_TERMINAL = (COMPLETED, SHED, EXPIRED)
_SLACK = 60.0   # "no deadline pressure" SLO for correctness-only tests


def _build_nfl(n=1500, seed=0, shards=1, **cfg_kw):
    rng = np.random.default_rng(seed)
    keys = np.unique(rng.uniform(0.0, 1e6, 3 * n))[:n]
    pay = np.arange(keys.shape[0], dtype=np.int64)
    nfl = NFL(NFLConfig(backend="flat", shards=shards, force_flow=False,
                        **cfg_kw))
    nfl.bulkload(keys, pay)
    return nfl, keys, dict(zip(keys.tolist(), pay.tolist()))


class _Oracle:
    """Dict oracle applied in dispatch order via the front-end hook;
    records per-request expectations on the request objects."""

    def __init__(self, oracle: dict):
        self.d = oracle
        self.expected = {}

    def hook(self, op, reqs):
        if op == "point":
            for r in reqs:
                self.expected[r.rid] = self.d.get(r.key, -1)
        elif op == "range":
            for r in reqs:
                ks = sorted(k for k in self.d if r.key <= k < r.hi)
                self.expected[r.rid] = [self.d[k] for k in ks]
        elif op == "insert":
            for r in reqs:
                self.d[r.key] = r.payload
        else:  # delete
            for r in reqs:
                self.expected[r.rid] = r.key in self.d
                self.d.pop(r.key, None)

    def check(self, reqs) -> int:
        """Count served results diverging from the dispatch-time
        expectation (completed AND late-expired — late results must
        still be correct, they are just useless)."""
        wrong = 0
        for r in reqs:
            if r.rid not in self.expected or r.result is None:
                continue
            exp = self.expected[r.rid]
            if r.op == "point" or r.op == "delete":
                wrong += int(r.result != exp)
            elif r.op == "range":
                # totals counts span *candidates* (pre-dedup, incl.
                # shadowed copies); the live results are the lanes
                got, _tot = r.result
                wrong += int(list(got) != list(exp))
        return wrong


def _mixed_requests(rng, n, known, spare, deadline_s, p=(0.7, 0.1, 0.15,
                                                         0.05)):
    reqs, si = [], 0
    pool = list(known)
    for rid in range(n):
        u = rng.random()
        if u < p[0] or si >= len(spare):
            r = ServiceRequest(rid, "point", float(rng.choice(pool)),
                               deadline_s=deadline_s)
        elif u < p[0] + p[1]:
            lo = float(rng.choice(pool))
            r = ServiceRequest(rid, "range", lo, hi=lo * (1 + 1e-3),
                               deadline_s=deadline_s)
        elif u < p[0] + p[1] + p[2]:
            r = ServiceRequest(rid, "insert", float(spare[si]),
                               payload=1_000_000 + si,
                               deadline_s=deadline_s)
            pool.append(float(spare[si]))
            si += 1
        else:
            r = ServiceRequest(rid, "delete", float(rng.choice(pool)),
                               deadline_s=deadline_s)
        reqs.append(r)
    return reqs


def _submit_drain(fe, reqs):
    for r in reqs:
        fe.submit(r)
    fe.drain()


def _assert_terminal_exactly_once(fe, reqs):
    c = fe.counters
    assert c["admitted"] == len(reqs)
    assert c["admitted"] == c["completed"] + c["shed"] + c["expired"]
    for r in reqs:
        assert r.state in _TERMINAL, (r.rid, r.state)
        assert r.t_done >= r.t_submit >= 0.0


def test_terminal_state_property_mixed_deadlines():
    """Property sweep: random op mixes with a spread of deadlines (some
    unmeetably tight, some slack) — every request lands in exactly one
    terminal state, the accounting identity is exact, and every served
    result matches the dispatch-time oracle."""
    nfl, keys, oracle = _build_nfl()
    spare = np.unique(np.random.default_rng(9).uniform(2e6, 3e6, 600))
    si = 0
    for trial in range(4):
        rng = np.random.default_rng(100 + trial)
        orc = _Oracle(oracle)
        fe = FrontEnd(nfl, FrontEndConfig(max_batch=32,
                                          batch_timeout_s=5e-4))
        fe.on_batch_dispatched = orc.hook
        reqs = _mixed_requests(rng, 150, keys, spare[si:si + 40],
                               deadline_s=_SLACK)
        si += 40
        # re-stamp a third of the deadlines unmeetably tight so shed /
        # expired paths actually run
        for r in reqs:
            if rng.random() < 0.33:
                r.deadline_s = 1e-6
        _submit_drain(fe, reqs)
        _assert_terminal_exactly_once(fe, reqs)
        assert orc.check(reqs) == 0
        # the tight third cannot all complete; terminal variety exists
        assert fe.counters["shed"] + fe.counters["expired"] > 0


def test_admission_off_serves_everything_exactly():
    nfl, keys, oracle = _build_nfl(seed=1)
    rng = np.random.default_rng(2)
    spare = np.unique(rng.uniform(2e6, 3e6, 200))
    orc = _Oracle(oracle)
    fe = FrontEnd(nfl, FrontEndConfig(max_batch=64, batch_timeout_s=1e-3,
                                      admission=False,
                                      expire_queued=False))
    fe.on_batch_dispatched = orc.hook
    reqs = _mixed_requests(rng, 300, keys, spare, deadline_s=_SLACK)
    _submit_drain(fe, reqs)
    _assert_terminal_exactly_once(fe, reqs)
    assert fe.counters["shed"] == 0
    # slack deadlines + no admission: everything completes, exactly
    assert fe.counters["completed"] == len(reqs)
    assert orc.check(reqs) == 0


def test_overload_sheds_and_stays_exact():
    """2x-style overload model: everything submitted at once with a
    deadline shorter than the backlog can serve — admission control must
    shed rather than serve late, and nothing served may be wrong."""
    nfl, keys, oracle = _build_nfl(seed=3)
    rng = np.random.default_rng(4)
    orc = _Oracle(oracle)
    fe = FrontEnd(nfl, FrontEndConfig(max_batch=32, batch_timeout_s=1e-4))
    fe.on_batch_dispatched = orc.hook
    # prime the service-time model so admission predictions are live,
    # and time a batch: the backlog (25 batches of 32) is set against a
    # deadline of 8 batch times, an overload however fast the route is
    batch_s = []
    for _ in range(6):
        t0 = time.perf_counter()
        nfl.lookup_batch(rng.choice(keys, 32, replace=False))
        batch_s.append(time.perf_counter() - t0)
    deadline_s = 8 * float(np.median(batch_s[3:]))
    reqs = [ServiceRequest(i, "point", float(rng.choice(keys)),
                           deadline_s=deadline_s) for i in range(800)]
    _submit_drain(fe, reqs)
    _assert_terminal_exactly_once(fe, reqs)
    assert fe.counters["shed"] + fe.counters["expired"] > 0
    assert orc.check(reqs) == 0
    # everything that did complete met its deadline (reads only count
    # completed when on time)
    for r in reqs:
        if r.state == COMPLETED:
            assert r.latency_s <= r.deadline_s + 1e-9


def test_sharded_frontend_mixed_exact():
    nfl, keys, oracle = _build_nfl(n=1200, seed=5, shards=2)
    rng = np.random.default_rng(6)
    spare = np.unique(rng.uniform(2e6, 3e6, 300))
    orc = _Oracle(oracle)
    fe = FrontEnd(nfl, FrontEndConfig(max_batch=48, batch_timeout_s=1e-3))
    fe.on_batch_dispatched = orc.hook
    reqs = _mixed_requests(rng, 400, keys, spare, deadline_s=_SLACK)
    _submit_drain(fe, reqs)
    _assert_terminal_exactly_once(fe, reqs)
    assert orc.check(reqs) == 0
    assert fe.counters["completed"] > 0


def test_mid_fold_write_storm_exact():
    """Write-heavy stream through squeezed tier bounds: batches land
    mid-fold constantly; in-flight reads dispatched around fold ticks
    must still match the dispatch-time oracle."""
    nfl, keys, oracle = _build_nfl(
        n=1200, seed=7,
        flat_index=FlatAFLIConfig(delta_cap=24, fold_step_keys=48,
                                  fold_work_factor=4.0))
    rng = np.random.default_rng(8)
    spare = np.unique(rng.uniform(2e6, 3e6, 2000))
    orc = _Oracle(oracle)
    fe = FrontEnd(nfl, FrontEndConfig(max_batch=24, batch_timeout_s=5e-4))
    fe.on_batch_dispatched = orc.hook
    reqs = _mixed_requests(rng, 500, keys, spare, deadline_s=_SLACK,
                           p=(0.40, 0.05, 0.45, 0.10))
    _submit_drain(fe, reqs)
    _assert_terminal_exactly_once(fe, reqs)
    assert orc.check(reqs) == 0


def test_mid_reflow_write_storm_exact():
    """Flow-on serving with an aggressive background re-flow: the §14
    machinery retrains and re-keys underneath the front-end while the
    stream keeps flowing.  Every served result stays oracle-exact
    across the atomic swap."""
    rng = np.random.default_rng(11)
    keys = np.unique(rng.lognormal(0, 2.0, 4000))[:1500]
    pay = np.arange(keys.shape[0], dtype=np.int64)
    nfl = NFL(NFLConfig(
        backend="flat", force_flow=True,
        flow_train=FlowTrainConfig(epochs=1),
        flat_index=FlatAFLIConfig(fold_step_keys=2048),
        drift=DriftConfig(reflow=True, threshold=1.2, min_tail=2,
                          check_every=64, window_keys=1024,
                          cooldown_keys=512, train_epochs=1,
                          train_batch=128, steps_per_tick=8, seed=0)))
    nfl.bulkload(keys, pay)
    oracle = dict(zip(keys.tolist(), pay.tolist()))
    # drift cluster: tight multiplicative jitter at the top quantiles
    centers = np.quantile(keys, np.linspace(0.9, 0.999, 8))
    drift = np.unique(np.concatenate(
        [c * (1 + rng.uniform(0, 1e-4, 150)) for c in centers]))
    drift = drift[~np.isin(drift, keys)]
    orc = _Oracle(oracle)
    fe = FrontEnd(nfl, FrontEndConfig(max_batch=32, batch_timeout_s=5e-4))
    fe.on_batch_dispatched = orc.hook
    reqs, si = [], 0
    pool = list(keys)
    for rid in range(420):
        if rng.random() < 0.5 and si < drift.shape[0]:
            r = ServiceRequest(rid, "insert", float(drift[si]),
                               payload=2_000_000 + si, deadline_s=_SLACK)
            pool.append(float(drift[si]))
            si += 1
        else:
            r = ServiceRequest(rid, "point", float(rng.choice(pool)),
                               deadline_s=_SLACK)
        reqs.append(r)
    _submit_drain(fe, reqs)
    _assert_terminal_exactly_once(fe, reqs)
    assert orc.check(reqs) == 0
    st = nfl.dispatch_stats()["drift"]
    assert st["enabled"] and st["checks"] > 0


# ------------------------------------------------------- fault injection
def test_fault_forced_fallback_exact_and_attributed():
    nfl, keys, oracle = _build_nfl(n=800, seed=12)
    rng = np.random.default_rng(13)
    orc = _Oracle(oracle)
    fe = FrontEnd(nfl, FrontEndConfig(max_batch=32, batch_timeout_s=1e-3,
                                      admission=False,
                                      expire_queued=False))
    fe.on_batch_dispatched = orc.hook
    nfl.dispatch_stats(reset=True)
    faults.injection_stats(reset=True)
    reqs = [ServiceRequest(i, "point", float(rng.choice(keys)),
                           deadline_s=_SLACK) for i in range(200)]
    with faults.inject(faults.FaultPlan(force_oracle=True)):
        _submit_drain(fe, reqs)
    _assert_terminal_exactly_once(fe, reqs)
    assert orc.check(reqs) == 0
    d = nfl.dispatch_stats()["dispatch"]
    assert d["fallback_count"] > 0 and d["fused_count"] == 0
    reason = d["fallback_reasons"]["point"]
    assert reason["component"] == "fault-injection"
    assert faults.injection_stats()["forced_fallbacks"] > 0
    # the plan is uninstalled on exit: the kernel path is back
    nfl.lookup_batch(keys[:16])
    assert nfl.dispatch_stats()["dispatch"]["fused_count"] > 0


def test_fault_transient_errors_are_retried():
    nfl, keys, oracle = _build_nfl(n=800, seed=14)
    rng = np.random.default_rng(15)
    orc = _Oracle(oracle)
    fe = FrontEnd(nfl, FrontEndConfig(max_batch=32, batch_timeout_s=1e-3,
                                      admission=False, expire_queued=False,
                                      retry_backoff_s=1e-4))
    fe.on_batch_dispatched = orc.hook
    reqs = [ServiceRequest(i, "point", float(rng.choice(keys)),
                           deadline_s=_SLACK) for i in range(150)]
    with faults.inject(faults.FaultPlan(dispatch_error_every=3)):
        _submit_drain(fe, reqs)
    _assert_terminal_exactly_once(fe, reqs)
    assert fe.counters["completed"] == len(reqs)
    assert fe.counters["retries"] > 0
    assert fe.counters["retry_giveups"] == 0
    assert orc.check(reqs) == 0


def test_fault_retry_exhaustion_sheds_loudly():
    """Every dispatch fails, including every retry: the batch must
    resolve as shed(reason=error) — bounded retries, no silent drop,
    no unbounded spin."""
    nfl, keys, _ = _build_nfl(n=400, seed=16)
    fe = FrontEnd(nfl, FrontEndConfig(max_batch=16, batch_timeout_s=1e-4,
                                      admission=False, expire_queued=False,
                                      max_retries=2, retry_backoff_s=1e-5))
    reqs = [ServiceRequest(i, "point", float(keys[i]), deadline_s=_SLACK)
            for i in range(40)]
    with faults.inject(faults.FaultPlan(dispatch_error_every=1)):
        _submit_drain(fe, reqs)
    _assert_terminal_exactly_once(fe, reqs)
    assert fe.counters["shed"] == len(reqs)
    assert fe.counters["retry_giveups"] > 0
    assert all(r.reason == "error" for r in reqs)


def test_fault_stalls_and_slow_folds_degrade_not_break():
    nfl, keys, oracle = _build_nfl(
        n=600, seed=17,
        flat_index=FlatAFLIConfig(delta_cap=24, fold_step_keys=48,
                                  fold_work_factor=4.0,
                                  rebuild_frac=0.02))
    rng = np.random.default_rng(18)
    spare = np.unique(rng.uniform(2e6, 3e6, 400))
    orc = _Oracle(oracle)
    fe = FrontEnd(nfl, FrontEndConfig(max_batch=16, batch_timeout_s=1e-4,
                                      admission=False, expire_queued=False))
    fe.on_batch_dispatched = orc.hook
    faults.injection_stats(reset=True)
    reqs = _mixed_requests(rng, 120, keys, spare, deadline_s=_SLACK,
                           p=(0.5, 0.0, 0.4, 0.1))
    with faults.inject(faults.FaultPlan(device_stall_s=1e-3, stall_every=4,
                                        fold_stall_s=1e-3)):
        _submit_drain(fe, reqs)
    _assert_terminal_exactly_once(fe, reqs)
    assert fe.counters["completed"] == len(reqs)
    assert orc.check(reqs) == 0
    st = faults.injection_stats()
    assert st["stalls"] > 0 and st["fold_stalls"] > 0


def test_fault_retrain_failure_backs_off_and_serves():
    rng = np.random.default_rng(19)
    keys = np.unique(rng.lognormal(0, 2.0, 3000))[:1200]
    pay = np.arange(keys.shape[0], dtype=np.int64)
    nfl = NFL(NFLConfig(
        backend="flat", force_flow=True,
        flow_train=FlowTrainConfig(epochs=1),
        drift=DriftConfig(reflow=True, threshold=1.2, min_tail=2,
                          check_every=64, window_keys=1024,
                          cooldown_keys=512, train_epochs=1,
                          train_batch=128, steps_per_tick=8, seed=0)))
    nfl.bulkload(keys, pay)
    oracle = dict(zip(keys.tolist(), pay.tolist()))
    centers = np.quantile(keys, np.linspace(0.9, 0.999, 8))
    drift = np.unique(np.concatenate(
        [c * (1 + rng.uniform(0, 1e-4, 120)) for c in centers]))
    drift = drift[~np.isin(drift, keys)]
    orc = _Oracle(oracle)
    fe = FrontEnd(nfl, FrontEndConfig(max_batch=32, batch_timeout_s=5e-4))
    fe.on_batch_dispatched = orc.hook
    reqs, si, pool = [], 0, list(keys)
    for rid in range(300):
        if rng.random() < 0.55 and si < drift.shape[0]:
            r = ServiceRequest(rid, "insert", float(drift[si]),
                               payload=3_000_000 + si, deadline_s=_SLACK)
            pool.append(float(drift[si]))
            si += 1
        else:
            r = ServiceRequest(rid, "point", float(rng.choice(pool)),
                               deadline_s=_SLACK)
        reqs.append(r)
    with faults.inject(faults.FaultPlan(retrain_failure=True), nfl=nfl):
        _submit_drain(fe, reqs)
    _assert_terminal_exactly_once(fe, reqs)
    assert orc.check(reqs) == 0
    st = nfl.dispatch_stats()["drift"]
    assert st["retrain_failures"] >= 1
    assert st["reflows_completed"] == 0


def _reshard_nfl(seed):
    return _build_nfl(
        n=1500, seed=seed, shards=4,
        flat_index=FlatAFLIConfig(rebuild_frac=0.1, delta_cap=24,
                                  fold_step_keys=48, fold_work_factor=4.0),
        reshard=ReshardConfig(enabled=True, hot_frac=1.8, min_load=128.0,
                              min_keys=256, check_every=256,
                              cooldown_keys=512, load_window_keys=1024))


@pytest.mark.parametrize("mode", ["contention", "snapshot", "fold"])
def test_fault_reshard_failure_backs_off_and_serves(mode):
    """A poisoned §18 migration — swap-window contention from a
    concurrent re-flow, a snapshot that raises mid-freeze, or a
    candidate fold that dies in flight — must leave boundaries and
    serving untouched, count a monotone failure, and double the
    cooldown; after the fault clears, the next episode migrates."""
    nfl, keys, oracle = _reshard_nfl(seed=21)
    idx = nfl.index
    b0 = idx.boundaries.copy()
    span0 = nfl._reshard._cooldown_span
    hot = keys[keys.astype(np.float32) < b0[0]]
    rng = np.random.default_rng(22)
    orc = _Oracle(oracle)
    fe = FrontEnd(nfl, FrontEndConfig(max_batch=32, batch_timeout_s=5e-4,
                                      admission=False, expire_queued=False))
    fe.on_batch_dispatched = orc.hook
    reqs = [ServiceRequest(rid, "point",
                           float(rng.choice(hot if rng.random() < 0.8
                                            else keys)),
                           deadline_s=_SLACK)
            for rid in range(700)]
    with faults.inject(faults.FaultPlan(fail_reshard=mode), nfl=nfl):
        _submit_drain(fe, reqs)
    _assert_terminal_exactly_once(fe, reqs)
    assert fe.counters["completed"] == len(reqs)
    assert orc.check(reqs) == 0, f"{mode}: served wrong results"
    st = nfl.dispatch_stats()["reshard"]
    assert st["migrations_failed"] >= 1, f"{mode}: fault never fired"
    assert st["migrations_completed"] == 0
    assert st["resharding_episodes"] == st["migrations_failed"], \
        f"{mode}: episode/failure accounting drifted (double count?)"
    assert st["cooldown_span"] >= 2 * span0, f"{mode}: no backoff"
    assert st["state"] == "idle"
    assert np.array_equal(idx.boundaries, b0), \
        f"{mode}: a failed migration moved the boundaries"
    assert idx.n_reshards == 0
    assert not any(s._tier_hold for s in idx.shards), \
        f"{mode}: a failed migration left a shard frozen"
    # the failure counters are monotone state: they survive a reset
    again = nfl.dispatch_stats(reset=True)["reshard"]
    assert again["migrations_failed"] == st["migrations_failed"]
    assert again["resharding_episodes"] == st["resharding_episodes"]
    # inject() restored the seams on exit: the fault is gone and an
    # explicit un-faulted episode migrates cleanly
    assert idx._reshard_fault is None
    swapped = []
    assert idx.start_reshard(0, 1, on_swap=lambda: swapped.append(1))
    idx.rebuild()
    assert swapped == [1] and idx.n_reshards == 1
    live = np.array(sorted(orc.d))
    res = nfl.lookup_batch(live)
    exp = np.array([orc.d[k] for k in live.tolist()])
    assert int((res != exp).sum()) == 0


def test_reshard_fault_plan_validates():
    nfl, _, _ = _build_nfl(n=200, seed=23)   # single-shard: no §18
    with pytest.raises(ValueError, match="sharded"):
        with faults.inject(faults.FaultPlan(fail_reshard="fold"), nfl=nfl):
            pass
    nfl2, _, _ = _reshard_nfl(seed=24)
    with pytest.raises(ValueError, match="unknown fail_reshard"):
        with faults.inject(faults.FaultPlan(fail_reshard="typo"), nfl=nfl2):
            pass
    # both rejections rolled the partial install back
    assert nfl2.index._reshard_fault is None
    nfl2.lookup_batch(np.array([1.0]))


def test_retrain_failure_plan_requires_reflow_nfl():
    nfl, _, _ = _build_nfl(n=200, seed=20,
                           drift=DriftConfig(enabled=False))
    with pytest.raises(ValueError):
        with faults.inject(faults.FaultPlan(retrain_failure=True), nfl=nfl):
            pass
    # and the partial install was rolled back
    assert ops.fault_injection_stats()["dispatches_seen"] >= 0
    nfl.lookup_batch(np.array([1.0]))  # no injected faults fire


# ----------------------------------------------- concurrent telemetry reset
def test_dispatch_stats_reset_is_atomic_under_concurrency():
    """Satellite: snapshot-and-reset racing live dispatches must never
    lose counts — the per-window snapshots plus the final residue must
    sum to exactly the number of dispatches issued."""
    nfl, keys, _ = _build_nfl(n=600, seed=21)
    q = keys[:64]
    nfl.lookup_batch(q)  # warm the shape bucket outside the window
    nfl.dispatch_stats(reset=True)

    n_calls = 150
    snapshots = []
    stop = threading.Event()

    def reader():
        while not stop.is_set():
            snapshots.append(
                nfl.dispatch_stats(reset=True)["dispatch"]
                ["dispatch_count"])
            time.sleep(1e-4)

    t = threading.Thread(target=reader)
    t.start()
    try:
        for _ in range(n_calls):
            nfl.lookup_batch(q)
    finally:
        stop.set()
        t.join()
    residue = nfl.dispatch_stats()["dispatch"]["dispatch_count"]
    assert sum(snapshots) + residue == n_calls
