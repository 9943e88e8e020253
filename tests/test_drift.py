"""Drift telemetry, background re-flow, and graceful degradation
(DESIGN.md §14).

Three layers, matching the module split:

- ``DriftMonitor`` unit tests: the decayed reservoir ages out old keys
  at the configured time constant and the check cadence fires on
  observed-key counts, not wall clock.
- ``ReflowManager`` unit tests with stub callbacks: every edge of the
  state machine — accept (flow and identity), margin rejection,
  retrain failure with cooldown backoff, busy-apply retry, and the
  single-apply guarantee — driven deterministically.
- End-to-end ``NFL`` fault injection: a drifting insert storm against a
  dict oracle with re-flow on, off, forced-retrain-failure, and
  worse-candidate modes.  Every mode must serve zero wrong answers and
  never stall; only the healthy mode may swap.
"""

import jax
import numpy as np
import pytest

import repro.core.drift as drift_mod
from repro.core.drift import DriftConfig, DriftMonitor, ReflowManager
from repro.core.flat_afli import FlatAFLI, FlatAFLIConfig
from repro.core.flow import FlowConfig
from repro.core.nfl import NFL, NFLConfig
from repro.core.train_flow import FlowTrainConfig


# ------------------------------------------------------------- DriftMonitor
def test_monitor_fill_then_decay():
    cfg = DriftConfig(sample_size=128, window_keys=256, seed=0)
    mon = DriftMonitor(cfg)
    old = -np.arange(1.0, 500.0)
    mon.seed(old)
    assert mon.keys_observed == 0  # seeding is not insert traffic
    assert (mon.sample() < 0).all() and mon.sample().shape == (128,)
    # per-key slot-replacement probability is 1/window_keys, so after
    # 8 windows of new traffic the old sample survives w.p. ~e^-8
    new = np.arange(1.0, 1.0 + 8 * 256)
    for i in range(0, new.shape[0], 64):
        mon.observe(new[i:i + 64])
    assert mon.keys_observed == new.shape[0]
    s = mon.sample()
    assert (s > 0).mean() > 0.9, "reservoir failed to age out old keys"


def test_monitor_fill_before_decay():
    cfg = DriftConfig(sample_size=16, window_keys=64, seed=1)
    mon = DriftMonitor(cfg)
    mon.observe(np.arange(10.0))
    assert np.array_equal(mon.sample(), np.arange(10.0))
    mon.observe(np.arange(10.0, 20.0))  # fills to 16, rest decays
    assert mon.sample().shape == (16,)
    assert np.isin(mon.sample(), np.arange(20.0)).all()


def test_monitor_check_cadence():
    cfg = DriftConfig(check_every=100, seed=2)
    mon = DriftMonitor(cfg)
    assert not mon.should_check()  # empty reservoir never checks
    mon.observe(np.arange(50.0))
    assert not mon.should_check()
    mon.observe(np.arange(50.0))
    assert mon.should_check()
    assert not mon.should_check()  # cadence, not level-trigger
    mon.observe(np.arange(100.0))
    assert mon.should_check()


# ------------------------------------------------------------ ReflowManager
class _StubTrainer:
    """FlowTrainer-shaped stub: done after ``steps`` calls, optionally
    raising at call ``fail_at``."""

    def __init__(self, steps=3, fail_at=None):
        self.n = 0
        self.steps = steps
        self.fail_at = fail_at

    def step(self):
        if self.fail_at is not None and self.n >= self.fail_at:
            raise RuntimeError("injected trainer fault")
        self.n += 1
        return self.n >= self.steps


def _armed_manager(*, serving_tail=100, evaluate=None, apply=None,
                   trainer=None, **cfg_kw):
    """Manager whose monitor is primed with 64 identical keys (so the
    internal identity tail is exactly 64) and armed to check on the
    next tick."""
    kw = dict(reflow=True, threshold=2.0, min_tail=4, check_every=64,
              sample_size=64, window_keys=256, cooldown_keys=100,
              max_attempts=2, steps_per_tick=1, seed=3)
    kw.update(cfg_kw)
    cfg = DriftConfig(**kw)
    mon = DriftMonitor(cfg)
    mon.observe(np.full(64, 7.0))  # fills reservoir AND arms the check
    calls = {"apply": 0}

    def _apply(cand, use_flow, tail):
        calls["apply"] += 1
        return True if apply is None else apply(cand, use_flow, tail)

    mgr = ReflowManager(
        cfg, mon,
        serving_tail=lambda s: serving_tail,
        train_factory=lambda s, a: trainer or _StubTrainer(steps=1),
        evaluate=evaluate or (lambda t, s: (5, "cand")),
        apply=_apply)
    return mgr, mon, calls


def test_manager_accepts_flow_candidate():
    mgr, mon, calls = _armed_manager(serving_tail=100,
                                     evaluate=lambda t, s: (5, "cand"))
    mgr.tick()  # check -> trigger -> TRAINING
    assert mgr.state == ReflowManager.TRAINING
    assert mgr.triggers == 1 and mgr.last_score == 100.0
    mgr.tick()  # one step -> done -> validate -> accept -> apply
    assert mgr.state == ReflowManager.PENDING
    assert mgr.reflows_started == 1 and calls["apply"] == 1
    mgr.tick()  # fold in flight: apply must NOT be re-invoked
    assert calls["apply"] == 1
    mgr.note_swap()
    assert mgr.state == ReflowManager.IDLE
    assert mgr.reflows_completed == 1 and mgr.identity_switches == 0
    assert mgr.baseline_tail == 5  # score re-anchors on the new transform
    assert mgr.cooldown_until > mon.keys_observed - 1


def test_manager_identity_wins_ties_and_worse_flows():
    # candidate tail 99 vs internal identity tail 64: identity serves
    mgr, _, calls = _armed_manager(serving_tail=100,
                                   evaluate=lambda t, s: (99, "cand"))
    applied = {}
    mgr.apply = lambda c, use_flow, tail: applied.update(
        cand=c, use_flow=use_flow, tail=tail) or True
    mgr.tick()
    mgr.tick()
    assert applied == {"cand": None, "use_flow": False, "tail": 64}
    mgr.note_swap()
    assert mgr.identity_switches == 1 and mgr.baseline_tail == 64


def test_manager_margin_rejection():
    # identity (64) beats the candidate (99) but misses the 10% margin
    # against serving (65): reject, serving untouched, cooldown set
    mgr, mon, calls = _armed_manager(serving_tail=65,
                                     evaluate=lambda t, s: (99, "cand"))
    mgr.tick()
    mgr.tick()
    assert mgr.state == ReflowManager.IDLE
    assert mgr.candidates_rejected == 1 and calls["apply"] == 0
    assert mgr.reflows_started == 0
    assert mgr.cooldown_until == mon.keys_observed + 100


def test_manager_retrain_failure_backoff():
    mgr, mon, _ = _armed_manager(serving_tail=100)
    boom = RuntimeError("injected train fault")

    def _raise(sample, attempt):
        raise boom

    mgr.train_factory = _raise
    mgr.tick()
    assert mgr.retrain_failures == 1 and mgr.state == ReflowManager.IDLE
    assert mgr.cooldown_until == mon.keys_observed + 100
    # second consecutive failure hits max_attempts=2: span doubles
    mon.observe(np.full(128, 7.0))  # past cooldown, re-arms the check
    mgr.tick()
    assert mgr.retrain_failures == 2
    assert mgr.cooldown_until == mon.keys_observed + 200
    # span is capped at 64x the base cooldown
    for _ in range(20):
        mon.observe(np.full(mgr.cooldown_until - mon.keys_observed + 64,
                            7.0))
        mgr.tick()
    assert mgr.cooldown_until - mon.keys_observed <= 64 * 100
    assert mgr.reflows_started == 0  # degradation never touched serving


def test_manager_trainer_fault_mid_training():
    mgr, _, calls = _armed_manager(
        trainer=_StubTrainer(steps=3, fail_at=1))
    mgr.tick()  # -> TRAINING (factory ok)
    assert mgr.state == ReflowManager.TRAINING
    mgr.tick()  # first step ok
    mgr.tick()  # second step raises
    assert mgr.state == ReflowManager.IDLE
    assert mgr.retrain_failures == 1 and calls["apply"] == 0


@pytest.mark.parametrize("err", [
    NotImplementedError("Unimplemented primitive in Pallas TPU lowering: "
                        "optimization_barrier"),
    jax.errors.JaxRuntimeError("INTERNAL: Mosaic failed to compile TPU "
                               "kernel"),
], ids=["lowering", "device-runtime"])
@pytest.mark.parametrize("stage", ["serving_tail", "train_factory", "step",
                                   "evaluate", "apply"])
def test_manager_device_error_propagates(stage, err):
    """A compile or device failure inside a re-flow step is a broken
    build, not a failed episode: it propagates (like a lock-discipline
    violation) instead of being counted and backed off."""
    def _raise(*_a, **_k):
        raise err

    trainer = _StubTrainer(steps=1)
    if stage == "step":
        trainer.step = _raise
    mgr, _, _ = _armed_manager(trainer=trainer)
    if stage != "step":
        setattr(mgr, stage, _raise)
    with pytest.raises(type(err)):
        for _ in range(3):
            mgr.tick()
    assert mgr.retrain_failures == 0


def test_manager_busy_apply_retries():
    busy = {"n": 0}

    def _apply(cand, use_flow, tail):
        busy["n"] += 1
        return busy["n"] > 2  # a regular fold is mid-flight twice

    mgr, _, _ = _armed_manager(apply=_apply)
    mgr.tick()
    mgr.tick()  # validate -> apply refused (1)
    assert mgr.state == ReflowManager.PENDING and mgr.reflows_started == 0
    mgr.tick()  # refused (2)
    mgr.tick()  # started (3)
    assert mgr.reflows_started == 1 and busy["n"] == 3


# ------------------------------------------------------ lock discipline
def test_manager_reentrant_tick_trips():
    """An injected callable driving tick() recursively must raise
    LockDisciplineError — and the error must propagate, not be
    swallowed by the degradation ladder as a 'failed retrain'."""
    mgr_box = {}

    def _apply(cand, use_flow, tail):
        mgr_box["m"].stats()  # reading stats from a callable is legal
        mgr_box["m"].tick()   # re-driving the machine is not
        return True

    mgr, _, _ = _armed_manager(apply=_apply)
    mgr_box["m"] = mgr
    mgr.tick()  # -> TRAINING
    with pytest.raises(drift_mod.LockDisciplineError):
        mgr.tick()  # step -> validate -> apply -> reentrant tick
    # a discipline violation is a programming error, not an episode
    # failure: no cooldown, no failure count, machine still PENDING
    assert mgr.retrain_failures == 0 and mgr.state == ReflowManager.PENDING
    # and the guard resets: the owner's next tick still runs
    mgr.apply = lambda c, f, t: True
    mgr.tick()
    assert mgr.reflows_started == 1


def test_manager_stats_blocked_mid_commit():
    """stats() inside a commit window would read mutually inconsistent
    counters (e.g. reflows_completed advanced, state still PENDING)."""
    mgr, _, _ = _armed_manager()
    with pytest.raises(drift_mod.LockDisciplineError):
        with mgr._commit():
            mgr.stats()
    mgr.stats()  # window closed: reads are legal again
    with pytest.raises(drift_mod.LockDisciplineError):
        with mgr._commit():
            with mgr._commit():  # nesting = transition inside transition
                pass


def test_manager_immediate_swap_not_wedged():
    """apply() may swap synchronously (flat_afli's empty-snapshot
    start_reflow calls on_swap before returning True).  note_swap then
    closes the episode *inside* the apply call; the manager must not
    re-mark the episode in flight afterwards, or every later PENDING
    episode waits forever on a swap that already happened."""
    mgr_box = {}

    def _apply(cand, use_flow, tail):
        mgr_box["m"].note_swap()  # the empty-snapshot immediate swap
        return True

    mgr, mon, _ = _armed_manager(apply=_apply)
    mgr_box["m"] = mgr
    mgr.tick()
    mgr.tick()
    assert mgr.state == ReflowManager.IDLE
    assert mgr.reflows_started == 1 and mgr.reflows_completed == 1
    # second episode end-to-end: past cooldown, re-arm, drive again —
    # before the epoch fix this stayed wedged behind _applied=True
    mon.observe(np.full(mgr.cooldown_until - mon.keys_observed + 64, 7.0))
    mgr.tick()
    assert mgr.state == ReflowManager.TRAINING
    mgr.tick()
    assert mgr.reflows_started == 2 and mgr.reflows_completed == 2
    assert mgr.state == ReflowManager.IDLE


# ----------------------------------------------------------- NFL end-to-end
def _drift_nfl(**drift_kw):
    kw = dict(reflow=True, threshold=1.5, min_tail=2, check_every=512,
              window_keys=2048, cooldown_keys=1024, train_epochs=1,
              steps_per_tick=8, seed=0)
    kw.update(drift_kw)
    return NFL(NFLConfig(
        backend="flat", force_flow=True, flow=FlowConfig(),
        flow_train=FlowTrainConfig(epochs=1),
        flat_index=FlatAFLIConfig(fold_step_keys=1024),
        drift=DriftConfig(**kw)))


def _storm(nfl, oracle, batches, rng, probe_every=1):
    """Insert drifting batches, probing live keys for wrong answers
    after each batch (the mid-re-flow write-storm check)."""
    for step, (k, v) in enumerate(batches):
        nfl.insert_batch(k, v)
        oracle.update(zip(k.tolist(), v.tolist()))
        if step % probe_every == 0:
            live = np.array(sorted(oracle))
            q = rng.choice(live, min(64, live.shape[0]), replace=False)
            res = nfl.lookup_batch(q)
            exp = np.array([oracle[kk] for kk in q.tolist()])
            assert (res == exp).all(), f"wrong answer mid-storm step {step}"


def _drain(nfl, oracle, hi, max_ticks=400):
    """Tiny inserts until any in-flight episode (and its fold) lands."""
    j = 0
    while j < max_ticks:
        st = nfl._reflow
        if (st.state == ReflowManager.IDLE
                and st.reflows_started == st.reflows_completed):
            break
        k = np.asarray([hi * (1.7 + j * 1e-6)])
        v = np.asarray([900_000 + j], dtype=np.int64)
        nfl.insert_batch(k, v)
        oracle[float(k[0])] = int(v[0])
        j += 1
    return j


def _base_and_drift(seed=0, n_base=6000, n_drift=4000, batch=96):
    """Drifted traffic the stale flow maps badly: tight micro-clusters
    at high in-range quantiles.  Each cluster collapses into a few model
    slots under the old transform, and spreading them over ≥1% of the
    occupied slots is what moves the gamma-percentile tail (a single
    mega-conflict slot would not)."""
    rng = np.random.default_rng(seed)
    base = np.unique(rng.lognormal(0, 2, n_base) * 1e6)
    pv = np.arange(base.shape[0], dtype=np.int64)
    hi = float(base.max())
    centers = np.quantile(base, np.linspace(0.80, 0.999, 16))
    drift = np.unique(np.concatenate(
        [c * (1 + rng.uniform(0, 1e-4, n_drift // 16)) for c in centers]))
    drift = drift[~np.isin(drift, base)]
    rng.shuffle(drift)
    batches = [(drift[i:i + batch],
                np.arange(drift[i:i + batch].shape[0], dtype=np.int64)
                + 100_000 + i)
               for i in range(0, drift.shape[0], batch)]
    return rng, base, pv, hi, batches


def _check_all(nfl, oracle):
    qk = np.array(sorted(oracle))
    qv = np.array([oracle[k] for k in qk.tolist()])
    res = nfl.lookup_batch(qk)
    assert int((res != qv).sum()) == 0, "wrong answers after drift storm"


def test_nfl_reflow_off_score_still_visible():
    rng, base, pv, hi, batches = _base_and_drift(seed=1, n_base=4000,
                                                 n_drift=2500)
    nfl = _drift_nfl(reflow=False)
    nfl.bulkload(base, pv)
    oracle = dict(zip(base.tolist(), pv.tolist()))
    _storm(nfl, oracle, batches, rng, probe_every=4)
    d = nfl.dispatch_stats()["drift"]
    assert d["enabled"] and d["checks"] >= 1
    assert d["last_score"] >= 1.5, "drift score failed to surface"
    assert d["triggers"] == 0 and d["reflows_started"] == 0
    _check_all(nfl, oracle)


def test_nfl_reflow_end_to_end_under_write_storm():
    rng, base, pv, hi, batches = _base_and_drift(seed=0)
    nfl = _drift_nfl()
    nfl.bulkload(base, pv)
    oracle = dict(zip(base.tolist(), pv.tolist()))
    _storm(nfl, oracle, batches, rng)
    _drain(nfl, oracle, hi)
    d = nfl.dispatch_stats()["drift"]
    assert d["triggers"] >= 1 and d["reflows_completed"] >= 1
    assert d["reflows_started"] == d["reflows_completed"]
    assert d["state"] == "idle"
    assert d["signals"]["n_reflows"] >= 1
    assert not d["signals"]["reflow_active"]
    # the re-key re-anchored the score on the retrained transform
    assert d["baseline_tail"] >= 1
    # the swap refreshed the AutoSwitch verdict over the re-keyed
    # snapshot (the build-time verdict described the old transform)
    sw = d["signals"]["autoswitch"]
    assert sw["use_flow"] is not None and sw["tail_transformed"] >= 1
    _check_all(nfl, oracle)
    # deletes still route correctly under the new transform
    dels = np.array(sorted(oracle))[::7][:100]
    assert nfl.delete_batch(dels).all()
    assert (nfl.lookup_batch(dels) == -1).all()


def test_nfl_forced_retrain_failure_never_stalls():
    rng, base, pv, hi, batches = _base_and_drift(seed=2, n_base=4000,
                                                 n_drift=2500)
    nfl = _drift_nfl(max_attempts=2, cooldown_keys=512)
    nfl.bulkload(base, pv)

    def _boom(sample, attempt):
        raise RuntimeError("injected retrain fault")

    nfl._reflow.train_factory = _boom
    oracle = dict(zip(base.tolist(), pv.tolist()))
    _storm(nfl, oracle, batches, rng, probe_every=4)
    d = nfl.dispatch_stats()["drift"]
    assert d["triggers"] >= 1 and d["retrain_failures"] >= 1
    assert d["reflows_started"] == 0 and d["state"] == "idle"
    assert d["cooldown_until"] > 0
    assert nfl.use_flow, "failed retrain must leave serving untouched"
    _check_all(nfl, oracle)


def test_nfl_worse_candidate_rejected(monkeypatch):
    rng, base, pv, hi, batches = _base_and_drift(seed=3, n_base=4000,
                                                 n_drift=2500)
    nfl = _drift_nfl(max_attempts=2, cooldown_keys=512)
    nfl.bulkload(base, pv)
    # candidate AND identity both evaluate catastrophically worse than
    # serving: the margin gate must reject and leave serving alone
    nfl._reflow.evaluate = lambda trainer, sample: (10 ** 9, None)
    monkeypatch.setattr(drift_mod, "dataset_tail_conflict",
                        lambda keys, gamma=0.99: 10 ** 9)
    oracle = dict(zip(base.tolist(), pv.tolist()))
    _storm(nfl, oracle, batches, rng, probe_every=4)
    d = nfl.dispatch_stats()["drift"]
    assert d["candidates_rejected"] >= 1
    assert d["reflows_started"] == 0 and d["retrain_failures"] == 0
    assert nfl.use_flow
    _check_all(nfl, oracle)


def test_nfl_flow_to_identity_switch():
    rng = np.random.default_rng(4)
    base = np.unique(rng.lognormal(0, 2, 4000) * 1e6)
    pv = np.arange(base.shape[0], dtype=np.int64)
    nfl = _drift_nfl()
    nfl.bulkload(base, pv)
    assert nfl.use_flow
    # force the retrained flow to lose so the online AutoSwitch must
    # fall back to identity — the drifted traffic is wide uniform, so
    # identity's tail is tiny while the stale flow's tail is huge
    nfl._reflow.evaluate = lambda trainer, sample: (10 ** 9, None)
    hi = float(base.max())
    drift = np.unique(rng.uniform(hi, 5 * hi, 4000))
    oracle = dict(zip(base.tolist(), pv.tolist()))
    batches = [(drift[i:i + 96],
                np.arange(drift[i:i + 96].shape[0], dtype=np.int64)
                + 100_000 + i)
               for i in range(0, drift.shape[0], 96)]
    _storm(nfl, oracle, batches, rng, probe_every=4)
    _drain(nfl, oracle, 4 * hi)
    d = nfl.dispatch_stats()["drift"]
    assert d["identity_switches"] >= 1, "identity never won the switch"
    assert not nfl.use_flow
    _check_all(nfl, oracle)


def test_nfl_sharded_reflow_end_to_end():
    rng, base, pv, hi, batches = _base_and_drift(seed=5, n_base=5000,
                                                 n_drift=3000)
    nfl = NFL(NFLConfig(
        backend="flat", shards=2, force_flow=True, flow=FlowConfig(),
        flow_train=FlowTrainConfig(epochs=1),
        flat_index=FlatAFLIConfig(fold_step_keys=1024),
        drift=DriftConfig(reflow=True, threshold=1.5, min_tail=2,
                          check_every=512, window_keys=2048,
                          cooldown_keys=1024, train_epochs=1,
                          steps_per_tick=8)))
    nfl.bulkload(base, pv)
    b_before = np.asarray(nfl.index.boundaries).copy()
    oracle = dict(zip(base.tolist(), pv.tolist()))
    _storm(nfl, oracle, batches, rng, probe_every=2)
    _drain(nfl, oracle, hi)
    d = nfl.dispatch_stats()["drift"]
    assert d["reflows_completed"] >= 1
    st = nfl.index.stats()
    assert st["n_reflows"] >= 1 and not st["reflow_active"]
    b_after = np.asarray(nfl.index.boundaries)
    assert b_after.shape == b_before.shape
    assert not np.array_equal(b_after, b_before), \
        "router boundaries were not re-derived at the swap"
    _check_all(nfl, oracle)
    # per-shard drift signals remain attributable after the swap, and
    # the fold-built candidates carry a fresh AutoSwitch verdict (a
    # re-flow candidate never runs build(), where the verdict normally
    # lands)
    sig = d["signals"]
    assert len(sig["shards"]) == 2 and len(sig["autoswitch"]) == 2
    for sw in sig["autoswitch"]:
        assert sw["use_flow"] is not None
        assert sw["tail_original"] >= 1 and sw["tail_transformed"] >= 1


# ----------------------------------------------- flat-index re-key (no NFL)
def test_flat_start_reflow_refused_while_active():
    rng = np.random.default_rng(6)
    keys = np.unique(rng.lognormal(0, 2, 3000) * 1e6)
    idx = FlatAFLI(FlatAFLIConfig(fold_step_keys=256))
    idx.build(keys.astype(np.float64), np.arange(keys.shape[0]))
    assert idx.start_reflow(np.log1p, None, lambda: None)
    assert idx._fold is not None and idx._fold.reflow is not None
    # a second re-key (or any competing fold) must be refused
    assert not idx.start_reflow(np.log1p, None, lambda: None)
    # drive to completion with write traffic; answers stay right
    oracle = dict(zip(keys.tolist(), range(keys.shape[0])))
    fresh = 10 ** 6
    i = 0
    while idx._fold is not None and i < 200:
        k = np.unique(rng.lognormal(0, 2, 40) * 1e6)
        k = k[~np.isin(k, sorted(oracle))]
        idx.insert_batch(k, np.arange(fresh, fresh + k.shape[0]))
        oracle.update(zip(k.tolist(), range(fresh, fresh + k.shape[0])))
        fresh += k.shape[0]
        i += 1
    assert idx.n_reflows == 1
    live = np.array(sorted(oracle))
    got = idx.lookup_batch(np.log1p(live).astype(np.float32),
                           ikeys=live)
    exp = np.array([oracle[k] for k in live.tolist()])
    assert (got == exp).all()


# ------------------------------------------------- resettable counters (§11)
def test_dispatch_stats_reset():
    rng = np.random.default_rng(7)
    keys = np.unique(rng.uniform(0, 1e6, 3000))
    pv = np.arange(keys.shape[0], dtype=np.int64)
    nfl = NFL(NFLConfig(backend="flat", force_flow=False,
                        flow_train=FlowTrainConfig(epochs=1)))
    nfl.bulkload(keys, pv)
    nfl.lookup_batch(keys[:256])
    nfl.scan_batch([keys[0]], [keys[100]])
    ds1 = nfl.dispatch_stats(reset=True)
    assert ds1["dispatch"]["dispatch_count"] >= 1
    assert ds1["dispatch"]["scan_dispatch_count"] >= 1
    assert ds1["serving"]["tree_packs"] >= 1
    ds2 = nfl.dispatch_stats()
    # counters zeroed by the reset...
    assert ds2["dispatch"]["dispatch_count"] == 0
    assert ds2["dispatch"]["scan_dispatch_count"] == 0
    assert ds2["serving"]["tree_packs"] == 0
    assert ds2["serving"]["tier_uploads"] == 0
    # ...gauges and ratchets survive (they describe resident state)
    for g in ("run_capacity", "delta_capacity", "scan_capacity",
              "static_max_depth", "static_dense_window", "run_window"):
        assert ds2["serving"][g] == ds1["serving"][g]
    # drift episode counters are state, not per-phase counts
    assert ds2["drift"]["checks"] == ds1["drift"]["checks"]
    # counting resumes from zero
    nfl.lookup_batch(keys[:64])
    assert nfl.dispatch_stats()["dispatch"]["dispatch_count"] == 1


def test_sharded_dispatch_stats_reset():
    rng = np.random.default_rng(8)
    keys = np.unique(rng.uniform(0, 1e6, 3000))
    pv = np.arange(keys.shape[0], dtype=np.int64)
    nfl = NFL(NFLConfig(backend="flat", shards=2, force_flow=False,
                        flow_train=FlowTrainConfig(epochs=1)))
    nfl.bulkload(keys, pv)
    nfl.lookup_batch(keys[:256])
    ds1 = nfl.dispatch_stats(reset=True)
    assert ds1["router"]["point_queries"] == 256
    ds2 = nfl.dispatch_stats()
    assert ds2["router"]["point_queries"] == 0
    assert ds2["router"]["per_shard_points"] == [0, 0]
    assert ds2["serving"]["tree_packs"] == 0
    for g in ("run_capacity", "static_max_depth"):
        assert ds2["serving"][g] == ds1["serving"][g]
