"""NFL — the two-stage Normalizing-Flow Learned index framework (paper §3).

Stage 1: Numerical NF transforms bulk-loaded keys toward a near-uniform
distribution (offline training on a 10% sample; online batched inference).
A switching mechanism keeps the flow only if it lowers the tail conflict
degree (paper §3.2.2).

Stage 2: AFLI indexes the (possibly transformed) keys.

All request processing is batched, as in the paper (§3.1: "our NFL also
processes requests in batches").

Two serving backends (DESIGN.md §9):

* ``backend="afli"`` — the paper-faithful pointer tree, probed key by key
  on the host.  Full read/write API (insert/update/delete).
* ``backend="flat"`` — FlatAFLI served through the fused single-dispatch
  Pallas kernel: one ``pallas_call`` per request batch runs the NF forward,
  the whole multi-level traversal, AND the write-tier probe (DESIGN.md
  §9/§10).  Bulk-load positioning keys come from the *kernel* NF path so
  build-time and serve-time placement is bit-identical.  Reads +
  log-structured tiered inserts with last-write-wins identity semantics
  (so update == insert of an existing key), tombstone deletes, and fused
  tier-merged range scans (``scan_batch`` / ``lookup_range``, DESIGN.md
  §12) — a batch of [lo, hi) ranges is one ``pallas_call`` end to end.
* ``backend="flat", shards=P`` — the flat pipeline partitioned across P
  devices at flow-CDF boundaries (DESIGN.md §13): a jit-fused router
  bins each batch, the per-shard fused kernels fan out concurrently,
  and results gather back to input order; every shard runs its own
  write tiers and incremental folds.
"""

from __future__ import annotations

import dataclasses
import threading
import time
from typing import Dict, Optional

import numpy as np

from repro.core.afli import AFLI, AFLIConfig
from repro.core.conflict import dataset_tail_conflict, should_use_flow
from repro.core.drift import (
    DriftConfig,
    DriftMonitor,
    ExclusionLock,
    ReflowManager,
    ReshardConfig,
    ReshardManager,
)
from repro.core.feature import expand_features
from repro.core.flat_afli import FlatAFLI, FlatAFLIConfig
from repro.core.flow import FlowConfig, transform_keys
from repro.core.train_flow import FlowTrainConfig, FlowTrainer, train_flow
from repro.obs import span

__all__ = ["NFL", "NFLConfig"]


@dataclasses.dataclass(frozen=True)
class NFLConfig:
    flow: FlowConfig = dataclasses.field(default_factory=FlowConfig)
    flow_train: FlowTrainConfig = dataclasses.field(default_factory=FlowTrainConfig)
    index: AFLIConfig = dataclasses.field(default_factory=AFLIConfig)
    flat_index: FlatAFLIConfig = dataclasses.field(default_factory=FlatAFLIConfig)
    gamma: float = 0.99
    force_flow: Optional[bool] = None  # None -> paper's switching mechanism
    backend: str = "afli"              # "afli" (paper tree) | "flat" (fused)
    shards: int = 1                    # flat backend: key-space shards, one
                                       # device each (DESIGN.md §13)
    drift: DriftConfig = dataclasses.field(default_factory=DriftConfig)
                                       # drift telemetry + background
                                       # re-flow (flat backend, §14)
    reshard: ReshardConfig = dataclasses.field(
        default_factory=ReshardConfig)
                                       # hot-shard load telemetry +
                                       # boundary migration (sharded
                                       # flat backend, §18)


class NFL:
    """Two-stage learned index: Numerical NF + AFLI."""

    def __init__(self, config: NFLConfig | None = None):
        self.cfg = config or NFLConfig()
        if self.cfg.backend == "flat":
            if self.cfg.shards > 1:
                from repro.core.sharded_nfl import ShardedFlatAFLI

                self.index = ShardedFlatAFLI(self.cfg.flat_index,
                                             n_shards=self.cfg.shards)
            else:
                self.index = FlatAFLI(self.cfg.flat_index)
        elif self.cfg.backend == "afli":
            self.index = AFLI(self.cfg.index)
        else:
            raise ValueError(f"unknown NFL backend: {self.cfg.backend!r}")
        self.flow_params = None
        self.normalizer = None
        self.use_flow = False
        self.metrics: Dict[str, float] = {}
        self._packed_w = None   # pack_flow_weights block (flat backend)
        self._shapes = ()
        # drift telemetry + background re-flow (DESIGN.md §14)
        if self.cfg.drift.reflow and self.cfg.backend != "flat":
            raise ValueError("drift.reflow requires backend='flat' (the "
                             "re-key rides the incremental-fold machinery)")
        if self.cfg.reshard.enabled and (self.cfg.backend != "flat"
                                         or self.cfg.shards < 2):
            raise ValueError("reshard.enabled requires backend='flat' "
                             "with shards > 1 (boundary migration moves "
                             "the sharded router's boundaries)")
        self._drift: Optional[DriftMonitor] = None
        self._reflow: Optional[ReflowManager] = None
        self._reshard: Optional[ReshardManager] = None
        # serializes the drift/re-flow tick on the write path against
        # ``dispatch_stats(reset=True)`` snapshots from another thread
        # (the §16 front-end loop): an unlocked reset racing a tick
        # could zero counters mid-transition and lose counts.  RLock —
        # the tick's injected callables may themselves read stats.
        self._telemetry_lock = threading.RLock()
        # one structural-exclusion token shared by BOTH managers (§18):
        # a re-flow re-derives every boundary, a migration moves a
        # window of them — they must never interleave
        self._exclusion = ExclusionLock()
        if self.cfg.backend == "flat" and self.cfg.drift.enabled:
            self._drift = DriftMonitor(self.cfg.drift)
            self._reflow = ReflowManager(
                self.cfg.drift, self._drift,
                serving_tail=self._drift_serving_tail,
                train_factory=self._drift_train_factory,
                evaluate=self._drift_evaluate,
                apply=self._drift_apply,
                exclusion=self._exclusion)
        if self.cfg.reshard.enabled:
            self.index.load_window_keys = int(
                self.cfg.reshard.load_window_keys)
            self._reshard = ReshardManager(
                self.cfg.reshard,
                load_snapshot=self.index.load_snapshot,
                start_migration=self._reshard_apply,
                exclusion=self._exclusion)

    # ------------------------------------------------------------ bulkload
    def bulkload(self, keys: np.ndarray, payloads: np.ndarray) -> None:
        keys = np.asarray(keys, dtype=np.float64)
        payloads = np.asarray(payloads, dtype=np.int64)
        t0 = time.perf_counter()
        params, normalizer, train_metrics = train_flow(
            keys, self.cfg.flow, self.cfg.flow_train
        )
        t_train = time.perf_counter() - t0

        t0 = time.perf_counter()
        transformed = self._transform(params, normalizer, keys)
        t_transform = time.perf_counter() - t0

        if self.cfg.force_flow is None:
            use, tail_orig, tail_flow = should_use_flow(keys, transformed, self.cfg.gamma)
        else:
            use = self.cfg.force_flow
            _, tail_orig, tail_flow = should_use_flow(keys, transformed, self.cfg.gamma)
        self.use_flow = bool(use)
        self.flow_params = params
        self.normalizer = normalizer
        if self.cfg.backend == "flat":
            self._packed_w, self._shapes = self._pack_weights(params)

        t0 = time.perf_counter()
        n_shadow = 0
        if self.cfg.backend == "flat":
            if self.use_flow:
                self.index.build(transformed, payloads, ikeys=keys)
                # register the serve-path flow so every future fold can
                # re-verify placement through the in-kernel NF (§8/§10)
                self.index.set_serve_flow(normalizer, self.cfg.flow,
                                          self._packed_w, self._shapes)
                # verify the *serve* path (in-kernel NF) end to end; any
                # divergent key is shadowed into the run tier (§8/§9)
                feats = expand_features(keys, normalizer, self.cfg.flow.dim,
                                        self.cfg.flow.theta, dtype=np.float32)
                n_shadow = self.index.verify_serve_flow(
                    feats, keys, self._packed_w, self._shapes, payloads)
            else:
                self.index.build(keys, payloads)
        elif self.use_flow:
            self.index.bulkload(transformed, payloads, ikeys=keys)
        else:
            self.index.bulkload(keys, payloads)
        t_build = time.perf_counter() - t0

        if self._drift is not None:
            # prime the reservoir with the build distribution and anchor
            # the drift score at the accepted transform's tail (§14)
            self._drift.seed(keys)
            self._reflow.set_baseline(tail_flow if self.use_flow
                                      else tail_orig)

        self.metrics = {
            **{f"flow_{k}": v for k, v in train_metrics.items()},
            "flow_train_s": t_train,
            "transform_s": t_transform,
            "index_build_s": t_build,
            "tail_conflict_original": float(tail_orig),
            "tail_conflict_transformed": float(tail_flow),
            "use_flow": float(self.use_flow),
            "serve_verify_shadowed": float(n_shadow),
        }

    # ------------------------------------------------------------- helpers
    def _transform(self, params, normalizer, keys: np.ndarray) -> np.ndarray:
        """Bulk key transformation on the backend's canonical path.

        The flat backend positions by the *kernel* NF output so serve-time
        in-kernel placement arithmetic is bit-identical to the build."""
        if self.cfg.backend == "flat":
            from repro.kernels.ops import nf_transform_keys

            return nf_transform_keys(params, normalizer, keys, self.cfg.flow)
        return transform_keys(params, normalizer, keys, self.cfg.flow)

    @staticmethod
    def _pack_weights_for(params, flow_cfg: FlowConfig):
        """The flow's pack_flow_weights block (fused-kernel serve input)."""
        import jax.numpy as jnp

        from repro.core.flow import materialize_weights
        from repro.kernels.nf_forward import pack_flow_weights

        weights = materialize_weights(params, flow_cfg)
        out_scale = jnp.exp(params["out_log_scale"])
        feat_mu = params.get("feat_mu", jnp.zeros((flow_cfg.dim,), jnp.float32))
        feat_sd = params.get("feat_sd", jnp.ones((flow_cfg.dim,), jnp.float32))
        return pack_flow_weights(weights, out_scale, feat_mu, feat_sd)

    def _pack_weights(self, params):
        return self._pack_weights_for(params, self.cfg.flow)

    # ----------------------------------------------- drift callbacks (§14)
    def _drift_serving_tail(self, sample: np.ndarray) -> int:
        """Tail conflict degree of the reservoir sample under the
        transform that is CURRENTLY serving — the drift monitor's
        measured quantity.  Rides the host flow path (not the serving
        kernels), so measuring drift never touches the serve-path jit
        caches or counters."""
        sample = np.asarray(sample, dtype=np.float64)
        if self.use_flow:
            z = np.asarray(transform_keys(self.flow_params, self.normalizer,
                                          sample, self.cfg.flow), np.float64)
            if not np.all(np.isfinite(z)):
                raise ValueError("serving flow produced non-finite z on "
                                 "the drift sample")
            return dataset_tail_conflict(z, self.cfg.drift.gamma)
        return dataset_tail_conflict(sample, self.cfg.drift.gamma)

    def _drift_train_factory(self, sample: np.ndarray, attempt: int):
        """Incremental retrainer over the (small) reservoir sample; the
        attempt index perturbs the seed so a failed episode does not
        deterministically repeat itself."""
        d = self.cfg.drift
        tcfg = FlowTrainConfig(
            sample_frac=1.0,
            epochs=max(int(d.train_epochs), 1),
            batch_size=max(min(int(d.train_batch), len(sample)), 1),
            lr=self.cfg.flow_train.lr,
            seed=int(d.seed) + int(attempt),
            feature_standardize=self.cfg.flow_train.feature_standardize)
        return FlowTrainer(np.asarray(sample, np.float64),
                           self.cfg.flow, tcfg)

    def _drift_evaluate(self, trainer, sample: np.ndarray):
        """Finish the retrained flow into a candidate and measure its
        tail on the drift sample.  Raises on non-finite z — an unusable
        candidate is a failed episode, never a served transform."""
        params, normalizer, _metrics = trainer.result()
        z = np.asarray(transform_keys(params, normalizer,
                                      np.asarray(sample, np.float64),
                                      self.cfg.flow), np.float64)
        if not np.all(np.isfinite(z)):
            raise ValueError("candidate flow produced non-finite z")
        return (dataset_tail_conflict(z, self.cfg.drift.gamma),
                (params, normalizer))

    def _drift_apply(self, candidate, use_flow: bool,
                     accepted_tail: int) -> bool:
        """Start the atomic re-key under the accepted candidate (flow or
        identity).  The index's ``start_reflow`` owns atomicity; the
        ``on_swap`` callback installs the NFL-level flow state at the
        same instant the structure adopts the new positioning keys, then
        closes the manager's episode."""
        if use_flow:
            params, normalizer = candidate
            packed_w, shapes = self._pack_weights(params)
            flow_cfg = self.cfg.flow

            def transform_fn(k64):
                from repro.kernels.ops import nf_transform_keys

                return nf_transform_keys(params, normalizer, k64, flow_cfg)

            serve_ctx = (normalizer, flow_cfg, packed_w, shapes)

            def on_swap():
                self.use_flow = True
                self.flow_params = params
                self.normalizer = normalizer
                self._packed_w, self._shapes = packed_w, shapes
                self._reflow.note_swap()
        else:  # flow -> identity: position by the raw keys again
            def transform_fn(k64):
                return np.asarray(k64, np.float64)

            serve_ctx = None

            def on_swap():
                self.use_flow = False
                self._reflow.note_swap()

        return self.index.start_reflow(transform_fn, serve_ctx, on_swap)

    # -------------------------------------------- reshard callbacks (§18)
    def _reshard_apply(self, lo: int, hi: int) -> bool:
        """Start the localized boundary migration of shard window
        ``[lo, hi]``.  The sharded index owns atomicity and rollback;
        the manager's ``note_swap`` / ``note_failure`` close the episode
        from the index's swap/abort callbacks."""
        return self.index.start_reshard(
            lo, hi, on_swap=self._reshard.note_swap,
            on_abort=self._reshard.note_failure)

    def _reshard_note(self, n_keys: int) -> None:
        """Feed routed traffic to the reshard manager (reads AND writes
        — read skew is the §18 trigger) and give it one bounded control
        tick, under the same telemetry lock the §14 tick uses."""
        if self._reshard is None:
            return
        with self._telemetry_lock:
            self._reshard.observe(int(n_keys))
            self._reshard.tick()

    def _features(self, keys: np.ndarray) -> np.ndarray:
        """Host feature expansion of serve-path query keys, the fused
        NF's input."""
        with span("nfl.features"):
            return expand_features(keys, self.normalizer, self.cfg.flow.dim,
                                   self.cfg.flow.theta, dtype=np.float32)

    def _pkeys(self, keys: np.ndarray) -> np.ndarray:
        """Positioning keys for a batch of query keys (online NF inference)."""
        keys = np.asarray(keys, dtype=np.float64)
        if not self.use_flow:
            return keys
        return self._transform(self.flow_params, self.normalizer, keys)

    # ------------------------------------------------------------ batch ops
    def lookup_batch(self, keys: np.ndarray) -> np.ndarray:
        """Batched point lookups; -1 marks not-found."""
        with span("nfl.lookup"):
            keys = np.asarray(keys, dtype=np.float64)
            if self.cfg.backend == "flat":
                if not self.use_flow:
                    res = self.index.lookup_batch(keys)
                    self._reshard_note(keys.shape[0])
                    return res
                # fused single dispatch: NF forward + traversal in one kernel
                res = self.index.lookup_batch_flow(
                    self._features(keys), keys, self._packed_w, self._shapes)
                self._reshard_note(keys.shape[0])
                return res
            pkeys = self._pkeys(keys)
            out = np.empty(keys.shape[0], dtype=np.int64)
            lookup = self.index.lookup
            for i in range(keys.shape[0]):
                r = lookup(float(pkeys[i]), float(keys[i]))
                out[i] = -1 if r is None else r
            return out

    def lookup_batch_async(self, keys: np.ndarray):
        """Dispatch a batched point lookup without blocking; returns a
        zero-arg finisher producing the payload array.

        On the flat backend (single or sharded) the kernel inputs are
        snapshot at dispatch time, so the §16 front-end can keep a
        second batch in flight behind the first (double-buffered
        dispatch) and still read results consistent with the index
        state each batch was dispatched into.  The AFLI backend has no
        device path — the lookup runs eagerly and the finisher just
        hands the result back."""
        with span("nfl.lookup"):
            keys = np.asarray(keys, dtype=np.float64)
            if self.cfg.backend == "flat":
                if not self.use_flow:
                    finish = self.index.lookup_batch_async(keys)
                else:
                    finish = self.index.lookup_batch_flow_async(
                        self._features(keys), keys, self._packed_w,
                        self._shapes)
                # kernels are already in flight: the reshard control tick
                # overlaps the device work it is charged to
                self._reshard_note(keys.shape[0])
                return finish
            res = self.lookup_batch(keys)
            return lambda: res

    def insert_batch(self, keys: np.ndarray, payloads: np.ndarray) -> None:
        with span("nfl.insert"):
            keys = np.asarray(keys, dtype=np.float64)
            payloads = np.asarray(payloads, dtype=np.int64)
            pkeys = self._pkeys(keys)
            if self.cfg.backend == "flat":
                self.index.insert_batch(
                    pkeys, payloads, ikeys=keys if self.use_flow else None)
                if self._drift is not None:
                    with self._telemetry_lock:
                        self._drift.observe(keys)
                        self._reflow.tick()
                self._reshard_note(keys.shape[0])
                return
            insert = self.index.insert
            for i in range(keys.shape[0]):
                insert(float(pkeys[i]), int(payloads[i]), float(keys[i]))

    def update_batch(self, keys: np.ndarray, payloads: np.ndarray) -> np.ndarray:
        keys = np.asarray(keys, dtype=np.float64)
        if self.cfg.backend == "flat":
            # tiered write path is last-write-wins by identity (§10), so
            # updating an existing key IS an insert; absent keys are
            # refused (update must not create them)
            ok = self.index.contains_batch(keys)
            if ok.any():
                self.insert_batch(keys[ok], np.asarray(payloads)[ok])
            return ok
        pkeys = self._pkeys(keys)
        ok = np.zeros(keys.shape[0], dtype=bool)
        for i in range(keys.shape[0]):
            ok[i] = self.index.update(float(pkeys[i]), int(payloads[i]), float(keys[i]))
        return ok

    def delete_batch(self, keys: np.ndarray) -> np.ndarray:
        """Batched deletes; per-key success (False = key absent).

        Flat backend: tombstone appends to the active delta (DESIGN.md
        §12) — deleted keys vanish from point AND range results
        immediately and are physically dropped by the next fold.  AFLI
        backend: the paper tree's per-key delete, with the pkey
        transform batched up front and a tightened loop body."""
        keys = np.asarray(keys, dtype=np.float64)
        pkeys = self._pkeys(keys)
        if self.cfg.backend == "flat":
            res = self.index.delete_batch(
                pkeys, ikeys=keys if self.use_flow else None)
            self._reshard_note(keys.shape[0])
            return res
        delete = self.index.delete
        return np.fromiter(
            (delete(p, k) for p, k in zip(pkeys.tolist(), keys.tolist())),
            dtype=bool, count=keys.shape[0])

    # -------------------------------------------------------- range scans
    def scan_batch(self, lo_keys: np.ndarray, hi_keys: np.ndarray,
                   cap: int | None = None):
        """Batched ``[lo, hi)`` range scans (flat backend, DESIGN.md §12).

        Returns ``(payloads i32[n, cap] (-1 padded), counts i32[n],
        totals i32[n])``: per query the first ``counts[i]`` lanes hold
        the live payloads in range, in positioning-key order;
        ``totals[i] > cap`` flags truncation.  Range semantics follow
        the index's positioning order: the key order itself when the
        flow is off, the NF-transformed order when it is on (both
        endpoints ride the same transform as every stored key)."""
        if self.cfg.backend != "flat":
            raise NotImplementedError(
                "range scans are served by the flat backend's fused "
                "range-scan kernel; use backend='flat'")
        with span("nfl.scan"):
            lo_keys = np.asarray(lo_keys, dtype=np.float64)
            hi_keys = np.asarray(hi_keys, dtype=np.float64)
            if not self.use_flow:
                res = self.index.scan_batch(lo_keys, hi_keys, cap=cap)
            else:
                res = self.index.scan_batch_flow(
                    self._features(lo_keys), self._features(hi_keys),
                    self._packed_w, self._shapes, cap=cap)
            self._reshard_note(lo_keys.shape[0])
            return res

    # established range-query spelling alongside the batched name
    lookup_range = scan_batch

    # ---------------------------------------------------------------- misc
    def stats(self):
        return self.index.stats()

    def dispatch_stats(self, reset: bool = False):
        """Serving-path telemetry for benchmarks and ops dashboards
        (DESIGN.md §11/§12/§13): the fused-dispatch counters (fallbacks,
        tier routing, ``retrace_count``) and the range-scan counters
        (scan dispatches, oracle fallbacks, ``scan_cap`` truncations)
        plus, on the flat backend, the persistent serving-state counters
        (pack reuse, tier prefix uploads, full repacks) and the host
        tier-probe / host-scan fallback counts.  With ``shards > 1`` the
        serving block is the cross-shard aggregate, and ``shards`` /
        ``router`` break out the per-shard counters and the fan-out
        accounting.  ``out["drift"]`` (flat backend) carries the §14
        drift score, re-flow state-machine counters, and the structural
        drift signals (per shard with ``shards > 1``).

        ``reset=True`` zeroes the dispatch and serving *counters* after
        snapshotting (gauges, ratchets, and the drift episode counters
        are state and survive), so multi-phase benches and drift windows
        read per-phase counts."""
        from repro.kernels.ops import fused_lookup_stats

        with self._telemetry_lock:
            out = {"dispatch": fused_lookup_stats(reset=reset)}
            if self.cfg.backend == "flat":
                out.update(self.index.serving_telemetry())
                if self._reflow is not None:
                    out["drift"] = {"enabled": True,
                                    "use_flow": self.use_flow,
                                    **self._reflow.stats(),
                                    "signals": self.index.drift_signals()}
                else:
                    out["drift"] = {"enabled": False}
                if self._reshard is not None:
                    # episode counters are monotone state and survive
                    # reset, exactly like the §14 drift counters; the
                    # per-shard load gauges ride in out["shards"] (and
                    # here) and survive too
                    out["reshard"] = {"enabled": True,
                                      **self._reshard.stats(),
                                      "load": self.index.load_snapshot()}
                else:
                    out["reshard"] = {"enabled": False}
                if reset:
                    self.index.reset_telemetry()
        return out
