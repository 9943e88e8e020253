"""Sharded key-space serving: P FlatAFLI shards, one device each
(DESIGN.md §13).

A single ``FlatAFLI`` caps serving throughput at one chip no matter how
fast the fused kernels get.  ``ShardedFlatAFLI`` splits the *positioning
key domain* (z-space when the flow is on) into P contiguous shards at
boundaries drawn from the trained flow's CDF (``kernels/shard_dispatch
.choose_boundaries`` — equal-mass quantiles of the build snapshot, so
shards are balanced in z-space regardless of raw-key skew), builds one
complete ``FlatAFLI`` + ``ServingState`` per shard, and places each
shard's device pools on its own device via the ``repro.dist.sharding``
mesh utilities (``shard_mesh``).

Serving a mixed batch is a three-step dataflow:

1. **route** — one jit-fused dispatch bins the batch by boundary
   lower-bound (``route`` / ``route_flow``; with the flow on, the NF
   forward and the binning fuse into a single compiled call).  The
   routed z rides the SAME ``nf_forward_pallas`` path that positioned
   every build and insert, so routing, placement, and probing all agree
   bit-for-bit — the sharded route has no in-kernel NF
   re-materialization hazard and therefore needs no flow shadows (§8
   applies per shard, through each shard's own build verification);
2. **fan out** — the existing fused lookup / tier-probe / range-scan
   kernels run per shard on that shard's local pools.  Point lookups
   dispatch through ``FlatAFLI.lookup_batch_async`` for every shard
   *before* finishing any, so kernels on distinct devices execute
   concurrently (JAX async dispatch) and the gather pays one transfer
   per shard;
3. **gather** — results scatter back to input order through the inverse
   of the stable shard-major binning permutation.  Range queries that
   straddle a boundary split into one sub-range per touched shard
   (``split_ranges``) and merge on the way back: sub-results concatenate
   in shard order, which IS global positioning-key order because the
   sub-ranges tile the query interval and each shard's pools hold only
   in-domain keys.

Writes route identically: each shard runs its own active delta,
compacted run, and incremental fold, so a fold on one (busy) shard never
stalls serving on the others — fold work is charged to the inserts that
route to that shard, and the §11 zero-repack guarantees hold per shard.

``NFL(backend="flat", shards=P)`` builds one of these transparently;
``benchmarks.common.ShardedNFLAdapter`` exposes it to the harness.
"""

from __future__ import annotations

import contextlib
from typing import List, Optional

import jax
import numpy as np

from repro.core.drift import DEVICE_ERRORS
from repro.core.flat_afli import (
    TOMBSTONE,
    FlatAFLI,
    FlatAFLIConfig,
    _IncrementalFold,
    _ids64,
    split_key_bits,
)
from repro.dist.sharding import shard_mesh
from repro.kernels.shard_dispatch import (
    choose_boundaries,
    fanout_plan,
    refresh_boundaries,
    route,
    route_flow,
    split_ranges,
)

__all__ = ["ShardedFlatAFLI"]


def _seed_candidate(parent: "ShardedFlatAFLI", cand: FlatAFLI, slot: int,
                    spk: np.ndarray, shi: np.ndarray, slo: np.ndarray,
                    spv: np.ndarray) -> _IncrementalFold:
    """Configure a fresh candidate ``FlatAFLI`` for device ``slot`` and
    start its incremental fold (shared by the §14 cross-shard re-key and
    the §18 boundary migration).  The candidate's bucket tail mirrors
    ``FlatAFLI.build``'s conflict fit over ITS OWN sub-distribution, and
    the per-shard AutoSwitch verdict lands here because a fold-built
    candidate never runs ``build()`` — which is where the verdict
    normally lands."""
    from repro.core.conflict import (
        conflict_degrees, fit_linear_model, should_use_flow,
        tail_conflict_degree,
    )

    model = fit_linear_model(spk.astype(np.float64))
    if spk.shape[0] >= 2 and model.slope > 0:
        d = tail_conflict_degree(
            conflict_degrees(spk.astype(np.float64), model),
            parent.cfg.gamma)
    else:
        d = parent.cfg.max_bucket
    cand.d_tail = int(np.clip(d, parent.cfg.min_bucket,
                              parent.cfg.max_bucket))
    sik64 = _ids64(shi, slo).view(np.float64)
    use, t_orig, t_new = should_use_flow(sik64, spk, parent.cfg.gamma)
    cand.autoswitch = {"use_flow": bool(use),
                       "tail_original": int(t_orig),
                       "tail_transformed": int(t_new)}
    with parent._on(slot):
        return _IncrementalFold(cand, spk, shi, slo,
                                spv.astype(np.int64))


class _ShardedReflow:
    """Cross-shard atomic re-key (DESIGN.md §14, sharded form).

    A per-shard ``start_reflow`` would re-key each shard's keys in
    place — but under a new transform the keys' z values move across
    the OLD shard boundaries, so per-shard re-keys and the router would
    permanently disagree.  Instead the re-key is coordinated globally:

    1. **freeze** — snapshot every shard's live keyset
       (``_snapshot_live``: tree + tiers, tombstones dropped) and put
       the old shards on ``_tier_hold`` — their deltas keep absorbing
       writes, but no local fold may consume entries this snapshot
       already owns (double-apply at swap);
    2. **re-partition** — transform all identities under the candidate,
       re-derive the boundaries from the NEW flow's CDF
       (``choose_boundaries`` over the re-keyed snapshot), and route
       every key to its new shard;
    3. **rebuild incrementally** — each non-empty shard gets a fresh
       candidate ``FlatAFLI`` built by a standard ``_IncrementalFold``
       on its own device, advanced by the bounded per-write budget
       (serving continues against the OLD shards + boundaries
       throughout);
    4. **swap atomically** — when every candidate fold has verified and
       swapped internally, the held deltas are re-keyed and routed by
       the NEW boundaries into the candidates, then shards, boundaries,
       and serve-flow context flip in one assignment block: route and
       pools can never disagree, because no query observes new
       boundaries with old pools or vice versa.
    """

    def __init__(self, parent: "ShardedFlatAFLI", transform_fn,
                 serve_flow, on_swap):
        self.parent = parent
        self.transform_fn = transform_fn
        self.serve_flow = serve_flow
        self.on_swap = on_swap
        P = parent.n_shards
        # 1. freeze: complete live keyset, one pass per shard
        his, los, pvs = [], [], []
        for s, idx in enumerate(parent.shards):
            _pk, hi, lo, pv = idx._snapshot_live()
            his.append(hi)
            los.append(lo)
            pvs.append(pv)
            # the local fold (if any) duplicated part of this snapshot;
            # the candidate structure supersedes it — kill it, and hold
            # the tiers so post-snapshot writes stay in the delta until
            # the swap re-keys them
            idx._fold = None
            idx._tier_hold = True
        hi = np.concatenate(his) if his else np.empty(0, np.uint32)
        lo = np.concatenate(los) if los else np.empty(0, np.uint32)
        pv = np.concatenate(pvs) if pvs else np.empty(0, np.int64)
        # 2. re-partition under the candidate transform
        ik64 = _ids64(hi, lo).view(np.float64)
        pk = np.asarray(transform_fn(ik64), np.float64).astype(np.float32)
        order = np.argsort(pk, kind="stable")
        pk, hi, lo = pk[order], hi[order], lo[order]
        pv = np.asarray(pv, np.int64)[order]
        self.boundaries_new = (choose_boundaries(pk, P) if pk.shape[0]
                               else np.empty(0, np.float32))
        sids = route(pk, self.boundaries_new)
        segs, _inv = fanout_plan(sids, P)
        # 3. fresh candidate per shard, built incrementally on-device
        self.candidates = [FlatAFLI(parent.cfg) for _ in range(P)]
        self.folds: List[Optional[_IncrementalFold]] = []
        for s, seg in enumerate(segs):
            if not seg.shape[0]:
                self.folds.append(None)
                continue
            self.folds.append(_seed_candidate(
                parent, self.candidates[s], s, pk[seg], hi[seg], lo[seg],
                pv[seg]))

    def tick(self, budget: int) -> bool:
        """Advance pending candidate folds round-robin under the
        caller's budget; returns True once the swap has happened."""
        pending = [(s, f) for s, f in enumerate(self.folds) if f is not None]
        if pending:
            share = max(budget // len(pending), 1)
            for s, f in pending:
                with self.parent._on(s):
                    if f.tick(share):
                        self.folds[s] = None
        if any(f is not None for f in self.folds):
            return False
        self._swap_all()
        return True

    def _swap_all(self) -> None:
        """4. the atomic flip: re-key the held deltas into the
        candidates, then publish shards + boundaries + serve flow in one
        block."""
        parent = self.parent
        P = parent.n_shards
        # candidate id sets from their swapped scan mirrors (== their
        # snapshot segments, tombstones already dropped)
        id_sets = []
        for cand in self.candidates:
            ids = set(_ids64(cand._scan_hi, cand._scan_lo).tolist())
            id_sets.append(ids)
        # held deltas: writes that landed during the re-key, one copy
        # per identity per old shard (append-time dedup), and each
        # identity routes to exactly one old shard — so the concat holds
        # at most one copy per identity
        dhi, dlo, dpv = [], [], []
        for idx in parent.shards:
            if idx._delta_pk.shape[0]:
                dhi.append(idx._delta_hi)
                dlo.append(idx._delta_lo)
                dpv.append(idx._delta_pv)
        if dhi:
            hi = np.concatenate(dhi)
            lo = np.concatenate(dlo)
            pv = np.concatenate(dpv)
            ik64 = _ids64(hi, lo).view(np.float64)
            pk = np.asarray(self.transform_fn(ik64),
                            np.float64).astype(np.float32)
            sids = route(pk, self.boundaries_new)
            segs, _inv = fanout_plan(sids, P)
            for s, seg in enumerate(segs):
                if not seg.shape[0]:
                    continue
                cand = self.candidates[s]
                with parent._on(s):
                    cand._append_delta(pk[seg], hi[seg], lo[seg],
                                       pv[seg].astype(np.int32))
                for u, p in zip(_ids64(hi[seg], lo[seg]).tolist(),
                                pv[seg].tolist()):
                    if p == TOMBSTONE:
                        id_sets[s].discard(u)
                    else:
                        id_sets[s].add(u)
        for s, cand in enumerate(self.candidates):
            cand._id_set = id_sets[s]
            cand.n_keys = len(id_sets[s])
            with parent._on(s):
                cand._sync_tiers()
        # ---- the flip: one assignment block, no query in between
        parent.shards = self.candidates
        parent._set_boundaries(self.boundaries_new)
        parent._serve_flow = self.serve_flow
        parent.n_reflows += 1
        self.on_swap()


class _ShardedReshard:
    """Localized boundary migration (DESIGN.md §18): split a hot shard /
    merge cold neighbors by re-partitioning ONE contiguous window of
    shards ``[lo, hi]`` under fresh equal-mass boundaries while every
    shard outside the window keeps serving untouched.

    Same four-phase shape as :class:`_ShardedReflow`, scoped to the
    window and with NO transform — positioning keys do not move, only
    the boundaries between them do, so snapshot keys partition directly
    and the held deltas route under the new interior boundaries without
    re-keying:

    1. **freeze** — snapshot the window shards (``_snapshot_live``) and
       put them on ``_tier_hold``: their deltas keep absorbing writes,
       but no local fold may consume entries this snapshot owns;
    2. **re-partition** — the new interior boundaries are the equal-mass
       quantiles of the window's OWN snapshot (``choose_boundaries``
       over the affected shards' flow-CDF mass), so the k window slots
       rebalance while the outer boundaries ``B[lo-1]`` / ``B[hi]`` —
       and therefore every untouched shard's domain — stay
       bit-identical;
    3. **rebuild incrementally** — one fresh candidate ``FlatAFLI`` per
       window slot (fresh ``ServingState``: fresh capacity buckets, and
       ratchets release exactly as a §14 fold swap releases them —
       scoped to the migrated slots only), folds advanced by the
       routed-traffic budget while the old window shards keep serving;
    4. **swap atomically** — held window deltas route by the new
       interior boundaries into the candidates, then the window shards
       and the boundary splice flip in one assignment block.  The
       boundary array changes VALUES only (same length), so
       ``_route_flow`` keeps its compiled trace and the §17 streamed
       router — whose shape is a function of pool capacity, never of
       boundary values — is untouched.

    Any construction or fold failure aborts the episode: the parent
    drops the coordinator, un-holds the window tiers, and serving
    continues on the old shards + boundaries (nothing was published, so
    there is nothing to roll back beyond the holds — ``_snapshot_live``
    merges deltas INTO the live run tier, never out of it).
    """

    def __init__(self, parent: "ShardedFlatAFLI", lo: int, hi: int,
                 on_swap, on_abort=None):
        self.parent = parent
        self.lo = int(lo)
        self.hi = int(hi)
        self.on_swap = on_swap
        self.on_abort = on_abort
        k = self.hi - self.lo + 1
        # 1. freeze the window (fault seam: a snapshot that raises
        # mid-window exercises the partial-freeze rollback)
        pks, his, los, pvs, wts = [], [], [], [], []
        for s in range(self.lo, self.hi + 1):
            if s > self.lo and parent._reshard_fault == "snapshot":
                raise RuntimeError("injected fault: reshard snapshot")
            idx = parent.shards[s]
            spk, shi, slo, spv = idx._snapshot_live()
            pks.append(spk)
            his.append(shi)
            los.append(slo)
            pvs.append(spv)
            # per-key weight: the source shard's decayed load spread
            # uniformly over its own keys (the router sees shards, not
            # keys, so uniform-within-shard is the finest attribution
            # the telemetry supports)
            load_s = (float(parent._load_reads[s])
                      + float(parent._load_writes[s]))
            n_s = max(int(spk.shape[0]), 1)
            wts.append(np.full(spk.shape[0], 1.0 + load_s / n_s,
                               np.float64))
            idx._fold = None
            idx._tier_hold = True
        pk = np.concatenate(pks) if pks else np.empty(0, np.float32)
        hi_ = np.concatenate(his) if his else np.empty(0, np.uint32)
        lo_ = np.concatenate(los) if los else np.empty(0, np.uint32)
        pv = np.concatenate(pvs) if pvs else np.empty(0, np.int64)
        wt = np.concatenate(wts) if wts else np.empty(0, np.float64)
        pk = np.asarray(pk, np.float32)
        order = np.argsort(pk, kind="stable")
        pk, hi_, lo_ = pk[order], hi_[order], lo_[order]
        pv = np.asarray(pv, np.int64)[order]
        wt = wt[order]
        # 2. re-partition: equal-mass interior boundaries over the
        # window's LOAD-WEIGHTED flow-CDF mass (DILI's balancing
        # objective): each key carries ``1 + load/n`` of its source
        # shard, so with balanced load this is exactly the key-mass
        # quantile split (``choose_boundaries``), and under read skew
        # the hot shard's range splits finer — a read-hot range spreads
        # across slots even when the key mass is already balanced.
        # Window keys live in [B[lo-1], B[hi]), so the quantile values
        # can never cross the outer boundaries.
        if pk.shape[0]:
            cw = np.cumsum(wt)
            targets = cw[-1] * (np.arange(1, k, dtype=np.float64) / k)
            cut = np.clip(np.searchsorted(cw, targets, side="left"),
                          0, pk.shape[0] - 1)
            self.interior = np.ascontiguousarray(pk[cut], np.float32)
        else:
            # empty window: the splice becomes an identity write
            self.interior = parent.boundaries[self.lo:self.hi].copy()
        sids = route(pk, self.interior)
        segs, _inv = fanout_plan(sids, k)
        # 3. fresh candidate per window slot, built incrementally on the
        # slot's own device
        self.candidates = [FlatAFLI(parent.cfg) for _ in range(k)]
        self.folds: List[Optional[_IncrementalFold]] = []
        for j, seg in enumerate(segs):
            if not seg.shape[0]:
                self.folds.append(None)
                continue
            self.folds.append(_seed_candidate(
                parent, self.candidates[j], self.lo + j, pk[seg],
                hi_[seg], lo_[seg], pv[seg]))

    def tick(self, budget: int) -> bool:
        """Advance pending window folds round-robin under the caller's
        budget; returns True once the swap has happened."""
        if self.parent._reshard_fault == "fold":
            raise RuntimeError("injected fault: reshard candidate fold")
        pending = [(j, f) for j, f in enumerate(self.folds)
                   if f is not None]
        if pending:
            share = max(budget // len(pending), 1)
            for j, f in pending:
                with self.parent._on(self.lo + j):
                    if f.tick(share):
                        self.folds[j] = None
        if any(f is not None for f in self.folds):
            return False
        self._swap_window()
        return True

    def _swap_window(self) -> None:
        """4. the atomic flip: route the held window deltas into the
        candidates under the new interior boundaries, then publish the
        window shards + the boundary splice in one block.  Shards
        outside ``[lo, hi]`` are never read or written here — the §11
        zero-repack guarantees hold for them through the swap."""
        parent = self.parent
        k = self.hi - self.lo + 1
        # candidate id sets from their swapped scan mirrors (== their
        # snapshot segments, tombstones already dropped)
        id_sets = []
        for cand in self.candidates:
            id_sets.append(set(_ids64(cand._scan_hi,
                                      cand._scan_lo).tolist()))
        # held deltas: writes that landed during the migration, one copy
        # per identity per old window shard; positioning keys are
        # unchanged, so they route directly by the new interior
        dpk, dhi, dlo, dpv = [], [], [], []
        for idx in parent.shards[self.lo:self.hi + 1]:
            if idx._delta_pk.shape[0]:
                dpk.append(idx._delta_pk)
                dhi.append(idx._delta_hi)
                dlo.append(idx._delta_lo)
                dpv.append(idx._delta_pv)
        if dpk:
            pk = np.asarray(np.concatenate(dpk), np.float32)
            hi_ = np.concatenate(dhi)
            lo_ = np.concatenate(dlo)
            pv = np.concatenate(dpv)
            sids = route(pk, self.interior)
            segs, _inv = fanout_plan(sids, k)
            for j, seg in enumerate(segs):
                if not seg.shape[0]:
                    continue
                cand = self.candidates[j]
                with parent._on(self.lo + j):
                    cand._append_delta(pk[seg], hi_[seg], lo_[seg],
                                       np.asarray(pv[seg], np.int32))
                for u, p in zip(_ids64(hi_[seg], lo_[seg]).tolist(),
                                np.asarray(pv[seg]).tolist()):
                    if p == TOMBSTONE:
                        id_sets[j].discard(u)
                    else:
                        id_sets[j].add(u)
        for j, cand in enumerate(self.candidates):
            cand._id_set = id_sets[j]
            cand.n_keys = len(id_sets[j])
            with parent._on(self.lo + j):
                cand._sync_tiers()
        # ---- the flip: one assignment block, no query in between
        parent.shards[self.lo:self.hi + 1] = self.candidates
        parent._refresh_boundaries(self.interior, self.lo)
        # the window's load gauges described the OLD domains — level
        # them (total preserved) so stale attribution cannot re-trigger
        # on the slots whose domains just moved; they re-converge within
        # one load window of routed traffic
        for g in (parent._load_reads, parent._load_writes):
            g[self.lo:self.hi + 1] = g[self.lo:self.hi + 1].mean()
        parent.n_reshards += 1
        self.on_swap()


class ShardedFlatAFLI:
    """P-way key-space-partitioned FlatAFLI behind the FlatAFLI serving
    surface (DESIGN.md §13) — ``NFL`` drives it exactly like the single
    index: ``build`` / ``lookup_batch(_flow)`` / ``insert_batch`` /
    ``delete_batch`` / ``scan_batch(_flow)`` / ``contains_batch`` /
    ``verify_serve_flow`` / ``rebuild`` / ``stats``."""

    def __init__(self, cfg: FlatAFLIConfig | None = None,
                 n_shards: int = 2, devices: Optional[list] = None):
        self.cfg = cfg or FlatAFLIConfig()
        self.n_shards = max(int(n_shards), 1)
        if devices is None:
            self.mesh, self.devices = shard_mesh(self.n_shards)
        else:
            self.mesh, self.devices = None, list(devices)
            if len(self.devices) < self.n_shards:
                self.devices = [self.devices[s % len(self.devices)]
                                for s in range(self.n_shards)]
        self.shards: List[FlatAFLI] = [FlatAFLI(self.cfg)
                                       for _ in range(self.n_shards)]
        self.boundaries = np.empty(0, np.float32)   # f32[P-1], host copy
        self._boundaries_dev = None                 # router-device copy
        self._serve_flow = None
        self._reflow: Optional[_ShardedReflow] = None   # §14 coordinator
        self.n_reflows = 0
        self._reshard: Optional[_ShardedReshard] = None  # §18 coordinator
        self.n_reshards = 0
        self.n_reshard_aborts = 0
        self._reshard_fault: Optional[str] = None   # §16 fault seam
        # §18 router load gauges: decayed per-shard key mass.  Reads and
        # writes decay together (shares stay comparable across the two),
        # and the decay clock is routed keys, not wall time, so the
        # gauges are deterministic under test.  Gauges, not counters:
        # reset_telemetry() leaves them alone.
        self.load_window_keys = 4096
        self._load_reads = np.zeros(self.n_shards, np.float64)
        self._load_writes = np.zeros(self.n_shards, np.float64)
        self._router = {
            "point_batches": 0, "point_queries": 0,
            "write_batches": 0, "write_keys": 0,
            "range_batches": 0, "range_queries": 0,
            "range_subqueries": 0, "straddling_ranges": 0,
            "per_shard_points": [0] * self.n_shards,
            "per_shard_writes": [0] * self.n_shards,
            "per_shard_ranges": [0] * self.n_shards,
        }

    # ------------------------------------------------------------ helpers
    @contextlib.contextmanager
    def _on(self, s: int):
        """Pin shard ``s``'s device as the dispatch default: pools built
        or refreshed inside land on (and serve from) ``devices[s]``."""
        with jax.default_device(self.devices[s]):
            yield

    def _set_boundaries(self, boundaries: np.ndarray) -> None:
        import jax.numpy as jnp

        self.boundaries = np.asarray(boundaries, np.float32)
        if self.boundaries.shape[0] == 0:
            self._boundaries_dev = None
            return
        # committed to the router's device (shard 0's): the router is a
        # one-device program, and a copy replicated across the shard
        # mesh would make it a P-device SPMD program that the NF
        # kernel cannot be partitioned into
        self._boundaries_dev = jax.device_put(jnp.asarray(self.boundaries),
                                              self.devices[0])

    def _route_points(self, z32: np.ndarray) -> np.ndarray:
        return route(z32, self.boundaries)

    def _reflow_tick(self, n_batch: int) -> None:
        """Advance an in-flight cross-shard re-key by the same bounded
        budget a local fold would get — re-key progress is charged to
        the writes, never to reads (§10/§14)."""
        if self._reflow is None:
            return
        budget = max(int(self.cfg.fold_step_keys),
                     int(self.cfg.fold_work_factor * max(n_batch, 1)))
        if self._reflow.tick(budget):
            self._reflow = None

    def start_reflow(self, transform_fn, serve_flow, on_swap) -> bool:
        """Begin the coordinated cross-shard re-key (DESIGN.md §14):
        freeze + re-partition now, then candidate shards build
        incrementally under the per-write budget while the old shards
        and boundaries keep serving; the final swap flips shards,
        boundaries, and the serve-flow context atomically.  Returns
        False while a previous re-key is still in flight."""
        if self._reflow is not None or self._reshard is not None:
            return False
        self._reflow = _ShardedReflow(self, transform_fn, serve_flow,
                                      on_swap)
        # degenerate case (nothing indexed): all folds empty — swap now
        self._reflow_tick(1)
        return True

    # ------------------------------------------------------ §18 resharding
    def _note_load(self, segs, *, write: bool) -> None:
        """Fold one routed batch into the decayed load gauges.  One
        batch of n keys decays every gauge by ``exp(-n / window)`` then
        adds the batch's per-shard counts, so each gauge is a key mass
        with an expected horizon of ``load_window_keys`` routed keys."""
        counts = np.array([int(seg.shape[0]) for seg in segs], np.float64)
        n = float(counts.sum())
        if n <= 0.0:
            return
        d = float(np.exp(-n / float(max(self.load_window_keys, 1))))
        self._load_reads *= d
        self._load_writes *= d
        if write:
            self._load_writes += counts
        else:
            self._load_reads += counts

    def load_snapshot(self) -> dict:
        """§18 trigger input (the ``ReshardManager.load_snapshot``
        seam): decayed per-shard read/write gauges plus live key counts,
        jsonable."""
        return {
            "reads": self._load_reads.tolist(),
            "writes": self._load_writes.tolist(),
            "n_keys": [int(idx.n_keys) for idx in self.shards],
            "window_keys": int(self.load_window_keys),
        }

    def start_reshard(self, lo: int, hi: int, on_swap,
                      on_abort=None) -> bool:
        """Begin the localized boundary migration of shard window
        ``[lo, hi]`` (DESIGN.md §18): freeze + re-partition now, then
        the window's candidates fold incrementally under the
        routed-traffic budget while ALL shards — window included — keep
        serving against the old boundaries; the swap flips the window
        shards and the boundary splice atomically.  Returns False while
        a §14 re-key or another migration is in flight; raises if the
        freeze itself fails (window un-held, nothing published)."""
        if self._reshard is not None or self._reflow is not None:
            return False
        lo = max(int(lo), 0)
        hi = min(int(hi), self.n_shards - 1)
        if hi <= lo:
            return False
        try:
            self._reshard = _ShardedReshard(self, lo, hi, on_swap,
                                            on_abort)
        except Exception:
            # partial-freeze rollback: un-hold the window and re-raise;
            # data is safe (_snapshot_live merges into the live run
            # tier, never out of it) and nothing was published
            for s in range(lo, hi + 1):
                self.shards[s]._tier_hold = False
            self.n_reshard_aborts += 1
            raise
        self._reshard_tick(1)   # degenerate (empty window) swaps now
        return True

    def _reshard_tick(self, n_batch: int) -> None:
        """Advance an in-flight migration by the same bounded budget a
        local fold would get.  Read skew is the §18 trigger, so reads
        AND writes fund migration folds (unlike §14 re-keys, which only
        writes fund — a read-only hot shard must still migrate).  A fold
        failure aborts the episode in place: drop the coordinator,
        un-hold the window, leave shards + boundaries exactly as they
        were, and notify the owner (``on_abort``)."""
        if self._reshard is None:
            return
        budget = max(int(self.cfg.fold_step_keys),
                     int(self.cfg.fold_work_factor * max(n_batch, 1)))
        r = self._reshard
        try:
            done = r.tick(budget)
        except DEVICE_ERRORS:
            raise
        except Exception:
            self._reshard = None
            for s in range(r.lo, r.hi + 1):
                self.shards[s]._tier_hold = False
            self.n_reshard_aborts += 1
            if r.on_abort is not None:
                r.on_abort()
            return
        if done:
            self._reshard = None

    def _refresh_boundaries(self, interior: np.ndarray, lo: int) -> None:
        """Value-only boundary refresh (§18): splice the window's new
        interior boundaries into the existing f32[P-1] array through the
        jitted ``_splice_boundaries`` kernel and republish.  The length
        never changes, so ``_route_flow`` — whose boundaries argument is
        traced, not static — keeps its compiled trace across the swap,
        and the §17 streamed router (shaped by pool capacity, not by
        boundary values) is untouched."""
        self._set_boundaries(
            refresh_boundaries(self.boundaries, interior, lo))

    # -------------------------------------------------------------- build
    def build(self, pkeys: np.ndarray, payloads: np.ndarray,
              ikeys: np.ndarray | None = None) -> None:
        """Partition the bulk-load snapshot at flow-CDF quantiles and
        build one FlatAFLI per shard on its own device.  Partitioning
        compares the same f32 positioning keys the router compares, so
        build placement and query routing agree exactly."""
        pk64 = np.asarray(pkeys, dtype=np.float64)
        ik64 = pk64 if ikeys is None else np.asarray(ikeys, dtype=np.float64)
        pv = np.asarray(payloads, dtype=np.int64)
        pk32 = pk64.astype(np.float32)
        self._set_boundaries(
            choose_boundaries(np.sort(pk32, kind="stable"), self.n_shards))
        sids = self._route_points(pk32)
        segs, _inv = fanout_plan(sids, self.n_shards)
        for s, seg in enumerate(segs):
            with self._on(s):
                if seg.shape[0]:
                    self.shards[s].build(pk64[seg], pv[seg], ikeys=ik64[seg])
                # an empty shard stays unbuilt: reads resolve to misses
                # through the pre-build path, writes buffer in its tiers

    def set_serve_flow(self, normalizer, flow_cfg, packed_w, shapes) -> None:
        """Register the serve-path flow for the router.  NOT forwarded
        to the shards: sharded serving computes z once at the router
        (the build-path ``nf_forward_pallas`` kernel) and probes every
        shard through the non-flow route, so there is no per-shard
        in-kernel NF whose divergence a fold would need to re-verify —
        each shard's §8 placement verification covers the rest."""
        self._serve_flow = (normalizer, flow_cfg, packed_w, shapes)

    def verify_serve_flow(self, feats: np.ndarray, ikeys: np.ndarray,
                          packed_w, shapes, payloads: np.ndarray) -> int:
        """§8 for the sharded route: re-run every built key through the
        actual serve path (fused route -> per-shard fused lookup).  A
        key the serve path cannot resolve is shadowed into the shard the
        *router* targets (run-tier append keyed by serve z), and any
        stale copy bookkept by a different shard is tombstoned there, so
        cross-shard routing drift can never surface as a miss.  Returns
        the number of repaired keys (0 in practice: router z and build z
        ride the same NF kernel)."""
        z, sids = route_flow(feats, packed_w, shapes, self._boundaries_dev)
        res = self._fanout_points(z.astype(np.float64), ikeys, sids)
        pv = np.asarray(payloads)
        wrong = res != pv.astype(res.dtype)
        if not wrong.any():
            return 0
        ik64 = np.asarray(ikeys, dtype=np.float64)
        hi, lo = split_key_bits(ik64)
        ids = _ids64(hi, lo)
        for s in np.unique(sids[wrong]):
            m = wrong & (sids == s)
            idx = self.shards[int(s)]
            with self._on(int(s)):
                idx._append_run(z[m].astype(np.float32), hi[m], lo[m],
                                pv[m].astype(np.int32))
            for u in ids[m].tolist():
                if u not in idx._id_set:
                    idx._id_set.add(u)
                    idx.n_keys += 1
        # tombstone stale copies bookkept by other shards
        for t, other in enumerate(self.shards):
            m = wrong & (sids != t)
            stale = m & np.fromiter(
                (int(u) in other._id_set for u in ids),
                bool, count=ids.shape[0])
            if stale.any():
                with self._on(t):
                    other.delete_batch(z[stale].astype(np.float64),
                                       ikeys=ik64[stale])
        return int(wrong.sum())

    def contains_batch(self, ikeys: np.ndarray) -> np.ndarray:
        """Exact membership by 64-bit identity, across all shards —
        the key bits are split once and tested against every shard's
        live-id set in a single pass (set lookups short-circuit), not
        P full per-shard passes."""
        hi, lo = split_key_bits(np.asarray(ikeys, dtype=np.float64))
        id_sets = [idx._id_set for idx in self.shards]
        return np.fromiter(
            (any(int(u) in s for s in id_sets)
             for u in _ids64(hi, lo)),
            bool, count=hi.shape[0])

    # ------------------------------------------------------------- points
    def _fanout_points_async(self, pk64: np.ndarray, ik64: np.ndarray,
                             sids: np.ndarray):
        """Dispatch every shard's sub-batch before finishing any (the
        fan-out/gather of DESIGN.md §13) and return a zero-arg finisher
        that gathers the parts and restores input order.  Every shard
        kernel is in flight when this returns, so a §16 front-end can
        stack a second batch behind the first before blocking."""
        segs, inv = fanout_plan(sids, self.n_shards)
        self._note_load(segs, write=False)
        ik64 = np.asarray(ik64, dtype=np.float64)
        finishers = []
        for s, seg in enumerate(segs):
            c = int(seg.shape[0])
            self._router["per_shard_points"][s] += c
            if not c:
                finishers.append(None)
                continue
            with self._on(s):
                finishers.append(self.shards[s].lookup_batch_async(
                    pk64[seg], ikeys=ik64[seg]))
        n = int(sids.shape[0])

        def finish() -> np.ndarray:
            parts = [f() for f in finishers if f is not None]
            if not parts:
                return np.full(n, -1, np.int32)
            return np.concatenate(parts)[inv]

        return finish

    def _fanout_points(self, pk64: np.ndarray, ik64: np.ndarray,
                       sids: np.ndarray) -> np.ndarray:
        return self._fanout_points_async(pk64, ik64, sids)()

    def lookup_batch_async(self, keys: np.ndarray,
                           ikeys: np.ndarray | None = None):
        """Non-blocking form of ``lookup_batch``: route, fan out to
        every shard, and return the gather as a finisher."""
        k64 = np.asarray(keys, dtype=np.float64)
        ik64 = k64 if ikeys is None else np.asarray(ikeys, dtype=np.float64)
        sids = self._route_points(k64.astype(np.float32))
        self._router["point_batches"] += 1
        self._router["point_queries"] += int(k64.shape[0])
        finish = self._fanout_points_async(k64, ik64, sids)
        self._reshard_tick(int(k64.shape[0]))
        return finish

    def lookup_batch(self, keys: np.ndarray,
                     ikeys: np.ndarray | None = None) -> np.ndarray:
        """Batched point lookups; ``keys`` are positioning keys (raw
        keys when the flow is off)."""
        return self.lookup_batch_async(keys, ikeys)()

    def lookup_batch_flow_async(self, feats: np.ndarray, ikeys: np.ndarray,
                                packed_w, shapes):
        """Non-blocking form of ``lookup_batch_flow``: one fused router
        dispatch, per-shard kernels all in flight on return."""
        z, sids = route_flow(feats, packed_w, shapes, self._boundaries_dev)
        self._router["point_batches"] += 1
        self._router["point_queries"] += int(z.shape[0])
        finish = self._fanout_points_async(z.astype(np.float64), ikeys,
                                           sids)
        self._reshard_tick(int(z.shape[0]))
        return finish

    def lookup_batch_flow(self, feats: np.ndarray, ikeys: np.ndarray,
                          packed_w, shapes) -> np.ndarray:
        """Flow-on point serving: ONE fused router dispatch (NF forward
        + boundary binning), then the per-shard fused kernels probe by
        the routed z — identity resolution and the in-kernel tier probes
        work exactly as on the single index."""
        return self.lookup_batch_flow_async(feats, ikeys, packed_w,
                                            shapes)()

    # ------------------------------------------------------------- writes
    def insert_batch(self, keys: np.ndarray, payloads: np.ndarray,
                     ikeys: np.ndarray | None = None) -> None:
        """Route the batch and append per shard: each shard's delta /
        run / incremental fold advances independently (§10 per shard),
        so a fold triggered on one shard is paid for only by the inserts
        routed there."""
        k64 = np.asarray(keys, dtype=np.float64)
        ik64 = k64 if ikeys is None else np.asarray(ikeys, dtype=np.float64)
        pv = np.asarray(payloads, dtype=np.int32)
        sids = self._route_points(k64.astype(np.float32))
        segs, _inv = fanout_plan(sids, self.n_shards)
        self._note_load(segs, write=True)
        self._router["write_batches"] += 1
        self._router["write_keys"] += int(k64.shape[0])
        for s, seg in enumerate(segs):
            c = int(seg.shape[0])
            self._router["per_shard_writes"][s] += c
            if not c:
                continue
            with self._on(s):
                self.shards[s].insert_batch(k64[seg], pv[seg],
                                            ikeys=ik64[seg])
        self._reflow_tick(int(k64.shape[0]))
        self._reshard_tick(int(k64.shape[0]))

    def delete_batch(self, keys: np.ndarray,
                     ikeys: np.ndarray | None = None) -> np.ndarray:
        """Tombstone deletes, routed like inserts; per-key success flags
        gather back to input order."""
        k64 = np.asarray(keys, dtype=np.float64)
        ik64 = k64 if ikeys is None else np.asarray(ikeys, dtype=np.float64)
        sids = self._route_points(k64.astype(np.float32))
        segs, inv = fanout_plan(sids, self.n_shards)
        self._note_load(segs, write=True)
        self._router["write_batches"] += 1
        self._router["write_keys"] += int(k64.shape[0])
        parts = []
        for s, seg in enumerate(segs):
            c = int(seg.shape[0])
            self._router["per_shard_writes"][s] += c
            if not c:
                continue
            with self._on(s):
                parts.append(self.shards[s].delete_batch(k64[seg],
                                                         ikeys=ik64[seg]))
        self._reflow_tick(int(k64.shape[0]))
        self._reshard_tick(int(k64.shape[0]))
        if not parts:
            return np.zeros(k64.shape[0], bool)
        return np.concatenate(parts)[inv]

    # ------------------------------------------------------------- ranges
    def scan_batch(self, lo_keys: np.ndarray, hi_keys: np.ndarray,
                   cap: int | None = None):
        """Batched ``[lo, hi)`` range scans across shards (§12 per
        shard, §13 split/merge)."""
        lo32 = np.asarray(lo_keys, dtype=np.float64).astype(np.float32)
        hi32 = np.asarray(hi_keys, dtype=np.float64).astype(np.float32)
        return self._fanout_scan(lo32, hi32, cap)

    def scan_batch_flow(self, feats_lo: np.ndarray, feats_hi: np.ndarray,
                        packed_w, shapes, cap: int | None = None):
        """Flow-on ranges: BOTH endpoint batches ride one concatenated
        router NF dispatch (splitting happens on host anyway), then
        split/fan out/merge in z-space."""
        n = np.asarray(feats_lo).shape[0]
        z, _ = route_flow(np.concatenate([feats_lo, feats_hi]),
                          packed_w, shapes, self._boundaries_dev)
        return self._fanout_scan(z[:n], z[n:], cap)

    def _fanout_scan(self, zlo32: np.ndarray, zhi32: np.ndarray,
                     cap: int | None):
        """Split straddling ranges at shard boundaries, scan each shard
        locally, merge sub-results back in z order (DESIGN.md §13).

        Merge semantics: sub-ranges tile ``[zlo, zhi)`` and shard order
        is z order, so concatenating each sub-scan's live lanes in shard
        order reproduces the single-index emission exactly while every
        sub-scan's candidate work stays bounded by ``cap``.  ``totals``
        sums the per-shard candidate totals (the single-index count);
        ``counts`` re-truncates at ``cap``.  When an earlier sub-range
        is itself truncated, later sub-ranges of that query are dropped
        from the lanes (their candidates would leave a z-order gap) but
        still counted in ``totals`` — exceeding ``cap`` flags truncation
        either way."""
        cap = int(cap if cap is not None else self.cfg.scan_cap)
        n = int(zlo32.shape[0])
        qid, sid, sub_lo, sub_hi = split_ranges(zlo32, zhi32,
                                                self.boundaries)
        m = int(qid.shape[0])
        self._router["range_batches"] += 1
        self._router["range_queries"] += n
        self._router["range_subqueries"] += m
        spans = np.bincount(qid, minlength=n)
        self._router["straddling_ranges"] += int((spans > 1).sum())
        out = np.full((n, cap), -1, np.int32)
        cnt = np.zeros(n, np.int32)
        tot = np.zeros(n, np.int64)
        if not m:
            return out, cnt, tot.astype(np.int32)
        sub_pv = np.empty((m, cap), np.int32)
        sub_cnt = np.empty(m, np.int32)
        sub_tot = np.empty(m, np.int64)
        segs, _inv = fanout_plan(sid, self.n_shards)
        self._note_load(segs, write=False)
        for s, seg in enumerate(segs):
            c = int(seg.shape[0])
            self._router["per_shard_ranges"][s] += c
            if not c:
                continue
            with self._on(s):
                pv_s, cnt_s, tot_s = self.shards[s].scan_batch(
                    sub_lo[seg].astype(np.float64),
                    sub_hi[seg].astype(np.float64), cap=cap)
            sub_pv[seg] = pv_s[:, :cap]
            sub_cnt[seg] = cnt_s
            sub_tot[seg] = tot_s
        # ---- merge: sub-queries are qid-major, shard ascending == z
        # ascending.  Lane offset of sub-query j = lanes emitted by the
        # earlier sub-queries of the same query.
        first = np.searchsorted(qid, np.arange(n))  # first sub of each q
        trunc = sub_tot > cap
        a = np.cumsum(trunc) - trunc               # exclusive cumsum
        dropped = (a - a[np.clip(first[qid], 0, max(m - 1, 0))]) > 0
        eff_cnt = np.where(dropped, 0, sub_cnt)
        csum = np.cumsum(eff_cnt) - eff_cnt        # exclusive cumsum
        offset = csum - csum[np.clip(first[qid], 0, max(m - 1, 0))]
        lane = np.arange(cap)[None, :]
        dest = offset[:, None] + lane
        keep = (lane < eff_cnt[:, None]) & (dest < cap)
        rows = np.broadcast_to(qid[:, None], (m, cap))
        out[rows[keep], dest[keep]] = sub_pv[keep]
        cnt = np.minimum(
            np.bincount(qid, weights=eff_cnt, minlength=n), cap
        ).astype(np.int32)
        tot = np.bincount(qid, weights=sub_tot, minlength=n).astype(np.int64)
        return out, cnt, np.clip(tot, 0, np.iinfo(np.int32).max
                                 ).astype(np.int32)

    # ---------------------------------------------------------------- misc
    def rebuild(self) -> None:
        """Fold every shard's write tiers synchronously (maintenance /
        test hook; production serving relies on per-shard incremental
        folds instead).  An in-flight cross-shard re-key is driven to
        its swap first — rebuilding the old shards would waste the work
        and re-freeze their tiers; same for an in-flight §18 migration
        (an aborting migration exits the loop by dropping itself)."""
        while self._reshard is not None:
            self._reshard_tick(1 << 50)
        while self._reflow is not None:
            self._reflow_tick(1 << 50)
        for s, idx in enumerate(self.shards):
            with self._on(s):
                idx.rebuild()

    @property
    def n_keys(self) -> int:
        return int(sum(idx.n_keys for idx in self.shards))

    @property
    def n_host_tier_probes(self) -> int:
        return int(sum(idx.n_host_tier_probes for idx in self.shards))

    @property
    def n_host_scans(self) -> int:
        return int(sum(idx.n_host_scans for idx in self.shards))

    def serving_telemetry(self) -> dict:
        """Aggregated ``NFL.dispatch_stats()`` slice (§11/§13): summed
        ServingState counters, per-shard breakdowns (each carrying its
        §18 decayed load gauges), and the router's fan-out
        accounting."""
        per_shard = []
        for s, idx in enumerate(self.shards):
            t = idx.serving_telemetry()
            t["load"] = {"reads": float(self._load_reads[s]),
                         "writes": float(self._load_writes[s])}
            per_shard.append(t)
        # counters sum across shards; gauges (resident capacities,
        # ratcheted statics) take the max — a summed depth bound would
        # describe no kernel anywhere
        gauges = {"static_max_depth", "static_dense_window",
                  "run_capacity", "delta_capacity", "scan_capacity",
                  "run_window", "delta_window", "scan_window"}
        agg: dict = {}
        for t in per_shard:
            for k, v in t["serving"].items():
                agg[k] = max(agg.get(k, 0), v) if k in gauges \
                    else agg.get(k, 0) + v
        return {
            "serving": agg,
            "host_tier_probes": self.n_host_tier_probes,
            "host_scans": self.n_host_scans,
            "shards": per_shard,
            "router": {k: (list(v) if isinstance(v, list) else v)
                       for k, v in self._router.items()},
        }

    def drift_signals(self) -> dict:
        """§14 drift signals, aggregated the same way the serving
        telemetry is: gauges take the worst shard, counters sum, and the
        per-shard breakdown rides along so a drifting sub-distribution
        is attributable."""
        per = [idx.drift_signals() for idx in self.shards]
        return {
            "max_depth": max((p["max_depth"] for p in per), default=1),
            "static_max_depth": max((p["static_max_depth"] for p in per),
                                    default=4),
            "static_dense_window": max((p["static_dense_window"]
                                        for p in per), default=4),
            "run_window": max((p["run_window"] for p in per), default=4),
            "delta_window": max((p["delta_window"] for p in per), default=4),
            "delta_len": sum(p["delta_len"] for p in per),
            "run_len": sum(p["run_len"] for p in per),
            "run_ratio": max((p["run_ratio"] for p in per), default=0.0),
            "fold_active": any(p["fold_active"] for p in per),
            "reflow_active": self._reflow is not None,
            "reshard_active": self._reshard is not None,
            "n_rebuilds": sum(p["n_rebuilds"] for p in per),
            "n_reflows": int(self.n_reflows),
            "n_reshards": int(self.n_reshards),
            "n_reshard_aborts": int(self.n_reshard_aborts),
            "autoswitch": [p["autoswitch"] for p in per],
            "shards": per,
        }

    def reset_telemetry(self) -> None:
        """Per-shard counter reset plus the router's fan-out accounting
        (per-shard lists reset to zeros; see ``FlatAFLI.reset_telemetry``
        for what counts as a counter vs. state).  The §18 decayed load
        gauges are state, not counters — they survive the reset, exactly
        like the capacity/ratchet gauges do."""
        for idx in self.shards:
            idx.reset_telemetry()
        for k, v in self._router.items():
            self._router[k] = [0] * self.n_shards if isinstance(v, list) \
                else 0

    def stats(self) -> dict:
        shard_stats = [idx.stats() for idx in self.shards]
        return {
            "n_shards": self.n_shards,
            "n_keys": self.n_keys,
            "boundaries": self.boundaries.tolist(),
            "devices": [str(d) for d in self.devices],
            "fold_active": any(s["fold_active"] for s in shard_stats),
            "reflow_active": self._reflow is not None,
            "reshard_active": self._reshard is not None,
            "n_rebuilds": sum(s["n_rebuilds"] for s in shard_stats),
            "n_reflows": self.n_reflows,
            "n_reshards": self.n_reshards,
            "n_reshard_aborts": self.n_reshard_aborts,
            "load": self.load_snapshot(),
            "max_depth": max((s["max_depth"] for s in shard_stats),
                             default=1),
            "n_host_tier_probes": self.n_host_tier_probes,
            "n_host_scans": self.n_host_scans,
            "router": {k: (list(v) if isinstance(v, list) else v)
                       for k, v in self._router.items()},
            "shards": shard_stats,
        }
