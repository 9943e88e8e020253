"""Persistent device-resident serving state (DESIGN.md §11).

The serving hot path must pay only for the kernel.  Before this module,
every pool mutation re-packed and re-uploaded whole tiers from host
numpy, and every tier length change altered the lane-padded shapes the
jit cache is keyed on — an XLA retrace + recompile in the middle of a
mixed workload (the BENCH_mixed_workload read p99 was ~750x its p50 for
exactly this reason).  ``ServingState`` makes serving zero-repack:

* **pack once** — the static tree pools are packed to kernel layout once
  per build/fold-swap and cached until the next swap (invalidate on
  mutation, never per call);
* **shape-bucketed tiers** — the write tiers live in *persistent* device
  buffers sized to power-of-two capacity buckets with a ``(length,)``
  scalar ridealong; a delta append overwrites the live prefix in place
  through ``lax.dynamic_update_slice`` (a small bounded device copy),
  so traced shapes change only when a tier outgrows its bucket;
* **ratcheted statics** — every static kernel parameter that can drift
  with the data (traversal depth bound, duplicate-run scan windows,
  binary-search iteration counts) only ever ratchets upward, so a fold
  swap that would shrink them cannot retrace the kernel.  Scanning or
  looping further than necessary is semantically free: all matching is
  by exact 64-bit identity and the traversal early-exits.

The rows of a tier buffer beyond the live prefix are inert by
construction: the in-kernel binary search is bounded by the length
scalar and the window scan masks on ``index < length``, so stale data
from a previous (longer) tier state is never observed.  ``+inf`` key
padding is still written inside each refreshed prefix as belt and
braces.

Instrumented throughout: uploads (count + bytes), full repacks
(fresh-buffer allocations), and pack reuse are all counted so the
serving benchmarks can assert the zero-repack property instead of
inferring it from tail latencies.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.obs import span

__all__ = ["ServingState", "DeviceTier", "pow2_bucket"]

_LANE = 128


def pow2_bucket(n: int, floor: int = _LANE) -> int:
    """Smallest power-of-two bucket >= max(n, floor)."""
    n = max(int(n), int(floor))
    return 1 << max(n - 1, 0).bit_length()


# ------------------------------------------------------------------ jitted
# device-side prefix writes: ONE cache entry per (capacity, dtype) pair
# per device — refreshes always ship the full capacity bucket, so there
# is no pow2 rung ladder to warm.  (Shipping the live prefix rounded to
# a smaller pow2 saved bytes but minted a fresh ~40ms XLA compile per
# rung crossing — multiplied by P devices on a sharded index (§13), the
# ladder put steady-state writes back on the compile path.)
@jax.jit
def _write_prefix(buf: jnp.ndarray, vals: jnp.ndarray) -> jnp.ndarray:
    return jax.lax.dynamic_update_slice(buf, vals, (0,))


@jax.jit
def _write_len(buf: jnp.ndarray, n) -> jnp.ndarray:
    return buf.at[0].set(n)


class DeviceTier:
    """One sorted write tier in a persistent bucketed device buffer
    (DESIGN.md §11 bucket ladder; also backs the §12 scan pool).

    Layout matches ``_pack_tier``: pk f32 / hi u32 / lo u32 / pv i32 at
    bucket capacity, plus an i32[128] length lane with the live length
    at [0].  ``refresh`` ships the new live prefix; the buffers are
    reallocated only when the tier outgrows its capacity bucket.
    """

    def __init__(self, bucketed: bool = True):
        self.bucketed = bucketed
        self.capacity = 0
        self.min_capacity = 0      # preallocation floor (see preallocate)
        self.length = 0
        self.window = 4            # ratcheted pow2 duplicate-run window
        self.pk = self.hi = self.lo = self.pv = self.plen = None
        self.uploads = 0
        self.upload_bytes = 0
        self.repacks = 0

    @property
    def iters(self) -> int:
        """Binary-search rounds covering the capacity bucket (static)."""
        return max(self.capacity, 1).bit_length()

    def _alloc(self, cap: int, pk, hi, lo, pv, n: int) -> None:
        """Fresh +inf-padded buffers at ``cap`` (full repack)."""
        ppk = np.full(cap, np.inf, np.float32)
        ppk[:n] = pk
        phi = np.zeros(cap, np.uint32)
        phi[:n] = hi
        plo = np.zeros(cap, np.uint32)
        plo[:n] = lo
        ppv = np.full(cap, -1, np.int32)
        ppv[:n] = pv
        plen = np.zeros(_LANE, np.int32)
        plen[0] = n
        self.pk, self.hi = jnp.asarray(ppk), jnp.asarray(phi)
        self.lo, self.pv = jnp.asarray(plo), jnp.asarray(ppv)
        self.plen = jnp.asarray(plen)
        self.capacity = cap
        self.repacks += 1
        self.upload_bytes += 4 * cap * 4 + _LANE * 4
        self.uploads += 1

    def refresh(self, pk: np.ndarray, hi: np.ndarray, lo: np.ndarray,
                pv: np.ndarray, window: int) -> None:
        """Adopt a new live tier state (sorted host mirror).

        Within the capacity bucket this is an in-place device prefix
        write; outgrowing the bucket (or ``bucketed=False`` legacy mode)
        reallocates.  The duplicate-run window only ratchets upward so
        the kernel statics stay warm."""
        n = int(pk.shape[0])
        # +1 keeps at least one +inf sentinel row inside the bucket
        need = max(pow2_bucket(n + 1), self.min_capacity)
        if not self.bucketed:
            # legacy per-mutation repack (the pre-§11 behavior, kept for
            # the before/after serving benchmark): exact window, fresh
            # buffers, capacity free to shrink — every drift retraces
            self.window = max(4, int(window))
            self._alloc(need, pk, hi, lo, pv, n)
            self.length = n
            return
        self.window = max(self.window, int(window))
        if self.pk is None or need > self.capacity:
            self._alloc(max(need, self.capacity), pk, hi, lo, pv, n)
            self.length = n
            return
        # in-bucket: overwrite the whole resident bucket (ONE traced
        # shape per capacity — see the ladder note above; the extra
        # bytes are a bounded host->device copy, off the read path).
        # Writing the full bucket also rewrites every row past n to
        # +inf, which the probe depends on: the fixed-round tier binary
        # search reads ppk[n] once converged at l=h=n, and a stale
        # finite key there would push the landing (and its scan window)
        # one slot high.
        m = self.capacity
        ppk = np.full(m, np.inf, np.float32)
        ppk[:n] = pk
        phi = np.zeros(m, np.uint32)
        phi[:n] = hi
        plo = np.zeros(m, np.uint32)
        plo[:n] = lo
        ppv = np.full(m, -1, np.int32)
        ppv[:n] = pv
        self.pk = _write_prefix(self.pk, jnp.asarray(ppk))
        self.hi = _write_prefix(self.hi, jnp.asarray(phi))
        self.lo = _write_prefix(self.lo, jnp.asarray(plo))
        self.pv = _write_prefix(self.pv, jnp.asarray(ppv))
        self.plen = _write_len(self.plen, np.int32(n))
        self.length = n
        self.uploads += 1
        self.upload_bytes += 4 * m * 4


class ServingState:
    """Device-resident serving cache for one ``FlatAFLI`` instance.

    Owns the packed tree pools (rebuilt only at build / fold-swap), the
    two persistent write-tier buffers (run + active delta), and the
    ratcheted static kernel parameters.  ``FlatAFLI`` routes every
    serve-path dispatch through this object; mutations mark the affected
    piece dirty and the next (or an eager) ``refresh`` ships only the
    changed prefix.
    """

    def __init__(self, bucketed: bool = True):
        self.bucketed = bucketed
        self.tree_pools = None          # KernelPools, packed once per swap
        self.run = DeviceTier(bucketed)
        self.delta = DeviceTier(bucketed)
        # rank-ordered scan pool (DESIGN.md §12): the static structure's
        # keys in sorted order, refreshed only at build / fold swap —
        # the fused range-scan kernel's tree-side merge input.  Same
        # persistent bucketed buffer discipline as the write tiers, so
        # steady-state range traffic cannot repack or retrace.
        self.scan = DeviceTier(bucketed)
        # ratcheted statics (upward-only; see module docstring)
        self.max_depth = 4
        self.dense_window = 4
        self.tree_packs = 0             # full tree pool packings
        self.tier_reuses = 0            # tier_pack calls with warm buffers
        self.scan_reuses = 0            # scan_pack calls with warm buffers
        self.ratchet_releases = 0       # release_ratchets calls (§14/§18)
        # streamed-tier router (DESIGN.md §17): resident first-key-per-
        # STREAM_ALIGN-slice vector over the scan pool, rebuilt only
        # when the pool content or capacity bucket moves (both happen
        # off the serve path) — steady-state stream_pack calls reuse it
        self._router = None
        self._router_for = None         # (scan.uploads, scan.capacity)
        self.router_builds = 0
        self.stream_reuses = 0          # stream_pack calls w/ warm router
        self._run_dirty = True
        self._delta_dirty = True

    # ------------------------------------------------------------- tree
    def set_tree(self, arrays, pools=None, *, max_depth: int,
                 dense_window: int) -> None:
        """Adopt a (re)built static structure (DESIGN.md §11
        invalidation points 1 and 2).  ``pools`` may be packed ahead of
        time (the incremental fold packs off the serve path); statics
        ratchet so a shallower new tree cannot retrace."""
        from repro.core.flat_afli import _depth_round, _window_round

        if pools is None:
            pools = arrays.to_kernel_args(bucketed=self.bucketed)
        self.tree_pools = pools
        self.tree_packs += 1
        if self.bucketed:
            self.max_depth = max(self.max_depth, _depth_round(max_depth))
            self.dense_window = max(self.dense_window,
                                    _window_round(dense_window))
        else:  # legacy: exact statics, free to shrink (and retrace)
            self.max_depth = _depth_round(max_depth)
            self.dense_window = _window_round(dense_window)

    def release_ratchets(self, *, max_depth: int, dense_window: int) -> None:
        """Drop the upward-only ratchets to a fresh geometry (DESIGN.md
        §14).  Ratcheting exists because the *distribution is assumed
        stationary* — a deeper probe window is assumed to come back.  A
        re-flow swap breaks that assumption by construction: the new
        transform was accepted precisely because its conflict tail is
        smaller, so carrying the drifted geometry (huge dense windows,
        wide tier scans) forward would spend the win on inert scanning
        forever.  Called ONLY at a structural swap — a §14 re-flow
        re-key, before ``set_tree`` — and counted (``ratchet_releases``)
        so the §18 migration tests can assert the release stays scoped
        to migrated shards: a fresh candidate shard starts from a fresh
        ``ServingState`` (released by construction), and an untouched
        shard's counter must not move.  The next dispatch per shape pays
        one retrace, which is the documented, bounded price of adopting
        the new geometry."""
        from repro.core.flat_afli import _depth_round, _window_round

        self.max_depth = _depth_round(max_depth)
        self.dense_window = _window_round(dense_window)
        for t in (self.run, self.delta, self.scan):
            t.window = 4
        self.ratchet_releases += 1

    def set_scan(self, pk, hi, lo, pv, window: int) -> None:
        """Adopt the (re)built structure's rank-ordered scan pool
        (DESIGN.md §12).  Called only at build / fold swap — off the
        serve path — so range serving finds the pool resident and pays
        nothing."""
        self.scan.refresh(pk, hi, lo, pv, window)

    def scan_pack(self):
        """The resident ``ScanPack`` for ``ops.fused_range_scan``
        (DESIGN.md §12).  Always materializes: before the first build
        the pool rides along empty (lower bounds collapse, every range
        resolves from the write tiers alone)."""
        from repro.kernels.range_scan import ScanPack, ScanPool

        if self.scan.pk is None:
            self.scan.refresh(np.empty(0, np.float32),
                              np.empty(0, np.uint32),
                              np.empty(0, np.uint32),
                              np.empty(0, np.int32), self.scan.window)
        self.scan_reuses += 1
        s = self.scan
        return ScanPack(
            pool=ScanPool(pk=s.pk, hi=s.hi, lo=s.lo, pv=s.pv, plen=s.plen),
            iters=s.iters)

    def stream_pack(self):
        """The streamed-tier dispatch bundle for ``ops.fused_lookup``'s
        HBM-streaming rung (DESIGN.md §17): the rank-ordered scan pool
        (streamed in tiles), its resident router vector, and the pool's
        duplicate-run window.  The router is keyed on the pool's upload
        version + capacity bucket, so it is rebuilt only at build / fold
        swap / bucket growth — the same off-serve-path cadence as the
        pool itself — and every steady-state call reuses the resident
        vector (zero-repack, §11 discipline).  The pool buffers are
        shared with ``scan_pack`` — the streamed tier adds only the
        router's few KiB of device state."""
        from repro.kernels.range_scan import ScanPool
        from repro.kernels.streamed_lookup import StreamPack, build_router

        if self.scan.pk is None:
            self.scan.refresh(np.empty(0, np.float32),
                              np.empty(0, np.uint32),
                              np.empty(0, np.uint32),
                              np.empty(0, np.int32), self.scan.window)
        s = self.scan
        key = (s.uploads, s.capacity)
        if self._router is None or self._router_for != key:
            self._router = build_router(s.pk)
            self._router_for = key
            self.router_builds += 1
        else:
            self.stream_reuses += 1
        return StreamPack(
            pool=ScanPool(pk=s.pk, hi=s.hi, lo=s.lo, pv=s.pv, plen=s.plen),
            router=self._router, window=s.window)

    # ------------------------------------------------------------ tiers
    def preallocate(self, *, delta_floor: int, run_floor: int,
                    scan_floor: int = 0) -> None:
        """Pin tier capacity buckets from the workload's configured
        bounds (delta cap, fold trigger) with headroom, and allocate the
        buffers now.  With capacities fixed up front, the kernel's tier
        block shapes and iteration statics are decided at build time —
        steady-state serving cannot hit a capacity-growth repack (and
        its retrace) no matter how the tier lengths move."""
        if not self.bucketed:
            return
        self.delta.min_capacity = max(self.delta.min_capacity,
                                      pow2_bucket(delta_floor))
        self.run.min_capacity = max(self.run.min_capacity,
                                    pow2_bucket(run_floor))
        if scan_floor:
            self.scan.min_capacity = max(self.scan.min_capacity,
                                         pow2_bucket(scan_floor))
        empty = (np.empty(0, np.float32), np.empty(0, np.uint32),
                 np.empty(0, np.uint32), np.empty(0, np.int32))
        for t in (self.run, self.delta, self.scan):
            if t.capacity < t.min_capacity:
                live = None
                if t.pk is not None and t.length:
                    live = tuple(np.asarray(a)[:t.length]
                                 for a in (t.pk, t.hi, t.lo, t.pv))
                t._alloc(t.min_capacity, *(live or empty),
                         n=t.length if live else 0)

    def reset_tiers(self) -> None:
        """Drop tier contents (new build).  Buffers stay allocated —
        lengths go to zero, capacities and ratchets are retained so the
        next workload starts with a warm jit cache."""
        if self.run.pk is not None:
            self.run.refresh(np.empty(0, np.float32), np.empty(0, np.uint32),
                             np.empty(0, np.uint32), np.empty(0, np.int32),
                             self.run.window)
        else:
            self.run.length = 0
        if self.delta.pk is not None:
            self.delta.refresh(np.empty(0, np.float32),
                               np.empty(0, np.uint32),
                               np.empty(0, np.uint32),
                               np.empty(0, np.int32), self.delta.window)
        else:
            self.delta.length = 0
        self._run_dirty = self._delta_dirty = False

    def mark_run_dirty(self) -> None:
        self._run_dirty = True

    def mark_delta_dirty(self) -> None:
        self._delta_dirty = True

    def refresh_tiers(self, run_mirror, delta_mirror) -> None:
        """Ship dirty tier prefixes to the device.  Mirrors are
        zero-arg thunks returning ``(pk, hi, lo, pv, window)`` of the
        live host state — evaluated only for the dirty tier(s), so a
        delta append never pays the window scan over the (unchanged,
        much larger) run mirror.  Called eagerly from the write path so
        reads never pay it."""
        if not (self._run_dirty or self._delta_dirty):
            return
        with span("afli.tier_sync"):
            if self._run_dirty:
                self.run.refresh(*run_mirror())
                self._run_dirty = False
            if self._delta_dirty:
                self.delta.refresh(*delta_mirror())
                self._delta_dirty = False

    def tier_pack(self):
        """The resident ``TierPack`` for the in-kernel tier probe
        (DESIGN.md §10/§11; ``None`` while both tiers are empty, so the
        probe stage compiles out).  Requires the tiers to be clean —
        ``FlatAFLI`` refreshes on mutation and before dispatch."""
        from repro.kernels.fused_lookup import TierPack, TierPools

        if not (self.run.length or self.delta.length):
            return None
        empty = (np.empty(0, np.float32), np.empty(0, np.uint32),
                 np.empty(0, np.uint32), np.empty(0, np.int32))
        for t in (self.run, self.delta):
            if t.pk is None:  # never-touched tier riding along empty
                t.refresh(*empty, window=t.window)
        self.tier_reuses += 1
        r, d = self.run, self.delta
        return TierPack(
            pools=TierPools(run_pk=r.pk, run_hi=r.hi, run_lo=r.lo,
                            run_pv=r.pv, run_len=r.plen,
                            dl_pk=d.pk, dl_hi=d.hi, dl_lo=d.lo,
                            dl_pv=d.pv, dl_len=d.plen),
            run_iters=r.iters, run_window=r.window,
            delta_iters=d.iters, delta_window=d.window)

    # ----------------------------------------------------- trace lattice
    def trace_signature(self) -> tuple:
        """The *declared* point-lookup trace-cache lattice point
        (DESIGN.md §15): everything the serving discipline (§11) allows
        a kernel retrace to depend on — tree pool buckets, tier
        presence, tier capacity buckets, probe statics, and the
        upward-only ratchets.  Two dispatches whose batch bucket and
        ``trace_signature()`` coincide must hit the same jit cache
        entry; the retrace-budget contract checker
        (``repro.analysis.retrace``) counts distinct declared points
        against the actual cache growth, which is exactly how the PR 5
        per-rung-prefix refresh bug class is caught — a rung crossing
        changes no declared coordinate, so any cache growth it causes
        is a violation."""
        pools = None
        if self.tree_pools is not None:
            pools = tuple((tuple(a.shape), str(a.dtype))
                          for a in self.tree_pools)
        tiers_live = bool(self.run.length or self.delta.length)
        # the scan-pool coordinates are point-lookup coordinates too
        # (§17): a point dispatch that falls off the fused rung serves
        # from the streamed scan pool, whose kernel statics (tile count,
        # router shape, duplicate window) are functions of the capacity
        # bucket + window ratchet — both only move at build/fold swap
        return (pools, tiers_live,
                self.run.capacity, self.run.iters, self.run.window,
                self.delta.capacity, self.delta.iters, self.delta.window,
                self.max_depth, self.dense_window,
                self.scan.capacity, self.scan.window)

    def scan_signature(self) -> tuple:
        """The declared range-scan lattice point: the point signature's
        tier coordinates plus the scan pool's capacity bucket and
        lower-bound statics (§12)."""
        tiers_live = bool(self.run.length or self.delta.length)
        return (tiers_live,
                self.run.capacity, self.run.iters, self.run.window,
                self.delta.capacity, self.delta.iters, self.delta.window,
                self.scan.capacity, self.scan.iters, self.scan.window)

    # ------------------------------------------------------------ stats
    def stats(self) -> dict:
        """Zero-repack telemetry (DESIGN.md §11): pack reuse, prefix
        uploads (count + bytes), full repacks, resident capacities, and
        the ratcheted statics — the counters the serving benchmarks
        assert on instead of inferring compiles from tail latency."""
        return {
            "tree_packs": self.tree_packs,
            "tier_reuses": self.tier_reuses,
            "scan_reuses": self.scan_reuses,
            "tier_uploads": self.run.uploads + self.delta.uploads,
            "tier_upload_bytes": (self.run.upload_bytes
                                  + self.delta.upload_bytes),
            "tier_repacks": (self.run.repacks + self.delta.repacks
                             + self.scan.repacks),
            "scan_uploads": self.scan.uploads,
            "ratchet_releases": self.ratchet_releases,
            "router_builds": self.router_builds,
            "stream_reuses": self.stream_reuses,
            "run_capacity": self.run.capacity,
            "delta_capacity": self.delta.capacity,
            "scan_capacity": self.scan.capacity,
            "static_max_depth": self.max_depth,
            "static_dense_window": self.dense_window,
            "run_window": self.run.window,
            "delta_window": self.delta.window,
            "scan_window": self.scan.window,
        }

    def reset_stats(self) -> None:
        for t in (self.run, self.delta, self.scan):
            t.uploads = t.upload_bytes = t.repacks = 0
        self.tree_packs = 0
        self.tier_reuses = 0
        self.scan_reuses = 0
        self.ratchet_releases = 0
        self.router_builds = 0
        self.stream_reuses = 0
