"""FlatAFLI — TPU-native flattened AFLI (DESIGN.md §3 "hardware adaptation").

The paper's AFLI is a pointer-chasing dynamic tree; TPUs want batched,
statically-shaped, gather-based traversal.  FlatAFLI keeps AFLI's exact
node semantics (model nodes with precise placement, conflict buckets, dense
nodes) but flattens everything into a structure-of-arrays pool:

* traversal is a ``lax.while_loop`` over a *batch* of queries — each round
  resolves one tree level for every outstanding query with vectorized
  gathers (no per-query recursion);
* placement arithmetic is float32 *end-to-end*: the builder computes slots
  with the same f32 ops the probe executes, so predictions are bit-exact on
  device (TPU has no f64 ALU — per DESIGN.md this replaces the paper's
  'double' math);
* key *identity* is exact regardless of f32 collisions: every record carries
  the original 64-bit key as a (hi, lo) uint32 pair compared bitwise;
* updates are log-structured and tiered (DESIGN.md §10, the TPU analog of
  AFLI's buckets-buffer-then-Modelling): batch inserts land in a bounded
  *active delta* that merges into a *compacted sorted run* (two-way merge,
  last-write-wins by 64-bit identity) when full; both tiers are
  device-resident pools probed *inside* the fused lookup kernel, and an
  *incremental fold* (the batched Modelling, split into bounded work
  steps) folds the run back into the static structure without an O(n)
  stall on any single ``insert_batch`` call;
* deletes are TOMBSTONE appends to the delta (DESIGN.md §12) — the
  newest copy of an identity masks every older one on the point and
  range paths, and the fold drops tombstoned identities physically;
* range queries (``scan_batch``) are served by the fused range-scan
  kernel over a *rank-ordered scan pool* (the structure's keys in
  sorted order, §12) merged in-kernel with both write tiers.

The pure-jnp probe here is also the reference oracle for the
``kernels/index_probe`` Pallas kernel, and ``_probe_delta`` is the host
oracle for the in-kernel tier probe.
"""

from __future__ import annotations

import collections
import dataclasses
from functools import partial
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.conflict import fit_linear_model, tail_conflict_degree
from repro.kernels.fused_lookup import (
    TOMBSTONE,
    _pow2ceil,
    merge_tiers,
    positioning_keys,
)
from repro.obs import span

__all__ = ["FlatAFLI", "FlatAFLIConfig", "FlatArrays", "TOMBSTONE"]

EMPTY, DATA, BUCKET, CHILD = 0, 1, 2, 3
KIND_MODEL, KIND_DENSE = 0, 1


def split_key_bits(keys: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """f64 keys -> exact (hi, lo) uint32 identity pair."""
    bits = np.asarray(keys, dtype=np.float64).view(np.uint64)
    return (bits >> np.uint64(32)).astype(np.uint32), (bits & np.uint64(0xFFFFFFFF)).astype(np.uint32)


def _max_equal_run(sorted_vals: np.ndarray) -> int:
    """Longest run of equal values in a sorted array (f32 collision bound)."""
    if sorted_vals.shape[0] == 0:
        return 0
    change = np.flatnonzero(np.diff(sorted_vals) != 0)
    edges = np.concatenate([[-1], change, [sorted_vals.shape[0] - 1]])
    return int(np.diff(edges).max())


def _ids64(hi: np.ndarray, lo: np.ndarray) -> np.ndarray:
    """(hi, lo) u32 identity bits -> u64 identity words."""
    return (hi.astype(np.uint64) << np.uint64(32)) | lo.astype(np.uint64)


def _depth_round(d: int) -> int:
    """Traversal depth bound rounded up to a multiple of 4: the level
    loop exits as soon as every query is done, so a larger static bound
    costs nothing at runtime but keeps rebuild-churned trees (whose
    exact height moves by one) on a handful of compiled kernels."""
    return ((int(d) + 3) // 4) * 4


def _window_round(w: int) -> int:
    """Duplicate-run scan window, rounded up to a power of two so the
    kernel compile count stays bounded.  Scanning further than the exact
    run length is semantically free: the scan matches by exact 64-bit
    identity, so extra positions can only find the one true entry."""
    return max(4, 1 << max(int(w) - 1, 0).bit_length())


def _dedup_newest(pk: np.ndarray, hi: np.ndarray, lo: np.ndarray,
                  pv: np.ndarray):
    """Last-write-wins by 64-bit identity, then stable re-sort by
    positioning key.  Input order is age order (oldest first): the
    stable identity sort keeps it, so ``keep-last`` selects the newest
    copy of every identity."""
    u64 = _ids64(hi, lo)
    order = np.argsort(u64, kind="stable")
    su = u64[order]
    keep = order[np.append(su[1:] != su[:-1], True)]
    pk, hi, lo, pv = pk[keep], hi[keep], lo[keep], pv[keep]
    order = np.argsort(pk, kind="stable")
    return pk[order], hi[order], lo[order], pv[order]


def _tier_window(pk_pool: np.ndarray) -> int:
    """Shared probe-window bound for one sorted tier: the pow2-rounded
    max equal-key run.  Used by BOTH the host probe and the kernel pack
    so the two probes scan the same neighborhood geometry."""
    return _window_round(max(_max_equal_run(pk_pool), 1))


def _probe_sorted_pool(pk_pool: np.ndarray, hi_pool: np.ndarray,
                       lo_pool: np.ndarray, pv_pool: np.ndarray,
                       q: np.ndarray, qhi: np.ndarray,
                       qlo: np.ndarray) -> np.ndarray:
    """Newest matching payload per query from one sorted tier (-1 = miss).

    Host oracle twin of the kernel's ``probe_tier`` with the SAME
    semantics: leftmost binary search locates the equal-key neighborhood,
    then a symmetric window scan ``[j - W, j + 3W)`` resolves by exact
    (hi, lo) identity only — the positioning key is the locator, never
    the matcher, so a query key that drifted 1 ulp from the stored copy
    (the kernel's NF re-materialization hazard) resolves identically on
    both dispatch routes.  Tiers keep insertion order within an
    equal-pkey window (stable sort), so the highest matching index is
    the last write — the NEWEST copy wins.

    Fully vectorized over the query batch: one ``searchsorted`` plus one
    [n_queries, 4*window] identity-compare round (no per-query or
    per-offset Python loop on the ``host_probe`` path)."""
    n = pk_pool.shape[0]
    if not n:
        return np.full(q.shape[0], -1, np.int32)
    window = _tier_window(pk_pool)
    j = np.searchsorted(pk_pool, q, side="left")
    widx = j[:, None] + np.arange(-window, 3 * window)[None, :]
    valid = (widx >= 0) & (widx < n)
    wc = np.clip(widx, 0, n - 1)
    ok = valid & (hi_pool[wc] == qhi[:, None]) & (lo_pool[wc] == qlo[:, None])
    last = np.max(np.where(ok, widx, -1), axis=1)  # highest index = newest
    return np.where(last >= 0, pv_pool[np.clip(last, 0, n - 1)],
                    -1).astype(np.int32)


def _pack_tier(pk: np.ndarray, hi: np.ndarray, lo: np.ndarray,
               pv: np.ndarray):
    """One write tier -> lane-padded device pool + static probe bounds.

    Pads to a power of two with at least one ``+inf`` sentinel row (the
    in-kernel binary search can then never land in live-looking padding)
    and returns ``(jnp arrays, bs_iters, window)``; sizes are
    pow2-rounded so recompiles stay bounded as the tiers grow."""
    n = int(pk.shape[0])
    m = max(128, _pow2ceil(n + 1))
    ppk = np.full(m, np.inf, np.float32)
    ppk[:n] = pk
    phi = np.zeros(m, np.uint32)
    phi[:n] = hi
    plo = np.zeros(m, np.uint32)
    plo[:n] = lo
    ppv = np.full(m, -1, np.int32)
    ppv[:n] = pv
    plen = np.zeros(128, np.int32)
    plen[0] = n
    arrays = (jnp.asarray(ppk), jnp.asarray(phi), jnp.asarray(plo),
              jnp.asarray(ppv), jnp.asarray(plen))
    return arrays, m.bit_length(), _tier_window(pk)


@dataclasses.dataclass(frozen=True)
class FlatAFLIConfig:
    gamma: float = 0.99
    max_bucket: int = 6
    min_bucket: int = 2
    alpha: float = 1.2
    max_depth: int = 16
    dense_search_iters: int = 24      # binary-search rounds (2^24 max dense)
    rebuild_frac: float = 0.25        # run/total ratio triggering the fold
    use_fused_kernel: bool = True     # serve via kernels/fused_lookup
    use_streamed_kernel: bool = True  # §17 HBM-streaming rung when the
                                      # fused pools outgrow the budget
    vmem_budget: Optional[int] = None  # pool-bytes cap; None -> backend default
    delta_cap: int = 4096             # active-delta bound before run merge
    fold_step_keys: int = 4096        # incremental-fold work unit (keys)
    fold_work_factor: float = 8.0     # fold work per insert call, x batch
    bucketed_serving: bool = True     # §11 persistent shape-bucketed pools
                                      # (False = legacy per-mutation repack)
    scan_cap: int = 128               # §12 range-scan output lanes per
                                      # query (= per-query candidate-work
                                      # bound; totals report truncation)


class FlatArrays(NamedTuple):
    """Device-resident structure-of-arrays (all jnp)."""

    node_kind: jnp.ndarray        # u8[N]   model / dense
    node_slope: jnp.ndarray       # f32[N]
    node_intercept: jnp.ndarray   # f32[N]
    node_offset: jnp.ndarray      # i32[N]  start into entry pool
    node_size: jnp.ndarray        # i32[N]
    etype: jnp.ndarray            # u8[P]
    ekey: jnp.ndarray             # f32[P]  positioning key of DATA entries
    ehi: jnp.ndarray              # u32[P]  identity bits
    elo: jnp.ndarray              # u32[P]
    epayload: jnp.ndarray         # i32[P]
    echild: jnp.ndarray           # i32[P]  bucket id / child node id
    bkey: jnp.ndarray             # f32[B, cap]
    bhi: jnp.ndarray              # u32[B, cap]
    blo: jnp.ndarray              # u32[B, cap]
    bpayload: jnp.ndarray         # i32[B, cap]
    blen: jnp.ndarray             # i32[B]

    def to_kernel_args(self, lane: int = 128, bucketed: bool = False):
        """Pack the pools for ``kernels/fused_lookup``: u8 type codes cast
        to i32 and every pool's leading dim padded to a lane multiple
        (padding is never addressed — all traversal indices stay in the
        built range).  Bucket arrays stay [B, cap] so the in-kernel scan
        is one row gather per level, as in the oracle.

        ``bucketed=True`` pads each leading dim up to a power-of-two
        bucket instead of the exact lane multiple, so a fold swap whose
        pool sizes drift within the bucket keeps the traced kernel
        shapes — the serving jit cache stays warm across rebuilds
        (DESIGN.md §11).  Padding is zero-filled: etype 0 is EMPTY and
        padded nodes/buckets are never addressed."""
        from repro.kernels.fused_lookup import KernelPools

        def pad1(x):
            x = np.asarray(x)
            n = x.shape[0]
            m = ((n + lane - 1) // lane) * lane
            if bucketed:
                m = max(lane, _pow2ceil(m))
            if m != n:
                pad = [(0, m - n)] + [(0, 0)] * (x.ndim - 1)
                x = np.pad(x, pad)
            return jnp.asarray(x)

        return KernelPools(
            node_kind=pad1(np.asarray(self.node_kind).astype(np.int32)),
            node_slope=pad1(self.node_slope),
            node_intercept=pad1(self.node_intercept),
            node_offset=pad1(self.node_offset),
            node_size=pad1(self.node_size),
            etype=pad1(np.asarray(self.etype).astype(np.int32)),
            ekey=pad1(self.ekey),
            ehi=pad1(self.ehi),
            elo=pad1(self.elo),
            epayload=pad1(self.epayload),
            echild=pad1(self.echild),
            bhi=pad1(self.bhi),
            blo=pad1(self.blo),
            bpayload=pad1(self.bpayload),
            blen=pad1(self.blen),
        )


class _Builder:
    """Host-side flattening of Alg 3.2 with f32 placement arithmetic."""

    def __init__(self, cfg: FlatAFLIConfig, d_tail: int):
        self.cfg = cfg
        self.d_tail = d_tail
        self.node_kind, self.node_slope, self.node_intercept = [], [], []
        self.node_offset, self.node_size = [], []
        self.etype, self.ekey, self.ehi, self.elo = [], [], [], []
        self.epayload, self.echild = [], []
        self.buckets = []
        self.max_depth = 1

    def _alloc_node(self, kind, slope, intercept, size):
        nid = len(self.node_kind)
        self.node_kind.append(kind)
        self.node_slope.append(np.float32(slope))
        self.node_intercept.append(np.float32(intercept))
        self.node_offset.append(len(self.etype))
        self.node_size.append(size)
        self.etype.extend([EMPTY] * size)
        self.ekey.extend([np.float32(0)] * size)
        self.ehi.extend([0] * size)
        self.elo.extend([0] * size)
        self.epayload.extend([0] * size)
        self.echild.extend([-1] * size)
        return nid

    def build(self, pk: np.ndarray, hi: np.ndarray, lo: np.ndarray,
              pv: np.ndarray, depth: int = 1, defer=None,
              key_base: int = 0) -> int:
        """Returns node id.  pk is f32, sorted.

        ``defer`` (an ``_IncrementalFold``) bounds the synchronous work:
        child subtrees and dense fills are enqueued as fold work items
        (identified by absolute key ranges via ``key_base``) instead of
        being built inline once ``defer.should_defer`` says the step
        budget is spent — inline leaf placements report their cost via
        ``defer.charge`` — so no single call pays more than one bounded
        partition pass plus ~``fold_step_keys`` of leaf building."""
        cfg = self.cfg
        n = pk.shape[0]
        self.max_depth = max(self.max_depth, depth)
        model = fit_linear_model(pk.astype(np.float64),
                                 np.arange(n, dtype=np.float64) * cfg.alpha)
        degenerate = model.slope <= 0.0 or n < 2
        if not degenerate:
            s32 = np.float32(model.slope)
            b32 = np.float32(model.intercept)
            # f32 slope*key can overflow for extreme key magnitudes; treat
            # non-finite predictions as a degenerate fit (dense fallback)
            raw = np.rint(s32 * pk + b32)
            if not np.isfinite(raw).all():
                degenerate = True
            else:
                pred = raw.astype(np.int64)
                first, last = int(pred[0]), int(pred[-1])
                degenerate = last == first
        if degenerate or depth >= cfg.max_depth:
            # dense node: sorted compact slice, probed by binary search
            nid = self._alloc_node(KIND_DENSE, 0.0, 0.0, n)
            off = self.node_offset[nid]
            if defer is not None and defer.should_defer(n):
                defer.defer_dense(off, key_base, key_base + n)
                return nid
            for i in range(n):
                self.etype[off + i] = DATA
                self.ekey[off + i] = pk[i]
                self.ehi[off + i] = int(hi[i])
                self.elo[off + i] = int(lo[i])
                self.epayload[off + i] = int(pv[i])
            if defer is not None:
                defer.charge(n)
            return nid
        size = min(max(int(np.floor(n * cfg.alpha)), 2), last - first + 1)
        # compress into [0, size) in f32, then recompute with f32 math
        scale = np.float32((size - 1) / (last - first))
        s32c = np.float32(s32 * scale)
        b32c = np.float32((np.float32(b32) - np.float32(first)) * scale)
        pred = np.clip(np.rint(s32c * pk + b32c).astype(np.int64), 0, size - 1)
        pred = np.maximum.accumulate(pred)  # guard monotonicity under f32
        nid = self._alloc_node(KIND_MODEL, s32c, b32c, size)
        off = self.node_offset[nid]
        slots, counts = np.unique(pred, return_counts=True)
        i = 0
        s = 0
        while s < slots.shape[0]:
            slot = int(slots[s])
            d = int(counts[s])
            e = off + slot
            if d == 1:
                self.etype[e] = DATA
                self.ekey[e] = pk[i]
                self.ehi[e] = int(hi[i])
                self.elo[e] = int(lo[i])
                self.epayload[e] = int(pv[i])
                i += 1
                s += 1
            elif d < self.d_tail:
                bid = len(self.buckets)
                self.buckets.append((pk[i:i + d].copy(), hi[i:i + d].copy(),
                                     lo[i:i + d].copy(), pv[i:i + d].copy()))
                self.etype[e] = BUCKET
                self.echild[e] = bid
                i += d
                s += 1
            else:
                run_end = s + 1
                total = d
                while (run_end < slots.shape[0]
                       and int(slots[run_end]) == int(slots[run_end - 1]) + 1
                       and int(counts[run_end]) >= self.d_tail):
                    total += int(counts[run_end])
                    run_end += 1
                last_slot = int(slots[run_end - 1])
                if total == n:
                    child = self._alloc_dense(pk[i:i + total], hi[i:i + total],
                                              lo[i:i + total], pv[i:i + total],
                                              defer, key_base + i)
                elif defer is not None and defer.should_defer(total):
                    # bounded-step fold: the subtree is built by a later
                    # work item, which patches these CHILD entries
                    child = -1
                    defer.defer_subtree(off + slot, off + last_slot,
                                        key_base + i, key_base + i + total,
                                        depth + 1)
                else:
                    child = self.build(pk[i:i + total], hi[i:i + total],
                                       lo[i:i + total], pv[i:i + total],
                                       depth + 1, defer, key_base + i)
                for p in range(slot, last_slot + 1):
                    ee = off + p
                    self.etype[ee] = CHILD
                    self.echild[ee] = child
                i += total
                s = run_end
        return nid

    def _alloc_dense(self, pk, hi, lo, pv, defer=None, key_base: int = 0) -> int:
        nid = self._alloc_node(KIND_DENSE, 0.0, 0.0, pk.shape[0])
        off = self.node_offset[nid]
        if defer is not None and defer.should_defer(pk.shape[0]):
            defer.defer_dense(off, key_base, key_base + pk.shape[0])
            return nid
        for i in range(pk.shape[0]):
            self.etype[off + i] = DATA
            self.ekey[off + i] = pk[i]
            self.ehi[off + i] = int(hi[i])
            self.elo[off + i] = int(lo[i])
            self.epayload[off + i] = int(pv[i])
        if defer is not None:
            defer.charge(pk.shape[0])
        return nid

    def fill_dense(self, off: int, pk, hi, lo, pv) -> None:
        """Deferred dense fill: one bounded chunk of DATA entries."""
        for i in range(pk.shape[0]):
            self.etype[off + i] = DATA
            self.ekey[off + i] = pk[i]
            self.ehi[off + i] = int(hi[i])
            self.elo[off + i] = int(lo[i])
            self.epayload[off + i] = int(pv[i])

    def finalize(self) -> FlatArrays:
        cap = self.cfg.max_bucket
        nb = max(len(self.buckets), 1)
        bkey = np.zeros((nb, cap), np.float32)
        bhi = np.zeros((nb, cap), np.uint32)
        blo = np.zeros((nb, cap), np.uint32)
        bpv = np.zeros((nb, cap), np.int32)
        blen = np.zeros((nb,), np.int32)
        for i, (k, h, l, v) in enumerate(self.buckets):
            m = k.shape[0]
            bkey[i, :m] = k
            bhi[i, :m] = h
            blo[i, :m] = l
            bpv[i, :m] = v
            blen[i] = m
        return FlatArrays(
            node_kind=jnp.asarray(np.asarray(self.node_kind, np.uint8)),
            node_slope=jnp.asarray(np.asarray(self.node_slope, np.float32)),
            node_intercept=jnp.asarray(np.asarray(self.node_intercept, np.float32)),
            node_offset=jnp.asarray(np.asarray(self.node_offset, np.int32)),
            node_size=jnp.asarray(np.asarray(self.node_size, np.int32)),
            etype=jnp.asarray(np.asarray(self.etype, np.uint8)),
            ekey=jnp.asarray(np.asarray(self.ekey, np.float32)),
            ehi=jnp.asarray(np.asarray(self.ehi, np.uint32)),
            elo=jnp.asarray(np.asarray(self.elo, np.uint32)),
            epayload=jnp.asarray(np.asarray(self.epayload, np.int32)),
            echild=jnp.asarray(np.asarray(self.echild, np.int32)),
            bkey=jnp.asarray(bkey), bhi=jnp.asarray(bhi), blo=jnp.asarray(blo),
            bpayload=jnp.asarray(bpv), blen=jnp.asarray(blen),
        )


class _IncrementalFold:
    """Bounded-step rebuild (DESIGN.md §10).

    The batched Modelling, split into work items processed under a
    per-call key budget so no single ``insert_batch`` pays the full O(n)
    reorganization stall:

    1. ``root``    — one partition pass over the snapshot (the frozen
       write tiers merged into the static entries, last-write-wins by
       identity); child subtrees / dense fills larger than
       ``fold_step_keys`` are *deferred* as further items;
    2. ``subtree`` / ``dense`` — bounded child builds that patch their
       parent CHILD entries when done;
    3. ``finalize`` — pool flattening + kernel packing;
    4. ``verify`` (and ``verify_flow`` when a flow serve context is set)
       — chunked device-verified placement (§8) against the *new* arrays;
       divergent keys are collected as shadows.

    The old structure plus the frozen tiers keep serving throughout; when
    the queue drains the new structure swaps in atomically, the consumed
    run tier is replaced by the collected shadows, and the active delta
    (which only grew during the fold, so its entries stay newest) carries
    over untouched.
    """

    def __init__(self, idx: "FlatAFLI", pk, hi, lo, pv, reflow=None):
        self.idx = idx
        self.pk, self.hi, self.lo, self.pv = pk, hi, lo, pv
        self.n = int(pk.shape[0])
        self.step = max(int(idx.cfg.fold_step_keys), 1)
        # re-flow fold (DESIGN.md §14): ``reflow = (transform_fn,
        # serve_flow, on_swap)`` — the snapshot arrives already re-keyed
        # under the CANDIDATE transform, so the candidate structure must
        # be verified against the candidate's serve context, not the
        # (still live) old one, and the swap installs the new transform
        # atomically with the new arrays.
        self.reflow = reflow
        self.autoswitch_new = None  # §14: fresh verdict installed at swap
        self.serve_flow_target = (reflow[1] if reflow is not None
                                  else idx._serve_flow)
        self.builder = _Builder(idx.cfg, idx.d_tail)
        self.build_items = collections.deque()
        self.post_items = collections.deque()
        self.phase = "root"
        self.arrays_new: Optional[FlatArrays] = None
        self.pools_new = None
        self.max_depth_new = 1
        self.dense_window_new = 8
        self.shadow = []  # [(pk, hi, lo, pv)] chunks for the new run tier
        self._tick_used = 0  # inline leaf work charged by the current item

    # ---- defer hooks (called from _Builder.build)
    def charge(self, n) -> None:
        """Inline leaf work performed by the current item (keys placed)."""
        self._tick_used += int(n)

    def should_defer(self, total) -> bool:
        """True once building ``total`` more keys inline would blow the
        per-item step budget — the run is enqueued as its own item
        instead, so item costs stay ~``fold_step_keys`` even when a
        partition consists entirely of small child runs."""
        return (total > self.step
                or self._tick_used + total > self.step)

    def defer_subtree(self, e_lo, e_hi, k_lo, k_hi, depth):
        self.build_items.append(("subtree", e_lo, e_hi, k_lo, k_hi, depth))

    def defer_dense(self, off, k_lo, k_hi):
        for s in range(k_lo, k_hi, self.step):
            self.build_items.append(
                ("dense", off + (s - k_lo), s, min(s + self.step, k_hi)))

    # ---- work loop
    def tick(self, budget: int) -> bool:
        """Process queued work under ``budget`` (in keys; at least one
        item per call).  Returns True once the new structure is live."""
        while budget > 0:
            if self.phase == "root":
                self._tick_used = 0
                self.builder.build(self.pk, self.hi, self.lo, self.pv,
                                   depth=1, defer=self)
                self.phase = "build"
                # inline leaf work + the O(#slots) partition scan
                budget -= max(self._tick_used, self.n // 16, 1)
            elif self.phase == "build":
                if not self.build_items:
                    self.phase = "finalize"
                    continue
                item = self.build_items.popleft()
                self._tick_used = 0
                budget -= self._build_item(item)
            elif self.phase == "finalize":
                budget -= self._finalize()
                self.phase = "verify"
            elif self.phase == "verify":
                if not self.post_items:
                    self._swap()
                    return True
                kind, k_lo, k_hi = self.post_items.popleft()
                if kind == "verify":
                    self._verify_chunk(k_lo, k_hi)
                else:
                    self._verify_flow_chunk(k_lo, k_hi)
                budget -= max(k_hi - k_lo, 1)
        return False

    def _build_item(self, item) -> int:
        b = self.builder
        if item[0] == "subtree":
            _, e_lo, e_hi, k_lo, k_hi, depth = item
            child = b.build(self.pk[k_lo:k_hi], self.hi[k_lo:k_hi],
                            self.lo[k_lo:k_hi], self.pv[k_lo:k_hi],
                            depth, defer=self, key_base=k_lo)
            for p in range(e_lo, e_hi + 1):
                b.echild[p] = child
            # the item may have deferred most of its range onward; charge
            # the inline leaf work plus its own partition scan
            return max(self._tick_used, (k_hi - k_lo) // 16, 1)
        _, off, k_lo, k_hi = item
        b.fill_dense(off, self.pk[k_lo:k_hi], self.hi[k_lo:k_hi],
                     self.lo[k_lo:k_hi], self.pv[k_lo:k_hi])
        return max(k_hi - k_lo, 1)

    def _finalize(self) -> int:
        self.arrays_new = self.builder.finalize()
        self.pools_new = self.arrays_new.to_kernel_args(
            bucketed=self.idx._serving.bucketed)
        self.max_depth_new = self.builder.max_depth + 1
        self.dense_window_new = _max_equal_run(self.pk) + 2
        for kind in (("verify",)
                     + (("verify_flow",) if self.serve_flow_target is not None
                        else ())):
            for s in range(0, self.n, self.step):
                # uniform chunk shapes: the final ragged chunk is slid
                # back to a full step (re-verifying overlap keys is
                # idempotent), so every fold's verify dispatches reuse
                # ONE traced kernel shape instead of minting a new
                # ragged-tail shape per fold (§11 zero-retrace serving)
                lo = min(s, max(self.n - self.step, 0))
                self.post_items.append((kind, lo, min(lo + self.step,
                                                      self.n)))
        return max(self.n // 4, 1)

    def _lookup_kwargs(self):
        """Dispatch overrides for the candidate structure.  The depth /
        window statics ratchet against the serving cache (§11): a fold
        whose tree is shallower or narrower than anything served so far
        reuses the warm verify shapes instead of minting a fresh trace —
        scanning or looping further than the new tree needs is
        semantically free, exactly as on the serve path."""
        sv = self.idx._serving
        depth = _depth_round(self.max_depth_new)
        window = _window_round(self.dense_window_new)
        if sv.bucketed:
            depth = max(sv.max_depth, depth)
            window = max(sv.dense_window, window)
        return dict(arrays=self.arrays_new, pools=self.pools_new,
                    max_depth=depth, dense_window=window, tiers=False)

    def _verify_chunk(self, k_lo, k_hi) -> None:
        """§8 device-verified placement, tree-only: tiers are excluded so
        a during-fold insert for the same identity cannot be mistaken for
        a placement divergence (its newer payload must keep winning)."""
        pk = self.pk[k_lo:k_hi]
        hi, lo = self.hi[k_lo:k_hi], self.lo[k_lo:k_hi]
        pv = self.pv[k_lo:k_hi]
        res = self.idx._device_lookup(pk, hi, lo, **self._lookup_kwargs())
        wrong = res != pv
        if wrong.any():
            self.shadow.append((pk[wrong], hi[wrong], lo[wrong], pv[wrong]))

    def _verify_flow_chunk(self, k_lo, k_hi) -> None:
        """§8 extended to the fused serve path: identity keys are
        reconstructed from the stored (hi, lo) bit pools and re-run
        through the in-kernel NF, so keys that diverge only under the
        serve-path transform keep their shadow across folds."""
        from repro.core.feature import expand_features

        normalizer, flow_cfg, packed_w, shapes = self.serve_flow_target
        hi, lo = self.hi[k_lo:k_hi], self.lo[k_lo:k_hi]
        pv = self.pv[k_lo:k_hi]
        ik64 = _ids64(hi, lo).view(np.float64)
        feats = expand_features(ik64, normalizer, flow_cfg.dim,
                                flow_cfg.theta, dtype=np.float32)
        res, z = self.idx._flow_device_lookup(feats, hi, lo, packed_w,
                                              shapes, **self._lookup_kwargs())
        wrong = res != pv
        if wrong.any():
            self.shadow.append((z[wrong].astype(np.float32), hi[wrong],
                                lo[wrong], pv[wrong]))

    def _swap(self) -> None:
        idx = self.idx
        idx.arrays = self.arrays_new
        idx.max_depth = self.max_depth_new
        idx.dense_window = self.dense_window_new
        if self.reflow is not None:
            transform_fn, serve_flow, _on_swap = self.reflow
            # drop the upward-only ratchets to the candidate's geometry
            # FIRST (§14): the drifted windows were the reason to
            # re-flow, and the new transform was accepted because it
            # does not need them — one retrace per shape is the price of
            # adoption.  Every refresh below re-ratchets from this base
            # to whatever the re-keyed data actually requires.
            idx._serving.release_ratchets(max_depth=self.max_depth_new,
                                          dense_window=self.dense_window_new)
            # inserts that landed while the fold ran carry OLD-transform
            # positioning keys; re-key them by identity so delta, run,
            # and tree all speak the new z-space from the same instant
            idx._rekey_delta(transform_fn)
            idx._serve_flow = serve_flow
            if self.autoswitch_new is not None:
                # the build-time verdict describes the OLD transform;
                # replace it with the candidate's (computed over the
                # re-keyed snapshot in start_reflow)
                idx.autoswitch = dict(self.autoswitch_new)
        # atomic serving swap: the pools were packed off the serve path
        # at finalize; statics ratchet inside the serving cache so the
        # warm jit entries survive the swap (§11)
        idx._serving.set_tree(self.arrays_new, self.pools_new,
                              max_depth=self.max_depth_new,
                              dense_window=self.dense_window_new)
        # the rank-ordered scan pool swaps with the tree it mirrors
        # (§12): the fold snapshot IS the new structure's keys in sorted
        # order, tombstones already dropped
        idx._set_scan_mirror(self.pk, self.hi, self.lo,
                             self.pv.astype(np.int32))
        # the frozen run was consumed by the snapshot; placement shadows
        # seed the new run tier (below the active delta, so newer inserts
        # for the same identity still win)
        if self.shadow:
            pk = np.concatenate([s[0] for s in self.shadow])
            hi = np.concatenate([s[1] for s in self.shadow])
            lo = np.concatenate([s[2] for s in self.shadow])
            pv = np.concatenate([s[3] for s in self.shadow])
            order = np.argsort(pk, kind="stable")
            idx._run_pk, idx._run_hi = pk[order], hi[order]
            idx._run_lo, idx._run_pv = lo[order], pv[order].astype(np.int32)
        else:
            idx._run_pk = np.empty(0, np.float32)
            idx._run_hi = np.empty(0, np.uint32)
            idx._run_lo = np.empty(0, np.uint32)
            idx._run_pv = np.empty(0, np.int32)
        idx._serving.mark_run_dirty()
        idx._sync_tiers()
        idx._preallocate_tiers(self.n)  # n grew: ratchet capacity floors
        idx.n_rebuilds += 1
        idx._fold = None
        if self.reflow is not None:
            idx.n_reflows += 1
            self.reflow[2]()  # on_swap: owner bookkeeping, strictly last


@partial(jax.jit, static_argnames=("max_depth", "dense_iters", "bucket_cap",
                                   "dense_window"))
def flat_lookup(arrays: FlatArrays, qkey: jnp.ndarray, qhi: jnp.ndarray,
                qlo: jnp.ndarray, max_depth: int, dense_iters: int,
                bucket_cap: int, dense_window: int = 8) -> jnp.ndarray:
    """Batched traversal over the flattened pools, pure jnp (DESIGN.md
    §3).  Returns payload (i32) or -1.  ``arrays`` is a ``FlatArrays``
    or its bucketed ``KernelPools`` twin (same fields, padding never
    addressed).

    This is the executable specification for the fused kernel's
    traversal stage (§9): ``kernels/fused_lookup`` must stay
    bit-identical to it on every input, and ``ops.fused_lookup`` falls
    back to it when the pools exceed the VMEM budget.  One
    ``lax.while_loop`` round resolves one tree level for the whole
    query batch (model-node FMA slot prediction, dense-node
    fixed-iteration binary search, conflict-bucket scan), early-exiting
    once every query is done."""

    nq = qkey.shape[0]

    def body(state):
        node, result, done, depth = state
        kind = arrays.node_kind[node]
        slope = arrays.node_slope[node]
        intercept = arrays.node_intercept[node]
        offset = arrays.node_offset[node]
        size = arrays.node_size[node]

        # ---- model-node path: precise predicted slot
        slot = jnp.clip(
            jnp.rint(slope * qkey + intercept).astype(jnp.int32), 0, size - 1
        )
        e_model = offset + slot

        # ---- dense-node path: fixed-iteration binary search by ekey
        lo_b = offset
        hi_b = offset + size

        def bs_body(_, lh):
            l, h = lh
            mid = (l + h) // 2
            v = arrays.ekey[mid]
            go_right = v < qkey
            return (jnp.where(go_right, mid + 1, l), jnp.where(go_right, h, mid))

        l_fin, _ = jax.lax.fori_loop(0, dense_iters, bs_body, (lo_b, hi_b))
        e_dense = jnp.clip(l_fin, offset, offset + size - 1)

        e = jnp.where(kind == KIND_MODEL, e_model, e_dense)
        et = arrays.etype[e]
        # dense hit requires key match at the binary-search landing
        is_dense = kind == KIND_DENSE

        hit_data = (et == DATA) & (arrays.ehi[e] == qhi) & (arrays.elo[e] == qlo)
        # dense duplicates of an f32 pkey: scan forward over the duplicate
        # run (bounded by the build-time max duplicate run length)
        def dense_scan(ei):
            def scan_body(w, acc):
                idx = jnp.clip(ei + w, offset, offset + size - 1)
                ok = (arrays.ekey[idx] == qkey) & (arrays.ehi[idx] == qhi) & (arrays.elo[idx] == qlo)
                return jnp.where(ok & (acc < 0), arrays.epayload[idx], acc)
            acc = jnp.full_like(ei, -1, dtype=jnp.int32)
            return jax.lax.fori_loop(0, dense_window, scan_body, acc)

        dense_payload = dense_scan(e_dense)

        # bucket scan (vectorized over the fixed capacity)
        bid = jnp.maximum(arrays.echild[e], 0)
        brow_hi = arrays.bhi[bid]          # [nq, cap]
        brow_lo = arrays.blo[bid]
        brow_pv = arrays.bpayload[bid]
        match = (brow_hi == qhi[:, None]) & (brow_lo == qlo[:, None]) & (
            jnp.arange(bucket_cap)[None, :] < arrays.blen[bid][:, None]
        )
        bucket_payload = jnp.max(jnp.where(match, brow_pv, -1), axis=-1)

        model_payload = jnp.where(
            hit_data, arrays.epayload[e],
            jnp.where(et == BUCKET, bucket_payload, -1),
        )
        new_result = jnp.where(
            done, result, jnp.where(is_dense, dense_payload, model_payload)
        )
        goes_deeper = (~is_dense) & (et == CHILD) & (~done)
        new_node = jnp.where(goes_deeper, arrays.echild[e], node)
        new_done = done | ~goes_deeper
        return new_node, new_result, new_done, depth + 1

    def cond(state):
        _, _, done, depth = state
        return (~jnp.all(done)) & (depth < max_depth)

    node0 = jnp.zeros((nq,), jnp.int32)
    result0 = jnp.full((nq,), -1, jnp.int32)
    done0 = jnp.zeros((nq,), bool)
    _, result, _, _ = jax.lax.while_loop(cond, body, (node0, result0, done0, 0))
    return result


@partial(jax.jit, static_argnames=(
    "dim", "shapes", "max_depth", "dense_iters", "bucket_cap",
    "dense_window", "use_flow", "interpret", "probe_tiers", "run_iters",
    "run_window", "delta_iters", "delta_window"))
def xla_lookup(pools, feats: jnp.ndarray, qhi: jnp.ndarray,
               qlo: jnp.ndarray, packed_w: jnp.ndarray, tiers=None, *,
               dim: int, shapes=(), max_depth: int, dense_iters: int,
               bucket_cap: int, dense_window: int = 8,
               use_flow: bool = True, interpret: bool = False,
               probe_tiers: bool = False, run_iters: int = 1,
               run_window: int = 4, delta_iters: int = 1,
               delta_window: int = 4):
    """The point route of a compiled TPU backend (DESIGN.md §2): the
    NF forward (``nf_forward_pallas``, compiled by Mosaic), the
    ``flat_lookup`` traversal and the write-tier merge
    (``fused_lookup.merge_tiers``) as ONE jitted dispatch over the
    device-resident bucketed pools.  Arguments as
    ``fused_lookup_pallas`` (``interpret`` applies to the NF kernel
    alone); returns ``(payload i32[B] or -1, positioning key f32[B])``,
    bit-identical to the fused kernel, whose traversal mirrors
    ``flat_lookup`` op for op."""
    z = positioning_keys(feats, packed_w, shapes, dim, use_flow, interpret)
    res = flat_lookup(pools, z, qhi, qlo, max_depth=max_depth,
                      dense_iters=dense_iters, bucket_cap=bucket_cap,
                      dense_window=dense_window)
    if probe_tiers and tiers is not None:
        res = merge_tiers(res, z, qhi, qlo, tiers, run_iters=run_iters,
                          run_window=run_window, delta_iters=delta_iters,
                          delta_window=delta_window)
    return res, z


class FlatAFLI:
    """Static flat index + tiered log-structured write path (§10)."""

    def __init__(self, cfg: FlatAFLIConfig | None = None):
        from repro.core.serving_state import ServingState

        self.cfg = cfg or FlatAFLIConfig()
        self.arrays: Optional[FlatArrays] = None
        # persistent device-resident serving cache (DESIGN.md §11): tree
        # pools packed once per build/fold-swap, bucketed tier buffers,
        # ratcheted static kernel params
        self._serving = ServingState(bucketed=self.cfg.bucketed_serving)
        self.last_dispatch = {}        # ops.fused_lookup info of last probe
        self.max_depth = 1
        self.d_tail = self.cfg.min_bucket
        self.n_keys = 0
        # write tiers (host mirrors, sorted by pkey f32; the device twins
        # live in the ServingState) — newest first: delta > compacted run
        self._fold: Optional[_IncrementalFold] = None
        self._reset_tiers()
        self._id_set = set()           # u64 identities currently indexed
        self._serve_flow = None        # (normalizer, flow_cfg, packed_w, shapes)
        self.n_rebuilds = 0
        self.n_reflows = 0             # re-key folds completed (§14)
        # sharded re-flow freeze (§14): while the parent coordinates a
        # cross-shard re-key, this shard's writes must stay buffered in
        # the tiers — starting a local fold would consume entries the
        # parent snapshotted (double-apply at swap)
        self._tier_hold = False
        # build-time switching decision for THIS index's keyset (§13
        # parity: each shard's sub-distribution judges the flow itself)
        self.autoswitch = {"use_flow": None, "tail_original": 0,
                           "tail_transformed": 0}
        self.n_host_tier_probes = 0    # host _probe_delta fallbacks taken
        self.n_host_scans = 0          # host _range_scan_host fallbacks
        self.last_scan_dispatch = {}   # ops.fused_range_scan info
        self._reset_scan_mirror()

    @staticmethod
    def _check_payloads(pv: np.ndarray) -> None:
        """Payloads must be non-negative: -1 is the miss sentinel and -2
        the TOMBSTONE (§12) — a negative payload entering the write path
        would silently act as a miss/delete while the identity
        bookkeeping (``n_keys``/``contains_batch``) counts it live."""
        if pv.shape[0] and int(pv.min()) < 0:
            raise ValueError(
                "payloads must be >= 0 (-1/-2 are reserved sentinels); "
                f"got min={int(pv.min())}")

    # -------------------------------------------------------------- build
    def build(self, pkeys: np.ndarray, payloads: np.ndarray,
              ikeys: np.ndarray | None = None) -> None:
        """Bulk build from *positioning* keys (DESIGN.md §3/§8): sort,
        fit the conflict-aware flattened tree with f32 placement
        arithmetic, pack the pools once into the serving cache (§11),
        adopt the sorted snapshot as the range path's scan pool (§12),
        preallocate the write-tier capacity buckets, and device-verify
        every key's placement (§8 — divergent keys are shadowed).

        ``ikeys`` carries the raw 64-bit identity keys when ``pkeys``
        are flow-transformed; identity defaults to the positioning key
        bits otherwise."""
        pk64 = np.asarray(pkeys, dtype=np.float64)
        ik64 = pk64 if ikeys is None else np.asarray(ikeys, dtype=np.float64)
        pv = np.asarray(payloads, dtype=np.int64)
        self._check_payloads(pv)
        order = np.argsort(pk64, kind="stable")
        pk64, ik64, pv = pk64[order], ik64[order], pv[order]
        pk32 = pk64.astype(np.float32)
        # f32 can reorder near-equal keys; re-sort by (pk32, ik-bits) stably
        order2 = np.argsort(pk32, kind="stable")
        pk32, ik64, pv = pk32[order2], ik64[order2], pv[order2]
        hi, lo = split_key_bits(ik64)

        model = fit_linear_model(pk32.astype(np.float64))
        if pk32.shape[0] >= 2 and model.slope > 0:
            from repro.core.conflict import conflict_degrees
            d = tail_conflict_degree(conflict_degrees(pk32.astype(np.float64), model),
                                     self.cfg.gamma)
        else:
            d = self.cfg.max_bucket
        # per-index AutoSwitch verdict (§13/§14): would THIS keyset keep
        # the transform its positioning keys came through?  With ikeys
        # given (flow on upstream), compare the identity-key tail to the
        # positioning-key tail; identity positioning trivially ties.
        if ikeys is not None:
            from repro.core.conflict import should_use_flow
            use, t_orig, t_flow = should_use_flow(ik64, pk32, self.cfg.gamma)
            self.autoswitch = {"use_flow": bool(use),
                               "tail_original": int(t_orig),
                               "tail_transformed": int(t_flow)}
        else:
            self.autoswitch = {"use_flow": False, "tail_original": int(d),
                               "tail_transformed": int(d)}
        self.d_tail = int(np.clip(d, self.cfg.min_bucket, self.cfg.max_bucket))

        builder = _Builder(self.cfg, self.d_tail)
        builder.build(pk32, hi, lo, pv.astype(np.int64))
        self.arrays = builder.finalize()
        self.max_depth = builder.max_depth + 1
        self.dense_window = _max_equal_run(pk32) + 2
        # pack ONCE into the serving cache; every serve call reuses the
        # device-resident pools until the next build / fold swap (§11)
        self._serving.set_tree(self.arrays, max_depth=self.max_depth,
                               dense_window=self.dense_window)
        self._reset_tiers()
        self._preallocate_tiers(pk32.shape[0])
        # the rank-ordered scan pool mirrors the built structure (§12):
        # the build input is already the sorted snapshot
        self._set_scan_mirror(pk32, hi, lo, pv.astype(np.int32))
        self._id_set = set(_ids64(hi, lo).tolist())
        self.n_keys = len(self._id_set)
        self._self_verify(pk32, hi, lo, pv.astype(np.int32))

    def _preallocate_tiers(self, n: int) -> None:
        """Fix the tier capacity buckets from the configured workload
        bounds (§11): the delta is capped at ``delta_cap`` between
        merges but keeps absorbing inserts while a fold is in flight,
        and the run peaks around the fold trigger plus deferred merges —
        8x headroom over both keeps steady-state serving off the
        capacity-growth (repack + retrace) path entirely."""
        self._serving.preallocate(
            delta_floor=8 * self.cfg.delta_cap + 1,
            run_floor=int(self.cfg.rebuild_frac * max(n, 1))
            + 8 * self.cfg.delta_cap + 1,
            # the scan pool tracks the live key count: n now, plus the
            # same fold-absorption headroom, so in-window folds refresh
            # a prefix instead of repacking (§12)
            scan_floor=int((1.0 + self.cfg.rebuild_frac) * max(n, 1))
            + 8 * self.cfg.delta_cap + 1)

    def _reset_tiers(self) -> None:
        self._delta_pk = np.empty(0, np.float32)
        self._delta_hi = np.empty(0, np.uint32)
        self._delta_lo = np.empty(0, np.uint32)
        self._delta_pv = np.empty(0, np.int32)
        self._run_pk = np.empty(0, np.float32)
        self._run_hi = np.empty(0, np.uint32)
        self._run_lo = np.empty(0, np.uint32)
        self._run_pv = np.empty(0, np.int32)
        self._serving.reset_tiers()
        self._fold = None

    def _reset_scan_mirror(self) -> None:
        self._scan_pk = np.empty(0, np.float32)
        self._scan_hi = np.empty(0, np.uint32)
        self._scan_lo = np.empty(0, np.uint32)
        self._scan_pv = np.empty(0, np.int32)

    def _set_scan_mirror(self, pk, hi, lo, pv) -> None:
        """Adopt the (re)built structure's sorted snapshot as the range
        path's scan pool (§12) and ship it to the persistent device
        buffer eagerly — build/fold-swap time, off the serve path."""
        self._scan_pk, self._scan_hi = pk, hi
        self._scan_lo, self._scan_pv = lo, pv
        self._serving.set_scan(pk, hi, lo, pv, _tier_window(pk))

    def _scan_pack(self):
        """ScanPack thunk for ``ops.fused_range_scan`` — always resident
        (an index served before its first build scans an empty pool)."""
        return self._serving.scan_pack()

    def set_serve_flow(self, normalizer, flow_cfg, packed_w, shapes) -> None:
        """Register the fused serve-path flow context so every fold can
        re-verify placement through the in-kernel NF (§8/§10): identity
        keys are reconstructed from the stored (hi, lo) bit pools, so no
        raw-key copy needs to be retained."""
        self._serve_flow = (normalizer, flow_cfg, packed_w, shapes)

    def contains_batch(self, ikeys: np.ndarray) -> np.ndarray:
        """Exact membership by 64-bit identity (tree + write tiers,
        DESIGN.md §12: tracks the *live* identity set — a tombstoned key
        is absent until re-inserted)."""
        hi, lo = split_key_bits(np.asarray(ikeys, dtype=np.float64))
        ids = self._id_set
        return np.fromiter((int(u) in ids for u in _ids64(hi, lo)),
                           bool, count=hi.shape[0])

    # ---------------------------------------------------- device dispatch
    def _kernel_pools(self):
        """The device-resident kernel pools: packed once per build/fold
        swap into the serving cache, reused by every dispatch (§11)."""
        if self._serving.tree_pools is None:
            self._serving.set_tree(self.arrays, max_depth=self.max_depth,
                                   dense_window=getattr(self, "dense_window",
                                                        8))
        return self._serving.tree_pools

    def _dense_window_static(self) -> int:
        """Ratcheted serve-path duplicate-run window (upward-only so a
        fold swap that shrinks it cannot retrace the kernel)."""
        return max(self._serving.dense_window,
                   _window_round(int(getattr(self, "dense_window", 8))))

    def _depth_static(self) -> int:
        return max(self._serving.max_depth, _depth_round(self.max_depth))

    def _sync_tiers(self) -> None:
        """Ship dirty tier prefixes into the persistent device buffers.
        Called eagerly from every write-path mutation so serve calls
        (reads) find the pack resident and pay nothing.  The mirror
        thunks are evaluated per dirty tier only — a delta append never
        re-scans the (unchanged, much larger) run mirror for its
        window."""
        self._serving.refresh_tiers(
            lambda: (self._run_pk, self._run_hi, self._run_lo,
                     self._run_pv, _tier_window(self._run_pk)),
            lambda: (self._delta_pk, self._delta_hi, self._delta_lo,
                     self._delta_pv, _tier_window(self._delta_pk)))

    def _tier_pack(self):
        """TierPack thunk for ``ops.fused_lookup`` — ``None`` when both
        write tiers are empty (the probe stage compiles out).  Returns
        the *resident* pack: mutations refresh only the changed prefix
        of the persistent bucketed buffers, never a full repack."""
        self._sync_tiers()
        return self._serving.tier_pack()

    def _stream_pack(self):
        """StreamPack thunk for ``ops.fused_lookup``'s HBM-streaming
        rung (§17): the rank-ordered scan pool + resident router.  The
        pool mirrors the live static structure exactly (same build /
        fold-swap refresh points as the tree pools), so a streamed probe
        of it is payload-identical to the tree traversal — which is what
        lets the ladder swap one for the other when the pools outgrow
        the VMEM budget."""
        return self._serving.stream_pack()

    def _stream_arg(self, *, live: bool):
        """The ``stream=`` argument for a point dispatch: the thunk on
        the live serve path (config-gated), ``None`` on fold/candidate
        verification dispatches — those probe an *override* structure
        (new arrays/pools), and serving them from the live scan pool
        would silently verify the wrong thing."""
        return (self._stream_pack
                if live and self.cfg.use_streamed_kernel else None)

    def _device_lookup_async(self, pk32: np.ndarray, hi: np.ndarray,
                             lo: np.ndarray, *, arrays=None, pools=None,
                             max_depth=None, dense_window=None,
                             tiers: bool = True):
        """Non-flow kernel dispatch, left on device: returns ``(res
        device array, n)`` WITHOUT forcing a host transfer, so a caller
        fanning one batch out across shard devices (DESIGN.md §13) can
        dispatch every shard before blocking on any result.  The keyword
        overrides let the incremental fold verify a *candidate*
        structure (new arrays/pools, tiers excluded) while the old one
        keeps serving."""
        from repro.kernels import ops

        if arrays is None and self.arrays is None:
            # not built yet (insert-before-build, or an empty shard of a
            # sharded index, DESIGN.md §13): there is no static
            # structure to probe — every query resolves from the write
            # tiers alone via the host probe the finisher runs
            self.last_dispatch = {"path": "unbuilt", "n_dispatch": 0,
                                  "tier_path": "host", "host_probe": True,
                                  "retraced": False}
            return np.full(pk32.shape[0], -1, np.int32), pk32.shape[0]

        from repro.kernels.backend import pow2_batch

        with span("afli.point.enqueue"):
            # pad to power-of-two buckets: ragged request batches would
            # recompile the kernel / traversal loop per distinct size
            n = pk32.shape[0]
            n_pad = pow2_batch(n)
            if n_pad != n:
                pk32 = np.pad(pk32, (0, n_pad - n))
                hi = np.pad(hi, (0, n_pad - n))
                lo = np.pad(lo, (0, n_pad - n))
            res, _z, self.last_dispatch = ops.fused_lookup(
                self.arrays if arrays is None else arrays,
                self._kernel_pools if pools is None else pools,
                jnp.asarray(np.ascontiguousarray(pk32).reshape(-1, 1)),
                jnp.asarray(hi), jnp.asarray(lo), flow=None,
                max_depth=(self._depth_static() if max_depth is None
                           else max_depth),
                dense_iters=self.cfg.dense_search_iters,
                bucket_cap=self.cfg.max_bucket,
                dense_window=(self._dense_window_static()
                              if dense_window is None else dense_window),
                tiers=self._tier_pack if tiers else None,
                stream=self._stream_arg(
                    live=arrays is None and pools is None and tiers),
                vmem_budget=self.cfg.vmem_budget
                if self.cfg.use_fused_kernel else 0,
                sync=False,
            )
        return res, n

    def _device_lookup(self, pk32: np.ndarray, hi: np.ndarray,
                       lo: np.ndarray, **kw) -> np.ndarray:
        """Non-flow kernel dispatch (DESIGN.md §9/§10), synchronous form
        of ``_device_lookup_async``."""
        res, n = self._device_lookup_async(pk32, hi, lo, **kw)
        return np.asarray(res)[:n]

    def _self_verify(self, pk32, hi, lo, pv) -> None:
        """Device-verified placement (DESIGN.md §8).

        Builder slot arithmetic (numpy f32) and compiled slot arithmetic
        (XLA, FMA-contracted) can disagree by one slot for keys sitting on
        an exact rint boundary (~0.1%).  Any key the *device* cannot find
        is shadowed into the run tier, whose probe uses only exact
        comparisons.  The stale in-tree copy is unreachable-or-identical
        (identity compare makes false positives impossible), and folds
        deduplicate.  Shadows live in the run — *below* the active delta —
        so a newer insert for the same identity still wins."""
        res = self._device_lookup(pk32, hi, lo, tiers=False)
        wrong = res != pv
        if wrong.any():
            self._append_run(pk32[wrong], hi[wrong], lo[wrong], pv[wrong])

    def _append_delta(self, pk, hi, lo, pv) -> None:
        """Append a batch to the active delta with last-write-wins dedup
        by 64-bit identity (the batch is newer than what the delta
        holds, and within the batch later entries win).

        Deduplicating here — not just at merge — keeps each identity at
        ONE copy, so an equal-pkey run in the delta can only come from
        genuinely colliding f32 positioning keys, never from re-insert
        traffic.  That bounds the probe window by the *data*, not the
        workload: a re-insert-heavy stream cannot ratchet the kernel's
        static scan window mid-serving (§11 zero-retrace), and the probe
        semantics are unchanged (the newest copy is the only copy)."""
        with span("afli.insert.delta"):
            (self._delta_pk, self._delta_hi,
             self._delta_lo, self._delta_pv) = _dedup_newest(
                np.concatenate([self._delta_pk, pk]),
                np.concatenate([self._delta_hi, hi]),
                np.concatenate([self._delta_lo, lo]),
                np.concatenate([self._delta_pv, pv.astype(np.int32)]))
        self._serving.mark_delta_dirty()
        self._sync_tiers()

    def _append_run(self, pk, hi, lo, pv) -> None:
        """Merge entries into the compacted run: two-way merge with
        last-write-wins dedup by 64-bit identity (appended entries are
        newer than what the run holds)."""
        (self._run_pk, self._run_hi,
         self._run_lo, self._run_pv) = _dedup_newest(
            np.concatenate([self._run_pk, pk]),
            np.concatenate([self._run_hi, hi]),
            np.concatenate([self._run_lo, lo]),
            np.concatenate([self._run_pv, pv.astype(np.int32)]))
        self._serving.mark_run_dirty()
        self._sync_tiers()

    def _merge_delta_into_run(self) -> None:
        """Retire the full active delta into the compacted run."""
        if not self._delta_pk.shape[0]:
            return
        with span("afli.run_merge"):
            self._append_run(self._delta_pk, self._delta_hi,
                             self._delta_lo, self._delta_pv)
            self._delta_pk = np.empty(0, np.float32)
            self._delta_hi = np.empty(0, np.uint32)
            self._delta_lo = np.empty(0, np.uint32)
            self._delta_pv = np.empty(0, np.int32)
            self._serving.mark_delta_dirty()
            self._sync_tiers()

    # ------------------------------------------------------------- lookup
    def _tier_state(self):
        """The current write-tier arrays as an immutable snapshot.

        Every tier mutation *replaces* these arrays (``_dedup_newest``
        builds fresh ones) — none is ever written in place — so holding
        the references IS a consistent snapshot.  An async finisher
        captures this at dispatch time: the device kernel already runs
        against dispatch-time tier buffers (functional device arrays),
        and the host probe must resolve against the same instant or a
        read gathered after a later write would see the future."""
        return (self._run_pk, self._run_hi, self._run_lo, self._run_pv,
                self._delta_pk, self._delta_hi, self._delta_lo,
                self._delta_pv)

    def _probe_delta(self, res: np.ndarray, q32: np.ndarray,
                     qhi: np.ndarray, qlo: np.ndarray) -> np.ndarray:
        return self._probe_tiers_at(self._tier_state(), res, q32, qhi, qlo)

    def _probe_tiers_at(self, tier_state, res: np.ndarray, q32: np.ndarray,
                        qhi: np.ndarray, qlo: np.ndarray) -> np.ndarray:
        """Host oracle for the in-kernel tier probe: resolve every query
        against the write tiers (sorted searchsorted pools; exact identity
        compares only), newest copy first — active delta > compacted run >
        device result.  Runs only when the kernel did not already probe
        the tiers on device (``last_dispatch["host_probe"]``)."""
        (run_pk, run_hi, run_lo, run_pv,
         dl_pk, dl_hi, dl_lo, dl_pv) = tier_state
        if not (dl_pk.shape[0] or run_pk.shape[0]):
            return res
        self.n_host_tier_probes += 1
        run_pay = _probe_sorted_pool(run_pk, run_hi, run_lo, run_pv,
                                     q32, qhi, qlo)
        dl_pay = _probe_sorted_pool(dl_pk, dl_hi, dl_lo, dl_pv,
                                    q32, qhi, qlo)
        # identity match in a newer tier wins even when it is a
        # TOMBSTONE — the tombstone masks every older copy below, then
        # surfaces as a miss (same precedence as the kernel, §12)
        out = np.where(dl_pay != -1, dl_pay,
                       np.where(run_pay != -1, run_pay, res))
        return np.where(out == TOMBSTONE, -1, out).astype(res.dtype)

    def lookup_batch_async(self, keys: np.ndarray,
                           ikeys: np.ndarray | None = None):
        """Dispatch a batched lookup and return a zero-arg *finisher*
        instead of blocking on the result.

        The kernel call is in flight when this returns; calling the
        finisher transfers the device result (and runs the host tier
        probe if the kernel could not take the tiers).  The sharded
        serving layer (DESIGN.md §13) dispatches one of these per shard
        before finishing any, so per-shard kernels on distinct devices
        overlap instead of serializing on each host transfer."""
        k64 = np.asarray(keys, dtype=np.float64)
        ik64 = k64 if ikeys is None else np.asarray(ikeys, dtype=np.float64)
        hi, lo = split_key_bits(ik64)
        q32 = k64.astype(np.float32)
        res_dev, n = self._device_lookup_async(q32, hi, lo)
        host_probe = self.last_dispatch.get("host_probe", True)
        tier_state = self._tier_state()

        def finish() -> np.ndarray:
            with span("afli.point.wait"):
                res = np.asarray(res_dev)[:n]
            if host_probe:
                return self._probe_tiers_at(tier_state, res, q32, hi, lo)
            return res

        return finish

    def lookup_batch(self, keys: np.ndarray,
                     ikeys: np.ndarray | None = None) -> np.ndarray:
        """Batched point lookups on the fused serve path (DESIGN.md
        §9/§10): one kernel dispatch resolves traversal AND write tiers;
        -1 marks not-found.  keys: positioning keys (must match
        build-time pkeys); ikeys: identity keys when positioning keys
        are flow-transformed."""
        return self.lookup_batch_async(keys, ikeys)()

    def _flow_device_lookup(self, feats: np.ndarray, hi: np.ndarray,
                            lo: np.ndarray, packed_w, shapes, *,
                            arrays=None, pools=None, max_depth=None,
                            dense_window=None, tiers: bool = True):
        """Fused NF + traversal dispatch; returns (payloads, serve pkeys).
        Keyword overrides as in ``_device_lookup`` (fold verification)."""
        from repro.kernels import ops
        from repro.kernels.backend import pow2_batch

        n = feats.shape[0]
        n_pad = pow2_batch(n)
        if n_pad != n:
            feats = np.pad(feats, ((0, n_pad - n), (0, 0)))
            hi = np.pad(hi, (0, n_pad - n))
            lo = np.pad(lo, (0, n_pad - n))
        res, z, self.last_dispatch = ops.fused_lookup(
            self.arrays if arrays is None else arrays,
            self._kernel_pools if pools is None else pools,
            jnp.asarray(feats, jnp.float32), jnp.asarray(hi),
            jnp.asarray(lo), flow=(packed_w, shapes),
            max_depth=self._depth_static() if max_depth is None else max_depth,
            dense_iters=self.cfg.dense_search_iters,
            bucket_cap=self.cfg.max_bucket,
            dense_window=(self._dense_window_static()
                          if dense_window is None else dense_window),
            tiers=self._tier_pack if tiers else None,
            stream=self._stream_arg(
                live=arrays is None and pools is None and tiers),
            vmem_budget=self.cfg.vmem_budget
            if self.cfg.use_fused_kernel else 0,
        )
        return np.array(res)[:n], np.asarray(z)[:n]

    def lookup_batch_flow(self, feats: np.ndarray, ikeys: np.ndarray,
                          packed_w, shapes) -> np.ndarray:
        """Single-dispatch serving for flow-positioned indexes: one Pallas
        call runs the NF forward, the traversal, AND the write-tier probe
        (DESIGN.md §9/§10) — a mixed read/insert workload needs no host
        round trip while the tiers fit the kernel pool budget.

        feats: [n, d] f32 expanded query features (``expand_features`` of
        the raw keys); ikeys: f64 identity keys; packed_w/shapes: the
        ``pack_flow_weights`` block of the flow that positioned the build.
        The kernel also emits the transformed positioning keys, which feed
        the host-side tier probe when the kernel could not take it.
        """
        return self.lookup_batch_flow_async(feats, ikeys, packed_w,
                                            shapes)()

    def lookup_batch_flow_async(self, feats: np.ndarray, ikeys: np.ndarray,
                                packed_w, shapes):
        """Flow-positioned twin of ``lookup_batch_async``: dispatch the
        fused NF + traversal + tier-probe kernel without blocking and
        return a zero-arg finisher.  The kernel inputs are snapshot at
        dispatch time (tier buffers are functional device arrays), so a
        finisher called after later writes still resolves against the
        index state the batch was dispatched into — the §16 front-end
        relies on this to overlap host-side batching with device
        execution."""
        from repro.kernels import ops
        from repro.kernels.backend import pow2_batch

        ik64 = np.asarray(ikeys, dtype=np.float64)
        hi, lo = split_key_bits(ik64)
        with span("afli.point.enqueue"):
            n = feats.shape[0]
            n_pad = pow2_batch(n)
            pf, phi, plo = feats, hi, lo
            if n_pad != n:
                pf = np.pad(feats, ((0, n_pad - n), (0, 0)))
                phi = np.pad(hi, (0, n_pad - n))
                plo = np.pad(lo, (0, n_pad - n))
            res_dev, z_dev, self.last_dispatch = ops.fused_lookup(
                self.arrays, self._kernel_pools,
                jnp.asarray(pf, jnp.float32), jnp.asarray(phi),
                jnp.asarray(plo), flow=(packed_w, shapes),
                max_depth=self._depth_static(),
                dense_iters=self.cfg.dense_search_iters,
                bucket_cap=self.cfg.max_bucket,
                dense_window=self._dense_window_static(),
                tiers=self._tier_pack,
                stream=self._stream_arg(live=True),
                vmem_budget=self.cfg.vmem_budget
                if self.cfg.use_fused_kernel else 0,
                sync=False,
            )
        host_probe = self.last_dispatch.get("host_probe", True)
        tier_state = self._tier_state()

        def finish() -> np.ndarray:
            with span("afli.point.wait"):
                res = np.asarray(res_dev)[:n]
            if host_probe:
                return self._probe_tiers_at(tier_state, res,
                                            np.asarray(z_dev)[:n], hi, lo)
            return res

        return finish

    def verify_serve_flow(self, feats: np.ndarray, ikeys: np.ndarray,
                          packed_w, shapes, payloads: np.ndarray) -> int:
        """Device-verified placement (DESIGN.md §8) extended to the fused
        serve path: any built key the serve-path kernel cannot resolve is
        shadowed into the run tier, keyed by the *serve-path* positioning
        key so every future probe finds it by exact comparison.  Returns
        the number of shadowed keys (0 in the common case — the serve NF
        tile is pinned to the build transform's tile)."""
        ik64 = np.asarray(ikeys, dtype=np.float64)
        hi, lo = split_key_bits(ik64)
        res, z = self._flow_device_lookup(feats, hi, lo, packed_w, shapes)
        if self.last_dispatch.get("host_probe", True):
            res = self._probe_delta(res, z, hi, lo)
        wrong = res != np.asarray(payloads, res.dtype)
        if wrong.any():
            self._append_run(z[wrong], hi[wrong], lo[wrong],
                             np.asarray(payloads)[wrong].astype(np.int32))
        return int(wrong.sum())

    # -------------------------------------------------------- range scan
    def scan_batch(self, lo_keys: np.ndarray, hi_keys: np.ndarray,
                   cap: int | None = None):
        """Batched ``[lo, hi)`` range scans over positioning-key order
        (§12).  Returns ``(payloads i32[n, cap] (-1 padded), counts
        i32[n], totals i32[n])``: per query the first ``counts[i]``
        payload lanes are the live entries in range, in key order;
        ``totals[i] > cap`` flags truncation (``cap`` bounds the
        candidates examined).  Without a flow the positioning order is
        the key order itself (the f32 cast is monotone)."""
        lo32 = np.asarray(lo_keys, dtype=np.float64).astype(np.float32)
        hi32 = np.asarray(hi_keys, dtype=np.float64).astype(np.float32)
        return self._device_scan(lo32.reshape(-1, 1), hi32.reshape(-1, 1),
                                 flow=None, cap=cap)

    def scan_batch_flow(self, feats_lo: np.ndarray, feats_hi: np.ndarray,
                        packed_w, shapes, cap: int | None = None):
        """Range scans for flow-positioned indexes: ONE pallas_call runs
        the NF forward on both endpoints, the lower-bound location, and
        the tier-merged emission (§12).  feats_lo/feats_hi are the
        ``expand_features`` of the raw endpoint keys."""
        return self._device_scan(feats_lo, feats_hi,
                                 flow=(packed_w, shapes), cap=cap)

    def _device_scan(self, feats_lo: np.ndarray, feats_hi: np.ndarray, *,
                     flow, cap: int | None):
        """Range dispatch: pad the query batch to a power-of-two bucket,
        route through ``ops.fused_range_scan`` (kernel when the pools fit
        the budget, bit-identical host oracle otherwise).  Zero-padded
        lanes have equal endpoints -> empty ranges, sliced off."""
        from repro.kernels import ops
        from repro.kernels.backend import pow2_batch

        cap = int(cap if cap is not None else self.cfg.scan_cap)
        n = feats_lo.shape[0]
        n_pad = pow2_batch(n)
        if n_pad != n:
            feats_lo = np.pad(feats_lo, ((0, n_pad - n), (0, 0)))
            feats_hi = np.pad(feats_hi, ((0, n_pad - n), (0, 0)))

        def host_fallback():
            if flow is not None:
                from repro.kernels.nf_forward import nf_forward_pallas

                packed_w, shapes = flow
                dim = feats_lo.shape[1]
                zlo = np.asarray(nf_forward_pallas(
                    jnp.asarray(feats_lo, jnp.float32), packed_w, shapes,
                    dim))
                zhi = np.asarray(nf_forward_pallas(
                    jnp.asarray(feats_hi, jnp.float32), packed_w, shapes,
                    dim))
            else:
                zlo = np.asarray(feats_lo[:, 0], np.float32)
                zhi = np.asarray(feats_hi[:, 0], np.float32)
            self.n_host_scans += 1
            return self._range_scan_host(zlo, zhi, cap)

        self._sync_tiers()
        pv, cnt, tot, self.last_scan_dispatch = ops.fused_range_scan(
            self._scan_pack, self._tier_pack,
            jnp.asarray(feats_lo, jnp.float32),
            jnp.asarray(feats_hi, jnp.float32),
            flow=flow, scan_cap=cap, host_fallback=host_fallback,
            vmem_budget=self.cfg.vmem_budget
            if self.cfg.use_fused_kernel else 0,
        )
        return pv[:n], cnt[:n], tot[:n]

    def _range_scan_host(self, zlo: np.ndarray, zhi: np.ndarray,
                         cap: int, chunk: int = 512):
        """Host oracle twin of ``kernels/range_scan``: same candidate
        order (pk-major, newest tier first on ties, in-tier index last),
        same per-candidate identity probes into the newer tiers, same
        tombstone filtering, same ``cap``-candidate truncation — results
        are bit-identical to the kernel by construction (the parity
        tests hold both to it).

        Vectorized across the query batch: candidates of ``chunk``
        queries at a time are flattened into one (qid, pk, prio)-sorted
        array, capped by rank-within-query, probed in two batched
        ``_probe_sorted_pool`` rounds, and scattered into the output
        lanes — no per-query Python loop on the fallback path."""
        n = zlo.shape[0]
        tiers = [  # priority order: newest first
            (self._delta_pk, self._delta_hi, self._delta_lo,
             self._delta_pv),
            (self._run_pk, self._run_hi, self._run_lo, self._run_pv),
            (self._scan_pk, self._scan_hi, self._scan_lo, self._scan_pv),
        ]
        bounds = [(np.searchsorted(pk, zlo, side="left"),
                   np.searchsorted(pk, zhi, side="left"))
                  for pk, _h, _l, _v in tiers]
        out = np.full((n, cap), -1, np.int32)
        cnt = np.zeros(n, np.int32)
        tot = np.zeros(n, np.int64)
        for (a, b) in bounds:
            tot += np.maximum(b - a, 0)

        def flat_ranges(a, b):
            """Concatenated [a_i, b_i) ranges -> (qid, pool index)."""
            lens = np.maximum(b - a, 0)
            total = int(lens.sum())
            qid = np.repeat(np.arange(lens.shape[0], dtype=np.int64),
                            lens)
            excl = np.concatenate([[0], np.cumsum(lens)[:-1]])
            intra = np.arange(total) - np.repeat(excl, lens)
            return qid, np.repeat(a, lens) + intra

        for c0 in range(0, n, chunk):
            c1 = min(c0 + chunk, n)
            qids, pks, his, los, pvs, prios = [], [], [], [], [], []
            # tier-major concatenation: within one (query, tier) group
            # the pool indices ascend, so the stable lexsort below keeps
            # in-tier insertion order on full ties
            for prio, ((pk, hi, lo, pv), (a, b)) in enumerate(
                    zip(tiers, bounds)):
                qid, idx = flat_ranges(a[c0:c1], b[c0:c1])
                qids.append(qid)
                pks.append(pk[idx])
                his.append(hi[idx])
                los.append(lo[idx])
                pvs.append(pv[idx])
                prios.append(np.full(idx.shape[0], prio, np.int32))
            qid = np.concatenate(qids)
            if not qid.shape[0]:
                continue
            cpk = np.concatenate(pks)
            cprio = np.concatenate(prios)
            # per-query pk-major merge order, newest tier first on ties
            # — exactly the kernel's cursor order, all queries at once
            order = np.lexsort((cprio, cpk, qid))
            qid, cpk, cprio = qid[order], cpk[order], cprio[order]
            chi = np.concatenate(his)[order]
            clo = np.concatenate(los)[order]
            cpv = np.concatenate(pvs)[order]
            # cap by rank within query (qid is the sort major)
            first = np.searchsorted(qid, np.arange(c1 - c0))
            rank = np.arange(qid.shape[0]) - first[qid]
            keep = rank < cap
            qid, cpk, cprio = qid[keep], cpk[keep], cprio[keep]
            chi, clo, cpv = chi[keep], clo[keep], cpv[keep]
            dl = _probe_sorted_pool(self._delta_pk, self._delta_hi,
                                    self._delta_lo, self._delta_pv,
                                    cpk, chi, clo)
            rn = _probe_sorted_pool(self._run_pk, self._run_hi,
                                    self._run_lo, self._run_pv,
                                    cpk, chi, clo)
            superseded = (((cprio == 2) & ((dl != -1) | (rn != -1)))
                          | ((cprio == 1) & (dl != -1)))
            valid = ~superseded & (cpv != TOMBSTONE)
            # compact valid payloads into per-query output lanes
            vex = np.concatenate([[0], np.cumsum(valid)[:-1]])  # exclusive
            first = np.searchsorted(qid, np.arange(c1 - c0))
            pos = vex - np.concatenate([vex, [0]])[first][qid]
            out[c0 + qid[valid], pos[valid]] = cpv[valid]
            cnt[c0:c1] = np.bincount(qid[valid], minlength=c1 - c0)
        return out, cnt, tot.astype(np.int32)

    # ------------------------------------------------------------- insert
    def insert_batch(self, keys: np.ndarray, payloads: np.ndarray,
                     ikeys: np.ndarray | None = None) -> None:
        """Tiered write path (§10): the batch lands in the active delta
        (device-probed inside the fused kernel); a full delta merges into
        the compacted run; an oversized run triggers the *incremental*
        fold, advanced here by a bounded work budget per call so no single
        insert pays the full O(n) reorganization."""
        k64 = np.asarray(keys, dtype=np.float64)
        ik64 = k64 if ikeys is None else np.asarray(ikeys, dtype=np.float64)
        pv = np.asarray(payloads, dtype=np.int32)
        self._check_payloads(pv)
        pk = k64.astype(np.float32)
        hi, lo = split_key_bits(ik64)
        self._append_delta(pk, hi, lo, pv)
        # count only genuinely new identities: re-inserts overwrite
        ids = self._id_set
        fresh = 0
        for u in _ids64(hi, lo).tolist():
            if u not in ids:
                ids.add(u)
                fresh += 1
        self.n_keys += fresh
        self._advance_write_path(pk.shape[0])

    def delete_batch(self, keys: np.ndarray,
                     ikeys: np.ndarray | None = None) -> np.ndarray:
        """Tombstone deletes (§12): each present key appends a TOMBSTONE
        entry to the active delta — the newest copy of its identity, so
        it masks every older copy (delta dedup, run, static tree) on both
        the point and range paths — and the next fold drops the identity
        physically.  Returns per-key success (False = key absent; the
        second delete of a duplicate within one batch fails, matching the
        sequential per-key semantics of the afli backend)."""
        k64 = np.asarray(keys, dtype=np.float64)
        ik64 = k64 if ikeys is None else np.asarray(ikeys, dtype=np.float64)
        pk = k64.astype(np.float32)
        hi, lo = split_key_bits(ik64)
        ids = _ids64(hi, lo)
        ok = np.zeros(ids.shape[0], dtype=bool)
        id_set = self._id_set
        for i, u in enumerate(ids.tolist()):
            if u in id_set:
                id_set.remove(u)
                ok[i] = True
        if ok.any():
            n_del = int(ok.sum())
            self.n_keys -= n_del
            self._append_delta(pk[ok], hi[ok], lo[ok],
                               np.full(n_del, TOMBSTONE, np.int32))
            self._advance_write_path(n_del)
        return ok

    def _advance_write_path(self, n_batch: int) -> None:
        """Shared write-path bookkeeping for inserts and deletes: advance
        an in-flight fold by the per-call budget, retire a full delta
        into the run, and trigger a fold when the run outgrows its
        bound."""
        budget = max(int(self.cfg.fold_step_keys),
                     int(self.cfg.fold_work_factor * max(n_batch, 1)))
        if self._tier_hold:
            # parent-coordinated re-flow in flight (§14): writes buffer
            # in the tiers; fold/merge decisions resume after the swap
            return
        if self._fold is not None:
            self._fold_tick(budget)
        if self._fold is None:
            if self._delta_pk.shape[0] > self.cfg.delta_cap:
                self._merge_delta_into_run()
            # no static structure yet (insert-before-build): the tiers
            # simply keep buffering — there is nothing to fold into
            if (self.arrays is not None
                    and self._run_pk.shape[0]
                    > self.cfg.rebuild_frac * max(self.n_keys, 1)):
                self._fold_start()
                if self._fold is not None:
                    self._fold_tick(budget)

    def _snapshot_live(self):
        """Freeze the live keyset: merge the delta into the run, gather
        static entries (oldest) + bucket entries + run (newest), dedup
        by 64-bit identity with the newest copy winning, and physically
        drop tombstoned identities (§12).  Returns sorted-by-age-rank
        ``(pk, hi, lo, pv)`` — the fold snapshot, and the §14 re-flow's
        complete picture of what must survive a re-key."""
        self._merge_delta_into_run()
        if self.arrays is not None:
            et = np.asarray(self.arrays.etype)
            data_mask = et == DATA
            pk = np.asarray(self.arrays.ekey)[data_mask]
            hi = np.asarray(self.arrays.ehi)[data_mask]
            lo = np.asarray(self.arrays.elo)[data_mask]
            pv = np.asarray(self.arrays.epayload)[data_mask]
            blen = np.asarray(self.arrays.blen)
            cap = self.cfg.max_bucket
            bmask = np.arange(cap)[None, :] < blen[:, None]
            pk = np.concatenate([pk, np.asarray(self.arrays.bkey)[bmask],
                                 self._run_pk])
            hi = np.concatenate([hi, np.asarray(self.arrays.bhi)[bmask],
                                 self._run_hi])
            lo = np.concatenate([lo, np.asarray(self.arrays.blo)[bmask],
                                 self._run_lo])
            pv = np.concatenate([pv, np.asarray(self.arrays.bpayload)[bmask],
                                 self._run_pv])
        else:  # unbuilt: the tiers hold everything
            pk, hi, lo = self._run_pk, self._run_hi, self._run_lo
            pv = self._run_pv
        pk, hi, lo, pv = _dedup_newest(pk, hi, lo,
                                       np.asarray(pv, np.int64))
        live = pv != TOMBSTONE
        if not live.all():
            pk, hi, lo, pv = pk[live], hi[live], lo[live], pv[live]
        return pk, hi, lo, pv

    def _fold_start(self) -> None:
        """Begin an incremental fold: freeze the write tiers into a
        snapshot (static entries oldest, run newest; last-write-wins dedup
        by identity) and seed the work queue.  Serving continues against
        the old structure + frozen tiers until the fold swaps in."""
        pk, hi, lo, pv = self._snapshot_live()
        if not pk.shape[0]:
            # everything tombstoned: nothing to fold into — the old
            # structure keeps serving with the tombstones masking it;
            # the run keeps the tombstones so older tree copies stay
            # invisible on every dispatch route
            return
        self._fold = _IncrementalFold(self, pk, hi, lo,
                                      pv.astype(np.int64))

    # ------------------------------------------------------------ re-flow
    def _rekey_delta(self, transform_fn) -> None:
        """Recompute the active delta's positioning keys under a new
        transform (§14 swap point).  Identities and payloads (including
        tombstones — they keep masking by identity) are untouched;
        entries re-sort stably by the new z.  Only marks the device twin
        dirty: the caller refreshes via ``_sync_tiers`` AFTER the
        ratchets settle, so the tier window is ratcheted by the re-keyed
        data, not the drifted history."""
        n = int(self._delta_pk.shape[0])
        if not n:
            return
        ik64 = _ids64(self._delta_hi, self._delta_lo).view(np.float64)
        pk = np.asarray(transform_fn(ik64), np.float64).astype(np.float32)
        order = np.argsort(pk, kind="stable")
        self._delta_pk = pk[order]
        self._delta_hi = self._delta_hi[order]
        self._delta_lo = self._delta_lo[order]
        self._delta_pv = self._delta_pv[order]
        self._serving.mark_delta_dirty()

    def _rekey_tiers(self, transform_fn) -> None:
        """Re-key BOTH write tiers in place (§14, unbuilt-index path:
        there is no static structure to fold, so adopting a new
        transform is a pure tier re-key)."""
        self._rekey_delta(transform_fn)
        n = int(self._run_pk.shape[0])
        if n:
            ik64 = _ids64(self._run_hi, self._run_lo).view(np.float64)
            pk = np.asarray(transform_fn(ik64), np.float64).astype(np.float32)
            order = np.argsort(pk, kind="stable")
            self._run_pk = pk[order]
            self._run_hi = self._run_hi[order]
            self._run_lo = self._run_lo[order]
            self._run_pv = self._run_pv[order]
            self._serving.mark_run_dirty()
        self._sync_tiers()

    def start_reflow(self, transform_fn, serve_flow, on_swap) -> bool:
        """Begin an atomic re-key of the whole index under a new
        positioning transform (DESIGN.md §14).

        ``transform_fn(ik64) -> z`` maps raw identity keys to the new
        positioning keys (the candidate flow's forward, or identity);
        ``serve_flow`` is the new serve context 4-tuple (or ``None`` for
        identity); ``on_swap()`` runs exactly once, after the swap, so
        the owner can install its own flow state at the same instant the
        structure adopts it.  Returns False (caller retries later) when
        a fold is already in flight — the §10 machinery supports one
        snapshot at a time.  The re-key itself IS an incremental fold
        over the re-transformed snapshot: serving continues against the
        old structure + frozen tiers, bounded work per write batch, and
        the verified swap is the adoption point."""
        if self._fold is not None or self._tier_hold:
            return False
        pk, hi, lo, pv = self._snapshot_live()
        if not pk.shape[0]:
            # nothing indexed beyond tombstones: re-key the tiers in
            # place and adopt the transform immediately
            self._rekey_tiers(transform_fn)
            self._serve_flow = serve_flow
            self.n_reflows += 1
            on_swap()
            return True
        ik64 = _ids64(hi, lo).view(np.float64)
        new_pk = np.asarray(transform_fn(ik64), np.float64).astype(np.float32)
        order = np.argsort(new_pk, kind="stable")
        self._fold = _IncrementalFold(
            self, new_pk[order], hi[order], lo[order],
            pv[order].astype(np.int64),
            reflow=(transform_fn, serve_flow, on_swap))
        # the AutoSwitch verdict over the re-keyed snapshot (§13/§14):
        # identity candidates tie and report use_flow=False
        from repro.core.conflict import should_use_flow

        use, t_orig, t_new = should_use_flow(ik64, new_pk, self.cfg.gamma)
        self._fold.autoswitch_new = {"use_flow": bool(use),
                                     "tail_original": int(t_orig),
                                     "tail_transformed": int(t_new)}
        return True

    def _fold_tick(self, budget: int) -> None:
        if self._fold is None:
            return
        with span("afli.fold_tick"):
            # §16 fault-injection hook: a FaultPlan with fold_stall_s set
            # models a slow fold, stretching the tier-resident window
            from repro.kernels import ops

            ops.fault_stall("fold")
            if self._fold.tick(budget):
                # swapped in; apply any delta merge deferred during the
                # fold
                if self._delta_pk.shape[0] > self.cfg.delta_cap:
                    self._merge_delta_into_run()

    def rebuild(self) -> None:
        """Fold every write tier into the static structure synchronously
        (DESIGN.md §10: the incremental fold run to completion in one
        call — the batched Modelling).  ``insert_batch`` amortizes the
        same work instead; this is the maintenance/test hook."""
        if self.arrays is None:
            return
        # a fold already in flight consumed a snapshot that excludes any
        # inserts made since; complete it, then fold the leftovers too
        while self._fold is not None:
            self._fold_tick(1 << 62)
        self._fold_start()
        while self._fold is not None:
            self._fold_tick(1 << 62)

    def serving_telemetry(self) -> dict:
        """The serving-side slice of ``NFL.dispatch_stats()`` (DESIGN.md
        §11): the persistent ``ServingState`` counters plus the host
        fallback counts for the point and range routes."""
        return {
            "serving": self._serving.stats(),
            "host_tier_probes": self.n_host_tier_probes,
            "host_scans": self.n_host_scans,
            "autoswitch": dict(self.autoswitch),
        }

    def drift_signals(self) -> dict:
        """The structural drift indicators (DESIGN.md §14): everything
        that ratchets or grows when the positioning transform stops
        fitting the keys — probe geometry, tier pressure, fold cadence —
        alongside the build-time AutoSwitch verdict.  The drift monitor's
        score is the trigger; these are the corroborating symptoms."""
        s = self._serving
        return {
            "max_depth": int(self.max_depth),
            "static_max_depth": int(s.max_depth),
            "static_dense_window": int(s.dense_window),
            "run_window": int(s.run.window),
            "delta_window": int(s.delta.window),
            "delta_len": int(self._delta_pk.shape[0]),
            "run_len": int(self._run_pk.shape[0]),
            "run_ratio": float(self._run_pk.shape[0]
                               / max(self.n_keys, 1)),
            "fold_active": self._fold is not None,
            "reflow_active": (self._fold is not None
                              and self._fold.reflow is not None),
            "n_rebuilds": int(self.n_rebuilds),
            "n_reflows": int(self.n_reflows),
            "autoswitch": dict(self.autoswitch),
        }

    def reset_telemetry(self) -> None:
        """Zero the host fallback counters and the ServingState's
        upload/repack accounting (gauges and ratchets are state, not
        counters — they stay).  Pairs with ``fused_lookup_stats(reset=
        True)`` so multi-phase benches read per-phase counts."""
        self.n_host_tier_probes = 0
        self.n_host_scans = 0
        self._serving.reset_stats()

    def stats(self):
        """Structure + write-path counters (DESIGN.md §10–§12): pool
        sizes, tier lengths, fold state, rebuild/host-fallback counts,
        and the nested ``ServingState`` counters."""
        a = self.arrays
        return {
            "n_nodes": int(a.node_kind.shape[0]) if a is not None else 0,
            "n_entries": int(a.etype.shape[0]) if a is not None else 0,
            "n_buckets": int(a.blen.shape[0]) if a is not None else 0,
            "max_depth": self.max_depth,
            "n_keys": self.n_keys,
            "delta_len": int(self._delta_pk.shape[0]),
            "run_len": int(self._run_pk.shape[0]),
            "fold_active": self._fold is not None,
            "n_rebuilds": self.n_rebuilds,
            "n_reflows": self.n_reflows,
            "n_host_tier_probes": self.n_host_tier_probes,
            "n_host_scans": self.n_host_scans,
            "scan_pool_len": int(self._scan_pk.shape[0]),
            "serving": self._serving.stats(),
        }
