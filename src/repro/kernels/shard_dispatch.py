"""Shard router for key-space-partitioned serving (DESIGN.md §13).

The key domain is split into P contiguous shards in *positioning-key
space* (z-space when the flow is on): shard ``s`` owns ``[B[s-1], B[s])``
for a sorted f32 boundary vector ``B`` of length P-1 (implicit -inf /
+inf sentinels at the ends).  Boundaries are chosen from the CDF of the
trained flow — equal-mass quantiles of the transformed build keys — so
shards are balanced in z-space no matter how skewed the raw keys are
(Kraska et al.'s top-level dispatcher, realized as a binary search over
P-1 floats instead of a learned sub-model: for contiguous balanced
partitions the CDF quantiles ARE the optimal top-level model).

Routing is **jit-fused**: one compiled dispatch takes a query batch and
emits ``(z, shard_id)`` — with the flow on, the NF forward
(``nf_forward_pallas``, the same fixed-tile kernel that positioned the
build) and the boundary lower-bound run inside a single jit computation,
so the router costs one dispatch regardless of P.  The per-query work is
a [B]-lane ``searchsorted`` over P-1 boundaries — O(log P) vector ops —
which is why the router is jnp inside jit rather than a dedicated Pallas
kernel: the NF forward dominates, and it already IS one.

The host-side helpers (`bin_by_shard`, `split_ranges`) turn routed ids
into the per-shard fan-out plan: stable binning that preserves intra-
shard request order (writes stay age-ordered per shard) plus the inverse
permutation that restores input order at gather time, and per-shard
sub-range splitting for range queries that straddle a boundary.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "choose_boundaries",
    "refresh_boundaries",
    "route",
    "route_flow",
    "bin_by_shard",
    "fanout_plan",
    "split_ranges",
]


def choose_boundaries(pk32_sorted: np.ndarray, n_shards: int) -> np.ndarray:
    """Equal-mass shard boundaries from the build snapshot's CDF.

    ``pk32_sorted``: the f32 positioning keys (the flow's z values when
    the flow is on) in ascending order — their empirical CDF is the
    trained flow's CDF over the keyset.  Returns f32[``n_shards - 1``]
    ascending boundaries at the ``s / n_shards`` quantiles; shard ``s``
    owns ``[B[s-1], B[s])``.  Duplicate-heavy keysets can yield equal
    boundaries (an empty shard), which the serving layer tolerates —
    balance degrades, correctness does not.
    """
    n = int(pk32_sorted.shape[0])
    P = int(n_shards)
    if P < 2:
        return np.empty(0, np.float32)
    idx = (np.arange(1, P, dtype=np.int64) * n) // P
    b = np.asarray(pk32_sorted, np.float32)[np.clip(idx, 0, max(n - 1, 0))]
    return np.ascontiguousarray(b, np.float32)


@jax.jit
def _splice_boundaries(boundaries: jnp.ndarray, interior: jnp.ndarray,
                       lo: jnp.ndarray) -> jnp.ndarray:
    """Value-only boundary refresh for a §18 migration swap: write the
    window's ``k - 1`` new interior boundaries over positions
    ``lo .. lo + k - 2`` of the f32[P-1] boundary vector.  The window
    offset rides as a TRACED scalar (``dynamic_update_slice`` start),
    and the output length equals the input length — so this dispatch,
    and every downstream consumer of the refreshed vector
    (``_route_flow`` takes boundaries as a traced argument), reuses its
    compiled trace no matter which window migrates.  The §17 streamed
    router is untouched by construction: its shape derives from pool
    capacity, never from boundary values."""
    return jax.lax.dynamic_update_slice(boundaries, interior, (lo,))


def refresh_boundaries(boundaries, interior, lo: int) -> np.ndarray:
    """Host wrapper for the migration-swap boundary splice: validate the
    window, run the jitted ``_splice_boundaries``, and check that the
    refreshed vector is still non-decreasing (a splice that breaks the
    routing order would silently mis-route every query past the window —
    fail loudly instead; the §18 coordinator derives interior boundaries
    from the window's own key mass, which cannot cross the outer
    boundaries, so this never trips in normal operation).  Returns the
    new f32[P-1] host vector; the caller republishes the device copy."""
    b = np.asarray(boundaries, np.float32)
    it = np.asarray(interior, np.float32)
    lo = int(lo)
    if it.shape[0] == 0:
        return b.copy()
    if lo < 0 or lo + it.shape[0] > b.shape[0]:
        raise ValueError(
            f"boundary splice [{lo}, {lo + it.shape[0]}) outside the "
            f"boundary vector of length {b.shape[0]}")
    out = np.asarray(_splice_boundaries(
        jnp.asarray(b), jnp.asarray(it), jnp.asarray(lo, jnp.int32)))
    if out.shape[0] > 1 and np.any(np.diff(out) < 0):
        raise ValueError("boundary splice breaks routing monotonicity")
    return np.ascontiguousarray(out, np.float32)


def route(z32: np.ndarray, boundaries) -> np.ndarray:
    """Route positioning keys (flow off, or pre-transformed z) to shard
    ids: the boundary lower-bound count (#B <= z).  Pure host numpy —
    P-1 floats do not warrant a device dispatch, and the f32
    ``searchsorted`` semantics are identical to the fused router's
    in-jit binning (``route_flow``), so the two routes can never
    disagree.  Empty boundaries = one shard."""
    z32 = np.asarray(z32, np.float32)
    if boundaries is None or boundaries.shape[0] == 0:
        return np.zeros(z32.shape[0], np.int32)
    return np.searchsorted(np.asarray(boundaries, np.float32), z32,
                           side="right").astype(np.int32)


@functools.partial(jax.jit, static_argnames=("dim", "shapes", "interpret"))
def _route_flow(feats: jnp.ndarray, packed_w: jnp.ndarray,
                boundaries: jnp.ndarray, *, dim: int, shapes,
                interpret: bool = False):
    """Fused NF forward + boundary lower-bound: ONE compiled dispatch
    from raw query features to (z, shard id).  The NF runs through
    ``nf_forward_pallas`` — the same fixed-``DEFAULT_TILE`` kernel that
    produced the build-time positioning keys (``ops.nf_transform_keys``)
    — so the routed z is bit-identical to the z each shard was built
    and is probed with (§8/§13: one NF path end to end, no in-kernel
    re-materialization hazard on the sharded route)."""
    from repro.kernels.nf_forward import nf_forward_pallas

    z = nf_forward_pallas(feats, packed_w, shapes, dim, interpret=interpret)
    # searchsorted-right as compare-and-count (#B <= z): P-1 boundaries
    # are a handful of lanes, and the count needs no clamped gather
    sid = jnp.sum(boundaries[None, :] <= z[:, None], axis=1)
    return z, sid.astype(jnp.int32)


def route_flow(feats: np.ndarray, packed_w, shapes,
               boundaries) -> Tuple[np.ndarray, np.ndarray]:
    """Flow-on routing: expanded query features -> ``(z f32[n],
    shard_id i32[n])`` in one fused dispatch.  Pads the batch to the
    shared power-of-two bucket (``backend.pow2_batch``) so ragged
    request sizes reuse a bounded set of traces, exactly like the
    per-shard serve dispatches."""
    from repro.kernels.backend import pow2_batch, should_interpret

    interpret = should_interpret()
    feats = np.asarray(feats, np.float32)
    n = feats.shape[0]
    n_pad = pow2_batch(n)
    if n_pad != n:
        feats = np.pad(feats, ((0, n_pad - n), (0, 0)))
    if boundaries is None or boundaries.shape[0] == 0:
        from repro.kernels.nf_forward import nf_forward_pallas

        z = nf_forward_pallas(jnp.asarray(feats), packed_w, shapes,
                              feats.shape[1], interpret=interpret)
        return np.asarray(z)[:n], np.zeros(n, np.int32)
    z, sid = _route_flow(jnp.asarray(feats), packed_w,
                         jnp.asarray(boundaries), dim=feats.shape[1],
                         shapes=tuple(shapes), interpret=interpret)
    return np.asarray(z)[:n], np.asarray(sid)[:n]


def bin_by_shard(sids: np.ndarray, n_shards: int
                 ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Fan-out plan from routed shard ids.

    Returns ``(order, counts, inv)``: ``order`` is a stable permutation
    grouping queries by shard (shard-major, input order *within* each
    shard preserved — per-shard write batches stay age-ordered, which
    the tiers' last-write-wins dedup relies on); ``counts[s]`` is shard
    s's group length (group s occupies
    ``order[counts[:s].sum() : counts[:s+1].sum()]``); ``inv`` is the
    inverse permutation — ``gathered[inv]`` restores input order from
    shard-major results."""
    sids = np.asarray(sids)
    order = np.argsort(sids, kind="stable")
    counts = np.bincount(sids, minlength=n_shards).astype(np.int64)
    inv = np.empty_like(order)
    inv[order] = np.arange(order.shape[0])
    return order, counts, inv


def fanout_plan(sids: np.ndarray, n_shards: int
                ) -> Tuple[list, np.ndarray]:
    """``bin_by_shard`` unrolled into per-shard segments.

    Returns ``(segments, inv)``: ``segments[s]`` is the stable index
    array of the queries routed to shard ``s`` (input order preserved
    within the shard — write batches stay age-ordered), and ``inv``
    restores input order from the shard-major concatenation of
    non-empty segment results.  Every fan-out call site walks this
    exact plan, so the offset arithmetic lives in one place."""
    order, counts, inv = bin_by_shard(sids, int(n_shards))
    offs = np.zeros(int(n_shards) + 1, np.int64)
    np.cumsum(counts, out=offs[1:])
    segs = [order[offs[s]:offs[s + 1]] for s in range(int(n_shards))]
    return segs, inv


def split_ranges(zlo: np.ndarray, zhi: np.ndarray, boundaries
                 ) -> Tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
    """Split ``[zlo, zhi)`` range queries at shard boundaries.

    A range that straddles boundaries becomes one sub-range per touched
    shard: shard ``s`` in ``[first, last]`` gets
    ``[max(zlo, B[s-1]), min(zhi, B[s]))`` — the sub-ranges tile the
    original half-open interval exactly, and because every shard's pools
    hold only in-domain keys the per-shard scans are disjoint and their
    shard-ordered concatenation is the global positioning-key order
    (DESIGN.md §13 merge semantics).

    Returns flat sub-query arrays ``(qid i64[m], sid i32[m],
    sub_lo f32[m], sub_hi f32[m])``, shard-id ascending within each
    query; empty ranges (``zhi <= zlo``) contribute no sub-queries.
    """
    zlo = np.asarray(zlo, np.float32)
    zhi = np.asarray(zhi, np.float32)
    B = (np.empty(0, np.float32) if boundaries is None
         else np.asarray(boundaries, np.float32))
    nonempty = zhi > zlo
    # first shard touched: lower-bound of zlo (#B <= zlo); last shard
    # touched: #B < zhi (a range ending exactly AT a boundary does not
    # touch the shard that starts there)
    first = np.searchsorted(B, zlo, side="right").astype(np.int64)
    last = np.searchsorted(B, zhi, side="left").astype(np.int64)
    spans = np.where(nonempty, last - first + 1, 0)
    qid = np.repeat(np.arange(zlo.shape[0], dtype=np.int64), spans)
    excl = np.cumsum(spans) - spans  # exclusive cumsum, shape-safe at n=0
    step = np.arange(int(spans.sum()), dtype=np.int64) - np.repeat(excl, spans)
    sid = (np.repeat(first, spans) + step).astype(np.int32)
    # clip each sub-range to its shard's domain [B[s-1], B[s])
    ext = np.concatenate([[-np.inf], B, [np.inf]]).astype(np.float32)
    sub_lo = np.maximum(zlo[qid], ext[sid])
    sub_hi = np.minimum(zhi[qid], ext[sid + 1])
    return qid, sid, sub_lo, sub_hi
