"""Bytes the point and range routes need, counted from the index
structure and the queried keys: never from a kernel's loop bounds, its
padding or its tiles, so the count stays the same whatever implements
the route.  A roofline share is these bytes over the route's device time
over the chip's HBM bandwidth (``peaks.json``).

Point, per query: 16 bytes in and out (positioning key, two identity
words, the payload), then for each level the traversal visits the node
record (kind u8, slope, intercept, offset, size: 17 bytes) and the entry
it lands on (type u8, key, two identity words, payload, child: 21 bytes);
a dense node adds its binary-search probes (4 bytes each), and an entry
that is a conflict bucket adds the bucket's live slots (identity words
and payload, 12 bytes each) and its length word.  Each non-empty write
tier adds its lower-bound probes and one 16-byte entry.

Range, per query: 8 bytes of endpoints, the lower-bound probes into the
scan pool and into each non-empty write tier, and the candidates it
examines (up to ``scan_cap``), 16 bytes each read and 4 written.
"""

from __future__ import annotations

import math

import numpy as np

NODE_BYTES = 1 + 4 * 4
ENTRY_BYTES = 1 + 4 * 5
SLOT_BYTES = 3 * 4
QUERY_BYTES = 4 * 4
TIER_ENTRY_BYTES = 4 * 4

# entry types and node kinds of the flat index (core/flat_afli.py)
EMPTY, DATA, BUCKET, CHILD = 0, 1, 2, 3
KIND_MODEL, KIND_DENSE = 0, 1


def probes(n: int) -> int:
    """Lower-bound probes of a binary search over ``n`` sorted items."""
    return int(math.ceil(math.log2(n + 1))) if n > 0 else 0


def point_levels(a: dict, z: np.ndarray, max_levels: int = 64):
    """Replay the traversal on the host: per query, the nodes it
    visits.  Yields ``(node ids, entry ids, kinds)`` per level for the
    queries still descending."""
    z = np.asarray(z, np.float32)
    q = np.arange(z.shape[0])
    node = np.zeros(z.shape[0], np.int64)
    for _ in range(max_levels):
        if not q.size:
            return
        nd = node[q]
        kind = a["node_kind"][nd]
        off = a["node_offset"][nd].astype(np.int64)
        size = a["node_size"][nd].astype(np.int64)
        slot = np.rint(a["node_slope"][nd] * z[q]
                       + a["node_intercept"][nd]).astype(np.int64)
        e = off + np.clip(slot, 0, np.maximum(size - 1, 0))
        dense = kind == KIND_DENSE
        for i in np.flatnonzero(dense):
            ek = a["ekey"][off[i]:off[i] + size[i]]
            j = int(np.searchsorted(ek, z[q[i]], side="left"))
            e[i] = off[i] + min(j, max(size[i] - 1, 0))
        yield nd, e, kind, size
        deeper = (~dense) & (a["etype"][e] == CHILD)
        node[q[deeper]] = a["echild"][e[deeper]]
        q = q[deeper]


def point_bytes(a: dict, z: np.ndarray, tier_lens=()) -> int:
    """Bytes the point route needs for the queries ``z`` (positioning
    keys) over the structure ``a`` (``FlatArrays`` fields as numpy)."""
    total = QUERY_BYTES * int(np.asarray(z).shape[0])
    for nd, e, kind, size in point_levels(a, z):
        total += (NODE_BYTES + ENTRY_BYTES) * nd.shape[0]
        dense = kind == KIND_DENSE
        total += 4 * sum(probes(int(s)) for s in size[dense])
        bucket = (~dense) & (a["etype"][e] == BUCKET)
        if bucket.any():
            blen = a["blen"][a["echild"][e[bucket]]].astype(np.int64)
            total += int((4 + SLOT_BYTES * blen).sum())
    per_tier = sum(4 * probes(int(t)) + TIER_ENTRY_BYTES
                   for t in tier_lens if t)
    return total + per_tier * int(np.asarray(z).shape[0])


def range_bytes(pool_len: int, tier_lens, candidates: np.ndarray,
                cap: int) -> int:
    """Bytes the range route needs for ranges whose candidate counts
    are ``candidates``."""
    c = np.minimum(np.asarray(candidates, np.int64), cap)
    lb = 4 * probes(pool_len) + sum(4 * probes(int(t)) for t in tier_lens
                                    if t)
    return int(c.shape[0] * (8 + lb) + (TIER_ENTRY_BYTES + 4) * c.sum())


# ----------------------------------------------------- for a live index
def _positioning(nfl, keys: np.ndarray) -> np.ndarray:
    if not nfl.use_flow:
        return np.asarray(keys, np.float64).astype(np.float32)
    from repro.kernels.ops import nf_transform_keys

    z = nf_transform_keys(nfl.flow_params, nfl.normalizer, keys,
                          nfl.cfg.flow)
    return np.asarray(z, np.float64).astype(np.float32)


def structure(nfl) -> dict:
    arr = nfl.index.arrays
    return {f: np.asarray(getattr(arr, f)) for f in arr._fields}


def traced_point_bytes(nfl, traced: dict) -> int | None:
    keys = traced.get("point_keys") or []
    if not keys:
        return None
    k = np.concatenate(keys)
    st = nfl.index.stats()
    return point_bytes(structure(nfl), _positioning(nfl, k),
                       (st["delta_len"], st["run_len"]))


def traced_range_bytes(nfl, traced: dict, load_keys) -> int | None:
    lo = traced.get("range_lo") or []
    if not lo:
        return None
    lo = np.concatenate(lo)
    hi = np.concatenate(traced["range_hi"])
    pool = np.sort(_positioning(nfl, load_keys))
    cand = (np.searchsorted(pool, _positioning(nfl, hi), side="left")
            - np.searchsorted(pool, _positioning(nfl, lo), side="left"))
    st = nfl.index.stats()
    return range_bytes(st["scan_pool_len"], (st["delta_len"], st["run_len"]),
                       np.maximum(cand, 0), int(nfl.cfg.flat_index.scan_cap))
