"""Pallas TPU kernel: fused single-dispatch range scan (DESIGN.md §12).

A batch of ``[lo, hi)`` range queries is answered in ONE ``pallas_call``,
end to end:

1. **NF forward on both endpoints** — ``positioning_keys``, the build
   transform's own ``nf_forward_pallas``, inside the same jit, so the
   endpoint positioning keys are bit-equal to the build transform's;
2. **lower-bound location** — each endpoint is located in three sorted
   pools with the shared bounded binary search (``lower_bound``): the
   *scan pool* (the static structure's keys flattened to rank order —
   the sorted leaf level the tree's precise placement defines, packed
   once per build/fold swap into a persistent device buffer), the
   compacted run, and the active delta;
3. **rank merge** — the three segments merge in one vectorised pass,
   in (pk, newest tier, in-tier index) order: the first ``scan_cap``
   candidates of that order are *examined*.  Only the first ``scan_cap``
   entries of a segment can be, so each tier gives one row of pool
   blocks per query (a block gather, one index per 128 entries).  A
   candidate's merged rank is its in-tier offset plus, per other tier,
   the segment entries before it (``pk <=`` its key for a newer tier,
   ``pk <`` for an older one), counted by a compare fused into a row
   reduction.  Each candidate of an older tier is checked once against
   every newer tier by exact 64-bit identity, inside the same window
   around its lower bound that the point path's ``probe_pool`` scans,
   so a superseded copy (re-insert, update, placement shadow) is
   dropped in favor of its newest version and a TOMBSTONE (-2) in any
   tier masks every older copy — deletes are range-invisible without
   any host round trip.  A surviving examined candidate's output lane
   is the number of live candidates merged before it.  No step loops
   over ``scan_cap``; batches whose compares outgrow ``_MERGE_BUDGET``
   run as a loop over lane chunks, so memory stays linear in the batch
   (the compares grow with ``scan_cap``^2 per lane).

Range semantics are over the **positioning-key order** — the index's
native sort order.  Without a flow that is the key order itself (the f32
cast is monotone); with a flow it is the transformed order, which
matches key order whenever the trained NF is monotone over the keyset.
``scan_cap`` bounds per-query *work*: the merge examines at most
``scan_cap`` candidates (live + superseded + tombstoned), so a truncated
query (``total > scan_cap``, reported per query) may return fewer
results than exist; callers re-issue with a larger cap or fall back to
the host oracle.

Grid: (ceil(B / TILE),) — the same tiled-grid machinery as
``kernels/fused_lookup``: query tiles stream, pools ride as
grid-invariant VMEM blocks, and all static bounds (pool iteration
counts, probe windows, ``scan_cap``) come ratcheted from the
``ServingState`` so steady-state range traffic cannot retrace.

Steps 2-3 index the pools with vector gathers, which Mosaic does not
lower, so the ``pallas_call`` runs in interpret mode only.  On a
compiled TPU backend ``xla_range_scan`` runs the SAME body
(``scan_merge``) as one jitted XLA program over the same device pools
(DESIGN.md §2) — one code path, two lowerings.
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.backend import resolve_interpret
from repro.kernels.fused_lookup import (
    TOMBSTONE,
    TierPools,
    empty_tiers,
    lower_bound,
    positioning_keys,
    select_tile,
)

# elements that the largest compare of one rank-merge chunk may hold: a
# 64-lane serve batch at scan_cap 128 is one chunk; larger batches and
# caps loop over lane chunks, in memory linear in the batch
_MERGE_BUDGET = 1 << 24

__all__ = ["fused_range_scan_pallas", "xla_range_scan", "scan_merge",
           "ScanPool", "ScanPack"]


class ScanPool(NamedTuple):
    """The static structure's keys in rank (sorted positioning-key)
    order: one lane-padded sorted pool of (pk, identity bits, payload)
    plus a length lane — the same layout as one write tier, packed once
    per build/fold swap into a persistent bucketed device buffer."""

    pk: jnp.ndarray    # f32[S]  sorted positioning keys (+inf padded)
    hi: jnp.ndarray    # u32[S]  identity bits
    lo: jnp.ndarray    # u32[S]
    pv: jnp.ndarray    # i32[S]
    plen: jnp.ndarray  # i32[lane]  built length at [0]

    def nbytes(self) -> int:
        return int(sum(a.size * a.dtype.itemsize for a in self))


class ScanPack(NamedTuple):
    """ScanPool plus its static lower-bound iteration count."""

    pool: ScanPool
    iters: int

    def nbytes(self) -> int:
        return self.pool.nbytes()


def _pool_rows(pool, lo, size: int):
    """Per lane, the pool entries ``[lo, lo + size)`` (clamped into the
    pool) as rows of whole aligned blocks of each array of ``pool``, and
    each row entry's pool index: a block gather, one index per block."""
    nmax = pool[0].shape[0]
    blk = math.gcd(nmax, 128)
    nblk = min(-(-size // blk) + 1, nmax // blk)
    first = jnp.clip(lo // blk, 0, nmax // blk - nblk)
    ids = first[:, None] + jax.lax.broadcasted_iota(
        jnp.int32, (lo.shape[0], nblk), 1)
    rows = [x.reshape(-1, blk)[ids].reshape(lo.shape[0], nblk * blk)
            for x in pool]
    pos = (first * blk)[:, None] + jax.lax.broadcasted_iota(
        jnp.int32, (lo.shape[0], nblk * blk), 1)
    return rows, pos


def _row_width(size: int) -> int:
    """Upper bound on the width of ``_pool_rows``' rows for ``size``."""
    return (-(-size // 128) + 1) * 128


class _Segment(NamedTuple):
    """One tier's examinable candidates for a batch: rows of its pool
    holding the first ``scan_cap`` entries of ``[a, b)`` (``real``), with
    each row entry's in-tier offset ``j``."""

    pk: jnp.ndarray
    hi: jnp.ndarray
    lo: jnp.ndarray
    pv: jnp.ndarray
    j: jnp.ndarray     # i32[B, W]  pool index - a
    real: jnp.ndarray  # bool[B, W]
    n: jnp.ndarray     # i32[B]  real entries


def _segment(pool, a, b, scan_cap: int) -> _Segment:
    (pk, hi, lo, pv), pos = _pool_rows(pool, a, scan_cap)
    j = pos - a[:, None]
    n = jnp.clip(b - a, 0, scan_cap)
    real = (j >= 0) & (j < n[:, None])
    return _Segment(pk, hi, lo, pv, j, real, n)


def _before(seg: _Segment, mask, pk, *, inclusive: bool):
    """Per candidate key ``pk[B, M]``, how many ``mask``ed entries of
    ``seg`` hold a smaller key (``<=`` when ``inclusive``): a compare
    that XLA fuses into its row reduction."""
    e = seg.pk[:, None, :]
    x = pk[:, :, None]
    lt = (e <= x) if inclusive else (e < x)
    return jnp.sum(lt & mask[:, None, :], axis=2, dtype=jnp.int32)


def _held_by(ids, a, plen, window: int, newer: _Segment, cands,
             scan_cap: int):
    """Whether the newer tier (identity arrays ``ids``) holds each
    candidate's identity, for the candidates of the segments ``cands``,
    exactly as ``probe_pool`` finds it: a match in ``[lb - window,
    lb + 3*window)`` around the candidate's lower bound ``lb`` in that
    tier.  For every candidate the merge examines, ``lb`` is ``a`` plus
    the ``newer`` segment's entries below its key, so the probe rows lie
    in ``[a - window, a + scan_cap + 3*window)``: dense compares."""
    (hi, lo), pos = _pool_rows(ids, a - window, scan_cap + 4 * window)
    ck, chi, clo = (jnp.concatenate([getattr(c, f) for c in cands], axis=1)
                    for f in ("pk", "hi", "lo"))
    lb = a[:, None] + _before(newer, newer.real, ck, inclusive=False)
    p = pos[:, None, :]
    near = ((p >= (lb - window)[:, :, None])
            & (p < (lb + 3 * window)[:, :, None]) & (p < plen))
    same = ((hi[:, None, :] == chi[:, :, None])
            & (lo[:, None, :] == clo[:, :, None]))
    return jnp.any(near & same, axis=2)


def _rank_merge(bounds, *, spool: ScanPool, tiers: TierPools, scan_cap: int,
                probe_tiers: bool, run_window: int, delta_window: int):
    """Steps (3)-(4) of ``scan_merge`` for the lanes whose ``[a, b)``
    bounds in the scan pool, run and delta are ``bounds``: ``(payloads
    i32[R, scan_cap], counts i32[R])``."""
    s0, s1, r0, r1, d0, d1 = bounds
    t = tiers

    # ---- (3) rank merge.  Merged order is (pk, newest tier, in-tier
    # index); only the first scan_cap entries of a tier's [a, b) can be
    # among the first scan_cap merged candidates (the examined ones).  A
    # candidate's merged rank is its in-tier offset plus, per other
    # tier, that tier's entries merged before it.
    segs = [_segment(spool[:4], s0, s1, scan_cap)]
    if probe_tiers:
        delta = (t.dl_pk, t.dl_hi, t.dl_lo, t.dl_pv)
        run = (t.run_pk, t.run_hi, t.run_lo, t.run_pv)
        segs = [_segment(delta, d0, d1, scan_cap),
                _segment(run, r0, r1, scan_cap)] + segs
    held = [jnp.zeros(seg.real.shape, jnp.bool_) for seg in segs]
    if probe_tiers:
        # identity probes into the newer tiers — the point path's
        # matching rule, so a placement shadow whose stored key drifted
        # 1 ulp from the scan pool's copy still supersedes it (identity
        # is the matcher, the key only the locator)
        dl, rn, sp = segs
        in_dl = _held_by(delta[1:3], d0, t.dl_len[0], delta_window, dl,
                         (rn, sp), scan_cap)
        in_rn = _held_by(run[1:3], r0, t.run_len[0], run_window, rn, (sp,),
                         scan_cap)
        w = rn.pk.shape[1]
        held = [held[0], in_dl[:, :w], in_dl[:, w:] | in_rn]
    live = [seg.real & ~h & (seg.pv != TOMBSTONE)
            for seg, h in zip(segs, held)]

    # ---- (4) compaction: a valid candidate's output lane is the number
    # of live candidates merged before it
    lanes, valid = [], []
    for i, seg in enumerate(segs):
        rank = seg.j
        lane = jnp.cumsum(live[i], axis=1, dtype=jnp.int32) - live[i]
        for u, other in enumerate(segs):
            if u != i:
                # a newer tier's equal keys merge first, an older one's after
                rank = rank + _before(other, other.real, seg.pk,
                                      inclusive=u < i)
                lane = lane + _before(other, live[u], seg.pk,
                                      inclusive=u < i)
        lanes.append(lane)
        valid.append(live[i] & (rank < scan_cap))
    lane = jnp.concatenate(lanes, axis=1)
    valid = jnp.concatenate(valid, axis=1)
    pay = jnp.concatenate([seg.pv for seg in segs], axis=1)
    cnt = jnp.sum(valid, axis=1, dtype=jnp.int32)
    col = jax.lax.broadcasted_iota(jnp.int32, (1, scan_cap, 1), 1)
    hit = valid[:, None, :] & (lane[:, None, :] == col)
    out = jnp.sum(jnp.where(hit, pay[:, None, :], 0), axis=2)
    return jnp.where(col[:, :, 0] < cnt[:, None], out, -1), cnt


def scan_merge(zlo, zhi, spool: ScanPool, tiers: TierPools, *,
               scan_cap: int, scan_iters: int, probe_tiers: bool,
               run_iters: int, run_window: int, delta_iters: int,
               delta_window: int):
    """Range queries ``[zlo, zhi)`` -> ``(payloads i32[B, scan_cap],
    counts i32[B], totals i32[B])`` over the scan pool merged with the
    write tiers.  Pure array code: the kernel body runs it on one query
    tile, ``xla_range_scan`` on the whole batch.

    Mirrors ``repro.core.flat_afli._range_scan_host`` candidate-for-
    candidate (the host oracle); any change here must keep the parity
    tests bit-exact.
    """
    # ---- (2) lower-bound both endpoints in every pool: [a, b) holds
    # exactly the pool entries with pk in [zlo, zhi) (searchsorted-left
    # on both ends; an inverted/empty range yields b <= a)
    ends = jnp.concatenate([zlo, zhi])
    s0, s1 = jnp.split(
        lower_bound(spool.pk, spool.plen[0], ends, scan_iters), 2)
    if probe_tiers:
        r0, r1 = jnp.split(lower_bound(tiers.run_pk, tiers.run_len[0], ends,
                                       run_iters), 2)
        d0, d1 = jnp.split(lower_bound(tiers.dl_pk, tiers.dl_len[0], ends,
                                       delta_iters), 2)
    else:
        r0 = r1 = d0 = d1 = jnp.zeros(zlo.shape, jnp.int32)
    total = (jnp.maximum(s1 - s0, 0) + jnp.maximum(r1 - r0, 0)
             + jnp.maximum(d1 - d0, 0))

    # ---- (3)-(4) rank merge, over lane chunks whose largest compare
    # fits _MERGE_BUDGET
    bounds = (s0, s1, r0, r1, d0, d1)
    merge = functools.partial(
        _rank_merge, spool=spool, tiers=tiers, scan_cap=scan_cap,
        probe_tiers=probe_tiers, run_window=run_window,
        delta_window=delta_window)
    b = zlo.shape[0]
    # per lane: the rank compares [seg, seg], the delta's identity
    # check [2 seg, probe] and the output's one-hot [scan_cap, 3 seg]
    seg = _row_width(scan_cap)
    per_row = max(seg, scan_cap * (3 if probe_tiers else 1)) * seg
    if probe_tiers:
        probe = _row_width(scan_cap + 4 * max(run_window, delta_window))
        per_row = max(per_row, 2 * seg * probe)
    rows = math.gcd(b, 1 << max(_MERGE_BUDGET // per_row, 1).bit_length() - 1)
    if rows == b:
        out, cnt = merge(bounds)
    else:
        def chunk(bnd):
            # a chunk of empty ranges (the batch's padding) merges nothing
            s0, s1, r0, r1, d0, d1 = bnd
            some = jnp.any((s1 > s0) | (r1 > r0) | (d1 > d0))
            return jax.lax.cond(some, merge, lambda _: (
                jnp.full((rows, scan_cap), -1, jnp.int32),
                jnp.zeros((rows,), jnp.int32)), bnd)

        out, cnt = jax.lax.map(chunk, tuple(x.reshape(-1, rows)
                                            for x in bounds))
        out, cnt = out.reshape(b, scan_cap), cnt.reshape(b)
    return out, cnt, total


def _kernel(zlo_ref, zhi_ref,
            spk_ref, shi_ref, slo_ref, spv_ref, slen_ref,
            rpk_ref, rhi_ref, rlo_ref, rpv_ref, rlen_ref,
            dpk_ref, dhi_ref, dlo_ref, dpv_ref, dlen_ref,
            pv_ref, cnt_ref, tot_ref, **statics):
    """One [TILE] tile of range queries -> [TILE, scan_cap] payloads
    (``scan_merge`` over the VMEM-resident pools)."""
    out, cnt, total = scan_merge(
        zlo_ref[...], zhi_ref[...],
        ScanPool(spk_ref[...], shi_ref[...], slo_ref[...], spv_ref[...],
                 slen_ref[...]),
        TierPools(rpk_ref[...], rhi_ref[...], rlo_ref[...], rpv_ref[...],
                  rlen_ref[...], dpk_ref[...], dhi_ref[...], dlo_ref[...],
                  dpv_ref[...], dlen_ref[...]),
        **statics)
    pv_ref[...] = out
    cnt_ref[...] = cnt
    tot_ref[...] = total

@functools.partial(
    jax.jit,
    static_argnames=("dim", "shapes", "scan_cap", "scan_iters", "use_flow",
                     "tile", "interpret", "probe_tiers", "run_iters",
                     "run_window", "delta_iters", "delta_window"),
)
def fused_range_scan_pallas(
    feats_lo: jnp.ndarray,
    feats_hi: jnp.ndarray,
    packed_w: jnp.ndarray,
    scan_pool: ScanPool,
    tiers: Optional[TierPools] = None,
    *,
    dim: int,
    shapes: Tuple[Tuple[int, int], ...] = (),
    scan_cap: int,
    scan_iters: int,
    use_flow: bool = True,
    tile: Optional[int] = None,
    interpret: Optional[bool] = None,
    probe_tiers: bool = False,
    run_iters: int = 1,
    run_window: int = 4,
    delta_iters: int = 1,
    delta_window: int = 4,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Fused tier-merged range scan in one ``pallas_call``.

    feats_lo/feats_hi: [B, d] f32 expanded endpoint features
    (``use_flow=True``) or [B, 1] positioning keys (``use_flow=False``);
    packed_w: [1, n] ``pack_flow_weights`` block (any [1, >=1] f32 array
    when ``use_flow=False``); scan_pool: the rank-ordered static keys
    (``ServingState.scan_pack``); tiers: the write tiers, probed and
    merged in-kernel when ``probe_tiers`` is set.

    Returns ``(payloads i32[B, scan_cap] (-1 padded), counts i32[B],
    totals i32[B], zlo f32[B], zhi f32[B])``: per query the first
    ``counts[b]`` payload lanes hold the live entries with positioning
    key in ``[zlo, zhi)`` in key order; ``totals[b] > scan_cap`` flags
    truncation (the merge examined only the first ``scan_cap``
    candidates).  Bit-identical to the host oracle
    (``FlatAFLI._range_scan_host``) by construction.
    """
    interpret = resolve_interpret(interpret)
    if tiers is None:
        probe_tiers = False
        tiers = empty_tiers()
    zlo = positioning_keys(feats_lo, packed_w, shapes, dim, use_flow,
                           interpret)
    zhi = positioning_keys(feats_hi, packed_w, shapes, dim, use_flow,
                           interpret)
    b = zlo.shape[0]
    tile = select_tile(b, tile, interpret)
    b_pad = ((b + tile - 1) // tile) * tile
    zlo_q, zhi_q = zlo, zhi
    if b_pad != b:
        # zero-padded lanes have identical endpoints -> empty ranges ->
        # zero counts; never observed by the caller's slice
        zlo_q = jnp.pad(zlo, (0, b_pad - b))
        zhi_q = jnp.pad(zhi, (0, b_pad - b))

    qspec = pl.BlockSpec((tile,), lambda i: (i,))
    ospec = pl.BlockSpec((tile, scan_cap), lambda i: (i, 0))

    def pool_spec(a):
        return pl.BlockSpec(a.shape, lambda i: (0,) * a.ndim)

    pv, cnt, tot = pl.pallas_call(
        functools.partial(
            _kernel, scan_cap=scan_cap, scan_iters=scan_iters,
            probe_tiers=probe_tiers, run_iters=run_iters,
            run_window=run_window, delta_iters=delta_iters,
            delta_window=delta_window,
        ),
        out_shape=(
            jax.ShapeDtypeStruct((b_pad, scan_cap), jnp.int32),
            jax.ShapeDtypeStruct((b_pad,), jnp.int32),
            jax.ShapeDtypeStruct((b_pad,), jnp.int32),
        ),
        grid=(b_pad // tile,),
        in_specs=[qspec, qspec]
        + [pool_spec(a) for a in scan_pool] + [pool_spec(a) for a in tiers],
        out_specs=(ospec, qspec, qspec),
        interpret=interpret,
    )(zlo_q, zhi_q, *scan_pool, *tiers)
    return pv[:b], cnt[:b], tot[:b], zlo, zhi


@functools.partial(
    jax.jit,
    static_argnames=("dim", "shapes", "scan_cap", "scan_iters", "use_flow",
                     "interpret", "probe_tiers", "run_iters", "run_window",
                     "delta_iters", "delta_window"),
)
def xla_range_scan(
    feats_lo: jnp.ndarray,
    feats_hi: jnp.ndarray,
    packed_w: jnp.ndarray,
    scan_pool: ScanPool,
    tiers: Optional[TierPools] = None,
    *,
    dim: int,
    shapes: Tuple[Tuple[int, int], ...] = (),
    scan_cap: int,
    scan_iters: int,
    use_flow: bool = True,
    interpret: bool = False,
    probe_tiers: bool = False,
    run_iters: int = 1,
    run_window: int = 4,
    delta_iters: int = 1,
    delta_window: int = 4,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """The range route of a compiled TPU backend: ``nf_forward_pallas``
    (compiled by Mosaic) on both endpoints, then ``scan_merge`` as XLA
    over the device-resident scan pool and write tiers — one jitted
    dispatch, bit-identical to ``fused_range_scan_pallas`` (same body)
    and to the host oracle.  Arguments as ``fused_range_scan_pallas``
    (``interpret`` applies to the NF kernel alone); returns
    ``(payloads, counts, totals)``."""
    if tiers is None:
        probe_tiers = False
        tiers = empty_tiers()
    zlo = positioning_keys(feats_lo, packed_w, shapes, dim, use_flow,
                           interpret)
    zhi = positioning_keys(feats_hi, packed_w, shapes, dim, use_flow,
                           interpret)
    return scan_merge(zlo, zhi, scan_pool, tiers, scan_cap=scan_cap,
                      scan_iters=scan_iters, probe_tiers=probe_tiers,
                      run_iters=run_iters, run_window=run_window,
                      delta_iters=delta_iters, delta_window=delta_window)
