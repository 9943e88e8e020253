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
3. **tier-merged emission** — a three-way ordered merge by positioning
   key walks the three segments in lockstep for ``scan_cap`` steps,
   emitting payloads into fixed output lanes.  Per candidate, the two
   newer tiers are probed by exact 64-bit identity (the shared
   ``probe_pool``), so a superseded copy (re-insert, update, placement
   shadow) is dropped in favor of its newest version and a TOMBSTONE
   (-2) in any tier masks every older copy — deletes are range-invisible
   without any host round trip.

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
    probe_pool,
    select_tile,
)

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
    spk, shi, slo, spv = spool.pk, spool.hi, spool.lo, spool.pv
    s_len = spool.plen[0]
    rpk, rhi, rlo, rpv = tiers.run_pk, tiers.run_hi, tiers.run_lo, tiers.run_pv
    r_len = tiers.run_len[0]
    dpk, dhi, dlo, dpv = tiers.dl_pk, tiers.dl_hi, tiers.dl_lo, tiers.dl_pv
    d_len = tiers.dl_len[0]
    smax = spk.shape[0]
    rmax = rpk.shape[0]
    dmax = dpk.shape[0]

    # ---- (2) lower-bound both endpoints in every pool: [a, b) holds
    # exactly the pool entries with pk in [zlo, zhi) (searchsorted-left
    # on both ends; an inverted/empty range yields b <= a)
    s0 = lower_bound(spk, s_len, zlo, scan_iters)
    s1 = lower_bound(spk, s_len, zhi, scan_iters)
    if probe_tiers:
        r0 = lower_bound(rpk, r_len, zlo, run_iters)
        r1 = lower_bound(rpk, r_len, zhi, run_iters)
        d0 = lower_bound(dpk, d_len, zlo, delta_iters)
        d1 = lower_bound(dpk, d_len, zhi, delta_iters)
    else:
        r0 = r1 = d0 = d1 = jnp.zeros(zlo.shape, jnp.int32)
    total = (jnp.maximum(s1 - s0, 0) + jnp.maximum(r1 - r0, 0)
             + jnp.maximum(d1 - d0, 0))

    # ---- (3) three-way ordered merge, scan_cap lockstep rounds.  Each
    # round picks the per-lane minimum head key (ties prefer the newest
    # tier: delta > run > scan pool), probes the newer tiers for a
    # superseding copy of the candidate's identity, and compacts valid
    # payloads into the output lanes via a one-hot column write.
    col = jax.lax.broadcasted_iota(jnp.int32, (zlo.shape[0], scan_cap), 1)

    def merge_step(_, carry):
        it, ir, idl, cnt, out = carry
        t_ok = it < s1
        r_ok = ir < r1
        d_ok = idl < d1
        ti = jnp.clip(it, 0, smax - 1)
        ri = jnp.clip(ir, 0, rmax - 1)
        di = jnp.clip(idl, 0, dmax - 1)
        t_pk = jnp.where(t_ok, spk[ti], jnp.inf)
        r_pk = jnp.where(r_ok, rpk[ri], jnp.inf)
        d_pk = jnp.where(d_ok, dpk[di], jnp.inf)
        m = jnp.minimum(t_pk, jnp.minimum(r_pk, d_pk))
        any_c = m < jnp.inf
        pick_d = any_c & (d_pk == m)
        pick_r = any_c & ~pick_d & (r_pk == m)
        pick_t = any_c & ~pick_d & ~pick_r

        chi = jnp.where(pick_d, dhi[di], jnp.where(pick_r, rhi[ri], shi[ti]))
        clo = jnp.where(pick_d, dlo[di], jnp.where(pick_r, rlo[ri], slo[ti]))
        cpv = jnp.where(pick_d, dpv[di], jnp.where(pick_r, rpv[ri], spv[ti]))

        if probe_tiers:
            # per-candidate identity probe into the newer tiers — the
            # point path's exact machinery, so a placement shadow whose
            # stored key drifted 1 ulp from the scan pool's copy still
            # supersedes it (identity is the matcher, the key only the
            # locator).  Length-gated like the point kernel's tier_stage.
            miss = jnp.full(m.shape, -1, jnp.int32)

            def probe_delta(_):
                lb = lower_bound(dpk, d_len, m, delta_iters)
                return probe_pool(dhi, dlo, dpv, d_len, lb, dmax,
                                  delta_window, chi, clo)

            def probe_run(_):
                lb = lower_bound(rpk, r_len, m, run_iters)
                return probe_pool(rhi, rlo, rpv, r_len, lb, rmax,
                                  run_window, chi, clo)

            dl_pay = jax.lax.cond(d_len > 0, probe_delta,
                                  lambda _: miss, None)
            rn_pay = jax.lax.cond(r_len > 0, probe_run,
                                  lambda _: miss, None)
            superseded = ((pick_t & ((dl_pay != -1) | (rn_pay != -1)))
                          | (pick_r & (dl_pay != -1)))
        else:
            superseded = jnp.zeros(m.shape, jnp.bool_)

        valid = any_c & ~superseded & (cpv != TOMBSTONE)
        out = jnp.where((col == cnt[:, None]) & valid[:, None],
                        cpv[:, None], out)
        cnt = cnt + valid.astype(jnp.int32)
        it = it + pick_t.astype(jnp.int32)
        ir = ir + pick_r.astype(jnp.int32)
        idl = idl + pick_d.astype(jnp.int32)
        return it, ir, idl, cnt, out

    zero = jnp.zeros(zlo.shape, jnp.int32)
    out0 = jnp.full((zlo.shape[0], scan_cap), -1, jnp.int32)
    _, _, _, cnt, out = jax.lax.fori_loop(
        0, scan_cap, merge_step, (s0, r0, d0, zero, out0))

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
