"""Pallas TPU kernel: HBM-streaming lookup tier (DESIGN.md §17).

``fused_lookup`` dies the moment the packed tree pools outgrow the VMEM
budget: the whole read path used to fall back to the host oracle (two
dispatches + a gather-per-level jnp traversal + a host-side tier probe).
Learned indexes are pitched at key counts 10-100x past VMEM residency
(Kraska et al.; the SOSD benchmark's 200M-key datasets), so this module
keeps over-budget serving on a single ``pallas_call`` by *streaming* the
pool through VMEM instead of holding it resident:

1. **what streams** — the rank-ordered scan pool (DESIGN.md §12): the
   static structure's deduped (key, identity, payload) rows in sorted
   order, refreshed only at build / fold swap.  A point lookup against
   it (bounded lower-bound search + identity-window probe) returns
   exactly the tree traversal's payload, because the pool *is* the tree
   contents in rank order — so streaming the pool replaces streaming
   the (pointer-chasing, layout-hostile) node/entry/bucket pools.
2. **how it streams** — a 2-D grid ``(query_tiles, pool_tiles)`` with
   the pool arrays blocked ``[stream_tile]`` along the *inner* grid
   axis.  Pallas's pipeline emitter double-buffers revolving blocks:
   while the kernel probes tile ``t`` the DMA engine is already copying
   tile ``t+1`` HBM→VMEM (the ``emit_pipeline`` pattern), so the probe
   compute rides under the copy latency.  Only ``2 * stream_tile`` rows
   of the pool ever occupy VMEM — the budget bills the per-tile working
   set, not the whole pool.
3. **what stays resident** — the query/output blocks, the write tiers (run + delta, probed in-kernel at the final pool
   tile with the same newest-copy-wins precedence as ``fused_lookup``),
   and a small *router* vector: the first key of every
   ``STREAM_ALIGN``-row slice of the pool.  The router gates each pool
   tile — a tile whose key span cannot contain any query key (±2 ulp
   slack for NF re-materialization drift) skips its search/probe
   compute entirely, so a tight query batch pays for the tiles it
   lands in, not the whole stream.
4. **accumulation** — per query, the best (largest) matching global
   pool index + its payload accumulate across pool tiles in output
   blocks whose index map ignores the inner axis (they stay pinned in
   VMEM for the whole inner sweep).  Global index order is insertion
   order, so max-index == newest — identical tie semantics to
   ``probe_pool`` and the host ``_probe_sorted_pool`` oracle.

Correctness does not depend on the router gate or on which tile a
query's lower bound lands in: matching is by exact 64-bit identity, so
probing a tile never false-positives, and the per-tile window scan
(``window`` = pow2-rounded max equal-key run of the whole pool) covers
any run portion inside one tile by the same backward-W / forward-3W
argument as ``probe_pool``.  Results are bit-identical to
``fused_lookup_pallas`` (tree traversal + tier probe) by construction;
the parity suite (tests/test_streamed.py) pins it.

The tile probe indexes the pool slice with vector gathers, which Mosaic
does not lower, so this rung runs in interpret mode only; a compiled TPU
backend serves every pool size through the XLA point route
(``flat_afli.xla_lookup``, DESIGN.md §2).
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
    _pow2ceil,
    empty_tiers,
    lower_bound,
    merge_tiers,
    positioning_keys,
    probe_pool_index,
    select_tile,
)
from repro.kernels.range_scan import ScanPool

__all__ = ["streamed_lookup_pallas", "StreamPack", "STREAM_ALIGN",
           "MIN_STREAM_TILE", "build_router", "router_len",
           "select_stream_tile", "stream_resident_parts"]

# Router granularity: one resident f32 key per STREAM_ALIGN pool rows.
# Pool capacity buckets are pow2 >= 128 (serving_state.pow2_bucket), so
# every bucket is trivially a whole number of stream tiles and fold
# swaps never repack for alignment; the router's *shape* is a function
# of the capacity bucket alone, so steady-state refreshes reuse the
# resident vector (zero-repack, DESIGN.md §11 discipline).
STREAM_ALIGN = 1024
# Smallest stream tile the budget fitter will propose (lane-aligned;
# below this the per-tile DMA is latency- not bandwidth-bound and the
# grid overhead dominates).  Tiles below STREAM_ALIGN simply run with
# the router gate compiled out.
MIN_STREAM_TILE = 128
_LANE = 128


class StreamPack(NamedTuple):
    """The streamed tier's dispatch bundle: the rank-ordered scan pool
    (streamed), its resident router vector, and the pool's duplicate-run
    window static (host-computed at build/fold-swap time)."""

    pool: ScanPool        # pk f32 / hi u32 / lo u32 / pv i32 [C] + plen
    router: jnp.ndarray   # f32[R] first key per STREAM_ALIGN slice (+inf pad)
    window: int           # pow2 max equal-key run of the pool

    def resident_nbytes(self) -> int:
        """Bytes that stay VMEM-resident for the whole call (router +
        length lane) — the streamed pool arrays bill per-tile instead."""
        return int(self.router.size * 4 + self.pool.plen.size * 4)


def router_len(capacity: int) -> int:
    """Lane-padded router length for a capacity-``C`` pool: one entry
    per whole ``STREAM_ALIGN`` slice plus the trailing sentinel.  The
    one padding rule shared by ``build_router`` and the static VMEM
    proof (``repro.analysis.vmem``)."""
    n_slices = max(int(capacity) // STREAM_ALIGN, 1)
    return ((n_slices + 1 + _LANE - 1) // _LANE) * _LANE


def build_router(pk: jnp.ndarray) -> jnp.ndarray:
    """Resident router vector for a capacity-``C`` sorted pool buffer:
    ``router[j] = pk[j * STREAM_ALIGN]`` for every whole slice, one
    trailing ``+inf`` sentinel (the gate reads ``router[t+1]`` as the
    next tile's first key), lane-padded with ``+inf``.  Shape depends
    on ``C`` only, so in-bucket refreshes keep one traced shape."""
    cap = int(pk.shape[0])
    n_slices = max(cap // STREAM_ALIGN, 1)
    n_pad = router_len(cap)
    router = jnp.full((n_pad,), jnp.inf, jnp.float32)
    step = STREAM_ALIGN if cap >= STREAM_ALIGN else cap
    heads = jax.lax.slice(pk, (0,), (n_slices * step,), (step,))
    return jax.lax.dynamic_update_slice(router, heads, (0,))


def stream_resident_parts(capacity: int, router_len: int, tier_bytes: int,
                          stream_tile: int, tile: int, dim: int):
    """The streamed call's VMEM bill as ``overflow_reason`` parts, in
    residency order: the per-query-tile blocks (feats f32[tile, dim],
    qhi/qlo u32, payload/best-index/best-payload i32, z f32), the
    write-tier pools at bucket capacity, the resident router + length
    lane, and the double-buffered pool tile pair (4 arrays x 4 B x
    ``stream_tile`` rows x 2 in-flight copies)."""
    del capacity
    return [
        ("query-block", tile * (dim + 6) * 4),
        ("write-tiers", int(tier_bytes)),
        ("stream-router", int(router_len) * 4 + _LANE * 4),
        ("stream-tiles", 2 * 4 * 4 * int(stream_tile)),
    ]


def select_stream_tile(capacity: int, budget: int, resident_bytes: int,
                       floor: int = MIN_STREAM_TILE) -> Optional[int]:
    """Largest pow2 stream tile (``floor`` .. ``capacity``) whose
    double-buffered pair fits the budget after the resident bill, or
    ``None`` when even the floor tile does not fit (the resident top
    levels alone exceed the budget — streaming cannot run)."""
    cap = int(capacity)
    if cap <= 0:
        return None
    best = None
    t = min(_pow2ceil(max(int(floor), 1)), _pow2ceil(cap))
    while t <= cap:
        if int(resident_bytes) + 2 * 4 * 4 * t <= int(budget):
            best = t
        t *= 2
    return best


def _ord_f32(x: jnp.ndarray) -> jnp.ndarray:
    """Total-order int32 image of f32 (monotone: a < b  =>  ord(a) <
    ord(b) for all non-NaN values incl. ±inf, ±0 mapping together), so
    the router gate can take ±ulp slack with integer arithmetic."""
    i = jax.lax.bitcast_convert_type(x, jnp.int32)
    return jnp.where(i < 0, jnp.int32(-2147483648) - i, i)


def _kernel(z_ref, qhi_ref, qlo_ref,
            spk_ref, shi_ref, slo_ref, spv_ref, slen_ref, router_ref,
            rpk_ref, rhi_ref, rlo_ref, rpv_ref, rlen_ref,
            dpk_ref, dhi_ref, dlo_ref, dpv_ref, dlen_ref,
            pay_ref, bi_ref, bp_ref, *,
            stream_tile: int, window: int, use_router: bool,
            probe_tiers: bool, run_iters: int, run_window: int,
            delta_iters: int, delta_window: int):
    """One (query tile, pool tile) grid step.

    The inner grid axis sweeps the pool tiles; the query/output blocks'
    index maps ignore it, so they stay VMEM-pinned across the sweep and
    act as per-query accumulators (best global index + payload).  The
    pool blocks revolve every inner step — Pallas's pipeline emitter
    double-buffers them, prefetching tile t+1 while this body probes
    tile t.
    """
    pt = pl.program_id(1)
    n_pt = pl.num_programs(1)

    @pl.when(pt == 0)
    def _init():
        bi_ref[...] = jnp.full(z_ref.shape, -1, jnp.int32)
        bp_ref[...] = jnp.full(z_ref.shape, -1, jnp.int32)

    qkey = z_ref[...]
    qhi = qhi_ref[...]
    qlo = qlo_ref[...]
    n_pool = slen_ref[...][0]

    base = pt * stream_tile
    t_live = jnp.clip(n_pool - base, 0, stream_tile)

    if use_router:
        # the resident router brackets this tile's key span: first key
        # of the tile .. first key of the next (sentinel +inf past the
        # end).  ±2 ulp ordered-int slack absorbs NF re-materialization
        # drift (the same 1-ulp bound the probe windows are built on).
        apt = stream_tile // STREAM_ALIGN
        rtr = router_ref[...]
        lo_k = _ord_f32(rtr[pt * apt]) - 2
        hi_k = _ord_f32(rtr[pt * apt + apt]) + 2
        mz = _ord_f32(qkey)
        relevant = jnp.any((mz >= lo_k) & (mz <= hi_k))
    else:
        relevant = jnp.bool_(True)

    @pl.when((t_live > 0) & relevant)
    def _probe_tile():
        # local lower bound within the (sorted, +inf-padded) tile slice,
        # then the shared identity-window probe; a match's window-local
        # coverage follows probe_pool's backward-W / forward-3W argument
        # because any equal-run portion inside one tile is <= window.
        iters = max(int(stream_tile).bit_length(), 1)
        l_loc = lower_bound(spk_ref[...], t_live, qkey, iters)
        last = probe_pool_index(shi_ref[...], slo_ref[...], t_live, l_loc,
                                stream_tile, window, qhi, qlo)
        pay = spv_ref[...][jnp.clip(last, 0, stream_tile - 1)]
        gidx = jnp.where(last >= 0, base + last, -1)
        better = gidx > bi_ref[...]
        bp_ref[...] = jnp.where(better, pay, bp_ref[...])
        bi_ref[...] = jnp.where(better, gidx, bi_ref[...])

    @pl.when(pt == n_pt - 1)
    def _finalize():
        result = jnp.where(bi_ref[...] >= 0, bp_ref[...], -1)
        if probe_tiers:
            # identical tier merge to fused_lookup: active delta >
            # compacted run > streamed pool
            result = merge_tiers(
                result, qkey, qhi, qlo,
                TierPools(rpk_ref[...], rhi_ref[...], rlo_ref[...],
                          rpv_ref[...], rlen_ref[...], dpk_ref[...],
                          dhi_ref[...], dlo_ref[...], dpv_ref[...],
                          dlen_ref[...]),
                run_iters=run_iters, run_window=run_window,
                delta_iters=delta_iters, delta_window=delta_window)
        result = jnp.where(result == TOMBSTONE, -1, result)
        pay_ref[...] = result


@functools.partial(
    jax.jit,
    static_argnames=("dim", "shapes", "window", "use_flow", "stream_tile",
                     "tile", "interpret", "probe_tiers", "run_iters",
                     "run_window", "delta_iters", "delta_window"),
)
def streamed_lookup_pallas(
    feats: jnp.ndarray,
    qhi: jnp.ndarray,
    qlo: jnp.ndarray,
    packed_w: jnp.ndarray,
    pool: ScanPool,
    router: jnp.ndarray,
    tiers: Optional[TierPools] = None,
    *,
    dim: int,
    shapes: Tuple[Tuple[int, int], ...] = (),
    window: int = 4,
    use_flow: bool = True,
    stream_tile: int = STREAM_ALIGN,
    tile: Optional[int] = None,
    interpret: Optional[bool] = None,
    probe_tiers: bool = False,
    run_iters: int = 1,
    run_window: int = 4,
    delta_iters: int = 1,
    delta_window: int = 4,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """HBM-streaming NF-transform + pool-probe lookup in one
    ``pallas_call`` (DESIGN.md §17).

    feats / qhi / qlo / packed_w: as ``fused_lookup_pallas``.  pool: the
    rank-ordered deduped ``ScanPool`` snapshot of the static structure
    (``ServingState.scan``), streamed ``stream_tile`` rows at a time;
    router: its resident ``build_router`` vector; window: the pool's
    pow2 duplicate-run window.  When ``tiers``/``probe_tiers`` is set
    the write tiers stay fully VMEM-resident and are merged at the last
    pool tile with fused_lookup's precedence, so over-budget serving
    still needs no host-side tier probe.

    Returns (payload i32[B] or -1, positioning key f32[B]), bit-identical
    to ``fused_lookup_pallas`` on the same serving state.  The VMEM
    working set is ``stream_resident_parts`` — independent of the pool
    size — which is the whole point.
    """
    interpret = resolve_interpret(interpret)
    cap = int(pool.pk.shape[0])
    stream_tile = int(stream_tile)
    if stream_tile < 1 or (stream_tile & (stream_tile - 1)):
        raise ValueError(f"stream_tile must be pow2, got {stream_tile}")
    if cap % stream_tile:
        raise ValueError(
            f"pool capacity {cap} is not a whole number of "
            f"stream tiles ({stream_tile})")
    n_pt = cap // stream_tile
    use_router = (stream_tile % STREAM_ALIGN == 0
                  and int(router.shape[0]) > cap // STREAM_ALIGN)

    if tiers is None:
        probe_tiers = False
        tiers = empty_tiers()
    z = positioning_keys(feats, packed_w, shapes, dim, use_flow, interpret)
    b = z.shape[0]
    tile = select_tile(b, tile, interpret)
    b_pad = ((b + tile - 1) // tile) * tile
    zq = z
    if b_pad != b:
        zq = jnp.pad(z, (0, b_pad - b))
        qhi = jnp.pad(qhi, (0, b_pad - b))
        qlo = jnp.pad(qlo, (0, b_pad - b))

    # grid order: pool tiles innermost (fastest) — the query/output
    # blocks' index maps ignore axis 1 so they stay resident across the
    # whole pool sweep; the pool blocks revolve and get double-buffered
    qspec = pl.BlockSpec((tile,), lambda q, t: (q,))
    sspec = pl.BlockSpec((stream_tile,), lambda q, t: (t,))

    def resident(a):
        return pl.BlockSpec(a.shape, lambda q, t: (0,) * a.ndim)

    pay, _bi, _bp = pl.pallas_call(
        functools.partial(
            _kernel, stream_tile=stream_tile, window=window,
            use_router=use_router, probe_tiers=probe_tiers,
            run_iters=run_iters, run_window=run_window,
            delta_iters=delta_iters, delta_window=delta_window,
        ),
        out_shape=(
            jax.ShapeDtypeStruct((b_pad,), jnp.int32),
            jax.ShapeDtypeStruct((b_pad,), jnp.int32),
            jax.ShapeDtypeStruct((b_pad,), jnp.int32),
        ),
        grid=(b_pad // tile, n_pt),
        in_specs=[qspec, qspec, qspec,
                  sspec, sspec, sspec, sspec,
                  resident(pool.plen), resident(router)]
        + [resident(a) for a in tiers],
        out_specs=(qspec, qspec, qspec),
        interpret=interpret,
    )(zq, qhi, qlo, pool.pk, pool.hi, pool.lo, pool.pv, pool.plen, router,
      *tiers)
    return pay[:b], z
