"""Pallas kernel: fused single-dispatch lookup (DESIGN.md §9).

One ``pallas_call`` per query batch folds the read path together:

1. **positioning keys** — with the flow on, the wrapper computes z with
   ``nf_forward_pallas``, the kernel that positioned the build, inside
   the same jit: each key's NF value is computed once, by one kernel, so
   build-time and serve-time positioning keys are bit-identical;
2. **multi-level traversal** — a bounded level loop (tree heights after
   the NF transform are 2-3, paper Table 1) with per-query active masks.
   Each level runs all three node resolutions — model-node FMA slot
   prediction, dense-node fixed-iteration binary search, conflict-bucket
   scan — and selects per query, exactly mirroring the ``flat_lookup``
   oracle so results are bit-identical;
3. **exact identity resolution** — 64-bit (hi, lo) key identity compares,
   emitting payloads in one VMEM round trip;
4. **in-kernel write-path tiers** — the compacted run and active delta
   (log-structured inserts, DESIGN.md §10) ride along as sorted VMEM
   pools probed by bounded binary search + newest-match window scan
   (``merge_tiers``), so mixed read/insert batches stay a single dispatch
   with no host-side delta probe.

The traversal indexes the pools with data-dependent vector gathers,
which Mosaic does not lower ("Only 2D gather is supported"), so this
kernel runs in interpret mode only; on a compiled TPU backend
``kernels/ops.fused_lookup`` serves the same traversal as XLA
(``flat_afli.xla_lookup``, DESIGN.md §2).

Grid: (ceil(B / TILE),) — a real tiled grid over the query batch with
the pools as grid-invariant blocks (DESIGN.md §11); ``select_tile``
picks TILE.  Per-level work is batch-gated: the dense binary search +
duplicate scan run only on levels where some live query sits on a dense
node, and each write tier's probe only while the tier is non-empty.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.backend import resolve_interpret
from repro.kernels.nf_forward import nf_forward_pallas

__all__ = ["fused_lookup_pallas", "KernelPools", "TierPools", "TierPack",
           "DEFAULT_TILE", "INTERPRET_TILE", "TOMBSTONE", "lower_bound",
           "probe_pool", "probe_pool_index", "merge_tiers",
           "positioning_keys"]

DEFAULT_TILE = 512       # lane-aligned query tile for a compiled grid
INTERPRET_TILE = 2048    # CPU validation: per-step query tile of the
#                          tiled grid (a 4k+ batch is a multi-step grid,
#                          not one giant block — DESIGN.md §11)

# entry / node codes — schema owned by repro.core.flat_afli
EMPTY, DATA, BUCKET, CHILD = 0, 1, 2, 3
KIND_MODEL, KIND_DENSE = 0, 1

# payload sentinels (DESIGN.md §12): -1 is a miss everywhere; -2 marks a
# tombstoned identity riding the write tiers — a tier probe returning it
# must MASK any older copy below (run / static tree), then surface a miss
TOMBSTONE = -2


# ---------------------------------------------------------------- shared
# traversal helpers, used by this kernel, kernels/range_scan.py,
# kernels/streamed_lookup.py AND the XLA routes (bounded lower-bound
# search, identity-window probes, write-tier precedence).  They take
# arrays, not refs, so the same code runs inside a kernel body and as
# plain XLA.

def positioning_keys(feats, packed_w, shapes, dim: int, use_flow: bool,
                     interpret: bool) -> jnp.ndarray:
    """Query features -> f32 positioning keys, once per batch.

    With the flow on this is ``nf_forward_pallas`` — the kernel, tile
    and block shape that produced the build-time keys
    (``ops.nf_transform_keys``) — so serve-time z is bit-equal to the
    z every key was placed by; with the flow off the [B, 1] feature
    column IS the key."""
    if use_flow:
        return nf_forward_pallas(feats.astype(jnp.float32), packed_w,
                                 shapes, dim, interpret=interpret)
    return feats[:, 0].astype(jnp.float32)


def lower_bound(ppk, n_pool, qkey, iters: int) -> jnp.ndarray:
    """Leftmost index with ``ppk[i] >= qkey`` in a sorted +inf-padded
    pool (== ``np.searchsorted(..., side='left')``), as a fixed
    ``iters``-round binary search (2^iters must cover the pool)."""
    def bs_body(_, lh):
        l, h = lh
        mid = (l + h) // 2
        go_right = ppk[mid] < qkey
        return (jnp.where(go_right, mid + 1, l),
                jnp.where(go_right, h, mid))

    l0 = jnp.zeros(qkey.shape, jnp.int32)
    h0 = jnp.full(qkey.shape, n_pool, jnp.int32)
    l_fin, _ = jax.lax.fori_loop(0, iters, bs_body, (l0, h0))
    return l_fin


def probe_pool_index(phi, plo, n_pool, l_fin, nmax, window: int,
                     qhi, qlo) -> jnp.ndarray:
    """Newest matching *pool index* per lane from one sorted pool
    (-1 = no identity match in the probe window).

    Scans ``[l_fin - window, l_fin + 3*window)`` around the lower-bound
    landing: backward reach for a high landing (a query key 1 ulp above
    the stored key skips its whole equal run), forward reach for a low
    landing plus the equal run itself (each bounded by ``window``, the
    pow2-rounded max equal-key run length of the pool).  Matching is by
    exact (hi, lo) identity ONLY — the positioning key is the locator,
    never the matcher (XLA's per-consumer-shape NF re-materialization is
    1-ulp divergent, so f32 key equality is not codegen-stable).  The
    index form is what the streamed tier accumulates across pool tiles
    (global index order == insertion order, so max-index == newest)."""
    widx = (l_fin - window)[:, None] + jax.lax.broadcasted_iota(
        jnp.int32, (l_fin.shape[0], 4 * window), 1)
    wc = jnp.clip(widx, 0, nmax - 1)
    ok = ((widx >= 0) & (widx < n_pool)
          & (phi[wc] == qhi[:, None])
          & (plo[wc] == qlo[:, None]))
    return jnp.max(jnp.where(ok, widx, -1), axis=1)


def probe_pool(phi, plo, ppv, n_pool, l_fin, nmax, window: int,
               qhi, qlo) -> jnp.ndarray:
    """Newest matching payload per lane from one sorted pool (-1 = miss;
    a matched TOMBSTONE payload passes through for the caller to mask).
    Payload form of ``probe_pool_index`` — see there for the window
    coverage and identity-only matching arguments."""
    last = probe_pool_index(phi, plo, n_pool, l_fin, nmax, window,
                            qhi, qlo)
    pay = ppv[jnp.clip(last, 0, nmax - 1)]
    return jnp.where(last >= 0, pay, -1)


class KernelPools(NamedTuple):
    """Kernel-ready FlatAFLI pools: i32-coded types, lane-padded 1-D
    arrays, conflict buckets flattened row-major to [B * cap].

    Built by ``FlatArrays.to_kernel_args()``; consumed as grid-invariant
    VMEM blocks by ``fused_lookup_pallas``.  (Bucket *keys* are not needed:
    bucket hits resolve purely by 64-bit identity, as in the oracle.)
    """

    node_kind: jnp.ndarray       # i32[N]  model / dense
    node_slope: jnp.ndarray      # f32[N]
    node_intercept: jnp.ndarray  # f32[N]
    node_offset: jnp.ndarray     # i32[N]
    node_size: jnp.ndarray       # i32[N]
    etype: jnp.ndarray           # i32[P]
    ekey: jnp.ndarray            # f32[P]
    ehi: jnp.ndarray             # u32[P]
    elo: jnp.ndarray             # u32[P]
    epayload: jnp.ndarray        # i32[P]
    echild: jnp.ndarray          # i32[P]
    bhi: jnp.ndarray             # u32[B, cap]
    blo: jnp.ndarray             # u32[B, cap]
    bpayload: jnp.ndarray        # i32[B, cap]
    blen: jnp.ndarray            # i32[B]

    def nbytes(self) -> int:
        return int(sum(a.size * a.dtype.itemsize for a in self))


class TierPools(NamedTuple):
    """Device-resident write-path tiers (DESIGN.md §10): the compacted
    sorted run and the active delta, each a lane-padded sorted pool of
    (positioning key, identity bits, payload) plus a length scalar.

    Padding rows carry ``+inf`` keys so the in-kernel binary search never
    lands in them; the length scalar rides in lane 0 of a lane-padded
    vector so every block stays 1-D lane-aligned.  Probed *after* the tree
    traversal with newest-copy-wins precedence: active delta > compacted
    run > static tree.
    """

    run_pk: jnp.ndarray   # f32[R]  sorted positioning keys (+inf padded)
    run_hi: jnp.ndarray   # u32[R]  identity bits
    run_lo: jnp.ndarray   # u32[R]
    run_pv: jnp.ndarray   # i32[R]
    run_len: jnp.ndarray  # i32[lane]  built length at [0]
    dl_pk: jnp.ndarray    # f32[D]  active delta (same layout)
    dl_hi: jnp.ndarray    # u32[D]
    dl_lo: jnp.ndarray    # u32[D]
    dl_pv: jnp.ndarray    # i32[D]
    dl_len: jnp.ndarray   # i32[lane]

    def nbytes(self) -> int:
        return int(sum(a.size * a.dtype.itemsize for a in self))


class TierPack(NamedTuple):
    """TierPools plus their static probe bounds (binary-search iteration
    count per pool and the duplicate-pkey window, both host-computed at
    pack time and rounded so the kernel compile count stays bounded)."""

    pools: TierPools
    run_iters: int
    run_window: int
    delta_iters: int
    delta_window: int

    def nbytes(self) -> int:
        return self.pools.nbytes()


def merge_tiers(result, qkey, qhi, qlo, tiers: TierPools, *,
                run_iters: int, run_window: int, delta_iters: int,
                delta_window: int) -> jnp.ndarray:
    """Write-tier precedence over a static-structure result (DESIGN.md
    §10/§12): probe the compacted run and the active delta, newest copy
    first — active delta > compacted run > ``result``.

    Each tier is a sorted pool: bounded binary search locates the
    equal-key neighborhood, then a static window scan resolves by exact
    (hi, lo) identity ONLY — the positioning key is the locator, never
    the matcher, so a query key 1 ulp off a stored copy still lands
    within the adjacent equal-key runs the symmetric window covers.
    Tiers keep insertion order within an equal-pkey window (stable
    sort), so the largest matching index is the last write.  An
    identity MATCH in a newer tier always wins — a TOMBSTONE (-2) match
    masks any older copy below, then surfaces as a miss.  Mirrors the
    host ``FlatAFLI._probe_delta`` oracle; parity must stay exact."""
    def tier_stage(phi, plo, ppv, ppk, plen, iters, window):
        n_pool = plen[0]
        nmax = ppk.shape[0]

        # length-gated: a tier that is empty right now (e.g. the run
        # between a fold swap and the first shadow) skips its whole
        # search+scan; misses are the only possible outcome anyway
        def live(_):
            return probe_pool(phi, plo, ppv, n_pool,
                              lower_bound(ppk, n_pool, qkey, iters),
                              nmax, window, qhi, qlo)

        def empty(_):
            return jnp.full(qkey.shape, -1, jnp.int32)

        return jax.lax.cond(n_pool > 0, live, empty, None)

    t = tiers
    run_pay = tier_stage(t.run_hi, t.run_lo, t.run_pv, t.run_pk, t.run_len,
                         run_iters, run_window)
    dl_pay = tier_stage(t.dl_hi, t.dl_lo, t.dl_pv, t.dl_pk, t.dl_len,
                        delta_iters, delta_window)
    result = jnp.where(dl_pay != -1, dl_pay,
                       jnp.where(run_pay != -1, run_pay, result))
    return jnp.where(result == TOMBSTONE, -1, result)


def empty_tiers() -> TierPools:
    """Tiny dummy write tiers for a call without live tiers: the probe
    stage is compiled out by the static ``probe_tiers`` flag, so these
    only fill the argument slots."""
    lane = jnp.zeros((128,), jnp.int32)
    return TierPools(
        run_pk=jnp.full((128,), jnp.inf, jnp.float32),
        run_hi=jnp.zeros((128,), jnp.uint32),
        run_lo=jnp.zeros((128,), jnp.uint32),
        run_pv=jnp.full((128,), -1, jnp.int32), run_len=lane,
        dl_pk=jnp.full((128,), jnp.inf, jnp.float32),
        dl_hi=jnp.zeros((128,), jnp.uint32),
        dl_lo=jnp.zeros((128,), jnp.uint32),
        dl_pv=jnp.full((128,), -1, jnp.int32), dl_len=lane,
    )


def _kernel(z_ref, qhi_ref, qlo_ref,
            nkind_ref, nslope_ref, nicept_ref, noff_ref, nsize_ref,
            etype_ref, ekey_ref, ehi_ref, elo_ref, epay_ref, echild_ref,
            bhi_ref, blo_ref, bpay_ref, blen_ref,
            rpk_ref, rhi_ref, rlo_ref, rpv_ref, rlen_ref,
            dpk_ref, dhi_ref, dlo_ref, dpv_ref, dlen_ref,
            pay_ref, *, max_depth: int, dense_iters: int, bucket_cap: int,
            dense_window: int, probe_tiers: bool, run_iters: int,
            run_window: int, delta_iters: int, delta_window: int):
    """One [TILE] query tile: full traversal + tier probe -> payloads.

    Mirrors ``repro.core.flat_afli.flat_lookup`` op-for-op (the oracle);
    any change here must keep the parity tests bit-exact.
    """
    qkey = z_ref[...]
    qhi = qhi_ref[...]
    qlo = qlo_ref[...]

    # pools, VMEM-resident for the whole tile
    nkind = nkind_ref[...]
    nslope = nslope_ref[...]
    nicept = nicept_ref[...]
    noff = noff_ref[...]
    nsize = nsize_ref[...]
    etype = etype_ref[...]
    ekey = ekey_ref[...]
    ehi = ehi_ref[...]
    elo = elo_ref[...]
    epay = epay_ref[...]
    echild = echild_ref[...]
    bhi = bhi_ref[...]
    blo = blo_ref[...]
    bpay = bpay_ref[...]
    blen = blen_ref[...]

    node = jnp.zeros(qkey.shape, jnp.int32)
    result = jnp.full(qkey.shape, -1, jnp.int32)
    done = jnp.zeros(qkey.shape, jnp.bool_)

    # ---- (2) bounded traversal: early-exit while_loop over levels with
    # per-query active masks, exactly as the flat_lookup oracle runs it (a
    # loop, not a python unroll — compile time stays flat in tree height).
    # NOTE gather idiom: plain ``pool[idx]`` indexing, never
    # ``jnp.take(pool, idx)``.  Both clamp out-of-bounds reads, but the
    # explicit clip-mode gather take() emits defeats XLA:CPU
    # vectorization and ran the whole traversal ~2x slower than the
    # flat_lookup oracle (the BENCH_fused_lookup traversal_only.speedup
    # = 0.79 anomaly); indexing compiles to the same gather the oracle
    # uses, restoring parity op-for-op.
    def level_body(carry):
        node, result, done, depth = carry
        kind = nkind[node]
        slope = nslope[node]
        intercept = nicept[node]
        offset = noff[node]
        size = nsize[node]

        # model-node path: precise predicted slot (f32 FMA, as built)
        slot = jnp.clip(
            jnp.rint(slope * qkey + intercept).astype(jnp.int32), 0, size - 1
        )
        e_model = offset + slot
        is_dense = kind == KIND_DENSE

        # dense-node path, level-gated: the fixed-iteration binary
        # search + duplicate-run scan are the dominant per-level gather
        # cost (dense_iters rounds), but NF-transformed trees are
        # model-node-heavy — most levels have NO live query on a dense
        # node.  ``lax.cond`` on the batch-collective predicate skips
        # the whole stage for such levels; ``dense_payload`` feeds only
        # ``is_dense`` lanes, so the skip is bit-invisible (this is
        # where the fused path overtakes the unconditionally-searching
        # flat_lookup oracle on traversal-only workloads).
        def dense_stage(_):
            def bs_body(_, lh):
                l, h = lh
                mid = (l + h) // 2
                v = ekey[mid]
                go_right = v < qkey
                return (jnp.where(go_right, mid + 1, l),
                        jnp.where(go_right, h, mid))

            l_fin, _ = jax.lax.fori_loop(0, dense_iters, bs_body,
                                         (offset, offset + size))
            e_dense = jnp.clip(l_fin, offset, offset + size - 1)

            # dense duplicates of an f32 pkey: bounded forward scan, done
            # as one [tile, window] vectorized gather round; the first
            # matching position wins (argmax picks the first True),
            # exactly the oracle's acc<0 first-match fold
            widx = jnp.clip(
                e_dense[:, None]
                + jax.lax.broadcasted_iota(jnp.int32, (e_dense.shape[0],
                                                       dense_window), 1),
                offset[:, None], (offset + size - 1)[:, None])
            wok = ((ekey[widx] == qkey[:, None])
                   & (ehi[widx] == qhi[:, None])
                   & (elo[widx] == qlo[:, None]))
            # argmax < window by construction, so the column pick can
            # promise in-bounds — the default fill-mode gather would
            # devectorize exactly like the PR 3 clip-mode take
            first = jnp.argmax(wok, axis=1)
            found = jnp.take_along_axis(
                wok, first[:, None], 1, mode="promise_in_bounds")[:, 0]
            wpay = jnp.take_along_axis(
                epay[widx], first[:, None], 1,
                mode="promise_in_bounds")[:, 0]
            return e_dense, jnp.where(found, wpay, -1)

        def dense_skip(_):
            # no live dense-node query this level: e_dense only feeds
            # is_dense lanes (none live) so any in-range entry index is
            # equivalent; offset is always valid
            return offset, jnp.full(offset.shape, -1, jnp.int32)

        e_dense, dense_payload = jax.lax.cond(
            jnp.any(is_dense & ~done), dense_stage, dense_skip, None)

        e = jnp.where(kind == KIND_MODEL, e_model, e_dense)
        et = etype[e]

        # (3) exact 64-bit identity resolution
        hit_data = (et == DATA) & (ehi[e] == qhi) & (elo[e] == qlo)

        # conflict-bucket scan: one row gather over the fixed capacity
        # (max over where(match, payload, -1), as in the oracle)
        bid = jnp.maximum(echild[e], 0)
        brow_hi = bhi[bid]                           # [tile, cap]
        brow_lo = blo[bid]
        brow_pv = bpay[bid]
        col = jax.lax.broadcasted_iota(jnp.int32, brow_hi.shape, 1)
        bmatch = ((brow_hi == qhi[:, None]) & (brow_lo == qlo[:, None])
                  & (col < blen[bid][:, None]))
        bucket_payload = jnp.max(jnp.where(bmatch, brow_pv, -1), axis=-1)

        model_payload = jnp.where(
            hit_data, epay[e],
            jnp.where(et == BUCKET, bucket_payload, -1),
        )
        result = jnp.where(
            done, result, jnp.where(is_dense, dense_payload, model_payload)
        )
        goes_deeper = (~is_dense) & (et == CHILD) & (~done)
        node = jnp.where(goes_deeper, echild[e], node)
        done = done | ~goes_deeper
        return node, result, done, depth + 1

    def level_cond(carry):
        _, _, done, depth = carry
        return (~jnp.all(done)) & (depth < max_depth)

    _, result, _, _ = jax.lax.while_loop(level_cond, level_body,
                                         (node, result, done, 0))

    # ---- (4) write-path tiers (DESIGN.md §10): probe the compacted run
    # and the active delta in-kernel so a mixed read/insert batch never
    # needs a host-side delta round trip
    if probe_tiers:
        result = merge_tiers(
            result, qkey, qhi, qlo,
            TierPools(rpk_ref[...], rhi_ref[...], rlo_ref[...], rpv_ref[...],
                      rlen_ref[...], dpk_ref[...], dhi_ref[...],
                      dlo_ref[...], dpv_ref[...], dlen_ref[...]),
            run_iters=run_iters, run_window=run_window,
            delta_iters=delta_iters, delta_window=delta_window)

    pay_ref[...] = result


def _pow2ceil(n: int) -> int:
    return 1 << max(int(n) - 1, 0).bit_length()


def select_tile(b: int, tile: Optional[int] = None,
                interpret: Optional[bool] = None) -> int:
    """Query-tile selection for the tiled grid (DESIGN.md §11).

    The batch is served as a grid over query tiles with the pools as
    grid-invariant blocks.  The tile is a pure throughput choice (the
    NF runs before the grid, see ``positioning_keys``): power-of-two
    bucketed so per-batch-size recompiles stay bounded, capped at
    ``DEFAULT_TILE`` compiled / ``INTERPRET_TILE`` interpreted so a
    large batch becomes a multi-step grid instead of one giant block.
    Exposed so the dispatch shim can bill the per-step query blocks
    against the VMEM budget with the same tile the kernel will actually
    use."""
    interpret = resolve_interpret(interpret)
    if tile is None:
        tile = INTERPRET_TILE if interpret else DEFAULT_TILE
    # never pad a small batch up to a huge tile; stay lane-aligned on TPU
    tile = min(tile, _pow2ceil(b))
    return tile if interpret else max(tile, 128)


@functools.partial(
    jax.jit,
    static_argnames=("dim", "shapes", "max_depth", "dense_iters",
                     "bucket_cap", "dense_window", "use_flow", "tile",
                     "interpret", "probe_tiers", "run_iters", "run_window",
                     "delta_iters", "delta_window"),
)
def fused_lookup_pallas(
    feats: jnp.ndarray,
    qhi: jnp.ndarray,
    qlo: jnp.ndarray,
    packed_w: jnp.ndarray,
    pools: KernelPools,
    tiers: Optional[TierPools] = None,
    *,
    dim: int,
    shapes: Tuple[Tuple[int, int], ...] = (),
    max_depth: int,
    dense_iters: int,
    bucket_cap: int,
    dense_window: int = 8,
    use_flow: bool = True,
    tile: Optional[int] = None,
    interpret: Optional[bool] = None,
    probe_tiers: bool = False,
    run_iters: int = 1,
    run_window: int = 4,
    delta_iters: int = 1,
    delta_window: int = 4,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """NF transform + fused FlatAFLI traversal, one jitted dispatch.

    feats: [B, d] f32 expanded query features (``use_flow=True``) or
    [B, 1] positioning keys (``use_flow=False``); qhi/qlo: [B] u32 exact
    identity bits; packed_w: [1, n] ``pack_flow_weights`` block (any
    [1, >=1] f32 array when ``use_flow=False``).

    Returns (payload i32[B] or -1, positioning key f32[B]).  When
    ``tiers``/``probe_tiers`` is set, the write-path tiers (compacted run
    + active delta, DESIGN.md §10) are probed in-kernel after the
    traversal with newest-copy-wins precedence, so a mixed read/insert
    batch needs no host-side delta probe; otherwise the key output feeds
    the host ``_probe_delta`` fallback.  Bit-identical to
    ``nf_forward_pallas`` + ``flat_lookup`` (+ the host tier probe) by
    construction: z IS ``nf_forward_pallas`` (``positioning_keys``) and
    the traversal uses only IEEE-exact ops (mul/add/rint/compare/gather),
    so the query tile is a pure throughput choice.  ``interpret=None``
    auto-detects the backend.
    """
    interpret = resolve_interpret(interpret)
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

    qspec = pl.BlockSpec((tile,), lambda i: (i,))

    def pool_spec(a):
        return pl.BlockSpec(a.shape, lambda i: (0,) * a.ndim)

    pay = pl.pallas_call(
        functools.partial(
            _kernel, max_depth=max_depth,
            dense_iters=dense_iters, bucket_cap=bucket_cap,
            dense_window=dense_window, probe_tiers=probe_tiers,
            run_iters=run_iters, run_window=run_window,
            delta_iters=delta_iters, delta_window=delta_window,
        ),
        out_shape=jax.ShapeDtypeStruct((b_pad,), jnp.int32),
        grid=(b_pad // tile,),
        in_specs=[qspec, qspec, qspec]
        + [pool_spec(a) for a in pools] + [pool_spec(a) for a in tiers],
        out_specs=qspec,
        interpret=interpret,
    )(zq, qhi, qlo, *pools, *tiers)
    return pay[:b], z
