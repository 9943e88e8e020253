"""Public jit'd wrappers around the Pallas kernels, and the dispatch
shims that pick each serving route.

``interpret`` mode is selected automatically: Pallas executes the kernel
bodies in Python on CPU (the validation platform) and compiles to Mosaic on
real TPU backends.  Which route serves a request follows one rule
(``traversal_route``, DESIGN.md §2): the traversal kernels gather from
their pools with vector indices that Mosaic does not lower, so a compiled
backend serves points and ranges as XLA over the same device pools, with
the NF forward still a Mosaic kernel; the interpreter runs the Pallas
ladder.  The route that ran is named in every dispatch's ``info`` and
counted in ``fused_lookup_stats``.
"""

from __future__ import annotations

import threading
import time

from typing import Dict

import jax.numpy as jnp
import numpy as np

from repro.core.feature import KeyNormalizer, expand_features
from repro.core.flow import FlowConfig, materialize_weights
from repro.kernels.backend import resolve_interpret, should_interpret
from repro.kernels.nf_forward import nf_forward_pallas, pack_flow_weights
from repro.kernels.index_probe import index_probe_pallas
from repro.kernels.flash_decode import flash_decode_pallas
from repro.obs import span

__all__ = [
    "should_interpret",
    "nf_transform_keys",
    "index_probe",
    "fused_lookup",
    "fused_range_scan",
    "fused_lookup_stats",
    "traversal_route",
    "reset_fused_lookup_stats",
    "pool_nbytes",
    "kernel_block_bytes",
    "scan_block_bytes",
    "overflow_reason",
    "serving_cache_size",
    "flash_decode",
]


def nf_transform_keys(
    params: Dict,
    normalizer: KeyNormalizer,
    keys: np.ndarray,
    cfg: FlowConfig,
) -> np.ndarray:
    """Kernel-backed version of ``repro.core.flow.transform_keys``: the
    build-time positioning keys, from the same ``nf_forward_pallas``
    kernel and tile every serve route evaluates (DESIGN.md §9)."""
    keys = np.asarray(keys, dtype=np.float64)
    feats = expand_features(keys, normalizer, cfg.dim, cfg.theta, dtype=np.float32)
    weights = materialize_weights(params, cfg)
    out_scale = jnp.exp(params["out_log_scale"])
    feat_mu = params.get("feat_mu", jnp.zeros((cfg.dim,), jnp.float32))
    feat_sd = params.get("feat_sd", jnp.ones((cfg.dim,), jnp.float32))
    packed, shapes = pack_flow_weights(weights, out_scale, feat_mu, feat_sd)
    z = nf_forward_pallas(
        jnp.asarray(feats), packed, shapes, cfg.dim,
        interpret=should_interpret(),
    )
    return np.asarray(z, dtype=np.float64)


# ---------------------------------------------------------------- fused
# Per-core VMEM share for the grid-invariant pool blocks of a compiled
# traversal kernel.  No route consults it on a compiled backend today:
# the traversal kernels run in the interpreter only (``traversal_route``)
# and the one compiled kernel, ``nf_forward_pallas``, holds a few KiB per
# grid step.  A Mosaic traversal must set it, and ``vmem_limit_bytes``,
# from what the compiler accepts for the chip's ``device_kind``.
DEFAULT_VMEM_BUDGET = 12 * 2 ** 20
# The CPU validation platform has no VMEM; cap where the single-block
# interpret kernel stops being profitable against the jitted oracle.
DEFAULT_INTERPRET_BUDGET = 256 * 2 ** 20


def pool_nbytes(pools) -> int:
    """Total bytes of the kernel pool blocks (the VMEM-residency bill)."""
    return pools.nbytes()


def kernel_block_bytes(pools, tier_bytes: int, tile: int, dim: int) -> int:
    """The full VMEM-residency bill for one grid step: the grid-invariant
    pool blocks *as padded* (shape-bucketed padding is what the kernel
    actually holds resident, not the raw pool bytes), the write-tier
    pools at their bucket capacities, and the per-step query/output
    blocks (feats f32[tile, dim], qhi/qlo u32[tile], payload i32[tile],
    z f32[tile])."""
    q_bytes = tile * (dim + 4) * 4
    return pool_nbytes(pools) + int(tier_bytes) + q_bytes


def scan_block_bytes(scan_pack, tier_bytes: int, tile: int, dim: int,
                     scan_cap: int) -> int:
    """VMEM bill for one fused-range-scan grid step: the scan pool at
    its bucketed padded capacity, the write tiers, and the per-step
    query/output blocks (two endpoint feature blocks f32[tile, dim],
    zlo/zhi f32[tile], counts/totals i32[tile], payload lanes
    i32[tile, scan_cap])."""
    q_bytes = tile * (2 * dim + 4 + scan_cap) * 4
    return scan_pack.nbytes() + int(tier_bytes) + q_bytes


def overflow_reason(parts, budget: int) -> Dict:
    """Attribute a VMEM-budget overflow to one component.

    ``parts`` is ``[(component, bytes), ...]`` in residency order
    (grid-invariant blocks first).  The blamed component is the first
    whose cumulative sum crosses the budget — "the pools fit, adding
    the write tiers did not" reads as ``component="write-tiers"``.

    This is the ONE vocabulary for overflow reporting: the runtime
    fallback telemetry (``fused_lookup_stats()["fallback_reasons"]``)
    and the static VMEM proof (``repro.analysis.vmem``) both emit this
    structure, so a bench report and a CI finding describe the same
    cliff in the same words (DESIGN.md §15).
    """
    total = sum(b for _, b in parts)
    component = parts[-1][0] if parts else "unknown"
    acc = 0
    for name, b in parts:
        acc += b
        if acc > budget:
            component = name
            break
    return {
        "component": component,
        "padded_bytes": int(total),
        "budget_bytes": int(budget),
        "over_bytes": int(max(0, total - budget)),
        "parts": {name: int(b) for name, b in parts},
    }


# ------------------------------------------------------- serving telemetry
# Cumulative fused-lookup dispatch counters (reset via
# ``reset_fused_lookup_stats``).  ``retrace_count`` counts calls that
# grew a serving jit cache — i.e. paid an XLA trace+compile inside the
# serving window; the zero-retrace acceptance gates read it directly
# instead of inferring compiles from tail latencies.
_FUSED_STATS = {
    "dispatch_count": 0,   # fused_lookup shim calls
    "interpret_count": 0,  # point + range calls run by the Pallas
    #                        interpreter (a chip run must show 0)
    "fused_count": 0,      # single-dispatch kernel path taken
    "xla_count": 0,        # XLA point route taken (compiled backend)
    "fallback_count": 0,   # oracle fallback taken (budget exceeded)
    "tier_kernel_count": 0,  # calls that resolved the tiers on device
    "host_probe_count": 0,   # calls whose tiers fell to the host oracle
    "retrace_count": 0,    # calls that paid a fresh XLA trace
    # HBM-streaming rung (DESIGN.md §17)
    "streamed_count": 0,       # streamed single-dispatch path taken
    "stream_fallback_count": 0,  # streaming attempted but could not run
    "streamed_tiles_count": 0,   # cumulative pool tiles DMA'd by the
    #                              streamed grid (query tiles x pool tiles)
    # range-scan path (DESIGN.md §12)
    "scan_dispatch_count": 0,  # fused_range_scan shim calls
    "scan_fused_count": 0,     # single-dispatch range kernel taken
    "scan_xla_count": 0,       # XLA range route taken (compiled backend)
    "scan_fallback_count": 0,  # host-oracle fallback taken
    "scan_trunc_count": 0,     # queries whose candidate span > scan_cap
}

# Structured reason for the last budget-driven fallback per route, in
# the ``overflow_reason`` vocabulary (+ a cumulative count).  Routes:
# "point" = tree pools fell off the kernel path entirely (oracle),
# "point-tiers" = pools fit but the tier ride-along did not (host
# probe), "point-streamed" = the HBM-streaming rung could not run
# either (its resident floor — write tiers + router + the minimum
# double-buffered tile pair — already exceeds the budget), "scan" = the
# all-or-nothing range path went host.  ``None`` until that route falls
# back — a silent fallback is no longer possible: every budget miss
# names the component and the bytes.
_FALLBACK_REASONS: Dict[str, Dict | None] = {
    "point": None, "point-tiers": None, "point-streamed": None,
    "scan": None,
}

# One lock serializes every counter mutation AND the snapshot-and-reset
# in ``fused_lookup_stats(reset=True)``: the §16 front-end loop reads
# per-window stats from its serving thread while the §14 background
# re-flow tick keeps dispatching on the write path, and an unlocked
# reset racing a bump would silently lose counts.
_STATS_LOCK = threading.Lock()


def _bump(**counts) -> None:
    with _STATS_LOCK:
        for k, v in counts.items():
            _FUSED_STATS[k] += v


def _note_fallback(route: str, reason: Dict) -> Dict:
    with _STATS_LOCK:
        prev = _FALLBACK_REASONS.get(route)
        reason = dict(reason)
        reason["route"] = route
        reason["count"] = (prev["count"] + 1) if prev else 1
        _FALLBACK_REASONS[route] = reason
    return reason


def fused_lookup_stats(reset: bool = False) -> Dict[str, int]:
    """Snapshot of the cumulative fused-lookup dispatch counters.

    ``reset=True`` zeroes the counters after snapshotting, so
    multi-phase benchmarks and drift windows read per-phase counts
    instead of totals accumulated by warmup/previous phases.  Snapshot
    and reset happen atomically under the stats lock: concurrent
    dispatches land either in this snapshot or the next window, never
    nowhere."""
    with _STATS_LOCK:
        out = dict(_FUSED_STATS)
        out["fallback_reasons"] = {k: (dict(v) if v else None)
                                   for k, v in _FALLBACK_REASONS.items()}
        if reset:
            _reset_stats_unlocked()
    return out


def reset_fused_lookup_stats() -> None:
    with _STATS_LOCK:
        _reset_stats_unlocked()


def _reset_stats_unlocked() -> None:
    for k in _FUSED_STATS:
        _FUSED_STATS[k] = 0
    for k in _FALLBACK_REASONS:
        _FALLBACK_REASONS[k] = None


# --------------------------------------------------------- fault injection
class TransientDispatchError(RuntimeError):
    """Injected transient dispatch failure (``serve.faults.FaultPlan``).

    Raised *before* the kernel launches, so a failed dispatch has no
    side effect on index state and is safe to retry; the front-end's
    bounded-retry-with-backoff loop (DESIGN.md §16) is the intended
    handler."""


# Raw fault-injection state lives here — not in ``serve/`` — because
# ops.py is the one module every dispatch route already crosses;
# ``serve.faults.inject`` is the structured front door that installs a
# ``FaultPlan`` and guarantees cleanup.
_FAULT_PLAN = {
    "force_fallback": False,  # every point/scan dispatch takes the oracle
    "stall_s": 0.0,           # sleep before dispatch (device-stall model)
    "stall_every": 1,         # ...on every Nth dispatch
    "fold_stall_s": 0.0,      # sleep inside each incremental fold tick
    "error_every": 0,         # raise TransientDispatchError on every Nth
}
_FAULT_COUNTS = {
    "dispatches_seen": 0, "forced_fallbacks": 0, "stalls": 0,
    "fold_stalls": 0, "transient_errors": 0,
}


def set_fault_plan(**knobs) -> None:
    """Install fault-injection knobs; unknown keys are an error."""
    with _STATS_LOCK:
        for k, v in knobs.items():
            if k not in _FAULT_PLAN:
                raise KeyError(f"unknown fault knob: {k!r}")
            _FAULT_PLAN[k] = v


def clear_fault_plan() -> None:
    with _STATS_LOCK:
        _FAULT_PLAN.update(force_fallback=False, stall_s=0.0,
                           stall_every=1, fold_stall_s=0.0, error_every=0)


def fault_injection_stats(reset: bool = False) -> Dict[str, int]:
    with _STATS_LOCK:
        out = dict(_FAULT_COUNTS)
        if reset:
            for k in _FAULT_COUNTS:
                _FAULT_COUNTS[k] = 0
    return out


def _fault_gate(route: str) -> bool:
    """Apply the installed fault plan to one dispatch: maybe stall,
    maybe raise a transient error, maybe force the oracle fallback.
    Returns True when the dispatch must take the fallback path."""
    with _STATS_LOCK:
        plan = dict(_FAULT_PLAN)
        _FAULT_COUNTS["dispatches_seen"] += 1
        n = _FAULT_COUNTS["dispatches_seen"]
        err = bool(plan["error_every"]) and n % plan["error_every"] == 0
        stall = (plan["stall_s"] > 0
                 and n % max(int(plan["stall_every"]), 1) == 0)
        if err:
            _FAULT_COUNTS["transient_errors"] += 1
        elif stall:
            _FAULT_COUNTS["stalls"] += 1
        if plan["force_fallback"] and not err:
            _FAULT_COUNTS["forced_fallbacks"] += 1
    if err:
        raise TransientDispatchError(
            f"injected transient fault on {route} dispatch #{n}")
    if stall:
        time.sleep(plan["stall_s"])
    return bool(plan["force_fallback"])


def fault_stall(point: str) -> None:
    """Injection hook for non-dispatch stall points (``"fold"`` is the
    incremental-fold tick on the write path)."""
    with _STATS_LOCK:
        s = _FAULT_PLAN["fold_stall_s"] if point == "fold" else 0.0
        if s > 0:
            _FAULT_COUNTS["fold_stalls"] += 1
    if s > 0:
        time.sleep(s)


def traversal_route(interpret: bool) -> str:
    """The route rule (DESIGN.md §2): ``"pallas"`` where the traversal
    kernels run (the interpreter), ``"xla"`` on a compiled backend.

    Mosaic refuses every traversal kernel of this package — they index
    their pools with data-dependent vector gathers ("Only 2D gather is
    supported") — so on the TPU the traversal, the tier probe and the
    range merge run as jitted XLA over the same device-resident pools,
    behind the Mosaic-compiled ``nf_forward_pallas``.  The rule is
    static: no dispatch ever catches a compile error to try another
    route."""
    return "pallas" if interpret else "xla"


def serving_cache_size() -> int:
    """Total jit-cache entries across the serving dispatch routes."""
    from repro.core.flat_afli import flat_lookup, xla_lookup
    from repro.kernels.fused_lookup import fused_lookup_pallas
    from repro.kernels.range_scan import (fused_range_scan_pallas,
                                          xla_range_scan)
    from repro.kernels.streamed_lookup import streamed_lookup_pallas

    total = 0
    for fn in (fused_lookup_pallas, streamed_lookup_pallas,
               fused_range_scan_pallas, flat_lookup, xla_lookup,
               xla_range_scan, nf_forward_pallas):
        try:
            total += fn._cache_size()
        except AttributeError:  # not a jit wrapper (e.g. monkeypatched)
            pass
    return total


def _xla_point(pools, feats, qhi, qlo, flow, tiers, *, max_depth: int,
               dense_iters: int, bucket_cap: int, dense_window: int,
               interpret: bool, sync: bool, cache_before: int):
    """The XLA point route (``traversal_route`` == ``"xla"``): NF kernel
    + ``flat_lookup`` traversal + device tier probe in one jitted
    dispatch over the resident bucketed pools (``flat_afli.xla_lookup``).
    Every pool size takes it, so no VMEM budget applies; the tiers are
    always resolved on device — never by the host probe."""
    from repro.core.flat_afli import xla_lookup

    pools = pools() if callable(pools) else pools
    tiers = tiers() if callable(tiers) else tiers
    have_tiers = tiers is not None
    if flow is not None:
        packed_w, shapes = flow
    else:
        packed_w, shapes = jnp.zeros((1, 1), jnp.float32), ()
    pay, z = xla_lookup(
        pools, feats, qhi, qlo, packed_w,
        tiers.pools if have_tiers else None,
        dim=int(feats.shape[1]), shapes=shapes, max_depth=max_depth,
        dense_iters=dense_iters, bucket_cap=bucket_cap,
        dense_window=dense_window, use_flow=flow is not None,
        interpret=interpret, probe_tiers=have_tiers,
        run_iters=tiers.run_iters if have_tiers else 1,
        run_window=tiers.run_window if have_tiers else 4,
        delta_iters=tiers.delta_iters if have_tiers else 1,
        delta_window=tiers.delta_window if have_tiers else 4,
    )
    retraced = serving_cache_size() > cache_before
    _bump(xla_count=1, retrace_count=int(retraced),
          tier_kernel_count=int(have_tiers))
    info = {"path": "xla", "n_dispatch": 1,
            "pool_bytes": pool_nbytes(pools),
            "tier_bytes": tiers.nbytes() if have_tiers else 0,
            "retraced": retraced,
            "tier_path": "device" if have_tiers else "none",
            "host_probe": False, "fallback_reason": None}
    if not sync:
        return pay, z, info
    return np.asarray(pay), np.asarray(z), info


def fused_lookup(arrays, pools, feats, qhi, qlo, *, flow=None,
                 max_depth: int, dense_iters: int, bucket_cap: int,
                 dense_window: int = 8, tiers=None, stream=None,
                 vmem_budget=None, tile=None, interpret=None,
                 sync: bool = True):
    """Dispatch shim for the point-lookup routes (DESIGN.md §2/§9/§17).

    On a compiled backend every batch takes the XLA route
    (``_xla_point``; ``traversal_route``).  In interpret mode the
    Pallas ladder runs: fused -> streamed -> oracle.

    When the packed pools fit the VMEM budget, the whole read path — NF
    forward + multi-level traversal + identity resolution — runs as ONE
    ``pallas_call`` (``kernels/fused_lookup``).  When they do not (or the
    tier ride-along pushes the bill over), the **streamed** rung keeps
    serving on a single ``pallas_call`` by streaming the rank-ordered
    pool HBM->VMEM in double-buffered tiles with the write tiers still
    resident (``kernels/streamed_lookup``) — its budget is billed per
    tile working set, not whole-pool bytes.  Only when even the streamed
    rung's resident floor exceeds the budget does the path fall back to
    the bit-identical oracle: ``nf_forward_pallas`` (when ``flow`` is
    given) followed by the pure-jnp ``flat_lookup`` while-loop plus a
    host-side tier probe.

    arrays: the ``FlatArrays`` pools (oracle path); pools: their packed
    ``KernelPools`` twin, or a zero-arg callable producing it — the thunk
    form lets callers skip the packing/upload entirely when the kernel
    path is disabled (``vmem_budget <= 0``); feats: [n, d] f32 query
    features, or [n, 1] positioning keys when ``flow is None``; flow:
    optional ``(packed_w, shapes)`` from ``pack_flow_weights``; tiers:
    optional ``TierPack`` (or a thunk producing one, or ``None`` when the
    write tiers are empty) — when it also fits the budget the run/delta
    tiers are probed *in-kernel* (DESIGN.md §10) and no host-side delta
    probe is needed; stream: optional ``StreamPack`` (or thunk / None)
    enabling the streamed rung — ``ServingState.stream_pack``.

    Returns ``(payload i32[n], positioning_key f32[n], info)`` as numpy
    — or as device arrays when ``sync=False``, which dispatches without
    blocking on the result so a sharded caller (DESIGN.md §13) can fan a
    batch out across devices and gather once all shards are in flight.
    ``info`` records the chosen path, dispatch count, and the tier
    routing: ``tier_path`` is ``"kernel"`` (tiers resolved on device),
    ``"host"`` (caller must run the host ``_probe_delta`` oracle), or
    ``"none"`` (no write tiers); ``host_probe`` is the boolean form.

    The VMEM budget is billed against the shapes the kernel actually
    holds resident — the bucketed *padded* pools plus the query tile
    blocks (``kernel_block_bytes``) — and every call updates the
    module-level dispatch counters (``fused_lookup_stats``):
    fallbacks taken, tier routing, and ``retrace_count`` (calls that
    grew a serving jit cache, i.e. paid an XLA trace+compile).
    """
    from repro.core.flat_afli import flat_lookup
    from repro.kernels.fused_lookup import fused_lookup_pallas, select_tile

    interpret = resolve_interpret(interpret)
    forced = _fault_gate("point")
    _bump(dispatch_count=1, interpret_count=int(interpret))
    cache_before = serving_cache_size()
    if vmem_budget is None:
        vmem_budget = (DEFAULT_INTERPRET_BUDGET if interpret
                       else DEFAULT_VMEM_BUDGET)
    use_flow = flow is not None
    dim = int(feats.shape[1])
    if (traversal_route(interpret) == "xla" and vmem_budget > 0
            and not forced):
        return _xla_point(pools, feats, qhi, qlo, flow, tiers,
                          max_depth=max_depth, dense_iters=dense_iters,
                          bucket_cap=bucket_cap, dense_window=dense_window,
                          interpret=interpret, sync=sync,
                          cache_before=cache_before)
    # the VMEM bill is checked against the shapes the kernel will
    # actually hold resident: bucketed padded pools + the query tile
    # blocks of the tile the grid will use — not the raw pool bytes
    q_tile = select_tile(int(feats.shape[0]), tile, interpret)
    nbytes = None
    if vmem_budget > 0 and not forced:
        if callable(pools):
            pools = pools()
        nbytes = kernel_block_bytes(pools, 0, q_tile, dim)
        if nbytes <= vmem_budget and callable(tiers):
            tiers = tiers()
    if callable(tiers):
        # kernel path ruled out: never pack/upload the tier pools just to
        # report their size — the host probe resolves them (and no-ops
        # when they are empty)
        have_tiers, tier_bytes = True, None
    else:
        have_tiers = tiers is not None
        tier_bytes = tiers.nbytes() if have_tiers else 0
    if use_flow:
        packed_w, shapes = flow
    else:
        packed_w, shapes = jnp.zeros((1, 1), jnp.float32), ()

    def _attempt_streamed(tiers_in):
        """The HBM-streaming rung (DESIGN.md §17): serve from the
        rank-ordered pool in double-buffered ``stream_tile`` slices with
        the write tiers VMEM-resident.  Returns the finished result
        tuple, or ``None`` — with the structured ``point-streamed``
        reason recorded — when even streaming cannot run (the resident
        floor alone exceeds the budget, or no stream pack is wired)."""
        nonlocal stream
        if stream is None or vmem_budget <= 0 or forced:
            return None
        from repro.kernels.streamed_lookup import (
            MIN_STREAM_TILE, select_stream_tile, stream_resident_parts,
            streamed_lookup_pallas)

        if callable(stream):
            stream = stream()
        if stream is None:
            return None
        tiers_s = tiers_in() if callable(tiers_in) else tiers_in
        have_t = tiers_s is not None
        t_bytes = tiers_s.nbytes() if have_t else 0
        cap = int(stream.pool.pk.shape[0])
        router_len = int(stream.router.shape[0])
        # every (query tile, pool tile) grid step costs real overhead —
        # pipeline bubbles compiled, per-step dispatch interpreted — so
        # co-optimize the two tiles for minimum total grid steps under
        # the budget instead of inheriting the fused rung's query tile.
        b_n = int(feats.shape[0])
        floor_parts = stream_resident_parts(cap, router_len, t_bytes,
                                            MIN_STREAM_TILE, q_tile, dim)
        best = None  # (grid_steps, query_tile, stream_tile)
        qt = q_tile
        while True:
            parts = stream_resident_parts(cap, router_len, t_bytes,
                                          MIN_STREAM_TILE, qt, dim)
            res_qt = sum(b for name, b in parts
                         if name != "stream-tiles")
            st_qt = select_stream_tile(cap, vmem_budget, res_qt)
            if st_qt is None:
                break  # a wider query block can only fit worse
            steps = -(-b_n // qt) * (cap // st_qt)
            if best is None or steps < best[0]:
                best = (steps, qt, st_qt)
            if qt >= b_n:
                break
            qt *= 2
        if best is None:
            _bump(stream_fallback_count=1)
            _note_fallback("point-streamed",
                           overflow_reason(floor_parts, vmem_budget))
            return None
        _, sq_tile, st = best
        pay, z = streamed_lookup_pallas(
            feats, qhi, qlo, packed_w, stream.pool, stream.router,
            tiers_s.pools if have_t else None,
            dim=dim, shapes=shapes, window=stream.window,
            use_flow=use_flow, stream_tile=st, tile=sq_tile,
            interpret=interpret, probe_tiers=have_t,
            run_iters=tiers_s.run_iters if have_t else 1,
            run_window=tiers_s.run_window if have_t else 4,
            delta_iters=tiers_s.delta_iters if have_t else 1,
            delta_window=tiers_s.delta_window if have_t else 4,
        )
        retraced = serving_cache_size() > cache_before
        b_pad = -(-b_n // sq_tile) * sq_tile
        n_tiles = (b_pad // sq_tile) * (cap // st)
        bill = sum(b for _, b in stream_resident_parts(
            cap, router_len, t_bytes, st, sq_tile, dim))
        _bump(streamed_count=1, retrace_count=int(retraced),
              tier_kernel_count=int(have_t), streamed_tiles_count=n_tiles)
        info = {"path": "streamed", "n_dispatch": 1, "pool_bytes": bill,
                "pool_stream_bytes": int(stream.pool.nbytes()),
                "stream_tile": st, "tiles_streamed": n_tiles,
                "tier_bytes": t_bytes, "retraced": retraced,
                "tier_path": "kernel" if have_t else "none",
                "host_probe": False, "fallback_reason": None}
        if not sync:
            return pay, z, info
        return np.asarray(pay), np.asarray(z), info

    if nbytes is not None and nbytes <= vmem_budget:
        # tree pools fit; tiers ride along only if the budget still holds
        kernel_tiers = have_tiers and nbytes + tier_bytes <= vmem_budget
        if have_tiers and not kernel_tiers:
            # the pools fit but the tier ride-along does not: before
            # dropping the tiers to the host probe, try the streamed
            # rung — its resident bill is tiers + router + one
            # double-buffered tile pair, usually far under the fused
            # pools, and it keeps the whole batch on one dispatch with
            # zero host tier probes
            out = _attempt_streamed(tiers)
            if out is not None:
                return out
        pay, z = fused_lookup_pallas(
            feats, qhi, qlo, packed_w, pools,
            tiers.pools if kernel_tiers else None,
            dim=dim, shapes=shapes,
            max_depth=max_depth, dense_iters=dense_iters,
            bucket_cap=bucket_cap, dense_window=dense_window,
            use_flow=use_flow, tile=tile, interpret=interpret,
            probe_tiers=kernel_tiers,
            run_iters=tiers.run_iters if kernel_tiers else 1,
            run_window=tiers.run_window if kernel_tiers else 4,
            delta_iters=tiers.delta_iters if kernel_tiers else 1,
            delta_window=tiers.delta_window if kernel_tiers else 4,
        )
        retraced = serving_cache_size() > cache_before
        _bump(fused_count=1, retrace_count=int(retraced),
              tier_kernel_count=int(kernel_tiers),
              host_probe_count=int(have_tiers and not kernel_tiers))
        reason = None
        if have_tiers and not kernel_tiers:
            # the pools fit but the tier ride-along pushed the bill
            # over budget: the write tiers fall to the host probe
            reason = _note_fallback("point-tiers", overflow_reason(
                [("tree-pools", pool_nbytes(pools)),
                 ("query-block", q_tile * (dim + 4) * 4),
                 ("write-tiers", tier_bytes)], vmem_budget))
        info = {"path": "fused", "n_dispatch": 1, "pool_bytes": nbytes,
                "tier_bytes": tier_bytes, "retraced": retraced,
                "tier_path": ("kernel" if kernel_tiers
                              else "host" if have_tiers else "none"),
                "host_probe": have_tiers and not kernel_tiers,
                "fallback_reason": reason}
        if not sync:
            return pay, z, info
        return np.asarray(pay), np.asarray(z), info

    # streamed rung: pools exceed the budget -> stream the rank-ordered
    # pool through VMEM in double-buffered tiles (DESIGN.md §17) before
    # surrendering the batch to the host oracle
    out = _attempt_streamed(tiers)
    if out is not None:
        return out

    # oracle fallback: pools exceed the budget AND the streamed rung's
    # resident floor does not fit (or no stream pack is wired) -> keep
    # the pools in HBM and use the gather-per-level jnp traversal (two
    # dispatches when flow is on)
    if use_flow:
        z = nf_forward_pallas(jnp.asarray(feats, jnp.float32), packed_w,
                              shapes, dim, interpret=interpret)
        n_dispatch = 2
    else:
        z = jnp.asarray(feats, jnp.float32)[:, 0]
        n_dispatch = 1
    res = flat_lookup(arrays, z, qhi, qlo, max_depth=max_depth,
                      dense_iters=dense_iters, bucket_cap=bucket_cap,
                      dense_window=dense_window)
    retraced = serving_cache_size() > cache_before
    _bump(fallback_count=1, retrace_count=int(retraced),
          host_probe_count=int(have_tiers))
    if forced:
        # an installed FaultPlan forced the oracle path: same structured
        # vocabulary as a real budget miss, component names the cause
        reason = _note_fallback("point", {
            "component": "fault-injection", "padded_bytes": 0,
            "budget_bytes": int(vmem_budget), "over_bytes": 0,
            "parts": {}})
    elif nbytes is None:
        # the kernel path was disabled by config, not outbid
        reason = _note_fallback("point", {
            "component": "kernel-disabled", "padded_bytes": 0,
            "budget_bytes": int(vmem_budget), "over_bytes": 0,
            "parts": {}})
    else:
        reason = _note_fallback("point", overflow_reason(
            [("tree-pools", pool_nbytes(pools)),
             ("query-block", q_tile * (dim + 4) * 4)], vmem_budget))
    info = {"path": "oracle", "n_dispatch": n_dispatch, "pool_bytes": nbytes,
            "tier_bytes": tier_bytes, "retraced": retraced,
            "tier_path": "host" if have_tiers else "none",
            "host_probe": have_tiers, "fallback_reason": reason}
    if not sync:
        return res, z, info
    return np.asarray(res), np.asarray(z), info


def fused_range_scan(scan_pack, tiers, feats_lo, feats_hi, *, flow=None,
                     scan_cap: int, host_fallback, vmem_budget=None,
                     tile=None, interpret=None):
    """Dispatch shim for the tier-merged range scan (DESIGN.md §2/§12).

    On a compiled backend every batch takes the XLA range route
    (``range_scan.xla_range_scan``: the kernel's own merge body as one
    jitted XLA program; ``traversal_route``).  In interpret mode:

    When the scan pool AND the write tiers fit the VMEM budget, the whole
    range path — endpoint NF forward + lower-bound location + three-way
    tier merge with identity dedup and tombstone filtering — runs as ONE
    ``pallas_call`` (``kernels/range_scan``).  Anything oversized falls
    back to the bit-identical host oracle (``host_fallback``, a zero-arg
    callable returning ``(payloads, counts, totals)`` numpy): unlike the
    point path there is no partial route — merging host-resident tier
    entries into kernel-emitted runs would itself be an ordered merge, so
    the fallback is all-host by construction.

    scan_pack: ``ScanPack`` or a zero-arg thunk producing it (the thunk
    form skips the pack when the kernel path is disabled); tiers:
    ``TierPack`` / thunk / ``None`` (both write tiers empty); feats_lo /
    feats_hi: [n, d] endpoint features ([n, 1] keys when ``flow`` is
    None); flow: optional ``(packed_w, shapes)``.

    Returns ``(payloads i32[n, scan_cap], counts i32[n], totals i32[n],
    info)`` as numpy.  Every call updates the scan counters in
    ``fused_lookup_stats`` (dispatches, fallbacks, per-query
    truncations) plus the shared ``retrace_count``.
    """
    from repro.kernels.fused_lookup import select_tile

    interpret = resolve_interpret(interpret)
    forced = _fault_gate("scan")
    _bump(scan_dispatch_count=1, interpret_count=int(interpret))
    cache_before = serving_cache_size()
    if vmem_budget is None:
        vmem_budget = (DEFAULT_INTERPRET_BUDGET if interpret
                       else DEFAULT_VMEM_BUDGET)
    use_flow = flow is not None
    dim = int(feats_lo.shape[1])
    q_tile = select_tile(int(feats_lo.shape[0]), tile, interpret)

    nbytes = None
    if vmem_budget > 0 and not forced:
        if callable(scan_pack):
            scan_pack = scan_pack()
        if callable(tiers):
            tiers = tiers()
        tier_bytes = tiers.nbytes() if tiers is not None else 0
        nbytes = scan_block_bytes(scan_pack, tier_bytes, q_tile, dim,
                                  scan_cap)
    if use_flow:
        packed_w, shapes = flow
    else:
        packed_w, shapes = jnp.zeros((1, 1), jnp.float32), ()

    if nbytes is not None and traversal_route(interpret) == "xla":
        # the XLA range route: same merge body as the kernel, over the
        # device pools at any size — no budget, never the host oracle
        from repro.kernels.range_scan import xla_range_scan

        have_tiers = tiers is not None
        pv, cnt, tot = xla_range_scan(
            feats_lo, feats_hi, packed_w, scan_pack.pool,
            tiers.pools if have_tiers else None,
            dim=dim, shapes=shapes, scan_cap=scan_cap,
            scan_iters=scan_pack.iters, use_flow=use_flow,
            interpret=interpret, probe_tiers=have_tiers,
            run_iters=tiers.run_iters if have_tiers else 1,
            run_window=tiers.run_window if have_tiers else 4,
            delta_iters=tiers.delta_iters if have_tiers else 1,
            delta_window=tiers.delta_window if have_tiers else 4,
        )
        with span("afli.scan.wait"):
            pv, cnt, tot = np.asarray(pv), np.asarray(cnt), np.asarray(tot)
        retraced = serving_cache_size() > cache_before
        n_trunc = int((tot > scan_cap).sum())
        _bump(scan_xla_count=1, retrace_count=int(retraced),
              scan_trunc_count=n_trunc)
        info = {"path": "xla", "n_dispatch": 1,
                "pool_bytes": scan_pack.nbytes() + tier_bytes,
                "retraced": retraced, "truncated": n_trunc,
                "tier_path": "device" if have_tiers else "none"}
        return pv, cnt, tot, info

    if nbytes is not None and nbytes <= vmem_budget:
        from repro.kernels.range_scan import fused_range_scan_pallas

        have_tiers = tiers is not None
        pv, cnt, tot, _zlo, _zhi = fused_range_scan_pallas(
            feats_lo, feats_hi, packed_w, scan_pack.pool,
            tiers.pools if have_tiers else None,
            dim=dim, shapes=shapes, scan_cap=scan_cap,
            scan_iters=scan_pack.iters, use_flow=use_flow, tile=tile,
            interpret=interpret, probe_tiers=have_tiers,
            run_iters=tiers.run_iters if have_tiers else 1,
            run_window=tiers.run_window if have_tiers else 4,
            delta_iters=tiers.delta_iters if have_tiers else 1,
            delta_window=tiers.delta_window if have_tiers else 4,
        )
        pv, cnt, tot = np.asarray(pv), np.asarray(cnt), np.asarray(tot)
        retraced = serving_cache_size() > cache_before
        n_trunc = int((tot > scan_cap).sum())
        _bump(scan_fused_count=1, retrace_count=int(retraced),
              scan_trunc_count=n_trunc)
        info = {"path": "fused", "n_dispatch": 1, "pool_bytes": nbytes,
                "retraced": retraced, "truncated": n_trunc,
                "tier_path": "kernel" if have_tiers else "none"}
        return pv, cnt, tot, info

    pv, cnt, tot = host_fallback()
    retraced = serving_cache_size() > cache_before
    n_trunc = int((np.asarray(tot) > scan_cap).sum())
    _bump(scan_fallback_count=1, retrace_count=int(retraced),
          scan_trunc_count=n_trunc)
    if forced:
        reason = _note_fallback("scan", {
            "component": "fault-injection", "padded_bytes": 0,
            "budget_bytes": int(vmem_budget), "over_bytes": 0,
            "parts": {}})
    elif nbytes is None:
        reason = _note_fallback("scan", {
            "component": "kernel-disabled", "padded_bytes": 0,
            "budget_bytes": int(vmem_budget), "over_bytes": 0,
            "parts": {}})
    else:
        reason = _note_fallback("scan", overflow_reason(
            [("scan-pool", scan_pack.nbytes()),
             ("query-block", q_tile * (2 * dim + 4 + scan_cap) * 4),
             ("write-tiers", tier_bytes)], vmem_budget))
    info = {"path": "host", "n_dispatch": 0, "pool_bytes": nbytes,
            "retraced": retraced, "truncated": n_trunc,
            "tier_path": "host", "fallback_reason": reason}
    return np.asarray(pv), np.asarray(cnt), np.asarray(tot), info


def index_probe(qkey, qhi, qlo, slope, intercept, etype, ehi, elo,
                epayload, echild, tile: int = 512):
    return index_probe_pallas(
        qkey, qhi, qlo, slope, intercept, etype, ehi, elo, epayload,
        echild, tile=tile,
    )


def flash_decode(q, k, v, kv_len, block: int = 256):
    return flash_decode_pallas(
        q, k, v, kv_len, block=block, interpret=should_interpret()
    )


def mamba_scan(dt, xi, b_in, c_out, a_log, chunk: int = 128,
               dblock: int = 256):
    from repro.kernels.mamba_scan import mamba_scan_pallas

    return mamba_scan_pallas(dt, xi, b_in, c_out, a_log, chunk=chunk,
                             dblock=dblock, interpret=should_interpret())
