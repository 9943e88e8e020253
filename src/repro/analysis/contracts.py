"""The serving-path entry-point registry and contract driver
(DESIGN.md §15).

The registry names every jitted/pallas function a serving dispatch can
reach.  Rather than hand-reconstructing their (many, static-heavy)
signatures, the driver *captures* real invocations: it patches each
registered symbol with a transparent recorder, exercises a miniature
serving world through the public API (build → serve → insert → scan →
shard-routed flow serving), then re-traces each distinct captured
signature with ``jax.make_jaxpr`` / ``.lower()`` and runs the jaxpr
and HLO checks on exactly what production dispatched.
"""

from __future__ import annotations

import contextlib
import dataclasses
import importlib
import inspect
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import numpy as np

from repro.analysis.findings import Finding, Report
from repro.analysis.jaxpr_checks import check_jaxpr

MAX_TRACES_PER_ENTRY = 8


@dataclasses.dataclass(frozen=True)
class EntryPoint:
    """One registered serving-path function.

    ``bindings`` lists every (module, attr) where the symbol is bound
    at call time — a top-level ``from x import f`` in a caller creates
    a second binding the recorder must also patch.
    """

    name: str
    module: str
    attr: str
    bindings: Tuple[Tuple[str, str], ...] = ()
    trip_budget: int = 256       # max static loop trips in kernel bodies
    check_hlo: bool = True       # lower + scan module text

    def target(self) -> Callable:
        return getattr(importlib.import_module(self.module), self.attr)

    def location(self) -> str:
        fn = self.target()
        fn = getattr(fn, "__wrapped__", fn)
        try:
            return (f"{inspect.getsourcefile(fn)}:"
                    f"{inspect.getsourcelines(fn)[1]}")
        except (TypeError, OSError):
            return f"{self.module}.{self.attr}"


ENTRY_POINTS: Tuple[EntryPoint, ...] = (
    EntryPoint(
        name="fused_lookup",
        module="repro.kernels.fused_lookup", attr="fused_lookup_pallas",
        # the dense stage and tier probes are bounded by config windows,
        # far under the default budget
        trip_budget=256),
    EntryPoint(
        name="range_scan",
        module="repro.kernels.range_scan", attr="fused_range_scan_pallas",
        # the rank merge's loops are the endpoint binary searches
        # (*_iters rounds) and, for a tile whose compares outgrow the
        # merge budget, one trip per lane chunk; nothing loops over
        # scan_cap
        trip_budget=256),
    EntryPoint(
        name="shard_router",
        module="repro.kernels.shard_dispatch", attr="_route_flow"),
    EntryPoint(
        name="boundary_splice",
        # the §18 migration-swap boundary refresh: a value-only
        # dynamic_update_slice over the f32[P-1] boundary vector, with
        # the window offset traced — the swap must hold the host-escape
        # and retrace contracts exactly like the steady serve path,
        # because it runs between two serving batches
        module="repro.kernels.shard_dispatch", attr="_splice_boundaries"),
    EntryPoint(
        name="tier_refresh",
        module="repro.core.serving_state", attr="_write_prefix"),
    EntryPoint(
        name="tier_len_write",
        module="repro.core.serving_state", attr="_write_len"),
    EntryPoint(
        name="oracle_lookup",
        module="repro.core.flat_afli", attr="flat_lookup",
        # the oracle's traversal runs per-level gathers over the whole
        # batch by design; it is the declared fallback, not a kernel —
        # kernel-body lints do not apply, host-escape still does
        trip_budget=1 << 30),
    EntryPoint(
        name="nf_forward",
        module="repro.kernels.nf_forward", attr="nf_forward_pallas",
        bindings=(("repro.kernels.ops", "nf_forward_pallas"),)),
    EntryPoint(
        name="streamed_lookup",
        module="repro.kernels.streamed_lookup", attr="streamed_lookup_pallas",
        # per-tile local lower_bound + tier probes, all window-bounded;
        # ops imports the symbol lazily at dispatch time, so the module
        # binding is the only one to patch
        trip_budget=256),
)


# ------------------------------------------------------------ capture
def _sig_of(args: tuple, kwargs: dict) -> tuple:
    """Cheap structural signature for dedup: shapes/dtypes of array
    leaves + reprs of everything static."""
    leaves = []
    for leaf in jax.tree_util.tree_leaves(args):
        if hasattr(leaf, "shape") and hasattr(leaf, "dtype"):
            leaves.append((tuple(leaf.shape), str(leaf.dtype)))
        else:
            leaves.append(repr(leaf))
    return (tuple(leaves),
            tuple(sorted((k, repr(v)) for k, v in kwargs.items())))


@contextlib.contextmanager
def capture_entry_calls(entries=ENTRY_POINTS):
    """Patch every registered binding with a transparent recorder;
    yields ``{entry_name: [(args, kwargs), ...]}`` deduped by
    structural signature."""
    captured: Dict[str, List[Tuple[tuple, dict]]] = {e.name: []
                                                     for e in entries}
    seen: Dict[str, set] = {e.name: set() for e in entries}
    originals: List[Tuple[Any, str, Callable]] = []
    try:
        for entry in entries:
            real = entry.target()

            def recorder(*args, _entry=entry, _real=real, **kwargs):
                sig = _sig_of(args, kwargs)
                if (sig not in seen[_entry.name]
                        and len(captured[_entry.name])
                        < MAX_TRACES_PER_ENTRY):
                    seen[_entry.name].add(sig)
                    captured[_entry.name].append((args, dict(kwargs)))
                return _real(*args, **kwargs)

            for mod_name, attr in ((entry.module, entry.attr),
                                   *entry.bindings):
                mod = importlib.import_module(mod_name)
                originals.append((mod, attr, getattr(mod, attr)))
                setattr(mod, attr, recorder)
        yield captured
    finally:
        for mod, attr, real in reversed(originals):
            setattr(mod, attr, real)


def exercise_serving_world(captured_sink=None, *, seed: int = 7,
                           n_build: int = 512, shards: int = 2):
    """Drive a miniature serving world through the public API so every
    registered entry point dispatches at least once: flow-off build +
    serve + writes + scans, then a flow-on sharded NFL (router +
    NF forward + per-shard kernels + tier refreshes)."""
    from repro.core.flat_afli import FlatAFLI, FlatAFLIConfig
    from repro.core.nfl import NFL, NFLConfig
    from repro.core.train_flow import FlowTrainConfig

    rng = np.random.default_rng(seed)

    # ---- flow-off single index
    keys = np.unique(rng.uniform(0.0, 1e6, 4 * n_build))[:n_build]
    pay = np.arange(keys.shape[0], dtype=np.int64)
    idx = FlatAFLI(FlatAFLIConfig())
    idx.build(keys, pay)
    idx.lookup_batch(keys[:100])
    new = np.unique(rng.uniform(2e6, 3e6, 96))
    idx.insert_batch(new, np.arange(new.shape[0], dtype=np.int64) + 10_000)
    idx.lookup_batch(np.concatenate([keys[:50], new[:20]]))
    idx.scan_batch(keys[:16], keys[16:32])
    idx.delete_batch(keys[:4])
    idx.lookup_batch(keys[:8])

    # ---- declared-oracle index: kernel disabled by config, so the
    # gather-per-level `flat_lookup` route dispatches (it is a
    # registered serving region too — the fallback must not host-escape)
    oracle = FlatAFLI(FlatAFLIConfig(use_fused_kernel=False))
    oracle.build(keys[:128], pay[:128])
    oracle.lookup_batch(keys[:32])

    # ---- §17 streamed rung: a larger flow-off world (pools must
    # dwarf the write tiers) probed once to measure the fused bill,
    # then re-budgeted to half of it so the point route must stream
    # the scan pool tile-by-tile — tiers probed in-kernel at the last
    # tile after the insert below
    keys4 = np.unique(rng.uniform(0.0, 1e6, 4 * 4096))[:4096]
    sidx = FlatAFLI(FlatAFLIConfig(delta_cap=64))
    sidx.build(keys4, np.arange(keys4.shape[0], dtype=np.int64))
    sidx.lookup_batch(keys4[:64])
    bill = int(sidx.last_dispatch["pool_bytes"])
    sidx.cfg = dataclasses.replace(sidx.cfg, vmem_budget=bill // 2)
    sidx.lookup_batch(keys4[:64])
    assert sidx.last_dispatch["path"] == "streamed", sidx.last_dispatch
    snew = np.unique(rng.uniform(4e6, 5e6, 48))
    sidx.insert_batch(snew,
                      np.arange(snew.shape[0], dtype=np.int64) + 40_000)
    sidx.lookup_batch(np.concatenate([keys4[:24], snew[:8]]))

    # ---- flow-on sharded NFL: router + NF forward + per-shard serving
    nfl = NFL(NFLConfig(backend="flat", shards=shards, force_flow=True,
                        flow_train=FlowTrainConfig(epochs=2)))
    keys2 = np.unique(rng.normal(5e5, 1e5, 2 * n_build))[:n_build]
    nfl.bulkload(keys2, np.arange(keys2.shape[0], dtype=np.int64))
    nfl.lookup_batch(keys2[:128])
    new2 = np.unique(rng.normal(8e5, 1e4, 64))
    nfl.insert_batch(new2, np.arange(new2.shape[0], dtype=np.int64) + 20_000)
    nfl.lookup_batch(np.concatenate([keys2[:32], new2[:16]]))
    nfl.scan_batch(keys2[:8], keys2[8:16])

    # ---- §18 boundary migration over the same sharded world: the
    # swap's boundary splice is a registered entry point (it runs
    # between two serving batches, so host-escape and retrace budgets
    # apply to it like any serve dispatch); rebuild() drives the
    # in-flight window folds to the atomic swap, and the post-swap
    # lookup serves through the refreshed boundaries
    assert nfl.index.start_reshard(0, shards - 1, on_swap=lambda: None)
    nfl.index.rebuild()
    nfl.lookup_batch(keys2[:32])

    # ---- §16 SLO front-end over the same sharded flow-on NFL: the
    # double-buffered async dispatch forms its own (smaller, mixed-op)
    # batch shapes — the contract checker must see exactly what the
    # continuous loop launches, not just the hand-batched calls above
    from repro.serve.frontend import (FrontEnd, FrontEndConfig,
                                      ServiceRequest)

    fe = FrontEnd(nfl, FrontEndConfig(max_batch=32, batch_timeout_s=1e-4,
                                      admission=False, expire_queued=False))
    spare3 = np.unique(rng.normal(9e5, 1e3, 24))
    rid = 0
    for i in range(0, 64, 16):
        for k in keys2[i:i + 16]:
            fe.submit(ServiceRequest(rid, "point", float(k),
                                     deadline_s=60.0))
            rid += 1
        lo = float(keys2[i])
        fe.submit(ServiceRequest(rid, "range", lo, hi=lo * (1 + 1e-4),
                                 deadline_s=60.0))
        rid += 1
    for j, k in enumerate(spare3):
        fe.submit(ServiceRequest(rid, "insert", float(k),
                                 payload=30_000 + j, deadline_s=60.0))
        rid += 1
    fe.submit(ServiceRequest(rid, "delete", float(keys2[0]),
                             deadline_s=60.0))
    fe.drain()
    return idx, nfl


def collect_captures(entries=ENTRY_POINTS, **world_kw):
    with capture_entry_calls(entries) as captured:
        exercise_serving_world(**world_kw)
    return captured


# ------------------------------------------------------ trace + check
def _split_static(args: tuple) -> Tuple[list, dict]:
    """Split positional args into traced array pytrees and
    bake-into-closure statics (ints, shape tuples, ``None`` tier
    slots) — statics fed to ``make_jaxpr`` as tracers would leak into
    the inner jit's static params."""
    traced, static = [], {}
    for i, a in enumerate(args):
        leaves = jax.tree_util.tree_leaves(a)
        if leaves and all(hasattr(x, "shape") and hasattr(x, "dtype")
                          for x in leaves):
            traced.append((i, a))
        else:
            static[i] = a
    return traced, static


def trace_capture(entry: EntryPoint, args: tuple, kwargs: dict):
    real = entry.target()
    traced, static = _split_static(args)

    def rebuilt(*t):
        merged = dict(static)
        for (i, _), val in zip(traced, t):
            merged[i] = val
        return real(*(merged[i] for i in range(len(args))), **kwargs)

    return jax.make_jaxpr(rebuilt)(*(a for _, a in traced))


def lower_capture(entry: EntryPoint, args: tuple,
                  kwargs: dict) -> Optional[str]:
    real = entry.target()
    try:
        if hasattr(real, "lower"):
            return real.lower(*args, **kwargs).as_text()
        traced, static = _split_static(args)

        def rebuilt(*t):
            merged = dict(static)
            for (i, _), val in zip(traced, t):
                merged[i] = val
            return real(*(merged[i] for i in range(len(args))), **kwargs)

        return jax.jit(rebuilt).lower(*(a for _, a in traced)).as_text()
    except Exception:
        return None


def run_static_checks(report: Report, entries=ENTRY_POINTS,
                      captured: Optional[dict] = None,
                      check_hlo: bool = True) -> Report:
    """Contract 1 (host escape) + contract 4 (lints) over every
    registered entry point, at both jaxpr and lowered-module level."""
    from repro.utils.hlo import f64_census, host_escape_ops

    if captured is None:
        captured = collect_captures(entries)
    for entry in entries:
        calls = captured.get(entry.name, [])
        if not calls:
            report.add(Finding(
                contract="host-escape", entry=entry.name,
                location=entry.location(), severity="error",
                message=(f"entry point `{entry.module}.{entry.attr}` was "
                         "never dispatched by the serving world: the "
                         "registry and the serving path have drifted "
                         "apart — fix the exerciser or retire the entry"),
                details={"captured": 0}))
            continue
        for args, kwargs in calls:
            closed = trace_capture(entry, args, kwargs)
            check_jaxpr(closed, entry.name, report,
                        trip_budget=entry.trip_budget)
            if check_hlo and entry.check_hlo:
                text = lower_capture(entry, args, kwargs)
                if text is None:
                    continue
                escapes = host_escape_ops(text)
                for target, count in escapes.items():
                    report.add(Finding(
                        contract="host-escape", entry=entry.name,
                        location=entry.location(),
                        message=(f"lowered module contains {count}x "
                                 f"host round-trip op `{target}`"),
                        details={"target": target, "count": count}))
                n_f64 = f64_census(text)
                if n_f64:
                    report.add(Finding(
                        contract="lint", entry=entry.name,
                        location=entry.location(),
                        message=(f"lowered module carries {n_f64} "
                                 "f64-typed values (serving is "
                                 "f32-by-design, DESIGN.md §8)"),
                        details={"f64_values": n_f64}))
                if not escapes:
                    report.note_pass(entry.name, "host-escape-hlo")
    return report


def run_all(report: Optional[Report] = None, *,
            allowlist: Optional[List[str]] = None,
            check_hlo: bool = True, check_retrace: bool = True,
            check_vmem: bool = True) -> Report:
    """Full contract sweep: static jaxpr/HLO checks, the retrace-budget
    lattice drive, and the VMEM proof."""
    from repro.analysis import retrace, vmem

    report = report or Report(allowlist=allowlist)
    run_static_checks(report, check_hlo=check_hlo)
    if check_retrace:
        retrace.run_retrace_check(report)
    if check_vmem:
        vmem.run_vmem_checks(report)
    return report
