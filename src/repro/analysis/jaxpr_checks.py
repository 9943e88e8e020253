"""Jaxpr-level contract checks: host escape + devectorization/dtype
lints (DESIGN.md §15).

The walker descends every sub-jaxpr (``pjit`` bodies, ``scan``/
``while``/``cond`` branches, ``pallas_call`` kernel bodies, …) because
the interesting primitives almost never sit at the top level —
``jnp.take`` wraps its gather inside a ``pjit`` equation, and a kernel
body is an entire jaxpr hanging off the ``pallas_call`` params.

Checks map to previously-shipped bugs:

- gather mode CLIP / FILL_OR_DROP in a kernel body — PR 3's clip-mode
  ``jnp.take`` devectorized the XLA:CPU inner loop (~2x); plain
  ``arr[idx]`` lowers to PROMISE_IN_BOUNDS and stays vectorized.
- batch-length static loop trips in a kernel body — a ``fori_loop``
  over the whole batch defeats the tiled grid the kernel was given.
- identity-lane narrowing — the u64 identity rides as two u32 lanes;
  any cast of an unsigned lane to float (f32 mantissa: 24 bits) or a
  narrower int silently corrupts identity resolution.
- f64 anywhere in a serving jaxpr — the serving path is f32-by-design
  (DESIGN.md §8); an f64 upcast doubles VMEM traffic and falls off
  the TPU fast path.
- callbacks — ``pure_callback``/``io_callback``/``debug_callback``
  inside a serving region is a host round-trip per dispatch.
"""

from __future__ import annotations

from typing import Any, Callable, Iterator, List, Optional

import jax
import numpy as np
from jax._src import core as jax_core
from jax._src import source_info_util

from repro.analysis.findings import Finding, Report

# Primitives that round-trip through the host.  ``debug_print`` covers
# pl.debug_print in interpret mode; jax.debug.print lowers to
# debug_callback.
HOST_CALLBACK_PRIMS = frozenset({
    "pure_callback", "io_callback", "debug_callback", "callback",
    "outside_call", "infeed", "outfeed", "host_local_array_to_global_array",
})

# Loop-carrying primitives with a static trip count in params.
_LOOP_LENGTH_PARAMS = {"scan": "length"}

_BAD_GATHER_MODES = ("CLIP", "FILL_OR_DROP")


def _iter_sub_jaxprs(params: dict) -> Iterator[jax_core.Jaxpr]:
    """Yield every Jaxpr reachable from an equation's params — handles
    bare Jaxpr/ClosedJaxpr values and tuples/lists of them (``cond``
    branches, custom_vjp bundles, …)."""
    for val in params.values():
        vals = val if isinstance(val, (tuple, list)) else (val,)
        for v in vals:
            if isinstance(v, jax_core.ClosedJaxpr):
                yield v.jaxpr
            elif isinstance(v, jax_core.Jaxpr):
                yield v


def eqn_location(eqn) -> str:
    """Best-effort ``file.py:line`` for an equation."""
    try:
        summary = source_info_util.summarize(eqn.source_info)
    except Exception:
        return "<unknown>"
    # summarize() yields "path/to/file.py:123:7 (fn_name)"; findings key
    # on file:line, so the column is dropped
    if not summary:
        return "<unknown>"
    path, line = summary.split(" ")[0].split(":")[:2]
    return f"{path}:{line}"


def walk_jaxpr(jaxpr: jax_core.Jaxpr,
               visit: Callable[[Any, bool], None],
               in_kernel: bool = False) -> None:
    """Depth-first walk calling ``visit(eqn, in_kernel)`` on every
    equation; ``in_kernel`` flips once the walk crosses a
    ``pallas_call`` boundary (the kernel body jaxpr)."""
    for eqn in jaxpr.eqns:
        visit(eqn, in_kernel)
        child_in_kernel = in_kernel or eqn.primitive.name == "pallas_call"
        for sub in _iter_sub_jaxprs(eqn.params):
            walk_jaxpr(sub, visit, child_in_kernel)


def _gather_mode_name(eqn) -> Optional[str]:
    mode = eqn.params.get("mode")
    if mode is None:
        return None
    name = getattr(mode, "name", str(mode))
    # GatherScatterMode reprs like "GatherScatterMode.CLIP"
    return name.rsplit(".", 1)[-1].upper()


def _is_unsigned(dtype) -> bool:
    return np.issubdtype(np.dtype(dtype), np.unsignedinteger)


def check_jaxpr(closed: jax_core.ClosedJaxpr, entry: str, report: Report,
                *, trip_budget: int = 256,
                allow_callbacks: bool = False) -> List[Finding]:
    """Run every jaxpr-level check on one traced entry point.

    Returns the findings added (also pushed into ``report``); notes a
    pass per contract when a check comes up clean.
    """
    found: List[Finding] = []
    seen: set = set()

    def emit(contract: str, location: str, message: str, **details) -> None:
        # dedupe: one finding per (contract, location, message head) —
        # an f64 leak taints every downstream op at the same call site
        dedup = (contract, location, message.split(":", 1)[0])
        if dedup in seen:
            return
        seen.add(dedup)
        f = Finding(contract=contract, entry=entry, location=location,
                    message=message, details=details)
        found.append(f)
        report.add(f)

    def visit(eqn, in_kernel: bool) -> None:
        prim = eqn.primitive.name
        loc = eqn_location(eqn)

        # ---- host escape: callbacks and host-feed primitives
        if prim in HOST_CALLBACK_PRIMS and not allow_callbacks:
            emit("host-escape", loc,
                 f"`{prim}` in serving region: one host round-trip per "
                 "dispatch; serving jaxprs must stay on-device",
                 primitive=prim, in_kernel=in_kernel)

        # ---- lint: devectorizing gather modes
        if prim == "gather":
            mode = _gather_mode_name(eqn)
            if mode in _BAD_GATHER_MODES and in_kernel:
                emit("lint", loc,
                     f"{mode.lower()}-mode gather in kernel body "
                     "(PR 3 bug class): use plain `arr[idx]` indexing, "
                     "which lowers to PROMISE_IN_BOUNDS and keeps the "
                     "inner loop vectorized",
                     gather_mode=mode, in_kernel=True)
            elif mode == "CLIP" and not in_kernel:
                emit("lint", loc,
                     "clip-mode gather on the serving path: clamping "
                     "defeats XLA's vectorized gather lowering",
                     gather_mode=mode, in_kernel=False)

        # ---- lint: static loop trip counts at batch scale
        if in_kernel and prim in _LOOP_LENGTH_PARAMS:
            length = eqn.params.get(_LOOP_LENGTH_PARAMS[prim])
            if isinstance(length, int) and length > trip_budget:
                emit("lint", loc,
                     f"static `{prim}` with {length} trips in kernel "
                     f"body exceeds the {trip_budget}-trip budget: a "
                     "batch-length loop defeats the tiled grid",
                     trips=length, budget=trip_budget)

        # ---- lint: identity-lane narrowing + f64 upcasts
        if prim == "convert_element_type":
            src = eqn.invars[0].aval.dtype
            dst = np.dtype(eqn.params.get("new_dtype"))
            if _is_unsigned(src) and np.issubdtype(dst, np.floating):
                emit("lint", loc,
                     f"cast {np.dtype(src).name}->{dst.name} narrows an "
                     "unsigned identity lane: f32 carries 24 mantissa "
                     "bits, u64 identities ride as two u32 lanes and "
                     "must stay integral",
                     src=np.dtype(src).name, dst=dst.name)
            elif (_is_unsigned(src)
                  and np.issubdtype(dst, np.integer)
                  and dst.itemsize < np.dtype(src).itemsize):
                emit("lint", loc,
                     f"cast {np.dtype(src).name}->{dst.name} drops high "
                     "bits of an identity lane",
                     src=np.dtype(src).name, dst=dst.name)
            if dst == np.dtype(np.float64):
                emit("lint", loc,
                     "f64 upcast on the serving path: doubles VMEM "
                     "traffic and leaves the TPU fast path "
                     "(serving is f32-by-design, DESIGN.md §8)",
                     dst="float64")

        # ---- lint: f64 avals appearing anywhere
        for var in eqn.outvars:
            aval = getattr(var, "aval", None)
            dtype = getattr(aval, "dtype", None)
            if dtype is not None and np.dtype(dtype) == np.dtype(np.float64):
                if prim != "convert_element_type":   # cast already flagged
                    emit("lint", loc,
                         f"`{prim}` produces float64 in a serving jaxpr",
                         primitive=prim)
                break

    walk_jaxpr(closed.jaxpr, visit)

    contracts_hit = {f.contract for f in found}
    for contract in ("host-escape", "lint"):
        if contract not in contracts_hit:
            report.note_pass(entry, contract)
    return found


def trace_entry(fn: Callable, *args, **kwargs) -> jax_core.ClosedJaxpr:
    """``jax.make_jaxpr`` shim that tolerates jitted callables."""
    wrapped = getattr(fn, "__wrapped__", fn)
    return jax.make_jaxpr(wrapped, **{})(*args, **kwargs) if not kwargs \
        else jax.make_jaxpr(lambda *a: wrapped(*a, **kwargs))(*args)
