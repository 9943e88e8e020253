"""The XLA serve routes of a compiled TPU backend (DESIGN.md §2), on the CPU.

On the chip ``ops.traversal_route`` sends every point batch to
``flat_afli.xla_lookup`` and every range batch to
``range_scan.xla_range_scan``.  Here the rule is pinned to ``"xla"`` so
the same routes run on the CPU (their NF kernel interpreted), and every
answer is held to the Pallas kernels of the interpreter route, whose
parity with the host oracles the kernel suites pin.  Each scenario
leaves entries in the tree, the compacted run and the active delta, and
tombstones in the tiers, so both routes resolve all three tiers.
"""

import numpy as np
import pytest

from repro.core.flat_afli import FlatAFLI, FlatAFLIConfig
from repro.core.train_flow import FlowTrainConfig
from repro.core.nfl import NFL, NFLConfig
from repro.kernels import ops


def test_route_rule_is_static():
    assert ops.traversal_route(True) == "pallas"
    assert ops.traversal_route(False) == "xla"


def _flat(keys, cfg=None, ikeys=None):
    idx = FlatAFLI(cfg or FlatAFLIConfig(delta_cap=1500))
    idx.build(keys, np.arange(len(keys), dtype=np.int64), ikeys=ikeys)
    return idx


def _scenario(kind):
    """(index, point queries, range lo, range hi) after a write mix."""
    rng = np.random.default_rng({"model": 31, "dense": 32, "dup-f32": 33,
                                 "flow": 34}[kind])
    if kind == "flow":
        keys = np.unique(np.floor(rng.lognormal(0, 2, 20_000) * 1e9))
        idx = NFL(NFLConfig(flow_train=FlowTrainConfig(epochs=1),
                            backend="flat",
                            flat_index=FlatAFLIConfig(delta_cap=1500)))
        idx.bulkload(keys[::2], np.arange(len(keys[::2]), dtype=np.int64))
        assert idx.use_flow
    elif kind == "dense":
        keys = np.unique(rng.uniform(0, 1e6, 3_000))
        idx = _flat(keys[::2], FlatAFLIConfig(max_depth=1, delta_cap=1500))
    elif kind == "dup-f32":
        keys = 1e15 + np.arange(4_000, dtype=np.float64) * 3.0
        idx = _flat(keys[::2])
    else:
        keys = np.unique(rng.uniform(0, 1e9, 20_000))
        idx = _flat(keys[::2])
    built, new = keys[::2], keys[1::2][:2_000]
    idx.insert_batch(new, np.arange(len(new)) + 10_000_000)   # -> run
    idx.insert_batch(new[:300], np.arange(300) + 20_000_000)  # delta
    idx.delete_batch(np.concatenate([built[:200], new[300:400]]))
    q = np.concatenate([built[:1500], new, keys[1::2][2_000:2_500]])
    lo = np.sort(rng.choice(keys, 64))
    hi = lo + (keys[-1] - keys[0]) * rng.uniform(1e-4, 1e-2, 64)
    if kind == "dup-f32":   # straddle the few f32 values the keys share
        lo = 1e15 - rng.uniform(0, 2e8, 64)
        hi = lo + 2e8
    return idx, q, lo, hi


@pytest.mark.parametrize("kind", ["model", "dense", "dup-f32", "flow"])
def test_xla_routes_match_pallas_routes(kind, monkeypatch):
    idx, q, lo, hi = _scenario(kind)
    flat = idx.index if isinstance(idx, NFL) else idx
    p_pallas = idx.lookup_batch(q)
    s_pallas = idx.scan_batch(lo, hi, cap=256)
    assert flat.last_dispatch["path"] == "fused"
    assert flat.last_scan_dispatch["path"] == "fused"

    monkeypatch.setattr(ops, "traversal_route", lambda interpret: "xla")
    ops.reset_fused_lookup_stats()
    p_xla = idx.lookup_batch(q)
    assert flat.last_dispatch["path"] == "xla"
    assert flat.last_dispatch["n_dispatch"] == 1
    assert flat.last_dispatch["tier_path"] == "device"
    s_xla = idx.scan_batch(lo, hi, cap=256)
    assert flat.last_scan_dispatch["path"] == "xla"

    assert np.array_equal(p_xla, p_pallas)
    assert (p_xla[:200] == -1).all()                  # tombstoned
    assert (p_xla[1500:1800] >= 20_000_000).all()     # newest copy wins
    for got, want in zip(s_xla, s_pallas):
        assert np.array_equal(got, want)
    assert s_xla[1].sum() > 0
    st = ops.fused_lookup_stats()
    assert st["xla_count"] == 1 and st["scan_xla_count"] == 1
    assert st["host_probe_count"] == 0 and st["scan_fallback_count"] == 0
    assert st["fused_count"] == 0 and st["scan_fused_count"] == 0
