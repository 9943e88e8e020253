"""The trace reduction: busy union, device time by stable name, and idle
gaps named by the host spans, on a hand-made trace whose numbers are
worked out by hand, and on a small trace recorded on a TPU v5e chip."""

import os
import sys
from types import SimpleNamespace as NS

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from perfbench import trace as tr  # noqa: E402
from perfbench.harness import SPANS  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
RECORDED = os.path.join(HERE, "data", "lgn-ro-closed.xplane.pb")


def ev(name, start, dur):
    return NS(name=name, start_ns=start, duration_ns=dur)


def hand_made():
    """Window 0..1000 ns (the host spans' extent).  Device: program P
    over 100..400 holding ops a (100..300) and b (250..400, overlapping
    a), program Q over 600..700 holding op c; one op before the window.
    Host: fe.step 0..1000 around index.finish 400..550 and gen
    800..1000."""
    dev = NS(name="/device:TPU:0", lines=[
        NS(name="XLA Modules", events=[ev("jit_P(77)", 100, 300),
                                       ev("jit_Q(78)", 600, 100)]),
        NS(name="XLA Ops", events=[
            ev("%a.3 = f32[8] fusion(f32[8] %x)", 100, 200),
            ev("%b.1 = f32[8] copy(f32[8] %y)", 250, 150),
            ev("%c.9 = s32[4] custom-call()", 600, 100),
            ev("%d.0 = s32[4] copy()", -50, 20)]),
        NS(name="Async XLA Ops", events=[ev("%copy-start", 0, 1000)]),
    ])
    host = NS(name="/host:CPU", lines=[NS(name="python3", events=[
        ev("fe.step", 0, 1000), ev("index.finish", 400, 150),
        ev("gen", 800, 200), ev("$unrelated", 0, 5000)])])
    return [NS(name="/host:metadata", lines=[]), dev, host]


def test_hand_made_numbers():
    r = tr.reduce_planes(hand_made(), SPANS)
    assert r.window == (0.0, 1000.0)
    assert r.window_s == pytest.approx(1000e-9)
    # busy = [100, 400) + [600, 700) = 400 ns
    assert r.busy_s == pytest.approx(400e-9)
    assert r.program_seconds("jit_P") == pytest.approx(300e-9)
    assert r.program_seconds("jit_Q") == pytest.approx(100e-9)
    # self times: a loses the 50 ns that b overlaps; they add up to busy
    assert r.op_s == pytest.approx({"jit_P:a": 150e-9, "jit_P:b": 150e-9,
                                    "jit_Q:c": 100e-9})
    assert r.op_seconds("c") == pytest.approx(100e-9)
    # idle gaps [0,100) [400,600) [700,1000): the first is fe.step's
    # own; the second index.finish's (150 of 200 ns); the third gen's
    # (200 of 300 ns)
    assert r.idle_by_span == pytest.approx({"fe.step": 100e-9,
                                            "index.finish": 200e-9,
                                            "gen": 300e-9})
    assert r.span_seconds("index.finish") == pytest.approx(150e-9)
    b = r.breakdown()
    assert [k for k, _ in b["device_ops"]] == ["jit_P:a", "jit_P:b",
                                               "jit_Q:c"]
    assert [k for k, _ in b["idle_gaps"]] == ["gen", "index.finish",
                                              "fe.step"]


def test_no_device_plane_reads_nothing():
    planes = [p for p in hand_made() if not p.name.startswith("/device")]
    assert tr.reduce_planes(planes, SPANS) is None


@pytest.mark.parametrize("name,want", [
    ("%nf_forward_pallas.1 = f32[1024]{0} custom-call(f32[1024,2] %x)",
     "nf_forward_pallas"),
    ("jit_xla_lookup(182067467156564453)", "jit_xla_lookup"),
    ("%fusion.12 = f32[8] fusion()", "fusion"),
    ("fe.step", "fe.step"),
])
def test_stable_names(name, want):
    assert tr.stable(name) == want


def test_union_and_gaps():
    import numpy as np

    u = tr.union(np.array([[5, 7], [0, 2], [1, 3], [7, 8]], float))
    assert u.tolist() == [[0, 3], [5, 8]]
    assert tr.gaps(u, -1, 10).tolist() == [[-1, 0], [3, 5], [8, 10]]
    assert tr.overlap(u, 2, 6) == 2.0


def test_nested_ops_count_once():
    # a while op spanning its body's two fusions: self time 40 of 100
    ops = [("%while.1 = ()", 0.0, 100.0), ("%fusion.2 = ()", 10.0, 30.0),
           ("%fusion.3 = ()", 50.0, 30.0)]
    got = {n: t for n, _, t in tr.self_times(ops, 0.0, 1000.0)}
    assert got == {"%while.1 = ()": 40.0, "%fusion.2 = ()": 30.0,
                   "%fusion.3 = ()": 30.0}


def test_recorded_trace():
    """25 ms of ``lgn-ro-closed`` (seed 1005) on one TPU v5e chip, cut by
    ``data/slice_trace.py``.  The busy time was checked against a 1 ns
    grid over the window (4,895,263 ns busy, 18,517,135 ns idle)."""
    r = tr.reduce_trace(os.path.dirname(RECORDED), SPANS)
    assert r.n_devices == 1
    assert r.window == (145494992.0, 168907390.0)
    assert r.busy_s == pytest.approx(4.895263e-3, abs=1e-12)
    assert sum(r.op_s.values()) == pytest.approx(r.busy_s)
    assert set(r.program_s) == {"jit_xla_lookup"}
    assert r.program_seconds("jit_xla_lookup") == pytest.approx(4.897795e-3)
    assert r.op_seconds("nf_forward_pallas") == pytest.approx(3.3031e-05)
    assert r.breakdown()["device_ops"][0][0] == "jit_xla_lookup:fusion"
    assert r.idle_by_span == pytest.approx(
        {"fe.step": 0.018061612, "index.lookup_async": 0.000455523})
    assert (sum(r.idle_by_span.values())
            == pytest.approx(r.window_s - r.busy_s))


def test_insert_ms_per_call_reads_the_insert_span_over_traced_calls():
    """Two ``index.insert`` spans of 2 and 3 ms, overlapping by 1 ms:
    4 ms of union over two traced calls is 2 ms a call."""
    from perfbench.harness import load_reader

    ms = 1_000_000
    dev = NS(name="/device:TPU:0", lines=[
        NS(name="XLA Ops", events=[ev("%a.1 = f32[8] fusion()", 0, ms)])])
    host = NS(name="/host:CPU", lines=[NS(name="spans", events=[
        ev("fe.step", 0, 10 * ms), ev("index.insert", 1 * ms, 2 * ms),
        ev("index.insert", 2 * ms, 3 * ms)])])
    read = load_reader("write.insert_ms_per_call")
    red = tr.reduce_planes([dev, host], SPANS)
    assert read(NS(trace=red, traced={"insert_calls": 2})) == \
        pytest.approx(2.0)
    assert read(NS(trace=red, traced={"insert_calls": 0})) is None
    assert read(NS(trace=None, traced={"insert_calls": 2})) is None


def test_batch_fill_open_reads_the_traced_counters():
    from perfbench.harness import load_reader

    read = load_reader("fe.batch_fill.open")
    assert read(NS(fe_traced={"batches": 4, "dispatched_requests": 10})) \
        == 2.5
    assert read(NS(fe_traced={})) is None
