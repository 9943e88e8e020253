"""The program's own profiler spans (``repro.obs.SPANS``) and counters
leave every metric the benchmark reads as it was: the reduction keeps
only the benchmark's spans, and the front end's new counter rides along
in ``fe_window`` without moving the readers of the old ones."""

import os
import sys
from types import SimpleNamespace as NS

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for _p in (ROOT, os.path.join(ROOT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from perfbench import trace as tr  # noqa: E402
from perfbench.harness import SPANS, load_reader  # noqa: E402
from repro.obs import SPANS as PROGRAM_SPANS  # noqa: E402

RECORDED = os.path.join(ROOT, "perfbench", "tests", "data",
                        "lgn-ro-closed.xplane.pb")


def _copy(planes):
    return [NS(name=p.name, lines=[
        NS(name=ln.name, events=[NS(name=e.name, start_ns=e.start_ns,
                                    duration_ns=e.duration_ns)
                                 for e in ln.events])
        for ln in p.lines]) for p in planes]


def _with_program_spans(planes):
    """The planes with the program's spans nested as a serve path
    records them: ``fe.dispatch`` around each ``index.lookup_async``,
    ``nfl.lookup`` inside it with ``nfl.features`` then
    ``afli.point.enqueue``; ``fe.gather`` around each ``index.finish``
    with ``afli.point.wait``; a ``fe.form`` just before each dispatch."""
    out = _copy(planes)
    for p in out:
        if not p.name.startswith("/host:"):
            continue
        for ln in p.lines:
            extra = []
            for e in ln.events:
                s, d = e.start_ns, e.duration_ns
                if e.name == "index.lookup_async":
                    extra += [NS(name="fe.form", start_ns=s - 30,
                                 duration_ns=20),
                              NS(name="fe.dispatch", start_ns=s - 5,
                                 duration_ns=d + 10),
                              NS(name="nfl.lookup", start_ns=s + 1,
                                 duration_ns=d - 2),
                              NS(name="nfl.features", start_ns=s + 2,
                                 duration_ns=(d - 4) // 3),
                              NS(name="afli.point.enqueue",
                                 start_ns=s + 2 + (d - 4) // 3,
                                 duration_ns=(d - 4) // 2)]
                elif e.name == "index.finish":
                    extra += [NS(name="fe.gather", start_ns=s - 5,
                                 duration_ns=d + 10),
                              NS(name="afli.point.wait", start_ns=s + 1,
                                 duration_ns=max(d - 2, 0))]
            ln.events = list(ln.events) + extra
    return out


def _same(a, b):
    assert a.window == b.window
    assert a.busy_s == b.busy_s
    assert a.op_s == b.op_s
    assert a.program_s == b.program_s
    assert a.span_s == b.span_s
    assert a.idle_by_span == b.idle_by_span
    assert a.breakdown() == b.breakdown()


def test_recorded_trace_reads_the_same_with_program_spans():
    from jax.profiler import ProfileData

    planes = _copy(ProfileData.from_file(RECORDED).planes)
    spanned = _with_program_spans(planes)
    n_added = sum(len(ln.events) for p in spanned for ln in p.lines) - sum(
        len(ln.events) for p in planes for ln in p.lines)
    assert n_added > 0
    _same(tr.reduce_planes(spanned, SPANS), tr.reduce_planes(planes, SPANS))


def test_hand_made_trace_reads_the_same_with_program_spans():
    from perfbench.tests.test_perfbench_trace import hand_made

    base = tr.reduce_planes(hand_made(), SPANS)
    spanned = tr.reduce_planes(_with_program_spans(hand_made()), SPANS)
    _same(spanned, base)
    assert not set(PROGRAM_SPANS) & set(spanned.span_s)


def test_program_and_benchmark_span_names_are_apart():
    assert len(set(PROGRAM_SPANS)) == len(PROGRAM_SPANS)
    assert not set(PROGRAM_SPANS) & set(SPANS)


@pytest.mark.parametrize("window,want", [
    ({"batches": 4, "dispatched_requests": 41}, 10.25),
    ({"batches": 4, "dispatched_requests": 41, "queue_wait_ns": 9_000}, 10.25),
    ({"batches": 0, "dispatched_requests": 0, "queue_wait_ns": 0}, None),
])
def test_batch_fill_reads_the_same_beside_queue_wait(window, want):
    run = NS(fe_window=window)
    got = load_reader("fe.batch_fill.closed")(run)
    assert got == (None if want is None else pytest.approx(want))


def test_recorded_trace_idle_is_unchanged_in_total():
    from jax.profiler import ProfileData

    r = tr.reduce_planes(
        _with_program_spans(_copy(ProfileData.from_file(RECORDED).planes)),
        SPANS)
    assert (sum(r.idle_by_span.values())
            == pytest.approx(r.window_s - r.busy_s))
    assert np.isclose(r.busy_s, 4.895263e-3, atol=1e-12)
