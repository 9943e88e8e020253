"""Profiler spans at the program's layer boundaries (``repro.obs``), the
front end's queue-wait counter and its bounded latency window."""

import glob
import os
import re
from collections import deque

import jax
import numpy as np
import pytest

from repro import obs
from repro.core.flat_afli import FlatAFLI, FlatAFLIConfig
from repro.core.nfl import NFL, NFLConfig
from repro.core.train_flow import FlowTrainConfig
from repro.kernels import ops
from repro.serve.frontend import (LATENCY_WINDOW, FrontEnd, FrontEndConfig,
                                  ServiceRequest)

SRC = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))), "src", "repro")
FE_SPANS = ("fe.form", "fe.dispatch", "fe.gather", "fe.resolve")


def _host_spans(path):
    """``(name, start_ns, end_ns, stats)`` of every program span in the
    newest profile under ``path``."""
    from jax.profiler import ProfileData

    f = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                         recursive=True))[-1]
    out = []
    for p in ProfileData.from_file(f).planes:
        if not p.name.startswith("/host:"):
            continue
        for ln in p.lines:
            for e in ln.events:
                if e.name in obs.SPANS:
                    out.append((e.name, e.start_ns,
                                e.start_ns + e.duration_ns, dict(e.stats)))
    return out


def _inside(inner, outers):
    return any(s <= inner[1] and inner[2] <= e for _, s, e, _ in outers)


def test_span_off_builds_nothing(monkeypatch):
    def boom(*a, **k):
        raise AssertionError("built a TraceAnnotation with no trace running")

    monkeypatch.setattr(obs, "TraceAnnotation", boom)
    assert not jax.profiler.TraceAnnotation.is_enabled()
    cm = obs.span("fe.dispatch", batch=7)
    assert cm is obs._OFF
    assert obs.span("nfl.lookup") is cm
    with cm, obs.span("afli.point.wait"):
        pass


def test_every_span_site_is_named_in_spans():
    used = set()
    for path in glob.glob(os.path.join(SRC, "**", "*.py"), recursive=True):
        with open(path) as f:
            used |= set(re.findall(r'\bspan\("([^"]+)"', f.read()))
    assert used == set(obs.SPANS)


@pytest.fixture
def xla_routes(monkeypatch):
    # the chip's routes: points and ranges as XLA over the device pools
    monkeypatch.setattr(ops, "traversal_route", lambda interpret: "xla")


def test_serve_path_spans_under_a_trace(tmp_path, xla_routes):
    rng = np.random.default_rng(5)
    keys = np.unique(np.floor(rng.lognormal(0, 2, 6_000) * 1e9))
    load, spare = keys[::2], keys[1::2]
    nfl = NFL(NFLConfig(backend="flat", force_flow=True,
                        flow_train=FlowTrainConfig(epochs=1)))
    nfl.bulkload(load, np.arange(load.shape[0], dtype=np.int64))
    fe = FrontEnd(nfl, FrontEndConfig(max_batch=64))
    reqs = [ServiceRequest(i, "point", float(k), deadline_s=3600.0)
            for i, k in enumerate(rng.choice(load, 100))]
    lo = np.sort(rng.choice(load, 4))
    reqs += [ServiceRequest(200 + i, "range", float(k), hi=float(k) * 1.01,
                            deadline_s=3600.0) for i, k in enumerate(lo)]
    reqs += [ServiceRequest(300, "insert", float(spare[0]), payload=7,
                            deadline_s=3600.0)]
    jax.profiler.start_trace(str(tmp_path))
    try:
        for r in reqs:
            fe.submit(r)
        fe.drain()
    finally:
        jax.profiler.stop_trace()
    assert all(r.state == "completed" for r in reqs)

    ev = _host_spans(str(tmp_path))
    by = {}
    for e in ev:
        by.setdefault(e[0], []).append(e)
    want = set(obs.SPANS) - {"afli.run_merge", "afli.fold_tick"}
    assert want <= set(by), sorted(want - set(by))
    # one span per batch, never per request: 2 point batches (64 + 36)
    assert len(by["fe.dispatch"]) == fe.counters["batches"] == 4
    assert len(by["nfl.lookup"]) == 2
    # nesting on the host thread: features inside the lookup inside the
    # front end's dispatch; the wait inside the gather
    for f in by["nfl.features"]:
        if _inside(f, by["nfl.lookup"]):
            assert _inside(f, by["fe.dispatch"])
    looks = [f for f in by["nfl.features"] if _inside(f, by["nfl.lookup"])]
    assert len(looks) == 2
    assert all(_inside(e, by["nfl.lookup"]) for e in by["afli.point.enqueue"])
    assert all(_inside(e, by["fe.gather"]) for e in by["afli.point.wait"])
    assert all(_inside(e, by["nfl.scan"]) for e in by["afli.scan.wait"])
    assert all(_inside(e, by["nfl.insert"]) for e in by["afli.insert.delta"])
    # the front end's spans carry the batch number; a batch's spans share it
    for name in FE_SPANS:
        assert all("batch" in st for *_, st in by[name]), name
    assert sorted(st["batch"] for *_, st in by["fe.dispatch"]) == [1, 2, 3, 4]
    gathered = {st["batch"] for *_, st in by["fe.gather"]}
    assert gathered == {1, 2}


def test_merge_and_fold_spans_under_a_trace(tmp_path):
    rng = np.random.default_rng(6)
    keys = np.unique(rng.uniform(0, 1e9, 6_000))
    load, spare = keys[:2_000], keys[2_000:]
    idx = FlatAFLI(FlatAFLIConfig(delta_cap=24, fold_step_keys=48,
                                  rebuild_frac=0.02, fold_work_factor=4.0))
    idx.build(load, np.arange(load.shape[0], dtype=np.int64))
    jax.profiler.start_trace(str(tmp_path))
    try:
        for i in range(4):
            new = spare[30 * i:30 * (i + 1)]
            idx.insert_batch(new, np.arange(30) + 10_000 * (i + 1))
    finally:
        jax.profiler.stop_trace()
    names = {e[0] for e in _host_spans(str(tmp_path))}
    assert {"afli.run_merge", "afli.fold_tick", "afli.insert.delta",
            "afli.tier_sync"} <= names


class _StubIndex:
    def lookup_batch_async(self, keys):
        return lambda: np.zeros(len(keys), np.int64)

    def insert_batch(self, keys, payloads):
        return None


def test_queue_wait_is_submit_to_dispatch_under_a_fake_clock():
    now = [1.0]
    fe = FrontEnd(_StubIndex(),
                  FrontEndConfig(max_batch=2, max_inflight=1,
                                 admission=False, batch_timeout_s=0.25),
                  clock=lambda: now[0])
    dispatched = []

    def hook(op, batch):
        dispatched.extend((now[0], r.t_submit) for r in batch)

    fe.on_batch_dispatched = hook
    plan = [(1.0, "point", 60.0), (1.25, "point", 60.0),
            (1.5, "insert", 60.0), (2.0, None, 0), (2.5, None, 0),
            (2.5, "point", 0.125), (3.0, None, 0), (3.5, "point", 60.0),
            (4.5, None, 0)]
    rid = 0
    for t, op, deadline in plan:
        now[0] = t
        if op is None:
            fe.step()
        else:
            fe.submit(ServiceRequest(rid, op, float(rid), payload=rid,
                                     deadline_s=deadline))
            rid += 1
    fe.drain()
    want = sum(round((td - ts) * 1e9) for td, ts in dispatched)
    assert fe.counters["dispatched_requests"] == len(dispatched) == 4
    assert fe.counters["expired"] == 1      # never dispatched, not counted
    assert fe.counters["queue_wait_ns"] == want == 3_750_000_000


def test_latency_window_keeps_the_newest():
    now = [0.0]
    fe = FrontEnd(_StubIndex(), FrontEndConfig(max_batch=1, max_inflight=1,
                                              admission=False),
                  clock=lambda: now[0])
    assert fe.latency_percentiles()["n"] == 0
    assert fe._served_lat.maxlen == fe._ontime_lat.maxlen == LATENCY_WINDOW
    fe._served_lat = deque(maxlen=100)
    fe._ontime_lat = deque(maxlen=100)
    for i in range(250):
        now[0] = 10.0 * i
        fe.submit(ServiceRequest(i, "point", float(i), deadline_s=60.0))
        now[0] += i * 1e-3          # request i is answered i ms after submit
        fe.drain()
    newest = np.arange(150, 250) * 1e6
    for which in ("served", "ontime"):
        p = fe.latency_percentiles(which)
        assert p["n"] == 100
        assert p["max_ns"] == pytest.approx(newest.max())
        assert p["p50_ns"] == pytest.approx(np.percentile(newest, 50))
    assert fe.stats()["latency_served"]["n"] == 100


def test_front_end_latency_telemetry_is_bounded():
    now = [0.0]
    fe = FrontEnd(_StubIndex(), FrontEndConfig(max_batch=256,
                                              admission=False),
                  clock=lambda: now[0])
    fe._served_lat = deque(maxlen=512)
    fe._ontime_lat = deque(maxlen=512)
    for i in range(3_000):
        now[0] = i * 1e-3
        fe.submit(ServiceRequest(i, "point", float(i), deadline_s=60.0))
        if i % 100 == 99:
            fe.drain()
    fe.drain()
    assert len(fe._served_lat) == len(fe._ontime_lat) == 512
    p = fe.latency_percentiles()
    assert p["n"] == 512
    assert 0.0 <= p["p50_ns"] <= p["max_ns"] <= 0.1 * 1e9
