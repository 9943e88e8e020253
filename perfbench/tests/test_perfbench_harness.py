"""The harness on the CPU, at a size that runs in seconds: discovery by
name, a cell added as new files only, a host without a TPU, and the
comparison that decides ``correct`` catching every fault a run of these
cells can have, and the control."""

import io
import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for _p in (ROOT, os.path.join(ROOT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from perfbench import harness  # noqa: E402
from perfbench.testing import cpu_program, make_root  # noqa: E402

with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    BENCH = json.load(_f)

TINY_CONFIG = {
    "name": "tiny", "dataset": "ycsb", "n_keys": 2048,
    "index": {"backend": "flat", "shards": 1, "force_flow": False},
}
TINY_MIX = {
    "loop": "closed", "clients": 64,
    "mix": {"point": 0.6, "range": 0.2, "insert": 0.2},
    "point": {"zipf_s": 0.99},
    "range": {"zipf_s": 0.99, "len_min": 1, "len_max": 20},
    "deadline_s": 3600.0, "warmup_requests": 200,
}
TINY_READER = '''"""Requests sent in the window (a test metric)."""


def read(run):
    return run.attempted
'''


def tiny_root(tmp_path):
    cell = {"name": "tiny-mixed", "config": "tiny", "traffic": "tiny-mix",
            "chips": 1, "why": "test cell"}
    files = {
        "perfbench/configs/tiny.json": json.dumps(TINY_CONFIG),
        "perfbench/traffic/tiny-mix.json": json.dumps(TINY_MIX),
        "perfbench/metrics/test.requests.py": TINY_READER,
    }

    def edit(bench):
        bench["configs"].append({"name": "tiny", "source": "test",
                                 "file": "perfbench/configs/tiny.json",
                                 "reduced": []})
        bench["end_to_end"].append({
            "name": "test.requests", "unit": "count", "better": "higher",
            "bound": 0.25, "source": "host_clock",
            "workloads": ["tiny-mixed"]})

    return make_root(tmp_path, n_keys=2048, extra_cells=[(cell, files)],
                     edit=edit)


def run_tiny(root, system=None, seed=2 ** 31 + 12345):
    out = io.StringIO()
    rc = harness.run_cell("tiny-mixed", seed, 0.5, False, root=root,
                          require_chip=False, system=system, out=out)
    assert rc == 0
    return json.loads(out.getvalue().strip().splitlines()[-1])


# ---------------------------------------------------------- discovery
@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_cells_found_by_name(cell):
    c = harness.discover(cell)
    w = next(w for w in BENCH["workloads"] if w["name"] == cell)
    assert c.config["name"] == w["config"]
    assert c.mix["loop"] in ("closed", "open")
    names = {m["name"] for m in c.end_to_end}
    assert "setup_s" in names and len(names) >= 2
    assert c.per_layer, "every cell reports a per-layer metric"


@pytest.mark.parametrize("metric", [m["name"] for m in BENCH["end_to_end"]
                                    + BENCH["per_layer"]])
def test_every_metric_has_a_reader(metric):
    assert callable(harness.load_reader(metric))


def test_unknown_cell_is_an_error():
    with pytest.raises(KeyError):
        harness.discover("no-such-cell")


def test_peaks_table_is_keyed_by_device_kind():
    assert harness.load_peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        harness.load_peaks("cpu")


# ------------------------------------------------------------ no chip
def _run_cli(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "perfbench/run.py", "--workload", "lgn-ro-closed",
         "--seed", "3000000000", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=120)


def test_no_tpu_fails_without_a_result_line():
    p = _run_cli(ROOT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "TPU" in p.stderr


def test_bare_checkout_fails_without_a_result_line(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "perfbench"), tmp_path / "perfbench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    p = _run_cli(tmp_path)
    assert p.returncode != 0
    assert p.stdout.strip() == ""


# ------------------------------------------------- a cell of new files
def test_new_cell_of_new_files_runs_and_is_correct(tmp_path, monkeypatch):
    cpu_program(monkeypatch)
    res = run_tiny(tiny_root(tmp_path))
    assert res["correct"] is True, res
    assert res["failed"] == 0 and res["attempted"] > 0
    assert res["metrics"]["test.requests"]["value"] == res["attempted"]
    assert set(res["metrics"]) == {"test.requests", "ops_per_s", "p99_ms",
                                   "setup_s"}
    assert list(res)[-1] == "checks"
    assert res["checks"]["wrong_answers"] == {"value": 0, "limit": 0}


# --------------------------------------------- faults and the control
class Faulty:
    """The index with one fault planted in the timed path."""

    def __init__(self, index, fault):
        self._index, self._fault = index, fault

    def __getattr__(self, name):
        return getattr(self._index, name)

    def lookup_batch_async(self, keys):
        fin = self._index.lookup_batch_async(keys)

        def finish():
            res = np.array(fin())
            if self._fault == "answer_altered":
                res[0] = res[0] + 1 if res[0] >= 0 else 7
            elif self._fault == "half_batch_left_out":
                res[len(res) // 2:] = -1
            return res

        return finish

    def lookup_batch(self, keys):
        return self.lookup_batch_async(keys)()

    def insert_batch(self, keys, payloads):
        if self._fault == "state_unchanged":
            return None
        if (self._fault == "fold_inserts_dropped"
                and harness.write_path(self._index).get("fold_active")):
            return None
        return self._index.insert_batch(keys, payloads)

    def scan_batch(self, lo, hi):
        pv, cnt, tot = self._index.scan_batch(lo, hi)
        if self._fault == "range_altered":
            cnt = np.array(cnt)
            cnt[cnt > 0] -= 1
        return pv, cnt, tot


@pytest.mark.parametrize("fault", ["answer_altered", "half_batch_left_out",
                                   "state_unchanged", "range_altered"])
def test_fault_makes_the_run_incorrect(tmp_path, monkeypatch, fault):
    cpu_program(monkeypatch)

    def system(cell, k, p):
        return Faulty(harness.build_nfl(cell, k, p), fault)

    res = run_tiny(tiny_root(tmp_path), system=system)
    assert res["correct"] is False
    assert res["checks"]["wrong_answers"]["value"] > 0
    assert res["failed"] > 0


def test_control_is_refused(tmp_path, monkeypatch):
    cpu_program(monkeypatch)
    from perfbench.control import control_system

    # 62-bit keys held in float32 collide: the control answers wrong
    cfg = dict(TINY_CONFIG, n_keys=1 << 17)
    root = tiny_root(tmp_path)
    with open(os.path.join(root, "perfbench/configs/tiny.json"), "w") as f:
        json.dump(cfg, f)
    res = run_tiny(root, system=control_system)
    assert res["correct"] is False
    assert res["checks"]["wrong_answers"]["value"] > 0


def test_every_seed_is_offered_the_same_work():
    from perfbench.traffic.gen import RequestStream, load_mix, stratified_ops

    ops = stratified_ops([0.95, 0.05], 4096)
    assert np.bincount(ops).tolist() == [3891, 205]
    load = np.arange(0.0, 4000.0)
    ins = np.arange(4000.5, 9000.5)
    mix = load_mix("e-closed")
    work = []
    for seed in (1, 2 ** 31 + 7):
        st = RequestStream(mix, load, ins, np.arange(5000),
                           np.random.default_rng(seed))
        reqs = [st.next() for _ in range(RequestStream.CHUNK)]
        spans = sorted(int(np.searchsorted(load, h) - np.searchsorted(load, k))
                       for op, k, h, _ in reqs if op == "range")
        work.append((sorted(op for op, *_ in reqs), spans,
                     [op for op, *_ in reqs]))
    assert work[0][:2] == work[1][:2]       # the same ops and range lengths
    assert work[0][2] != work[1][2]         # in another order
    assert min(work[0][1]) == 1 and max(work[0][1]) == 100


# ------------------------------------------------------------ open loop
def test_open_loop_times_requests_from_their_scheduled_arrival():
    """A ``fe.step`` that stalls 50 ms shows in the latency of every
    request scheduled during the stall, not only in the step's own."""
    import time

    from perfbench.reference import ReferenceIndex
    from perfbench.traffic.gen import RequestStream, arrival_times, load_mix
    from repro.serve.frontend import FrontEnd, FrontEndConfig, ServiceRequest

    mix = load_mix("rh-open")
    load = np.arange(0.0, 4000.0)
    st = RequestStream(mix, load, np.arange(4000.5, 9000.5),
                       np.arange(5000), np.random.default_rng(3))
    fe = FrontEnd(ReferenceIndex(load, np.arange(4000)), FrontEndConfig())
    drv = harness.Driver(fe, st, mix, time.perf_counter, ServiceRequest)
    rate = 1000.0
    t0 = time.perf_counter() + 0.01
    arrivals = t0 + arrival_times(rate, 1000, np.random.default_rng(4))
    step, stall = fe.step, []

    def stalling_step(drain=False):
        if not stall and time.perf_counter() >= t0 + 0.1:
            s0 = time.perf_counter()
            time.sleep(0.05)
            stall.append((s0, time.perf_counter()))
        return step(drain)

    fe.step = stalling_step
    served = drv.open(arrivals, until=t0 + 0.4)
    (s0, s1), = stall
    assert served.sent == int(np.searchsorted(arrivals, t0 + 0.4))
    assert served.latency_s.shape[0] == served.sent
    # every request due in [s0, s1 - 25 ms] waited 25 ms or more
    due = int(((arrivals >= s0) & (arrivals <= s1 - 0.025)).sum())
    assert due >= 10
    assert int((served.latency_s >= 0.025).sum()) >= due
    assert served.latency_s.max() >= 0.045
    assert served.backlog >= 0


RH_OPEN = {"name": "lgn-rh-open", "config": "lgn-4m", "traffic": "rh-open",
           "chips": 1, "why": "the read-heavy open mix with a fold in flight"}


def _rh_open_root(tmp_path, n_keys, **mix_edit):
    """The cell of ``rh-open.json`` on ``lgn-4m``, cut to ``n_keys``: two
    pre-fill batches of 4,097 merge twice into the run, which then
    outgrows a quarter of the keys, so a fold is in flight when the
    window starts."""
    root = make_root(tmp_path, n_keys=n_keys,
                     edit=lambda b: b["workloads"].append(dict(RH_OPEN)))
    path = os.path.join(root, "perfbench", "traffic", "rh-open.json")
    with open(path) as f:
        mix = json.load(f)
    mix.update(prefill_inserts=2 * 4097, warmup_requests=16,
               warmup_clients=16, **mix_edit)
    with open(path, "w") as f:
        json.dump(mix, f)
    return root


def _run_rh_open(root, system=None, seed=2 ** 31 + 4321):
    out = io.StringIO()
    rc = harness.run_cell("lgn-rh-open", seed, 0.5, False, root=root,
                          require_chip=False, system=system, out=out)
    assert rc == 0
    return json.loads(out.getvalue().strip().splitlines()[-1])


def test_rh_open_runs_correct_with_a_fold_in_flight(tmp_path, monkeypatch,
                                                    capsys):
    cpu_program(monkeypatch)
    res = _run_rh_open(_rh_open_root(tmp_path, 1 << 15))
    err = capsys.readouterr().err
    assert res["correct"] is True, res
    assert res["failed"] == 0 and res["attempted"] > 0
    assert set(res["metrics"]) == {"ops_per_s", "p99_ms", "setup_s"}
    assert "prefill: 8194 inserts in 18 batches (sizes 4097, then [3961, 16, 15," in err
    start = next(ln for ln in err.splitlines()
                 if ln.startswith("write path at the window's start"))
    assert '"fold_active": true' in start.split(", at its end")[0]
    assert "open loop: offered 20.0 /s" in err


def test_rh_open_control_is_refused(tmp_path, monkeypatch):
    cpu_program(monkeypatch)
    from perfbench.control import control_system

    # at 2^20 lognormal keys some share float32 values: the control's
    # reads of them answer wrong
    res = _run_rh_open(_rh_open_root(tmp_path, 1 << 20),
                       system=control_system)
    assert res["correct"] is False
    assert res["checks"]["wrong_answers"]["value"] > 0


@pytest.mark.parametrize("fault", ["answer_altered", "half_batch_left_out",
                                   "state_unchanged"])
def test_rh_open_fault_makes_the_run_incorrect(tmp_path, monkeypatch, fault):
    """The open cell's faults: a lost insert shows in the reads of
    inserted keys, which no read of a loaded key would see."""
    cpu_program(monkeypatch)

    def system(cell, k, p):
        return Faulty(harness.build_nfl(cell, k, p), fault)

    res = _run_rh_open(_rh_open_root(tmp_path, 1 << 15), system=system)
    assert res["correct"] is False
    assert res["checks"]["wrong_answers"]["value"] > 0


def test_inserts_dropped_while_a_fold_runs_make_the_run_incorrect(
        tmp_path, monkeypatch, capsys):
    """An index that drops every insert made while a fold is in flight:
    as in the full cell, those are a small share of the inserts (here the
    warm-up's and the window's against 8,194 pre-filled), and the reads
    of recent inserts find them missing."""
    cpu_program(monkeypatch)

    def system(cell, k, p):
        return Faulty(harness.build_nfl(cell, k, p), "fold_inserts_dropped")

    res = _run_rh_open(_rh_open_root(tmp_path, 1 << 15, rate_per_s=400))
    assert res["correct"] is True, res
    res = _run_rh_open(_rh_open_root(tmp_path / "faulty", 1 << 15,
                                     rate_per_s=400), system=system)
    err = capsys.readouterr().err
    assert "a fold was in flight at the start" in err
    assert res["correct"] is False
    assert res["checks"]["wrong_answers"]["value"] > 0


def test_trace_waits_for_an_awaited_fold_to_start():
    """The traced stretch of a run that awaits a fold opens after the
    insert call that starts it (or ``length`` past ``t_start`` at the
    latest), so no seed traces the start and another its ticks."""
    from types import SimpleNamespace as NS

    class Profiler:
        def __init__(self):
            self.started = []

        def ProfileOptions(self):
            return NS()

        def start_trace(self, path, profiler_options):
            self.started.append(now[0])

        def stop_trace(self):
            pass

    now = [0.0]
    for watch, fold_at, want in ((False, None, 1.0), (True, 2.5, 2.5),
                                 (True, None, 4.0)):
        prof = Profiler()
        spanned = NS(watch_fold=watch, tracing=False)
        fe = NS(counters={"batches": 0})
        tw = harness._TraceWindow(NS(profiler=prof), "", spanned, fe, 1.0,
                                  3.0, lambda: now[0])
        for t in np.arange(0.0, 6.0, 0.5):
            now[0] = float(t)
            if fold_at is not None and t >= fold_at:
                spanned.watch_fold = False
            tw.poll()
        assert prof.started == [want]
