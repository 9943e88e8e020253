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
    assert c.mix["loop"] == "closed"
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
        if self._fault != "state_unchanged":
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
