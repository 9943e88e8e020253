"""Helpers of the benchmark's own tests (``perfbench/tests``): a copy
of the benchmark's data files at a size the CPU runs in seconds, and the
program set up on the CPU as the chip runs it."""

import json
import os
import shutil

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def make_root(dst, n_keys=2048, extra_cells=(), edit=None):
    """A checkout-shaped directory under ``dst``: ``BENCHMARK.json`` and
    the benchmark's configs, mixes, readers and peaks, every
    configuration cut to ``n_keys`` keys.  ``extra_cells`` are
    ``(workload entry, {relative path: file text})`` added as new
    files only; ``edit(bench)`` may change the copied BENCHMARK.json."""
    dst = str(dst)
    os.makedirs(os.path.join(dst, "perfbench"), exist_ok=True)
    for d in ("configs", "traffic", "metrics"):
        shutil.copytree(os.path.join(ROOT, "perfbench", d),
                        os.path.join(dst, "perfbench", d),
                        dirs_exist_ok=True,
                        ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "perfbench", "peaks.json"),
                os.path.join(dst, "perfbench", "peaks.json"))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for cell, files in extra_cells:
        bench["workloads"].append(cell)
        for rel, text in files.items():
            path = os.path.join(dst, rel)
            os.makedirs(os.path.dirname(path), exist_ok=True)
            with open(path, "w") as f:
                f.write(text)
    if edit is not None:
        edit(bench)
    with open(os.path.join(dst, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    cdir = os.path.join(dst, "perfbench", "configs")
    for name in os.listdir(cdir):
        path = os.path.join(cdir, name)
        with open(path) as f:
            cfg = json.load(f)
        cfg["n_keys"] = n_keys
        with open(path, "w") as f:
            json.dump(cfg, f)
    return dst


def cpu_program(monkeypatch):
    """The program on the CPU as the chip runs it: every point and range
    on the XLA routes, and no persistent compile cache."""
    from repro.kernels import backend, ops

    monkeypatch.setattr(ops, "traversal_route", lambda interpret: "xla")
    monkeypatch.setattr(backend, "enable_compile_cache", lambda: "")
