#!/usr/bin/env python3
"""The control: the plain reference put in the index's place, holding
keys one precision below what the configuration states (float32 for
float64 identities), driven through the same front end, window and
comparison as a run of the cell.  Its answers must come out not
correct; the smallest wrong count over the seeds is the comparison's
upper reading.

  python3 perfbench/control.py --workload <cell> --seeds 1,2,3 --seconds <s>

One process, one run per seed; each run prints its result line.  Needs
the chip the cell asks for, as a run does.
"""

import argparse
import os
import sys
import time

T_START = time.perf_counter()
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "src"))
os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")

import numpy as np  # noqa: E402

from perfbench.harness import run_cell  # noqa: E402
from perfbench.reference import ReferenceIndex  # noqa: E402


def control_system(cell, load_keys, load_payloads):
    return ReferenceIndex(load_keys, load_payloads, key_dtype=np.float32)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    a = ap.parse_args(argv)
    rc = 0
    for seed in (int(s) for s in a.seeds.split(",")):
        rc |= run_cell(a.workload, seed, a.seconds, False,
                       t_start=time.perf_counter(), system=control_system)
    return rc


if __name__ == "__main__":
    sys.exit(main())
