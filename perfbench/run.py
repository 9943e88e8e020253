#!/usr/bin/env python3
"""Run one benchmark cell once, from the root of a checkout:

  python3 perfbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cells, their configurations, mixes and metrics are named in
``BENCHMARK.json``.  Progress and the checks go to standard error; the
last line of standard output is the result, a JSON object.  A host with
no TPU, or with fewer chips than the cell asks for, fails with no result
line.  JAX's persistent compile cache is kept in ``<checkout>/.jax_cache``,
so only the first run in a checkout compiles.
"""

import os
import sys
import time

T_START = time.perf_counter()
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "src"))
# the persistent compile cache lives at a fixed path inside the
# checkout; the program's enable_compile_cache() takes it from here
os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")

from perfbench.harness import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main(t_start=T_START))
