"""Backend auto-detection shared by every Pallas kernel wrapper.

Pallas kernels run compiled (Mosaic) only on real TPU backends; everywhere
else — the CPU validation/CI platform — they execute in interpret mode.
Kernel wrappers take ``interpret=None`` by default and resolve it here, so
the *same call site* runs compiled on hardware and interpreted in CI
(DESIGN.md §2 "hardware adaptation").
"""

from __future__ import annotations

import os
from pathlib import Path
from typing import Optional

import jax

__all__ = ["should_interpret", "resolve_interpret", "pow2_batch",
           "enable_compile_cache"]

# the checkout root: src/repro/kernels/backend.py -> three levels up
_REPO_ROOT = Path(__file__).resolve().parents[3]


def pow2_batch(n: int, floor: int = 64) -> int:
    """Serve-path request-batch bucket: the power-of-two pad size every
    dispatch route uses for ragged query batches (DESIGN.md §11 — one
    traced kernel shape per bucket instead of one per distinct batch
    size).  Shared so the routes' trace buckets can never silently
    diverge."""
    return max(1 << max(int(n) - 1, 0).bit_length(), floor)


def should_interpret() -> bool:
    """True iff there is no TPU backend to compile for."""
    return jax.default_backend() != "tpu"


def resolve_interpret(interpret: Optional[bool]) -> bool:
    """``None`` -> auto-detect; explicit booleans pass through."""
    if interpret is None:
        return should_interpret()
    return bool(interpret)


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache for an entry point
    (``chip_smoke.py``, ``benchmarks/run.py``, ``launch/serve.py``,
    ``examples/*``); the tier-1 tests never call it.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    no other directory is set here.  Otherwise the cache lives at the
    fixed ``<checkout>/.jax_cache`` — the path is part of the cache key,
    so it never depends on a temporary name, a pid or the time.  Every
    program is cached, however fast it compiled.  Returns the directory
    in use."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(_REPO_ROOT / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
