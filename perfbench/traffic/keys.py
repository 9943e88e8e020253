"""Key sets and key choice, kept with the benchmark so that no change to
the program can move them.

Copied from the program's ``data/datasets.py`` (``lognormal``, ``ycsb``,
``_unique_n``) and ``data/workloads.py`` (``_zipf_indices`` and the
paper's §4.1.1 split: bulk-load a random half, insert from the other
half).  The copies take every seed as an argument; the generator seeds
them from ``--seed``.
"""

from __future__ import annotations

import numpy as np

__all__ = ["DATASETS", "make_keys", "split_half", "zipf_cdf", "zipf_indices"]


def _unique_n(raw: np.ndarray, n: int, rng: np.random.Generator) -> np.ndarray:
    keys = np.unique(raw.astype(np.float64))
    while keys.shape[0] < n:
        extra = rng.uniform(keys.min(), keys.max(), size=n)
        keys = np.unique(np.concatenate([keys, extra]))
    idx = rng.choice(keys.shape[0], size=n, replace=False)
    return np.sort(keys[idx])


def lognormal(n: int, rng: np.random.Generator) -> np.ndarray:
    """The NFL paper's LGN stand-in: ``floor(lognormal(0, 2) * 1e9)``."""
    raw = np.floor(rng.lognormal(0.0, 2.0, int(n * 1.4)) * 1e9)
    return _unique_n(raw, n, rng)


def ycsb(n: int, rng: np.random.Generator) -> np.ndarray:
    """YCSB-style user keys: uniform 62-bit integers."""
    raw = rng.integers(0, 1 << 62, size=int(n * 1.2)).astype(np.float64)
    return _unique_n(raw, n, rng)


DATASETS = {"lognormal": lognormal, "ycsb": ycsb}


def make_keys(dataset: str, n: int, rng: np.random.Generator) -> np.ndarray:
    """``n`` unique sorted f64 keys of ``dataset``."""
    return DATASETS[dataset](n, rng)


def split_half(keys: np.ndarray, rng: np.random.Generator):
    """Paper §4.1.1: a random half is bulk-loaded (sorted, payload = its
    index in ``keys``); the other half, in a random order, is what
    inserts draw from.  Returns ``(load_keys, load_payloads,
    insert_keys, insert_payloads)``."""
    n = keys.shape[0]
    perm = rng.permutation(n)
    half = n // 2
    load_idx = np.sort(perm[:half])
    ins_idx = perm[half:]
    return (keys[load_idx], load_idx.astype(np.int64),
            keys[ins_idx], ins_idx.astype(np.int64))


def zipf_cdf(n_items: int, s: float) -> np.ndarray:
    """CDF of a zeta distribution with exponent ``s`` truncated to
    ``n_items`` ranks."""
    w = np.arange(1, n_items + 1, dtype=np.float64) ** (-s)
    return np.cumsum(w / w.sum())


def zipf_indices(rng: np.random.Generator, cdf: np.ndarray, size: int,
                 perm: np.ndarray) -> np.ndarray:
    """Zipfian ranks by inverse CDF (``zipf_cdf``), scattered over the
    items by ``perm`` so the hot items lie anywhere in the key space."""
    idx = np.searchsorted(cdf, rng.uniform(0, 1, size), side="left")
    return perm[np.clip(idx, 0, cdf.shape[0] - 1)]
