"""The one traffic generator: every mix is a data file beside this one
(``<traffic>.json``), and this module turns it and ``--seed`` into
requests.

A mix file holds:

* ``loop``: ``"closed"``, the one loop the harness drives: ``clients``
  requests outstanding, each completion submits the next;
* ``mix``: op -> share, over ``point``, ``range`` and ``insert``;
* ``point``: ``{"zipf_s"}`` -- lookups of loaded keys, zipfian and
  scattered over the key space (paper §4.1.1);
* ``range``: ``{"zipf_s", "len_min", "len_max"}`` -- a scan starts at a
  zipfian-chosen loaded key and covers the next ``L`` loaded keys in key
  order, ``L`` uniform in ``[len_min, len_max]`` (YCSB workload E), given
  as ``[lo, hi)``;
* ``insert``: keys of the unloaded half, in a seeded order, each once;
* ``deadline_s``: the deadline every request carries;
* ``warmup_requests``: requests of the same mix sent before the window.

Every seed offers the same work in another order: each block of
``CHUNK`` requests holds each op's share exactly and its range lengths
cover their interval evenly, shuffled by the seed; which keys are read
is drawn from the seed.  The same seed gives the same request sequence,
and a closed loop takes as much of it as it can serve.
"""

from __future__ import annotations

import json
import os

import numpy as np

from perfbench.traffic.keys import zipf_cdf, zipf_indices

__all__ = ["OPS", "load_mix", "RequestStream", "stratified_ops"]

OPS = ("point", "range", "insert")
HERE = os.path.dirname(os.path.abspath(__file__))


def load_mix(name: str, root: str | None = None) -> dict:
    """The mix file ``<name>.json`` of the traffic directory."""
    path = os.path.join(root or HERE, f"{name}.json")
    with open(path) as f:
        mix = json.load(f)
    shares = mix["mix"]
    bad = set(shares) - set(OPS)
    if bad:
        raise ValueError(f"mix {name}: unknown ops {sorted(bad)}")
    if abs(sum(shares.values()) - 1.0) > 1e-9:
        raise ValueError(f"mix {name}: shares sum to {sum(shares.values())}")
    if mix["loop"] != "closed":
        raise ValueError(f"mix {name}: the harness drives closed loops only")
    return mix


def stratified_ops(shares: list, m: int) -> np.ndarray:
    """Op indices of a block of ``m`` requests holding each op's share
    exactly (largest remainders get the odd ones), in index order."""
    want = np.asarray(shares, np.float64) * m
    n = np.floor(want).astype(np.int64)
    rest = m - int(n.sum())
    n[np.argsort(-(want - n), kind="stable")[:rest]] += 1
    return np.repeat(np.arange(len(shares)), n)


class RequestStream:
    """An endless seeded stream of request specs, made in chunks.

    ``next()`` returns ``(op, key, hi, payload)``.  ``load_keys`` are the
    sorted bulk-loaded keys; ``insert_keys`` / ``insert_payloads`` the
    unloaded half in the order inserts take them."""

    CHUNK = 4096

    def __init__(self, mix: dict, load_keys: np.ndarray,
                 insert_keys: np.ndarray, insert_payloads: np.ndarray,
                 rng: np.random.Generator):
        self.mix = mix
        self.rng = rng
        self.load_keys = load_keys
        self.insert_keys = insert_keys
        self.insert_payloads = insert_payloads
        self.ins_pos = 0
        self.ops = [op for op in OPS if mix["mix"].get(op, 0) > 0]
        n = load_keys.shape[0]
        rcfg = mix.get("range")
        self.span_max = int(rcfg["len_max"]) if rcfg else 0
        # one scatter of the zipf ranks per stream: hot keys stay hot
        self.perm_point = rng.permutation(n)
        self.perm_range = rng.permutation(max(n - self.span_max, 1))
        self.cdf_point = (zipf_cdf(n, float(mix["point"]["zipf_s"]))
                          if "point" in mix else None)
        self.cdf_range = (zipf_cdf(n - self.span_max, float(rcfg["zipf_s"]))
                          if rcfg else None)
        self._buf: list = []
        self._i = 0

    def take_inserts(self, n: int):
        """The next ``n`` insert keys and payloads (for set-up writes)."""
        a = self.ins_pos
        if a + n > self.insert_keys.shape[0]:
            raise ValueError("the unloaded half is used up")
        self.ins_pos = a + n
        return self.insert_keys[a:a + n], self.insert_payloads[a:a + n]

    def _chunk(self) -> None:
        rng, m = self.rng, self.CHUNK
        which = rng.permutation(stratified_ops(
            [self.mix["mix"][op] for op in self.ops], m))
        keys = np.zeros(m, np.float64)
        his = np.zeros(m, np.float64)
        pays = np.zeros(m, np.int64)
        lk = self.load_keys
        for j, op in enumerate(self.ops):
            sel = np.flatnonzero(which == j)
            k = sel.shape[0]
            if not k:
                continue
            if op == "point":
                keys[sel] = lk[zipf_indices(rng, self.cdf_point, k,
                                            self.perm_point)]
            elif op == "range":
                r = self.mix["range"]
                start = zipf_indices(rng, self.cdf_range, k, self.perm_range)
                lo, hi = int(r["len_min"]), int(r["len_max"])
                span = rng.permutation(lo + np.arange(k) * (hi - lo + 1) // k)
                keys[sel] = lk[start]
                his[sel] = lk[start + span]
            else:  # insert
                ik, ip = self.take_inserts(k)
                keys[sel] = ik
                pays[sel] = ip
        ops = [self.ops[j] for j in which.tolist()]
        self._buf = list(zip(ops, keys.tolist(), his.tolist(), pays.tolist()))
        self._i = 0

    def next(self):
        if self._i >= len(self._buf):
            self._chunk()
        out = self._buf[self._i]
        self._i += 1
        return out
