"""The one traffic generator: every mix is a data file beside this one
(``<traffic>.json``), and this module turns it and ``--seed`` into
requests.

A mix file holds:

* ``loop``: ``"closed"``: ``clients`` requests outstanding, each
  completion submits the next; or ``"open"``: requests arrive at
  ``rate_per_s`` on a seeded schedule (``arrival_times``), whether or
  not earlier ones were answered;
* ``mix``: op -> share, over ``point``, ``range`` and ``insert``;
* ``point``: ``{"zipf_s", "inserted_share", "recent_share",
  "recent_inserts"}`` -- lookups of loaded keys, zipfian and scattered
  over the key space (paper §4.1.1); with ``inserted_share`` (default 0)
  that share of the lookups instead names a key inserted earlier in the
  stream: ``recent_share`` of those (default 0) one of the last
  ``recent_inserts`` inserts before it, so that writes made in the
  window are read back, and the rest one of every insert before it
  (set-up writes included), uniformly;
* ``range``: ``{"zipf_s", "len_min", "len_max"}`` -- a scan starts at a
  zipfian-chosen loaded key and covers the next ``L`` loaded keys in key
  order, ``L`` uniform in ``[len_min, len_max]`` (YCSB workload E), given
  as ``[lo, hi)``;
* ``insert``: keys of the unloaded half, in a seeded order, each once;
* ``deadline_s``: the deadline every request carries;
* ``warmup_requests``: requests of the same mix sent before the window
  (an open mix sends them in a closed loop of ``warmup_clients``);
* ``prefill_inserts`` (optional): keys of the unloaded half inserted in
  set-up before the warm-up, in batches of ``prefill_batch`` and then
  one batch of each size from ``prefill_warm_sizes`` down to 1
  (``harness.prefill_sizes``).

Every seed offers the same work in another order: each block of
``CHUNK`` requests holds each op's share exactly and its range lengths
cover their interval evenly, shuffled by the seed; which keys are read
is drawn from the seed.  The same seed gives the same request sequence,
and a closed loop takes as much of it as it can serve.  An open loop's
arrival gaps are exponential, and every block of them is the same set of
gaps in another order; the harness makes a block last the window, so
every seed offers the same number of requests in it.
"""

from __future__ import annotations

import json
import os

import numpy as np

from perfbench.traffic.keys import zipf_cdf, zipf_indices

__all__ = ["OPS", "LOOPS", "arrival_times", "load_mix", "RequestStream",
           "stratified_ops"]

OPS = ("point", "range", "insert")
LOOPS = ("closed", "open")
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
    if mix["loop"] not in LOOPS:
        raise ValueError(f"mix {name}: loop {mix['loop']!r} is not one of "
                         f"{LOOPS}")
    if mix["loop"] == "open":
        rate = mix.get("rate_per_s")
        if isinstance(rate, bool) or not isinstance(rate, (int, float)) \
                or not rate > 0:
            raise ValueError(f"mix {name}: an open loop needs a positive "
                             f"number rate_per_s (have {rate!r})")
    return mix


def arrival_times(rate: float, n: int, rng: np.random.Generator,
                  block: int = 4096) -> np.ndarray:
    """The first ``n`` arrival times (seconds from 0) of an open loop at
    ``rate`` per second.  Gaps are exponential: each block of ``block``
    gaps holds the exponential's ``block`` mid-quantiles, scaled so the
    block lasts exactly ``block / rate``, in an order drawn from
    ``rng``."""
    q = -np.log1p(-(np.arange(block) + 0.5) / block)
    q /= q.mean() * float(rate)
    n_blocks = -(-int(n) // block)
    gaps = np.concatenate([rng.permutation(q) for _ in range(n_blocks)])
    return np.cumsum(gaps[:n])


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
        # inserts in the stream before each slot of this chunk
        ins = (which == self.ops.index("insert") if "insert" in self.ops
               else np.zeros(m, bool))
        ins_before = self.ins_pos + np.cumsum(ins) - ins
        for j, op in enumerate(self.ops):
            sel = np.flatnonzero(which == j)
            k = sel.shape[0]
            if not k:
                continue
            if op == "point":
                keys[sel] = lk[zipf_indices(rng, self.cdf_point, k,
                                            self.perm_point)]
                if self.mix["point"].get("inserted_share", 0) > 0:
                    self._read_inserted(keys, sel, ins_before[sel])
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

    def _read_inserted(self, keys, sel, before) -> None:
        """Point the mix's ``inserted_share`` of the reads at ``sel`` at
        keys inserted before them (each share exact per chunk):
        ``before[i]`` inserts came first in the stream, and one of them
        is drawn uniformly, for ``recent_share`` of these reads from the
        last ``recent_inserts`` alone."""
        rng, p = self.rng, self.mix["point"]
        share = float(p["inserted_share"])
        recent = share * float(p.get("recent_share", 0))
        kind = rng.permutation(stratified_ops(
            [1 - share, share - recent, recent], sel.shape[0]))
        pick = kind > 0
        avail = before[pick]
        lo = np.where(kind[pick] == 2,
                      np.maximum(avail - int(p.get("recent_inserts", 0)), 0),
                      0)
        idx = lo + np.floor(rng.random(avail.shape[0])
                            * (avail - lo)).astype(np.int64)
        ok = avail > 0
        keys[sel[pick][ok]] = self.insert_keys[idx[ok]]

    def next(self):
        if self._i >= len(self._buf):
            self._chunk()
        out = self._buf[self._i]
        self._i += 1
        return out
