"""The plain reference: a dict of live keys and a sorted array of the
bulk-loaded ones, with the index's semantics, independent of the code
under test (it imports nothing of the program).

Semantics (the program's DESIGN.md §10 and §12):

* a key's identity is its full float64 value; a point lookup answers the
  payload of the newest write of that identity, or -1;
* an insert of a present identity overwrites it (last write wins) and is
  acknowledged ``True``; a delete answers whether the key was live;
* a range ``[lo, hi)`` holds the live keys whose positioning key lies in
  ``[p(lo), p(hi))``.  Without a flow the positioning key is the float32
  cast of the key, so keys that share one float32 value sort together
  and fall on the same side of an endpoint.  A range whose candidate
  count passed the program's ``scan_cap`` may be cut short: its answer
  must then be a subset of the range.

``Reference.replay`` applies the dispatched batches in dispatch order,
which is the order in which the index saw them: a read observes exactly
the writes dispatched before it.  It runs after the window, over
``Batch`` records: the run keeps each batch as a few arrays, not as the
requests themselves.

``key_dtype`` is the precision keys are held in.  The configuration
states float64; ``np.float32`` gives the control, the same reference one
precision lower, whose answers the comparison has to refuse.
"""

from __future__ import annotations

import itertools
from collections import Counter
from typing import NamedTuple

import numpy as np

__all__ = ["Batch", "Reference", "ReferenceIndex", "Verdict", "record"]


class Verdict:
    """Counts of the comparison, per op."""

    def __init__(self):
        self.checked = Counter()
        self.wrong = Counter()
        self.truncated = 0
        self.unanswered = 0
        self.examples: list = []

    def bad(self, op: str, detail) -> None:
        self.wrong[op] += 1
        if len(self.examples) < 5:
            self.examples.append((op, detail))

    @property
    def n_wrong(self) -> int:
        return sum(self.wrong.values())


class Reference:
    """Live keys (dict) plus the loaded keys sorted by positioning key."""

    def __init__(self, load_keys: np.ndarray, load_payloads: np.ndarray,
                 key_dtype=np.float64, scan_cap: int = 128):
        self.key_dtype = key_dtype
        self.scan_cap = scan_cap
        k = np.asarray(load_keys, np.float64).astype(key_dtype)
        p = np.asarray(load_payloads, np.int64)
        self.pay: dict = dict(zip(k.tolist(), p.tolist()))
        z = k.astype(np.float32)
        order = np.argsort(z, kind="stable")
        self.base_z = z[order]
        self.base_k = k[order]
        self.base_p = p[order]
        # base keys deleted or overwritten since the load
        self.base_changed: set = set()
        self.base_set = set(self.base_k.tolist())
        # keys written after the load that are not base keys: sorted by
        # z, and those not yet sorted in (the ranges sort them in)
        self.extra_z = np.zeros(0, np.float64)
        self.extra_k = np.zeros(0, np.float64)
        self.extra_new: list = []
        self.extra_set: set = set()

    def _key(self, key) -> float:
        return float(self.key_dtype(key))

    # --------------------------------------------------------- writes
    def insert(self, key, payload) -> bool:
        k = self._key(key)
        if k in self.base_set:
            self.base_changed.add(k)
        elif k not in self.extra_set:
            self.extra_set.add(k)
            self.extra_new.append(k)
        self.pay[k] = int(payload)
        return True

    def delete(self, key) -> bool:
        k = self._key(key)
        if k not in self.pay:
            return False
        del self.pay[k]
        if k in self.base_set:
            self.base_changed.add(k)
        return True

    # ---------------------------------------------------------- reads
    def point(self, key) -> int:
        return self.pay.get(self._key(key), -1)

    def _sort_extra(self) -> None:
        """Sort the keys written since the last range into ``extra_z``
        / ``extra_k`` (by the float32 positioning key)."""
        k = np.asarray(self.extra_new, np.float64)
        self.extra_new = []
        z = k.astype(np.float32).astype(np.float64)
        order = np.argsort(z, kind="stable")
        at = np.searchsorted(self.extra_z, z[order], side="right")
        self.extra_z = np.insert(self.extra_z, at, z[order])
        self.extra_k = np.insert(self.extra_k, at, k[order])

    def ranges(self, lo: np.ndarray, hi: np.ndarray) -> list:
        """Per range, the payloads of the live keys in it."""
        if self.extra_new:
            self._sort_extra()
        zlo = np.asarray(lo, np.float64).astype(self.key_dtype).astype(
            np.float32)
        zhi = np.asarray(hi, np.float64).astype(self.key_dtype).astype(
            np.float32)
        a = np.searchsorted(self.base_z, zlo, side="left")
        b = np.searchsorted(self.base_z, zhi, side="left")
        out = []
        pay = self.pay
        for i in range(zlo.shape[0]):
            if self.base_changed:
                got = [pay[k] for k in self.base_k[a[i]:b[i]].tolist()
                       if k in pay]
            else:
                got = self.base_p[a[i]:b[i]].tolist()
            if self.extra_z.shape[0]:
                x = int(np.searchsorted(self.extra_z, zlo[i], side="left"))
                y = int(np.searchsorted(self.extra_z, zhi[i], side="left"))
                got += [pay[k] for k in self.extra_k[x:y].tolist()
                        if k in pay]
            out.append(got)
        return out

    # --------------------------------------------------------- replay
    def replay(self, log: list, verdict: Verdict | None = None) -> Verdict:
        """Apply the ``Batch`` records of ``log`` in order and compare
        every answer in them."""
        v = verdict or Verdict()
        for b in log:
            if b.ok is not None:
                v.unanswered += int((~b.ok).sum())
            self._apply(b, v)
        return v

    def _apply(self, b: "Batch", v: Verdict) -> None:
        op, keys = b.op, b.keys.tolist()
        ok = b.ok.tolist() if b.ok is not None else [True] * len(keys)
        if op in ("insert", "delete"):
            got = b.got.tolist() if b.got is not None else None
            pays = b.pays.tolist() if op == "insert" else None
            for i, k in enumerate(keys):
                want = (self.insert(k, pays[i]) if op == "insert"
                        else self.delete(k))
                if got is not None and ok[i]:
                    v.checked[op] += 1
                    if bool(got[i]) != want:
                        v.bad(op, (k, got[i], want))
        elif op == "point":
            got = b.got.tolist()
            for i, k in enumerate(keys):
                if not ok[i]:
                    continue
                want = self.point(k)
                v.checked[op] += 1
                if got[i] != want:
                    v.bad(op, (k, got[i], want))
        elif op == "range":
            flat, counts, totals = b.got
            ends = np.cumsum(counts).tolist()
            flat, totals = flat.tolist(), totals.tolist()
            wants = self.ranges(b.keys, b.hi)
            for i, want in enumerate(wants):
                if not ok[i]:
                    continue
                v.checked[op] += 1
                payloads = flat[ends[i] - int(counts[i]):ends[i]]
                cut = totals[i] > self.scan_cap
                if not range_ok(payloads, want, cut):
                    v.bad(op, (keys[i], float(b.hi[i]), sorted(payloads)[:8],
                               sorted(want)[:8]))
                v.truncated += int(cut)
        else:
            raise ValueError(f"unknown op {op!r}")


class Batch(NamedTuple):
    """One batch the index answered, held compactly for the replay.

    ``keys`` (and ``hi`` for ranges) are float64; ``pays`` are the insert
    payloads; ``got`` the answers: int64 payloads (points), bools
    (write acknowledgements), or ``(payloads, counts, totals)`` with the
    ranges' payloads end to end (ranges); ``None`` where nothing is
    compared.  ``ok[i]`` says request ``i`` was answered; ``None`` means
    every one was."""

    op: str
    keys: np.ndarray
    hi: np.ndarray | None = None
    pays: np.ndarray | None = None
    got: object = None
    ok: np.ndarray | None = None


def record(op: str, reqs: list) -> Batch:
    """The ``Batch`` of answered front-end requests (each with ``key``,
    ``hi``, ``payload``, ``state`` and ``result``)."""
    n = len(reqs)
    keys = np.fromiter((r.key for r in reqs), np.float64, n)
    ok = np.fromiter((r.state == "completed" and r.result is not None
                      for r in reqs), bool, n)
    hi = pays = None
    if op == "range":
        hi = np.fromiter((r.hi for r in reqs), np.float64, n)
        res = [r.result if o else ((), 0) for r, o in zip(reqs, ok.tolist())]
        counts = np.fromiter((len(p) for p, _ in res), np.int64, n)
        flat = np.fromiter(itertools.chain.from_iterable(p for p, _ in res),
                           np.int64, int(counts.sum()))
        got = (flat, counts, np.fromiter((t for _, t in res), np.int64, n))
    elif op == "point":
        got = np.fromiter((r.result if r.result is not None else -1
                           for r in reqs), np.int64, n)
    else:
        if op == "insert":
            pays = np.fromiter((r.payload for r in reqs), np.int64, n)
        got = np.fromiter((bool(r.result) for r in reqs), bool, n)
    return Batch(op, keys, hi, pays, got, ok)


def range_ok(got, want, truncated: bool) -> bool:
    """Exact (as multisets) when the range was not cut short; a
    sub-multiset of the range when it was."""
    g, w = Counter(got), Counter(want)
    if truncated:
        return not (g - w)
    return g == w


class ReferenceIndex:
    """The reference, in the index's place: the four batch calls the
    front end makes, answered from a ``Reference``.  With ``key_dtype``
    ``np.float32`` it is the control."""

    use_flow = False

    def __init__(self, load_keys, load_payloads, key_dtype=np.float64,
                 scan_cap: int = 128):
        self.ref = Reference(load_keys, load_payloads, key_dtype, scan_cap)
        self.cap = scan_cap

    def lookup_batch_async(self, keys):
        res = np.array([self.ref.point(k) for k in np.asarray(keys).tolist()],
                       np.int64)
        return lambda: res

    def lookup_batch(self, keys):
        return self.lookup_batch_async(keys)()

    def insert_batch(self, keys, payloads):
        for k, p in zip(np.asarray(keys).tolist(),
                        np.asarray(payloads).tolist()):
            self.ref.insert(k, p)

    def delete_batch(self, keys):
        return np.array([self.ref.delete(k) for k in
                         np.asarray(keys).tolist()], bool)

    def scan_batch(self, lo, hi):
        res = self.ref.ranges(np.asarray(lo), np.asarray(hi))
        n = len(res)
        pv = np.full((n, self.cap), -1, np.int64)
        cnt = np.zeros(n, np.int64)
        tot = np.zeros(n, np.int64)
        for i, r in enumerate(res):
            m = min(len(r), self.cap)
            pv[i, :m] = r[:m]
            cnt[i] = m
            tot[i] = len(r)
        return pv, cnt, tot
