"""The plain reference and the dispatch-order comparison: it passes the
right answers and flags a wrong point, a wrong range and a lost insert."""

import os
import sys
from types import SimpleNamespace

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from perfbench.reference import (  # noqa: E402
    Reference, ReferenceIndex, range_ok, record)


def req(op, key, hi=0.0, payload=0, result=None, state="completed"):
    return SimpleNamespace(op=op, key=float(key), hi=float(hi),
                           payload=payload, result=result, state=state)


@pytest.fixture
def loaded():
    keys = np.array([10.0, 20.0, 30.0, 40.0, 50.0])
    return keys, np.arange(5) * 100


def test_right_answers_pass(loaded):
    ref = Reference(*loaded)
    log = [
        record("point", [req("point", 20, result=100),
                            req("point", 25, result=-1)]),
        record("insert", [req("insert", 25, payload=7, result=True)]),
        record("point", [req("point", 25, result=7)]),
        record("range", [req("range", 20, hi=40, result=([100, 7, 200],
                                                             3))]),
        record("delete", [req("delete", 30, result=True),
                             req("delete", 31, result=False)]),
        record("range", [req("range", 20, hi=60, result=([100, 7, 300,
                                                             400], 4))]),
    ]
    v = ref.replay(log)
    assert v.n_wrong == 0 and v.unanswered == 0
    assert sum(v.checked.values()) == 8


def test_wrong_point_flagged(loaded):
    v = Reference(*loaded).replay(
        [record("point", [req("point", 20, result=101)])])
    assert v.wrong["point"] == 1


def test_wrong_range_flagged(loaded):
    # one payload left out of an untruncated range
    v = Reference(*loaded).replay(
        [record("range", [req("range", 10, hi=40, result=([0, 100], 3))])])
    assert v.wrong["range"] == 1


def test_lost_insert_flagged(loaded):
    # acknowledged, but the next read does not see it
    v = Reference(*loaded).replay([
        record("insert", [req("insert", 60, payload=9, result=True)]),
        record("point", [req("point", 60, result=-1)]),
    ])
    assert v.wrong["point"] == 1


def test_dispatch_order_decides(loaded):
    # a read dispatched before the write must not see it
    v = Reference(*loaded).replay([
        record("point", [req("point", 60, result=-1)]),
        record("insert", [req("insert", 60, payload=9, result=True)]),
    ])
    assert v.n_wrong == 0


def test_float32_positioning_ties():
    # 2^60 and 2^60 + 2^10 share one float32 value: a range starting at
    # either covers both, one ending at either covers neither
    a, b, c = 2.0 ** 60, 2.0 ** 60 + 1024.0, 2.0 ** 61
    ref = Reference(np.array([a, b, c]), np.array([1, 2, 3]))
    assert sorted(ref.ranges(np.array([b]), np.array([c]))[0]) == [1, 2]
    assert ref.ranges(np.array([1.0]), np.array([b]))[0] == []


def test_truncated_range_is_a_subset():
    assert range_ok([1, 2], [1, 2, 3], truncated=True)
    assert not range_ok([1, 9], [1, 2, 3], truncated=True)
    assert not range_ok([1, 2], [1, 2, 3], truncated=False)


def test_unanswered_counted(loaded):
    v = Reference(*loaded).replay(
        [record("point", [req("point", 20, result=None, state="shed")])])
    assert v.unanswered == 1 and v.n_wrong == 0


def test_control_in_float32_is_refused():
    # float64 identities that collide in float32: the control answers
    # the later one's payload for both
    keys = np.array([2.0 ** 40, 2.0 ** 40 + 1.0, 2.0 ** 41])
    ctl = ReferenceIndex(keys, np.array([5, 6, 7]), key_dtype=np.float32)
    got = ctl.lookup_batch(keys)
    v = Reference(keys, np.array([5, 6, 7])).replay(
        [record("point", [req("point", k, result=int(g))
                             for k, g in zip(keys, got)])])
    assert v.wrong["point"] >= 1
