"""The byte functions of the point and range routes, on a hand-built
structure whose bytes are counted by hand."""

import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from perfbench import roofline as rf  # noqa: E402


def structure():
    """Root model node 0 (slope 1, 4 slots): slot 0 DATA, slot 1 BUCKET
    (3 live slots), slot 2 CHILD -> dense node 1 (8 entries), slot 3
    EMPTY."""
    a = {
        "node_kind": np.array([rf.KIND_MODEL, rf.KIND_DENSE], np.uint8),
        "node_slope": np.array([1.0, 0.0], np.float32),
        "node_intercept": np.array([0.0, 0.0], np.float32),
        "node_offset": np.array([0, 4], np.int32),
        "node_size": np.array([4, 8], np.int32),
        "etype": np.array([rf.DATA, rf.BUCKET, rf.CHILD, rf.EMPTY]
                          + [rf.DATA] * 8, np.uint8),
        "ekey": np.array([0, 1, 2, 3] + [2.0 + i / 8 for i in range(8)],
                         np.float32),
        "echild": np.array([0, 0, 1, 0] + [0] * 8, np.int32),
        "blen": np.array([3], np.int32),
    }
    return a


def test_levels_follow_the_structure():
    levels = list(rf.point_levels(structure(), np.array([0.0, 2.3])))
    assert [lv[0].tolist() for lv in levels] == [[0, 0], [1]]
    # the dense search lands on the first entry >= 2.3 (offset 4 + 3)
    assert levels[1][1].tolist() == [7]


def test_point_bytes_by_hand():
    a = structure()
    q = rf.QUERY_BYTES                               # 16
    one_level = rf.NODE_BYTES + rf.ENTRY_BYTES       # 17 + 21 = 38
    # key 0.0: root -> DATA
    assert rf.point_bytes(a, np.array([0.0])) == q + one_level
    # key 1.0: root -> BUCKET of 3 live slots: 4 + 3 * 12
    assert rf.point_bytes(a, np.array([1.0])) == q + one_level + 4 + 36
    # key 2.3: root -> CHILD -> dense node of 8: 4 probes of 4 bytes
    assert rf.point_bytes(a, np.array([2.3])) == q + 2 * one_level + 16
    # key 3.0: root -> EMPTY
    assert rf.point_bytes(a, np.array([3.0])) == q + one_level
    # a non-empty tier adds its probes and one entry per query
    assert (rf.point_bytes(a, np.array([0.0, 3.0]), tier_lens=(0, 7))
            == 2 * (q + one_level) + 2 * (4 * 3 + 16))


@pytest.mark.parametrize("n,p", [(0, 0), (1, 1), (2, 2), (3, 2), (7, 3),
                                 (8, 4), (1 << 20, 21)])
def test_probes(n, p):
    assert rf.probes(n) == p


def test_range_bytes_by_hand():
    # two ranges over a pool of 1023 keys (10 probes) and a run tier of
    # 3 (2 probes); 5 and 200 candidates, the second capped at 128
    got = rf.range_bytes(1023, (0, 3), np.array([5, 200]), cap=128)
    per_range = 8 + 4 * 10 + 4 * 2
    assert got == 2 * per_range + (16 + 4) * (5 + 128)
