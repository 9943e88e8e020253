"""The traffic generator: the closed mixes keep offering the stream they
always did, an open mix is read and scheduled from its file, and reads
of inserted keys only name keys inserted before them."""

import hashlib
import json
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from perfbench.traffic.gen import (  # noqa: E402
    RequestStream, arrival_times, load_mix)
from perfbench.traffic.keys import make_keys, split_half  # noqa: E402

# sha256 over repr() of the first 3 * CHUNK requests, recorded before the
# generator learned open loops: the existing cells are offered the same
# stream as before
GOLDEN = {
    ("ro-closed", "lognormal", 1):
        "2091017fe70f75c750b6e0cec869d87d0048475218b1fb516a87de47fb163831",
    ("ro-closed", "lognormal", 2 ** 31 + 7):
        "3a1aeabf2546035964d862c86f7b031f91413c3a59debeab5856156df75442ef",
    ("e-closed", "ycsb", 1):
        "24796c17d3e00b9ff1f2528844fa34ae2bb81ad76aaa16f1f000376313ee0a50",
    ("e-closed", "ycsb", 2 ** 31 + 7):
        "770cb0aa924389bc46d86fe3b32e0146464ee77014722d6bfedc1feef2d6b168",
}


def _stream(traffic, dataset, seed, n_keys=1 << 15, mix=None):
    rng = np.random.default_rng(20260)
    keys = make_keys(dataset, n_keys, rng)
    lk, lp, ik, ip = split_half(keys, rng)
    return RequestStream(mix or load_mix(traffic), lk, ik, ip,
                         np.random.default_rng(seed))


@pytest.mark.parametrize("traffic,dataset,seed", sorted(GOLDEN))
def test_closed_mixes_offer_the_recorded_stream(traffic, dataset, seed):
    st = _stream(traffic, dataset, seed)
    h = hashlib.sha256()
    for _ in range(3 * RequestStream.CHUNK):
        h.update(repr(st.next()).encode())
    assert h.hexdigest() == GOLDEN[(traffic, dataset, seed)]


def _write_mix(tmp_path, name, mix):
    with open(tmp_path / f"{name}.json", "w") as f:
        json.dump(mix, f)


def test_load_mix_takes_both_loops(tmp_path):
    assert load_mix("ro-closed")["loop"] == "closed"
    assert load_mix("rh-open")["loop"] == "open"
    base = {"mix": {"point": 1.0}, "point": {"zipf_s": 0.99},
            "deadline_s": 3600.0, "warmup_requests": 10}
    _write_mix(tmp_path, "no-rate", dict(base, loop="open"))
    with pytest.raises(ValueError, match="rate_per_s"):
        load_mix("no-rate", str(tmp_path))
    _write_mix(tmp_path, "bad-rate", dict(base, loop="open", rate_per_s=0))
    with pytest.raises(ValueError, match="rate_per_s"):
        load_mix("bad-rate", str(tmp_path))
    _write_mix(tmp_path, "half-open", dict(base, loop="half-open"))
    with pytest.raises(ValueError, match="loop"):
        load_mix("half-open", str(tmp_path))


def test_arrival_schedule_is_seeded_and_keeps_its_rate():
    n, rate = 10_000, 250.0
    a = arrival_times(rate, n, np.random.default_rng(2 ** 31 + 99))
    b = arrival_times(rate, n, np.random.default_rng(2 ** 31 + 99))
    c = arrival_times(rate, n, np.random.default_rng(5))
    assert np.array_equal(a, b)
    assert not np.array_equal(a, c)
    assert np.all(np.diff(a) > 0) and a[0] > 0
    assert abs(n / a[-1] / rate - 1.0) < 0.03
    assert abs(n / c[-1] / rate - 1.0) < 0.03
    gaps = np.diff(a)
    # exponential gaps: the standard deviation is the mean
    assert abs(gaps.std() / gaps.mean() - 1.0) < 0.05


def test_reads_of_inserted_keys_name_earlier_inserts():
    mix = load_mix("rh-open")
    st = _stream("rh-open", "lognormal", 2 ** 31 + 3, n_keys=1 << 16,
                 mix=mix)
    pre_k, _ = st.take_inserts(1000)
    inserted = set(pre_k.tolist())
    loaded = set(st.load_keys.tolist())
    n_point = n_from_inserts = 0
    for _ in range(2 * RequestStream.CHUNK):
        op, key, _hi, _pay = st.next()
        if op == "insert":
            assert key not in inserted
            inserted.add(key)
        elif key in loaded:
            n_point += 1
        else:
            assert key in inserted, "a read of a key not yet inserted"
            n_point += 1
            n_from_inserts += 1
    share = mix["point"]["inserted_share"]
    assert abs(n_from_inserts / n_point - share) < 0.01


@pytest.mark.parametrize("seed", [5, 2 ** 31 + 11])
def test_window_inserts_are_read_back_at_the_cells_size(seed):
    """At the cell's sizes, 1,400,353 inserts pre-filled and 820 more in
    the warm-up, the window's requests (20/s for 20 s) read back keys
    inserted in the window itself: 26 to 39 reads of recent inserts do on
    six seeds, where reads spread over every insert would reach one about
    once in a thousand runs."""
    mix = load_mix("rh-open")
    n_load = 1 << 16
    n_ins = mix["prefill_inserts"] + 3 * RequestStream.CHUNK
    st = RequestStream(mix, np.arange(float(n_load)),
                       n_load + 0.5 + np.arange(float(n_ins)),
                       np.arange(n_ins), np.random.default_rng(seed))
    st.take_inserts(mix["prefill_inserts"] + 1)   # pre-fill, warm-up's one
    for _ in range(mix["warmup_requests"]):
        st.next()
    window = set()
    n_read_back = 0
    for _ in range(round(mix["rate_per_s"] * 20)):
        op, key, _hi, _pay = st.next()
        if op == "insert":
            window.add(key)
        elif key in window:
            n_read_back += 1
    assert len(window) >= 60
    assert n_read_back >= 10
