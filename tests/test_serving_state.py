"""§11 zero-repack serving: persistent pools, bucketed tiers, ratchets.

Covers the three serving-state contracts:

* **zero retraces in-bucket** — a stream of insert/lookup batches whose
  tier lengths stay inside one capacity bucket must not grow any
  serving jit cache after the first (warming) cycle;
* **bucketed == exact padding** — the persistent bucketed tier buffers
  and pow2-padded tree pools are bit-equivalent to the legacy
  exact-padded packing on every query;
* **tiled grid == single step** — serving a batch as a multi-step grid
  over query tiles returns bit-identical payloads and positioning keys
  to the single-block dispatch.
"""

import numpy as np
import jax.numpy as jnp

from repro.core.flat_afli import (FlatAFLI, FlatAFLIConfig, _pack_tier,
                                  split_key_bits)
from repro.core.serving_state import DeviceTier, ServingState, pow2_bucket
from repro.kernels import ops


def _mk_index(n=6_000, seed=40, **cfg):
    rng = np.random.default_rng(seed)
    keys = np.unique(rng.uniform(0, 1e9, n))
    idx = FlatAFLI(FlatAFLIConfig(**cfg))
    idx.build(keys, np.arange(len(keys)))
    return idx, keys


# ------------------------------------------------------------ zero retrace
def test_zero_retraces_within_shape_bucket():
    """Regression (§11): insert/lookup batches whose tier lengths stay
    within one capacity bucket must reuse the traced kernels — the jit
    caches behind ``_device_lookup`` may only grow during the first
    (warming) cycle."""
    idx, keys = _mk_index(8_000, delta_cap=100_000)  # no merges/folds
    rng = np.random.default_rng(41)
    fresh = np.unique(rng.uniform(2e9, 3e9, 4_000))
    step = 256
    # warm cycle: first insert (tier pack + kernel variants) + lookups
    idx.insert_batch(fresh[:step], np.arange(step) + 10**6)
    idx.lookup_batch(keys[:step])
    idx.lookup_batch(fresh[:step])
    warmed = ops.serving_cache_size()
    stats0 = ops.fused_lookup_stats()["retrace_count"]
    repacks0 = idx.stats()["serving"]["tier_repacks"]  # build prealloc
    for s in range(step, 2_048, step):
        idx.insert_batch(fresh[s:s + step], np.arange(step) + 10**6 + s)
        res = idx.lookup_batch(fresh[s:s + step])
        assert (res == np.arange(step) + 10**6 + s).all()
        idx.lookup_batch(keys[s:s + step])
    assert ops.serving_cache_size() == warmed, \
        "serving dispatch retraced inside one shape bucket"
    assert ops.fused_lookup_stats()["retrace_count"] == stats0
    # the whole stream ran on the persistent preallocated buffers: no
    # full repacks after the warming cycle, only prefix writes
    assert idx.stats()["serving"]["tier_repacks"] == repacks0


def test_device_tier_prefix_writes_not_repacks():
    """In-bucket refreshes are device prefix writes on the SAME buffers;
    outgrowing the bucket reallocates once."""
    t = DeviceTier(bucketed=True)
    pk = np.sort(np.random.default_rng(0).uniform(0, 1e6, 300)) \
        .astype(np.float32)
    hi, lo = split_key_bits(pk.astype(np.float64))
    t.refresh(pk, hi, lo, np.arange(300, dtype=np.int32), window=4)
    cap0, buf0 = t.capacity, t.pk
    assert cap0 == pow2_bucket(301)
    assert t.repacks == 1
    # shrink and regrow inside the bucket: no reallocation
    t.refresh(pk[:50], hi[:50], lo[:50],
              np.arange(50, dtype=np.int32), window=4)
    t.refresh(pk[:200], hi[:200], lo[:200],
              np.arange(200, dtype=np.int32), window=4)
    assert t.capacity == cap0 and t.repacks == 1
    assert int(t.plen[0]) == 200
    # outgrow: one reallocation to the next bucket
    big = np.sort(np.random.default_rng(1).uniform(0, 1e6, cap0 + 1)) \
        .astype(np.float32)
    bhi, blo = split_key_bits(big.astype(np.float64))
    t.refresh(big, bhi, blo, np.arange(len(big), dtype=np.int32), window=4)
    assert t.capacity == 2 * cap0 and t.repacks == 2
    del buf0


def test_in_bucket_refresh_rewrites_sentinel_row():
    """Regression: shrinking to an exact power-of-two length must still
    rewrite the +inf sentinel at row n — the fixed-round tier binary
    search reads ppk[n] once converged at l=h=n, and a stale finite key
    left there by a previous longer prefix would push the landing (and
    its identity-scan window) one slot high."""
    t = DeviceTier(bucketed=True)
    pk = np.sort(np.random.default_rng(2).uniform(0, 1e6, 200)) \
        .astype(np.float32)
    hi, lo = split_key_bits(pk.astype(np.float64))
    t.refresh(pk, hi, lo, np.arange(200, dtype=np.int32), window=4)
    assert np.isfinite(np.asarray(t.pk)[64])  # stale finite row planted
    t.refresh(pk[:64], hi[:64], lo[:64],
              np.arange(64, dtype=np.int32), window=4)
    assert np.isinf(np.asarray(t.pk)[64])
    assert int(t.plen[0]) == 64


def test_serving_statics_ratchet_upward_only():
    st = ServingState()
    st.max_depth = 8
    st.dense_window = 16

    class _A:
        def to_kernel_args(self, bucketed=False):
            return None

    st.set_tree(_A(), max_depth=3, dense_window=4)   # shallower new tree
    assert st.max_depth == 8 and st.dense_window == 16
    st.set_tree(_A(), max_depth=13, dense_window=33)  # deeper: ratchet up
    assert st.max_depth == 16 and st.dense_window == 64


# --------------------------------------------------- bucketed/exact parity
def test_bucketed_vs_exact_padding_parity():
    """The §11 bucketed serving state must answer every query exactly as
    the legacy exact-padding packing does (tree + both tiers live)."""
    rng = np.random.default_rng(42)
    keys = np.unique(rng.uniform(0, 1e9, 9_000))
    pv = np.arange(len(keys), dtype=np.int64)
    answers = {}
    for bucketed in (True, False):
        idx = FlatAFLI(FlatAFLIConfig(delta_cap=600,
                                      bucketed_serving=bucketed))
        idx.build(keys[::2], pv[::2])
        idx.insert_batch(keys[1::2][:1_000], pv[1::2][:1_000])  # -> merge
        idx.insert_batch(keys[1::2][1_000:1_400],
                         pv[1::2][1_000:1_400])                 # delta
        q = np.concatenate([keys, keys[:500] + 0.125])
        answers[bucketed] = idx.lookup_batch(q)
        assert idx.last_dispatch["tier_path"] == "kernel"
    assert np.array_equal(answers[True], answers[False])


def test_bucketed_tier_pack_matches_exact_pack_tier():
    """DeviceTier's persistent bucketed pool vs the exact ``_pack_tier``
    reference: same probe semantics through the kernel."""
    idx, keys = _mk_index(5_000, seed=43, delta_cap=100_000)
    rng = np.random.default_rng(43)
    fresh = np.unique(rng.uniform(2e9, 3e9, 700))
    idx.insert_batch(fresh, np.arange(len(fresh)) + 5_000_000)
    from repro.kernels.fused_lookup import TierPack, TierPools

    bucketed = idx._tier_pack()
    (d_arrays, d_iters, d_window) = _pack_tier(
        idx._delta_pk, idx._delta_hi, idx._delta_lo, idx._delta_pv)
    (r_arrays, r_iters, r_window) = _pack_tier(
        idx._run_pk, idx._run_hi, idx._run_lo, idx._run_pv)
    exact = TierPack(pools=TierPools(*r_arrays, *d_arrays),
                     run_iters=r_iters, run_window=r_window,
                     delta_iters=d_iters, delta_window=d_window)
    q = np.concatenate([keys[:1_000], fresh, fresh + 1.0])
    hi, lo = split_key_bits(q)
    q32 = q.astype(np.float32)
    kw = dict(max_depth=idx._depth_static(),
              dense_iters=idx.cfg.dense_search_iters,
              bucket_cap=idx.cfg.max_bucket,
              dense_window=idx._dense_window_static())
    out = {}
    for name, pack in (("bucketed", bucketed), ("exact", exact)):
        res, _z, info = ops.fused_lookup(
            idx.arrays, idx._kernel_pools(), jnp.asarray(q32.reshape(-1, 1)),
            jnp.asarray(hi), jnp.asarray(lo), flow=None, tiers=pack, **kw)
        assert info["tier_path"] == "kernel"
        out[name] = res
    assert np.array_equal(out["bucketed"], out["exact"])
    assert (out["bucketed"][1_000:1_000 + len(fresh)] >= 5_000_000).all()


def test_to_kernel_args_bucketed_parity():
    """pow2-bucketed tree pool padding is bit-invisible to the kernel."""
    idx, keys = _mk_index(4_000, seed=44)
    hi, lo = split_key_bits(keys)
    q32 = keys.astype(np.float32)
    kw = dict(max_depth=idx._depth_static(),
              dense_iters=idx.cfg.dense_search_iters,
              bucket_cap=idx.cfg.max_bucket,
              dense_window=idx._dense_window_static())
    out = {}
    for name, pools in (("exact", idx.arrays.to_kernel_args()),
                        ("bucketed",
                         idx.arrays.to_kernel_args(bucketed=True))):
        res, z, info = ops.fused_lookup(
            idx.arrays, pools, jnp.asarray(q32.reshape(-1, 1)),
            jnp.asarray(hi), jnp.asarray(lo), flow=None, **kw)
        assert info["path"] == "fused"
        out[name] = (res, z)
    assert np.array_equal(out["exact"][0], out["bucketed"][0])
    assert np.array_equal(out["exact"][1], out["bucketed"][1])


# ------------------------------------------------------- tiled grid parity
def test_tiled_grid_matches_single_step():
    """A multi-step grid over query tiles must be bit-identical to the
    single-block dispatch (payloads AND positioning keys)."""
    from repro.kernels.fused_lookup import fused_lookup_pallas

    idx, keys = _mk_index(6_000, seed=45)
    q = np.concatenate([keys[:2_000], keys[:48] + 0.5])  # ragged batch
    hi, lo = split_key_bits(q)
    feats = jnp.asarray(q.astype(np.float32).reshape(-1, 1))
    kw = dict(dim=1, shapes=(), use_flow=False,
              max_depth=idx._depth_static(),
              dense_iters=idx.cfg.dense_search_iters,
              bucket_cap=idx.cfg.max_bucket,
              dense_window=idx._dense_window_static())
    pools = idx._kernel_pools()
    ref = None
    for tile in (4_096, 1_024, 512, 256):  # 1, 1, 2, 4, 8 grid steps
        pay, z = fused_lookup_pallas(feats, jnp.asarray(hi),
                                     jnp.asarray(lo),
                                     jnp.zeros((1, 1), jnp.float32),
                                     pools, None, tile=tile, **kw)
        if ref is None:
            ref = (np.asarray(pay), np.asarray(z))
        else:
            assert np.array_equal(np.asarray(pay), ref[0]), tile
            assert np.array_equal(np.asarray(z), ref[1]), tile


def test_select_tile_policy():
    from repro.kernels.fused_lookup import (DEFAULT_TILE, INTERPRET_TILE,
                                            select_tile)

    # pow2-bucketed, capped so large batches become grids
    assert select_tile(100, interpret=True) == 128
    assert select_tile(8_192, interpret=True) == INTERPRET_TILE
    assert select_tile(8_192, interpret=False) == DEFAULT_TILE
    # an explicit tile is capped by the batch bucket, lane-aligned when
    # compiled (the NF runs before the grid, so the flow pins nothing)
    assert select_tile(100, tile=700, interpret=True) == 128
    assert select_tile(100, tile=64, interpret=False) == 128


# ------------------------------------------------------------ preallocation
# --------------------------------------------------- §18 migration swap
def test_migration_swap_zero_retraces_on_untouched_shards():
    """§18 satellite: an online boundary migration of shards [0, 1]
    must leave the untouched shards' serving machinery alone — same
    shard objects across the swap, zero tier repacks, zero ratchet
    releases, and fixed-shape lookups reuse every warmed kernel through
    the whole episode (0 retraces, 0 new cache entries).  Ratchet
    release is scoped to the migrated slots by construction: the
    candidates are fresh ``ServingState``s, so their ratchets start
    released without ever calling ``release_ratchets`` on a live shard."""
    from repro.core.nfl import NFL, NFLConfig

    rng = np.random.default_rng(47)
    keys = np.unique(rng.uniform(0.0, 100.0, 6_000))
    pay = np.arange(keys.shape[0], dtype=np.int64)
    nfl = NFL(NFLConfig(backend="flat", shards=4, force_flow=False,
                        flat_index=FlatAFLIConfig(
                            rebuild_frac=0.1, delta_cap=24,
                            fold_step_keys=48, fold_work_factor=4.0)))
    nfl.bulkload(keys, pay)
    idx = nfl.index
    oracle = dict(zip(keys.tolist(), pay.tolist()))
    # a fixed-shape batch that routes only to the untouched shards 2..3
    hi_keys = keys[keys.astype(np.float32) >= idx.boundaries[1]]
    batch = np.ascontiguousarray(hi_keys[:256])
    exp = np.array([oracle[k] for k in batch.tolist()])
    for _ in range(3):   # warm the serving caches at this shape
        assert (nfl.lookup_batch(batch) == exp).all()
    untouched = [idx.shards[2], idx.shards[3]]
    old_window = [idx.shards[0], idx.shards[1]]
    base = [s.stats()["serving"] for s in untouched]

    swapped = []
    assert idx.start_reshard(0, 1, on_swap=lambda: swapped.append(1))
    for _ in range(400):
        assert (nfl.lookup_batch(batch) == exp).all()   # funds the ticks
        if swapped:
            break
    assert swapped == [1], "migration never swapped"
    # the swap replaced exactly the window slots
    assert idx.shards[2] is untouched[0] and idx.shards[3] is untouched[1]
    assert idx.shards[0] is not old_window[0]
    assert idx.shards[1] is not old_window[1]
    # post-swap, the warmed shape serves with zero retraces and zero new
    # jit cache entries — the swap invalidated nothing the untouched
    # shards were serving from (building the fresh candidates may trace
    # THEIR fold/pack shapes mid-flight; the swap itself adds nothing)
    warmed = ops.serving_cache_size()
    r0 = ops.fused_lookup_stats()["retrace_count"]
    for _ in range(4):
        assert (nfl.lookup_batch(batch) == exp).all()
    assert ops.serving_cache_size() == warmed, \
        "migration swap retraced a warmed serving kernel"
    assert ops.fused_lookup_stats()["retrace_count"] == r0
    for s, b in zip(untouched, base):
        now = s.stats()["serving"]
        assert now["tier_repacks"] == b["tier_repacks"], \
            "migration repacked an untouched shard's tiers"
        assert now["ratchet_releases"] == b["ratchet_releases"], \
            "migration released ratchets outside the window"
    # fresh candidates: ratchets released by construction, not by a
    # release call on a shard that was serving
    for s in idx.shards[:2]:
        assert s.stats()["serving"]["ratchet_releases"] == 0


def test_preallocate_pins_tier_capacity():
    idx, _ = _mk_index(4_000, seed=46, delta_cap=128)
    serving = idx._serving
    assert serving.delta.capacity >= pow2_bucket(8 * 128 + 1)
    assert serving.run.capacity >= serving.run.min_capacity
    repacks0 = serving.stats()["tier_repacks"]
    # fill the delta to its configured cap: no capacity growth
    rng = np.random.default_rng(46)
    fresh = np.unique(rng.uniform(2e9, 3e9, 500))
    for s in range(0, len(fresh), 100):
        idx.insert_batch(fresh[s:s + 100], np.arange(100))
    assert serving.stats()["tier_repacks"] == repacks0
