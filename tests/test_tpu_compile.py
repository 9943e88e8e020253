"""Compile every serve-path program for a described TPU v5e chip.

Nothing here runs: the TPU compiler installed with JAX compiles for a
chip that is described, not attached, and raises what the chip's
compiler would raise (DESIGN.md §2).  The shapes are those of a
4,194,304-key lognormal index with the flow on — the pool buckets
``NFL(backend="flat")`` builds for it, past any VMEM budget — so a
change that the chip would refuse fails here, at no chip time.

Covered: ``nf_forward_pallas`` (the one Pallas kernel on the chip path,
at the build-transform and serve batch shapes), the XLA point route
(``flat_afli.xla_lookup``), the XLA range route
(``range_scan.xla_range_scan``) and the shard router (``_route_flow``).
The traversal kernels (fused, streamed, range) are not compiled here:
Mosaic does not lower their vector gathers, and ``ops.traversal_route``
never sends them to a compiled backend.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.flat_afli import xla_lookup
from repro.kernels.fused_lookup import KernelPools, TierPools
from repro.kernels.nf_forward import nf_forward_pallas
from repro.kernels.range_scan import ScanPool, xla_range_scan
from repro.kernels.shard_dispatch import _route_flow

# pool buckets of a 2^22-key lognormal flat index (flow on): nodes,
# entries, conflict buckets, rank-ordered scan pool, run and delta tiers
N_NODES, N_ENTRIES, N_BUCKETS, BUCKET_CAP = 1 << 17, 1 << 24, 1 << 21, 6
SCAN_CAP, RUN_CAP, DELTA_CAP = 1 << 23, 1 << 21, 1 << 16
FLOW_SHAPES = ((4, 2), (2, 4))     # FlowConfig() defaults: d=2, h=2
N_WEIGHTS = 28
DIM = 2
SERVE_BATCH = 1024


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=False)
def no_cache():
    """A compile for a described chip is written to the persistent
    cache but cannot be read back without the chip: keep it off."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _tiers(sh):
    return TierPools(
        _sds((RUN_CAP,), jnp.float32, sh), _sds((RUN_CAP,), jnp.uint32, sh),
        _sds((RUN_CAP,), jnp.uint32, sh), _sds((RUN_CAP,), jnp.int32, sh),
        _sds((128,), jnp.int32, sh),
        _sds((DELTA_CAP,), jnp.float32, sh),
        _sds((DELTA_CAP,), jnp.uint32, sh),
        _sds((DELTA_CAP,), jnp.uint32, sh),
        _sds((DELTA_CAP,), jnp.int32, sh), _sds((128,), jnp.int32, sh))


def _feats(sh, batch, use_flow):
    return _sds((batch, DIM if use_flow else 1), jnp.float32, sh)


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


@pytest.mark.parametrize("batch", [SERVE_BATCH, 1 << 22])
def test_nf_forward_compiles(one_chip, no_cache, batch):
    """The NF kernel at a serve batch and at the 2^22-key build
    transform: Mosaic accepts the 1-D output block (tile 1024)."""
    c = _compile(
        lambda f, w: nf_forward_pallas(f, w, FLOW_SHAPES, DIM,
                                       interpret=False),
        _feats(one_chip, batch, True),
        _sds((1, N_WEIGHTS), jnp.float32, one_chip))
    assert "tpu_custom_call" in c.as_text()


@pytest.mark.parametrize("use_flow", [True, False])
@pytest.mark.parametrize("tiers", [True, False])
def test_xla_point_route_compiles(one_chip, no_cache, use_flow, tiers):
    sh = one_chip
    pools = KernelPools(
        _sds((N_NODES,), jnp.int32, sh), _sds((N_NODES,), jnp.float32, sh),
        _sds((N_NODES,), jnp.float32, sh), _sds((N_NODES,), jnp.int32, sh),
        _sds((N_NODES,), jnp.int32, sh),
        _sds((N_ENTRIES,), jnp.int32, sh),
        _sds((N_ENTRIES,), jnp.float32, sh),
        _sds((N_ENTRIES,), jnp.uint32, sh),
        _sds((N_ENTRIES,), jnp.uint32, sh),
        _sds((N_ENTRIES,), jnp.int32, sh),
        _sds((N_ENTRIES,), jnp.int32, sh),
        _sds((N_BUCKETS, BUCKET_CAP), jnp.uint32, sh),
        _sds((N_BUCKETS, BUCKET_CAP), jnp.uint32, sh),
        _sds((N_BUCKETS, BUCKET_CAP), jnp.int32, sh),
        _sds((N_BUCKETS,), jnp.int32, sh))
    q = _sds((SERVE_BATCH,), jnp.uint32, sh)

    def route(p, f, qhi, qlo, w, t):
        return xla_lookup(
            p, f, qhi, qlo, w, t, dim=DIM if use_flow else 1,
            shapes=FLOW_SHAPES if use_flow else (), max_depth=8,
            dense_iters=24, bucket_cap=BUCKET_CAP, dense_window=32,
            use_flow=use_flow, probe_tiers=tiers, run_iters=22,
            run_window=16, delta_iters=17, delta_window=4)

    c = _compile(route, pools, _feats(sh, SERVE_BATCH, use_flow), q, q,
                 _sds((1, N_WEIGHTS if use_flow else 1), jnp.float32, sh),
                 _tiers(sh) if tiers else None)
    assert ("tpu_custom_call" in c.as_text()) == use_flow


@pytest.mark.parametrize("use_flow", [True, False])
def test_xla_range_route_compiles(one_chip, no_cache, use_flow):
    sh = one_chip
    spool = ScanPool(_sds((SCAN_CAP,), jnp.float32, sh),
                     _sds((SCAN_CAP,), jnp.uint32, sh),
                     _sds((SCAN_CAP,), jnp.uint32, sh),
                     _sds((SCAN_CAP,), jnp.int32, sh),
                     _sds((128,), jnp.int32, sh))
    f = _feats(sh, 256, use_flow)

    def route(flo, fhi, w, sp, t):
        return xla_range_scan(
            flo, fhi, w, sp, t, dim=DIM if use_flow else 1,
            shapes=FLOW_SHAPES if use_flow else (), scan_cap=128,
            scan_iters=24, use_flow=use_flow, probe_tiers=True,
            run_iters=22, run_window=16, delta_iters=17, delta_window=4)

    c = _compile(route, f, f,
                 _sds((1, N_WEIGHTS if use_flow else 1), jnp.float32, sh),
                 spool, _tiers(sh))
    assert ("tpu_custom_call" in c.as_text()) == use_flow


def test_shard_router_compiles(one_chip, no_cache):
    c = _compile(
        lambda f, w, b: _route_flow(f, w, b, dim=DIM, shapes=FLOW_SHAPES,
                                    interpret=False),
        _feats(one_chip, SERVE_BATCH, True),
        _sds((1, N_WEIGHTS), jnp.float32, one_chip),
        _sds((3,), jnp.float32, one_chip))
    assert "tpu_custom_call" in c.as_text()
