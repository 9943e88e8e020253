"""Deliberately-broken serving kernels for analyzer self-tests
(DESIGN.md §15).

Each fixture re-introduces one previously-shipped bug class in
miniature so the test suite can assert the analyzer reports it with a
file:line finding — and so a future refactor of the checks cannot
silently stop detecting the bug that motivated them.

These are *traced*, never executed: every fixture builds a
``ClosedJaxpr`` via ``jax.make_jaxpr`` (pallas kernels trace fine
without a TPU).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl


def _trace(fn, *avals):
    args = [jnp.zeros(s, d) for s, d in avals]
    return jax.make_jaxpr(fn)(*args)


# ------------------------------------------------------- clip gather
def _clip_gather_kernel(idx_ref, table_ref, out_ref):
    # PR 3 bug class: mode="clip" take inside the kernel body — the
    # fixed kernel uses plain `table[idx]` (PROMISE_IN_BOUNDS).
    idx = idx_ref[...]
    table = table_ref[...]
    out_ref[...] = jnp.take(table, idx, mode="clip")


def clip_gather_jaxpr():
    fn = pl.pallas_call(
        _clip_gather_kernel,
        out_shape=jax.ShapeDtypeStruct((128,), jnp.float32),
        interpret=True)
    return _trace(fn, ((128,), jnp.int32), ((128,), jnp.float32))


# ----------------------------------------------------- host callback
def _host_probe(pk):
    return np.zeros(pk.shape, np.int32)


def host_callback_jaxpr():
    # A "serving" wrapper that shells out to the host per dispatch —
    # the oracle-fallback bug class, expressed as a callback so it is
    # visible in the jaxpr instead of hiding in python control flow.
    def serve(pk):
        z = pk * 2.0
        hit = jax.pure_callback(
            _host_probe, jax.ShapeDtypeStruct(pk.shape, jnp.int32), z)
        return hit + 1
    return _trace(serve, ((64,), jnp.float32))


# ------------------------------------------------ identity-lane cast
def _lane_cast_kernel(hi_ref, lo_ref, out_ref):
    # u64 identities ride as two u32 lanes; summing them through f32
    # (24-bit mantissa) collides distinct identities.
    hi = hi_ref[...].astype(jnp.float32)
    lo = lo_ref[...].astype(jnp.float32)
    out_ref[...] = hi * 4294967296.0 + lo


def lane_cast_jaxpr():
    fn = pl.pallas_call(
        _lane_cast_kernel,
        out_shape=jax.ShapeDtypeStruct((128,), jnp.float32),
        interpret=True)
    return _trace(fn, ((128,), jnp.uint32), ((128,), jnp.uint32))


# -------------------------------------------------- batch-length loop
def _batch_loop_kernel(q_ref, pool_ref, out_ref):
    # A fori_loop over the whole batch serializes what the tiled grid
    # was built to parallelize.
    q = q_ref[...]
    pool = pool_ref[...]
    n = q.shape[0]

    def body(i, acc):
        return acc.at[i].set(jnp.sum(jnp.where(pool <= q[i], 1, 0)))

    out_ref[...] = jax.lax.fori_loop(
        0, n, body, jnp.zeros((n,), jnp.int32))


def batch_loop_jaxpr(batch: int = 4096):
    fn = pl.pallas_call(
        _batch_loop_kernel,
        out_shape=jax.ShapeDtypeStruct((batch,), jnp.int32),
        interpret=True)
    return _trace(fn, ((batch,), jnp.float32), ((256,), jnp.float32))


# ------------------------------------------------------- f64 upcast
def f64_upcast_jaxpr():
    def serve(pk):
        # x64 is disabled repo-wide, so model the upcast the way it
        # actually bites: an f64 constant table captured into the trace.
        with jax.enable_x64(True):
            table = jnp.linspace(0.0, 1.0, 8, dtype=jnp.float64)
        return jnp.searchsorted(table.astype(jnp.float32), pk)
    return _trace(serve, ((64,), jnp.float32))


# --------------------------------- bucket-dependent traced shape (PR 5)
@functools.partial(jax.jit, donate_argnums=(0,))
def _rung_write_prefix(buf, vals):
    """The PR 5 bug class, reconstructed: refresh ships a
    pow2-*rounded prefix* instead of the full capacity bucket, so the
    traced shape of ``vals`` changes at every rung crossing and each
    crossing pays a fresh XLA compile."""
    return jax.lax.dynamic_update_slice(buf, vals, (0,))


def _pow2ceil(n: int, floor: int = 8) -> int:
    p = floor
    while p < n:
        p *= 2
    return p


class RungRefreshTier:
    """Miniature ``DeviceTier`` with the pre-PR-5 prefix discipline:
    every refresh pads the host values to the pow2 *rung*, not the
    full capacity bucket — one jit signature per rung."""

    def __init__(self, capacity: int = 1024):
        self.capacity = capacity
        self.buf = jnp.zeros((capacity,), jnp.float32)

    def refresh(self, vals: np.ndarray) -> None:
        rung = min(_pow2ceil(max(len(vals), 1)), self.capacity)
        padded = np.zeros((rung,), np.float32)
        padded[:len(vals)] = vals
        self.buf = _rung_write_prefix(self.buf, jnp.asarray(padded))

    @staticmethod
    def cache_size() -> int:
        return _rung_write_prefix._cache_size()

    @staticmethod
    def clear_cache() -> None:
        _rung_write_prefix.clear_cache()


class RungPrefixDeviceTier:
    """Drop-in broken ``DeviceTier``: re-introduces the PR 5 refresh
    discipline where the live prefix is shipped rounded to a pow2
    *rung* instead of the full capacity bucket — every rung crossing
    mints a fresh ``_write_prefix`` trace.  Swapped into a
    ``ServingState`` by the retrace-budget regression tests via
    ``drive_lattice(tier_factory=...)``."""

    def __new__(cls):
        from repro.core.serving_state import DeviceTier

        class _Broken(DeviceTier):
            def refresh(self, pk, hi, lo, pv, window):
                from repro.core.serving_state import (_LANE, _write_len,
                                                      _write_prefix,
                                                      pow2_bucket)
                n = int(pk.shape[0])
                need = max(pow2_bucket(n + 1), self.min_capacity)
                self.window = max(self.window, int(window))
                if self.pk is None or need > self.capacity:
                    self._alloc(max(need, self.capacity), pk, hi, lo, pv, n)
                    self.length = n
                    return
                # THE BUG: pad to the pow2 rung, not the capacity
                # bucket — "saves" copy bytes, mints one jit trace per
                # (rung, dtype) as lengths drift across rungs
                m = min(pow2_bucket(n + 1), self.capacity)
                ppk = np.full(m, np.inf, np.float32)
                ppk[:n] = pk
                phi = np.zeros(m, np.uint32)
                phi[:n] = hi
                plo = np.zeros(m, np.uint32)
                plo[:n] = lo
                ppv = np.full(m, -1, np.int32)
                ppv[:n] = pv
                self.pk = _write_prefix(self.pk, jnp.asarray(ppk))
                self.hi = _write_prefix(self.hi, jnp.asarray(phi))
                self.lo = _write_prefix(self.lo, jnp.asarray(plo))
                self.pv = _write_prefix(self.pv, jnp.asarray(ppv))
                self.plen = _write_len(self.plen, np.int32(n))
                self.length = n
                self.uploads += 1
                self.upload_bytes += 4 * m * 4

        return _Broken()


FIXTURES = {
    "fixture:clip-gather": clip_gather_jaxpr,
    "fixture:host-callback": host_callback_jaxpr,
    "fixture:lane-cast": lane_cast_jaxpr,
    "fixture:batch-loop": batch_loop_jaxpr,
    "fixture:f64-upcast": f64_upcast_jaxpr,
}
