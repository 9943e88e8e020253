"""Pallas TPU kernel: fused Numerical-NF inference (paper Table 2 hot path).

The paper runs NF inference with MKL small-matmul calls per layer; on TPU we
instead keep the *entire* flow for a key-batch tile resident in VMEM and
drive the VPU with the batch laid out along lanes:

* the feature dim (d <= 8) and hidden width (h <= 4) are far below MXU tile
  size, so matmuls would waste the systolic array.  We unroll the tiny
  weight loops at trace time into vector FMAs over the [TILE]-lane batch —
  a VPU-shaped computation (DESIGN.md 'hardware adaptation');
* standardization, all layers, tanh, the output scale, and the sum-decode
  (paper Alg 3.1 decoder) are fused into a single VMEM round-trip: one read
  of the [TILE, d] features, one write of the [TILE] transformed keys;
* weights travel as one flat [1, n_params] block replicated to every grid
  step (a few hundred bytes).

Grid: (ceil(B / TILE),).  TILE is a multiple of 1024: Mosaic tiles the
1-D f32 output block as T(TILE) and XLA lays a 1-D f32 array out as
T(1024), and the two must match ("XLA layout ({0:T(1024)}) does not
match Mosaic layout ({0:T(512)})" is what a 512 tile gets on v5e).

This is the one NF evaluation on the flat serve path: the build
transform (``ops.nf_transform_keys``), the point and range routes
(``fused_lookup.positioning_keys``) and the shard router all call it
with the same tile, so a key's serve-time z is bit-equal to the z it
was placed by on every backend (DESIGN.md §9).  It is also the one
kernel of the serve path that Mosaic compiles for the TPU.
"""

from __future__ import annotations

import functools
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.backend import resolve_interpret

__all__ = ["nf_forward_pallas", "pack_flow_weights", "apply_flow_tile",
           "DEFAULT_TILE"]

DEFAULT_TILE = 1024


def pack_flow_weights(
    weights: Sequence[Tuple[jnp.ndarray, jnp.ndarray]],
    out_scale: jnp.ndarray,
    feat_mu: jnp.ndarray,
    feat_sd: jnp.ndarray,
) -> Tuple[jnp.ndarray, Tuple[Tuple[int, int], ...]]:
    """Flatten effective layer weights into one [1, n] f32 row.

    Layout: mu(d) | sd_inv(d) | per-layer [W(row-major out x in) | b] |
    out_scale(d).  Returns (packed, layer_shapes) where layer_shapes[i] =
    (out_width, in_width).
    """
    parts = [feat_mu.reshape(-1), (1.0 / feat_sd).reshape(-1)]
    shapes = []
    for w, b in weights:
        shapes.append((w.shape[0], w.shape[1]))
        parts.append(w.reshape(-1))
        parts.append(b.reshape(-1))
    parts.append(out_scale.reshape(-1))
    packed = jnp.concatenate([p.astype(jnp.float32) for p in parts])
    return packed.reshape(1, -1), tuple(shapes)


def apply_flow_tile(cols, w_ref, dim: int,
                    shapes: Tuple[Tuple[int, int], ...]) -> jnp.ndarray:
    """Unrolled NF forward + sum-decode over one lane-batch tile.

    ``cols`` is the list of ``dim`` [TILE] feature-column vectors; ``w_ref``
    the packed [1, n] weight block (``pack_flow_weights`` layout).  Returns
    the [TILE] transformed keys.  This is THE flow arithmetic of
    ``nf_forward_pallas`` (DESIGN.md §9).
    """
    idx = 0

    def rd(n):
        nonlocal idx
        vals = [w_ref[0, idx + i] for i in range(n)]
        idx += n
        return vals

    mu = rd(dim)
    sd_inv = rd(dim)
    # h: list of [TILE] lane vectors, one per current layer width
    h = [(cols[k] - mu[k]) * sd_inv[k] for k in range(dim)]
    n_layers = len(shapes)
    for li, (n_out, n_in) in enumerate(shapes):
        w = rd(n_out * n_in)
        b = rd(n_out)
        new_h = []
        for j in range(n_out):
            acc = jnp.full_like(h[0], b[j])
            for k in range(n_in):
                acc = acc + h[k] * w[j * n_in + k]
            if li < n_layers - 1:
                acc = jnp.tanh(acc)
            new_h.append(acc)
        h = new_h
    out_scale = rd(dim)
    # decoder (Alg 3.1): z = sum_k h_k * scale_k
    z = h[0] * out_scale[0]
    for k in range(1, dim):
        z = z + h[k] * out_scale[k]
    return z


def _kernel(x_ref, w_ref, o_ref, *, dim: int, shapes: Tuple[Tuple[int, int], ...]):
    """One [TILE, d] feature tile -> [TILE] transformed keys."""
    o_ref[...] = apply_flow_tile([x_ref[:, k] for k in range(dim)],
                                 w_ref, dim, shapes)


@functools.partial(
    jax.jit, static_argnames=("shapes", "dim", "tile", "interpret")
)
def nf_forward_pallas(
    feats: jnp.ndarray,
    packed_w: jnp.ndarray,
    shapes: Tuple[Tuple[int, int], ...],
    dim: int,
    tile: int = DEFAULT_TILE,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """feats [B, d] f32 -> transformed 1-D keys [B] f32.

    B is padded to a tile multiple internally.  ``interpret=None``
    auto-detects the backend (compiled on TPU, interpreted elsewhere).
    """
    interpret = resolve_interpret(interpret)
    b = feats.shape[0]
    b_pad = ((b + tile - 1) // tile) * tile
    if b_pad != b:
        feats = jnp.pad(feats, ((0, b_pad - b), (0, 0)))
    out = pl.pallas_call(
        functools.partial(_kernel, dim=dim, shapes=shapes),
        out_shape=jax.ShapeDtypeStruct((b_pad,), jnp.float32),
        grid=(b_pad // tile,),
        in_specs=[
            pl.BlockSpec((tile, dim), lambda i: (i, 0)),
            pl.BlockSpec((1, packed_w.shape[1]), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((tile,), lambda i: (i,)),
        interpret=interpret,
    )(feats.astype(jnp.float32), packed_w)
    return out[:b]
