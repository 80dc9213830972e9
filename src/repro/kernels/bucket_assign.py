"""Pallas TPU kernel — fused nearest-center assignment.

Scan-2 hot loop of bucketization: for a block of vectors X (M, d) and the
center table C (B, d), find argmin_b d²(x, c_b) per row. Tiling: grid
(M/bm, B/bb); the running (min, argmin) pair lives in the output refs across
the center-tile loop (out block index ignores the center axis), so the
(bm, bb) distance tile never round-trips to HBM.

The outputs are lane-dense (M, 128) arrays whose lanes all hold the row's
value; the wrapper keeps lane 0. A 1-D (bm,) output block would need a
tiling Mosaic does not share with XLA's for a long 1-D array.

d is kept whole per tile (embedding dims ≤ a few K fit VMEM comfortably:
128 rows × 1536 dims × 4 B = 768 KiB).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.ref import MATMUL_PRECISION

DEFAULT_BM = 128
DEFAULT_BB = 128
LANES = 128


def _assign_kernel(x_ref, c_ref, mind2_ref, idx_ref, *, bb: int):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        mind2_ref[...] = jnp.full_like(mind2_ref, jnp.inf)
        idx_ref[...] = jnp.zeros_like(idx_ref)

    x = x_ref[...].astype(jnp.float32)           # (bm, d)
    c = c_ref[...].astype(jnp.float32)           # (bb, d)
    d2 = (jnp.sum(x * x, axis=1, keepdims=True)
          - 2.0 * jax.lax.dot_general(
              x, c, (((1,), (1,)), ((), ())),
              preferred_element_type=jnp.float32,
              precision=MATMUL_PRECISION)
          + jnp.sum(c * c, axis=1)[None, :])     # (bm, bb)
    tile_min = jnp.min(d2, axis=1, keepdims=True)
    tile_arg = jnp.argmin(d2, axis=1).astype(jnp.int32)[:, None] + j * bb

    shape = mind2_ref.shape
    tile_min = jnp.broadcast_to(tile_min, shape)
    tile_arg = jnp.broadcast_to(tile_arg, shape)
    better = tile_min < mind2_ref[...]
    mind2_ref[...] = jnp.where(better, tile_min, mind2_ref[...])
    idx_ref[...] = jnp.where(better, tile_arg, idx_ref[...])


@functools.partial(jax.jit, static_argnames=("bm", "bb", "interpret"))
def bucket_assign(x: jax.Array, centers: jax.Array,
                  bm: int = DEFAULT_BM, bb: int = DEFAULT_BB,
                  interpret: bool = False):
    """(M, d) × (B, d) → (min_d2 (M,) f32, argmin (M,) int32).

    M and B must be multiples of bm/bb (callers pad; padded centers must be
    far away — use `ops.bucket_assign`, which pads with 1e15 rows).
    """
    m, d = x.shape
    b, _ = centers.shape
    bm, bb = min(bm, m), min(bb, b)
    if m % bm or b % bb:
        raise ValueError(f"shapes ({m},{b}) not divisible by ({bm},{bb})")
    grid = (m // bm, b // bb)
    kernel = functools.partial(_assign_kernel, bb=bb)
    mind2, idx = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, d), lambda i, j: (i, 0)),
            pl.BlockSpec((bb, d), lambda i, j: (j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bm, LANES), lambda i, j: (i, 0)),
            pl.BlockSpec((bm, LANES), lambda i, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((m, LANES), jnp.float32),
            jax.ShapeDtypeStruct((m, LANES), jnp.int32),
        ],
        interpret=interpret,
    )(x, centers)
    return mind2[:, 0], idx[:, 0]
