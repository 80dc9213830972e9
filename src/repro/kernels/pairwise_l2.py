"""Pallas TPU kernel — blocked pairwise squared-L2 distance (+ threshold).

The verify step of DiskJoin computes d²(a, b) for every (a, b) across a
bucket pair. On TPU this is a matmul-shaped workload:

    D² = ‖a‖² − 2·A Bᵀ + ‖b‖²

Tiling: grid (M/bm, N/bn, d/bk). Each step loads an A tile (bm, bk) and a
B tile (bn, bk) into VMEM and accumulates −2·A Bᵀ into the (bm, bn) output
tile that lives in VMEM across the k loop (out block index ignores k). The
squared norms are folded in on the final k step, fused with the ε²
threshold mask — no second pass over HBM.

Block defaults (128, 128, 128) keep the MXU fully shaped: A+B tiles are
2·128·128·4 B = 128 KiB plus a 64 KiB f32 accumulator tile ≪ 16 MiB VMEM,
leaving room for double buffering.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.ref import MATMUL_PRECISION


DEFAULT_BM = 128
DEFAULT_BN = 128
DEFAULT_BK = 128


def _pairwise_kernel(a_ref, b_ref, d2_ref, mask_ref, *, eps2: float,
                     nk: int):
    """One (m, n, k) grid step."""
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        d2_ref[...] = jnp.zeros_like(d2_ref)

    a = a_ref[...].astype(jnp.float32)          # (bm, bk)
    b = b_ref[...].astype(jnp.float32)          # (bn, bk)
    # accumulate -2 A B^T plus the per-k-slice norm contributions; summing
    # |a_k|^2 and |b_k|^2 per slice is exact since norms decompose over k.
    acc = d2_ref[...]
    acc += -2.0 * jax.lax.dot_general(
        a, b, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
        precision=MATMUL_PRECISION)
    acc += jnp.sum(a * a, axis=1)[:, None]
    acc += jnp.sum(b * b, axis=1)[None, :]
    d2_ref[...] = acc

    @pl.when(k == nk - 1)
    def _finalize():
        d2 = jnp.maximum(d2_ref[...], 0.0)
        d2_ref[...] = d2
        mask_ref[...] = (d2 <= eps2).astype(jnp.int8)


def _pairwise_kernel_batched(a_ref, b_ref, d2_ref, mask_ref, *, eps2: float,
                             nk: int):
    """One (e, m, n, k) grid step — leading batch (edge) dimension."""
    k = pl.program_id(3)

    @pl.when(k == 0)
    def _init():
        d2_ref[...] = jnp.zeros_like(d2_ref)

    a = a_ref[0].astype(jnp.float32)            # (bm, bk)
    b = b_ref[0].astype(jnp.float32)            # (bn, bk)
    acc = d2_ref[0]
    acc += -2.0 * jax.lax.dot_general(
        a, b, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
        precision=MATMUL_PRECISION)
    acc += jnp.sum(a * a, axis=1)[:, None]
    acc += jnp.sum(b * b, axis=1)[None, :]
    d2_ref[0] = acc

    @pl.when(k == nk - 1)
    def _finalize():
        d2 = jnp.maximum(d2_ref[...], 0.0)
        d2_ref[...] = d2
        mask_ref[...] = (d2 <= eps2).astype(jnp.int8)


@functools.partial(jax.jit, static_argnames=("eps2", "bm", "bn", "bk",
                                             "interpret"))
def pairwise_l2_threshold_batched(a: jax.Array, b: jax.Array, eps2: float,
                                  bm: int = DEFAULT_BM, bn: int = DEFAULT_BN,
                                  bk: int = DEFAULT_BK,
                                  interpret: bool = False):
    """(E, M, d) × (E, N, d) → (d2 (E, M, N) f32, mask (E, M, N) int8).

    One grid dispatch for a whole verify batch — the per-edge Python loop
    the executor used to run (E separate jit calls) collapses into a
    single kernel launch with a leading batch grid dimension.
    """
    e, m, d = a.shape
    _, n, _ = b.shape
    bm, bn, bk = min(bm, m), min(bn, n), min(bk, d)
    if m % bm or n % bn or d % bk:
        raise ValueError(f"shapes ({m},{n},{d}) not divisible by blocks "
                         f"({bm},{bn},{bk})")
    nk = d // bk
    grid = (e, m // bm, n // bn, nk)
    kernel = functools.partial(_pairwise_kernel_batched, eps2=float(eps2),
                               nk=nk)
    d2, mask = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bm, bk), lambda e, i, j, k: (e, i, k)),
            pl.BlockSpec((1, bn, bk), lambda e, i, j, k: (e, j, k)),
        ],
        out_specs=[
            pl.BlockSpec((1, bm, bn), lambda e, i, j, k: (e, i, j)),
            pl.BlockSpec((1, bm, bn), lambda e, i, j, k: (e, i, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((e, m, n), jnp.float32),
            jax.ShapeDtypeStruct((e, m, n), jnp.int8),
        ],
        interpret=interpret,
    )(a, b)
    return d2, mask


@functools.partial(jax.jit, static_argnames=("eps2", "bm", "bn", "bk",
                                             "interpret"))
def pairwise_l2_threshold(a: jax.Array, b: jax.Array, eps2: float,
                          bm: int = DEFAULT_BM, bn: int = DEFAULT_BN,
                          bk: int = DEFAULT_BK, interpret: bool = False):
    """(M, d) × (N, d) → (d2 (M, N) f32, mask (M, N) int8).

    M, N, d must be multiples of the block sizes — callers pad (the DiskJoin
    executor pads buckets to `bucket_capacity`, which is MXU-aligned).
    """
    m, d = a.shape
    n, _ = b.shape
    bm, bn, bk = min(bm, m), min(bn, n), min(bk, d)
    if m % bm or n % bn or d % bk:
        raise ValueError(f"shapes ({m},{n},{d}) not divisible by blocks "
                         f"({bm},{bn},{bk})")
    nk = d // bk
    grid = (m // bm, n // bn, nk)
    kernel = functools.partial(_pairwise_kernel, eps2=float(eps2), nk=nk)
    d2, mask = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bn, bk), lambda i, j, k: (j, k)),
        ],
        out_specs=[
            pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
            pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((m, n), jnp.float32),
            jax.ShapeDtypeStruct((m, n), jnp.int8),
        ],
        interpret=interpret,
    )(a, b)
    return d2, mask
