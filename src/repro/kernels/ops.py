"""jit'd public wrappers around the Pallas kernels.

Every op pads its inputs to kernel block multiples, dispatches to the Pallas
kernel compiled on TPU (interpreted on the CPU backend, for tests; any other
backend is an error), or to the pure-jnp reference when ``use_pallas`` is
off, and strips padding from the result. The DiskJoin executor and the model
stack call only this layer.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import bucket_assign as _assign_kernel
from repro.kernels import flash_attention as _flash_kernel
from repro.kernels import pairwise_l2 as _pairwise_kernel
from repro.kernels import ref


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def interpret_mode() -> bool:
    """Compiled on TPU, interpreted on CPU; never a silent interpreter
    run on an accelerator the kernels were not written for."""
    if on_tpu():
        return False
    backend = jax.default_backend()
    if backend == "cpu":
        return True
    raise RuntimeError(f"Pallas kernels run compiled on TPU or interpreted "
                       f"on CPU, not on backend {backend!r}")


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def pad_rows(x, rows: int, value: float = 0.0):
    if x.shape[0] == rows:
        return x
    pad = [(0, rows - x.shape[0])] + [(0, 0)] * (x.ndim - 1)
    return jnp.pad(x, pad, constant_values=value)


# ---------------------------------------------------------------------------
# pairwise distance + threshold (DiskJoin verify step)
# ---------------------------------------------------------------------------
def pairwise_l2_threshold(a, b, eps: float, *, use_pallas: bool = False,
                          block: int = 128):
    """(M,d) × (N,d) → (d2 (M,N) f32, mask (M,N) bool). Unpadded shapes."""
    a = jnp.asarray(a, jnp.float32)
    b = jnp.asarray(b, jnp.float32)
    eps2 = float(eps) ** 2
    if not use_pallas:
        return ref.pairwise_l2_threshold(a, b, eps2)
    m, d = a.shape
    n, _ = b.shape
    mp, np_, dp = _round_up(m, block), _round_up(n, block), _round_up(d, block)
    ap = jnp.pad(a, ((0, mp - m), (0, dp - d)))
    bp = jnp.pad(b, ((0, np_ - n), (0, dp - d)))
    d2, mask = _pairwise_kernel.pairwise_l2_threshold(
        ap, bp, eps2, interpret=interpret_mode())
    return d2[:m, :n], mask[:m, :n].astype(bool)


@functools.partial(jax.jit, static_argnames=("eps2",))
def _verify_pairs_ref(u, v, eps2: float):
    d2 = jax.vmap(ref.pairwise_l2)(u, v)
    return d2, d2 <= eps2


def verify_pairs_batch(u, v, eps: float, *, use_pallas: bool = False,
                       block: int = 128):
    """Batched verify: (E, M, d) × (E, N, d) → (d2, mask), (E, M, N).

    ONE dispatch for the whole edge batch — the Pallas path rides a
    leading batch grid dimension (``pairwise_l2_threshold_batched``)
    instead of E separate jit calls, and the reference path is the
    vmapped oracle. Both engines (``repro.compute``) consume this, so
    host and device compute modes see bitwise-identical d2. A join lane
    pairs two bucket slabs (M = N = capacity); a query lane pairs a
    wave's query rows with one bucket slab (M ≠ N).
    """
    eps2 = float(eps) ** 2
    if not use_pallas:
        return _verify_pairs_ref(u, v, eps2)
    _, m, d = u.shape
    n = v.shape[1]
    # the kernel clamps blocks to the dims, so only dims above `block`
    # that aren't multiples of it need padding
    if d > block and d % block:
        dp = _round_up(d, block)
        u = jnp.pad(u, ((0, 0), (0, 0), (0, dp - d)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, dp - d)))
    mp = _round_up(m, block) if (m > block and m % block) else m
    np_ = _round_up(n, block) if (n > block and n % block) else n
    # pad rows far away so they can never pass the ε² threshold; each
    # operand to its own block multiple, sliced back below
    if mp != m:
        u = jnp.pad(u, ((0, 0), (0, mp - m), (0, 0)), constant_values=1e15)
    if np_ != n:
        v = jnp.pad(v, ((0, 0), (0, np_ - n), (0, 0)), constant_values=1e15)
    d2, mask = _pairwise_kernel.pairwise_l2_threshold_batched(
        u, v, eps2, interpret=interpret_mode())
    if mp != m or np_ != n:
        d2, mask = d2[:, :m, :n], mask[:, :m, :n]
    return d2, mask.astype(bool)


# ---------------------------------------------------------------------------
# nearest-center assignment (bucketization scan 2)
# ---------------------------------------------------------------------------
def bucket_assign(x, centers, *, use_pallas: bool = True, block: int = 128):
    """(M,d) × (B,d) → (min_d2 (M,), argmin (M,) int32)."""
    x = jnp.asarray(x, jnp.float32)
    centers = jnp.asarray(centers, jnp.float32)
    if not use_pallas:
        return ref.bucket_assign(x, centers)
    m, d = x.shape
    b, _ = centers.shape
    mp, bp = _round_up(m, block), _round_up(b, block)
    xp = pad_rows(x, mp)
    # pad centers far away so padded rows never win the argmin
    cp = pad_rows(centers, bp, value=0.0)
    if bp != b:
        far = jnp.full((bp - b, d), 1e15, jnp.float32)
        cp = jnp.concatenate([centers, far], axis=0)
    mind2, idx = _assign_kernel.bucket_assign(xp, cp,
                                              interpret=interpret_mode())
    return mind2[:m], idx[:m]


# ---------------------------------------------------------------------------
# flash attention (LM substrate)
# ---------------------------------------------------------------------------
def flash_attention(q, k, v, *, causal: bool = True,
                    scale: float | None = None, use_pallas: bool = False):
    """q: (B,H,S,D); k/v: (B,H,T,D) — GQA repeat done by caller."""
    if not use_pallas:
        return ref.attention(q, k, v, causal=causal, scale=scale)
    B, H, S, D = q.shape
    T = k.shape[2]
    if causal and S != T:
        # kernel causal convention: q position == row index (self-attn
        # prefill); offset-causal (decode against a longer cache) goes
        # through the cache-aware jnp path
        return ref.attention(q, k, v, causal=causal, scale=scale)
    bq = min(128, S)
    bkv = min(128, T)
    sp, tp = _round_up(S, bq), _round_up(T, bkv)
    qf = q.reshape(B * H, S, D)
    kf = k.reshape(B * H, T, D)
    vf = v.reshape(B * H, T, D)
    if sp != S:
        qf = jnp.pad(qf, ((0, 0), (0, sp - S), (0, 0)))
    if tp != T:
        kf = jnp.pad(kf, ((0, 0), (0, tp - T), (0, 0)))
        vf = jnp.pad(vf, ((0, 0), (0, tp - T), (0, 0)))
        # padded kv columns masked out by causal rows < T; for non-causal,
        # fall back to ref to avoid attending to pad
        if not causal:
            return ref.attention(q, k, v, causal=causal, scale=scale)
    out = _flash_kernel.flash_attention(qf, kf, vf, causal=causal,
                                        scale=scale, bq=bq, bkv=bkv,
                                        interpret=interpret_mode())
    return out[:, :S, :].reshape(B, H, S, D)


# ---------------------------------------------------------------------------
# host-side helpers for the executor
# ---------------------------------------------------------------------------
def extract_pairs(d2: np.ndarray, mask: np.ndarray,
                  ids_a: np.ndarray, ids_b: np.ndarray,
                  *, upper_triangle: bool = False):
    """mask → (pairs (P,2) int64 original ids, dists (P,) f32)."""
    m = np.asarray(mask)
    if upper_triangle:
        m = np.triu(m, k=1)
    rows, cols = np.nonzero(m)
    if rows.size == 0:
        return np.zeros((0, 2), np.int64), np.zeros(0, np.float32)
    d = np.sqrt(np.asarray(d2)[rows, cols].astype(np.float32))
    pairs = np.stack([ids_a[rows], ids_b[cols]], axis=1).astype(np.int64)
    return pairs, d
