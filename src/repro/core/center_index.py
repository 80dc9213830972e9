"""Center index — TPU-native replacement for the paper's HNSW (§5.1).

The paper builds an HNSW over the sampled centers and answers
nearest-center queries by graph traversal. Graph traversal is pointer
chasing: data-dependent gathers and branches, which starve the MXU/VPU.
On TPU the idiomatic equivalent is a *dense blocked distance matmul*:

    d²(q, c) = ‖q‖² − 2 q·cᵀ + ‖c‖²

computed tile-by-tile at matmul speed, followed by a top-L reduce. For very
large center sets a two-level IVF structure bounds work: centers are grouped
under √B coarse centroids; a query scans the nprobe nearest coarse cells
only. Both paths are exact within the probed set and run as a handful of
einsums — no host round-trips inside the scan loop.

This file is pure JAX (jit'd); the Pallas `bucket_assign` kernel in
repro.kernels fuses the distance+argmin for the assignment hot loop.
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.ref import MATMUL_PRECISION


@partial(jax.jit, static_argnames=("k",))
def _topk_neg_dist(queries: jax.Array, centers: jax.Array,
                   center_sq: jax.Array, k: int):
    """Top-k nearest (squared L2) centers per query via one matmul."""
    qsq = jnp.sum(queries * queries, axis=1, keepdims=True)
    d2 = (qsq - 2.0 * jnp.matmul(queries, centers.T,
                                 precision=MATMUL_PRECISION)
          + center_sq[None, :])
    neg, idx = jax.lax.top_k(-d2, k)
    return -neg, idx


@jax.jit
def _nearest(queries: jax.Array, centers: jax.Array, center_sq: jax.Array):
    qsq = jnp.sum(queries * queries, axis=1, keepdims=True)
    d2 = (qsq - 2.0 * jnp.matmul(queries, centers.T,
                                 precision=MATMUL_PRECISION)
          + center_sq[None, :])
    idx = jnp.argmin(d2, axis=1)
    return jnp.take_along_axis(d2, idx[:, None], axis=1)[:, 0], idx


@dataclasses.dataclass
class BruteForceCenterIndex:
    """Exact blocked matmul index — right answer for ≲64k centers."""

    centers: np.ndarray  # (B, d) float32

    def __post_init__(self):
        self._centers_dev = jnp.asarray(self.centers, jnp.float32)
        self._center_sq = jnp.sum(self._centers_dev ** 2, axis=1)

    @property
    def num_centers(self) -> int:
        return self.centers.shape[0]

    def assign(self, queries: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
        """Nearest center per query → (sq_dists, center_ids)."""
        d2, idx = _nearest(jnp.asarray(queries, jnp.float32),
                           self._centers_dev, self._center_sq)
        return np.asarray(d2), np.asarray(idx)

    def search(self, queries: np.ndarray, k: int) -> tuple[np.ndarray, np.ndarray]:
        """k nearest centers per query → (sq_dists (Q,k), ids (Q,k))."""
        k = min(k, self.num_centers)
        d2, idx = _topk_neg_dist(jnp.asarray(queries, jnp.float32),
                                 self._centers_dev, self._center_sq, k)
        return np.asarray(d2), np.asarray(idx)


@dataclasses.dataclass
class IVFCenterIndex:
    """Two-level index: coarse k-means-lite over centers, probe-limited scan.

    Build: sample √B coarse centroids from the centers, one Lloyd refinement
    pass (all matmuls), group centers by coarse cell. Query: find nprobe
    nearest coarse cells, scan their member centers exactly.

    Memory: centers + int32 cell assignment ≈ the paper's "2‰ of dataset"
    HNSW footprint claim; compute: O(Q·(√B + B·nprobe/√B)·d) vs O(Q·B·d)
    brute force.
    """

    centers: np.ndarray
    nprobe: int = 8
    seed: int = 0

    def __post_init__(self):
        B, d = self.centers.shape
        ncoarse = max(1, int(np.sqrt(B)))
        rng = np.random.default_rng(self.seed)
        coarse = self.centers[rng.choice(B, size=ncoarse, replace=False)]
        # one Lloyd step (matmul-only refinement)
        cj = jnp.asarray(coarse, jnp.float32)
        xs = jnp.asarray(self.centers, jnp.float32)
        _, assign = _nearest(xs, cj, jnp.sum(cj ** 2, axis=1))
        assign = np.asarray(assign)
        for c in range(ncoarse):
            m = assign == c
            if m.any():
                coarse[c] = self.centers[m].mean(axis=0)
        cj = jnp.asarray(coarse, jnp.float32)
        _, assign = _nearest(xs, cj, jnp.sum(cj ** 2, axis=1))
        assign = np.asarray(assign)

        self.coarse = coarse
        self._coarse_dev = cj
        self._coarse_sq = jnp.sum(cj ** 2, axis=1)
        # bucket-list layout: members sorted by cell, offsets per cell
        order = np.argsort(assign, kind="stable")
        self._member_ids = order.astype(np.int32)
        self._cell_of = assign
        counts = np.bincount(assign, minlength=ncoarse)
        self._cell_offsets = np.concatenate([[0], np.cumsum(counts)])
        self._centers_sorted = self.centers[order]
        self._centers_sorted_dev = jnp.asarray(self._centers_sorted, jnp.float32)
        self._centers_sorted_sq = jnp.sum(self._centers_sorted_dev ** 2, axis=1)
        self.ncoarse = ncoarse

    @property
    def num_centers(self) -> int:
        return self.centers.shape[0]

    def _probe_members(self, cells: np.ndarray) -> np.ndarray:
        segs = [np.arange(self._cell_offsets[c], self._cell_offsets[c + 1])
                for c in cells]
        return np.concatenate(segs) if segs else np.zeros(0, np.int64)

    def search(self, queries: np.ndarray, k: int) -> tuple[np.ndarray, np.ndarray]:
        queries = np.asarray(queries, np.float32)
        nprobe = min(self.nprobe, self.ncoarse)
        _, cell_idx = _topk_neg_dist(jnp.asarray(queries), self._coarse_dev,
                                     self._coarse_sq, nprobe)
        cell_idx = np.asarray(cell_idx)
        out_d = np.full((len(queries), k), np.inf, np.float32)
        out_i = np.zeros((len(queries), k), np.int64)
        # batch queries that probe identical cell sets to amortize gathers
        for qi in range(len(queries)):
            members = self._probe_members(cell_idx[qi])
            if members.size == 0:
                continue
            sub = self._centers_sorted_dev[members]
            d2 = np.asarray(
                jnp.sum((sub - jnp.asarray(queries[qi])[None, :]) ** 2, axis=1))
            kk = min(k, members.size)
            part = np.argpartition(d2, kk - 1)[:kk]
            part = part[np.argsort(d2[part])]
            out_d[qi, :kk] = d2[part]
            out_i[qi, :kk] = self._member_ids[members[part]]
        return out_d, out_i

    def assign(self, queries: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
        d, i = self.search(queries, 1)
        return d[:, 0], i[:, 0]


def make_center_index(centers: np.ndarray, *, exact_threshold: int = 65536,
                      nprobe: int = 8, seed: int = 0):
    """Pick brute-force vs IVF by center count (DESIGN §2 crossover)."""
    if centers.shape[0] <= exact_threshold:
        return BruteForceCenterIndex(centers)
    return IVFCenterIndex(centers, nprobe=nprobe, seed=seed)
