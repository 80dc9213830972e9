"""Seeded clustered vectors for the chip benchmark, and the ε calibration.

A copy of the program's ``repro.data.synthetic.clustered_vectors`` (a
Gaussian mixture sampled in a low-dimensional latent space and embedded by
a random orthonormal map, plus small ambient noise), kept here so that no
change to the program can change the benchmark's data. Three departures:

- The whole dataset (cluster centres, each row's cluster and offset, the
  embedding and the ambient noise) comes from the configuration's fixed
  ``structure_seed``; ``--seed`` draws a random rotation of it. A rotation
  keeps every distance, so every seed has the same buckets, bucket graph,
  pairs and so the same work, in other coordinates: seeds change the
  values, not the size of the job, and ε calibrated once holds for all.
- It is drawn with ``jax.random`` on the device, in blocks of rows by one
  compiled program, and copied to the host: 1M x 256 takes seconds (the
  program's float64 draw on the host took 33 s at 1M x 128), and a block
  at a time keeps the device's peak memory that of the program.
- So the rows are not those ``clustered_vectors`` gives for a seed;
  ``test_bench.py`` checks that both calibrate to the same ε within a few
  per cent at a small size.

    python benchmarks/chip/datagen.py <config name> [--seed N]

prints the ε at which a vector has the configuration's ``avg_neighbors``
neighbours on average: the value its file states.
"""
from __future__ import annotations

import argparse
import functools
import json
import os

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
BLOCK_ROWS = 1 << 16


def _key(seed: int):
    import jax
    seed = int(seed)
    return jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0xFFFFFFFF)


@functools.cache
def _block_fn():
    import jax
    import jax.numpy as jnp

    @functools.partial(jax.jit, static_argnames=("rows", "dim", "idim",
                                                 "clusters"))
    def block(skey, key, b, *, rows, dim, idim, clusters, spread, std):
        """Rows [b * rows, (b + 1) * rows) of the mixture, rotated."""
        def mm(u, v):
            return jnp.matmul(u, v, precision=jax.lax.Precision.HIGHEST)

        kc, kb, kp, kn = jax.random.split(skey, 4)
        centers = jax.random.normal(kc, (clusters, idim)) * spread
        ka, kz = jax.random.split(jax.random.fold_in(kb, b))
        assign = jax.random.randint(ka, (rows,), 0, clusters)
        x = centers[assign] + jax.random.normal(kz, (rows, idim)) * std
        if idim < dim:
            proj = jnp.linalg.qr(jax.random.normal(kp, (dim, idim)))[0]
            noise = jax.random.normal(jax.random.fold_in(kn, b), (rows, dim))
            x = mm(x, proj.T) + noise * (std * 0.1)
        rot = jnp.linalg.qr(jax.random.normal(key, (dim, dim)))[0]
        return mm(x, rot)

    return block


def clustered(rows: int, dim: int, *, structure_seed: int, seed: int,
              points_per_cluster: int = 256, intrinsic_dim: int = 12,
              spread: float = 1.0, cluster_std: float = 0.08) -> np.ndarray:
    """(rows, dim) float32 clustered vectors (module docstring)."""
    block = _block_fn()
    clusters = max(4, rows // points_per_cluster)
    idim = min(dim, intrinsic_dim)
    skey, key = _key(structure_seed), _key(seed)
    x = np.empty((rows, dim), np.float32)
    step = min(rows, BLOCK_ROWS)
    for b, r0 in enumerate(range(0, rows, step)):
        r1 = min(rows, r0 + step)
        out = block(skey, key, b, rows=step, dim=dim, idim=idim,
                    clusters=clusters, spread=spread, std=cluster_std)
        x[r0:r1] = np.asarray(out)[:r1 - r0]
    return x


def make(cfg: dict, seed: int) -> np.ndarray:
    """The configuration's data for ``seed``."""
    d = cfg["data"]
    return clustered(cfg["rows"], cfg["dim"],
                     structure_seed=d["structure_seed"], seed=seed,
                     points_per_cluster=d["points_per_cluster"],
                     intrinsic_dim=d["intrinsic_dim"], spread=d["spread"],
                     cluster_std=d["cluster_std"])


def epsilon_for_avg_neighbors(x: np.ndarray, k: int, sample: int = 512,
                              seed: int = 0) -> float:
    """ε at which a vector has ``k`` neighbours (itself excluded), as the
    median over a sample of rows of the distance to the k-th neighbour:
    the program's calibration (the paper's protocol), in float64 over row
    blocks."""
    rng = np.random.default_rng(seed)
    n = x.shape[0]
    idx = rng.choice(n, size=min(sample, n), replace=False)
    q = x[idx].astype(np.float64)
    qsq = np.sum(q * q, axis=1)[:, None]
    kk = min(k, n - 1)
    best = np.full((q.shape[0], kk + 1), np.inf)
    for r0 in range(0, n, BLOCK_ROWS):
        b = x[r0:r0 + BLOCK_ROWS].astype(np.float64)
        d2 = qsq - 2.0 * q @ b.T + np.sum(b * b, axis=1)[None, :]
        both = np.concatenate([best, np.maximum(d2, 0.0)], axis=1)
        best = np.partition(both, kk, axis=1)[:, :kk + 1]
    kth = np.sort(best, axis=1)[:, kk]
    return float(np.sqrt(np.median(kth)))


def load_config(name: str) -> dict:
    with open(os.path.join(HERE, "configs", f"{name}.json")) as f:
        return json.load(f)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("config")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    cfg = load_config(args.config)
    x = make(cfg, args.seed)
    eps = epsilon_for_avg_neighbors(x, cfg["avg_neighbors"])
    print(json.dumps({"config": args.config, "seed": args.seed,
                      "epsilon": eps}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
