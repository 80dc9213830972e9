"""Plain float64 reference for ε-range answers, and the comparison that
decides a run's ``correct``.

The reference imports nothing of the program: it takes the benchmark's own
data (``datagen``) and ε, and finds ε-neighbourhoods by brute force in
float64, over row blocks so that 1M x 256 fits. It is a copy of the
bring-up's ``chip_smoke.Reference`` and ``check_join_rows``, extended to
every returned pair.

Each comparison returns numbers, each beside its limit from the
configuration's ``limits``:

- ``recall``: truth pairs found over truth pairs, on the sampled rows;
  the configuration's own recall target is its limit;
- ``beyond_band``: returned pairs whose float64 distance exceeds ε by more
  than ``REL_TOL`` of ε (a pair within the band may fall either way under
  float32 rounding); limit 0;
- ``max_d2_err``: the widest gap between a returned distance and float64,
  as |d² − d64²| / ε², over every returned pair; the number a lower
  precision moves;
- ``malformed``: self pairs, repeated pairs, ids out of range, and pairs
  not in (low, high) order; limit 0.
"""
from __future__ import annotations

import numpy as np

REL_TOL = 1e-3
_BLOCK = 1 << 16
_QBLOCK = 256
# float32 d² of a query and a row is off by at most about
# dim * 2**-24 of their squared norms; this keeps a hundredfold margin
_F32_SLACK = 1e-3


class Reference:
    """Exact float64 ε-neighbourhoods against the data ``x``."""

    def __init__(self, x: np.ndarray, eps: float):
        self.x = x
        self.eps = float(eps)
        self.sq = np.empty(x.shape[0], np.float64)
        for r0 in range(0, x.shape[0], _BLOCK):
            b = x[r0:r0 + _BLOCK].astype(np.float64)
            self.sq[r0:r0 + b.shape[0]] = np.sum(b * b, axis=1)

    def neighbors(self, q: np.ndarray) -> list[np.ndarray]:
        """Per query row, the sorted ids within ε in float64.

        A float32 pass over each row block keeps every row whose d² could
        be within ε² (its rounding error is far below ``_F32_SLACK`` of
        the two squared norms), and the float64 difference decides."""
        q64 = np.asarray(q, np.float64)
        eps2 = self.eps * self.eps
        parts: list[list[np.ndarray]] = [[] for _ in range(q.shape[0])]
        for q0 in range(0, q64.shape[0], _QBLOCK):
            qb = q64[q0:q0 + _QBLOCK]
            q32 = qb.astype(np.float32)
            qsq = np.sum(qb * qb, axis=1).astype(np.float32)[:, None]
            for r0 in range(0, self.x.shape[0], _BLOCK):
                b = self.x[r0:r0 + _BLOCK]
                bsq = self.sq[None, r0:r0 + b.shape[0]].astype(np.float32)
                d2 = qsq - 2.0 * (q32 @ b.astype(np.float32).T) + bsq
                rows, cols = np.nonzero(d2 <= eps2 + _F32_SLACK * (qsq + bsq))
                exact = np.sum((qb[rows] - b[cols].astype(np.float64)) ** 2,
                               axis=1) <= eps2
                rows, cols = rows[exact], cols[exact]
                for i in np.unique(rows):
                    parts[q0 + i].append(cols[rows == i] + r0)
        return [np.concatenate(p) if p else np.zeros(0, np.int64)
                for p in parts]

    def dist(self, a: np.ndarray, b: np.ndarray) -> np.ndarray:
        """Float64 distances by direct difference, one per (a, b) row."""
        return np.sqrt(np.sum((np.asarray(a, np.float64)
                               - np.asarray(b, np.float64)) ** 2, axis=1))

    def pair_dist(self, pairs: np.ndarray) -> np.ndarray:
        out = np.empty(pairs.shape[0], np.float64)
        for i0 in range(0, pairs.shape[0], _BLOCK):
            p = pairs[i0:i0 + _BLOCK]
            out[i0:i0 + p.shape[0]] = self.dist(self.x[p[:, 0]],
                                                self.x[p[:, 1]])
        return out


def _d2_err(ref: Reference, d: np.ndarray, d64: np.ndarray) -> float:
    if not d.size:
        return 0.0
    return float(np.abs(np.asarray(d, np.float64) ** 2 - d64 ** 2).max()
                 / ref.eps ** 2)


def _malformed_pairs(pairs: np.ndarray, n: int) -> int:
    if not pairs.size:
        return 0
    a, b = pairs[:, 0].astype(np.int64), pairs[:, 1].astype(np.int64)
    bad = int(np.sum((a >= b) | (a < 0) | (b >= n)))
    keys = a * n + b
    return bad + int(keys.size - np.unique(keys).size)


def sample_rows(n: int, count: int, seed: int) -> np.ndarray:
    return np.sort(np.random.default_rng(seed).choice(
        n, size=min(count, n), replace=False))


class JoinTruth:
    """The float64 neighbourhoods of sampled rows, made once per run and
    used to judge every join of its window."""

    def __init__(self, ref: Reference, rows: np.ndarray):
        self.ref = ref
        self.rows = rows
        self.truth = [t[t != r] for r, t in
                      zip(rows, ref.neighbors(ref.x[rows]))]


def compare_join(jt: JoinTruth, pairs: np.ndarray,
                 dists: np.ndarray) -> dict:
    """Numbers of one join's whole result (module docstring)."""
    ref = jt.ref
    pairs = np.asarray(pairs, np.int64).reshape(-1, 2)
    n = ref.x.shape[0]
    malformed = _malformed_pairs(pairs, n)
    ok = (pairs[:, 0] >= 0) & (pairs[:, 1] < n) & (pairs[:, 0] < n) \
        & (pairs[:, 1] >= 0)
    d64 = ref.pair_dist(pairs[ok])
    d = np.asarray(dists)[ok]
    got = pairs[ok, 0] * n + pairs[ok, 1]
    want = np.concatenate([np.minimum(r, t) * n + np.maximum(r, t)
                           for r, t in zip(jt.rows.tolist(), jt.truth)]
                          + [np.zeros(0, np.int64)])
    found, total = int(np.isin(want, got).sum()), int(want.size)
    return {
        "recall": found / total if total else 1.0,
        "beyond_band": int(np.sum(d64 > ref.eps * (1.0 + REL_TOL))),
        "max_d2_err": _d2_err(ref, d, d64),
        "malformed": malformed,
        "pairs": int(pairs.shape[0]),
    }


def judge(numbers: dict, limits: dict) -> dict:
    """Each compared number beside its limit: ``recall`` must reach its
    limit, every other number must not pass it."""
    out = {}
    for name, limit in limits.items():
        if name not in numbers:
            continue
        v = numbers[name]
        ok = v >= limit if name == "recall" else v <= limit
        out[name] = {"value": v, "limit": limit, "ok": bool(ok)}
    return out


def worst(checks: list[dict]) -> dict:
    """The per-number worst over several judged results (several joins of
    one window): lowest recall, highest of the rest."""
    out: dict = {}
    for c in checks:
        for name, e in c.items():
            cur = out.get(name)
            if cur is None or (e["value"] < cur["value"] if name == "recall"
                               else e["value"] > cur["value"]):
                out[name] = dict(e)
    return out
