"""The control of ``correct``: the reference's own answers, computed one
precision below the program's and judged by the same comparison. It has
to come out not correct.

The program states float32 distances with every distance matmul at
``Precision.HIGHEST``; the step below is three bf16 passes (``high``).
``d2_three_pass`` spells those passes out (bf16 high and low halves of
each operand, every product but low x low, accumulated in float32), so
that the control computes the same thing on the chip and on the CPU, where
a matmul ignores the precision it is asked for.

    python3 benchmarks/chip/control.py --workload <name> --seeds 1 2 3

makes each seed's data as a run does, puts the control in the program's
place, and prints one JSON line
per seed with the compared numbers, each beside its limit, and
``correct``. The limit of ``max_d2_err`` lies between the program's
readings over a dozen seeds and this control's.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
_BLOCK = 1 << 15


def d2_three_pass(q, b):
    """Squared distances (Q, d) x (B, d) -> (Q, B) float32, the products
    in three bf16 passes."""
    import jax
    import jax.numpy as jnp

    def bf16(a):
        # reduce_precision first: XLA may drop an f32 -> bf16 -> f32 round
        # trip as excess precision, which on the chip made every low half
        # zero (one pass, not three)
        return jax.lax.reduce_precision(a, exponent_bits=8, mantissa_bits=7)

    def halves(a):
        hi = bf16(a)
        return (hi.astype(jnp.bfloat16),
                bf16(a - hi).astype(jnp.bfloat16))

    def mm(u, v):
        return jnp.matmul(u, v.T, preferred_element_type=jnp.float32)

    qh, ql = halves(q)
    bh, bl = halves(b)
    ab = mm(qh, bh) + mm(qh, bl) + mm(ql, bh)
    d2 = (jnp.sum(q * q, axis=1)[:, None] - 2.0 * ab
          + jnp.sum(b * b, axis=1)[None, :])
    return jnp.maximum(d2, 0.0)


def range_answers(x: np.ndarray, q: np.ndarray, eps: float) -> list:
    """Per query, (ids, distances) within ε by the three-pass distances,
    thresholded in float32 as the program does."""
    import jax
    import jax.numpy as jnp
    f = jax.jit(d2_three_pass)
    eps2 = np.float32(eps * eps)
    qd = jnp.asarray(q, jnp.float32)
    ids = [[] for _ in range(q.shape[0])]
    dd = [[] for _ in range(q.shape[0])]
    for r0 in range(0, x.shape[0], _BLOCK):
        blk = x[r0:r0 + _BLOCK]
        if blk.shape[0] < _BLOCK:
            blk = np.concatenate([blk, np.full(
                (_BLOCK - blk.shape[0], x.shape[1]), 1e6, np.float32)])
        d2 = np.asarray(f(qd, jnp.asarray(blk)))
        rows, cols = np.nonzero(d2 <= eps2)
        for i in np.unique(rows):
            sel = rows == i
            ids[i].append(cols[sel] + r0)
            dd[i].append(np.sqrt(d2[i, cols[sel]]))
    return [(np.concatenate(a) if a else np.zeros(0, np.int64),
             np.concatenate(b) if b else np.zeros(0, np.float32))
            for a, b in zip(ids, dd)]


def join_pairs(x: np.ndarray, rows: np.ndarray, eps: float):
    """The control's self-join answer on ``rows``: every pair (r, j),
    j != r, within ε by the three-pass distances, as (low, high)."""
    ans = range_answers(x, x[rows], eps)
    pairs, dists = [], []
    for r, (ids, d) in zip(rows.tolist(), ans):
        keep = ids != r
        ids, d = ids[keep], d[keep]
        pairs.append(np.stack([np.minimum(r, ids), np.maximum(r, ids)], 1))
        dists.append(d)
    p = np.concatenate(pairs).astype(np.int64)
    d = np.concatenate(dists).astype(np.float32)
    _, first = np.unique(p[:, 0] * x.shape[0] + p[:, 1], return_index=True)
    return p[first], d[first]


def control_check(cell, seed: int) -> dict:
    """The control's judged numbers for one seed of ``cell``."""
    import datagen
    import reference
    cfg, traffic = cell.config, cell.traffic
    x = datagen.make(cfg, seed)
    eps = cfg.get("epsilon")
    if eps is None:
        eps = datagen.epsilon_for_avg_neighbors(x, cfg["avg_neighbors"])
    ref = reference.Reference(x, eps)
    rows = reference.sample_rows(x.shape[0], traffic["check_rows"], seed)
    p, d = join_pairs(x, rows, eps)
    numbers = reference.compare_join(reference.JoinTruth(ref, rows), p, d)
    return reference.judge(numbers, cfg["limits"])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    sys.path.insert(0, HERE)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    import cells
    bench = cells.load_benchmark(ROOT)
    cell = cells.load_cell(bench, args.workload, rehearse=args.rehearse)
    import jax
    dev = jax.devices()[0]
    for seed in args.seeds:
        check = control_check(cell, seed)
        print(json.dumps({"workload": cell.name, "seed": seed,
                          "device": dev.device_kind,
                          "correct": all(c["ok"] for c in check.values()),
                          "check": check}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
