"""The check of ε-range answers, and its control.

A range cell's queries are indexed rows' own vectors, so an answer for
row r is judged against the float64 ε-neighbourhood of ``x[r]``
(``reference.Reference``). Each distinct answer of the window is judged
once:

- ``recall``: true neighbours returned over true neighbours, on up to
  ``check_rows`` distinct answered rows sampled by the seed;
- ``beyond_band``: returned (query, id) pairs whose float64 distance
  exceeds ε by more than ``reference.REL_TOL`` of ε, over every answer;
- ``max_d2_err``: the widest |d² − d64²| / ε² over every returned pair;
- ``malformed``: ids repeated within one answer, ids out of range, and
  answers whose ids and distances differ in length. A row's own id is a
  valid answer.

Each number is judged against the configuration's ``limits`` by
``reference.judge``.

    python3 benchmarks/chip/range_check.py --workload <name> --seeds 1 2 3

puts ``control.range_answers`` (the reference in three bf16 passes) in
the program's place on each seed's sampled rows and prints one JSON line
per seed with the judged numbers and ``correct``; it has to come out not
correct.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


def _malformed(ids: np.ndarray, dists: np.ndarray, n: int) -> int:
    ids = np.asarray(ids, np.int64)
    bad = int(np.sum((ids < 0) | (ids >= n)))
    bad += int(ids.size - np.unique(ids).size)
    return bad + int(ids.size != np.asarray(dists).size)


def compare(ref, answers: list, rows: np.ndarray) -> dict:
    """Numbers of ``answers``, a list of (row, ids, dists), one per
    distinct answer; recall over the answers of ``rows``."""
    import reference
    n = ref.x.shape[0]
    malformed, pairs, dists = 0, [], []
    for row, ids, d in answers:
        ids = np.asarray(ids, np.int64)
        malformed += _malformed(ids, d, n)
        ok = (ids >= 0) & (ids < n)
        if ids.size == np.asarray(d).size:
            pairs.append(np.stack([np.full(int(ok.sum()), row), ids[ok]], 1))
            dists.append(np.asarray(d, np.float64)[ok])
    p = (np.concatenate(pairs) if pairs else np.zeros((0, 2), np.int64))
    d = np.concatenate(dists) if dists else np.zeros(0)
    d64 = ref.pair_dist(p)
    want = dict(zip(rows.tolist(), ref.neighbors(ref.x[rows])))
    found = total = 0
    for row, ids, _ in answers:
        if row in want:
            found += int(np.isin(want[row], ids).sum())
            total += int(want[row].size)
    return {
        "recall": found / total if total else 1.0,
        "beyond_band": int(np.sum(d64 > ref.eps * (1.0 + reference.REL_TOL))),
        "max_d2_err": (float(np.abs(d ** 2 - d64 ** 2).max() / ref.eps ** 2)
                       if d.size else 0.0),
        "malformed": malformed,
        "pairs": int(p.shape[0]),
    }


def recall_rows(answered: list, count: int, seed: int) -> np.ndarray:
    """Up to ``count`` of the distinct answered rows, sampled by ``seed``."""
    import reference
    rows = np.unique(np.asarray(answered, np.int64))
    return rows[reference.sample_rows(rows.size, count, seed)]


def check(x: np.ndarray, eps: float, answers: list, limits: dict,
          count: int, seed: int) -> tuple[dict, dict]:
    """The judged numbers of a window's distinct answers, and the raw
    numbers."""
    import reference
    ref = reference.Reference(x, eps)
    rows = recall_rows([a[0] for a in answers], count, seed)
    numbers = compare(ref, answers, rows)
    return reference.judge(numbers, limits), numbers


def control_check(cell, seed: int) -> dict:
    """The control's judged numbers for one seed of ``cell``: the three
    bf16 pass answers of ``check_rows`` sampled rows."""
    import control
    import datagen
    import reference
    cfg = cell.config
    x = datagen.make(cfg, seed)
    eps = cfg.get("epsilon")
    if eps is None:
        eps = datagen.epsilon_for_avg_neighbors(x, cfg["avg_neighbors"])
    rows = reference.sample_rows(x.shape[0], cell.traffic["check_rows"],
                                 seed)
    answers = [(r, ids, d) for r, (ids, d) in
               zip(rows.tolist(), control.range_answers(x, x[rows], eps))]
    numbers = compare(reference.Reference(x, eps), answers, rows)
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
    cell = cells.load_cell(cells.load_benchmark(ROOT), args.workload,
                           rehearse=args.rehearse)
    import jax
    dev = jax.devices()[0]
    for seed in args.seeds:
        judged = control_check(cell, seed)
        print(json.dumps({"workload": cell.name, "seed": seed,
                          "device": dev.device_kind,
                          "correct": all(c["ok"] for c in judged.values()),
                          "check": judged}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
