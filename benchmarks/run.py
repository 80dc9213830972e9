"""Benchmark harness — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows (benchmarks/common.emit).
``REPRO_BENCH_SMALL=1`` runs each at 1/10 scale (CI smoke).

``--json-out DIR`` additionally writes one ``BENCH_<figure>.json`` per
executed module — the emitted rows, any trace-derived stats the module
attached (``common.attach_stats``), the config fingerprint, elapsed wall
time and pass/fail status. CI archives these per commit: the perf
trajectory of the repo, one point per figure per revision.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
import traceback

import numpy as np

from repro.launch.compile_cache import enable_compile_cache

from benchmarks import (common, fig7_baselines, fig8_recall, fig9_memory,
                        fig10_threshold, fig11_buckets, fig12_breakdown,
                        fig13_crossjoin, fig14_fragmentation, fig15_io,
                        fig17_ablation, fig18_pruning, fig19_pipeline,
                        fig20_striping, fig21_online, fig22_scheduler,
                        fig23_device_pipeline, fig24_planner,
                        fig25_resilience, fig26_live, fig27_replication,
                        obs_trace, randomness)

MODULES = [
    ("fig7_baselines", fig7_baselines),
    ("fig8_recall", fig8_recall),
    ("fig9_memory", fig9_memory),
    ("fig10_threshold", fig10_threshold),
    ("fig11_buckets", fig11_buckets),
    ("fig12_breakdown", fig12_breakdown),
    ("fig13_crossjoin", fig13_crossjoin),
    ("fig14_fragmentation", fig14_fragmentation),
    ("fig15_io", fig15_io),
    ("fig17_ablation", fig17_ablation),
    ("fig18_pruning", fig18_pruning),
    ("fig19_pipeline", fig19_pipeline),
    ("fig20_striping", fig20_striping),
    ("fig21_online", fig21_online),
    ("fig22_scheduler", fig22_scheduler),
    ("fig23_device_pipeline", fig23_device_pipeline),
    ("fig24_planner", fig24_planner),
    ("fig25_resilience", fig25_resilience),
    ("fig26_live", fig26_live),
    ("fig27_replication", fig27_replication),
    ("obs_trace", obs_trace),
    ("randomness", randomness),
]


def _json_default(o):
    if isinstance(o, (np.integer,)):
        return int(o)
    if isinstance(o, (np.floating,)):
        return float(o)
    if isinstance(o, np.ndarray):
        return o.tolist()
    return str(o)


def _git_sha() -> str | None:
    """Commit the record was produced at, best-effort (regress.py prints
    it in diffs; records from exported tarballs just omit it)."""
    import subprocess
    try:
        out = subprocess.run(
            ["git", "rev-parse", "HEAD"], capture_output=True, text=True,
            cwd=os.path.dirname(os.path.abspath(__file__)), timeout=10)
    except (OSError, subprocess.TimeoutExpired):
        return None
    sha = out.stdout.strip()
    return sha if out.returncode == 0 and sha else None


def _write_record(json_out: str, name: str, *, rows, stats, elapsed,
                  status, fingerprint) -> str:
    rec = {
        "figure": name,
        "status": status,
        "elapsed_s": elapsed,
        "wall_s": elapsed,
        "seed": common.BENCH_SEED,
        "git_sha": _git_sha(),
        "timestamp": time.strftime("%Y-%m-%dT%H:%M:%S%z"),
        "fingerprint": fingerprint,
        "rows": rows,
        "trace_stats": stats,
    }
    path = os.path.join(json_out, f"BENCH_{name}.json")
    with open(path, "w") as f:
        json.dump(rec, f, indent=2, default=_json_default)
    return path


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("only", nargs="?", default=None,
                    help="substring filter on module names")
    ap.add_argument("--json-out", metavar="DIR", default=None,
                    help="write per-figure BENCH_<figure>.json records "
                         "into DIR (perf-trajectory pipeline)")
    args = ap.parse_args()
    enable_compile_cache(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

    fingerprint = None
    if args.json_out:
        os.makedirs(args.json_out, exist_ok=True)
        fingerprint = common.config_fingerprint()

    failures = []
    for name, mod in MODULES:
        if args.only and args.only not in name:
            continue
        t0 = time.perf_counter()
        print(f"# === {name} ===", flush=True)
        common.set_figure(name)
        status = "ok"
        try:
            mod.main()
        except Exception:
            failures.append(name)
            status = "error"
            traceback.print_exc()
        elapsed = time.perf_counter() - t0
        print(f"# {name} done in {elapsed:.1f}s", flush=True)
        if args.json_out:
            path = _write_record(
                args.json_out, name,
                rows=common.COLLECTED.get(name, []),
                stats=common.TRACE_STATS.get(name, {}),
                elapsed=elapsed, status=status, fingerprint=fingerprint)
            print(f"# wrote {path}", flush=True)
    if failures:
        print(f"# FAILED: {failures}")
        sys.exit(1)


if __name__ == "__main__":
    main()
