"""Run one benchmark cell once on the chip and print its result line.

    python3 benchmarks/chip/run.py --workload <name> --seed <n> \
        --seconds <s> --trace <0|1>

The cell, its configuration, traffic mix, the driver of the mix's kind and
the per-layer metric readers are found by name from ``BENCHMARK.json``
(``cells.py``). The run makes its data from ``--seed``, builds and warms
the index (set-up), measures for ``--seconds`` (a job that is under way
when the window closes runs to its end), then compares what the window
produced with the float64 reference.
With ``--trace 0`` the result holds the cell's end-to-end metrics; with
``--trace 1`` its per-layer metrics, read from the program's spans and
counters and from the device trace of the window.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, with ``--trace 1``
also ``breakdown``, and last ``check``, each compared number beside its
limit; the same numbers close standard error. Without a TPU, or with
fewer chips than the cell asks for, the run prints no result and exits
with 1. ``--rehearse`` runs the cell at the configuration's tiny
``rehearsal`` sizes on whatever JAX finds, the CPU included (tests).
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def _jax_setup(cell, rehearse: bool):
    """Import JAX for this cell: virtual devices for a rehearsal of a
    multi-chip cell; on the chip, the persistent compilation cache at a
    fixed path inside the checkout (or where ``JAX_COMPILATION_CACHE_DIR``
    says). A rehearsal keeps no cache: it would hold CPU programs."""
    if rehearse and cell.chips > 1 and "jax" not in sys.modules:
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                f"{flags} --xla_force_host_platform_device_count="
                f"{cell.chips}").strip()
    import jax
    if rehearse:
        jax.config.update("jax_enable_compilation_cache", False)
        return jax, None
    cache = (os.environ.get("JAX_COMPILATION_CACHE_DIR")
             or os.path.join(ROOT, ".jax_cache"))
    jax.config.update("jax_compilation_cache_dir", cache)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return jax, cache


def _per_layer(cell, outcome, device_kind: str) -> dict:
    with open(os.path.join(HERE, "peaks.json")) as f:
        peaks = json.load(f)["devices"]

    def peak(kind: str = device_kind) -> dict:
        if kind not in peaks:
            raise KeyError(f"device kind {kind!r} is not in peaks.json")
        return peaks[kind]

    ctx = {"spans": outcome.spans, "trace": outcome.trace,
           "counters": outcome.counters, "peak": peak}
    out = {}
    for m in cell.per_layer:
        v = cell.readers[m["name"]](ctx)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes on any backend, the CPU included")
    args = ap.parse_args(argv)
    sys.path.insert(0, HERE)
    import cells
    try:
        cell = cells.load_cell(cells.load_benchmark(ROOT), args.workload,
                               rehearse=args.rehearse, root=ROOT)
        drive = cells.load_driver(cell.traffic["kind"])
    except cells.CellError as e:
        log(f"error: {e}")
        return 2
    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        log(f"error: the program is not in this checkout ({src})")
        return 2
    sys.path.insert(0, src)
    jax, cache = _jax_setup(cell, args.rehearse)
    import drivers
    compiles = drivers.CompileCounter()
    devices = jax.devices()
    dev = devices[0]
    log(f"device: platform={dev.platform} kind={dev.device_kind} "
        f"count={len(devices)} jax={jax.__version__} cache={cache}")
    if dev.platform != "tpu" and not args.rehearse:
        log("error: JAX finds no TPU (use --rehearse for a CPU rehearsal)")
        return 1
    if len(devices) < cell.chips:
        log(f"error: the cell needs {cell.chips} chips, JAX finds "
            f"{len(devices)}")
        return 1
    kind = cell.traffic["kind"]
    log(f"cell {cell.name}: traffic kind {kind}, seed {args.seed}, "
        f"window {args.seconds}s, trace {args.trace}"
        + (", rehearsal sizes" if args.rehearse else ""))
    t0 = time.perf_counter()
    with tempfile.TemporaryDirectory(prefix="chipbench-") as workdir:
        run = drivers.Run(cell=cell, seed=args.seed, seconds=args.seconds,
                          trace=bool(args.trace), workdir=workdir,
                          devices=devices, compiles=compiles, log=log)
        outcome = drive(run)
        units = cell.metric_units
        if args.trace:
            metrics = _per_layer(cell, outcome, dev.device_kind)
        else:
            metrics = {"setup_s": {"value": outcome.setup_s,
                                   "unit": units["setup_s"]}}
            for m in cell.end_to_end:
                v = outcome.metrics.get(m["name"])
                if m["name"] != "setup_s" and v is not None:
                    metrics[m["name"]] = {"value": float(v),
                                          "unit": m["unit"]}
    log(compiles.line("setup"))
    log(compiles.line("window"))
    log(f"run: setup {outcome.setup_s:.3f}s, whole run "
        f"{time.perf_counter() - t0:.3f}s, memory peak "
        f"{outcome.memory_peak_bytes} bytes")
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices),
              "memory_peak_bytes": outcome.memory_peak_bytes}
    result = {"correct": bool(outcome.check) and all(
                  c["ok"] for c in outcome.check.values()),
              "attempted": outcome.attempted, "failed": outcome.failed,
              "metrics": metrics, "device": device}
    if args.trace and outcome.trace is not None:
        tr = outcome.trace
        top = sorted(tr["programs"].items(), key=lambda kv: -kv[1])[:6]
        log(f"trace: window {tr['window_s']!r}s, busy per chip "
            f"{tr['per_chip_busy_s']!r}s, device time by program: "
            + ", ".join(f"{k} {v!r}s" for k, v in top))
        device["busy_s"] = outcome.trace["busy_s"]
        device["window_s"] = outcome.trace["window_s"]
        result["breakdown"] = {"device_ops": outcome.trace["device_ops"],
                               "idle_gaps": outcome.trace["idle_gaps"]}
    result["check"] = outcome.check
    for name, c in outcome.check.items():
        rel = ">=" if name == "recall" else "<="
        log(f"check {name}: {c['value']!r} (limit {rel} {c['limit']!r}) "
            f"{'ok' if c['ok'] else 'FAILED'}")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
