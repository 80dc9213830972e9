"""The device trace of a run's window, and its reduction to numbers.

``traced`` records the window with the JAX profiler (Python tracing off,
so the host is not slowed call by call) and marks the window with one host
annotation, ``chipbench.window``, whose start is also recorded as an
instant in the program's own span tracer: that pair puts the program's
spans on the profiler's clock.

``load`` turns the profiler's ``.xplane.pb`` into plain lists, and
``reduce`` computes from them, over the window:

- ``busy_s``: the union of the intervals in which an operation ran on a
  device (the ``XLA Ops`` line of each ``/device:TPU:<i>`` plane), averaged
  over the cell's chips; ``window_s``: the window's length;
- ``programs``: device time per compiled program (``XLA Modules`` line),
  summed over the cell's chips;
- ``device_ops``: the ten operations that took most device time;
- ``idle_gaps``: device 0's idle time within the window, each gap given to
  the host span that overlaps it most (the program's spans first, then the
  runtime's own host events), summed by span name, the ten largest.
"""
from __future__ import annotations

import contextlib
import glob
import os
import re

import numpy as np

WINDOW = "chipbench.window"
_DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
_GAPS_ATTRIBUTED = 2000


@contextlib.contextmanager
def traced(logdir: str, tracer):
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(logdir, profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation(WINDOW):
            tracer.instant(WINDOW)
            yield
    finally:
        jax.profiler.stop_trace()


def load(logdir: str) -> dict:
    """The newest profile under ``logdir`` as {"planes": [{"name",
    "lines": [{"name", "events": [[name, start_ns, dur_ns], ...]}]}]}."""
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no profile under {logdir}")
    pd = ProfileData.from_file(max(paths, key=os.path.getmtime))
    planes = []
    for plane in pd.planes:
        lines = [{"name": line.name,
                  "events": [[e.name, float(e.start_ns), float(e.duration_ns)]
                             for e in line.events]}
                 for line in plane.lines]
        planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def _union(iv: np.ndarray) -> np.ndarray:
    """Merge (start, end) rows into disjoint sorted intervals."""
    if not iv.size:
        return iv.reshape(0, 2)
    iv = iv[np.argsort(iv[:, 0], kind="stable")]
    out = []
    s, e = iv[0]
    for a, b in iv[1:]:
        if a <= e:
            e = max(e, b)
        else:
            out.append((s, e))
            s, e = a, b
    out.append((s, e))
    return np.asarray(out, np.float64)


def _clip(ev: list, w0: float, w1: float) -> tuple[list, np.ndarray]:
    names = [e[0] for e in ev]
    iv = np.asarray([[e[1], e[1] + e[2]] for e in ev],
                    np.float64).reshape(-1, 2)
    iv = np.clip(iv, w0, w1)
    keep = iv[:, 1] > iv[:, 0]
    return [n for n, k in zip(names, keep) if k], iv[keep]


def _line(plane: dict, name: str) -> list:
    for line in plane["lines"]:
        if line["name"] == name:
            return line["events"]
    return []


def _module_name(name: str) -> str:
    return re.sub(r"\(\d+\)$", "", name)


def window_bounds(trace: dict) -> tuple[float, float]:
    for plane in trace["planes"]:
        if _DEVICE_PLANE.match(plane["name"]):
            continue
        for line in plane["lines"]:
            for name, start, dur in line["events"]:
                if name == WINDOW:
                    return start, start + dur
    raise ValueError(f"the trace holds no {WINDOW} annotation")


def _attribute(gaps: np.ndarray, host: list) -> dict:
    """Idle seconds by host span name (module docstring)."""
    out: dict = {}
    order = np.argsort(gaps[:, 0] - gaps[:, 1])  # longest first
    rest = order[_GAPS_ATTRIBUTED:]
    if rest.size:
        out["(shorter gaps)"] = float(np.sum(gaps[rest, 1] - gaps[rest, 0])
                                      / 1e9)
    tiers = [(names, iv) for names, iv in host if iv.size]
    for g in order[:_GAPS_ATTRIBUTED]:
        g0, g1 = gaps[g]
        label = "(no host span)"
        for names, iv in tiers:
            ov = np.minimum(iv[:, 1], g1) - np.maximum(iv[:, 0], g0)
            if ov.max() > 0:
                best = np.flatnonzero(ov == ov.max())
                i = best[np.argmin(iv[best, 1] - iv[best, 0])]
                label = names[i]
                break
        out[label] = out.get(label, 0.0) + (g1 - g0) / 1e9
    return out


def reduce(trace: dict, chips: int, spans: list | None = None) -> dict:
    """Numbers of the window (module docstring). ``spans`` are the
    program's host spans as (name, start_ns, dur_ns) on the profiler's
    clock."""
    w0, w1 = window_bounds(trace)
    devs = sorted((int(_DEVICE_PLANE.match(p["name"]).group(1)), p)
                  for p in trace["planes"] if _DEVICE_PLANE.match(p["name"]))
    devs = [p for _, p in devs][:chips]
    busy, programs, ops = [], {}, {}
    gaps = np.zeros((0, 2))
    for i, plane in enumerate(devs):
        names, iv = _clip(_line(plane, "XLA Ops"), w0, w1)
        u = _union(iv)
        busy.append(float(np.sum(u[:, 1] - u[:, 0])) / 1e9)
        for n, (a, b) in zip(names, iv):
            ops[n] = ops.get(n, 0.0) + (b - a) / 1e9
        mnames, miv = _clip(_line(plane, "XLA Modules"), w0, w1)
        for n, (a, b) in zip(mnames, miv):
            n = _module_name(n)
            programs[n] = programs.get(n, 0.0) + (b - a) / 1e9
        if i == 0:
            edges = np.concatenate([[w0], u.ravel(), [w1]])
            gaps = edges.reshape(-1, 2)
            gaps = gaps[gaps[:, 1] > gaps[:, 0]]
    host_names, host_iv = [], []
    for plane in trace["planes"]:
        if _DEVICE_PLANE.match(plane["name"]):
            continue
        for line in plane["lines"]:
            for name, start, dur in line["events"]:
                if name != WINDOW and dur > 0:
                    host_names.append(name)
                    host_iv.append((start, start + dur))
    tiers = []
    if spans:
        tiers.append(([s[0] for s in spans],
                      np.asarray([[s[1], s[1] + s[2]] for s in spans],
                                 np.float64)))
    tiers.append((host_names, np.asarray(host_iv, np.float64).reshape(-1, 2)))
    idle = _attribute(gaps, tiers) if devs else {}

    def top(d: dict) -> list:
        return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])
                ][:10]

    return {
        "chips": len(devs),
        "window_s": (w1 - w0) / 1e9,
        "busy_s": float(np.mean(busy)) if busy else 0.0,
        "per_chip_busy_s": busy,
        "programs": programs,
        "device_ops": top(ops),
        "idle_gaps": top(idle),
    }


def spans_on_profiler_clock(events: list, trace: dict) -> list:
    """The program tracer's complete spans as (name, start_ns, dur_ns) on
    the profiler's clock, aligned by the window's instant."""
    sync = [e for e in events if e["name"] == WINDOW]
    if not sync:
        return []
    w0, _ = window_bounds(trace)
    off = w0 - sync[0]["ts"] * 1e3
    return [(e["name"], e["ts"] * 1e3 + off, e["dur"] * 1e3)
            for e in events if e.get("ph") == "X"]
