"""Reduce a profiler trace of the measured window to the numbers the
per-layer metrics read: device busy time (the union of device operation
intervals), device time per program and per kernel (and per kernel call
shape, its label, so that a reader can price each call by its own
shape), and a breakdown of the operations that took most time and of the
longest idle gaps.

``load`` reads the ``.xplane.pb`` the JAX profiler writes into a plain
record: per device plane a table of operation names (the HLO instruction
text the trace carries), the operations as ``[name index, start ns,
duration ns]`` and the program (module) executions as ``[name, start,
duration]``; and the host events as ``[thread, name, start, duration]``.
``reduce`` works on that record alone, so it is tested on a small
recorded trace (tests/test_trace_reduce.py).  Names are matched by the
patterns in ``trace_names.json``.
"""
from __future__ import annotations

import glob
import os
import re
from collections import defaultdict
from typing import Any, Dict, List, Optional, Tuple

NS = 1e-9
WINDOW = "bench.window"
_CONTROL = re.compile(r"condition=%|branch_computations=|true_computation=")


def load(log_dir: str, names: Dict[str, Any]) -> Dict[str, Any]:
    """The newest ``.xplane.pb`` under ``log_dir`` as a plain record."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    data = ProfileData.from_file(paths[-1])
    dev_re = re.compile(names["device_plane"])
    out: Dict[str, Any] = {"devices": [], "host": []}
    for plane in data.planes:
        if dev_re.search(plane.name):
            index: Dict[str, int] = {}
            dev = {"name": plane.name, "op_names": [], "ops": [],
                   "modules": []}
            for line in plane.lines:
                if line.name in names["op_lines"]:
                    for ev in line.events:
                        i = index.setdefault(ev.name, len(index))
                        if i == len(dev["op_names"]):
                            dev["op_names"].append(ev.name)
                        dev["ops"].append([i, ev.start_ns, ev.duration_ns])
                elif line.name in names["module_lines"]:
                    for ev in line.events:
                        dev["modules"].append([ev.name, ev.start_ns,
                                               ev.duration_ns])
            out["devices"].append(dev)
        elif plane.name.startswith("/host"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.duration_ns > 0:
                        out["host"].append([line.name, ev.name,
                                            ev.start_ns, ev.duration_ns])
    return out


def merge(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Union of [start, end) intervals, sorted and disjoint."""
    out: List[Tuple[float, float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def window(trace: Dict[str, Any]) -> Tuple[float, float]:
    """The traced window: from the start of the benchmark's own host span
    around the measured window (else of the device operations) to the
    end of the last device operation inside it.  The device tracer keeps
    a bounded number of events, so a long window's trace can stop short
    of the window's end; the part after its last event is not seen."""
    ops = [(s, s + d) for dev in trace["devices"] for _, s, d in dev["ops"]]
    spans = [(s, s + d) for _, n, s, d in trace["host"] if n == WINDOW]
    if spans:
        lo, hi = min(s for s, _ in spans), max(e for _, e in spans)
    else:
        lo, hi = min(s for s, _ in ops), max(e for _, e in ops)
    return lo, min(hi, max(e for s, e in ops if s < hi))


def label(text: str) -> str:
    """``planned_matmul bf16[128,11008]`` from an HLO instruction's
    text: its name without the number, and its result type without the
    layout."""
    name, _, rest = text.partition(" = ")
    name = re.sub(r"\.\d+$", "", name.lstrip("%"))
    result = re.sub(r"\{[^}]*\}", "", rest.split(" ", 1)[0])
    return f"{name} {result}".strip()[:120]


def _gap_label(host, s: float, e: float) -> str:
    """What the host was doing in a device idle gap: the host event that
    overlaps it most (the window span itself excepted)."""
    best, found = 0.0, "no host event recorded"
    for _, name, hs, hd in host:
        if name == WINDOW:
            continue
        ov = min(e, hs + hd) - max(s, hs)
        if ov > best:
            best, found = ov, name[:120]
    return found


def reduce(trace: Dict[str, Any], names: Dict[str, Any],
           top: int = 10) -> Dict[str, Any]:
    lo, hi = window(trace)
    devs = trace["devices"]
    if not devs:
        raise ValueError("the trace holds no device plane")
    prog_re = {k: re.compile(p) for k, p in names["programs"].items()}
    kern_re = {k: re.compile(p) for k, p in names["kernels"].items()}
    programs = {k: [0.0, 0] for k in prog_re}
    kernels = {k: [0.0, 0] for k in kern_re}
    shapes: Dict[str, Dict[str, List[float]]] = {k: {} for k in kern_re}
    op_time: Dict[str, float] = defaultdict(float)
    busy_total, gaps = 0.0, []
    for dev in devs:
        texts = dev["op_names"]
        kinds: List[Optional[str]] = []
        for text in texts:
            kinds.append(next((k for k, r in kern_re.items()
                               if r.search(text)), None))
        control = [bool(_CONTROL.search(t)) for t in texts]
        labels = [label(t) for t in texts]
        spans = []
        for i, s, d in dev["ops"]:
            if s + d <= lo or s >= hi:
                continue
            spans.append((max(s, lo), min(s + d, hi)))
            if not control[i]:
                op_time[labels[i]] += d
            if kinds[i] is not None:
                kernels[kinds[i]][0] += d * NS
                kernels[kinds[i]][1] += 1
                by = shapes[kinds[i]].setdefault(labels[i], [0.0, 0])
                by[0] += d * NS
                by[1] += 1
        busy = merge(spans)
        busy_total += sum(e - s for s, e in busy)
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        gaps += [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                 if edges[i + 1] > edges[i]]
        for name, s, d in dev["modules"]:
            if s + d <= lo or s >= hi:
                continue
            for k, r in prog_re.items():
                if r.search(name):
                    programs[k][0] += d * NS
                    programs[k][1] += 1
    n = len(devs)
    gaps.sort(key=lambda g: g[1] - g[0], reverse=True)
    return {
        "window_s": (hi - lo) * NS,
        "busy_s": busy_total * NS / n,
        "programs": {k: {"seconds": v[0] / n, "count": v[1] / n}
                     for k, v in programs.items()},
        "kernels": {k: {"seconds": v[0] / n, "count": v[1] / n,
                        "shapes": {lab: [sec / n, cnt / n] for lab, (sec, cnt)
                                   in shapes[k].items()}}
                    for k, v in kernels.items()},
        "breakdown": {
            "device_ops": [[k, v * NS / n] for k, v in sorted(
                op_time.items(), key=lambda kv: -kv[1])[:top]],
            "idle_gaps": [[_gap_label(trace["host"], s, e), (e - s) * NS]
                          for s, e in gaps[:top]],
        },
    }
