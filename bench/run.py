#!/usr/bin/env python3
"""Run one benchmark cell on the accelerator this process finds.

    python3 bench/run.py --workload mamba2.chat --seed 7 --seconds 40 --trace 0

The cell (``workloads`` in BENCHMARK.json) names a configuration file
(the models, as served) and a traffic file (the task schedule).  Set-up
builds one multi-tenant server, warms it by replaying the cell's own
schedule, and then measures one window: the schedule sized to
``--seconds``, run to its end.  ``--trace 0`` reports the cell's
end-to-end metrics, ``--trace 1`` traces the window with the profiler
and reports its per-layer metrics (each read by ``metrics/<name>.py``).
After the window the served tokens of a sample of tasks are checked
against the float32 references (check.py).

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, with ``--trace 1``
``breakdown``, and last ``checks``, each compared number beside its
limit.  Without an accelerator, or with fewer chips than the cell asks
for, it exits 3 and prints no result.
"""
from __future__ import annotations

import argparse
import gc
import importlib
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time
from types import SimpleNamespace as Window  # what the readers see of one window
from typing import Any, Callable, Dict, List, Optional

T_START = time.perf_counter()
BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
CACHE_HIT = "/jax/compilation_cache/cache_hits"


class NoChip(RuntimeError):
    pass


def load_json(rel: str) -> Any:
    with open(os.path.join(ROOT, rel)) as f:
        return json.load(f)


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


class CompileCounter:
    """Programs JAX builds (backend compiles, persistent-cache reads
    included) and persistent-cache hits, from jax.monitoring events."""

    def __init__(self):
        from jax import monitoring
        self.programs = 0
        self.seconds = 0.0
        self.cache_hits = 0
        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)

    def _duration(self, event: str, secs: float, **_) -> None:
        if event == BACKEND_COMPILE:
            self.programs += 1
            self.seconds += secs

    def _event(self, event: str, **_) -> None:
        if event == CACHE_HIT:
            self.cache_hits += 1


def devices(chips: int, require_chip: bool):
    import jax
    devs = jax.devices()
    if require_chip and (devs[0].platform == "cpu" or len(devs) < chips):
        raise NoChip(f"the cell needs {chips} accelerator chip(s); JAX "
                     f"found {len(devs)} {devs[0].platform} device(s)")
    return devs[:chips]


def metric_reader(name: str) -> Callable:
    path = os.path.join(BENCH, "metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


class Cell:
    """One cell's server, built and warmed in set-up, driven by windows."""

    def __init__(self, cell: Dict[str, Any], config: Dict[str, Any],
                 traffic: Dict[str, Any], seed: int,
                 require_chip: bool = True):
        import adapter
        cache_dir = adapter.enable_compile_cache()
        self.devs = devices(cell["chips"], require_chip)
        self.gen = importlib.import_module(
            f"generators.{traffic['generator']}")
        self.kind = self.devs[0].device_kind
        peaks = load_json("bench/peaks.json")
        if require_chip and self.kind not in peaks:
            raise KeyError(f"no peaks for device kind {self.kind!r} in "
                           f"bench/peaks.json")
        self.peaks = peaks.get(self.kind)
        self.config, self.traffic = config, traffic
        self.models = config["models"]
        self.rows = traffic["batch"]
        self.counter = CompileCounter()
        self.served = adapter.Served(self.models, batch=self.rows,
                                     max_len=traffic["max_len"],
                                     epoch_len=traffic["epoch_len"])
        warm = self.gen.tasks(traffic, warm=True)
        self.gen.assign_seeds(warm, seed, "warm")
        self.served.serve(warm)
        self.setup_s = time.perf_counter() - T_START
        log(f"set-up {self.setup_s:.3f} s: {self.counter.programs} programs "
            f"built ({self.counter.seconds:.3f} s, {self.counter.cache_hits} "
            f"persistent-cache hits; cache {cache_dir}); warm-up served "
            f"{len(warm)} tasks")

    def window(self, seed: int, seconds: float, trace: bool) -> Window:
        """Serve the window's schedule; with ``trace`` under the
        profiler, reduced to the numbers the readers take."""
        import jax
        import trace_reduce
        import work
        tasks = self.gen.tasks(self.traffic, seconds=seconds)
        self.gen.assign_seeds(tasks, seed, "window")
        built0 = self.counter.programs
        log_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
        if trace:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(log_dir, profiler_options=opts)
        try:
            with jax.profiler.TraceAnnotation(trace_reduce.WINDOW):
                t0 = time.perf_counter()
                result = self.served.serve(tasks)
                window_s = time.perf_counter() - t0
        finally:
            if trace:
                jax.profiler.stop_trace()
        built = self.counter.programs - built0
        peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                   for d in self.devs)
        log(f"window {window_s:.3f} s: {len(tasks)} tasks, {built} programs "
            f"built inside it; peak {peak} bytes")
        reduced = None
        if trace:
            names = load_json("bench/trace_names.json")
            try:
                reduced = trace_reduce.reduce(
                    trace_reduce.load(log_dir, names), names)
            finally:
                shutil.rmtree(log_dir, ignore_errors=True)
        return Window(tasks=tasks, records=result["tasks"],
                      host=result["host"], shed=result["shed"],
                      queued=result["queued"], rows=self.rows,
                      epoch_len=self.traffic["epoch_len"],
                      models=self.models, window_s=window_s,
                      programs_built=built, memory_peak_bytes=peak,
                      trace=reduced, peaks=self.peaks, work=work)

    def close(self) -> None:
        """Free the server and everything it holds on the device."""
        self.served.close()
        self.served = None
        gc.collect()


def failures(w: Window) -> List[str]:
    import check
    bad = check.served_failures(w.models, w.tasks, w.records, w.rows)
    bad += [f"{w.shed} tasks shed"] if w.shed else []
    bad += [f"{w.queued} tasks still queued"] if w.queued else []
    return bad


def judge(w: Window, seed: int, limits: Dict[str, float]):
    """``correct`` and the numbers compared, each beside its limit."""
    import check
    bad = failures(w)
    for f in bad:
        log(f"not served: {f}")
    numbers: Dict[str, float] = {}
    if not bad:
        t0 = time.perf_counter()
        numbers = check.gap_numbers(w.models, w.tasks, w.records,
                                    check.sample(w.tasks, seed))
        log(f"reference check {time.perf_counter() - t0:.3f} s")
    return check.judge(limits, numbers, bad)


def run_cell(cell: Dict[str, Any], config: Dict[str, Any],
             traffic: Dict[str, Any], metrics: List[Dict[str, Any]],
             seed: int, seconds: float, trace: bool,
             require_chip: bool = True) -> Dict[str, Any]:
    import check
    c = Cell(cell, config, traffic, seed, require_chip)
    w = c.window(seed, seconds, trace)
    devs, kind = c.devs, c.kind
    c.close()
    ok, checks = judge(w, seed, config["limits"])
    out_metrics: Dict[str, Dict[str, Any]] = {}
    for m in metrics:
        value = metric_reader(m["name"])(w)
        if value is not None:
            out_metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = {"platform": devs[0].platform, "kind": kind,
              "count": len(devs), "memory_peak_bytes": w.memory_peak_bytes}
    if trace:
        device["busy_s"] = w.trace["busy_s"]
        device["window_s"] = w.trace["window_s"]
    line = {"correct": ok, "attempted": len(w.tasks),
            "failed": len(check.served_failures(w.models, w.tasks, w.records,
                                                w.rows)) + w.shed,
            "metrics": out_metrics, "device": device}
    if trace:
        line["breakdown"] = w.trace["breakdown"]
    line["checks"] = checks
    return {"line": line, "setup_s": c.setup_s, "window_s": w.window_s}


def for_cell(cell_name: str, metrics: List[Dict[str, Any]]):
    return [m for m in metrics
            if cell_name in m.get("workloads", [cell_name])]


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    bench = load_json("BENCHMARK.json")
    cells = {c["name"]: c for c in bench["workloads"]}
    if args.workload not in cells:
        ap.error(f"unknown workload {args.workload!r}; have {sorted(cells)}")
    cell = cells[args.workload]
    conf = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    config = load_json(conf["file"])
    traffic = load_json(f"bench/traffic/{cell['traffic']}.json")
    if args.trace:
        metrics = for_cell(cell["name"], bench["per_layer"])
    else:
        metrics = [m for m in for_cell(cell["name"], bench["end_to_end"])
                   if m["name"] != "setup_s"]
    try:
        out = run_cell(cell, config, traffic, metrics, args.seed,
                       args.seconds, bool(args.trace))
    except NoChip as e:
        log(f"no result: {e}")
        return 3
    line = out["line"]
    if not args.trace:
        line["metrics"]["setup_s"] = {"value": out["setup_s"], "unit": "s"}
    for name, c in line["checks"].items():
        log(f"check {name}: {c['value']} (limit {c['limit']})")
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
