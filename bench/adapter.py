"""The one module of the benchmark that imports the system under test.

It builds the served models from a configuration file, one
``MultiTenantServer`` per run, and turns the benchmark's task list into
``TenantSpec`` arrivals.  It uses only the server's public surface: the
constructor, ``enqueue``, ``run`` and the keys of the result dict.
"""
from __future__ import annotations

import os
import sys
from typing import Any, Dict, List

import families

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SRC = os.path.join(ROOT, "src")


def enable_compile_cache() -> str:
    """Persistent compile cache in the checkout (or where
    ``JAX_COMPILATION_CACHE_DIR`` points), every program cached however
    short its compile, so a second run of a cell compiles nothing."""
    if not os.path.isdir(os.path.join(SRC, "repro")):
        raise FileNotFoundError(f"no src/repro under {ROOT}: run from a "
                                f"checkout of the repository")
    if SRC not in sys.path:
        sys.path.insert(0, SRC)
    from repro.launch.env import enable_compile_cache as enable
    path = enable()
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def arch_config(entry: Dict[str, Any]):
    """The program's ArchConfig for one model entry of a configuration
    file.  Widths and depth come from the entry's ``config`` (the keys
    as run) through its family's map (``families/<family>.py``), the
    program's name for the model from ``program_name``."""
    from repro.models.base import ArchConfig
    return ArchConfig(name=entry["program_name"],
                      **families.of(entry).arch_fields(entry["config"]))


class Served:
    """One multi-tenant server built in set-up and driven by task lists.

    A task is a dict with ``model`` (a role of the configuration),
    ``arrive`` (logical steps after the run starts), ``prompt``,
    ``decode`` (tokens decoded after the first), ``param_seed`` and
    ``prompt_seed``."""

    def __init__(self, models: Dict[str, Dict[str, Any]], batch: int,
                 max_len: int, epoch_len: int):
        from repro.launch.serve import MultiTenantServer
        self.archs = {role: arch_config(e) for role, e in models.items()}
        self.batch = batch
        self.server = MultiTenantServer([], batch=batch, max_len=max_len,
                                        epoch_len=epoch_len)

    def serve(self, tasks: List[Dict[str, Any]]) -> Dict[str, Any]:
        """Queue the tasks, run the server until every one has departed,
        and return the result with one record per task, in task order."""
        from repro.sim.driver import TenantSpec
        specs = [TenantSpec(self.archs[t["model"]],
                            arrive_at=float(t["arrive"]),
                            n_inferences=t["decode"],
                            prompt_len=t["prompt"],
                            param_seed=t["param_seed"],
                            prompt_seed=t["prompt_seed"])
                 for t in tasks]
        self.server.enqueue(specs)
        out = self.server.run()
        # the server admits in arrival order (stable for equal steps)
        # and lists every tenant it ever admitted in admission order
        admitted = list(out["tenants"].items())[-len(tasks):]
        order = sorted(range(len(tasks)), key=lambda j: tasks[j]["arrive"])
        records: List[Dict[str, Any]] = [{}] * len(tasks)
        for j, (tid, info) in zip(order, admitted):
            name = self.archs[tasks[j]["model"]].name
            if not tid.endswith(":" + name) or \
                    info["prompt_len"] != tasks[j]["prompt"]:
                raise RuntimeError(f"tenant {tid} (prompt "
                                   f"{info['prompt_len']}) does not match "
                                   f"task {j}: {tasks[j]}")
            records[j] = {"tid": tid, "tokens": info["output"],
                          "ttft_s": info["ttft_s"],
                          "departed": info["departed"],
                          "prefill_chunks": list(info["prefill_chunks"]),
                          "lbm_frac": info["lbm_frac"],
                          "kv_wanted": info["kv_wanted"],
                          "kv_reserved": info["kv_reserved"]}
        return {"tasks": records, "host": out["host"],
                "shed": out["overload"]["shed_count"],
                "queued": out["overload"]["queued"]}

    def close(self) -> None:
        self.server = None
