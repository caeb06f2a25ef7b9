"""Periodic arrival streams on the server's logical step clock.

A traffic file lists streams.  A stream sends one arrival group every
``period`` steps, starting at ``offset``; arrival ``j`` carries the
tasks of ``cycle[j % len(cycle)]``, each a ``[prompt, decode]`` pair
for the stream's ``model``.  So the shapes, the arrival steps and their
order are fixed by the file, and the seed draws only weights and prompt
tokens (``assign_seeds``): every seed gives the same programs.

The number of arrivals per stream grows with the run's seconds at the
file's ``steps_per_s`` (the step rate measured on the chip), is a whole
number of cycles, and is never below ``warm_arrivals``: the warm-up
replays ``warm_arrivals`` arrivals per stream, which reach every state
of the periodic schedule, so the measured window builds no new program.
"""
from __future__ import annotations

import math
import random
from typing import Any, Dict, List


def arrivals(traffic: Dict[str, Any], stream: Dict[str, Any],
             seconds: float) -> int:
    cycle = len(stream["cycle"])
    n = traffic["steps_per_s"] * seconds / stream["period"]
    n = max(traffic["warm_arrivals"], int(round(n)))
    return int(math.ceil(n / cycle) * cycle)


def tasks(traffic: Dict[str, Any], seconds: float = 0.0,
          warm: bool = False) -> List[Dict[str, Any]]:
    """The task list of one run's window (or of its warm-up)."""
    out: List[Dict[str, Any]] = []
    for s in traffic["streams"]:
        n = traffic["warm_arrivals"] if warm else arrivals(traffic, s,
                                                             seconds)
        for j in range(n):
            for prompt, decode in s["cycle"][j % len(s["cycle"])]:
                out.append({"model": s["model"],
                            "arrive": s["offset"] + j * s["period"],
                            "prompt": int(prompt), "decode": int(decode)})
    out.sort(key=lambda t: t["arrive"])
    return out


def assign_seeds(tasks_: List[Dict[str, Any]], seed: int,
                 phase: str) -> None:
    """Weights and prompt tokens of every task, drawn from ``seed``."""
    rng = random.Random(f"{seed}/{phase}")
    for t in tasks_:
        t["param_seed"] = rng.randrange(1 << 30)
        t["prompt_seed"] = rng.randrange(1 << 30)
