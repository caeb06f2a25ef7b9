"""Operations and bytes the served work needs, from the shapes in the
configuration files: what the whole-step utilisations divide by.  Bytes
are HBM bytes at the served dtype; a model's matmul weights are read once
per step of each task and once per prefill chunk, the tied head reads
the whole table for every token whose logits are produced.  What one
layer and one token need is the family's (``families/<family>.py``)."""
from __future__ import annotations

from typing import Any, Dict, List

import numpy as np

import families


def served_work(entry: Dict[str, Any], task: Dict[str, Any],
                record: Dict[str, Any], rows: int) -> Dict[str, float]:
    """FLOPs and HBM bytes one served task needed: its prefill chunks,
    its first token and its decode steps, all ``rows`` of its batch."""
    fam = families.of(entry)
    m = fam.dims(entry)
    P, D = task["prompt"], task["decode"]
    head = 2.0 * m["V"] * m["d"]
    flops = rows * (fam.token_flops(entry, np.arange(P + D))
                    + head * (1 + D))
    w = fam.weight_bytes(entry)
    nbytes, pos = 0.0, 0
    for chunk in record["prefill_chunks"]:
        nbytes += w + fam.state_bytes(entry, rows, pos + chunk)
        pos += chunk
    for i in range(D):
        nbytes += w + fam.state_bytes(entry, rows, P + i + 1)
    return {"flops": flops, "bytes": nbytes}


def totals(models: Dict[str, Dict[str, Any]], tasks: List[Dict[str, Any]],
           records: List[Dict[str, Any]], rows: int) -> Dict[str, float]:
    out = {"flops": 0.0, "bytes": 0.0}
    for t, r in zip(tasks, records):
        w = served_work(models[t["model"]], t, r, rows)
        out["flops"] += w["flops"]
        out["bytes"] += w["bytes"]
    return out
