"""Operations and bytes the served work needs, from the shapes in the
configuration files: what the whole-step utilisations divide by.  Bytes
are HBM bytes at the served dtype; a model's matmul weights are read once
per step of each task and once per prefill chunk, the tied head reads
the whole table for every token whose logits are produced."""
from __future__ import annotations

from typing import Any, Dict, List

import numpy as np


def dims(entry: Dict[str, Any]) -> Dict[str, int]:
    c = entry["config"]
    if entry["family"] == "llama":
        d, H = c["hidden_size"], c["num_attention_heads"]
        return {"d": d, "L": c["num_hidden_layers"], "H": H,
                "Hkv": c["num_key_value_heads"], "hd": d // H,
                "f": c["intermediate_size"], "V": c["vocab_size"]}
    d = c["d_model"]
    di = c["expand"] * d
    return {"d": d, "L": c["n_layer"], "di": di, "n": c["d_state"],
            "nh": di // c["headdim"], "p": c["headdim"], "k": c["d_conv"],
            "V": c["vocab_size"]}


def layer_params(entry: Dict[str, Any]) -> int:
    """Matmul (and conv) weights of one layer."""
    m = dims(entry)
    if entry["family"] == "llama":
        attn = 2 * m["d"] * m["H"] * m["hd"] + 2 * m["d"] * m["Hkv"] * m["hd"]
        return attn + 3 * m["d"] * m["f"]
    proj = m["d"] * (2 * m["di"] + 2 * m["n"] + m["nh"]) + m["di"] * m["d"]
    return proj + m["k"] * (m["di"] + 2 * m["n"])


def weight_bytes(entry: Dict[str, Any], eb: int = 2) -> int:
    """Layer weights plus the tied head's table."""
    m = dims(entry)
    return eb * (m["L"] * layer_params(entry) + m["V"] * m["d"])


def state_bytes(entry: Dict[str, Any], rows: int, ctx: int,
                eb: int = 2) -> int:
    """KV prefix (attention) or recurrent state (SSM) one step reads."""
    m = dims(entry)
    if entry["family"] == "llama":
        return 2 * m["L"] * m["Hkv"] * m["hd"] * eb * ctx * rows
    return rows * m["L"] * (m["nh"] * m["n"] * m["p"] * 4
                            + (m["k"] - 1) * (m["di"] + 2 * m["n"]) * eb)


def token_flops(entry: Dict[str, Any], pos: np.ndarray) -> float:
    """Forward FLOPs of tokens at positions ``pos`` (no head)."""
    m = dims(entry)
    n = float(np.size(pos))
    mats = 2.0 * m["L"] * layer_params(entry) * n
    if entry["family"] == "llama":
        return mats + 4.0 * m["L"] * m["H"] * m["hd"] * float(
            np.sum(np.asarray(pos) + 1))
    return mats + 4.0 * m["L"] * m["nh"] * m["n"] * m["p"] * n


def served_work(entry: Dict[str, Any], task: Dict[str, Any],
                record: Dict[str, Any], rows: int) -> Dict[str, float]:
    """FLOPs and HBM bytes one served task needed: its prefill chunks,
    its first token and its decode steps, all ``rows`` of its batch."""
    m = dims(entry)
    P, D = task["prompt"], task["decode"]
    head = 2.0 * m["V"] * m["d"]
    flops = rows * (token_flops(entry, np.arange(P + D))
                    + head * (1 + D))
    w = weight_bytes(entry)
    nbytes, pos = 0.0, 0
    for chunk in record["prefill_chunks"]:
        nbytes += w + state_bytes(entry, rows, pos + chunk)
        pos += chunk
    for i in range(D):
        nbytes += w + state_bytes(entry, rows, P + i + 1)
    return {"flops": flops, "bytes": nbytes}


def totals(models: Dict[str, Dict[str, Any]], tasks: List[Dict[str, Any]],
           records: List[Dict[str, Any]], rows: int) -> Dict[str, float]:
    out = {"flops": 0.0, "bytes": 0.0}
    for t, r in zip(tasks, records):
        w = served_work(models[t["model"]], t, r, rows)
        out["flops"] += w["flops"]
        out["bytes"] += w["bytes"]
    return out
