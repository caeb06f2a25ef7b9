"""Mamba2 (state-spaces/mamba2-*): a stack of Mamba2 mixers with the
state-space duality (SSD) scan, no attention and no MLP, the head tied
to the embedding table."""
from __future__ import annotations

from typing import Any, Dict, Optional

import numpy as np

# published config keys -> the program's ArchConfig fields
ARCH_KEYS = {"d_model": "d_model", "n_layer": "num_layers",
             "vocab_size": "vocab_size", "d_state": "ssm_state",
             "expand": "ssm_expand", "headdim": "ssm_head_dim",
             "chunk_size": "ssm_chunk", "norm_epsilon": "norm_eps",
             "torch_dtype": "dtype"}

# the CPU size of bench/tests/test_reference.py
TINY = {"d_model": 128, "n_layer": 2, "vocab_size": 512,
        "d_state": 16, "d_conv": 4, "expand": 2, "headdim": 32,
        "chunk_size": 32, "norm_epsilon": 1e-5,
        "torch_dtype": "float32"}


def arch_fields(cfg: Dict[str, Any]) -> Dict[str, Any]:
    """ArchConfig keyword arguments (all but ``name``) of a config."""
    kw = {f: cfg[k] for k, f in ARCH_KEYS.items()}
    kw.update(family="ssm", num_heads=0, num_kv_heads=0, d_ff=0,
              sub_quadratic=True)
    return kw


def dims(entry: Dict[str, Any]) -> Dict[str, int]:
    c = entry["config"]
    d = c["d_model"]
    di = c["expand"] * d
    return {"d": d, "L": c["n_layer"], "di": di, "n": c["d_state"],
            "nh": di // c["headdim"], "p": c["headdim"], "k": c["d_conv"],
            "V": c["vocab_size"]}


def layer_params(entry: Dict[str, Any]) -> int:
    """Matmul and conv weights of one layer."""
    m = dims(entry)
    proj = m["d"] * (2 * m["di"] + 2 * m["n"] + m["nh"]) + m["di"] * m["d"]
    return proj + m["k"] * (m["di"] + 2 * m["n"])


def weight_bytes(entry: Dict[str, Any], eb: int = 2) -> int:
    """Layer weights plus the tied head's table."""
    m = dims(entry)
    return eb * (m["L"] * layer_params(entry) + m["V"] * m["d"])


def state_bytes(entry: Dict[str, Any], rows: int, ctx: int,
                eb: int = 2) -> int:
    """The recurrent state one step reads: float32 SSM state and the
    conv window, whatever the context."""
    m = dims(entry)
    return rows * m["L"] * (m["nh"] * m["n"] * m["p"] * 4
                            + (m["k"] - 1) * (m["di"] + 2 * m["n"]) * eb)


def token_flops(entry: Dict[str, Any], pos: np.ndarray) -> float:
    """Forward FLOPs of tokens at positions ``pos`` (no head)."""
    m = dims(entry)
    n = float(np.size(pos))
    mats = 2.0 * m["L"] * layer_params(entry) * n
    return mats + 4.0 * m["L"] * m["nh"] * m["n"] * m["p"] * n


def ssd_decode_work(entry: Dict[str, Any], rows: int, heads: int,
                    p: int) -> Optional[Dict[str, float]]:
    """FLOPs and HBM bytes of one call of the in-place decode kernel
    (kernels/ssd_decode.py) on one layer of ``rows`` rows: one float32
    read and one write of the layer's state slice [rows, heads, p, n],
    and its small operands (dA and dt per head, x in and y out per head
    and p, B and C per row, all float32).  Per state element it does
    h * dA + u * B and the readout's multiply and add: 5 FLOPs to 8
    bytes, far under the chip's ridge, so its bytes bound it.  None for
    a call shape that is not this model's."""
    m = dims(entry)
    if (heads, p) != (m["nh"], m["p"]):
        return None
    state = rows * heads * p * m["n"]
    small = 2 * rows * heads + 2 * rows * heads * p + 2 * rows * m["n"]
    return {"flops": 5.0 * state + rows * heads * p,
            "bytes": 4.0 * (2 * state + small)}
