"""Llama-style decoders (Yi, ...): grouped-query attention with RoPE and
a SwiGLU MLP in every layer, the head tied to the embedding table."""
from __future__ import annotations

from typing import Any, Dict

import numpy as np

# published config keys -> the program's ArchConfig fields
ARCH_KEYS = {"hidden_size": "d_model", "num_hidden_layers": "num_layers",
             "num_attention_heads": "num_heads",
             "num_key_value_heads": "num_kv_heads",
             "intermediate_size": "d_ff", "vocab_size": "vocab_size",
             "rope_theta": "rope_theta", "rms_norm_eps": "norm_eps",
             "torch_dtype": "dtype"}

# the CPU size of bench/tests/test_reference.py
TINY = {"hidden_size": 128, "num_attention_heads": 4,
        "num_key_value_heads": 2, "intermediate_size": 256,
        "num_hidden_layers": 2, "vocab_size": 512,
        "rms_norm_eps": 1e-5, "rope_theta": 10000.0,
        "torch_dtype": "float32"}


def arch_fields(cfg: Dict[str, Any]) -> Dict[str, Any]:
    """ArchConfig keyword arguments (all but ``name``) of a config."""
    kw = {f: cfg[k] for k, f in ARCH_KEYS.items()}
    kw.update(family="dense", head_dim=cfg["hidden_size"]
              // cfg["num_attention_heads"])
    return kw


def dims(entry: Dict[str, Any]) -> Dict[str, int]:
    c = entry["config"]
    d, H = c["hidden_size"], c["num_attention_heads"]
    return {"d": d, "L": c["num_hidden_layers"], "H": H,
            "Hkv": c["num_key_value_heads"], "hd": d // H,
            "f": c["intermediate_size"], "V": c["vocab_size"]}


def layer_params(entry: Dict[str, Any]) -> int:
    """Matmul weights of one layer."""
    m = dims(entry)
    attn = 2 * m["d"] * m["H"] * m["hd"] + 2 * m["d"] * m["Hkv"] * m["hd"]
    return attn + 3 * m["d"] * m["f"]


def weight_bytes(entry: Dict[str, Any], eb: int = 2) -> int:
    """Layer weights plus the tied head's table."""
    m = dims(entry)
    return eb * (m["L"] * layer_params(entry) + m["V"] * m["d"])


def state_bytes(entry: Dict[str, Any], rows: int, ctx: int,
                eb: int = 2) -> int:
    """The KV prefix one step reads."""
    m = dims(entry)
    return 2 * m["L"] * m["Hkv"] * m["hd"] * eb * ctx * rows


def token_flops(entry: Dict[str, Any], pos: np.ndarray) -> float:
    """Forward FLOPs of tokens at positions ``pos`` (no head)."""
    m = dims(entry)
    n = float(np.size(pos))
    mats = 2.0 * m["L"] * layer_params(entry) * n
    return mats + 4.0 * m["L"] * m["H"] * m["hd"] * float(
        np.sum(np.asarray(pos) + 1))
