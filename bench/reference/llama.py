"""Plain float32 forward of a Llama-family decoder (Yi): RMSNorm, GQA
attention with rotary positions (rotate-half), SwiGLU, tied head.

Written from the published equations; the one departure from the
published Yi-9B is the tied head, which the served model has (the
configuration file lists it).  Computed layer by layer, each layer's
weights drawn from the seed as it is reached, so the whole model never
sits in float32 at once.
"""
from __future__ import annotations

import functools
import math
from typing import Any, Dict

import jax
import jax.numpy as jnp

from reference.common import (F32, embed_table, linear_w, make_dot,
                              rms_norm, root_keys)


def _dims(cfg: Dict[str, Any]):
    d, H = cfg["hidden_size"], cfg["num_attention_heads"]
    return d, H, cfg["num_key_value_heads"], d // H, cfg["intermediate_size"]


def layer_weights(key, cfg: Dict[str, Any]) -> Dict[str, jnp.ndarray]:
    d, H, Hkv, hd, f = _dims(cfg)
    dt = jnp.dtype(cfg["torch_dtype"])
    k_attn, k_ffn = jax.random.split(key)
    kq, kk, kv, ko = jax.random.split(k_attn, 4)
    kg, ku, kd = jax.random.split(k_ffn, 3)
    return {"wq": linear_w(kq, d, H * hd, dt),
            "wk": linear_w(kk, d, Hkv * hd, dt),
            "wv": linear_w(kv, d, Hkv * hd, dt),
            "wo": linear_w(ko, H * hd, d, dt),
            "gate": linear_w(kg, d, f, dt),
            "up": linear_w(ku, d, f, dt),
            "down": linear_w(kd, f, d, dt)}


def rope(x: jnp.ndarray, theta: float) -> jnp.ndarray:
    """x: [B, T, heads, hd] at positions 0..T-1 (rotate-half pairs)."""
    T, hd = x.shape[1], x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=F32) / hd)
    ang = jnp.arange(T, dtype=F32)[:, None] * inv          # [T, hd/2]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


@functools.partial(jax.jit, static_argnames=("cfgkey", "low"))
def _layer(x, key, cfgkey, low):
    cfg = dict(cfgkey)
    d, H, Hkv, hd, _ = _dims(cfg)
    eps = cfg["rms_norm_eps"]
    w = layer_weights(key, cfg)
    dot = make_dot(low)
    B, T, _ = x.shape
    h = rms_norm(x, eps)
    q = rope(dot(h, w["wq"]).reshape(B, T, H, hd), cfg["rope_theta"])
    k = rope(dot(h, w["wk"]).reshape(B, T, Hkv, hd), cfg["rope_theta"])
    v = dot(h, w["wv"]).reshape(B, T, Hkv, hd)
    q = q.reshape(B, T, Hkv, H // Hkv, hd)
    s = jnp.einsum("bqkgh,bskh->bkgqs", q, k,
                   precision=jax.lax.Precision.HIGHEST) / math.sqrt(hd)
    causal = jnp.tril(jnp.ones((T, T), bool))
    p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
    ctx = jnp.einsum("bkgqs,bskh->bqkgh", p, v,
                     precision=jax.lax.Precision.HIGHEST)
    x = x + dot(ctx.reshape(B, T, H * hd), w["wo"])
    h = rms_norm(x, eps)
    return x + dot(jax.nn.silu(dot(h, w["gate"])) * dot(h, w["up"]),
                   w["down"])


@functools.partial(jax.jit, static_argnames=("cfgkey", "start", "low"))
def _head(x, ke, cfgkey, start, low):
    cfg = dict(cfgkey)
    table = embed_table(ke, cfg["vocab_size"], cfg["hidden_size"],
                        jnp.dtype(cfg["torch_dtype"]))
    h = rms_norm(x[:, start:], cfg["rms_norm_eps"])
    return make_dot(low)(h, table.T)


def _cfgkey(cfg: Dict[str, Any]):
    keep = ("hidden_size", "num_attention_heads", "num_key_value_heads",
            "intermediate_size", "rms_norm_eps", "rope_theta",
            "torch_dtype", "vocab_size")
    return tuple((k, cfg[k]) for k in keep)


def logits(cfg: Dict[str, Any], param_seed: int, tokens: jnp.ndarray,
           start: int, low: bool = False) -> jnp.ndarray:
    """Logits [B, T - start, vocab] of positions start..T-1 of
    ``tokens`` [B, T]; ``low`` is the fp8 control."""
    ke, keys = root_keys(param_seed, cfg["num_hidden_layers"])
    ck = _cfgkey(cfg)
    table = embed_table(ke, cfg["vocab_size"], cfg["hidden_size"],
                        jnp.dtype(cfg["torch_dtype"]))
    x = jnp.take(table, tokens, axis=0)
    del table
    for g in range(cfg["num_hidden_layers"]):
        x = _layer(x, keys[g], ck, low)
    return _head(x, ke, ck, start, low)
