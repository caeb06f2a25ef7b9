"""Plain float32 forward of Mamba2 (arXiv:2405.21060): RMSNorm, one
input projection into z, x, B, C and dt, a causal depthwise convolution
of width ``d_conv`` with SiLU over [x, B, C], the state-space model in
its quadratic (attention-like) form per head,

    y_t = sum_{s<=t} (C_t . B_s) exp(sum_{s<r<=t} dt_r A) dt_s x_s + D x_t,

then y * silu(z) and the output projection, and a tied head.

Written from the published equations, with ``ngroups`` 1.  The served
model departs from the published block in two places, listed in the
configuration file and followed here: there is no gated RMSNorm before
the output projection, and the convolution has no bias.  Computed layer
by layer, each layer's weights drawn from the seed as it is reached.
"""
from __future__ import annotations

import functools
import math
from typing import Any, Dict

import jax
import jax.numpy as jnp

from reference.common import (F32, embed_table, linear_w, make_dot,
                              normal, rms_norm, root_keys)


def _dims(cfg: Dict[str, Any]):
    d = cfg["d_model"]
    di = cfg["expand"] * d
    return d, di, cfg["d_state"], di // cfg["headdim"], cfg["headdim"]


def layer_weights(key, cfg: Dict[str, Any]) -> Dict[str, jnp.ndarray]:
    d, di, n, nh, _ = _dims(cfg)
    dt = jnp.dtype(cfg["torch_dtype"])
    k = cfg["d_conv"]
    ks = jax.random.split(key, 5)
    return {"in_proj": linear_w(ks[0], d, 2 * di + 2 * n + nh, dt),
            "conv_w": normal(ks[1], (k, di + 2 * n), 1.0 / math.sqrt(k), dt),
            "A_log": jnp.log(jnp.linspace(1.0, 16.0, nh, dtype=F32)),
            "D": jnp.ones((nh,), F32),
            "dt_bias": jnp.zeros((nh,), F32),
            "out_proj": linear_w(ks[2], di, d, dt)}


def ssm(x, dt, A, B, C, D):
    """Quadratic form.  x: [b, T, h, p]; dt: [b, T, h]; A, D: [h];
    B, C: [b, T, n].  Returns y [b, T, h, p]."""
    T = x.shape[1]
    cum = jnp.cumsum(dt * A, axis=1)                         # [b, T, h]
    diff = cum[:, :, None, :] - cum[:, None, :, :]           # [b, t, s, h]
    causal = jnp.tril(jnp.ones((T, T), bool))[None, :, :, None]
    decay = jnp.exp(jnp.where(causal, diff, -jnp.inf))
    cb = jnp.einsum("btn,bsn->bts", C, B,
                    precision=jax.lax.Precision.HIGHEST)
    w = cb[..., None] * decay * dt[:, None, :, :]             # [b, t, s, h]
    y = jnp.einsum("btsh,bshp->bthp", w, x,
                   precision=jax.lax.Precision.HIGHEST)
    return y + D[None, None, :, None] * x


@functools.partial(jax.jit, static_argnames=("cfgkey", "low"))
def _layer(x, key, cfgkey, low):
    cfg = dict(cfgkey)
    d, di, n, nh, p = _dims(cfg)
    w = layer_weights(key, cfg)
    dot = make_dot(low)
    b, T, _ = x.shape
    zxbcdt = dot(rms_norm(x, cfg["norm_epsilon"]), w["in_proj"])
    z, xbc, dt = jnp.split(zxbcdt, [di, 2 * di + 2 * n], axis=-1)
    k = cfg["d_conv"]
    pad = jnp.pad(xbc, ((0, 0), (k - 1, 0), (0, 0)))
    xbc = sum(pad[:, i:i + T] * w["conv_w"][i] for i in range(k))
    xs, B, C = jnp.split(jax.nn.silu(xbc), [di, di + n], axis=-1)
    dt = jax.nn.softplus(dt + w["dt_bias"])
    # one batch row at a time: the [T, T, heads] decay is the big term
    A = -jnp.exp(w["A_log"])
    y = jax.lax.map(lambda a: ssm(a[0], a[1], A, a[2], a[3], w["D"]),
                    (xs.reshape(b, 1, T, nh, p), dt[:, None], B[:, None],
                     C[:, None]))
    y = y.reshape(b, T, di) * jax.nn.silu(z)
    return x + dot(y, w["out_proj"])


@functools.partial(jax.jit, static_argnames=("cfgkey", "start", "low"))
def _head(x, ke, cfgkey, start, low):
    cfg = dict(cfgkey)
    table = embed_table(ke, cfg["vocab_size"], cfg["d_model"],
                        jnp.dtype(cfg["torch_dtype"]))
    h = rms_norm(x[:, start:], cfg["norm_epsilon"])
    return make_dot(low)(h, table.T)


def _cfgkey(cfg: Dict[str, Any]):
    keep = ("d_model", "expand", "d_state", "headdim", "d_conv",
            "norm_epsilon", "torch_dtype", "vocab_size")
    return tuple((k, cfg[k]) for k in keep)


def logits(cfg: Dict[str, Any], param_seed: int, tokens: jnp.ndarray,
           start: int, low: bool = False) -> jnp.ndarray:
    """Logits [B, T - start, vocab] of positions start..T-1 of
    ``tokens`` [B, T]; ``low`` is the fp8 control."""
    ke, keys = root_keys(param_seed, cfg["n_layer"])
    ck = _cfgkey(cfg)
    table = embed_table(ke, cfg["vocab_size"], cfg["d_model"],
                        jnp.dtype(cfg["torch_dtype"]))
    x = jnp.take(table, tokens, axis=0)
    del table
    for g in range(cfg["n_layer"]):
        x = _layer(x, keys[g], ck, low)
    return _head(x, ke, ck, start, low)
