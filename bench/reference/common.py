"""Pieces the plain float32 references share: the seeded weight recipe,
RMSNorm, the tied head, the lower-precision control and the logit gap.

Weights are made from a task's ``param_seed`` by the documented recipe
the served models are initialised with (``jax.random`` normals scaled
by ``1/sqrt(fan_in)``, rounded to the served dtype, norms at one): the
reference draws them itself, one layer at a time, and takes no array
from the system under test.
"""
from __future__ import annotations

import math
from typing import Tuple

import jax
import jax.numpy as jnp

F32 = jnp.float32
FP8 = jnp.float8_e4m3fn
FP8_MAX = 448.0


def normal(key, shape, scale: float, dtype) -> jnp.ndarray:
    """A served weight as drawn and rounded, returned in float32."""
    w = (jax.random.normal(key, shape, F32) * scale).astype(dtype)
    return w.astype(F32)


def linear_w(key, d_in: int, d_out: int, dtype) -> jnp.ndarray:
    return normal(key, (d_in, d_out), 1.0 / math.sqrt(d_in), dtype)


def root_keys(param_seed: int, n_layers: int) -> Tuple[jnp.ndarray, ...]:
    """(embedding key, per-layer keys) of one model instance."""
    ke, kl, _ = jax.random.split(jax.random.PRNGKey(param_seed), 3)
    return ke, jax.random.split(kl, n_layers)


def padded_vocab(vocab: int) -> int:
    return -(-vocab // 256) * 256


def embed_table(ke, vocab: int, d: int, dtype) -> jnp.ndarray:
    """The tied embedding, vocab rows only (padding rows never serve)."""
    return normal(ke, (padded_vocab(vocab), d), 1.0, dtype)[:vocab]


def rms_norm(x: jnp.ndarray, eps: float) -> jnp.ndarray:
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def round_fp8(x: jnp.ndarray, axis: int) -> jnp.ndarray:
    """Round to float8 e4m3 with one scale per slice along ``axis``:
    the control's one-step-lower precision (the configuration states
    bfloat16)."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, amax / FP8_MAX, 1.0)
    return (x / scale).astype(FP8).astype(F32) * scale


def make_dot(low: bool):
    """x @ w in float32; ``low`` rounds both operands to fp8 first
    (activations per row, weights per output column)."""
    def dot(x, w):
        if low:
            x = round_fp8(x, axis=-1)
            w = round_fp8(w, axis=0)
        return jnp.einsum("...k,kn->...n", x, w,
                          precision=jax.lax.Precision.HIGHEST)
    return dot


def gaps(logits: jnp.ndarray, tokens: jnp.ndarray) -> jnp.ndarray:
    """By how much each token's logit lies below the best: [B, n]."""
    best = jnp.max(logits, axis=-1)
    got = jnp.take_along_axis(logits, tokens[..., None], axis=-1)[..., 0]
    return best - got
