"""Mamba2 / SSD (state-space duality) blocks, pure JAX.

Implements the chunked SSD algorithm (arXiv:2405.21060): intra-chunk
attention-like matmuls + inter-chunk state recurrence via lax.scan —
all MXU-friendly contractions.  ``ssd_decode_step`` is the O(1)
recurrent form used by the serving path (state cache instead of KV
cache; this is why the SSM archs run the ``long_500k`` cell).

kernels/ssd_scan.py provides a Pallas variant of the intra-chunk part,
validated against :func:`ssd` in tests.
"""
from __future__ import annotations

import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.distributed.sharding import shard_hint
from repro.models.base import ArchConfig
from repro.models.layers import Params, _normal, init_linear, linear

CONV_K = 4  # causal depthwise conv kernel width


def init_mamba2(key, cfg: ArchConfig) -> Params:
    d, di, n, nh = cfg.d_model, cfg.d_inner, cfg.ssm_state, cfg.ssm_heads
    conv_dim = di + 2 * n
    ks = jax.random.split(key, 5)
    return {
        # in_proj -> [z (di), x (di), B (n), C (n), dt (nh)]
        "in_proj": init_linear(ks[0], d, 2 * di + 2 * n + nh, cfg.jdtype),
        "conv_w": _normal(ks[1], (CONV_K, conv_dim), 1.0 / math.sqrt(CONV_K),
                          cfg.jdtype),
        "A_log": jnp.log(jnp.linspace(1.0, 16.0, nh)).astype(jnp.float32),
        "D": jnp.ones((nh,), jnp.float32),
        "dt_bias": jnp.zeros((nh,), jnp.float32),
        "out_proj": init_linear(ks[2], di, d, cfg.jdtype),
    }


def _causal_conv(x: jnp.ndarray, w: jnp.ndarray,
                 state: Optional[jnp.ndarray] = None
                 ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Depthwise causal conv1d.  x: [b, s, c]; w: [k, c].
    Returns (y, new_state[b, k-1, c])."""
    k = w.shape[0]
    if state is None:
        state = jnp.zeros((x.shape[0], k - 1, x.shape[2]), x.dtype)
    xp = jnp.concatenate([state, x], axis=1)
    y = sum(xp[:, i:i + x.shape[1], :] * w[i] for i in range(k))
    return y, xp[:, -(k - 1):, :]


def ssd(x: jnp.ndarray, dt: jnp.ndarray, A: jnp.ndarray, B: jnp.ndarray,
        C: jnp.ndarray, D: jnp.ndarray, chunk: int,
        h0: Optional[jnp.ndarray] = None
        ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Chunked state-space dual scan.

    x: [b, s, h, p]  dt: [b, s, h]  A: [h] (positive; decay = exp(-dt*A))
    B, C: [b, s, n]  D: [h].  Returns (y [b,s,h,p], final state [b,h,n,p]).
    """
    b, s, h, p = x.shape
    n = B.shape[-1]
    assert s % chunk == 0, f"seq {s} not divisible by chunk {chunk}"
    c = s // chunk
    xr = x.reshape(b, c, chunk, h, p)
    dtr = dt.reshape(b, c, chunk, h)
    Br = B.reshape(b, c, chunk, n)
    Cr = C.reshape(b, c, chunk, n)

    dA = -dtr * A  # [b,c,q,h], negative
    cum = jnp.cumsum(dA, axis=2)
    seg_end = cum[:, :, -1:, :]                                # [b,c,1,h]

    # ---- intra-chunk (quadratic within chunk) -------------------------
    # L[i,j] = exp(cum_i - cum_j) for i >= j; mask the exponent BEFORE
    # exp so masked entries never produce inf (which would NaN the grads)
    diff = cum[:, :, :, None, :] - cum[:, :, None, :, :]
    tri = jnp.tril(jnp.ones((chunk, chunk), bool))[None, None, :, :, None]
    Lm = jnp.exp(jnp.where(tri, diff, -jnp.inf))
    Lm = shard_hint(Lm, ("data", None, None, None, "model"))
    cb = jnp.einsum("bcin,bcjn->bcij", Cr, Br,
                    preferred_element_type=jnp.float32)
    w = cb[:, :, :, :, None] * Lm * dtr[:, :, None, :, :]      # [b,c,i,j,h]
    w = shard_hint(w, ("data", None, None, None, "model"))
    # mixed-precision contraction: keep x in bf16 (no convert traffic);
    # accumulation stays fp32 via preferred_element_type
    y_diag = jnp.einsum("bcijh,bcjhp->bcihp", w.astype(x.dtype), xr,
                        preferred_element_type=jnp.float32)

    # ---- chunk summary states -----------------------------------------
    decay_out = jnp.exp(seg_end - cum)                          # [b,c,q,h]
    S = jnp.einsum("bcqh,bcqn,bcqhp->bchnp",
                   (decay_out * dtr).astype(x.dtype), Br, xr,
                   preferred_element_type=jnp.float32)          # [b,c,h,n,p]

    # ---- inter-chunk recurrence ----------------------------------------
    chunk_decay = jnp.exp(seg_end[:, :, 0, :])                  # [b,c,h]
    if h0 is None:
        h0 = jnp.zeros((b, h, n, p), jnp.float32)

    def step(hprev, inp):
        dec, s_c = inp                                           # [b,h], [b,h,n,p]
        hnew = hprev * dec[:, :, None, None] + s_c
        return hnew, hprev

    hfin, hstarts = jax.lax.scan(
        step, h0, (jnp.moveaxis(chunk_decay, 1, 0), jnp.moveaxis(S, 1, 0)))
    hstarts = jnp.moveaxis(hstarts, 0, 1)                        # [b,c,h,n,p]

    # ---- inter-chunk contribution ---------------------------------------
    decay_in = jnp.exp(cum)                                      # [b,c,q,h]
    y_off = jnp.einsum("bcqn,bcqh,bchnp->bcqhp",
                       Cr, decay_in.astype(x.dtype),
                       hstarts.astype(x.dtype),
                       preferred_element_type=jnp.float32)

    y = (y_diag + y_off).reshape(b, s, h, p) + D[None, None, :, None] * x
    return y.astype(x.dtype), hfin


def mamba2_forward(params: Params, x: jnp.ndarray, cfg: ArchConfig,
                   state: Optional[Dict[str, jnp.ndarray]] = None,
                   chunk: Optional[int] = None
                   ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """Full Mamba2 block over a sequence.  x: [b, s, d].

    ``chunk`` overrides the architecture's SSD chunk (the KernelPlan
    path: a smaller page grant lowers to a smaller intra-chunk working
    set); it applies only when it divides the sequence length.

    A sequence that is NOT a multiple of the SSD chunk runs the aligned
    prefix through the chunked scan and the remainder as one final
    chunk of its own length, carrying the inter-chunk state across the
    split.  The segmentation is therefore ``[chunk]*n + [tail]`` — the
    same segmentation a *chunked prefill* at chunk-aligned boundaries
    produces — so chunked prefill with state carry is bit-identical to
    the one-shot forward for any prompt length
    (tests/test_continuous_batching.py)."""
    b, s, d = x.shape
    ssd_chunk_len = cfg.ssm_chunk
    if chunk and chunk > 0 and s % chunk == 0:
        ssd_chunk_len = chunk
    di, n, nh, p = cfg.d_inner, cfg.ssm_state, cfg.ssm_heads, cfg.ssm_head_dim
    zxbcdt = linear(params["in_proj"], x)
    z, xs, B, C, dt = jnp.split(
        zxbcdt, [di, 2 * di, 2 * di + n, 2 * di + 2 * n], axis=-1)
    conv_in = jnp.concatenate([xs, B, C], axis=-1)
    conv_state = state["conv"] if state else None
    conv_out, new_conv = _causal_conv(conv_in, params["conv_w"], conv_state)
    conv_out = jax.nn.silu(conv_out.astype(jnp.float32)).astype(x.dtype)
    xs, B, C = jnp.split(conv_out, [di, di + n], axis=-1)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + params["dt_bias"])
    A = jnp.exp(params["A_log"])
    xh = xs.reshape(b, s, nh, p)
    # pin head sharding across the split/reshape boundary — GSPMD loses
    # the 'model' sharding of in_proj's output through split+reshape and
    # would otherwise replicate every SSD intermediate (§Perf cell A)
    xh = shard_hint(xh, ("data", None, "model", None))
    dt = shard_hint(dt, ("data", None, "model"))
    h0 = state["ssm"] if state else None
    s_main = (s // ssd_chunk_len) * ssd_chunk_len
    if s_main == s:
        y, hfin = ssd(xh, dt, A, B, C, params["D"], ssd_chunk_len, h0)
    else:
        parts, hfin = [], h0
        if s_main:
            y1, hfin = ssd(xh[:, :s_main], dt[:, :s_main], A,
                           B[:, :s_main], C[:, :s_main], params["D"],
                           ssd_chunk_len, hfin)
            parts.append(y1)
        y2, hfin = ssd(xh[:, s_main:], dt[:, s_main:], A,
                       B[:, s_main:], C[:, s_main:], params["D"],
                       s - s_main, hfin)
        parts.append(y2)
        y = jnp.concatenate(parts, axis=1) if len(parts) > 1 else parts[0]
    y = y.reshape(b, s, di)
    y = y * jax.nn.silu(z.astype(jnp.float32)).astype(x.dtype)
    out = linear(params["out_proj"], y)
    return out, {"conv": new_conv, "ssm": hfin}


def ssd_decode_step(params: Params, x: jnp.ndarray, cfg: ArchConfig,
                    state: Dict[str, jnp.ndarray],
                    layer: Optional[jnp.ndarray] = None
                    ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """O(1) recurrent step.  x: [b, 1, d]; state {conv, ssm}.

    The returned state is pinned to the input state's dtypes: the
    serving path scans this step over a K-token epoch with the state as
    a donated carry, and a carry whose dtype drifts (e.g. an f32
    accumulation escaping into a bf16 conv window) would break both the
    scan signature and in-place donation.

    With ``layer`` given, ``state["ssm"]`` is the whole stacked state
    [L, b, h, n, p] of a deep stack and the returned ``"ssm"`` is that
    stack with layer ``layer`` updated in place by the Pallas kernel
    (kernels/ssd_decode.py): one read and one write of the layer's
    state, the same float32 update and readout."""
    b, _, d = x.shape
    di, n, nh, p = cfg.d_inner, cfg.ssm_state, cfg.ssm_heads, cfg.ssm_head_dim
    zxbcdt = linear(params["in_proj"], x)
    z, xs, B, C, dt = jnp.split(
        zxbcdt, [di, 2 * di, 2 * di + n, 2 * di + 2 * n], axis=-1)
    conv_in = jnp.concatenate([xs, B, C], axis=-1)
    conv_out, new_conv = _causal_conv(conv_in, params["conv_w"], state["conv"])
    conv_out = jax.nn.silu(conv_out.astype(jnp.float32)).astype(x.dtype)
    xs, B, C = jnp.split(conv_out, [di, di + n], axis=-1)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + params["dt_bias"])[:, 0]  # [b,nh]
    A = jnp.exp(params["A_log"])
    dA = jnp.exp(-dt * A)                                        # [b,nh]
    xh = xs.reshape(b, nh, p).astype(jnp.float32)
    Bf = B[:, 0].astype(jnp.float32)                             # [b,n]
    Cf = C[:, 0].astype(jnp.float32)
    if layer is None:
        h = state["ssm"] * dA[:, :, None, None] + jnp.einsum(
            "bh,bn,bhp->bhnp", dt, Bf, xh)
        y = jnp.einsum("bn,bhnp->bhp", Cf, h)
        h = h.astype(state["ssm"].dtype)
    else:
        from repro.kernels import ops as kops
        y, h = kops.ssd_decode_inplace(state["ssm"], layer, dA, dt, xh, Bf,
                                       Cf)
    y = y + params["D"][None, :, None] * xh
    y = y.reshape(b, 1, di).astype(x.dtype)
    y = y * jax.nn.silu(z.astype(jnp.float32)).astype(x.dtype)
    return linear(params["out_proj"], y), {
        "conv": new_conv.astype(state["conv"].dtype), "ssm": h}


def init_ssm_state(cfg: ArchConfig, batch: int) -> Dict[str, jnp.ndarray]:
    di, n = cfg.d_inner, cfg.ssm_state
    return {
        "conv": jnp.zeros((batch, CONV_K - 1, di + 2 * n), cfg.jdtype),
        "ssm": jnp.zeros((batch, cfg.ssm_heads, n, cfg.ssm_head_dim),
                         jnp.float32),
    }
