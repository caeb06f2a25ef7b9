"""VMEM-tiled causal flash attention (online softmax) with GQA.

Adapts the paper's "retain long-reuse-distance data" rule to attention:
the running (m, l, acc) statistics are the resident working set; K/V
blocks stream through VMEM (bypass—touched once per query block).  The
kv-head index map implements GQA without materializing repeated K/V —
one HBM read serves a whole query-head group, the kernel-level analogue
of NEC multicast-read.

Grid: (batch * q_heads, q_blocks, kv_blocks), kv innermost.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.vmem import attn_vmem_bytes, compiler_params

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, *rest, n_kv: int, block_q: int,
                  block_kv: int, causal: bool, sm_scale: float,
                  quantized: bool = False):
    """Online-softmax flash attention.  ``quantized`` streams int8/fp8
    K/V blocks with per-row fp32 scale stripes (two extra input refs)
    and dequantizes in-register on the VMEM-resident block — the fp K/V
    never exist in HBM, only one [bkv, hd] tile at a time exists at all.
    """
    if quantized:
        ks_ref, vs_ref, o_ref, m_ref, l_ref, acc_ref = rest
    else:
        o_ref, m_ref, l_ref, acc_ref = rest
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_start = qi * block_q
    k_start = ki * block_kv

    def body():
        q = q_ref[0, ...]                          # [bq, hd]
        if quantized:
            # in-register dequant: scale stripe [bkv] broadcasts over hd
            k = k_ref[0, ...].astype(jnp.float32) * ks_ref[0, :][:, None]
            v = v_ref[0, ...].astype(jnp.float32) * vs_ref[0, :][:, None]
        else:
            k = k_ref[0, ...]                      # [bkv, hd]
            v = v_ref[0, ...]
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * sm_scale
        if causal:
            qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(kpos <= qpos, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=-1))
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + p.sum(axis=-1)
        acc_ref[...] = acc_ref[...] * alpha[:, None] + jnp.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    if causal:
        # skip fully-masked kv blocks (their last k precedes q block start)
        pl.when(k_start <= q_start + block_q - 1)(body)
    else:
        body()

    @pl.when(ki == n_kv - 1)
    def _flush():
        l = l_ref[...]
        safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, ...] = (acc_ref[...] / safe[:, None]).astype(o_ref.dtype)


def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                    causal: bool = True, block_q: int = 128,
                    block_kv: int = 128, sm_scale: Optional[float] = None,
                    interpret: bool = True) -> jnp.ndarray:
    """q: [B, H, S, hd]; k, v: [B, Hkv, S, hd] with H % Hkv == 0.
    Returns [B, H, S, hd]."""
    B, H, S, hd = q.shape
    _, Hkv, Sk, _ = k.shape
    assert H % Hkv == 0
    groups = H // Hkv
    sm = sm_scale if sm_scale is not None else hd ** -0.5
    bq, bkv = min(block_q, S), min(block_kv, Sk)
    assert S % bq == 0 and Sk % bkv == 0
    grid = (B * H, S // bq, Sk // bkv)

    qr = q.reshape(B * H, S, hd)
    # GQA: index map picks the kv head for each q head (no repeat in HBM)
    kr = k.reshape(B * Hkv, Sk, hd)
    vr = v.reshape(B * Hkv, Sk, hd)

    def q_map(h, i, j):
        return (h, i, 0)

    def kv_map(h, i, j):
        return ((h // groups), j, 0)

    out = pl.pallas_call(
        functools.partial(_flash_kernel, n_kv=grid[2], block_q=bq,
                          block_kv=bkv, causal=causal, sm_scale=sm),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, hd), q_map),
            pl.BlockSpec((1, bkv, hd), kv_map),
            pl.BlockSpec((1, bkv, hd), kv_map),
        ],
        out_specs=pl.BlockSpec((1, bq, hd), q_map),
        out_shape=jax.ShapeDtypeStruct((B * H, S, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq,), jnp.float32),      # running max
            pltpu.VMEM((bq,), jnp.float32),      # running sum
            pltpu.VMEM((bq, hd), jnp.float32),   # accumulator
        ],
        compiler_params=compiler_params(attn_vmem_bytes(
            bq, bkv, hd, q.dtype.itemsize, k.dtype.itemsize)),
        interpret=interpret,
    )(qr, kr, vr)
    return out.reshape(B, H, S, hd)


def flash_attention_quantized(q: jnp.ndarray, k: jnp.ndarray,
                              v: jnp.ndarray, k_scale: jnp.ndarray,
                              v_scale: jnp.ndarray, *,
                              causal: bool = True, block_q: int = 128,
                              block_kv: int = 128,
                              sm_scale: Optional[float] = None,
                              interpret: bool = True) -> jnp.ndarray:
    """Dequant-fused flash attention: ``k``/``v`` are int8/fp8
    [B, Hkv, Sk, hd] with per-row fp32 scales [B, Hkv, Sk]; q stays in
    the compute dtype.  K/V blocks stream through VMEM at the quantized
    width (plus a 4-byte/row scale stripe riding the same kv index map)
    and are dequantized in-register inside the kernel — no materialized
    fp copy of the cache, so HBM traffic per kv block drops by the
    storage-width ratio.  Output matches :func:`flash_attention` on the
    dequantized K/V bit-for-bit (same f32 block math, tests enforce it).
    """
    B, H, S, hd = q.shape
    _, Hkv, Sk, _ = k.shape
    assert H % Hkv == 0
    assert k_scale.shape == (B, Hkv, Sk), (k_scale.shape, (B, Hkv, Sk))
    groups = H // Hkv
    sm = sm_scale if sm_scale is not None else hd ** -0.5
    bq, bkv = min(block_q, S), min(block_kv, Sk)
    assert S % bq == 0 and Sk % bkv == 0
    grid = (B * H, S // bq, Sk // bkv)

    qr = q.reshape(B * H, S, hd)
    kr = k.reshape(B * Hkv, Sk, hd)
    vr = v.reshape(B * Hkv, Sk, hd)
    ksr = k_scale.astype(jnp.float32).reshape(B * Hkv, Sk)
    vsr = v_scale.astype(jnp.float32).reshape(B * Hkv, Sk)

    def q_map(h, i, j):
        return (h, i, 0)

    def kv_map(h, i, j):
        return ((h // groups), j, 0)

    def scale_map(h, i, j):
        return ((h // groups), j)

    out = pl.pallas_call(
        functools.partial(_flash_kernel, n_kv=grid[2], block_q=bq,
                          block_kv=bkv, causal=causal, sm_scale=sm,
                          quantized=True),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, hd), q_map),
            pl.BlockSpec((1, bkv, hd), kv_map),
            pl.BlockSpec((1, bkv, hd), kv_map),
            pl.BlockSpec((1, bkv), scale_map),
            pl.BlockSpec((1, bkv), scale_map),
        ],
        out_specs=pl.BlockSpec((1, bq, hd), q_map),
        out_shape=jax.ShapeDtypeStruct((B * H, S, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq,), jnp.float32),      # running max
            pltpu.VMEM((bq,), jnp.float32),      # running sum
            pltpu.VMEM((bq, hd), jnp.float32),   # accumulator
        ],
        compiler_params=compiler_params(attn_vmem_bytes(
            bq, bkv, hd, q.dtype.itemsize, k.dtype.itemsize)),
        interpret=interpret,
    )(qr, kr, vr, ksr, vsr)
    return out.reshape(B, H, S, hd)
