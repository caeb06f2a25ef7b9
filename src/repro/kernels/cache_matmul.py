"""cache_matmul: budget-parametric tiled matmul Pallas kernel.

This is the TPU embodiment of a CaMDN *LWM mapping candidate*: the tile
shape (bm, bn, bk) — chosen by core/vmem.py from the allocator's page
grant — fixes the kernel's VMEM working set exactly the way a candidate's
loop table fixes the cache footprint on the paper's NPU.  Operand tiles
stream HBM->VMEM via the BlockSpec pipeline (the bypass path: no
residency beyond double buffers); the fp32 accumulator tile is the
output-stationary resident.

Grid: (M/bm, N/bn, K/bk), K innermost for accumulation.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.vmem import TileConfig, compiler_params, tile_vmem_bytes


def _matmul_kernel(a_ref, b_ref, o_ref, acc_ref, *, n_k: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(a_ref[...], b_ref[...],
                            preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(2) == n_k - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def _matmul_quant_kernel(a_ref, b_ref, s_ref, o_ref, acc_ref, *, n_k: int):
    """Dequant-fused tile matmul: the B tile arrives in VMEM at int8/fp8
    width and is dequantized in-register against its per-column fp32
    scale stripe right before the MXU dot — no fp copy of B is ever
    materialized in HBM or VMEM."""
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    b = b_ref[...].astype(jnp.float32) * s_ref[0, :][None, :]
    acc_ref[...] += jnp.dot(a_ref[...], b,
                            preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(2) == n_k - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def cache_matmul_quant(a: jnp.ndarray, b: jnp.ndarray, b_scale: jnp.ndarray,
                       tile: TileConfig, interpret: bool = True
                       ) -> jnp.ndarray:
    """C[M,N] = A[M,K] @ dequant(B[K,N]) with B quantized (int8/fp8)
    and per-output-column scales ``b_scale`` [1, N].  Same grid/tiling
    as :func:`cache_matmul`; the B operand streams at quantized width,
    cutting its HBM traffic by the storage ratio, and the scale stripe
    (4 bytes/column) rides the same j index map."""
    m, k = a.shape
    k2, n = b.shape
    assert k == k2, (a.shape, b.shape)
    assert b_scale.shape == (1, n), (b_scale.shape, n)
    bm, bn, bk = min(tile.bm, m), min(tile.bn, n), min(tile.bk, k)
    assert m % bm == 0 and n % bn == 0 and k % bk == 0, \
        f"shape ({m},{n},{k}) not divisible by tile ({bm},{bn},{bk})"
    grid = (m // bm, n // bn, k // bk)
    return pl.pallas_call(
        functools.partial(_matmul_quant_kernel, n_k=grid[2]),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((1, bn), lambda i, j, kk: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), a.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=compiler_params(
            tile_vmem_bytes(bm, bn, bk, a.dtype.itemsize)),
        interpret=interpret,
    )(a, b, b_scale.astype(jnp.float32))


def cache_matmul(a: jnp.ndarray, b: jnp.ndarray, tile: TileConfig,
                 interpret: bool = True) -> jnp.ndarray:
    """C[M,N] = A[M,K] @ B[K,N] with the tile sizes of one mapping
    candidate.  Shapes must be tile-divisible (ops.py pads)."""
    m, k = a.shape
    k2, n = b.shape
    assert k == k2, (a.shape, b.shape)
    bm, bn, bk = min(tile.bm, m), min(tile.bn, n), min(tile.bk, k)
    assert m % bm == 0 and n % bn == 0 and k % bk == 0, \
        f"shape ({m},{n},{k}) not divisible by tile ({bm},{bn},{bk})"
    grid = (m // bm, n // bn, k // bk)
    return pl.pallas_call(
        functools.partial(_matmul_kernel, n_k=grid[2]),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), a.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=compiler_params(
            tile_vmem_bytes(bm, bn, bk, a.dtype.itemsize)),
        interpret=interpret,
    )(a, b)
