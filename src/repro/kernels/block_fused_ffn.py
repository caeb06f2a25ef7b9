"""block_fused_ffn: the LBM (layer-block mapping) kernel.

Paper III-C(2): LBM keeps inter-layer intermediates entirely on-chip
with *zero DRAM allocation*.  On TPU the layer block is the SwiGLU FFN
(three matmuls + two elementwise layers); this kernel fuses the whole
block so the (block_s x d_ff) hidden activation lives only in a VMEM
scratch accumulator — it never exists in HBM, which is precisely the
LBM guarantee.  The unfused path (ref.py) writes both hidden tensors to
HBM; the roofline delta between the two is the LBM saving, measured in
benchmarks/roofline.py.

Grid: (S/block_s, d_ff/block_f) — f innermost; weights stream (bypass),
x tile + output accumulator are the resident set.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.vmem import compiler_params, fused_ffn_vmem_bytes


def _ffn_kernel(x_ref, wg_ref, wu_ref, wd_ref, o_ref, acc_ref, *, n_f: int):
    fi = pl.program_id(1)

    @pl.when(fi == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...]                                     # [bs, d]
    g = jnp.dot(x, wg_ref[...], preferred_element_type=jnp.float32)
    u = jnp.dot(x, wu_ref[...], preferred_element_type=jnp.float32)
    h = (jax.nn.silu(g) * u).astype(x.dtype)           # [bs, bf] — VMEM only
    acc_ref[...] += jnp.dot(h, wd_ref[...], preferred_element_type=jnp.float32)

    @pl.when(fi == n_f - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def block_fused_ffn(x: jnp.ndarray, wg: jnp.ndarray, wu: jnp.ndarray,
                    wd: jnp.ndarray, *, block_s: int = 256,
                    block_f: int = 512, interpret: bool = True
                    ) -> jnp.ndarray:
    """y = silu(x@wg) * (x@wu) @ wd.  x: [S, d]; wg/wu: [d, f]; wd: [f, d]."""
    S, d = x.shape
    d2, f = wg.shape
    assert d == d2 and wd.shape == (f, d)
    bs, bf = min(block_s, S), min(block_f, f)
    assert S % bs == 0 and f % bf == 0
    grid = (S // bs, f // bf)
    return pl.pallas_call(
        functools.partial(_ffn_kernel, n_f=grid[1]),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bs, d), lambda i, j: (i, 0)),
            pl.BlockSpec((d, bf), lambda i, j: (0, j)),
            pl.BlockSpec((d, bf), lambda i, j: (0, j)),
            pl.BlockSpec((bf, d), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((bs, d), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((S, d), x.dtype),
        scratch_shapes=[pltpu.VMEM((bs, d), jnp.float32)],
        compiler_params=compiler_params(
            fused_ffn_vmem_bytes(bs, bf, d, x.dtype.itemsize)),
        interpret=interpret,
    )(x, wg, wu, wd)
