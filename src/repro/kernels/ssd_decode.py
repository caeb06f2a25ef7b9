"""ssd_decode: Pallas kernel for one layer's recurrent SSD decode step
(Mamba2), updating that layer's slice of the stacked state in place.

A deep SSM stack keeps every layer's float32 state in one array, here
in the kernel's layout [L, B, H, P, N]: the state size N on the lanes,
so a (P, N) = (64, 128) head tile fills whole (8, 128) vregs.  Per
(row, head block) grid cell the kernel reads the block of layer ``l``
once, forms

    h_new = h * dA + (dt * x) outer B            (float32)

writes ``h_new`` back over the same block (the state is aliased from
input to output, and ``l`` is a scalar-prefetch argument of the
block's index map, so no other layer is touched), and reduces the
block it holds against C for the readout y = sum_n C[n] h_new[..., n].
One read and one write of the layer's state per step, and no copy of
the stack.  The rest of the decode step (projections, conv window, D
skip, gate) stays in jnp (models/ssm.py).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_decode_kernel(layer_ref, h_ref, da_ref, dt_ref, x_ref, b_ref, c_ref,
                       y_ref, h_out_ref):
    del layer_ref                    # used by the index maps only
    h = h_ref[0, 0]                  # [hb, P, N]
    da = da_ref[0]                   # [hb, 1]
    u = dt_ref[0] * x_ref[0]         # [hb, P]: dt * x
    hn = h * da[:, :, None] + u[:, :, None] * b_ref[0][None]
    h_out_ref[0, 0] = hn
    y_ref[0] = jnp.sum(hn * c_ref[0][None], axis=-1)


def ssd_decode_update(h: jnp.ndarray, layer: jnp.ndarray, dA: jnp.ndarray,
                      dt: jnp.ndarray, x: jnp.ndarray, B: jnp.ndarray,
                      C: jnp.ndarray, block_heads: int,
                      interpret: bool = True):
    """One decode step of layer ``layer`` of the stacked state.

    h: [L, B, H, P, N] float32 (aliased: the returned stack is ``h``
    with layer ``layer`` replaced); layer: int32 scalar; dA, dt: [B, H];
    x: [B, H, P]; B, C: [B, N], all float32.  ``block_heads`` must
    divide H.  Returns (y [B, H, P], h_new stack)."""
    L, Bsz, H, P, N = h.shape
    assert H % block_heads == 0, (H, block_heads)
    hb = block_heads
    state = pl.BlockSpec((1, 1, hb, P, N), lambda r, j, l: (l[0], r, j, 0, 0))
    per_head = pl.BlockSpec((1, hb, 1), lambda r, j, l: (r, j, 0))
    per_row = pl.BlockSpec((1, 1, N), lambda r, j, l: (r, 0, 0))
    rows = pl.BlockSpec((1, hb, P), lambda r, j, l: (r, j, 0))
    y, h_new = pl.pallas_call(
        _ssd_decode_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(Bsz, H // hb),
            in_specs=[state, per_head, per_head, rows, per_row, per_row],
            out_specs=[rows, state]),
        out_shape=[jax.ShapeDtypeStruct((Bsz, H, P), jnp.float32),
                   jax.ShapeDtypeStruct(h.shape, h.dtype)],
        # operand 1 is the state (operand 0 is the prefetched layer)
        input_output_aliases={1: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), h, dA[..., None],
      dt[..., None], x, B[:, None], C[:, None])
    return y, h_new
