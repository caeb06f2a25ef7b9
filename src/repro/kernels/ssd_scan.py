"""ssd_scan: Pallas kernel for the intra-chunk SSD computation (Mamba2).

Per (batch*head, chunk) grid cell: builds the decay matrix L from the
within-chunk cumulative log-decay, computes the chunk-local output
Y_diag = (C B^T o L o dt) X and the chunk summary state
S = (decay_out * dt * B)^T X.  The inter-chunk recurrence (a cheap
[B,H,N,P] scan) stays in jnp (models/ssm.py) — it is latency-trivial
and keeps the kernel free of cross-block carries.

The decay matrix and score tiles are VMEM-resident (the long-reuse
data); x/B/C stream per chunk (bypass).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.vmem import compiler_params, ssd_chunk_vmem_bytes


def _ssd_chunk_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref,
                      y_ref, state_ref, *, chunk: int):
    x = x_ref[0, ...].astype(jnp.float32)        # [Q, P]
    dt = dt_ref[0, ...].astype(jnp.float32)      # [Q, 1] -> [Q]
    dt = dt[:, 0]
    A = a_ref[0, 0]                              # scalar (per head)
    B = b_ref[0, ...].astype(jnp.float32)        # [Q, N]
    C = c_ref[0, ...].astype(jnp.float32)        # [Q, N]

    dA = -dt * A                                 # [Q], negative
    cum = jnp.cumsum(dA)
    diff = cum[:, None] - cum[None, :]
    qi = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    qj = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    L = jnp.exp(jnp.where(qi >= qj, diff, -jnp.inf))          # [Q, Q]

    scores = jnp.dot(C, B.T, preferred_element_type=jnp.float32)
    w = scores * L * dt[None, :]
    y_ref[0, ...] = jnp.dot(w, x, preferred_element_type=jnp.float32
                            ).astype(y_ref.dtype)

    decay_out = jnp.exp(cum[-1] - cum)                         # [Q]
    state = jnp.dot((B * (decay_out * dt)[:, None]).T, x,
                    preferred_element_type=jnp.float32)        # [N, P]
    state_ref[0, 0, ...] = state.astype(state_ref.dtype)


def ssd_chunk(x: jnp.ndarray, dt: jnp.ndarray, A: jnp.ndarray,
              B: jnp.ndarray, C: jnp.ndarray, chunk: int,
              interpret: bool = True):
    """Intra-chunk SSD.

    x: [BH, S, P]; dt: [BH, S]; A: [BH]; B, C: [BH, S, N].
    Returns (y_diag [BH, S, P], states [BH, S//chunk, N, P]).
    """
    BH, S, P = x.shape
    N = B.shape[-1]
    assert S % chunk == 0
    n_c = S // chunk
    grid = (BH, n_c)
    y, states = pl.pallas_call(
        functools.partial(_ssd_chunk_kernel, chunk=chunk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, chunk, P), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, 1), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, 1), lambda b, c: (b, 0)),
            pl.BlockSpec((1, chunk, N), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, N), lambda b, c: (b, c, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, P), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, 1, N, P), lambda b, c: (b, c, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, S, P), jnp.float32),
            jax.ShapeDtypeStruct((BH, n_c, N, P), jnp.float32),
        ],
        compiler_params=compiler_params(ssd_chunk_vmem_bytes(chunk)),
        interpret=interpret,
    )(x, dt[..., None], A[:, None], B, C)
    return y, states
