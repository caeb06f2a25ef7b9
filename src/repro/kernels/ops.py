"""jit'd public wrappers for the Pallas kernels: padding to tile
boundaries, budget-driven tile selection (the CaMDN candidate bridge),
KernelPlan dispatch (the grant -> kernel execution link), and the
interpret-mode switch (CPU validation vs TPU execution).

Every wrapper takes ``interpret=None``, which resolves when the wrapper
is traced: compiled Mosaic kernels on a TPU backend, the Pallas
interpreter anywhere else.  Pass ``interpret=False`` to compile for a
TPU that is described but not attached."""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.plan import FfnPlan
from repro.core.vmem import TileConfig, lower_matmul_tile
from repro.kernels import quant as kquant
from repro.kernels.block_fused_ffn import block_fused_ffn
from repro.kernels.cache_matmul import cache_matmul, cache_matmul_quant
from repro.kernels.flash_attention import (flash_attention,
                                           flash_attention_quantized)
from repro.kernels.ssd_decode import ssd_decode_update
from repro.kernels.ssd_scan import ssd_chunk


def _interpret(flag: Optional[bool]) -> bool:
    """Resolve a wrapper's ``interpret`` argument at trace time."""
    return jax.default_backend() != "tpu" if flag is None else flag


def _pad_to(x: jnp.ndarray, axis: int, mult: int) -> jnp.ndarray:
    n = x.shape[axis]
    pad = (-n) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


@functools.partial(jax.jit, static_argnames=("tile", "interpret"))
def planned_matmul(a: jnp.ndarray, b: jnp.ndarray, tile: TileConfig,
                   interpret: Optional[bool] = None) -> jnp.ndarray:
    """Matmul through an explicit, already-lowered tile — the KernelPlan
    dispatch point: the tile comes from the allocator's grant via
    core/plan.lower_selection, not from local re-enumeration."""
    m, k = a.shape
    _, n = b.shape
    ap = _pad_to(_pad_to(a, 0, tile.bm), 1, tile.bk)
    bp = _pad_to(_pad_to(b, 0, tile.bk), 1, tile.bn)
    out = cache_matmul(ap, bp, tile, interpret=_interpret(interpret))
    return out[:m, :n]


@functools.partial(jax.jit, static_argnames=("pages", "interpret"))
def budgeted_matmul(a: jnp.ndarray, b: jnp.ndarray, pages: int = 64,
                    interpret: Optional[bool] = None) -> jnp.ndarray:
    """Matmul through the tile candidate selected for a page budget."""
    m, k = a.shape
    _, n = b.shape
    tile = lower_matmul_tile(m, n, k, a.dtype.itemsize, pages)
    return planned_matmul(a, b, tile, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("plan", "interpret"))
def planned_ffn(x: jnp.ndarray, wg: jnp.ndarray, wu: jnp.ndarray,
                wd: jnp.ndarray, plan: FfnPlan,
                interpret: Optional[bool] = None) -> jnp.ndarray:
    """SwiGLU FFN executed the way the plan's candidate prescribes:

      LBM (plan.fused)  -> block_fused_ffn; the hidden activation never
                           leaves VMEM (zero DRAM for intermediates).
      LWM (tiled)       -> three cache_matmul launches with the plan's
                           tiles; the hidden tensors round-trip HBM.

    x: [S, d]; wg/wu: [d, f]; wd: [f, d].
    """
    if plan.fused:
        return fused_ffn(x, wg, wu, wd, block_s=plan.block_s,
                         block_f=plan.block_f, interpret=interpret)
    g = planned_matmul(x, wg, plan.up_tile, interpret=interpret)
    u = planned_matmul(x, wu, plan.up_tile, interpret=interpret)
    h = (jax.nn.silu(g.astype(jnp.float32)) * u.astype(jnp.float32)
         ).astype(x.dtype)
    return planned_matmul(h, wd, plan.down_tile, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("tile", "interpret"))
def planned_matmul_quant(a: jnp.ndarray, b: jnp.ndarray,
                         b_scale: jnp.ndarray, tile: TileConfig,
                         interpret: Optional[bool] = None) -> jnp.ndarray:
    """Dequant-fused planned matmul: ``b`` pre-quantized (int8/fp8)
    with per-column scales ``b_scale`` [1, N] (kernels.quant
    .quantize_cols).  The B operand streams at quantized width through
    the same grant-lowered tile as :func:`planned_matmul`."""
    m, k = a.shape
    _, n = b.shape
    ap = _pad_to(_pad_to(a, 0, tile.bm), 1, tile.bk)
    bp = _pad_to(_pad_to(b, 0, tile.bk), 1, tile.bn)
    sp = _pad_to(b_scale, 1, tile.bn)
    out = cache_matmul_quant(ap, bp, sp, tile, interpret=_interpret(interpret))
    return out[:m, :n]


@functools.partial(jax.jit, static_argnames=("plan", "interpret"))
def planned_ffn_quant(x: jnp.ndarray, wg, wg_s, wu, wu_s, wd, wd_s,
                      plan: FfnPlan, interpret: Optional[bool] = None
                      ) -> jnp.ndarray:
    """SwiGLU FFN over pre-quantized weights (per-column scales), each
    GEMM through the dequant-fused tiled kernel with the plan's tiles.
    Quantized weights always execute tiled (LWM): the fused LBM kernel
    keeps native weights — quantization exists to survive *tight*
    grants, where the plan is tiled anyway."""
    tile_up = plan.up_tile if plan.up_tile is not None else \
        lower_matmul_tile(x.shape[0], wg.shape[1], x.shape[1], 1, plan.vmem_pages)
    tile_dn = plan.down_tile if plan.down_tile is not None else tile_up
    g = planned_matmul_quant(x, wg, wg_s, tile_up, interpret=interpret)
    u = planned_matmul_quant(x, wu, wu_s, tile_up, interpret=interpret)
    h = (jax.nn.silu(g.astype(jnp.float32)) * u.astype(jnp.float32)
         ).astype(x.dtype)
    return planned_matmul_quant(h, wd, wd_s, tile_dn, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("causal", "block_q", "block_kv",
                                             "kv_dtype", "interpret"))
def attention(q, k, v, causal: bool = True, block_q: int = 128,
              block_kv: int = 128, kv_dtype: str = "native",
              interpret: Optional[bool] = None):
    """Flash attention; ``kv_dtype`` != "native" quantizes K/V per row
    and runs the dequant-fused kernel (the plan-lowered prefill path of
    a precision-downgraded tenant)."""
    S = q.shape[2]
    bq = min(block_q, S)
    bkv = min(block_kv, k.shape[2])
    qp = _pad_to(q, 2, bq)
    kp = _pad_to(k, 2, bkv)
    vp = _pad_to(v, 2, bkv)
    if kv_dtype != "native":
        kq, ks = kquant.quantize_rows(kp, kv_dtype)
        vq, vs = kquant.quantize_rows(vp, kv_dtype)
        out = flash_attention_quantized(
            qp, kq, vq, ks[..., 0], vs[..., 0], causal=causal,
            block_q=bq, block_kv=bkv, interpret=_interpret(interpret))
        return out[:, :, :S, :]
    out = flash_attention(qp, kp, vp, causal=causal, block_q=bq,
                          block_kv=bkv, interpret=_interpret(interpret))
    return out[:, :, :S, :]


@functools.partial(jax.jit, static_argnames=("block_s", "block_f",
                                             "interpret"))
def fused_ffn(x, wg, wu, wd, block_s: int = 256, block_f: int = 512,
              interpret: Optional[bool] = None):
    S = x.shape[0]
    bs = min(block_s, S)
    xp = _pad_to(x, 0, bs)
    out = block_fused_ffn(xp, wg, wu, wd, block_s=bs,
                          block_f=min(block_f, wg.shape[1]),
                          interpret=_interpret(interpret))
    return out[:S]


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_intra_chunk(x, dt, A, B, C, chunk: int = 256,
                    interpret: Optional[bool] = None):
    return ssd_chunk(x, dt, A, B, C, chunk, interpret=_interpret(interpret))


# Heads per grid block of the in-place decode update: (16, 64, 128) f32
# is 512 KiB of state per block, 8 blocks per layer at batch 4.  On a
# v5e, 16 heads took 18.55 us per layer against 20.29 for 8 and 18.42
# for all 32 (one read and one write of 8.4 MB; a plain copy of the
# same blocks took 16.9).
SSD_DECODE_BLOCK_HEADS = 16


def ssd_decode_block_heads(heads: int) -> int:
    """Heads per block: the largest multiple of 8 up to
    ``SSD_DECODE_BLOCK_HEADS`` that divides ``heads``, else all of them
    (a block may always span the whole head axis)."""
    for hb in range(SSD_DECODE_BLOCK_HEADS, 0, -8):
        if heads % hb == 0:
            return hb
    return heads


@functools.partial(jax.jit, static_argnames=("interpret",))
def ssd_decode_inplace(state, layer, dA, dt, x, B, C,
                       interpret: Optional[bool] = None):
    """The recurrent SSD decode step of layer ``layer`` of a stacked
    state ``state`` [L, B, H, N, P] (the model's layout), in place.

    The kernel works on [L, B, H, P, N]; the swaps in and out are
    layout bitcasts on a TPU, which stores this state N-minor at rest
    (a P-minor (64, 128) tile would pad P to 128 lanes).  dA, dt:
    [B, H]; x: [B, H, P]; B, C: [B, N], float32.  Returns
    (y [B, H, P] = C . h_new, the stack with layer ``layer`` updated)."""
    y, h = ssd_decode_update(jnp.swapaxes(state, -1, -2), layer, dA, dt, x,
                             B, C, ssd_decode_block_heads(state.shape[2]),
                             interpret=_interpret(interpret))
    return y, jnp.swapaxes(h, -1, -2)
