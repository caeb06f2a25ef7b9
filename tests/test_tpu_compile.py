"""The main path's Pallas kernels compile for a v5e chip at published
widths — compiled by the TPU compiler for a chip that is described, not
attached (``jax.experimental.topologies``), with ``interpret=False``.

Interpret mode hides what only Mosaic refuses: block shapes off the
(8, 128) tiling and working sets above the scoped-VMEM limit.  The
topology is described inside a module fixture, never at import: one
process at a time may load the TPU library, and every test worker
imports this file.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.plan import lower_ffn
from repro.core.vmem import (CHIP_VMEM_BYTES, DEFAULT_SCOPED_VMEM_BYTES,
                             VMEM_PAGES, fused_ffn_block_s,
                             fused_ffn_pages, fused_ffn_vmem_bytes,
                             lower_matmul_tile)
from repro.kernels import ops
from repro.kernels.ssd_decode import ssd_decode_update
from repro.models.base import all_archs, get_arch

YI = get_arch("yi-9b")
NEMO = get_arch("mistral-nemo-12b")
SEQ_BLOCK = 128      # the serving decode seq_block (max(batch, LANE))


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "no TPU compiler"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _compile_text(fn, shapes, one_chip) -> str:
    args = [jax.ShapeDtypeStruct(s, jnp.bfloat16, sharding=one_chip)
            for s in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text, "no Mosaic kernel in the program"
    return text


@pytest.mark.parametrize("rows", [1, SEQ_BLOCK])
@pytest.mark.parametrize("block_f", [128, 256])
def test_fused_ffn_compiles_at_yi_width(one_chip, block_f, rows):
    d, f = YI.d_model, YI.d_ff
    bs = fused_ffn_block_s(SEQ_BLOCK, 2)
    _compile_text(
        lambda x, wg, wu, wd: ops.fused_ffn(x, wg, wu, wd, block_s=bs,
                                            block_f=block_f,
                                            interpret=False),
        [(rows, d), (d, f), (d, f), (f, d)], one_chip)


@pytest.mark.parametrize("pages", [4, 16, 64, 256])
def test_cache_matmul_compiles_at_granted_tile(one_chip, pages):
    """The gate/up projection of yi-9b through the tile each page budget
    lowers to."""
    m, n, k = SEQ_BLOCK, YI.d_ff, YI.d_model
    tile = lower_matmul_tile(m, n, k, 2, pages)
    _compile_text(
        lambda a, b: ops.planned_matmul(a, b, tile, interpret=False),
        [(m, k), (k, n)], one_chip)


def test_flash_attention_compiles_at_yi_heads(one_chip):
    B, S = 1, 512
    H, Hkv, hd = YI.num_heads, YI.num_kv_heads, YI.hd
    assert (H, Hkv, hd) == (32, 4, 128)
    _compile_text(
        lambda q, k, v: ops.attention(q, k, v, causal=True,
                                      interpret=False),
        [(B, H, S, hd), (B, Hkv, S, hd), (B, Hkv, S, hd)], one_chip)


def test_largest_fused_plan_compiles_above_default_vmem(one_chip):
    """The widest fused FFN plan the lowering emits — mistral-nemo-12b
    at the whole page pool — claims several times the default scoped
    VMEM limit; it compiles only because the kernel raises its limit
    from the same claim."""
    d, f = NEMO.d_model, NEMO.d_ff
    plan = lower_ffn(SEQ_BLOCK, d, f, 2, VMEM_PAGES, True)
    assert plan.fused and plan.block_f == 1024
    claim = fused_ffn_vmem_bytes(plan.block_s, plan.block_f, d, 2)
    assert claim == plan.vmem_bytes
    assert 3 * DEFAULT_SCOPED_VMEM_BYTES < claim < CHIP_VMEM_BYTES
    _compile_text(
        lambda x, wg, wu, wd: ops.planned_ffn(x, wg, wu, wd, plan=plan,
                                              interpret=False),
        [(SEQ_BLOCK, d), (d, f), (d, f), (f, d)], one_chip)


MAMBA = get_arch("mamba2-370m")
SERVE_BATCH = 4      # the rows per task of the mamba2 serving cell


def _stack_copies(text: str, shape: str):
    """Instructions of the compiled program that copy an array of
    ``shape`` (any layout, either order of its two minor axes)."""
    dims = shape.split(",")
    swapped = ",".join(dims[:-2] + [dims[-1], dims[-2]])
    return [ln for ln in text.splitlines()
            if re.search(rf"= f32\[({shape}|{swapped})\]\S* copy", ln)]


@pytest.mark.parametrize("block_heads", [8, 16, 32])
def test_ssd_decode_kernel_compiles_at_mamba2_heads(one_chip, block_heads):
    """The in-place decode update at mamba2-370m's head tile, for each
    block the head axis allows: a donated stack is updated without a
    copy."""
    L, B, H = MAMBA.num_layers, SERVE_BATCH, MAMBA.ssm_heads
    N, P = MAMBA.ssm_state, MAMBA.ssm_head_dim
    f32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32, sharding=one_chip)
    fn = jax.jit(lambda h, l, da, dt, x, b, c: ssd_decode_update(
        h, l, da, dt, x, b, c, block_heads, interpret=False),
        donate_argnums=(0,))
    text = fn.lower(f32(L, B, H, P, N),
                    jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip),
                    f32(B, H), f32(B, H), f32(B, H, P), f32(B, N),
                    f32(B, N)).compile().as_text()
    assert "tpu_custom_call" in text
    assert not _stack_copies(text, f"{L},{B},{H},{P},{N}")


def test_stacked_ssm_decode_epoch_copies_no_state(one_chip, monkeypatch):
    """The serving cell's fused decode epoch at published widths (48
    layers, 4 rows, 8 steps): the stacked float32 state is read and
    written by the kernel alone, with no copy of the whole stack (the
    swaps to the kernel's [.., P, N] are layout bitcasts).  The wrapper
    is steered to the compiled kernel: on a host without a TPU it would
    pick the interpreter."""
    from repro.models import model as M
    from repro.models.transformer import ssm_state_in_place
    assert ssm_state_in_place(MAMBA)
    monkeypatch.setattr(ops, "_interpret", lambda flag: False)
    jax.clear_caches()
    specs = jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip),
        M.decode_epoch_input_specs(MAMBA, SERVE_BATCH, 1152)[:4])
    core = M.make_decode_epoch(MAMBA)
    fn = jax.jit(lambda p, c, t, i: core(p, c, t, i, None, k=8, kv_len=256),
                 donate_argnums=(1,))
    text = fn.lower(*specs).compile().as_text()
    jax.clear_caches()
    assert "tpu_custom_call" in text
    L, H = MAMBA.num_layers, MAMBA.ssm_heads
    shape = f"{L},{SERVE_BATCH},{H},{MAMBA.ssm_state},{MAMBA.ssm_head_dim}"
    assert f"f32[{shape}]" in text
    assert not _stack_copies(text, shape)


@pytest.mark.parametrize("arch", sorted(all_archs()))
def test_fused_block_f_is_lane_aligned_for_every_grant(arch):
    """Mosaic accepts a block whose lane dimension is a multiple of 128
    or the whole axis: no grant may lower to any other fused block_f."""
    cfg = all_archs()[arch]
    d, f = cfg.d_model, max(cfg.d_ff, cfg.d_model)
    for seq_block in (SEQ_BLOCK, 2 * SEQ_BLOCK):
        lo = fused_ffn_pages(seq_block, d, f, 2)
        for pages in range(lo, VMEM_PAGES + 1):
            plan = lower_ffn(seq_block, d, f, 2, pages, True)
            assert plan.fused, (arch, seq_block, pages)
            assert plan.block_f % 128 == 0 or plan.block_f == f, \
                (arch, seq_block, pages, plan.block_f)
