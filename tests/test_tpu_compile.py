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
