"""The family modules: the parent's ArchConfigs and work counts to the
float, found by the family's name alone, and the in-place SSD decode
kernel's roofline priced from its call shapes."""
import json
import os
from types import SimpleNamespace

import pytest

import families
import work
from adapter import arch_config

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
MAMBA2_370M = os.path.join(BENCH, "configs", "mamba2-370m.json")
TINY_COLO = os.path.join(BENCH, "tests", "data", "tiny-colo.json")


def models(path):
    with open(path) as f:
        return json.load(f)["models"]


# ArchConfig fields set from each model entry, as the adapter set them
# before the families had modules of their own
ARCHS = {
    (MAMBA2_370M, "mamba2"): dict(
        name="mamba2-370m", family="ssm", num_layers=48, d_model=1024,
        num_heads=0, num_kv_heads=0, d_ff=0, vocab_size=50280,
        ssm_state=128, ssm_expand=2, ssm_head_dim=64, ssm_chunk=256,
        sub_quadratic=True, dtype="bfloat16", norm_eps=1e-05),
    (TINY_COLO, "yi"): dict(
        name="tiny-yi", family="dense", num_layers=8, d_model=64,
        num_heads=4, num_kv_heads=2, d_ff=128, vocab_size=512, head_dim=16,
        rope_theta=5000000.0, dtype="bfloat16", norm_eps=1e-05),
    (TINY_COLO, "mamba2"): dict(
        name="tiny-mamba2", family="ssm", num_layers=16, d_model=64,
        num_heads=0, num_kv_heads=0, d_ff=0, vocab_size=512, ssm_state=16,
        ssm_expand=2, ssm_head_dim=16, ssm_chunk=32, sub_quadratic=True,
        dtype="bfloat16", norm_eps=1e-05),
}

# (model, prompt, decode, prefill chunks) of a few served tasks, and the
# FLOPs and bytes work.totals gave for them before the families had
# modules of their own
WORK = {
    MAMBA2_370M: (4, [("mamba2", 256, 32, [256]),
                      ("mamba2", 512, 64, [256, 256]),
                      ("mamba2", 1024, 128, [256] * 4)],
                  5605306073088.0, 217170100224.0),
    TINY_COLO: (2, [("yi", 128, 32, [128]), ("mamba2", 256, 32, [128, 128]),
                    ("yi", 128, 8, [64, 64])],
                1037385728.0, 82882560.0),
}


@pytest.mark.parametrize("path,role", sorted(ARCHS),
                         ids=lambda x: os.path.basename(str(x)))
def test_arch_config_as_before(path, role):
    from repro.models.base import ArchConfig
    assert arch_config(models(path)[role]) == ArchConfig(**ARCHS[path, role])


@pytest.mark.parametrize("path", sorted(WORK), ids=os.path.basename)
def test_work_totals_as_before(path):
    rows, spec, flops, nbytes = WORK[path]
    tasks = [{"model": m, "prompt": p, "decode": d} for m, p, d, _ in spec]
    records = [{"prefill_chunks": c} for *_, c in spec]
    assert work.totals(models(path), tasks, records, rows) == {
        "flops": flops, "bytes": nbytes}


def test_unknown_family_names_its_module():
    with pytest.raises(ValueError, match="bench/families/granite.py"):
        families.of({"family": "granite"})


def roofline_window(shapes, peaks=True):
    trace = {"kernels": {"ssd_decode": {"shapes": shapes}}}
    return SimpleNamespace(
        trace=trace, models=models(MAMBA2_370M),
        peaks={"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}
        if peaks else None)


def reader(name):
    import run
    return run.metric_reader(name)


def test_ssd_decode_roofline_prices_each_call_shape():
    read = reader("ssd_decode_roofline")
    # one layer of mamba2-370m at 4 and at 16 rows: state [rows, 32, 64,
    # 128] f32 read and written, and the small operands
    b4 = 4 * (2 * 4 * 32 * 64 * 128 + 2 * 4 * 32 + 2 * 4 * 32 * 64
              + 2 * 4 * 128)
    b16 = 4 * (2 * 16 * 32 * 64 * 128 + 2 * 16 * 32 + 2 * 16 * 32 * 64
               + 2 * 16 * 128)
    shapes = {"ssd_decode_inplace (f32[4,32,64],": [10 * b4 / 819e9 / 0.5,
                                                    10],
              "ssd_decode_inplace (f32[16,32,64],": [b16 / 819e9 / 0.8, 1]}
    least = (10 * b4 + b16) / 819e9
    spent = 10 * b4 / 819e9 / 0.5 + b16 / 819e9 / 0.8
    assert read(roofline_window(shapes)) == pytest.approx(100 * least / spent)


@pytest.mark.parametrize("shapes", [
    {},                                                # no call traced
    {"ssd_decode_inplace (f32[4,16,64],": [1e-3, 9]},  # not this model's
    {"ssd_decode_inplace (f32[2,4,32,64],": [1e-3, 9]},  # no 3-d readout
])
def test_ssd_decode_roofline_reads_nothing_it_cannot_price(shapes):
    assert reader("ssd_decode_roofline")(roofline_window(shapes)) is None


def test_ssd_decode_roofline_needs_trace_and_peaks():
    read = reader("ssd_decode_roofline")
    assert read(roofline_window({}, peaks=False)) is None
    w = roofline_window({})
    w.trace = None
    assert read(w) is None
