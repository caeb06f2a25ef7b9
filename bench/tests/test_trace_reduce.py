"""The trace reduction on a small recorded trace and on hand-made
records whose answers are known."""
import json
import os

import pytest

import trace_reduce as tr

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
with open(os.path.join(BENCH, "trace_names.json")) as f:
    NAMES = json.load(f)
with open(os.path.join(BENCH, "tests", "data", "trace_excerpt.json")) as f:
    EXCERPT = json.load(f)
# the fused (LBM) and one tiled (LWM) FFN kernel as the TPU compiler
# names them, cut before the backend config
FUSED = ('%fused_ffn.1 = bf16[8,4096]{1,0:T(8,128)(2,1)} custom-call(%x.1, '
         '%a.1, %b.1, %c.1), custom_call_target="tpu_custom_call", '
         'operand_layout_constraints={bf16[8,4096]{1,0}}')
TILED = ('%planned_matmul.4 = bf16[128,11008]{1,0:T(8,128)(2,1)S(1)} '
         'custom-call(%pad.7, %b.1), custom_call_target="tpu_custom_call"')
WHILE = ('%while.3 = (s32[]) while(s32[] %t), condition=%region_1, '
         'body=%region_0')


def record(ops, host=(), modules=()):
    names = sorted({o[0] for o in ops})
    return {"devices": [{"name": "/device:TPU:0", "op_names": names,
                         "ops": [[names.index(n), s, d] for n, s, d in ops],
                         "modules": [list(m) for m in modules]}],
            "host": [list(h) for h in host]}


def test_recorded_trace():
    out = tr.reduce(EXCERPT, NAMES)
    dev = EXCERPT["devices"][0]
    span = (max(s + d for _, s, d in dev["ops"])
            - min(s for _, s, _ in dev["ops"]))
    assert out["window_s"] == pytest.approx(span * 1e-9)
    assert 0 < out["busy_s"] <= out["window_s"]
    assert len(out["breakdown"]["device_ops"]) == 10
    assert all(t > 0 for _, t in out["breakdown"]["idle_gaps"])
    # the admission of the first tasks runs no decode epoch yet
    assert out["programs"]["decode_epoch"]["count"] == 0
    assert out["kernels"]["ffn_tiled"]["count"] == 0


def test_union_gaps_kernels_and_programs():
    ns = 1_000_000
    trace = record(
        ops=[(WHILE, 0, 10 * ns), (FUSED, 1 * ns, 4 * ns),
             (TILED, 3 * ns, 4 * ns), (TILED, 20 * ns, 5 * ns),
             ("%copy.1 = f32[4] copy(f32[4] %a)", 38 * ns, 2 * ns)],
        host=[("main", "bench.window", 0, 40 * ns),
              ("main", "PjitFunction(fused)", 9 * ns, 12 * ns)],
        modules=[("jit_fused(123)", 0, 10 * ns),
                 ("jit_serve_prefill_chunk(9)", 20 * ns, 5 * ns)])
    out = tr.reduce(trace, NAMES)
    assert out["window_s"] == pytest.approx(0.040)
    assert out["busy_s"] == pytest.approx(0.017)  # [0,10) [20,25) [38,40)
    gaps = out["breakdown"]["idle_gaps"]
    assert [g for _, g in gaps] == pytest.approx([0.013, 0.010])
    assert gaps[1][0] == "PjitFunction(fused)"
    assert out["kernels"]["ffn_fused"]["seconds"] == pytest.approx(0.004)
    assert out["kernels"]["ffn_fused"]["count"] == 1
    assert out["kernels"]["ffn_tiled"]["count"] == 2
    assert out["kernels"]["ffn_tiled"]["shapes"] == {
        "planned_matmul bf16[128,11008]": [pytest.approx(0.009), 2]}
    assert out["programs"]["decode_epoch"]["count"] == 1
    assert out["programs"]["prefill_chunk"]["seconds"] == pytest.approx(0.005)
    ops = dict(out["breakdown"]["device_ops"])
    assert "while (s32[])" not in ops                 # loops not counted
    assert ops["planned_matmul bf16[128,11008]"] == pytest.approx(0.009)


def test_trace_that_stops_short():
    """Events after the device tracer's buffer filled are missing: the
    window ends with the last recorded operation, not with the host's."""
    ns = 1_000_000
    trace = record(ops=[(FUSED, 0, 4 * ns), (TILED, 6 * ns, 4 * ns)],
                   host=[("main", "bench.window", 0, 100 * ns)])
    out = tr.reduce(trace, NAMES)
    assert out["window_s"] == pytest.approx(0.010)
    assert out["busy_s"] == pytest.approx(0.008)


def test_label():
    assert tr.label(FUSED) == "fused_ffn bf16[8,4096]"
