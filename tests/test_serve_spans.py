"""The serving loop's span recorder (launch/spans.py), tier-1 on the CPU.

One tiny run with arrivals and prompts: its top-level spans tile the
run without overlapping, each task's stamps add up to the TTFT the
server reports, the host walls it reports are the recorder's sums, the
per-epoch counters count every budgeted decode row-step, a profiler
trace carries the spans under their bare names, the epoch and prefill
programs keep the names the benchmark's trace patterns match, and the
benchmark's readers of the recorder read the run.
"""
import dataclasses
import glob
import importlib.util
import json
import os
import re
import shutil
import tempfile
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.launch import spans as S
from repro.launch.serve import MultiTenantServer
from repro.models.base import get_arch
from repro.sim.driver import TenantSpec

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BATCH = 2


def _arrivals():
    """Two bursts: two same-shape mamba2 prompts (they bucket once both
    decode) beside a yi prompt, then one more mamba2 prompt."""
    return [TenantSpec("mamba2-370m", arrive_at=0.0, n_inferences=16,
                       prompt_len=128),
            TenantSpec("mamba2-370m", arrive_at=0.0, n_inferences=16,
                       prompt_len=128),
            TenantSpec("yi-9b", arrive_at=0.0, n_inferences=8,
                       prompt_len=256),
            TenantSpec("mamba2-370m", arrive_at=24.0, n_inferences=8,
                       prompt_len=256)]


@pytest.fixture(scope="module")
def served():
    srv = MultiTenantServer([], batch=BATCH, max_len=512, epoch_len=8)
    srv.enqueue(_arrivals())
    out = srv.run()
    return srv, out


def _task_ids(out):
    return [tid for tid, info in out["tenants"].items() if info["prompt_len"]]


def test_top_level_spans_tile_the_run(served):
    srv, out = served
    rec = srv.spans
    top = sorted((s, e, n) for n, s, e, _, _, depth in rec.records
                 if depth == 0)
    assert {n for _, _, n in top} == set(S.NAMES)
    for (s0, e0, n0), (s1, e1, n1) in zip(top, top[1:]):
        assert e0 <= s1, f"{n0} [{s0}, {e0}] overlaps {n1} [{s1}, {e1}]"
    run_end = max(e for _, e, n in top if n == S.FINISH)
    covered = sum(e - s for s, e, _ in top)
    assert covered >= 0.9 * (run_end - rec.t0), \
        f"spans cover {covered:.4f} s of a {run_end - rec.t0:.4f} s run"
    # every nested span lies inside a top-level one
    for n, s, e, _, _, depth in rec.records:
        if depth:
            assert any(ts <= s and e <= te for ts, te, _ in top), n


def test_task_stamps_order_and_add_up_to_ttft(served):
    _, out = served
    tasks = out["host"]["tasks"]
    tids = _task_ids(out)
    assert len(tids) == 4 and set(tids) <= set(tasks)
    for tid in tids:
        st = tasks[tid]
        seq = [st[k] for k in S.STAMPS]
        assert all(v is not None for v in seq), (tid, st)
        assert seq == sorted(seq), (tid, st)
        assert abs((st["first_token"] - st["due"])
                   - out["tenants"][tid]["ttft_s"]) < 1e-6


def test_host_walls_are_the_recorders_sums(served):
    srv, out = served
    h = out["host"]
    spans = h["spans"]
    assert h["admit_wall_s"] == spans[S.ADMIT]["seconds"]
    assert h["sched_wall_s"] == spans[S.PLAN]["seconds"]
    assert h["dispatch_wall_s"] == spans[S.DISPATCH]["seconds"]
    assert h["epochs"] == spans[S.DISPATCH]["count"] == len(h["epoch_log"])
    assert spans[S.ADMIT]["count"] == 4
    assert spans[S.FIRST_TOKEN]["count"] == 4
    assert len(h["epoch_dispatch_walls"]) == h["epochs"]
    assert h["epoch_compiles"] == [e["programs_built"]
                                   for e in h["epoch_log"]]
    assert sum(h["epoch_compiles"]) > 0       # a cold server builds some
    assert sum(h["epoch_sched_walls"]) == pytest.approx(h["sched_wall_s"],
                                                        abs=1e-5)
    # one planning wall per dispatched epoch, and the last planning call,
    # which found nothing to dispatch
    assert len(h["epoch_sched_walls"]) == h["epochs"] + 1


def test_epoch_counters_count_every_budgeted_row_step(served):
    _, out = served
    log = out["host"]["epoch_log"]
    assert [e["epoch"] for e in log] == list(range(len(log)))
    budget = {tid: info["tokens"] // BATCH - 1
              for tid, info in out["tenants"].items() if info["prompt_len"]}
    assert budget == {tid: n for tid, n in zip(_task_ids(out),
                                               (16, 16, 8, 8))}
    assert sum(e["decode_row_steps"] for e in log) == \
        sum(BATCH * n for n in budget.values())
    assert sum(e["prefill_chunks"] for e in log) == sum(
        len(out["tenants"][t]["prefill_chunks"]) for t in budget)
    assert sum(e["prefill_tokens"] for e in log) == BATCH * (128 + 128 +
                                                             256 + 256)
    for e in log:
        assert e["decode_steps"] <= 8
        assert e["decode_row_steps"] <= e["decode_steps"] * BATCH * 4
        assert (e["decode_items"] == 0) == (e["decode_steps"] == 0)
    # the two same-shape prompts decode as one bucket of 2 x BATCH rows
    assert max(e["decode_row_steps"] - 8 * BATCH * (e["decode_items"] - 1)
               for e in log if e["decode_steps"] == 8) >= 8 * 2 * BATCH


def test_profiler_host_plane_holds_the_bare_spans(served):
    srv, _ = served
    from jax.profiler import ProfileData
    log_dir = tempfile.mkdtemp(prefix="spans-trace-")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    try:
        srv.enqueue([TenantSpec("mamba2-370m", arrive_at=0.0,
                                n_inferences=8, prompt_len=128)])
        out = srv.run()
    finally:
        jax.profiler.stop_trace()
    try:
        path = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                         recursive=True)[-1]
        data = ProfileData.from_file(path)
        seen = {}
        for plane in data.planes:
            if not plane.name.startswith("/host"):
                continue
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("serve."):
                        seen.setdefault(ev.name, []).append(dict(ev.stats))
    finally:
        shutil.rmtree(log_dir, ignore_errors=True)
    assert set(seen) == set(S.NAMES), sorted(seen)
    tid = _task_ids(out)[-1]
    assert {"epoch": 0, "tid": tid} in seen[S.ADMIT]
    assert any(st.get("tid") == tid for st in seen[S.FIRST_TOKEN])
    assert [st["epoch"] for st in seen[S.DISPATCH]] == \
        list(range(out["host"]["epochs"]))
    assert all(set(st) == {"epoch"} for st in seen[S.PLAN] + seen[S.FINISH])


def test_programs_keep_the_names_the_trace_patterns_match():
    with open(os.path.join(ROOT, "bench", "trace_names.json")) as f:
        patterns = json.load(f)["programs"]
    srv = MultiTenantServer([], batch=1, max_len=256)
    t = srv._admit_spec(TenantSpec("mamba2-370m", prompt_len=128,
                                   n_inferences=8))
    prefill = srv._prefill_fn(t.cfg.name).lower(
        t.params, t.caches, jnp.asarray(t.prompt[:, :128]), jnp.int32(0),
        t.enc, kv_len=128)
    key = (("single", t.cfg.name, None, 8, srv._kv_len(136)),)
    fused = srv._build_fused_jit(key).lower(*srv._abstract_epoch_args(key))
    for kind, lowered in (("prefill_chunk", prefill),
                          ("decode_epoch", fused)):
        module = lowered.compile().as_text().split(",", 1)[0].split()[-1]
        # the device trace names an execution "<HLO module>(<program id>)"
        assert re.search(patterns[kind], f"{module}(1)"), (kind, module)


def _reader(name):
    path = os.path.join(ROOT, "bench", "metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def test_readers_of_the_recorder_read_the_run(served):
    _, out = served
    tids = _task_ids(out)
    w = SimpleNamespace(host=out["host"],
                        records=[{"tid": tid,
                                  "ttft_s": out["tenants"][tid]["ttft_s"]}
                                 for tid in tids])
    values = {n: _reader(n)(w) for n in ("admit.wait_ms_p90",
                                         "prefill.first_token_ms_p50",
                                         "decode.rows_per_step")}
    assert all(np.isfinite(v) and v > 0 for v in values.values()), values
    log = out["host"]["epoch_log"]
    assert values["decode.rows_per_step"] == (
        sum(e["decode_row_steps"] for e in log)
        / sum(e["decode_steps"] for e in log))
    # a result without the recorder's keys (an older server) reads nothing
    bare = SimpleNamespace(host={"epochs": 1}, records=w.records)
    assert all(_reader(n)(bare) is None for n in values)


def test_plan_is_recorded_in_segments_around_admissions():
    rec = S.SpanRecorder()
    with rec.plan():
        with rec.span(S.ADMIT, "t0:a"):
            pass
        with rec.span(S.ADMIT, "t1:a"):
            with rec.span(S.FIRST_TOKEN, "t1:a"):
                pass
    with rec.span(S.DISPATCH):
        pass
    rec.log_epoch(decode_items=0)
    with rec.plan():
        pass
    names = [(n, epoch, depth) for n, _, _, epoch, _, depth in rec.records]
    assert names == [(S.PLAN, 0, 0), (S.ADMIT, 0, 0), (S.PLAN, 0, 0),
                     (S.FIRST_TOKEN, 0, 1), (S.ADMIT, 0, 0),
                     (S.PLAN, 0, 0), (S.DISPATCH, 0, 0), (S.PLAN, 1, 0)]
    assert len(rec.per_epoch(S.PLAN)) == 2
    assert rec.totals()[S.PLAN]["count"] == 4


def test_ssm_inplace_row_steps_count_the_stacked_state_rows(served):
    """Each epoch counts the decode row-steps whose SSM state went
    through the in-place kernel: none for the shallow (unrolled) mamba2
    and the yi tasks of the shared run; all of them for a mamba2 deep
    enough for the stacked state."""
    _, out = served
    assert all(e["ssm_inplace_row_steps"] == 0
               for e in out["host"]["epoch_log"])
    assert out["host"]["ssm_inplace_share"] == 0.0
    deep = dataclasses.replace(get_arch("mamba2-370m").reduced(),
                               num_layers=10, name="mamba2-370m-10L")
    srv = MultiTenantServer([], batch=1, max_len=128, epoch_len=4)
    srv.enqueue([TenantSpec(deep, arrive_at=0.0, n_inferences=8,
                            prompt_len=32)])
    out = srv.run()
    log = out["host"]["epoch_log"]
    assert sum(e["decode_row_steps"] for e in log) == 8
    assert all(e["ssm_inplace_row_steps"] == e["decode_row_steps"]
               for e in log)
    assert out["host"]["ssm_inplace_share"] == 1.0
