"""Benchmark harness: one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV lines (paper targets inline)
plus the roofline summary when dry-run reports are present, and dumps a
machine-readable ``benchmarks/BENCH_nec.json`` (per-figure
``us_per_call``, serving tokens/s, NEC line-requests/s) so the perf
trajectory is recorded run-over-run.

``--smoke`` runs the fast perf-path canary used by CI: the analytic
figures, the NEC hot-path microbenchmark, a short plan-lowered serving
run, the serving-throughput benchmark (serial reference vs the
epoch-pipelined loop), and the mixed prefill+decode continuous-batching
benchmark (interleaved cache-aware chunked prefill vs sequential
static-batching admission, tokens/s AND p95 TTFT ->
``benchmarks/BENCH_serve.json``), so regressions in the grant ->
Selection -> KernelPlan -> Pallas path and the serving pipeline fail
fast.  ``--check`` (CI) compares the fresh numbers against the
*committed* BENCH_nec.json / BENCH_serve.json and fails on a >2x
``us_per_call`` (or pipelined/mixed tokens/s, or mixed p95 TTFT)
regression; ``--budget-s N`` fails if the whole smoke run exceeds a
wall-time budget.

``--prefix`` runs the session-replay prefix-dedup benchmark (dedup on
vs off: prefill-token savings, warm-arrival p95 TTFT, bit-identical
decode) and records the ``prefix`` entry; ``--fleet`` runs the
4-replica fleet-scaling benchmark under forced host devices;
``--quant`` runs the precision-for-residency benchmark (int8 KV vs
native on an oversubscribed page pool: effective-pages gain, tokens/s
ratio, decode-accuracy bound, plus the analytic quantized-kernel
roofline gate under ``--check``) and records the ``quant`` entry;
``--faults`` runs the fault-injection suite (preempt/resume decode
bit-identity, replica-kill failover recovery p95, 2x-oversubscription
overload shedding) and records the ``faults`` entry.  All modes merge
into BENCH_serve.json without disturbing the other modes' entries.
"""
from __future__ import annotations

import json
import pathlib
import sys
import time

# `python benchmarks/run.py` puts benchmarks/ (not the repo root) on
# sys.path; add the root so `from benchmarks import ...` resolves
sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

BENCH_JSON = pathlib.Path(__file__).resolve().parent / "BENCH_nec.json"
BENCH_SERVE_JSON = pathlib.Path(__file__).resolve().parent / "BENCH_serve.json"
# entries faster than this are timer noise; the CI gate skips them
CHECK_FLOOR_US = 10_000.0


def nec_microbench() -> None:
    """NEC hot-path throughput: execute one cache-resident mapping
    candidate's full command stream (the codegen validation path — the
    innermost loop of the repo) and report line-requests/s."""
    from benchmarks.common import emit
    from repro.core.cache import CacheConfig, SharedCache
    from repro.core.codegen import run_candidate
    from repro.core.mapping import MapperConfig, map_layer_lwm
    from repro.core.nec import Nec
    from repro.core.types import GemmDims, LayerKind, LayerSpec

    mcfg = MapperConfig()
    layer = LayerSpec("bench", LayerKind.GEMM, (GemmDims(1024, 2048, 1024),),
                      input_bytes=1024 * 1024, output_bytes=1024 * 2048,
                      weight_bytes=1024 * 2048, elem_bytes=1)
    cand = map_layer_lwm(layer, mcfg.npu_subspace_bytes, mcfg)
    cache = SharedCache(CacheConfig())
    nec = Nec(cache)
    run_candidate(layer, cand, cache, nec, "t")          # warm the arena
    before = nec.traffic.accesses
    t0 = time.time()
    n = 20
    for _ in range(n):
        run_candidate(layer, cand, cache, nec, "t")
    dt = time.time() - t0
    reqs = nec.traffic.accesses - before
    emit("nec_microbench", dt / n * 1e6,
         f"{reqs / dt / 1e6:.1f}M line-requests/s ({cand.loops[0].residency})",
         extra={"line_requests_per_s": round(reqs / dt)})


def serve_bench() -> dict:
    """Serving-throughput benchmark: the serial reference loop (one
    scheduled, charged, jit-dispatched step per token — the pre-pipeline
    behaviour) vs the epoch-pipelined loop (K-step scan decode under one
    grant, donated caches, KV-window reads, fused per-epoch dispatch,
    one-epoch-ahead host scheduling) on the smoke workload: 3 tenants,
    128 pages.  Asserts the equivalence contract while measuring —
    per-tenant outputs bit-identical, NEC dram_total unchanged — and
    writes benchmarks/BENCH_serve.json (the CI regression baseline)."""
    import numpy as np

    from benchmarks.common import emit
    from repro.launch.serve import MultiTenantServer

    archs = ["olmoe-1b-7b", "yi-9b", "mamba2-370m"]
    kw = dict(batch=1, max_len=2048, total_pages=128)
    warm, steps, epoch_len, reps = 8, 48, 8, 3
    serial = MultiTenantServer(archs, pipeline=False, **kw)
    pipe = MultiTenantServer(archs, epoch_len=epoch_len, **kw)
    serial.run(warm)    # compile warmup: excluded from the measured runs
    pipe.run(warm)
    # median of `reps` interleaved measurements: the serial loop's wall
    # is noisy (its per-step full-cache copies are allocator-sensitive)
    rates_s, rates_p = [], []
    for _ in range(reps):
        out_s = serial.run(steps)
        out_p = pipe.run(steps)
        rates_s.append(out_s["tokens_per_s"])
        rates_p.append(out_p["tokens_per_s"])
    out_s["tokens_per_s"] = float(np.median(rates_s))
    out_p["tokens_per_s"] = float(np.median(rates_p))
    for tid in out_s["tenants"]:
        a = out_s["tenants"][tid]["output"]
        b = out_p["tenants"][tid]["output"]
        assert np.array_equal(a, b), f"pipelined decode diverged for {tid}"
        assert (out_s["tenants"][tid]["lbm_frac"]
                == out_p["tenants"][tid]["lbm_frac"]), tid
    assert out_s["dram_bytes"] == out_p["dram_bytes"], "epoch charging drift"
    speedup = out_p["tokens_per_s"] / max(out_s["tokens_per_s"], 1e-9)
    if speedup < 1.5:
        # machine-dependent: warn here, let the --check gate (fresh vs
        # committed pipelined tokens/s) make the pass/fail call
        print(f"[bench] WARNING pipelined speedup only {speedup:.2f}x",
              file=sys.stderr)
    emit("serve_serial", out_s["wall_s"] * 1e6,
         f"{out_s['tokens_per_s']:.1f} tok/s (per-step reference)",
         extra={"tokens_per_s": round(out_s["tokens_per_s"], 1)})
    emit("serve_pipelined", out_p["wall_s"] * 1e6,
         f"{out_p['tokens_per_s']:.1f} tok/s | {speedup:.2f}x vs serial",
         extra={"tokens_per_s": round(out_p["tokens_per_s"], 1),
                "speedup_vs_serial": round(speedup, 2)})
    return {
        "schema": 1,
        "workload": {"archs": archs, "batch": kw["batch"],
                     "max_len": kw["max_len"], "pages": kw["total_pages"],
                     "steps": steps, "epoch_len": epoch_len},
        "serial": {"tokens_per_s": round(out_s["tokens_per_s"], 1)},
        "pipelined": {"tokens_per_s": round(out_p["tokens_per_s"], 1),
                      "speedup_vs_serial": round(speedup, 2)},
    }


def serve_mixed_bench() -> dict:
    """Continuous-batching benchmark: a mixed prefill+decode workload
    (two resident decode tenants + three prompt arrivals joining
    mid-run) served with interleaved cache-aware chunked prefill vs the
    sequential static-batching baseline (arrivals wait for the batch to
    drain, then whole-prompt prefill, head-of-line).  Each mode first
    replays the scenario once to warm the arch/shape-keyed compile
    caches, then the two servers alternate measured scenario replays
    and the medians are compared — interleaving cancels the bursty
    host-throttling drift a single back-to-back pair is exposed to
    (same reasoning as serve_bench), and the step budget is sized so
    repeated replays never cross a KV-window recompile.  Asserts the
    equivalence contract — decode token streams bit-identical between
    the admission modes — and reports aggregate tokens/s and p95 TTFT
    for the BENCH_serve.json `mixed` entry (the CI regression
    baseline)."""
    import numpy as np

    from benchmarks.common import emit
    from repro.launch.serve import MultiTenantServer
    from repro.sim.driver import TenantSpec

    residents = ["olmoe-1b-7b", "mamba2-370m"]

    def specs():
        # LANE-multiple 1024-token prompts: every chunk/kv window stays
        # on the 128 grid (where chunked prefill is robustly
        # bit-stable), and the prompts are long enough that prefill
        # attention dominates — chunked prefill reads only the live
        # LANE-rounded prefix per chunk instead of the whole-prompt
        # S x S score matrix, which is where the interleaved mode's
        # tokens/s edge comes from on serial hardware
        return [TenantSpec("olmoe-1b-7b", arrive_at=2.0 + 2 * i,
                           n_inferences=12, prompt_len=1024)
                for i in range(3)]

    # residents decode 24 steps per replay: warm + 3 measured replays
    # stay inside one 128-slot KV window (indices 0..96), so the warm
    # run covers every fused-epoch program the measured replays execute
    steps, reps = 24, 3
    servers, metrics = {}, {}
    for mode in ("interleaved", "sequential"):
        srv = MultiTenantServer(residents, batch=1, max_len=2048,
                                total_pages=128, epoch_len=8,
                                tenants=specs(), admission=mode)
        srv.run(steps)            # compile warmup: same scenario, cold
        servers[mode] = srv
        metrics[mode] = {"tps": [], "ttft": [], "out": None}
    for _ in range(reps):         # alternate: drift hits both modes
        for mode, srv in servers.items():
            srv.enqueue(specs())
            out = srv.run(steps)
            metrics[mode]["tps"].append(out["tokens_per_s"])
            metrics[mode]["ttft"].append(out["p95_ttft_s"])
            metrics[mode]["out"] = out
    a, b = metrics["interleaved"]["out"], metrics["sequential"]["out"]
    for tid in a["tenants"]:
        assert np.array_equal(a["tenants"][tid]["output"],
                              b["tenants"][tid]["output"]), \
            f"admission modes diverged for {tid}"
    a = {"tokens_per_s": float(np.median(metrics["interleaved"]["tps"])),
         "p95_ttft_s": float(np.median(metrics["interleaved"]["ttft"])),
         "wall_s": a["wall_s"]}
    b = {"tokens_per_s": float(np.median(metrics["sequential"]["tps"])),
         "p95_ttft_s": float(np.median(metrics["sequential"]["ttft"])),
         "wall_s": b["wall_s"]}
    tps_ratio = a["tokens_per_s"] / max(b["tokens_per_s"], 1e-9)
    ttft_ratio = b["p95_ttft_s"] / max(a["p95_ttft_s"], 1e-9)
    if tps_ratio < 1.0 or ttft_ratio < 1.0:
        # machine-dependent: warn here, let the --check gate (fresh vs
        # committed) make the pass/fail call
        print(f"[bench] WARNING continuous batching won only "
              f"{tps_ratio:.2f}x tokens/s, {ttft_ratio:.2f}x p95 TTFT",
              file=sys.stderr)
    emit("serve_mixed_sequential", b["wall_s"] * 1e6,
         f"{b['tokens_per_s']:.1f} tok/s | p95 TTFT "
         f"{b['p95_ttft_s'] * 1e3:.0f}ms (static batching)",
         extra={"tokens_per_s": round(b["tokens_per_s"], 1),
                "p95_ttft_ms": round(b["p95_ttft_s"] * 1e3, 1)})
    emit("serve_mixed_interleaved", a["wall_s"] * 1e6,
         f"{a['tokens_per_s']:.1f} tok/s | p95 TTFT "
         f"{a['p95_ttft_s'] * 1e3:.0f}ms | {tps_ratio:.2f}x tok/s, "
         f"{ttft_ratio:.2f}x TTFT vs sequential",
         extra={"tokens_per_s": round(a["tokens_per_s"], 1),
                "p95_ttft_ms": round(a["p95_ttft_s"] * 1e3, 1)})
    return {
        "workload": {"residents": residents, "arrivals": 3,
                     "prompt_lens": [1024, 1024, 1024],
                     "decode_budget": 12, "steps": steps, "pages": 128,
                     "epoch_len": 8},
        "interleaved": {
            "tokens_per_s": round(a["tokens_per_s"], 1),
            "p95_ttft_ms": round(a["p95_ttft_s"] * 1e3, 1)},
        "sequential": {
            "tokens_per_s": round(b["tokens_per_s"], 1),
            "p95_ttft_ms": round(b["p95_ttft_s"] * 1e3, 1)},
        "tokens_per_s_ratio": round(tps_ratio, 2),
        "p95_ttft_ratio": round(ttft_ratio, 2),
        "decode_bit_identical": True,
    }


def serve_fleet_bench() -> dict:
    """Fleet scaling benchmark (the `fleet` BENCH_serve.json entry): 8
    identical prompt tenants served by a 4-replica FleetServer over a
    forced 4-device host mesh, vs one single-device pipelined server
    carrying all 8 (the monolith baseline).

    Metric: **critical-path aggregate tokens/s over an emulated mesh**.
    A forced-device CPU "mesh" shares one set of host cores, so the
    interleaved fleet's raw wall measures host contention, not the
    chip-parallel fleet the mesh models.  Instead each replica's routed
    scenario is replayed in isolation on a fresh single-device server
    (asserting decode streams bit-identical to the fleet run — the
    routing/replay contract) and the fleet aggregate is
    ``total_tokens / max(replica walls)``: every replica executes on its
    own chip, so the slowest replica is the fleet's critical path.  The
    speedup vs the monolith is then ``monolith_wall / max(replica
    walls)`` — near-linear (≈N) when routing balances the replicas, and
    environment-stable because both sides are single-device walls on
    the same host.  The observed interleaved-fleet numbers (tokens/s,
    per-replica page utilization, routing balance) ride along."""
    import dataclasses

    import numpy as np

    from benchmarks.common import emit
    from repro.launch import env
    from repro.launch.serve import FleetServer, MultiTenantServer
    from repro.sim.driver import TenantSpec

    env.set_host_device_count(4)
    print(f"[bench] fleet env: {env.describe()}", file=sys.stderr)
    N, steps, reps = 4, 24, 3
    kw = dict(batch=1, max_len=2048, epoch_len=8)

    def specs(seed_base=None):
        # 8 identical specs arriving together: least-loaded routing
        # round-robins them 2 per replica (tiebreak on active count)
        return [TenantSpec("olmoe-1b-7b", arrive_at=0.0, n_inferences=12,
                           prompt_len=256,
                           seed=None if seed_base is None
                           else seed_base + i)
                for i in range(8)]

    fleet = FleetServer(n_replicas=N, pages_per_replica=128,
                        tenants=specs(), **kw)
    out_f = fleet.run(steps)
    scen = fleet.replica_scenarios()
    counts = [len(s) for s in scen]
    assert max(counts) - min(counts) <= 1, f"routing imbalance: {counts}"

    # per-replica isolated replay: round 0 replays the exact routed
    # specs (global-admission seeds pinned) — the bit-identical check —
    # and warms the compile caches; the measured rounds replay the same
    # shapes under fresh seed-offset tenant identities on the warmed
    # server (reused seeds would collide tenant ids)
    total_tokens = 0
    walls = []
    for r in range(N):
        srv = MultiTenantServer([], total_pages=128, tenants=scen[r], **kw)
        res = srv.run(steps)
        for tid, info in res["tenants"].items():
            assert np.array_equal(out_f["tenants"][tid]["output"],
                                  info["output"]), \
                f"fleet replica r{r} diverged from single-device for {tid}"
        ws, toks = [], []
        for m in range(1, reps + 1):
            srv.enqueue([dataclasses.replace(s, seed=s.seed + 10_000 * m)
                         for s in scen[r]])
            rr = srv.run(steps)
            ws.append(rr["wall_s"])
            toks.append(rr["tokens_served"])
        walls.append(float(np.median(ws)))
        total_tokens += int(np.median(toks))

    # monolith baseline: all 8 tenants on ONE pipelined single-device
    # server with the same per-chip page budget (same warm protocol)
    mono = MultiTenantServer([], total_pages=128,
                             tenants=specs(seed_base=0), **kw)
    mono.run(steps)
    mws = []
    for m in range(1, reps + 1):
        mono.enqueue(specs(seed_base=10_000 * m))
        mws.append(mono.run(steps)["wall_s"])
    wall_mono = float(np.median(mws))

    crit_wall = max(walls)
    aggregate = total_tokens / crit_wall
    mono_rate = total_tokens / wall_mono
    speedup = wall_mono / crit_wall
    utils = {rep["replica"]: round(rep["page_util_mean"], 3)
             for rep in out_f["replicas"]}
    if speedup < 3.0:
        print(f"[bench] WARNING fleet speedup only {speedup:.2f}x",
              file=sys.stderr)
    emit("serve_fleet_single", wall_mono * 1e6,
         f"{mono_rate:.1f} tok/s (monolith, all 8 tenants one device)",
         extra={"tokens_per_s": round(mono_rate, 1)})
    emit("serve_fleet", crit_wall * 1e6,
         f"{aggregate:.1f} tok/s critical-path aggregate | "
         f"{speedup:.2f}x vs single-device | balance "
         f"{out_f['page_util_balance']:.2f}",
         extra={"tokens_per_s": round(aggregate, 1),
                "speedup_vs_single": round(speedup, 2)})
    return {
        "workload": {"arch": "olmoe-1b-7b", "tenants": 8,
                     "prompt_len": 256, "decode_budget": 12,
                     "steps": steps, "pages_per_replica": 128,
                     "epoch_len": 8, "n_replicas": N},
        "metric": "critical-path aggregate over an emulated mesh: "
                  "total_tokens / max(isolated replica walls)",
        "aggregate_tokens_per_s": round(aggregate, 1),
        "single_device_tokens_per_s": round(mono_rate, 1),
        "speedup_vs_single": round(speedup, 2),
        "replica_walls_s": [round(w, 3) for w in walls],
        "replica_tenants": counts,
        "observed_interleaved_tokens_per_s": round(out_f["tokens_per_s"], 1),
        "page_util": utils,
        "page_util_balance": round(out_f["page_util_balance"], 2),
        "decode_bit_identical": True,
    }


def serve_faults_bench() -> dict:
    """Fault-injection benchmark (the `faults` BENCH_serve.json entry),
    three acceptance scenarios on a forced 4-device host:

    * **preempt/resume** — one tenant preempted mid-decode (KV
      checkpoint, pages freed, resumed two epochs later) must produce a
      decode stream bit-identical to an uninterrupted run;
    * **failover** — a 2-replica fleet loses r0 at an epoch boundary;
      every moved tenant must complete on a survivor and the recovery
      p95 (survivor TTFT clocked from the kill) is recorded and gated;
    * **overload** — a 2x-oversubscribed arrival burst against a small
      page pool must defer/shed (bounded queue, deadline-aware) with
      ZERO unhandled exceptions and an empty queue at end of run.
    """
    import dataclasses

    import numpy as np

    from benchmarks.common import emit
    from repro.launch import env
    from repro.launch.serve import FleetServer, MultiTenantServer
    from repro.sim.driver import TenantSpec
    from repro.sim.faults import FaultEvent, FaultPlan

    env.set_host_device_count(4)
    print(f"[bench] faults env: {env.describe()}", file=sys.stderr)
    arch = "mamba2-370m"
    kw = dict(batch=1, max_len=128, epoch_len=4)

    # --- preempt -> resume bit-identity --------------------------------
    spec = TenantSpec(arch, prompt_len=32, n_inferences=24)
    ref = MultiTenantServer([], total_pages=64,
                            tenants=[dataclasses.replace(spec)], **kw)
    out_ref = ref.run(24)
    plan = FaultPlan([FaultEvent(step=8, kind="preempt", hold_epochs=2)])
    srv = MultiTenantServer([], total_pages=64, faults=plan,
                            tenants=[dataclasses.replace(spec)], **kw)
    out_p = srv.run(24)
    (tid, info_ref), = out_ref["tenants"].items()
    info_p = out_p["tenants"][tid]
    bit_identical = bool(
        info_ref["output"].shape == info_p["output"].shape
        and np.array_equal(info_ref["output"], info_p["output"]))
    n_preempt = out_p["faults"]["preemptions"]

    # --- replica-kill failover -----------------------------------------
    fleet = FleetServer(
        n_replicas=2, pages_per_replica=64, faults=FaultPlan(
            [FaultEvent(step=8, kind="replica_kill", target="r0")]),
        tenants=[TenantSpec(arch, prompt_len=32, n_inferences=24,
                            arrive_at=float(i)) for i in range(3)],
        **kw)
    out_f = fleet.run(24)
    fo = out_f["failover"]
    moved = fo["moved"]
    all_completed = bool(moved) and all(
        out_f["tenants"][m["tid"]]["replica"] == m["to"]
        and out_f["tenants"][m["tid"]]["output"].shape[-1] > 0
        and m["tid"] in fo["recovery_s"]
        for m in moved)
    recovery_p95 = fo["recovery_p95_s"]

    # --- overload burst -------------------------------------------------
    burst = [TenantSpec(arch, prompt_len=96, n_inferences=8, arrive_at=0.5,
                        qos_ms=(None if i % 3 == 0 else 50.0 * (i + 1)))
             for i in range(12)]
    unhandled = 0
    try:
        # queue_limit below the burst size: the overflow sheds on
        # arrival, the rest defers against the tiny pool
        osrv = MultiTenantServer([], total_pages=8, queue_limit=8,
                                 queue_deadline_s=24.0, tenants=[], **kw)
        osrv.enqueue(burst)
        out_o = osrv.run(16)
    except Exception as exc:   # the whole point: overload must not raise
        unhandled = 1
        out_o = {"overload": {"shed_count": 0, "deferrals": 0,
                              "queued": 1, "shed": []},
                 "tenants": {}}
        print(f"[bench] FAULTS overload raised: {exc!r}", file=sys.stderr)
    ov = out_o["overload"]
    shed_rate = ov["shed_count"] / len(burst)

    emit("serve_fault_recovery",
         (recovery_p95 or 0.0) * 1e6,
         f"failover recovery p95 {1e3 * (recovery_p95 or 0):.0f}ms | "
         f"{len(moved)} moved, completed={all_completed} | "
         f"preempt/resume bit-identical={bit_identical}",
         extra={"moved": len(moved),
                "bit_identical": bit_identical})
    emit("serve_fault_overload", shed_rate * 1e6,
         f"2x burst: {ov['shed_count']}/{len(burst)} shed, "
         f"{ov['deferrals']} deferrals, {unhandled} unhandled",
         extra={"shed_rate": round(shed_rate, 3)})
    return {
        "workload": {"arch": arch, "steps": 24, "epoch_len": 4,
                     "burst_arrivals": len(burst),
                     "burst_pages": 8, "n_replicas": 2},
        "preempt": {
            "decode_bit_identical": bit_identical,
            "preemptions": n_preempt,
            "recovery_s": out_p["faults"]["recovery_s"],
        },
        "failover": {
            "killed": fo["killed"],
            "moved": len(moved),
            "all_completed": all_completed,
            "recovery_p95_s": (round(recovery_p95, 3)
                               if recovery_p95 is not None else None),
        },
        "overload": {
            "shed_rate": round(shed_rate, 3),
            "shed_count": ov["shed_count"],
            "deferrals": ov["deferrals"],
            "queued_at_end": ov["queued"],
            "unhandled_exceptions": unhandled,
            "served": sum(1 for i in out_o["tenants"].values()
                          if i["tokens"] > 0),
        },
    }


def serve_prefix_bench() -> dict:
    """Prefix-dedup benchmark (the `prefix` BENCH_serve.json entry): a
    session-replay workload — 6 arrivals across 3 chat sessions sharing
    2 system prompts, each session returning for a second turn whose
    prompt extends its first — served twice by identical servers, one
    with ``prefix_dedup`` on and one off.

    Both servers first replay a different-seed copy of the scenario to
    warm the compile caches (including the dedup side's prefix-seeding
    jits), then alternate measured cold replays: before each, the dedup
    server's PrefixIndex is cleared, so every measured replay starts
    with an empty index and the savings measured are the true
    cold-session number (the warm run's resident system prefixes would
    otherwise turn every first arrival warm).  Asserts the equivalence
    contract — decode streams bit-identical between dedup on and off —
    and reports the prefill-token savings, the warm-arrival (prefix_hit
    > 0) p95 TTFT ratio, and the analytic ``shared_prefix_reuse``
    prediction the measured savings are cross-checked against."""
    import numpy as np

    from benchmarks.common import emit
    from repro.launch.serve import MultiTenantServer
    from repro.sim.driver import SessionArrivals
    from repro.sim.reuse import shared_prefix_reuse

    def workload(seed):
        # gap_s must outlast a producer's chunked prefill on the logical
        # clock: arrivals landing in the same admission wave as their
        # producer miss (nothing is registered until prefill completes)
        return SessionArrivals(models=["yi-9b"], n_sessions=3, turns=2,
                               n_prompts=2, prefix_len=512, turn_tokens=128,
                               gap_s=2.0, n_inferences=8, seed=seed)

    steps, reps = 24, 2
    # 192 pages: roomy enough that pool pressure does not LRU-evict the
    # resident prefixes mid-scenario (eviction-under-pressure is
    # exercised by the tests; this entry measures the dedup headroom)
    kw = dict(batch=1, max_len=1024, total_pages=192, epoch_len=8,
              steps_per_s=4.0)
    servers = {}
    for on in (True, False):
        srv = MultiTenantServer([], tenants=workload(999).specs(),
                                prefix_dedup=on, **kw)
        srv.run(steps)            # compile warmup: same shapes, cold
        servers[on] = srv
    predicted = shared_prefix_reuse(workload(0).specs(), align=128)

    metrics = {on: {"computed": [], "warm_p95": [], "tps": []}
               for on in servers}
    warm_tids = []
    for rep in range(reps):
        outs, new_tids = {}, {}
        for on, srv in servers.items():
            if on:
                # measured replays are COLD sessions: drop the previous
                # replay's resident prefixes (all tenants have departed,
                # so the index must drain completely)
                srv.control.prefix.clear()
                assert srv.control.prefix.stats()["entries"] == 0, \
                    "prefix entries survived clear(): tenant still attached"
            known = {t.tid for t in srv.tenants}
            before = sum(t.pf_computed for t in srv.tenants)
            srv.enqueue(workload(rep).specs())
            out = srv.run(steps)
            outs[on] = out
            new_tids[on] = [tid for tid in out["tenants"] if tid not in known]
            metrics[on]["computed"].append(out["prefill_computed"] - before)
            metrics[on]["tps"].append(out["tokens_per_s"])
        assert new_tids[True] == new_tids[False], "admission order diverged"
        for tid in new_tids[True]:
            assert np.array_equal(outs[True]["tenants"][tid]["output"],
                                  outs[False]["tenants"][tid]["output"]), \
                f"dedup changed the decode stream for {tid}"
        warm_tids = [tid for tid in new_tids[True]
                     if outs[True]["tenants"][tid]["prefix_hit"] > 0]
        assert warm_tids, "no warm arrivals: the session replay never hit"
        for on in servers:
            ttfts = [outs[on]["tenants"][tid]["ttft_s"] for tid in warm_tids]
            metrics[on]["warm_p95"].append(float(np.percentile(ttfts, 95)))
    prefix_stats = servers[True].control.prefix.stats()

    comp_on = float(np.median(metrics[True]["computed"]))
    comp_off = float(np.median(metrics[False]["computed"]))
    savings = 1.0 - comp_on / max(comp_off, 1e-9)
    p95_on = float(np.median(metrics[True]["warm_p95"]))
    p95_off = float(np.median(metrics[False]["warm_p95"]))
    ttft_ratio = p95_off / max(p95_on, 1e-9)
    if savings < 0.30 or ttft_ratio < 1.5:
        # machine-independent (savings) + machine-dependent (TTFT):
        # warn here, let the --check gate make the pass/fail call
        print(f"[bench] WARNING prefix dedup saved only "
              f"{savings * 100:.0f}% prefill tokens, {ttft_ratio:.2f}x "
              f"warm p95 TTFT", file=sys.stderr)
    emit("serve_prefix_off", p95_off * 1e6,
         f"{comp_off:.0f} prefill tok | warm p95 TTFT "
         f"{p95_off * 1e3:.0f}ms (dedup off)",
         extra={"prefill_computed": round(comp_off),
                "warm_p95_ttft_ms": round(p95_off * 1e3, 1)})
    emit("serve_prefix_on", p95_on * 1e6,
         f"{comp_on:.0f} prefill tok (-{savings * 100:.0f}%) | warm p95 "
         f"TTFT {p95_on * 1e3:.0f}ms | {ttft_ratio:.2f}x vs off",
         extra={"prefill_computed": round(comp_on),
                "warm_p95_ttft_ms": round(p95_on * 1e3, 1),
                "prefill_savings_pct": round(savings * 100, 1),
                "warm_ttft_ratio": round(ttft_ratio, 2)})
    return {
        "workload": {"arch": "yi-9b", "sessions": 3, "system_prompts": 2,
                     "turns": 2, "arrivals": 6, "prefix_len": 512,
                     "turn_tokens": 128, "decode_budget": 8,
                     "steps": steps, "pages": kw["total_pages"],
                     "epoch_len": kw["epoch_len"]},
        "dedup_on": {"prefill_computed": round(comp_on),
                     "warm_p95_ttft_ms": round(p95_on * 1e3, 1),
                     "tokens_per_s": round(
                         float(np.median(metrics[True]["tps"])), 1)},
        "dedup_off": {"prefill_computed": round(comp_off),
                      "warm_p95_ttft_ms": round(p95_off * 1e3, 1),
                      "tokens_per_s": round(
                          float(np.median(metrics[False]["tps"])), 1)},
        "prefill_savings_frac": round(savings, 3),
        "warm_ttft_ratio": round(ttft_ratio, 2),
        "warm_arrivals": len(warm_tids),
        "decode_bit_identical": True,
        "prefix_stats": prefix_stats,
        "predicted": {"dedup_frac": round(predicted["dedup_frac"], 3),
                      "dedup_tokens": predicted["dedup_tokens"],
                      "prompt_tokens": predicted["prompt_tokens"]},
    }


def _quant_decode_accuracy(kv_dtype: str = "int8", steps: int = 8) -> dict:
    """Model-level accuracy probe: yi-9b reduced decode with a quantized
    KV cache vs the native reference, teacher-forced on the native
    stream so every step's logits compare like-for-like.  Returns the
    min per-step cosine similarity and max abs logits error — the
    numbers the documented accuracy bound (cosine >= 0.999) gates."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.models import model as M
    from repro.models.base import get_arch
    from repro.models.transformer import (decode_step, init_caches,
                                          prefill_chunk)

    cfg = get_arch("yi-9b").reduced()
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    B, P = 1, 128
    max_len = P + steps
    toks = jax.random.randint(jax.random.PRNGKey(1), (B, P), 0,
                              cfg.vocab_size)
    streams = {}
    for kv in ("native", kv_dtype):
        caches = init_caches(params, cfg, B, max_len, kv_dtype=kv)
        logits, caches = prefill_chunk(params, toks, caches, jnp.int32(0),
                                       cfg)
        streams[kv] = {"caches": caches, "logits": [logits[:, -1:, :]]}
    cos_min, err_max = 1.0, 0.0
    token = jnp.argmax(streams["native"]["logits"][0][:, -1, :],
                       axis=-1)[:, None].astype(jnp.int32)
    for i in range(steps):
        nxt = None
        for kv, st in streams.items():
            logits, st["caches"] = decode_step(params, token,
                                               st["caches"],
                                               jnp.int32(P + i), cfg)
            st["logits"].append(logits[:, -1:, :])
            if kv == "native":
                nxt = jnp.argmax(logits[:, -1, :],
                                 axis=-1)[:, None].astype(jnp.int32)
        a = np.asarray(streams["native"]["logits"][-1], np.float64).ravel()
        b = np.asarray(streams[kv_dtype]["logits"][-1], np.float64).ravel()
        cos = float(a @ b / (np.linalg.norm(a) * np.linalg.norm(b)))
        cos_min = min(cos_min, cos)
        err_max = max(err_max, float(np.abs(a - b).max()))
        token = nxt
    return {"kv_dtype": kv_dtype, "steps": steps,
            "min_cosine": round(cos_min, 6),
            "max_abs_err": round(err_max, 4)}


def serve_quant_bench() -> dict:
    """Precision-for-residency benchmark (the `quant` BENCH_serve.json
    entry): three yi-9b tenants with 1024-token prompts and 12-step
    decode budgets admitted against a fixed 128-page pool, served by
    two identical servers — native KV vs int8 KV with per-page scales.

    At native width each tenant's KV working set wants ~64 pages, so
    three tenants oversubscribe the pool and the later reservations
    degrade; at int8 (+ per-row fp32 scales) the same working set
    prices at ~18 pages and every tenant stays fully resident — the
    ``effective_pages_gain`` is the per-tenant native/int8 reservation
    ratio (analytic, machine-independent; the >=1.8x CI floor).  Both
    servers warm once then alternate measured scenario replays
    (medians), reporting the quant/native tokens/s ratio (CI gates
    <2x regression; a ratio above 1.0 means the freed pages bought
    back more throughput than the dequant path costs).  The model-level
    accuracy probe rides along: int8-KV decode logits must stay within
    cosine >= 0.999 of the native reference (the documented bound)."""
    import numpy as np

    from benchmarks.common import emit
    from repro.launch.serve import MultiTenantServer, _kv_reserve_pages
    from repro.models.base import get_arch
    from repro.sim.driver import TenantSpec

    def specs():
        return [TenantSpec("yi-9b", arrive_at=0.0, n_inferences=12,
                           prompt_len=1024, param_seed=7,
                           prompt_seed=100 + i)
                for i in range(3)]

    cfg = get_arch("yi-9b").reduced()
    want_native = _kv_reserve_pages(cfg, 1, 1024, "native")
    want_int8 = _kv_reserve_pages(cfg, 1, 1024, "int8")
    pages_gain = want_native / want_int8

    steps, reps = 24, 3
    kw = dict(batch=1, max_len=2048, total_pages=128, epoch_len=8)
    servers, metrics = {}, {}
    for kv in ("native", "int8"):
        srv = MultiTenantServer([], tenants=specs(), kv_dtype=kv, **kw)
        srv.run(steps)            # compile warmup: same shapes, cold
        servers[kv] = srv
        metrics[kv] = {"tps": [], "reserved": [], "wanted": []}
    for _ in range(reps):         # alternate: drift hits both modes
        for kv, srv in servers.items():
            srv.enqueue(specs())
            out = srv.run(steps)
            metrics[kv]["tps"].append(out["tokens_per_s"])
            infos = list(out["tenants"].values())
            metrics[kv]["reserved"].append(
                sum(i["kv_reserved"] for i in infos))
            metrics[kv]["wanted"].append(
                sum(i["kv_wanted"] for i in infos))
    tps_n = float(np.median(metrics["native"]["tps"]))
    tps_q = float(np.median(metrics["int8"]["tps"]))
    ratio = tps_q / max(tps_n, 1e-9)
    resident_q = (metrics["int8"]["reserved"][-1]
                  == metrics["int8"]["wanted"][-1])
    degraded_n = (metrics["native"]["reserved"][-1]
                  < metrics["native"]["wanted"][-1])
    acc = _quant_decode_accuracy("int8")
    if ratio < 1.0:
        print(f"[bench] WARNING int8 KV tokens/s only {ratio:.2f}x native",
              file=sys.stderr)
    emit("serve_quant_native", 0.0,
         f"{tps_n:.1f} tok/s | kv {metrics['native']['reserved'][-1]}/"
         f"{metrics['native']['wanted'][-1]}p reserved (native)",
         extra={"tokens_per_s": round(tps_n, 1)})
    emit("serve_quant_int8", 0.0,
         f"{tps_q:.1f} tok/s ({ratio:.2f}x) | kv "
         f"{metrics['int8']['reserved'][-1]}/"
         f"{metrics['int8']['wanted'][-1]}p | {pages_gain:.2f}x effective "
         f"pages | cos {acc['min_cosine']:.5f}",
         extra={"tokens_per_s": round(tps_q, 1),
                "effective_pages_gain": round(pages_gain, 2)})
    return {
        "workload": {"arch": "yi-9b", "tenants": 3, "prompt_len": 1024,
                     "decode_budget": 12, "steps": steps, "pages": 128,
                     "epoch_len": kw["epoch_len"]},
        "native": {"tokens_per_s": round(tps_n, 1),
                   "kv_pages_per_tenant": want_native,
                   "fully_resident": not degraded_n},
        "int8": {"tokens_per_s": round(tps_q, 1),
                 "kv_pages_per_tenant": want_int8,
                 "fully_resident": resident_q},
        "effective_pages_gain": round(pages_gain, 2),
        "tokens_per_s_ratio": round(ratio, 2),
        "accuracy": acc,
        "accuracy_bound": {"min_cosine": 0.999},
    }


def serve_host_bench() -> dict:
    """Host-off-the-critical-path benchmark (the `host` BENCH_serve.json
    entry): three resident decode tenants plus prompt arrivals, served
    at epoch_len 8 / 4 / 2 with the batched Algorithm 1 planner and AOT
    fused-program precompile on.  Measures, per epoch length, tokens/s,
    arrival p95 TTFT, and the host sched wall vs the device dispatch
    wall; gates (in _check_serve) on the host staying off the critical
    path — sched wall < 30% of device wall, ZERO post-warmup program
    compiles — and on the epoch-length sweep showing a p95 TTFT
    reduction at a smaller epoch_len for <=5% tokens/s loss (the
    pipelined scheduler's latency/throughput knob is usable because the
    host no longer charges per-epoch overhead to the critical path)."""
    import numpy as np

    from benchmarks.common import emit
    from repro.launch import env
    from repro.launch.serve import MultiTenantServer
    from repro.sim.driver import TenantSpec

    residents = ["olmoe-1b-7b", "mamba2-370m", "yi-9b"]

    def specs():
        # LANE-multiple prompts (512 = 4 chunks of the 128 grid): every
        # chunk/kv window repeats across replays, so the warm replay
        # covers every program the measured replays execute
        return [TenantSpec("olmoe-1b-7b", arrive_at=2.0 + 2 * i,
                           n_inferences=12, prompt_len=512)
                for i in range(2)]

    steps, reps = 24, 3
    sweep_els = [8, 4, 2]
    servers = {}
    for el in sweep_els:
        # batch=8: enough device work per decode step that the fixed
        # per-epoch dispatch cost is measured against real epochs, not
        # toy ones (the regime the sweep's 5% throughput band assumes)
        srv = MultiTenantServer(residents, batch=8, max_len=2048,
                                total_pages=512, epoch_len=el,
                                tenants=specs(), aot_warmup=True)
        srv.run(steps)            # compile warmup: same scenario, cold
        srv.wait_aot()
        servers[el] = srv
    metrics = {el: {"tps": [], "ttft": [], "sched": [], "device": [],
                    "compiles": 0, "overlap": True, "host": None}
               for el in sweep_els}
    for _ in range(reps):         # alternate: drift hits every el alike
        for el, srv in servers.items():
            srv.enqueue(specs())
            out = srv.run(steps)
            h = out["host"]
            m = metrics[el]
            m["tps"].append(out["tokens_per_s"])
            m["ttft"].append(out["p95_ttft_s"])
            m["sched"].append(h["sched_wall_s"])
            m["device"].append(h["dispatch_wall_s"])
            m["compiles"] += sum(h["epoch_compiles"])
            m["overlap"] &= all(s < d for s, d in
                                zip(h["epoch_sched_walls"],
                                    h["epoch_dispatch_walls"]))
            m["host"] = h
    entry = {
        "workload": {"residents": residents, "arrivals": 2,
                     "prompt_len": 512, "decode_budget": 12, "batch": 8,
                     "steps": steps, "pages": 512,
                     "epoch_lens": sweep_els},
        "epoch_sweep": {},
    }
    for el in sweep_els:
        m = metrics[el]
        sched = float(np.median(m["sched"]))
        device = float(np.median(m["device"]))
        rec = {
            "tokens_per_s": round(float(np.median(m["tps"])), 1),
            "p95_ttft_ms": round(float(np.median(m["ttft"])) * 1e3, 1),
            "sched_wall_ms": round(sched * 1e3, 2),
            "device_wall_ms": round(device * 1e3, 2),
            "sched_frac": round(sched / max(device, 1e-9), 4),
            "post_warmup_compiles": m["compiles"],
            "sched_under_device_every_epoch": m["overlap"],
        }
        entry["epoch_sweep"][str(el)] = rec
        emit(f"serve_host_k{el}", device * 1e6,
             f"{rec['tokens_per_s']:.1f} tok/s | p95 TTFT "
             f"{rec['p95_ttft_ms']:.0f}ms | sched "
             f"{rec['sched_frac'] * 100:.1f}% of device wall",
             extra={"tokens_per_s": rec["tokens_per_s"],
                    "p95_ttft_ms": rec["p95_ttft_ms"],
                    "sched_frac": rec["sched_frac"]})
    base = entry["epoch_sweep"][str(sweep_els[0])]
    h8 = metrics[sweep_els[0]]["host"]
    # sweep pick: the smaller epoch length with the lowest p95 TTFT —
    # the latency point the host-overlap work makes affordable
    best_el = min(sweep_els[1:],
                  key=lambda el: entry["epoch_sweep"][str(el)]["p95_ttft_ms"])
    best = entry["epoch_sweep"][str(best_el)]
    entry.update({
        "env": env.describe(),
        "sched_frac": base["sched_frac"],
        "post_warmup_compiles": sum(m["compiles"]
                                    for m in metrics.values()),
        "batched_runs": h8["batched_runs"],
        "oracle_runs": h8["oracle_runs"],
        "aot": {"compiled": h8["aot_compiled"],
                "failed": h8["aot_failed"],
                "hits": h8["aot_hits"],
                "fallback_calls": h8["fallback_calls"]},
        "sweep_pick": {
            "epoch_len": best_el,
            "p95_ttft_ratio": round(
                base["p95_ttft_ms"] / max(best["p95_ttft_ms"], 1e-9), 3),
            "tokens_per_s_ratio": round(
                best["tokens_per_s"] / max(base["tokens_per_s"], 1e-9), 3),
        },
    })
    return entry


def _check_serve(baseline: dict, fresh: dict) -> int:
    """CI gate mirroring the BENCH_nec gate: a >2x tokens/s regression
    of the pipelined loop — or of the mixed-workload continuous-batching
    loop, or a >2x p95 TTFT regression — vs the committed
    BENCH_serve.json fails.  Entries the fresh run did not produce
    (e.g. `fleet` during --smoke, `pipelined` during --fleet) are
    skipped.  A fresh `fleet` entry is additionally gated on the
    ISSUE-6 acceptance floor: >=3x critical-path speedup at 4 replicas
    and balanced routing.  A fresh `prefix` entry is gated on the
    ISSUE-7 acceptance floor: >=30% prefill-token savings, >=1.5x warm
    p95 TTFT vs dedup-off, and bit-identical decode streams.  A fresh
    `quant` entry is gated on the ISSUE-8 acceptance floor: >=1.8x
    effective KV pages per tenant at int8, <2x tokens/s regression vs
    the native-KV server, full int8 residency on the oversubscribed
    pool, and the documented accuracy bound (decode logits cosine >=
    0.999 vs the native reference).  A fresh `faults` entry is gated on
    the ISSUE-10 acceptance floor: preempt/resume decode bit-identity,
    every killed replica's tenant completing on a survivor with a
    recorded recovery p95 under the ceiling, and the overload burst
    shedding/deferring with zero unhandled exceptions and a drained
    queue."""
    failures = []
    base = baseline.get("pipelined", {}).get("tokens_per_s", 0.0)
    got = fresh.get("pipelined", {}).get("tokens_per_s", 0.0)
    if base and got and got < base / 2.0:
        failures.append(f"serve_pipelined: {got:.1f} tok/s is <0.5x the "
                        f"baseline {base:.1f} tok/s")
    base_m = baseline.get("mixed", {}).get("interleaved", {})
    got_m = fresh.get("mixed", {}).get("interleaved", {})
    bt, gt = base_m.get("tokens_per_s", 0.0), got_m.get("tokens_per_s", 0.0)
    if bt and gt and gt < bt / 2.0:
        failures.append(f"serve_mixed: {gt:.1f} tok/s is <0.5x the "
                        f"baseline {bt:.1f} tok/s")
    bl, gl = base_m.get("p95_ttft_ms", 0.0), got_m.get("p95_ttft_ms", 0.0)
    if bl and gl and gl > bl * 2.0:
        failures.append(f"serve_mixed: p95 TTFT {gl:.0f}ms is >2x the "
                        f"baseline {bl:.0f}ms")
    got_f = fresh.get("fleet", {})
    if got_f:
        sp = got_f.get("speedup_vs_single", 0.0)
        if sp < 3.0:
            failures.append(f"serve_fleet: speedup {sp:.2f}x is below the "
                            f"3x acceptance floor at 4 replicas")
        bal = got_f.get("page_util_balance", 1.0)
        if bal < 0.5:
            failures.append(f"serve_fleet: page-util balance {bal:.2f} "
                            f"(min/max replica) is below 0.5")
        bagg = baseline.get("fleet", {}).get("aggregate_tokens_per_s", 0.0)
        gagg = got_f.get("aggregate_tokens_per_s", 0.0)
        if bagg and gagg < bagg / 2.0:
            failures.append(f"serve_fleet: {gagg:.1f} tok/s aggregate is "
                            f"<0.5x the baseline {bagg:.1f} tok/s")
    got_p = fresh.get("prefix", {})
    if got_p:
        sav = got_p.get("prefill_savings_frac", 0.0)
        if sav < 0.30:
            failures.append(f"serve_prefix: {sav * 100:.0f}% prefill-token "
                            f"savings is below the 30% acceptance floor")
        tr = got_p.get("warm_ttft_ratio", 0.0)
        if tr < 1.5:
            failures.append(f"serve_prefix: warm p95 TTFT ratio {tr:.2f}x "
                            f"is below the 1.5x acceptance floor")
        if got_p.get("decode_bit_identical") is not True:
            failures.append("serve_prefix: decode streams were not "
                            "bit-identical between dedup on and off")
        bon = baseline.get("prefix", {}).get("dedup_on", {}) \
                      .get("tokens_per_s", 0.0)
        gon = got_p.get("dedup_on", {}).get("tokens_per_s", 0.0)
        if bon and gon < bon / 2.0:
            failures.append(f"serve_prefix: {gon:.1f} tok/s (dedup on) is "
                            f"<0.5x the baseline {bon:.1f} tok/s")
    got_q = fresh.get("quant", {})
    if got_q:
        pg = got_q.get("effective_pages_gain", 0.0)
        if pg < 1.8:
            failures.append(f"serve_quant: effective-pages gain {pg:.2f}x "
                            f"is below the 1.8x acceptance floor")
        qr = got_q.get("tokens_per_s_ratio", 0.0)
        if qr < 0.5:
            failures.append(f"serve_quant: int8 tokens/s is {qr:.2f}x "
                            f"native — a >2x regression")
        if not got_q.get("int8", {}).get("fully_resident", False):
            failures.append("serve_quant: int8 tenants did not stay fully "
                            "resident on the oversubscribed pool")
        cos = got_q.get("accuracy", {}).get("min_cosine", 0.0)
        bound = got_q.get("accuracy_bound", {}).get("min_cosine", 0.999)
        if cos < bound:
            failures.append(f"serve_quant: decode cosine {cos:.5f} below "
                            f"the documented {bound} bound")
        bqt = baseline.get("quant", {}).get("int8", {}) \
                      .get("tokens_per_s", 0.0)
        gqt = got_q.get("int8", {}).get("tokens_per_s", 0.0)
        if bqt and gqt < bqt / 2.0:
            failures.append(f"serve_quant: {gqt:.1f} tok/s (int8) is "
                            f"<0.5x the baseline {bqt:.1f} tok/s")
    got_ft = fresh.get("faults", {})
    if got_ft:
        if got_ft.get("preempt", {}).get("decode_bit_identical") is not True:
            failures.append("serve_faults: preempted-resumed decode stream "
                            "was not bit-identical to the uninterrupted run")
        if got_ft.get("preempt", {}).get("preemptions", 0) < 1:
            failures.append("serve_faults: the preempt fault never fired")
        fov = got_ft.get("failover", {})
        if not fov.get("all_completed", False):
            failures.append("serve_faults: not every killed replica's "
                            "tenant completed on a survivor")
        rp = fov.get("recovery_p95_s")
        if rp is None:
            failures.append("serve_faults: failover recovery p95 was not "
                            "recorded")
        elif rp > 20.0:
            failures.append(f"serve_faults: failover recovery p95 {rp:.1f}s "
                            f"exceeds the 20s ceiling")
        ovf = got_ft.get("overload", {})
        if ovf.get("unhandled_exceptions", 1) != 0:
            failures.append("serve_faults: the overload burst raised an "
                            "unhandled exception")
        if ovf.get("shed_count", 0) + ovf.get("deferrals", 0) <= 0:
            failures.append("serve_faults: a 2x-oversubscribed burst "
                            "neither shed nor deferred anything")
        if ovf.get("queued_at_end", 1) != 0:
            failures.append(f"serve_faults: {ovf.get('queued_at_end')} "
                            f"arrivals still queued at end of run "
                            f"(queue must drain: admit or shed)")
    got_h = fresh.get("host", {})
    if got_h:
        sf = got_h.get("sched_frac", 1.0)
        if sf >= 0.30:
            failures.append(f"serve_host: sched wall is {sf * 100:.1f}% of "
                            f"the device wall — host is on the critical "
                            f"path (>=30%)")
        nc = got_h.get("post_warmup_compiles", -1)
        if nc != 0:
            failures.append(f"serve_host: {nc} fused-program compiles "
                            f"after warmup (steady state must be 0)")
        if got_h.get("oracle_runs", 1) != 0:
            failures.append(f"serve_host: {got_h.get('oracle_runs')} epoch "
                            f"plans fell back to the per-tenant oracle "
                            f"(batched Algorithm 1 should cover the "
                            f"decode steady state)")
        pick = got_h.get("sweep_pick", {})
        tr = pick.get("p95_ttft_ratio", 0.0)
        if tr <= 1.0:
            failures.append(f"serve_host: epoch sweep shows no p95 TTFT "
                            f"reduction at epoch_len="
                            f"{pick.get('epoch_len')} ({tr:.2f}x)")
        tpr = pick.get("tokens_per_s_ratio", 0.0)
        if tpr < 0.95:
            failures.append(f"serve_host: sweep point epoch_len="
                            f"{pick.get('epoch_len')} costs "
                            f"{(1 - tpr) * 100:.1f}% tokens/s (>5% loss)")
        bht = baseline.get("host", {}).get("epoch_sweep", {}) \
                      .get("8", {}).get("tokens_per_s", 0.0)
        ght = got_h.get("epoch_sweep", {}).get("8", {}) \
                   .get("tokens_per_s", 0.0)
        if bht and ght and ght < bht / 2.0:
            failures.append(f"serve_host: {ght:.1f} tok/s (epoch_len=8) is "
                            f"<0.5x the baseline {bht:.1f} tok/s")
    for f in failures:
        print(f"[bench-check] FAIL {f}", file=sys.stderr)
    if not failures:
        parts = []
        if got:
            parts.append(f"{got:.1f} tok/s pipelined")
        if gt:
            parts.append(f"mixed {gt:.1f} tok/s, p95 TTFT {gl:.0f}ms")
        if got_f:
            parts.append(f"fleet {got_f.get('aggregate_tokens_per_s', 0):.1f}"
                         f" tok/s @ {got_f.get('speedup_vs_single', 0):.2f}x")
        if got_p:
            parts.append(
                f"prefix -{got_p.get('prefill_savings_frac', 0) * 100:.0f}% "
                f"prefill @ {got_p.get('warm_ttft_ratio', 0):.2f}x warm TTFT")
        if got_q:
            parts.append(
                f"quant {got_q.get('effective_pages_gain', 0):.2f}x pages "
                f"@ {got_q.get('tokens_per_s_ratio', 0):.2f}x tok/s, cos "
                f"{got_q.get('accuracy', {}).get('min_cosine', 0):.5f}")
        if got_ft:
            rp = got_ft.get("failover", {}).get("recovery_p95_s", 0) or 0
            parts.append(
                f"faults recovery p95 {rp * 1e3:.0f}ms, shed rate "
                f"{got_ft.get('overload', {}).get('shed_rate', 0):.2f}, "
                f"bit-identical resume")
        if got_h:
            pick = got_h.get("sweep_pick", {})
            parts.append(
                f"host sched {got_h.get('sched_frac', 0) * 100:.1f}% of "
                f"device, sweep k={pick.get('epoch_len')} "
                f"{pick.get('p95_ttft_ratio', 0):.2f}x p95 TTFT @ "
                f"{pick.get('tokens_per_s_ratio', 0):.2f}x tok/s")
        print(f"[bench-check] serve ok ({'; '.join(parts)})",
              file=sys.stderr)
    return 1 if failures else 0


def _write_serve_json(payload: dict) -> None:
    """Merge-preserving BENCH_serve.json write: entries this run did not
    produce (the `fleet` entry during --smoke, the `pipelined`/`mixed`
    entries during --fleet) keep their committed values, so the file
    holds the union of both modes."""
    from repro.launch import env
    payload["env"] = env.describe_dict()
    if BENCH_SERVE_JSON.exists():
        try:
            prev = json.loads(BENCH_SERVE_JSON.read_text())
            for k, v in prev.items():
                payload.setdefault(k, v)
        except (OSError, ValueError):
            pass
    BENCH_SERVE_JSON.write_text(
        json.dumps(payload, indent=2, sort_keys=True) + "\n")
    print(f"[bench] wrote "
          f"{BENCH_SERVE_JSON.relative_to(BENCH_SERVE_JSON.parents[1])}",
          file=sys.stderr)


def _write_json(wall_s: float, mode: str) -> None:
    from benchmarks.common import RESULTS
    from repro.launch import env
    payload = {"schema": 1, "mode": mode, "wall_s": round(wall_s, 2),
               "env": env.describe_dict(), "figures": dict(RESULTS)}
    if BENCH_JSON.exists():
        try:
            prev = json.loads(BENCH_JSON.read_text())
            # merge: entries this run did not produce (e.g. the full
            # figures during a --smoke run) keep their recorded values,
            # so the committed file holds the union of both modes
            merged = prev.get("figures", {})
            merged.update(payload["figures"])
            payload["figures"] = merged
            # the `reference` block (the per-line-NEC wall times this
            # rewrite is measured against) is curated, not measured
            if prev.get("reference"):
                payload["reference"] = prev["reference"]
        except (OSError, ValueError):
            pass
    BENCH_JSON.write_text(json.dumps(payload, indent=2, sort_keys=True) + "\n")
    print(f"[bench] wrote {BENCH_JSON.relative_to(BENCH_JSON.parents[1])}",
          file=sys.stderr)


def _check(baseline: dict, wall_s: float, budget_s: float) -> int:
    """CI gate: >2x us_per_call regression vs the committed baseline, or
    a blown wall budget, fails the job."""
    from benchmarks.common import RESULTS
    failures = []
    if budget_s and wall_s > budget_s:
        failures.append(f"wall {wall_s:.1f}s exceeds budget {budget_s:.0f}s")
    for name, entry in RESULTS.items():
        if name in ("serve_serial", "serve_pipelined",
                    "serve_mixed_interleaved", "serve_mixed_sequential"):
            # the serial reference loop's wall is strongly bimodal
            # (page-cache/allocator behaviour of its per-step full-cache
            # copies), and the mixed entries' walls are scenario walls;
            # the serving regression gates are the dedicated tokens/s +
            # TTFT checks (_check_serve), not these walls
            continue
        base = baseline.get("figures", {}).get(name)
        # skip only when BOTH sides sit under the noise floor — a fast
        # baseline must not exempt an entry that regressed into the
        # measurable range (e.g. nec_microbench reverting to per-line)
        if not base or max(base["us_per_call"],
                           entry["us_per_call"]) < CHECK_FLOOR_US:
            continue
        ratio = entry["us_per_call"] / max(base["us_per_call"], 1e-9)
        if ratio > 2.0:
            failures.append(f"{name}: {entry['us_per_call']:.0f}us is "
                            f"{ratio:.1f}x the baseline "
                            f"{base['us_per_call']:.0f}us")
    for f in failures:
        print(f"[bench-check] FAIL {f}", file=sys.stderr)
    if not failures:
        print("[bench-check] ok", file=sys.stderr)
    return 1 if failures else 0


def smoke() -> dict:
    """Fast perf-path canary (CI benchmark smoke job).  Returns the
    fresh BENCH_serve.json payload."""
    from benchmarks import fig3_reuse, table3_area
    from benchmarks.common import emit
    print("name,us_per_call,derived")
    fig3_reuse.main()
    table3_area.main()
    nec_microbench()
    from repro.launch.serve import MultiTenantServer
    t0 = time.time()
    srv = MultiTenantServer(["olmoe-1b-7b", "yi-9b", "mamba2-370m"],
                            batch=1, max_len=16, total_pages=128)
    out = srv.run(steps=4)
    wall_us = (time.time() - t0) * 1e6
    assert out["tokens_per_s"] > 0, "serving produced no tokens"
    plans = sorted({p.describe() for t in srv.tenants for p in t.plans})
    assert plans, "no KernelPlans were lowered"
    emit("serve_smoke", wall_us, f"{out['tokens_per_s']:.1f} tok/s | "
         f"plans {plans}", extra={"tokens_per_s": round(out["tokens_per_s"], 1)})
    payload = serve_bench()
    payload["mixed"] = serve_mixed_bench()
    return payload


def main() -> None:
    args = sys.argv[1:]
    budget_s = 0.0
    if "--budget-s" in args:
        budget_s = float(args[args.index("--budget-s") + 1])
    if "--fleet" in args:
        # fleet scaling entry (CI mesh-smoke job): forces 4 host devices
        # (must happen before any jax device use, hence before the
        # BENCH_nec machinery), gates on the committed BENCH_serve.json
        t0 = time.time()
        print("name,us_per_call,derived")
        serve_payload = {"schema": 1, "fleet": serve_fleet_bench()}
        wall_s = time.time() - t0
        rc = 0
        if budget_s and wall_s > budget_s:
            print(f"[bench-check] FAIL wall {wall_s:.1f}s exceeds budget "
                  f"{budget_s:.0f}s", file=sys.stderr)
            rc = 1
        if "--check" in args and BENCH_SERVE_JSON.exists():
            rc |= _check_serve(json.loads(BENCH_SERVE_JSON.read_text()),
                               serve_payload)
        if rc == 0:
            _write_serve_json(serve_payload)
        else:
            print("[bench] fleet check FAILED; baseline left untouched",
                  file=sys.stderr)
        sys.exit(rc)
    if "--faults" in args:
        # fault-injection entry (CI fault-smoke job): forces 4 host
        # devices, gates on the ISSUE-10 floors (bit-identical resume,
        # failover completion + recovery p95, overload shed/defer with
        # zero unhandled exceptions)
        t0 = time.time()
        print("name,us_per_call,derived")
        serve_payload = {"schema": 1, "faults": serve_faults_bench()}
        wall_s = time.time() - t0
        rc = 0
        if budget_s and wall_s > budget_s:
            print(f"[bench-check] FAIL wall {wall_s:.1f}s exceeds budget "
                  f"{budget_s:.0f}s", file=sys.stderr)
            rc = 1
        if "--check" in args:
            baseline = (json.loads(BENCH_SERVE_JSON.read_text())
                        if BENCH_SERVE_JSON.exists() else {})
            rc |= _check_serve(baseline, serve_payload)
        if rc == 0:
            _write_serve_json(serve_payload)
        else:
            print("[bench] faults check FAILED; baseline left untouched",
                  file=sys.stderr)
        sys.exit(rc)
    if "--prefix" in args:
        # prefix-dedup entry (CI bench-smoke job, second step): gates on
        # the committed BENCH_serve.json and the ISSUE-7 floors
        t0 = time.time()
        print("name,us_per_call,derived")
        serve_payload = {"schema": 1, "prefix": serve_prefix_bench()}
        wall_s = time.time() - t0
        rc = 0
        if budget_s and wall_s > budget_s:
            print(f"[bench-check] FAIL wall {wall_s:.1f}s exceeds budget "
                  f"{budget_s:.0f}s", file=sys.stderr)
            rc = 1
        if "--check" in args and BENCH_SERVE_JSON.exists():
            rc |= _check_serve(json.loads(BENCH_SERVE_JSON.read_text()),
                               serve_payload)
        if rc == 0:
            _write_serve_json(serve_payload)
        else:
            print("[bench] prefix check FAILED; baseline left untouched",
                  file=sys.stderr)
        sys.exit(rc)
    if "--quant" in args:
        # precision-for-residency entry (CI bench-smoke job, third
        # step): gates on the committed BENCH_serve.json, the ISSUE-8
        # floors, and the analytic quantized-kernel rooflines
        t0 = time.time()
        print("name,us_per_call,derived")
        serve_payload = {"schema": 1, "quant": serve_quant_bench()}
        wall_s = time.time() - t0
        rc = 0
        if budget_s and wall_s > budget_s:
            print(f"[bench-check] FAIL wall {wall_s:.1f}s exceeds budget "
                  f"{budget_s:.0f}s", file=sys.stderr)
            rc = 1
        if "--check" in args:
            from benchmarks.roofline import check_quant_rooflines
            if check_quant_rooflines():
                rc = 1
            if BENCH_SERVE_JSON.exists():
                rc |= _check_serve(json.loads(BENCH_SERVE_JSON.read_text()),
                                   serve_payload)
        if rc == 0:
            _write_serve_json(serve_payload)
        else:
            print("[bench] quant check FAILED; baseline left untouched",
                  file=sys.stderr)
        sys.exit(rc)
    if "--host" in args:
        # host-off-the-critical-path entry (CI bench-smoke job, fourth
        # step): gates on the committed BENCH_serve.json and the ISSUE-9
        # floors (sched wall < 30% of device wall, zero post-warmup
        # compiles, epoch sweep p95-TTFT-vs-throughput band)
        t0 = time.time()
        print("name,us_per_call,derived")
        serve_payload = {"schema": 1, "host": serve_host_bench()}
        wall_s = time.time() - t0
        rc = 0
        if budget_s and wall_s > budget_s:
            print(f"[bench-check] FAIL wall {wall_s:.1f}s exceeds budget "
                  f"{budget_s:.0f}s", file=sys.stderr)
            rc = 1
        if "--check" in args and BENCH_SERVE_JSON.exists():
            rc |= _check_serve(json.loads(BENCH_SERVE_JSON.read_text()),
                               serve_payload)
        if rc == 0:
            _write_serve_json(serve_payload)
        else:
            print("[bench] host check FAILED; baseline left untouched",
                  file=sys.stderr)
        sys.exit(rc)
    baseline = None
    if "--check" in args:
        if not BENCH_JSON.exists():
            print("[bench-check] no committed BENCH_nec.json baseline",
                  file=sys.stderr)
            sys.exit(1)
        baseline = json.loads(BENCH_JSON.read_text())
    t0 = time.time()
    if "--smoke" in args:
        serve_payload = smoke()
        wall_s = time.time() - t0
        rc = _check(baseline, wall_s, budget_s) if baseline is not None else 0
        serve_rc = 0
        if "--check" in args and BENCH_SERVE_JSON.exists():
            serve_rc = _check_serve(json.loads(BENCH_SERVE_JSON.read_text()),
                                    serve_payload)
        _write_json(wall_s, "smoke")
        if serve_rc == 0:
            # never overwrite the committed baseline with a measurement
            # that just FAILED the gate — a failing local rerun would
            # otherwise ratchet the baseline down and pass on retry
            _write_serve_json(serve_payload)
        else:
            print("[bench] serve check FAILED; baseline left untouched",
                  file=sys.stderr)
        sys.exit(rc | serve_rc)
    from benchmarks import (arrival_sweep, fig2_contention, fig3_reuse,
                            fig7_speedup, fig8_scaling, fig9_qos, table3_area)
    print("name,us_per_call,derived")
    for mod in (fig3_reuse, table3_area, fig2_contention, fig7_speedup,
                fig8_scaling, fig9_qos, arrival_sweep):
        mod.main()
    nec_microbench()
    # roofline summary (requires prior `python -m repro.launch.dryrun`)
    try:
        from benchmarks import roofline
        reps = roofline.load_reports()
        ok = [r for r in reps if r.get("roofline")]
        if ok:
            doms = {}
            for r in ok:
                d = r["roofline"]["dominant"]
                doms[d] = doms.get(d, 0) + 1
            print(f"roofline_cells,0,{len(ok)} cells analysed | "
                  f"dominant terms: {doms}")
    except Exception as e:  # roofline table is optional for bench runs
        print(f"roofline_cells,0,unavailable ({e})", file=sys.stderr)
    wall_s = time.time() - t0
    rc = _check(baseline, wall_s, budget_s) if baseline is not None else 0
    _write_json(wall_s, "full")
    sys.exit(rc)


if __name__ == "__main__":
    main()
