"""Serving-stack fault injection: preemption with KV checkpoint/restore,
overload admission control, and fleet replica failover.

The contract under test (ISSUE: survive the fleet):

* a preempted-then-resumed tenant's decode stream is BIT-IDENTICAL to
  an uninterrupted run — decode is a pure function of (caches, token,
  index), and both snapshot paths (checkpoint.save round-trip, prefix
  re-seed) preserve all three exactly;
* under an oversubscription burst the server defers or sheds instead of
  raising, and the queue always drains by end of run;
* a killed replica's tenants complete on survivors, with per-tenant
  recovery latency recorded.

Fleet tests need >= 4 forced host devices and reuse the relaunch
pattern of tests/test_fleet.py.
"""
import dataclasses
import os
import subprocess
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.core.policy import QosPreemptionPolicy
from repro.core.runtime import STATE_PREEMPTED, STATE_RESUMED, STATE_SHED
from repro.launch import env
from repro.launch.serve import FleetServer, MultiTenantServer
from repro.sim.driver import TenantSpec
from repro.sim.faults import FaultEvent, FaultPlan

needs4 = pytest.mark.skipif(jax.device_count() < 4,
                            reason="needs 4 forced host devices "
                                   "(run via the relaunch test or "
                                   "XLA_FLAGS=--xla_force_host_platform"
                                   "_device_count=4)")

ARCH = "mamba2-370m"   # smallest registered arch: cheapest compile


def _srv(**kw):
    kw.setdefault("total_pages", 64)
    kw.setdefault("epoch_len", 4)
    kw.setdefault("pipeline", True)
    kw.setdefault("max_len", 128)
    return MultiTenantServer([], **kw)


def _outputs(res):
    return {tid: info["output"] for tid, info in res["tenants"].items()}


# ---------------------------------------------------------------------------
# victim selection policy (host-only)
# ---------------------------------------------------------------------------
def test_qos_policy_prefers_loosest_then_largest_holding():
    p = QosPreemptionPolicy()
    # no QoS target = loosest -> first choice regardless of pages
    assert p.select([("a", 0.05, 9, 0), ("b", None, 1, 0)]) == "b"
    # among targeted tenants: loosest (largest) target first
    assert p.select([("a", 0.05, 1, 0), ("c", 0.40, 1, 0)]) == "c"
    # ties on QoS break toward the larger page holding
    assert p.select([("a", 0.05, 2, 0), ("c", 0.05, 7, 0)]) == "c"
    assert p.select([]) is None


# ---------------------------------------------------------------------------
# preempt -> resume bit-identity (snapshot path)
# ---------------------------------------------------------------------------
def test_preempt_resume_is_bit_identical():
    spec = TenantSpec(ARCH, prompt_len=32, n_inferences=24)
    ref = _srv()
    ref.enqueue([dataclasses.replace(spec)])
    r_ref = ref.run(steps=24)

    plan = FaultPlan([FaultEvent(step=8, kind="preempt", hold_epochs=2)])
    srv = _srv(faults=plan)
    srv.enqueue([dataclasses.replace(spec)])
    r = srv.run(steps=24)

    assert r["faults"]["preemptions"] == 1
    kinds = [rec["kind"] for rec in r["faults"]["log"]]
    assert kinds == ["preempt", "resume"]
    assert r["faults"]["log"][0]["mode"] == "snapshot"
    assert r["faults"]["recovery_s"] and r["faults"]["recovery_s"][0] > 0

    (tid, a), = _outputs(r_ref).items()
    b = _outputs(r)[tid]
    assert a.shape == b.shape
    assert np.array_equal(a, b), "decode diverged across preempt/resume"
    info = r["tenants"][tid]
    # RESUMED is sticky in results: the record that this tenant came
    # back from a preemption (RUNNING is only re-stamped from ADMITTED)
    assert info["state"] == STATE_RESUMED
    assert info["preemptions"] == 1


def test_preempt_resume_prefix_reseed_path():
    """A tenant sitting exactly at the end of a registered full-prompt
    prefix entry checkpoints by REFCOUNT, not by copy: the resident
    entry is the snapshot, and resume re-seeds from it bit-identically."""
    base = TenantSpec(ARCH, prompt_len=32, n_inferences=4,
                      param_seed=0, prompt_seed=1)

    def warm_server():
        s = _srv(prefix_dedup=True)
        s.enqueue([dataclasses.replace(base)])
        s.run(steps=8)   # registers the full-prompt prefix (+ token)
        return s

    follow = dataclasses.replace(base, n_inferences=8)
    ctrl = warm_server()
    t0 = ctrl.admit_routed(dataclasses.replace(follow))
    assert t0.prefix_hit == 32 and t0.token is not None
    r_ctrl = ctrl.run(steps=16)

    srv = warm_server()
    t1 = srv.admit_routed(dataclasses.replace(follow))
    assert t1.index == t1.prompt_len
    assert srv.preempt_tenant(t1, resume_after_epochs=1)
    assert t1.state == STATE_PREEMPTED and t1.token is None
    assert srv.fault_log.of_kind("preempt")[0]["mode"] == "prefix"
    r = srv.run(steps=16)   # resume fires inside the run loop

    assert t1.preemptions == 1 and t1.recovery_s
    a, b = _outputs(r_ctrl)[t0.tid], _outputs(r)[t1.tid]
    assert t0.tid == t1.tid
    assert a.shape == b.shape and np.array_equal(a, b)


def test_preempted_tenant_frees_pages_and_reacquires():
    spec = TenantSpec(ARCH, prompt_len=32, n_inferences=24)
    plan = FaultPlan([FaultEvent(step=8, kind="preempt", hold_epochs=2)])
    srv = _srv(faults=plan)
    srv.enqueue([spec])
    free0 = srv.cache.free_pages
    r = srv.run(steps=24)
    tid = next(iter(r["tenants"]))
    info = r["tenants"][tid]
    # KV stats survived the preempt/resume round trip
    assert info["kv_reserved"] > 0
    assert info["kv_dtype"] in ("native", "int8", "fp8")
    # departure at end of budget returned everything
    assert srv.cache.free_pages == free0


# ---------------------------------------------------------------------------
# deterministic replay of a faulted run
# ---------------------------------------------------------------------------
def test_fault_schedule_replays_deterministically():
    plan_events = [FaultEvent(step=4, kind="pool_pressure", pages=48),
                   FaultEvent(step=12, kind="straggler", hold_epochs=3),
                   FaultEvent(step=16, kind="preempt", hold_epochs=1)]
    specs = [TenantSpec(ARCH, prompt_len=32, n_inferences=24, arrive_at=0.0),
             TenantSpec(ARCH, prompt_len=32, n_inferences=24, arrive_at=1.0)]

    def go():
        srv = _srv(faults=FaultPlan(list(plan_events)))
        srv.enqueue([dataclasses.replace(s) for s in specs])
        res = srv.run(steps=32)
        timeline = [(rec["step"], rec["kind"], rec.get("tid"))
                    for rec in res["faults"]["log"]]
        return timeline, _outputs(res)

    t_a, out_a = go()
    t_b, out_b = go()
    assert t_a == t_b
    assert set(out_a) == set(out_b)
    for tid in out_a:
        assert np.array_equal(out_a[tid], out_b[tid]), tid


def test_straggler_trip_preempts_then_recovers():
    plan = FaultPlan([FaultEvent(step=8, kind="straggler", hold_epochs=3)])
    srv = _srv(faults=plan)
    srv.enqueue([TenantSpec(ARCH, prompt_len=32, n_inferences=24)])
    r = srv.run(steps=32)
    counts = r["faults"]["counts"]
    assert counts.get("straggler_trip") == 1
    assert counts.get("preempt") == 1 and counts.get("resume") == 1


# ---------------------------------------------------------------------------
# overload admission control
# ---------------------------------------------------------------------------
def test_overload_burst_defers_or_sheds_never_raises():
    """2x oversubscription: more KV demand than the pool holds, all at
    once.  The server must keep serving (deferred arrivals retry with
    jittered backoff; hopeless ones shed at their deadline) and the
    queue must be empty when the run ends."""
    specs = [TenantSpec(ARCH, prompt_len=96, n_inferences=8, arrive_at=0.5,
                        qos_ms=(None if i % 3 == 0 else 50.0 * (i + 1)))
             for i in range(8)]
    srv = _srv(total_pages=8, queue_limit=16, queue_deadline_s=24.0)
    srv.enqueue(specs)
    res = srv.run(steps=16)
    ov = res["overload"]
    assert ov["queued"] == 0, "queue must drain (admit or shed) by run end"
    assert ov["deferrals"] > 0
    assert ov["shed_count"] > 0
    for s in ov["shed"]:
        assert s["state"] == STATE_SHED and s["reason"] == "deadline"
    # shedding is QoS-aware: nothing with a tight target sheds while a
    # no-target arrival is still waiting
    shed_qos = [s["qos_ms"] for s in ov["shed"]]
    assert None in shed_qos or max(q for q in shed_qos) >= 300.0
    # served tenants made real progress
    assert all(info["tokens"] > 0 for info in res["tenants"].values())


def test_bounded_queue_sheds_on_overflow():
    specs = [TenantSpec(ARCH, prompt_len=64, n_inferences=8, arrive_at=0.5)
             for _ in range(6)]
    srv = _srv(queue_limit=2)
    srv.enqueue(specs)
    res = srv.run(steps=8)
    reasons = {s["reason"] for s in res["overload"]["shed"]}
    assert reasons == {"queue_full"}
    assert res["overload"]["shed_count"] == 4


def test_malformed_prompts_shed_not_crash():
    plan = FaultPlan([FaultEvent(step=4, kind="bad_prompt")])
    srv = _srv(faults=plan)
    srv.enqueue([TenantSpec(ARCH, prompt_len=32, n_inferences=16),
                 TenantSpec(ARCH, prompt_len=-3, n_inferences=4,
                            arrive_at=0.5)])
    res = srv.run(steps=16)
    reasons = sorted(s["reason"] for s in res["overload"]["shed"])
    assert reasons == ["negative_prompt", "oversized_prompt"]
    # the well-formed tenant is unaffected
    assert sum(i["tokens"] for i in res["tenants"].values()) > 0


def test_pool_pressure_spike_releases_after_hold():
    plan = FaultPlan([FaultEvent(step=4, kind="pool_pressure", pages=48,
                                 hold_epochs=2)])
    srv = _srv(faults=plan)
    srv.enqueue([TenantSpec(ARCH, prompt_len=32, n_inferences=24)])
    free0 = srv.cache.free_pages
    res = srv.run(steps=24)
    log = res["faults"]["log"]
    seize = next(r for r in log if r["kind"] == "pool_pressure")
    release = next(r for r in log if r["kind"] == "pressure_release")
    assert seize["seized"] > 0
    assert release["step"] == seize["step"] + 2 * srv.epoch_len
    assert srv.cache.free_pages == free0   # nothing leaked


# ---------------------------------------------------------------------------
# fleet failover (forced >= 4 devices)
# ---------------------------------------------------------------------------
def test_relaunch_with_forced_devices():
    """On a single-device host, re-run this file with 4 forced devices
    so the @needs4 tests execute instead of skipping everywhere."""
    if jax.device_count() >= 4:
        pytest.skip("already multi-device; @needs4 tests ran in-process")
    # the child forces CPU devices and must never reach for a chip
    env_ = dict(os.environ, JAX_PLATFORMS="cpu")
    env_["XLA_FLAGS"] = env.merge_xla_flag(
        env_.get("XLA_FLAGS", ""),
        "--xla_force_host_platform_device_count", 4)
    src = str(Path(__file__).resolve().parents[1] / "src")
    env_["PYTHONPATH"] = src + os.pathsep + env_.get("PYTHONPATH", "")
    proc = subprocess.run(
        [sys.executable, "-m", "pytest", "-x", "-q", __file__],
        env=env_, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, \
        f"forced-device rerun failed:\n{proc.stdout}\n{proc.stderr}"


@needs4
def test_replica_kill_fails_over_to_survivors():
    specs = [TenantSpec(ARCH, prompt_len=32, n_inferences=24,
                        arrive_at=float(i)) for i in range(3)]
    plan = FaultPlan([FaultEvent(step=8, kind="replica_kill", target="r0")])
    fleet = FleetServer(n_replicas=2, tenants=specs, pages_per_replica=64,
                        batch=1, epoch_len=4, max_len=128, faults=plan)
    out = fleet.run(steps=24)
    fo = out["failover"]
    assert fo["killed"] == ["r0"]
    assert fo["moved"], "r0 had live tenants to move"
    for m in fo["moved"]:
        assert m["from"] == "r0" and m["to"] != "r0"
        info = out["tenants"][m["tid"]]
        # the survivor's record won the merge and it served tokens
        assert info["replica"] == m["to"]
        assert info["output"].shape[-1] > 0
        assert m["tid"] in fo["recovery_s"]
        assert fo["recovery_s"][m["tid"]] > 0
    assert fo["recovery_p95_s"] is not None
    dead = next(rep for rep in out["replicas"] if rep["replica"] == "r0")
    assert dead["dead"] is True


@needs4
def test_kill_last_live_replica_is_refused():
    plan = FaultPlan([FaultEvent(step=8, kind="replica_kill", target="r0"),
                      FaultEvent(step=12, kind="replica_kill", target="r1")])
    fleet = FleetServer(
        n_replicas=2, batch=1, epoch_len=4, max_len=128,
        pages_per_replica=64, faults=plan,
        tenants=[TenantSpec(ARCH, prompt_len=32, n_inferences=24,
                            arrive_at=float(i)) for i in range(2)])
    out = fleet.run(steps=24)
    assert out["failover"]["killed"] == ["r0"]   # r1 kill refused
    skipped = [r for r in out["faults"]["log"]
               if r["kind"] == "replica_kill" and "skipped" in r]
    assert len(skipped) == 1 and skipped[0]["target"] == "r1"


@needs4
def test_forwarded_faults_reach_target_replica():
    plan = FaultPlan([FaultEvent(step=8, kind="preempt", target="r1",
                                 hold_epochs=1)])
    fleet = FleetServer(
        n_replicas=2, batch=1, epoch_len=4, max_len=128,
        pages_per_replica=64, faults=plan,
        tenants=[TenantSpec(ARCH, prompt_len=32, n_inferences=24,
                            arrive_at=float(i)) for i in range(2)])
    out = fleet.run(steps=24)
    per_replica = out["faults"]["replica_counts"]
    assert per_replica[1].get("preempt", 0) == 1
    assert per_replica[0].get("preempt", 0) == 0
