"""Prefix-hash KV dedup (ISSUE 7): refcounted copy-on-write pages, the
PrefixIndex, and the session-replay serving contracts.

Covers the PR acceptance contract:
  * PrefixIndex register/lookup longest-match semantics, parent chains,
    and the idempotent re-register,
  * refcounted sharing edge cases — the producer departing while
    consumers remain (entry holds keep the pages resident), LRU
    eviction racing a concurrent attach (the attached chain survives
    pool pressure), eviction refusing attached/parented entries,
  * serving: a session-replay workload served with dedup on vs off is
    decode-bit-identical while prefilling strictly fewer tokens, a
    bit-identical full-prompt re-arrival skips prefill entirely
    (prefix_hit == prompt_len, no chunks), and
  * the fleet router's prefix-affinity: a warm arrival routes to the
    replica holding its prefix even when another replica is
    less loaded (skips without >=2 devices; CI's mesh-smoke forces 4,
    and the relaunch test reruns it with forced devices elsewhere).
"""
import os
import subprocess
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.core.cache import CacheConfig, PrefixIndex, SharedCache

needs2 = pytest.mark.skipif(jax.device_count() < 2,
                            reason="needs >=2 forced host devices")


def make_index():
    cache = SharedCache(CacheConfig())
    return cache, PrefixIndex(cache)


# ---------------------------------------------------------------------------
# PrefixIndex units (no jax)
# ---------------------------------------------------------------------------
def test_register_lookup_longest_match():
    cache, idx = make_index()
    pg = cache.alloc("prod", 6)
    k1 = idx.register("a", "ps0", b"AB", 128, pg[:3], {"snap": "s1"})
    k2 = idx.register("a", "ps0", b"ABCD", 256, pg[3:], {"snap": "s2"},
                      parent=k1)
    # candidates longest first: the full match wins
    ent = idx.lookup("a", "ps0", [(256, b"ABCD"), (128, b"AB")])
    assert ent.key == k2 and ent.kv_len == 256
    assert [e.key for e in idx.chain(ent)] == [k2, k1]
    assert sorted(idx.chain_pages(ent)) == sorted(pg)
    # unseen longest falls back to the resident shorter prefix
    ent = idx.lookup("a", "ps0", [(256, b"ABZZ"), (128, b"AB")])
    assert ent.key == k1
    # a different params instance never matches
    assert idx.lookup("a", "ps1", [(128, b"AB")]) is None
    assert idx.hits == 2 and idx.misses == 1
    # probe path (the fleet router) does not perturb the counters
    assert idx.match_len("a", "ps0", [(256, b"ABCD"), (128, b"AB")]) == 256
    assert idx.match_len("a", "ps9", [(128, b"AB")]) == 0
    assert idx.hits == 2 and idx.misses == 1


def test_register_is_idempotent():
    cache, idx = make_index()
    pg = cache.alloc("prod", 2)
    k = idx.register("a", "ps0", b"X", 128, pg, {"v": 1})
    assert idx.register("a", "ps0", b"X", 128, [], {"v": 2}) == k
    assert idx.entries[k].payload == {"v": 1}     # original kept
    assert idx.stats()["entries"] == 1


def test_pages_survive_producer_departure():
    """The producer departing first must not strand its consumers: the
    entry's own hold keeps the pages resident until the index evicts."""
    cache, idx = make_index()
    total = cache.config.num_pages
    pg = cache.alloc("prod#kv", 4)
    k = idx.register("a", "ps0", b"T", 128, pg, {"snap": 1})
    cache.share(pg, "cons#kv")                    # consumer maps them
    idx.attach(k, "cons")
    cache.free("prod#kv")                         # producer departs FIRST
    assert cache.free_pages == total - 4          # entry + consumer hold
    ent = idx.lookup("a", "ps0", [(128, b"T")])
    assert ent is not None and ent.payload == {"snap": 1}
    assert ent.refcount == 1
    idx.detach(k, "cons")                         # consumer departs
    cache.free("cons#kv")
    assert cache.free_pages == total - 4          # still warm for reuse
    assert idx.reclaim(1) == 4                    # now evictable
    assert cache.free_pages == total and idx.entries == {}


def test_attached_chain_survives_pressure_reclaim():
    """LRU eviction racing a concurrent attach: pool pressure (the
    alloc-driven pressure hook) must not evict any entry of a chain a
    tenant is attached to — and must evict it once detached."""
    cache, idx = make_index()
    total = cache.config.num_pages
    pg = cache.alloc("prod", 8)
    k1 = idx.register("a", "ps0", b"P", 128, pg[:4], None)
    k2 = idx.register("a", "ps0", b"PQ", 256, pg[4:], None, parent=k1)
    cache.free("prod")
    idx.attach(k2, "cons")                        # chain refcount++
    got = cache.alloc("hog", total)               # pressure: 8 short
    assert got is None                            # attach protected them
    assert set(idx.entries) == {k1, k2}
    assert idx.evictions == 0
    idx.detach(k2, "cons")
    got = cache.alloc("hog", total)               # pressure again
    assert got is not None and len(got) == total  # chain reclaimed
    assert idx.entries == {} and idx.evictions == 2


def test_reclaim_evicts_lru_first():
    cache, idx = make_index()
    keys = []
    for i in range(3):
        pg = cache.alloc(f"p{i}", 4)
        keys.append(idx.register("a", "ps0", bytes([i]), 128, pg, None))
        cache.free(f"p{i}")
    idx.lookup("a", "ps0", [(128, bytes([0]))])   # refresh entry 0
    assert idx.reclaim(1) == 4                    # one entry suffices
    assert keys[1] not in idx.entries             # least-recent went
    assert keys[0] in idx.entries and keys[2] in idx.entries


def test_evict_refuses_attached_or_parent():
    cache, idx = make_index()
    pg = cache.alloc("p", 4)
    k1 = idx.register("a", "ps0", b"p", 128, pg[:2], None)
    k2 = idx.register("a", "ps0", b"pq", 256, pg[2:], None, parent=k1)
    with pytest.raises(RuntimeError):
        idx.evict(k1)                             # registered child
    idx.attach(k2, "c")
    with pytest.raises(RuntimeError):
        idx.evict(k2)                             # attached tenant
    idx.detach(k2, "c")
    idx.evict(k2)                                 # leaf-first works
    idx.evict(k1)
    cache.free("p")                               # producer's own hold
    assert cache.free_pages == cache.config.num_pages


# ---------------------------------------------------------------------------
# serving contracts (single device)
# ---------------------------------------------------------------------------
def _session_workload(seed):
    from repro.sim.driver import SessionArrivals
    # gap_s outlasts each producer's chunked prefill on the logical
    # clock, so warm arrivals deterministically find their prefix
    return SessionArrivals(models=["olmoe-1b-7b"], n_sessions=2, turns=2,
                           n_prompts=1, prefix_len=256, turn_tokens=128,
                           gap_s=4.0, n_inferences=6, seed=seed)


def test_session_replay_dedup_bit_identical_and_saves():
    """The tentpole contract: dedup on vs off serves bit-identical
    decode streams while prefilling strictly fewer tokens on device,
    with warm arrivals recorded per tenant (prefix_hit > 0)."""
    from repro.launch.serve import MultiTenantServer
    outs = {}
    for on in (True, False):
        srv = MultiTenantServer([], tenants=_session_workload(0).specs(),
                                prefix_dedup=on, batch=1, max_len=640,
                                total_pages=128, epoch_len=8,
                                steps_per_s=4.0)
        outs[on] = srv.run(24)
    a, b = outs[True], outs[False]
    assert set(a["tenants"]) == set(b["tenants"])
    for tid in a["tenants"]:
        np.testing.assert_array_equal(
            a["tenants"][tid]["output"], b["tenants"][tid]["output"],
            err_msg=f"dedup changed the decode stream for {tid}")
    # turn-1 re-arrivals (and the second session's shared system
    # prompt) attach instead of recomputing
    warm = [tid for tid, i in a["tenants"].items() if i["prefix_hit"] > 0]
    assert len(warm) >= 2, a["prefix"]
    assert a["prefill_computed"] < b["prefill_computed"]
    assert a["prefix"]["hits"] >= 2
    assert b["prefix"]["hits"] == 0 and b["prefix"]["entries"] == 0
    for tid in warm:
        ai, bi = a["tenants"][tid], b["tenants"][tid]
        assert ai["prefill_computed"] < bi["prefill_computed"]
        assert sum(ai["prefill_chunks"]) == \
            ai["prompt_len"] - ai["prefix_hit"]


def test_full_prompt_rearrival_skips_prefill():
    """A bit-identical full-prompt re-arrival is a FULL hit: the stored
    first decode token short-circuits prefill entirely (no chunks), and
    the decode stream matches the producer's bit-for-bit."""
    from repro.launch.serve import MultiTenantServer
    from repro.sim.driver import TenantSpec

    def spec(at):
        return TenantSpec("olmoe-1b-7b", arrive_at=at, n_inferences=6,
                          prompt_len=256, param_seed=5, prompt_seed=7,
                          prefix_len=256, prefix_seed=3)

    srv = MultiTenantServer([], tenants=[spec(0.0), spec(4.0)],
                            prefix_dedup=True, batch=1, max_len=512,
                            total_pages=128, epoch_len=8, steps_per_s=4.0)
    out = srv.run(24)
    prod = out["tenants"]["t0:olmoe-1b-7b"]
    warm = out["tenants"]["t1:olmoe-1b-7b"]
    assert prod["prefix_hit"] == 0 and sum(prod["prefill_chunks"]) == 256
    assert warm["prefix_hit"] == 256
    assert warm["prefill_chunks"] == []           # prefill skipped
    assert warm["prefill_computed"] == 0
    assert warm["ttft_s"] is not None
    assert warm["tokens"] == 1 + 6
    np.testing.assert_array_equal(prod["output"], warm["output"])


# ---------------------------------------------------------------------------
# fleet routing (forced multi-device host)
# ---------------------------------------------------------------------------
@needs2
def test_fleet_prefix_affine_routing():
    """Prefix-affine admission: a warm arrival routes to the replica
    holding its prefix (longest match wins over least-loaded), attaches
    there, and the decoy replica — strictly less loaded at that moment
    — does not steal it."""
    from repro.launch.serve import FleetServer
    from repro.sim.driver import TenantSpec

    arch = "mamba2-370m"
    prod = TenantSpec(arch, arrive_at=0.0, n_inferences=24, prompt_len=256,
                      param_seed=5, prompt_seed=1, prefix_len=256,
                      prefix_seed=3)
    # promptless decoy: no KV reservation, so its replica stays the
    # least-loaded one while the producer holds pages
    decoy = TenantSpec(arch, arrive_at=0.0, n_inferences=24)
    warm = TenantSpec(arch, arrive_at=10.0, n_inferences=4, prompt_len=384,
                      param_seed=5, prompt_seed=2, prefix_len=256,
                      prefix_seed=3)
    fleet = FleetServer(n_replicas=2, pages_per_replica=64,
                        tenants=[prod, decoy, warm], prefix_dedup=True,
                        batch=1, max_len=512, epoch_len=4)
    out = fleet.run(16)
    routes = dict(out["routes"])
    assert routes["t0:" + arch] != routes["t1:" + arch]  # spread residents
    assert routes["t2:" + arch] == routes["t0:" + arch]  # prefix affinity
    info = out["tenants"]["t2:" + arch]
    assert info["prefix_hit"] == 256              # attached, not recomputed
    assert sum(info["prefill_chunks"]) == 384 - 256


def test_relaunch_fleet_routing_with_forced_devices():
    """On a single-device host, re-run the fleet routing test with 2
    forced host devices so it executes instead of skipping everywhere
    (CI's mesh-smoke job runs it in-process under 4 forced devices)."""
    if jax.device_count() >= 2:
        pytest.skip("already multi-device; the routing test ran in-process")
    from repro.launch import env
    # the child forces CPU devices and must never reach for a chip
    env_ = dict(os.environ, JAX_PLATFORMS="cpu")
    env_["XLA_FLAGS"] = env.merge_xla_flag(
        env_.get("XLA_FLAGS", ""),
        "--xla_force_host_platform_device_count", 2)
    src = str(Path(__file__).resolve().parents[1] / "src")
    env_["PYTHONPATH"] = src + os.pathsep + env_.get("PYTHONPATH", "")
    proc = subprocess.run(
        [sys.executable, "-m", "pytest", "-x", "-q",
         __file__ + "::test_fleet_prefix_affine_routing"],
        env=env_, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, \
        f"forced-device rerun failed:\n{proc.stdout}\n{proc.stderr}"
