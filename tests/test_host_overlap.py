"""Host/device overlap regression tests (tier-1).

The scheduling host must stay off the critical path once the epoch
programs are warm: a warmed server replays an identical workload with
zero new fused-jit compilations and a per-epoch ``sched_wall`` strictly
under the per-epoch ``dispatch_wall``.  The jit caches that make this
possible are LRU-bounded, so steady state must also show pure cache
hits — no evictions, no misses.  QoS targets are pinned once at
admission with longest-pattern matching, so a re-resolved pattern map
can never flip a live tenant's target mid-flight.
"""
import pytest

from repro.launch.serve import MultiTenantServer, _LruCache
from repro.sim.driver import TenantSpec


@pytest.fixture(scope="module")
def warmed_server():
    """Three-resident smoke server with one warm run already behind it:
    every epoch program the replay needs is compiled and cached."""
    srv = MultiTenantServer(["olmoe-1b-7b", "yi-9b", "mamba2-370m"],
                            batch=1, max_len=64, total_pages=128,
                            epoch_len=4)
    srv.run(8)
    return srv


# ---------------------------------------- satellite: host overlap -----
def test_warm_replay_compiles_nothing_new(warmed_server):
    out = warmed_server.run(8)
    h = out["host"]
    assert h["epochs"] > 0
    assert h["epoch_compiles"] == [0] * h["epochs"], \
        f"warm replay still compiled: {h['epoch_compiles']}"


def test_warm_replay_sched_wall_under_device_wall(warmed_server):
    out = warmed_server.run(8)
    h = out["host"]
    # One trailing plan call may see no runnable tenants and dispatch
    # nothing; compare only the epochs that actually hit the device.
    dispatch = h["epoch_dispatch_walls"]
    sched = h["epoch_sched_walls"][:len(dispatch)]
    assert len(dispatch) > 0
    for i, (s, d) in enumerate(zip(sched, dispatch)):
        assert s < d, (f"epoch {i}: host planning ({s * 1e3:.2f}ms) is on "
                       f"the critical path (dispatch {d * 1e3:.2f}ms)")


# ---------------------------------------- satellite: bounded caches ---
def test_steady_state_jit_cache_pure_hits(warmed_server):
    jits = warmed_server._fused_jits
    misses0, hits0 = jits.misses, jits.hits
    warmed_server.run(8)
    assert jits.misses == misses0, "steady-state replay missed the jit cache"
    assert jits.evictions == 0, "smoke working set should fit the LRU bound"
    assert jits.hits > hits0


def test_lru_cache_mechanics():
    c = _LruCache(2)
    c["a"] = 1
    c["b"] = 2
    assert c.get("a") == 1          # refreshes "a" → "b" is now LRU
    c["c"] = 3
    assert "b" not in c
    assert "a" in c and "c" in c
    assert c.evictions == 1
    assert c.hits == 1
    assert c.get("b") is None
    assert c.misses == 1


# ---------------------------------------- satellite: QoS pinning ------
def test_qos_pinned_at_admission_most_specific_pattern_wins():
    kw = dict(batch=1, max_len=16, total_pages=64)
    srv = MultiTenantServer([], tenants=[TenantSpec("yi-9b", n_inferences=4)],
                            qos_targets={"yi-9b": 0.05, "t0:yi-9b": 0.01},
                            **kw)
    srv.run(6)
    t = srv.tenants[0]
    assert t.tid == "t0:yi-9b"
    assert t.qos_target == 0.01, \
        "tenant-specific pattern must beat the arch-wide one"

    srv2 = MultiTenantServer([], tenants=[TenantSpec("yi-9b", n_inferences=4)],
                             qos_targets={"yi-9b": 0.05}, **kw)
    srv2.run(6)
    assert srv2.tenants[0].qos_target == 0.05
