"""Fleet serving over a device mesh: routing, placement, bit-identity.

The FleetServer contract (launch/serve.py): N replica chips, each
running its own epoch pipeline against its own per-chip CaMDN control
stack, behind a least-loaded admission router — and each replica's
decode token streams bit-identical to replaying its routed scenario on
a fresh single-device server.

Launcher-hygiene units (launch/env.py) run on any host; the fleet tests
need >= 4 forced host devices and use the same relaunch pattern as
tests/test_sharding.py: in-process under CI's mesh-smoke job
(``XLA_FLAGS=--xla_force_host_platform_device_count=4``), via a
subprocess rerun otherwise.
"""
import dataclasses
import os
import subprocess
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.launch import env
from repro.launch.mesh import replica_devices
from repro.launch.serve import FleetServer, MultiTenantServer
from repro.sim.driver import FleetScenario, TenantSpec

needs4 = pytest.mark.skipif(jax.device_count() < 4,
                            reason="needs 4 forced host devices "
                                   "(run via the relaunch test or "
                                   "XLA_FLAGS=--xla_force_host_platform"
                                   "_device_count=4)")

ARCH = "mamba2-370m"   # smallest registered arch: cheapest fleet compile


# ---------------------------------------------------------------------------
# launcher hygiene (no devices needed)
# ---------------------------------------------------------------------------
def test_merge_xla_flag():
    f = env.merge_xla_flag("", "--xla_force_host_platform_device_count", 4)
    assert f == "--xla_force_host_platform_device_count=4"
    # replaces an existing assignment, preserves unrelated flags
    f = env.merge_xla_flag(
        "--xla_cpu_enable_fast_math=true "
        "--xla_force_host_platform_device_count=2",
        "--xla_force_host_platform_device_count", 8)
    assert "--xla_force_host_platform_device_count=8" in f
    assert "count=2" not in f
    assert "--xla_cpu_enable_fast_math=true" in f


def test_env_describe_reports_count():
    d = env.describe()
    assert d.startswith("host_devices=") and "tcmalloc=" in d


def test_compile_cache_placed_from_environment(monkeypatch, tmp_path):
    """JAX_COMPILATION_CACHE_DIR, when set, is the cache: the call sets
    no directory of its own."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert env.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_defaults_to_fixed_checkout_dir(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        path = env.enable_compile_cache()
        assert path == str(Path(__file__).resolve().parents[1]
                           / ".jax_compile_cache")
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_fleet_scenario_shape():
    sc = FleetScenario(2, [[], []])
    assert sc.routes == [] and len(sc.per_replica) == 2


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


# ---------------------------------------------------------------------------
# fleet behaviour (forced 4-device host)
# ---------------------------------------------------------------------------
def _fleet(n, specs, **kw):
    kw.setdefault("batch", 1)
    kw.setdefault("max_len", 256)
    kw.setdefault("epoch_len", 4)
    return FleetServer(n_replicas=n, tenants=specs, **kw)


@needs4
def test_routing_round_robins_identical_specs():
    """Identical arrivals see identical loads -> the (load, active, idx)
    tiebreak round-robins them, one per replica then wrapping."""
    specs = [TenantSpec(ARCH, n_inferences=4) for _ in range(8)]
    fleet = _fleet(4, specs)
    counts = [len(s) for s in fleet.replica_scenarios()]
    assert sorted(counts) == [2, 2, 2, 2], counts
    assert len(fleet.scenario.routes) == 8
    tids = [tid for tid, _ in fleet.scenario.routes]
    assert len(set(tids)) == 8   # global admission index -> unique ids
    out = fleet.run(4)
    assert out["mode"] == "fleet" and out["n_replicas"] == 4
    assert all(rep["tokens_served"] > 0 for rep in out["replicas"])


@needs4
def test_tenants_pinned_to_replica_devices():
    """Data sharding by placement: every tenant's token/params/caches
    are committed to its replica's chip."""
    fleet = _fleet(4, [TenantSpec(ARCH, n_inferences=2) for _ in range(4)])
    devs = replica_devices(fleet.mesh)
    for r, srv in enumerate(fleet.replicas):
        assert len(srv.tenants) == 1
        for t in srv.tenants:
            assert t.token.devices() == {devs[r]}
            leaf = jax.tree_util.tree_leaves(t.params)[0]
            assert leaf.devices() == {devs[r]}
            cleaf = jax.tree_util.tree_leaves(t.caches)[0]
            assert cleaf.devices() == {devs[r]}


@needs4
def test_per_replica_streams_bit_identical_to_single_device():
    """The PR acceptance contract: replaying replica r's routed scenario
    (pinned seeds) on a fresh single-device server reproduces its decode
    streams bit-for-bit — grants, clocks, and co-tenants on OTHER
    replicas never leak into decode content."""
    specs = [TenantSpec(ARCH, n_inferences=6,
                        prompt_len=64 if i % 2 else 0)
             for i in range(4)]
    fleet = _fleet(2, specs, pages_per_replica=64)
    out = fleet.run(6)
    scen = fleet.replica_scenarios()
    assert sum(len(s) for s in scen) == 4
    for r, routed in enumerate(scen):
        solo = MultiTenantServer([], batch=1, max_len=256, epoch_len=4,
                                 total_pages=64, tenants=routed)
        ref = solo.run(6)
        for tid, info in ref["tenants"].items():
            assert tid in out["tenants"], (r, tid)
            assert out["tenants"][tid]["replica"] == f"r{r}"
            assert np.array_equal(out["tenants"][tid]["output"],
                                  info["output"]), \
                f"replica r{r} diverged from single-device for {tid}"


@needs4
def test_per_chip_allocators_are_independent():
    """No page pool or NEC ledger is shared between chips: draining one
    replica's pool leaves the others' free counts untouched."""
    fleet = _fleet(4, [TenantSpec(ARCH, n_inferences=2)],
                   pages_per_replica=32)
    frees = [srv.cache.free_pages for srv in fleet.replicas]
    loaded = [r for r, srv in enumerate(fleet.replicas) if srv.tenants]
    assert len(loaded) == 1
    # the loaded replica reports load; the idle ones report zero
    assert fleet.replicas[loaded[0]].load() >= 0
    assert all(fleet.replicas[r].load() == 0
               for r in range(4) if r != loaded[0])
    assert all(f == 32 for r, f in enumerate(frees) if r != loaded[0])


@needs4
def test_tensor_parallel_replica_group_smoke():
    """tp=2: two replicas of two chips each; params land sharded over the
    replica group and the fleet still serves tokens."""
    fleet = FleetServer(n_replicas=2, tp=2, batch=1, max_len=256,
                        epoch_len=4,
                        tenants=[TenantSpec("yi-9b", n_inferences=2),
                                 TenantSpec("yi-9b", n_inferences=2)])
    assert fleet.mesh.devices.shape == (2, 2)
    t = fleet.replicas[0].tenants[0]
    leaves = jax.tree_util.tree_leaves(t.params)
    group = set(fleet.mesh.devices[0].flat)
    assert any(len(leaf.devices()) == 2 for leaf in leaves)
    assert all(leaf.devices() <= group for leaf in leaves)
    out = fleet.run(4)
    assert out["tp"] == 2 and out["tokens_served"] > 0


@needs4
def test_queued_arrival_routes_to_least_loaded():
    """A mid-run arrival lands on the emptier replica: seed two tenants
    onto r0 (routing ties broken by index when loads match) and one on
    r1, then a fourth arriving later must route to r1."""
    specs = [TenantSpec(ARCH, n_inferences=16, prompt_len=128),
             TenantSpec(ARCH, n_inferences=16, prompt_len=128),
             TenantSpec(ARCH, n_inferences=2),
             TenantSpec(ARCH, arrive_at=2.0, n_inferences=2)]
    fleet = _fleet(2, specs, pages_per_replica=64)
    fleet.run(6)
    routes = dict(fleet.scenario.routes)
    # 3 immediate arrivals round-robin r0, r1, r0; the late one must see
    # r0 still busier (two prompted tenants) and pick r1
    late_tid = fleet.scenario.routes[-1][0]
    assert routes[late_tid] == 1, fleet.scenario.routes
