"""Launch-layer tests: HLO collective parsing, roofline math, serve
driver integration, mesh helpers."""
import math

import jax
import pytest

from repro.launch import hlo_analysis as H
from repro.launch.mesh import make_host_mesh


# -------------------------------------------------- collective parsing --
HLO_SNIPPET = """
ENTRY %main {
  %p0 = bf16[16,512]{1,0} parameter(0)
  %ag = bf16[256,512]{1,0} all-gather(%p0), replica_groups={{0,1}}, dimensions={0}
  %ar = f32[128]{0} all-reduce(%x), to_apply=%add
  %rs = f32[8,64]{1,0} reduce-scatter(%y), dimensions={0}
  %a2a = bf16[4,32,8]{2,1,0} all-to-all(%z), dimensions={1}
  %cp = u32[10]{0} collective-permute(%w), source_target_pairs={{0,1}}
  %agstart = (bf16[2,2]{1,0}) all-gather-start(%q), dimensions={0}
  %agdone = bf16[2,2]{1,0} all-gather-done(%agstart)
}
"""


def test_collective_bytes_parsing():
    got = H.collective_bytes(HLO_SNIPPET)
    assert got["all-gather"] == 256 * 512 * 2 + 2 * 2 * 2  # incl. -start
    assert got["all-reduce"] == 128 * 4
    assert got["reduce-scatter"] == 8 * 64 * 4
    assert got["all-to-all"] == 4 * 32 * 8 * 2
    assert got["collective-permute"] == 10 * 4


def test_collective_done_not_double_counted():
    got = H.collective_bytes(HLO_SNIPPET)
    # -done carries the same shape as -start; must be counted once
    assert got["all-gather"] < 256 * 512 * 2 + 2 * (2 * 2 * 2)


# ----------------------------------------------------- roofline math --
def _rf(f, b, c):
    return H.Roofline(flops=f, hbm_bytes=b, coll_bytes=c,
                      coll_breakdown={"all-reduce": int(c)})


def test_roofline_terms_and_dominant():
    r = H.Roofline(flops=197e12, hbm_bytes=819e9, coll_bytes=0.0,
                   coll_breakdown={})
    assert r.compute_s == pytest.approx(1.0)
    assert r.memory_s == pytest.approx(1.0)
    assert r.dominant in ("compute", "memory")
    r2 = H.Roofline(flops=1.0, hbm_bytes=1.0, coll_bytes=200e9,
                    coll_breakdown={})
    assert r2.dominant == "collective"


def test_extrapolate_unroll_delta():
    c1 = _rf(10.0, 100.0, 4.0)      # outside + 1 layer
    c2 = _rf(13.0, 130.0, 5.0)      # outside + 2 layers
    out = H.extrapolate(c1, c2, groups=48)
    # layer = 3/30/1 -> total = outside(7/70/3) + 48*layer
    assert out.flops == pytest.approx(7 + 48 * 3)
    assert out.hbm_bytes == pytest.approx(70 + 48 * 30)
    assert out.coll_bytes == pytest.approx(3 + 48 * 1)


def test_extrapolate_clamps_negative_delta():
    out = H.extrapolate(_rf(10, 10, 10), _rf(9, 9, 9), groups=10)
    assert out.flops >= 0 and out.hbm_bytes >= 0


# ------------------------------------------------------- serve driver --
def test_multi_tenant_server_runs_and_arbitrates():
    from repro.launch.serve import MultiTenantServer
    srv = MultiTenantServer(["olmoe-1b-7b", "mamba2-370m"], batch=1,
                            max_len=16, total_pages=24)
    out = srv.run(steps=3)
    assert out["tokens_per_s"] > 0
    for tid, info in out["tenants"].items():
        assert info["tokens"] == 3
        assert info["choices"], "allocator made no decisions"
    # pool fully released after run
    assert srv.cache.free_pages == srv.cache.config.num_pages


def test_server_downgrades_under_pressure():
    from repro.launch.serve import MultiTenantServer
    tight = MultiTenantServer(["yi-9b", "granite-3-8b"], batch=1,
                              max_len=16, total_pages=4)
    out = tight.run(steps=3)
    kinds = [c for t in out["tenants"].values() for c in t["choices"]]
    # with 4 pages the big LBM candidates cannot all be granted
    assert any(not k.startswith("LBM") or k.endswith(":0p") or
               int(k.split(":")[1][:-1]) <= 4 for k in kinds)


def test_archconfig_spec_is_served_as_given():
    """An arch-id string resolves to the reduced CPU variant; an
    ArchConfig (here a depth cut) is served exactly as given, under its
    own name; two different configs may not share one name."""
    import dataclasses
    from repro.launch.serve import MultiTenantServer
    from repro.models.base import get_arch
    from repro.sim.driver import TenantSpec
    cut = get_arch("yi-9b").reduced().depth_cut(1)
    assert cut.name == "yi-9b-smoke@1L" and cut.num_layers == 1
    srv = MultiTenantServer(["yi-9b"], batch=1, max_len=16, total_pages=24,
                            tenants=[TenantSpec(cut)], epoch_len=2)
    out = srv.run(steps=2)
    by_tid = {t.tid: t for t in srv.tenants}
    assert by_tid["t0:yi-9b"].cfg == get_arch("yi-9b").reduced()
    assert by_tid["t1:yi-9b-smoke@1L"].cfg is cut
    assert all(info["tokens"] == 2 for info in out["tenants"].values())
    clash = dataclasses.replace(cut, d_ff=384)
    with pytest.raises(ValueError, match="two configs"):
        srv.admit_routed(TenantSpec(clash))


def test_arch_without_dense_ffn_plans_and_serves():
    """Published mamba2 has d_ff 0: its FFN-block schedule and plans use
    a d_model-wide block instead of dividing by a zero d_ff."""
    import dataclasses
    from repro.launch.serve import MultiTenantServer
    from repro.models.base import get_arch
    from repro.sim.driver import TenantSpec
    cfg = dataclasses.replace(get_arch("mamba2-370m").reduced(), d_ff=0,
                              name="mamba2-no-ffn")
    srv = MultiTenantServer([], batch=1, max_len=256, total_pages=64,
                            tenants=[TenantSpec(cfg, prompt_len=128,
                                                n_inferences=4)])
    out = srv.run(steps=4)
    info = out["tenants"]["t0:mamba2-no-ffn"]
    assert info["tokens"] == 1 + 4 and info["plans"]


def test_peak_rates_keyed_by_device_kind():
    from repro.launch.mesh import peaks
    v5e = peaks("TPU v5 lite")
    assert v5e["bf16_flops"] == 197e12 and v5e["hbm_bw"] == 819e9
    with pytest.raises(KeyError, match="no peak rates"):
        peaks("cpu")


# ---------------------------------------------------------- mesh ------
def test_host_mesh_axes():
    m = make_host_mesh()
    assert set(m.axis_names) == {"data", "model"}
    assert m.devices.size == 1


def test_qos_target_most_specific_match_wins():
    """Regression: _slack used to keep the LAST matching qos_targets key;
    a generic suffix listed after an exact tenant key silently overrode
    it.  The most-specific (longest) matching key must win regardless of
    dict order."""
    from repro.launch.serve import MultiTenantServer
    srv = MultiTenantServer(["olmoe-1b-7b"], batch=1, max_len=8,
                            total_pages=16,
                            qos_targets={"olmoe-1b-7b": 1e-6,  # impossible
                                         "1b-7b": 100.0})      # trivial
    t = srv.tenants[0]
    t.tokens_served = 100
    # under the exact key (1e-6 s/token) the tenant is hopelessly late;
    # the generic "1b-7b" target would report positive slack instead
    assert srv._slack(t, now=1.0) < 0


def test_qos_priority_scheduling():
    """Deadline-aware serving: the tightest-QoS tenant is ordered first."""
    from repro.launch.serve import MultiTenantServer
    srv = MultiTenantServer(["olmoe-1b-7b", "mamba2-370m"], batch=1,
                            max_len=16, total_pages=24,
                            qos_targets={"olmoe-1b-7b": 1e-6})  # impossible
    out = srv.run(steps=3)
    assert out["tenants"]["t0:olmoe-1b-7b"]["tokens"] == 3
    assert out["tenants"]["t1:mamba2-370m"]["tokens"] == 3
