"""Seed sharding API under forced multi-device host meshes.

The distributed/sharding.py rules were written for TPU pods but have to
lower identically on a forced-CPU mesh (that is what the fleet serving
path and CI's mesh-smoke job run on).  Everything here needs >= 4 host
devices: under ``XLA_FLAGS=--xla_force_host_platform_device_count=4``
(the mesh-smoke job) the tests run in-process; on a stock single-device
host :func:`test_relaunch_with_forced_devices` re-runs this file in a
subprocess with the flag set, so `pytest -x -q` covers it everywhere.

Covers:
  * make_host_mesh sizes from jax.device_count() (the seed version was
    hardwired to (1, 1)),
  * make_serving_mesh / replica_submeshes / replica_devices geometry,
  * param_specs rules on a (2, 2) serving mesh — head sharding,
    indivisible-dim fallback, 'pod' filtering on a 3-axis mesh,
  * zero_specs extending the model dim over ('model', 'data'),
  * cache_specs locating the batch axis in both decode-cache layouts
    (tuple-of-buffers [B, ...] and stacked [G, B, ...]) for minor and
    seq modes,
  * batch_spec / shard_hint / use_mesh activation semantics.
"""
import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.distributed import sharding as shard
from repro.launch import env
from repro.launch.mesh import (make_host_mesh, make_serving_mesh,
                               replica_devices, replica_submeshes)

needs4 = pytest.mark.skipif(jax.device_count() < 4,
                            reason="needs 4 forced host devices "
                                   "(run via the relaunch test or "
                                   "XLA_FLAGS=--xla_force_host_platform"
                                   "_device_count=4)")


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
# mesh constructors
# ---------------------------------------------------------------------------
@needs4
def test_host_mesh_sizes_from_device_count():
    mesh = make_host_mesh()
    assert mesh.axis_names == ("data", "model")
    assert mesh.devices.shape == (jax.device_count(), 1)


@needs4
def test_serving_mesh_geometry():
    mesh = make_serving_mesh()  # defaults: every device, tp=1
    assert mesh.devices.shape == (jax.device_count(), 1)
    mesh22 = make_serving_mesh(2, tp=2)
    assert mesh22.devices.shape == (2, 2)
    subs = replica_submeshes(mesh22)
    assert [m.devices.shape for m in subs] == [(1, 2), (1, 2)]
    assert all(m.axis_names == ("data", "model") for m in subs)
    devs = replica_devices(mesh22)
    assert len(devs) == 2 and devs[0] != devs[1]
    # submesh rows are disjoint device sets covering the serving mesh
    flat = [d for m in subs for d in m.devices.flat]
    assert len(set(flat)) == 4
    with pytest.raises(AssertionError):
        make_serving_mesh(8, tp=2)  # 16 devices needed, have 4


# ---------------------------------------------------------------------------
# parameter specs
# ---------------------------------------------------------------------------
def _leaf(*shape):
    return jax.ShapeDtypeStruct(shape, jnp.float32)


_PARAMS = {
    "embed": {"table": _leaf(512, 64)},
    "layers": {
        "attn": {"wq": {"w": _leaf(64, 64)},
                 "wo": {"w": _leaf(64, 64)}},
        "mlp": {"down": {"w": _leaf(256, 64)}},
        "ln1": {"scale": _leaf(64)},
    },
}


@needs4
def test_param_specs_serving_mesh():
    mesh = make_serving_mesh(2, tp=2)
    specs = shard.param_specs(_PARAMS, mesh)
    assert specs["embed"]["table"] == P("model", None)
    assert specs["layers"]["attn"]["wq"]["w"] == P(None, "model")
    assert specs["layers"]["attn"]["wo"]["w"] == P("model", None)
    assert specs["layers"]["mlp"]["down"]["w"] == P("model", None)
    assert specs["layers"]["ln1"]["scale"] == P(None)
    shardings = shard.param_shardings(_PARAMS, mesh)
    s = shardings["layers"]["attn"]["wq"]["w"]
    assert isinstance(s, NamedSharding) and s.mesh.shape["model"] == 2


@needs4
def test_param_specs_drop_indivisible_dims():
    mesh = make_serving_mesh(2, tp=2)
    odd = {"attn": {"wq": {"w": _leaf(64, 63)}}}  # 63 % tp != 0
    specs = shard.param_specs(odd, mesh)
    assert specs["attn"]["wq"]["w"] == P(None, None)


@needs4
def test_param_specs_filter_pod_axis():
    """Specs written for the 3-axis pod mesh auto-filter on 2-D meshes,
    and a 3-axis mesh keeps them verbatim."""
    grid = np.array(jax.devices()[:4]).reshape(1, 2, 2)
    mesh3 = Mesh(grid, ("pod", "data", "model"))
    specs3 = shard.param_specs(_PARAMS, mesh3)
    assert specs3["layers"]["attn"]["wq"]["w"] == P(None, "model")
    assert shard.batch_spec(mesh3) == P(("pod", "data"))
    assert shard.batch_spec(make_host_mesh()) == P("data")


@needs4
def test_zero_specs_extend_model_dim():
    mesh = make_serving_mesh(2, tp=2)
    params = {"mlp": {"down": {"w": _leaf(256, 64)}}}
    st = shard.zero_specs(None, params, mesh)
    # 256 % (model * data) == 0 -> m/v shard the param's model dim over
    # both axes; the step counter stays replicated
    assert st.m["mlp"]["down"]["w"] == P(("model", "data"), None)
    assert st.step == P()


# ---------------------------------------------------------------------------
# decode-cache specs (both cache layouts)
# ---------------------------------------------------------------------------
@needs4
def test_cache_specs_tuple_layout():
    mesh = make_serving_mesh(2, tp=2)
    caches = ({"k": _leaf(2, 64, 4, 16), "v": _leaf(2, 64, 4, 16)},)
    specs = shard.cache_specs(caches, mesh, batch=2)
    # batch axis 0 over 'data'; minor mode shards head_dim over 'model'
    assert specs[0]["k"] == P("data", None, None, "model")
    seq = shard.cache_specs(caches, mesh, batch=2, mode="seq")
    # seq mode shards the longest (KV sequence) dim instead
    assert seq[0]["k"] == P("data", "model", None, None)


@needs4
def test_cache_specs_stacked_layout():
    mesh = make_serving_mesh(2, tp=2)
    stacked = {"k": _leaf(3, 2, 64, 4, 16)}  # [G, B, S, H, hd]
    specs = shard.cache_specs(stacked, mesh, batch=2)
    assert specs["k"] == P(None, "data", None, None, "model")
    shardings = shard.cache_shardings(stacked, mesh, batch=2)
    assert isinstance(shardings["k"], NamedSharding)


@needs4
def test_cache_specs_indivisible_batch_falls_back():
    mesh = make_serving_mesh(4, tp=1)
    caches = {"k": _leaf(3, 64, 4, 16)}  # batch 3 % data 4 != 0
    specs = shard.cache_specs(caches, mesh, batch=3)
    # batch stays unsharded; the largest divisible dim takes 'data'
    assert tuple(specs["k"])[0] is None
    assert "data" in tuple(specs["k"])


# ---------------------------------------------------------------------------
# shard_hint / use_mesh
# ---------------------------------------------------------------------------
def test_shard_hint_identity_without_mesh():
    x = jnp.ones((4, 8))
    assert shard.shard_hint(x, ("data", "model")) is x


@needs4
def test_shard_hint_constrains_under_mesh():
    mesh = make_host_mesh()
    x = jnp.ones((jax.device_count(), 8))
    with shard.use_mesh(mesh):
        y = jax.jit(lambda a: shard.shard_hint(a, ("data", None)))(x)
    assert y.sharding.is_equivalent_to(
        NamedSharding(mesh, P("data", None)), ndim=2)
    # mesh deactivates on exit
    assert shard.shard_hint(x, ("data", None)) is x
