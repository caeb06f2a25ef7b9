"""Production mesh definitions (TPU v5e pods) and serving-fleet meshes.

Functions, not module-level constants: importing this module never
touches jax device state (so smoke tests see 1 CPU device).

Serving axis roles (the fleet in launch/serve.py):
  data  — replica axis: each index along 'data' is one serving replica
          (one chip, or one tensor-parallel group of chips) running its
          own epoch pipeline with its own per-chip CaMDN allocator.
  model — tensor parallelism inside a replica group (heads / ffn inner
          via distributed.sharding.param_specs + shard_hint).

On CPU, ``XLA_FLAGS=--xla_force_host_platform_device_count=N``
(:mod:`repro.launch.env`) splits the host into N devices, so fleet
topologies are testable without accelerators.
"""
from __future__ import annotations

from typing import Dict, List, Optional

import jax
from jax.sharding import AxisType, Mesh

# Per-chip peaks (roofline terms, launch/hlo_analysis.py), keyed by
# ``jax.Device.device_kind``.  Source: Google Cloud documentation,
# "TPU v5e": 197 TFLOP/s bf16, 16 GB HBM at 819 GB/s, 1,600 Gbit/s of
# inter-chip interconnect (4 links of 50 GB/s).
PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bw": 819e9,
                    "hbm_bytes": 16e9, "ici_link_bw": 50e9},
}
CHIPS_PER_POD = 256


def peaks(device_kind: str) -> Dict[str, float]:
    """The peak table row for one device kind; an unknown kind is an
    error, never a silent v5e default."""
    if device_kind not in PEAKS:
        raise KeyError(f"no peak rates for device kind {device_kind!r}; "
                       f"have {sorted(PEAKS)}")
    return PEAKS[device_kind]


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes)


def make_host_mesh():
    """Host-device mesh for CPU smoke runs (same axis names as the
    production mesh).  Sized from :func:`jax.device_count` — under
    ``--xla_force_host_platform_device_count=N`` this is a real
    (N, 1) data-parallel mesh; on a stock single-device host it
    degrades to the old (1, 1) fallback.  Its axes are Auto, as a
    ``Mesh`` built directly has them (:func:`make_serving_mesh`):
    ``shard_hint`` constrains through ``with_sharding_constraint``,
    which refuses the Explicit axes ``jax.make_mesh`` defaults to."""
    return jax.make_mesh((jax.device_count(), 1), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)


def make_serving_mesh(n_replicas: Optional[int] = None, tp: int = 1,
                      devices: Optional[List] = None) -> Mesh:
    """Serving-fleet mesh: ``(n_replicas, tp)`` over ``('data',
    'model')``.  Each row along 'data' is one replica — a chip (tp=1)
    or a tensor-parallel group of ``tp`` chips — with its own epoch
    pipeline and CaMDN allocator arbitrating that chip's page budget.
    ``n_replicas`` defaults to every available device at the given
    ``tp``."""
    devices = list(devices if devices is not None else jax.devices())
    assert tp >= 1 and len(devices) >= tp, (tp, len(devices))
    if n_replicas is None:
        n_replicas = len(devices) // tp
    assert n_replicas * tp <= len(devices), \
        f"mesh ({n_replicas}, {tp}) needs {n_replicas * tp} devices, " \
        f"have {len(devices)}"
    import numpy as np
    grid = np.asarray(devices[:n_replicas * tp]).reshape(n_replicas, tp)
    return Mesh(grid, ("data", "model"))


def replica_submeshes(mesh: Mesh) -> List[Mesh]:
    """Per-replica submeshes of a serving mesh: row ``r`` of the 'data'
    axis as a ``(1, tp)`` mesh with the same axis names, so
    ``param_specs``/``shard_hint`` lower tensor-parallel shardings
    *within* the replica group while the replica axis stays outside
    (the fleet data-shards tenants across replicas by placement, not
    SPMD)."""
    n = mesh.devices.shape[0]
    return [Mesh(mesh.devices[r:r + 1], mesh.axis_names) for r in range(n)]


def replica_devices(mesh: Mesh) -> List:
    """The first device of each replica group — where a tp=1 replica
    pins its tenants' params/caches/tokens."""
    return [mesh.devices[r].flat[0] for r in range(mesh.devices.shape[0])]


def chips(mesh) -> int:
    return mesh.devices.size
