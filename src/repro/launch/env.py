"""Launcher hygiene: process-environment knobs that must be set before
the first JAX backend initialization, in one place.

Measured tokens/s should reflect device work, not launcher accidents, so
every entry point that benchmarks or serves (``benchmarks/run.py``,
``examples/multi_tenant_serve.py``, ``repro.launch.serve --devices``)
routes through these helpers instead of hand-rolling ``os.environ``
writes:

* **Host device count** — ``--xla_force_host_platform_device_count=N``
  splits the host CPU into N XLA devices, which is what makes fleet
  meshes (launch/mesh.py) fully testable on CPU CI.  JAX locks the
  device count at first backend init, so the flag is only effective
  before any ``jax.devices()`` / first op; :func:`set_host_device_count`
  merges it into ``XLA_FLAGS`` (preserving unrelated flags) and fails
  loudly if the backend already initialized with a different count.
* **Compilation cache** — :func:`enable_compile_cache` persists XLA
  executables across processes, so a second run skips recompiles of
  the (stable) fused epoch programs.  ``JAX_COMPILATION_CACHE_DIR``
  places it from outside; otherwise it lives in one fixed directory of
  the checkout (:data:`COMPILE_CACHE_DIR`, git-ignored).
* **tcmalloc** — glibc malloc serializes the multi-threaded XLA:CPU
  runtime under the allocation churn of many small per-tenant buffers.
  ``LD_PRELOAD`` cannot be set from inside the process (the loader has
  already run), so launchers that care should prefix:

      LD_PRELOAD=/usr/lib/x86_64-linux-gnu/libtcmalloc.so.4 \\
          python benchmarks/run.py ...

  :func:`describe` reports whether it is active.
"""
from __future__ import annotations

import os
import re
from pathlib import Path

_COUNT_FLAG = "--xla_force_host_platform_device_count"
TCMALLOC_PATH = "/usr/lib/x86_64-linux-gnu/libtcmalloc.so.4"
# <checkout>/src/repro/launch/env.py -> <checkout>/.jax_compile_cache
COMPILE_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_compile_cache"


def merge_xla_flag(flags: str, flag: str, value) -> str:
    """Set ``flag=value`` inside an XLA_FLAGS string, replacing an
    existing assignment of the same flag and preserving everything
    else."""
    new = f"{flag}={value}"
    pat = re.compile(rf"{re.escape(flag)}=\S+")
    if pat.search(flags):
        return pat.sub(new, flags)
    return f"{flags} {new}".strip()


def set_host_device_count(n: int) -> int:
    """Make ``n`` devices available to a fleet of ``n`` one-chip
    replicas.  Must run before the first backend initialization.

    The forced host device count is read by the CPU platform only, so
    on the CPU backend the host splits into ``n`` XLA devices (what
    makes fleet meshes testable without accelerators); on an
    accelerator backend the fleet runs on ``n`` real chips and the flag
    changes nothing.  Raises if the CPU backend came up with a different
    count (a too-early jax call pinned it) or the accelerator has fewer
    than ``n`` chips — silently serving a "fleet" on one device is the
    failure mode this guards."""
    n = int(n)
    assert n >= 1, n
    os.environ["XLA_FLAGS"] = merge_xla_flag(
        os.environ.get("XLA_FLAGS", ""), _COUNT_FLAG, n)
    import jax
    got = jax.device_count()
    if jax.default_backend() == "cpu" and got != n:
        raise RuntimeError(
            f"host platform initialized with {got} devices, wanted {n}: "
            f"set_host_device_count must run before the first jax device "
            f"use (or set XLA_FLAGS='{_COUNT_FLAG}={n}' in the launcher "
            f"environment)")
    if got < n:
        raise RuntimeError(f"{jax.default_backend()} backend has {got} "
                           f"devices, a fleet of {n} replicas needs {n}")
    return got


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its
    directory.  ``JAX_COMPILATION_CACHE_DIR``, when set, already placed
    it and is left alone; otherwise the cache goes to
    :data:`COMPILE_CACHE_DIR`, a fixed path (the path is part of the
    cache key, so a directory that moved would never hit).  Call before
    the first compile; importing this module enables nothing."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        import jax
        path = str(COMPILE_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path


def tcmalloc_active() -> bool:
    return "tcmalloc" in os.environ.get("LD_PRELOAD", "")


def describe() -> str:
    """One-line launcher-environment summary for bench/serve logs."""
    flags = os.environ.get("XLA_FLAGS", "")
    m = re.search(rf"{_COUNT_FLAG}=(\d+)", flags)
    return (f"host_devices={m.group(1) if m else 'default'} "
            f"tcmalloc={'on' if tcmalloc_active() else 'off'} "
            f"compile_cache={_compile_cache_dir() or 'off'}")


def _compile_cache_dir():
    import jax
    return jax.config.jax_compilation_cache_dir


def describe_dict() -> dict:
    """Structured launcher-environment record, embedded into every BENCH
    json entry so a number can always be traced back to the environment
    that produced it.  Pure reads — never initializes the jax backend."""
    flags = os.environ.get("XLA_FLAGS", "")
    m = re.search(rf"{_COUNT_FLAG}=(\d+)", flags)
    return {
        "host_devices": int(m.group(1)) if m else None,
        "tcmalloc": tcmalloc_active(),
        "compile_cache": _compile_cache_dir(),
        "xla_flags": flags or None,
        "summary": describe(),
    }
