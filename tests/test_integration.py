"""End-to-end integration: real train loop with resume, and a
subprocess multi-device dry-run (the 512-device flag must be set before
jax initializes, hence the subprocess)."""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SRC = os.path.join(ROOT, "src")


@pytest.mark.slow
def test_train_loop_improves_and_resumes(tmp_path):
    """launch/train.py path: loss descends; killing and resuming from the
    checkpoint continues from the same step with identical data."""
    from repro.launch.train import build
    from repro.distributed.fault_tolerance import (SupervisorConfig,
                                                   TrainSupervisor)
    from repro.optim import adamw

    opt_cfg = adamw.AdamWConfig(lr=1e-3, warmup_steps=5, total_steps=40)
    cfg, params, opt_state, step_fn, batch_at = build(
        "olmoe-1b-7b", smoke=True, seq_len=32, global_batch=4,
        opt_cfg=opt_cfg)
    sup = TrainSupervisor(SupervisorConfig(ckpt_dir=str(tmp_path),
                                           ckpt_every=10, async_save=False))
    losses = []
    params, opt_state, step = sup.run(
        step_fn, (params, opt_state), batch_at, num_steps=20,
        on_metrics=lambda s, m: losses.append(float(m["loss"])))
    assert step == 20
    # resume from checkpoint, continue to 40
    p2, o2, resumed = sup.restore((params, opt_state))
    assert resumed == 20
    p2, o2, step = sup.run(step_fn, (p2, o2), batch_at, num_steps=40,
                           start_step=resumed,
                           on_metrics=lambda s, m: losses.append(float(m["loss"])))
    assert step == 40
    assert losses[-1] < losses[0]


@pytest.mark.slow
def test_dryrun_subprocess_multidevice():
    """The real multi-pod dry-run entry point compiles a small cell on
    512 virtual devices in a fresh process."""
    env = dict(os.environ, PYTHONPATH=SRC, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun",
         "--arch", "whisper-tiny", "--shape", "decode_32k", "--multi-pod"],
        env=env, cwd=ROOT, capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "0 failures" in out.stdout


def test_serve_example_script():
    """examples/multi_tenant_serve.py runs as a script."""
    env = dict(os.environ, PYTHONPATH=SRC, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, "examples/quickstart.py"],
        env=env, cwd=ROOT, capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "Saved" in out.stdout
