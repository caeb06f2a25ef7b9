"""The control at a CPU test size: the reference computed in fp8 (one
step below the bfloat16 the configuration states) put in the program's
place fails the limits that sound runs pass, on every seed tried."""
import pytest

import check
import run
import test_faults as tf


@pytest.mark.parametrize("seed", [3, 2 ** 31 + 5, 4_000_000_001])
def test_control_fails_where_program_passes(seed):
    config, traffic = tf.tiny()
    c = run.Cell(tf.CELL, config, traffic, seed, require_chip=False)
    try:
        w = c.window(seed, 1.0, False)
    finally:
        c.close()
    picked = check.sample(w.tasks, seed)
    ok, _ = check.judge(config["limits"], check.gap_numbers(
        w.models, w.tasks, w.records, picked), [])
    bad, checks = check.judge(config["limits"], check.gap_numbers(
        w.models, w.tasks, w.records, picked, low=True), [])
    assert ok and not bad, checks
