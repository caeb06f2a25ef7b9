"""A whole run at a CPU test size, the look for a chip skipped: sound,
it comes out correct; with the timed path broken underneath in each way
a one-chip serving cell can be broken, it comes out not correct.  (The
exchange between chips does not exist in a one-chip cell.)"""
import json
import os

import jax.numpy as jnp
import pytest

import run

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
CELL = {"name": "tiny", "chips": 1}
SEED = 2 ** 31 + 77


def tiny():
    with open(os.path.join(DATA, "tiny-colo.json")) as f:
        config = json.load(f)
    with open(os.path.join(DATA, "tiny-traffic.json")) as f:
        traffic = json.load(f)
    return config, traffic


def run_tiny():
    config, traffic = tiny()
    return run.run_cell(CELL, config, traffic, [], SEED, 1.0, False,
                        require_chip=False)["line"]


def state_unchanged(monkeypatch):
    from repro.models import transformer as T
    step = T.decode_step

    def frozen(params, token, caches, *a, **kw):
        logits, _ = step(params, token, caches, *a, **kw)
        return logits, caches
    monkeypatch.setattr(T, "decode_step", frozen)


def half_batch(monkeypatch):
    from repro.models import transformer as T
    step = T.decode_step

    def half(params, token, caches, *a, **kw):
        logits, new = step(params, token, caches, *a, **kw)
        h = logits.shape[0] // 2
        return jnp.concatenate([logits[:h]] * 2, axis=0), new
    monkeypatch.setattr(T, "decode_step", half)


def token_altered(monkeypatch):
    from repro.models import model as M
    greedy = M._greedy_next_token

    def shifted(cfg):
        nxt = greedy(cfg)
        return lambda logits: (nxt(logits) + 1) % cfg.vocab_size
    monkeypatch.setattr(M, "_greedy_next_token", shifted)


def test_sound_run_is_correct():
    line = run_tiny()
    assert line["correct"], line["checks"]
    assert line["failed"] == 0 and line["attempted"] > 0


@pytest.mark.parametrize("fault", [state_unchanged, half_batch,
                                   token_altered])
def test_broken_path_is_not_correct(monkeypatch, fault):
    fault(monkeypatch)
    line = run_tiny()
    assert not line["correct"], line["checks"]
    assert list(line)[-1] == "checks"
