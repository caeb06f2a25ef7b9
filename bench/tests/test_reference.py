"""The float32 references against the program's own forward, at a tiny
size on the CPU, with float32 weights: same seed, same equations."""
import importlib
import pkgutil

import jax
import numpy as np
import pytest

import families

# every family with a module in bench/families/
FAMILIES = sorted(m.name for m in pkgutil.iter_modules(families.__path__))


def tiny(family):
    return families.of({"family": family}).TINY


def reference(family):
    return importlib.import_module(f"reference.{family}")


def program_logits(family, cfg, seed, tokens):
    from adapter import arch_config
    from repro.models import model as M
    from repro.models.transformer import lm_forward
    arch = arch_config({"family": family, "program_name": f"tiny-{family}",
                        "config": cfg})
    params = M.init_params(arch, jax.random.PRNGKey(seed))
    with jax.default_matmul_precision("highest"):
        logits, _ = lm_forward(params, tokens, arch)
    return np.asarray(logits[..., :cfg["vocab_size"]], np.float32)


@pytest.mark.parametrize("family", FAMILIES)
def test_reference_matches_program(family):
    cfg = tiny(family)
    ref = reference(family)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 96), 0,
                                cfg["vocab_size"])
    want = program_logits(family, cfg, 1234, tokens)
    got = np.asarray(ref.logits(cfg, 1234, tokens, start=0))
    err = np.abs(got - want).max() / np.abs(want).max()
    assert err < 1e-4, err


@pytest.mark.parametrize("family", FAMILIES)
def test_fp8_control_departs(family):
    """The control's logits lie measurably off the float32 ones."""
    cfg = tiny(family)
    ref = reference(family)
    tokens = jax.random.randint(jax.random.PRNGKey(2), (2, 64), 0,
                                cfg["vocab_size"])
    hi = np.asarray(ref.logits(cfg, 99, tokens, start=32))
    lo = np.asarray(ref.logits(cfg, 99, tokens, start=32, low=True))
    assert np.abs(lo - hi).max() > 1e-3 * np.abs(hi).max()
